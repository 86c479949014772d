"""Phase spans on the profiler's clock (DESIGN §13): a serving or training
step stepped under ``jax.profiler`` leaves one span per phase, nested in
the step's span and in the order the step runs them, with the counts the
phase knows carried as the event's stats."""

import glob

import jax
import pytest

from repro.configs import PeftConfig, TrainConfig, get_config, reduced
from repro.data.loader import peek_batch
from repro.models import get_model
from repro.obs import spans
from repro.peft import get_peft
from repro.serve import ServeEngine
from repro.train.trainer import Trainer

SERVE_PHASES = (spans.SERVE_EXPIRE, spans.SERVE_ADMIT, spans.SERVE_RESERVE,
                spans.SERVE_PLAN, spans.SERVE_PUT, spans.SERVE_DISPATCH,
                spans.SERVE_FETCH, spans.SERVE_EMIT, spans.SERVE_GAUGES)
TRAIN_PHASES = (spans.TRAIN_FEED, spans.TRAIN_PUT, spans.TRAIN_DISPATCH,
                spans.TRAIN_FETCH, spans.TRAIN_LOG)
SLOTS, CHUNK = 3, 8
_CACHE = {}


def _model():
    if "m" not in _CACHE:
        cfg = reduced(get_config("qwen2-1.5b")).replace(dtype="float32")
        m = get_model(cfg)
        _CACHE["m"] = (cfg, m, m.init(jax.random.PRNGKey(0)))
    return _CACHE["m"]


def _profiled(tmp_path, body):
    """Run ``body`` under the profiler; the program's host spans it left,
    as (name, start_ns, end_ns, stats) in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                    for ev in line.events if ev.name.startswith(("serve.", "train."))]
    return sorted(out, key=lambda e: e[1])


def _children(events, step):
    """The phases inside each ``step`` span, in start order."""
    outer = [e for e in events if e[0] == step]
    assert outer
    return [[e for e in events if e[0] != step and o[1] <= e[1] and e[2] <= o[2]]
            for o in outer]


@pytest.mark.parametrize("kind", ["mixed", "decode", "spec"])
def test_serve_step_phases_nest_in_the_step(tmp_path, monkeypatch, kind):
    """A paged engine's mixed step, decode megastep and n-gram
    speculative megastep each show every phase inside ``serve.step``;
    the mixed step's plan counts the flat rows the device computes (16:
    a budget of 8 plus 2 other slots, rounded up to 8) and the real ones
    (Σ q_len of the plan it ran), the pages the prefill attention kernel
    sweeps and the table's, and the replay counts what it emitted."""
    _, m, params = _model()
    eng = ServeEngine(m, params, slots=SLOTS, max_len=64, eos_id=1 << 20, paged=True,
                      decode_chunk=2, prefill_chunk=CHUNK,
                      draft="ngram" if kind == "spec" else "off")
    reqs = [eng.submit([1, 5 + i, 9, 2] * (3 - i), max_new=12) for i in range(2)]
    reqs = [eng.scheduler.get(r) for r in reqs]
    eng.step()  # compiles the mixed step: 12 + 8 prompt tokens against a budget of 8
    if kind != "mixed":
        while eng.scheduler.has_prefilling():
            eng.step()
    plans = []
    chunk_plan = eng.scheduler.chunk_plan
    monkeypatch.setattr(eng.scheduler, "chunk_plan",
                        lambda *a: plans.append(chunk_plan(*a)) or plans[-1])
    before = sum(len(r.out) for r in reqs)
    events = _profiled(tmp_path, eng.step)
    emitted = sum(len(r.out) for r in reqs) - before

    (inside,) = _children(events, spans.SERVE_STEP)
    assert [e[0] for e in inside] == list(SERVE_PHASES)
    stats = {e[0]: e[3] for e in inside}
    assert stats[spans.SERVE_EMIT] == {"emitted": emitted}
    if kind == "mixed":
        (plan,) = plans
        vl = plan["q_offset"] + plan["q_len"]
        assert stats[spans.SERVE_PLAN] == {
            "rows": 16, "real_rows": int(plan["q_len"].sum()),
            "kv_pages": int(sum(-(-v // 16) for v, n in zip(vl, plan["q_len"]) if n)),
            "kv_pages_grid": SLOTS * 64 // 16,
        }
        assert 0 < plan["q_len"].sum() <= CHUNK + SLOTS - 1
    else:
        assert not plans and stats[spans.SERVE_PLAN] == {}
        assert emitted > 0


def test_mixed_step_plan_counts_kv_pages(tmp_path):
    """``kv_pages`` on the mixed step's plan, by hand: in 4-token pages the
    second step's chunks end at 12 (slot 0, 4 more prompt tokens) and at 4
    (slot 1, its whole prompt), 3 + 1 pages; the idle slot 2 sweeps none.
    ``kv_pages_grid`` is the table: 3 slots × 16 pages. The step computes
    16 flat rows, 8 of them real."""
    _, m, params = _model()
    eng = ServeEngine(m, params, slots=SLOTS, max_len=64, eos_id=1 << 20, paged=True,
                      page_size=4, prefill_chunk=CHUNK)
    eng.submit([1, 5, 9, 2] * 3, max_new=4)
    eng.submit([1, 6, 9, 2], max_new=4)
    eng.step()  # slot 0 takes the budget's 8 prompt tokens; slot 1 waits
    events = _profiled(tmp_path, eng.step)
    (plan,) = [e[3] for e in events if e[0] == spans.SERVE_PLAN]
    assert plan == {"rows": 16, "real_rows": 8, "kv_pages": 4, "kv_pages_grid": 48}


def test_train_step_phases_nest_in_the_step(tmp_path):
    cfg, m, params = _model()
    tr = Trainer(m, get_peft(PeftConfig(method="neuroada", k=2)),
                 TrainConfig(learning_rate=1e-3, steps=3, log_every=0, checkpoint_every=0),
                 params)
    data = iter([peek_batch("lm", cfg.vocab_size, 2, 16, seed=i) for i in range(3)])
    tr.run(data, steps=1)  # compiles
    events = _profiled(tmp_path, lambda: tr.run(data, steps=3))
    for inside in _children(events, spans.TRAIN_STEP):
        assert [e[0] for e in inside] == list(TRAIN_PHASES)
    assert len(tr.history) == 3
