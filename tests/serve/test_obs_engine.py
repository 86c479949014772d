"""Engine observability: zero-extra-transfer, zero-recompile, lifecycle.

The two pinned invariants of DESIGN §13 live here: with metrics AND
request tracing enabled, a compiled serving step still costs exactly ONE
``jax.device_get`` (instrumentation reads the already-fetched bundle and
host bookkeeping, never the device), and drives zero recompiles (it adds
no traced inputs — jit cache sizes are flat across mixed, decode and
speculative steps after warmup). The lifecycle tests check the registry
and trace against ground truth the scheduler/pool already expose:
requests finished == submitted, pool occupancy drains to zero, and a
preempted request's trace shows the preempt instant followed by the
exact re-prefill spans.
"""

import jax
import pytest

from repro.configs import get_config, reduced
from repro.models import get_model
from repro.obs import Tracer
from repro.serve import ServeEngine

_NO_EOS = 1 << 20
_CACHE = {}


def _model():
    if "m" not in _CACHE:
        cfg = reduced(get_config("qwen2-1.5b")).replace(dtype="float32")
        m = get_model(cfg)
        _CACHE["m"] = (cfg, m, m.init(jax.random.PRNGKey(0)))
    return _CACHE["m"]


def _engine(**kw):
    cfg, m, params = _model()
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("eos_id", _NO_EOS)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("metrics", True)
    kw.setdefault("tracer", Tracer())
    return ServeEngine(m, params, **kw)


# ------------------------------------------------- pinned invariant: transfers


@pytest.mark.parametrize("draft", ["off", "ngram"])
def test_one_transfer_per_step_with_obs_enabled(monkeypatch, draft):
    """Metrics + tracing on: still exactly one device→host fetch per
    compiled step, and the registry's transfer counter agrees with the
    monkeypatched ground truth."""
    eng = _engine(paged=True, draft=draft)
    eng.submit([1, 5, 9, 2], max_new=40)
    eng.submit([1, 6, 9, 2], max_new=40)
    eng.step()  # admission + first mixed chunk (its own single transfer)
    while eng.scheduler.has_prefilling():
        eng.step()
    calls = []
    real = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: (calls.append(1), real(x))[1]
    )
    before = eng.transfers
    for _ in range(3):
        assert eng.step()
    assert len(calls) == 3
    assert eng.transfers - before == 3
    assert eng.metrics.value("serve_transfers_total") == eng.transfers
    assert len(eng.tracer) > 0  # tracing really was on


# ------------------------------------------------ pinned invariant: recompiles


@pytest.mark.parametrize("draft", ["off", "ngram", "int8"])
def test_zero_recompiles_across_step_kinds(draft):
    """Instrumentation adds no traced inputs: after one warmup of each
    live step kind (mixed chunk, decode/spec megastep), further steps —
    including a fresh mid-run arrival re-entering the mixed path — leave
    every jit cache size unchanged. The engine registers one jitted
    function per step kind, and each kind it runs compiles once; a
    speculative engine never runs the plain megastep."""
    eng = _engine(paged=True, draft=draft)
    eng.submit([1, 5, 9, 2], max_new=24)
    eng.submit([1, 6, 9, 2], max_new=24)
    eng.step()  # mixed chunkstep compiles
    while eng.scheduler.has_prefilling():
        eng.step()
    eng.step()  # decode (or spec) megastep compiles
    eng.submit([1, 7, 9, 2], max_new=8)  # arrival → mixed path again
    eng.step()
    warm = eng.compile_counts()
    spec = {"off": {}, "ngram": {"ngram_megastep": 1},
            "int8": {"spec_megastep": 1}}[draft]
    assert warm == {"chunkstep": 1, "megastep": 0 if spec else 1, **spec}
    while eng.step():
        pass
    assert eng.compile_counts() == warm
    assert eng.metrics.value("serve_jit_compiles") == sum(warm.values())


# ------------------------------------------------------- lifecycle accounting


def test_lifecycle_counters_and_pool_drain():
    eng = _engine(paged=True)
    for i in range(3):  # 3 requests on 2 slots: one waits in the queue
        eng.submit([1, 5 + i, 9, 2], max_new=5)
    eng.run_to_completion()
    reg = eng.metrics
    assert reg.get("serve_requests_submitted_total").total == 3
    assert reg.get("serve_requests_admitted_total").total == 3
    fin = reg.get("serve_requests_finished_total")
    assert fin.total == 3
    assert fin.labels("0", "max_new").value == 3
    assert reg.get("serve_tokens_total").total == 15
    assert reg.get("serve_tenant_tokens_total").labels("0").value == 15
    assert reg.get("serve_ttft_seconds").count == 3
    # ITL: every emitted token after a request's first observes one gap
    assert reg.get("serve_itl_seconds").count == 12
    # the final step drained everything: gauges read an idle engine
    assert reg.value("serve_queue_depth") == 0
    assert reg.value("serve_slots_active") == 0
    assert reg.value("serve_pool_blocks_used") == 0
    assert reg.value("serve_pool_blocks_free") == eng.kv.num_blocks
    # per-request trace: the full lifecycle in order
    for rid in range(3):
        names = [e["name"] for e in eng.tracer.events_for(rid)]
        assert names[0] == "submit"
        assert names[-1] == "finish"
        for must in ("queued", "admitted", "prefill_chunk", "first_token"):
            assert must in names
        assert names.index("queued") < names.index("admitted")
        fin_ev = eng.tracer.events_for(rid)[-1]
        assert fin_ev["args"] == {"reason": "max_new", "tokens": 5}


def test_step_kind_counters_split_mixed_and_decode():
    eng = _engine(paged=True)
    eng.submit([1, 5, 9, 2], max_new=9)
    eng.run_to_completion()
    reg = eng.metrics
    mixed = reg.value("serve_steps_total", "mixed")
    decode = reg.value("serve_steps_total", "decode")
    assert mixed >= 1 and decode >= 1
    assert reg.get("serve_step_seconds").labels("mixed").count == mixed
    assert reg.get("serve_step_seconds").labels("decode").count == decode
    assert eng.metrics.value("serve_transfers_total") == mixed + decode


def test_spec_metrics_and_acceptance_histogram():
    eng = _engine(paged=True, draft="ngram", spec_k=3, decode_chunk=2)
    # repetitive prompt: the ngram drafter should land at least sometimes
    eng.submit([1, 2, 3, 1, 2, 3, 1, 2], max_new=24)
    eng.run_to_completion()
    reg = eng.metrics
    drafted = reg.value("serve_spec_drafted_total")
    accepted = reg.value("serve_spec_accepted_total")
    emitted = reg.value("serve_spec_emitted_total")
    assert drafted > 0 and drafted % 3 == 0
    assert 0 <= accepted <= drafted
    # the request's FIRST token is the mixed prefill step's sample; the
    # other 23 all flow through the speculative megastep
    assert emitted == 23
    assert reg.value("serve_tokens_total", "spec") == 23
    assert reg.get("serve_tokens_total").total == 24
    # back-compat properties read the same registry series
    assert (eng.spec_drafted, eng.spec_accepted, eng.spec_emitted) == (
        drafted, accepted, emitted,
    )
    h = reg.get("serve_spec_accept_len")
    assert h.count == drafted / 3  # one observation per live slot-round
    assert h.sum == accepted
    assert h.buckets == (0.0, 1.0, 2.0, 3.0)
    # trace rounds agree with the histogram
    rounds = sum(
        e["args"]["rounds"]
        for e in eng.tracer.events_for(0)
        if e["name"] == "spec_round"
    )
    assert rounds == h.count


# -------------------------------------------------- preemption + re-prefill


def test_preempt_trace_shows_exact_reprefill():
    """Under pool pressure the victim's trace reads: …decode → preempt →
    queued → admitted(resume) → prefill_chunk(s) covering exactly the
    prompt + everything generated before the preempt → first re-token."""
    cfg, m, params = _model()
    eng = _engine(slots=3, paged=True, page_size=4, num_blocks=16)
    prompts = [([1, 5, 9, 2], 20), ([1, 6, 9, 2], 20), ([1, 7, 9, 2], 20)]
    for p, mn in prompts:
        eng.submit(p, max_new=mn)
    eng.run_to_completion()
    assert eng.preemptions >= 1
    assert eng.preemptions == eng.metrics.get("serve_preemptions_total").total
    # find a preempted request and replay its trace
    preempted = {
        e["rid"] for e in eng.tracer.events if e["name"] == "preempt"
    }
    assert preempted
    rid = min(preempted)
    evs = eng.tracer.events_for(rid)
    i_pre = next(i for i, e in enumerate(evs) if e["name"] == "preempt")
    tokens_done = evs[i_pre]["args"]["tokens_done"]
    after = evs[i_pre + 1 :]
    names = [e["name"] for e in after]
    assert names[0] == "queued"  # re-queued at the front
    i_adm = names.index("admitted")
    adm = after[i_adm]
    assert adm["args"]["resume"] is True
    # the re-prefill basis is prompt + out-at-preemption, minus any
    # shared-prefix lead admission could skip
    target = adm["args"]["prefill_target"]
    assert target == len(prompts[rid][0]) + tokens_done
    re_prefill = sum(
        e["args"]["tokens"] for e in after if e["name"] == "prefill_chunk"
    )
    assert re_prefill == target - adm["args"]["prefilled"]
    assert "finish" in names


# ------------------------------------------------------------- metrics-off


def test_metrics_off_engine_matches_and_reads_zero():
    """``metrics=False`` serves identically (greedy parity) through no-op
    instruments; the back-compat properties read 0 instead of raising."""
    on = _engine(paged=True)
    off = _engine(paged=True, metrics=False, tracer=None)
    for eng in (on, off):
        for i in range(2):
            eng.submit([1, 5 + i, 9, 2], max_new=6)
    got_on = [r.out for r in on.run_to_completion()]
    got_off = [r.out for r in off.run_to_completion()]
    assert got_on == got_off
    assert not off.metrics.enabled
    assert off.transfers == 0 == off.preemptions
    assert (off.spec_drafted, off.spec_accepted, off.spec_emitted) == (0, 0, 0)
    assert off.metrics.expose() == ""
    assert on.metrics.value("serve_transfers_total") > 0
