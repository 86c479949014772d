"""Request lifecycle (DESIGN §16): validation, cancellation, deadlines,
backpressure, fairness, graceful drain.

Everything time-dependent runs on an injected fake clock — the engine,
scheduler and tracer all stamp from ONE source, so deadline arithmetic
and rate-limit refills are exact and the suite never sleeps. Pool
reclamation is asserted through ``kv.drained()``: every terminal path
(cancel mid-queue / mid-prefill / mid-decode, deadline eviction, drain)
must return the block pool to a full free list with zero refcounts.
"""

import math
import random

import jax
import pytest

from repro.configs import get_config, reduced
from repro.models import get_model
from repro.obs import Tracer
from repro.serve import (
    QueueFullError,
    RateLimitedError,
    Scheduler,
    ServeEngine,
)

_NO_EOS = 1 << 20
_CACHE = {}


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


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
    kw.setdefault("decode_chunk", 2)
    return ServeEngine(m, params, **kw)


# ------------------------------------------------------- input validation


def test_submit_validation():
    eng = _engine()
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([], max_new=4)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit([1, 2], max_new=0)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit([1, 2], max_new=-3)
    with pytest.raises(ValueError, match="timeout"):
        eng.submit([1, 2], max_new=4, timeout=0.0)
    # non-numeric / non-finite knobs are ValueErrors at intake, never a
    # crash inside step() (which would take down a whole server)
    with pytest.raises(ValueError, match="temperature"):
        eng.submit([1, 2], max_new=4, temperature="hot")
    with pytest.raises(ValueError, match="temperature"):
        eng.submit([1, 2], max_new=4, temperature=[1, 2])
    with pytest.raises(ValueError, match="temperature"):
        eng.submit([1, 2], max_new=4, temperature=math.nan)
    with pytest.raises(ValueError, match="timeout"):
        eng.submit([1, 2], max_new=4, timeout="soon")
    with pytest.raises(ValueError, match="timeout"):
        eng.submit([1, 2], max_new=4, timeout=math.inf)
    with pytest.raises(ValueError, match="deadline"):
        eng.submit([1, 2], max_new=4, deadline="tomorrow")
    sched = Scheduler(2)
    with pytest.raises(ValueError, match="max_new"):
        sched.submit([1, 2], max_new=-1)


def test_scheduler_arg_validation():
    with pytest.raises(ValueError, match="policy"):
        Scheduler(2, policy="lifo")
    with pytest.raises(ValueError, match="queue_limit"):
        Scheduler(2, queue_limit=0)
    with pytest.raises(ValueError, match="quantum"):
        Scheduler(2, quantum=0)
    with pytest.raises(ValueError, match="fairness"):
        _engine(fairness="round-robin")


# ------------------------------------------------- bounded queue + limits


def test_queue_limit_sheds_with_retry_after():
    clock = FakeClock()
    sched = Scheduler(1, queue_limit=2, clock=clock)
    sched.submit([1], 4)
    sched.submit([2], 4)
    with pytest.raises(QueueFullError) as ei:
        sched.submit([3], 4)
    assert ei.value.retry_after > 0
    # admission frees backlog space: submits work again
    sched.admissible()
    sched.submit([3], 4)


def test_token_bucket_rate_limit_exact_refill():
    clock = FakeClock()
    sched = Scheduler(4, clock=clock)
    sched.set_rate_limit(1, rate=2.0, burst=1.0)
    sched.submit([1], 4, adapter_id=1)
    with pytest.raises(RateLimitedError) as ei:
        sched.submit([2], 4, adapter_id=1)
    assert ei.value.retry_after == pytest.approx(0.5)
    # other tenants are not limited
    sched.submit([3], 4, adapter_id=0)
    clock.advance(0.5)  # exactly one token accrued
    sched.submit([2], 4, adapter_id=1)
    sched.clear_rate_limit(1)
    for _ in range(5):
        sched.submit([4], 4, adapter_id=1)


def test_queue_full_shed_does_not_debit_rate_bucket():
    """A request shed on queue_limit must not also consume a rate-limit
    token — under overload that would double-penalize the tenant with
    429s for requests that were never queued."""
    clock = FakeClock()
    sched = Scheduler(1, queue_limit=1, clock=clock)
    sched.set_rate_limit(0, rate=1.0, burst=1.0)
    sched.submit([1], 4)  # takes the banked token, fills the backlog
    clock.advance(1.0)  # exactly one token accrued again
    with pytest.raises(QueueFullError):
        sched.submit([2], 4)
    sched.admissible()  # admission frees backlog space
    sched.submit([2], 4)  # the accrued token was NOT debited by the shed


def test_engine_shed_counters(monkeypatch):
    clock = FakeClock()
    eng = _engine(queue_limit=5, metrics=True, clock=clock)
    eng.set_rate_limit(0, rate=1.0, burst=3.0)
    for _ in range(3):  # burst exhausted; backlog still has room
        eng.submit([1, 2], max_new=2)
    with pytest.raises(RateLimitedError):
        eng.submit([1, 2], max_new=2)
    clock.advance(10.0)  # bucket refills: now fill the backlog itself
    for _ in range(2):
        eng.submit([1, 2], max_new=2)
    with pytest.raises(QueueFullError):
        eng.submit([1, 2], max_new=2)
    shed = eng.metrics.get("serve_requests_shed_total")
    assert shed.labels("rate_limit").value == 1
    assert shed.labels("queue_full").value == 1
    eng.run_to_completion()


# ---------------------------------------------------------- cancellation


def test_cancel_mid_queue():
    eng = _engine(paged=True, metrics=True)
    rids = [eng.submit([1, 5 + i, 9], max_new=4) for i in range(3)]
    eng.step()  # 2 admitted, rids[2] still queued
    assert eng.cancel(rids[2])
    assert not eng.cancel(rids[2])  # idempotent
    assert not eng.cancel(12345)  # unknown rid
    reqs = {r.rid: r for r in [eng.scheduler.get(rid) for rid in rids[:2]]}
    eng.run_to_completion()
    cancelled = eng.metrics.get("serve_requests_cancelled_total")
    assert cancelled.labels("queued").value == 1
    fin = eng.metrics.get("serve_requests_finished_total")
    assert fin.labels("0", "cancelled").value == 1
    assert fin.labels("0", "max_new").value == 2
    assert eng.kv.drained()
    assert all(r.done and r.reason == "max_new" for r in reqs.values())


@pytest.mark.parametrize("paged", [True, False])
def test_cancel_mid_prefill_and_mid_decode_reclaims_pool(paged):
    eng = _engine(paged=paged, prefill_chunk=4, metrics=True)
    long_prompt = [1] + [7] * 20  # several chunk steps of prefill
    r0 = eng.submit(long_prompt, max_new=4)
    r1 = eng.submit([1, 5, 9], max_new=16)
    eng.step()  # mixed step: r0 mid-prefill, r1 prefilled or decoding
    assert eng.scheduler.get(r0).mid_prefill
    assert eng.cancel(r0)  # mid-prefill cancellation
    while eng.scheduler.has_prefilling():
        eng.step()
    eng.step()  # r1 decoding
    assert eng.cancel(r1)  # mid-decode cancellation
    assert not eng.step()  # nothing left
    assert eng.kv.drained()
    cancelled = eng.metrics.get("serve_requests_cancelled_total")
    assert cancelled.labels("prefill").value == 1
    assert cancelled.labels("decode").value == 1
    req0, req1 = eng.scheduler.get(r0), eng.scheduler.get(r1)
    assert req0 is None and req1 is None  # dropped from in-flight tracking


def test_cancel_survivor_parity():
    """Cancelling one stream never perturbs the others: survivors'
    greedy outputs are token-identical to an unperturbed run."""
    eng = _engine(paged=True, slots=3)
    prompts = [[1, 5, 9], [1, 6, 9], [1, 7, 9]]
    base = [eng.submit(p, max_new=6) for p in prompts]
    expect = {r.rid - base[0]: list(r.out) for r in eng.run_to_completion()}
    rids = [eng.submit(p, max_new=6) for p in prompts]
    reqs = [eng.scheduler.get(rid) for rid in rids]
    eng.step()
    eng.step()
    assert eng.cancel(rids[1])
    eng.run_to_completion()
    assert reqs[0].out == expect[0]
    assert reqs[2].out == expect[2]
    assert reqs[1].reason == "cancelled"
    assert eng.kv.drained()


# -------------------------------------------------------------- deadlines


def test_deadline_expiry_queued_and_active():
    clock = FakeClock()
    eng = _engine(paged=True, metrics=True, clock=clock, slots=2)
    r_live = eng.submit([1, 5, 9], max_new=8)
    r_act = eng.submit([1, 6, 9], max_new=8, timeout=5.0)
    r_q = eng.submit([1, 7, 9], max_new=8, timeout=5.0)  # queued: slots full
    req_live, req_act, req_q = (
        eng.scheduler.get(r) for r in (r_live, r_act, r_q)
    )
    eng.step()  # admits r_live + r_act; r_q waits
    assert eng.scheduler.slot_of(r_q) is None
    clock.advance(6.0)  # both deadlines pass
    eng.step()  # boundary sweep evicts queued AND active expired requests
    expired = eng.metrics.get("serve_deadline_expired_total")
    assert expired.labels("queued").value == 1
    assert expired.total == 2
    fin = eng.metrics.get("serve_requests_finished_total")
    assert fin.labels("0", "deadline").value == 2
    assert req_act.reason == "deadline" and req_q.reason == "deadline"
    eng.run_to_completion()
    assert req_live.reason == "max_new" and len(req_live.out) == 8
    assert eng.scheduler.get(r_live) is None  # finished and deallocated
    assert eng.kv.drained()


def test_deadline_aware_admission_refuses_hopeless_requests():
    clock = FakeClock()
    eng = _engine(metrics=True, clock=clock)
    eng.step_seconds_ema = 0.5  # as if measured: a step costs 500ms
    with pytest.raises(QueueFullError, match="deadline unreachable"):
        eng.submit([1, 2], max_new=4, timeout=0.1)
    assert eng.metrics.get("serve_requests_shed_total").labels(
        "deadline"
    ).value == 1
    # a reachable deadline is admitted
    rid = eng.submit([1, 2], max_new=4, timeout=60.0)
    assert eng.scheduler.get(rid) is not None
    eng.run_to_completion()


def test_step_seconds_ema_measured():
    eng = _engine()
    assert eng.step_seconds_ema is None  # unknown until a step runs
    eng.submit([1, 5, 9], max_new=2)
    eng.run_to_completion()
    # the very first mixed/decode steps are JIT compiles and are never
    # folded in — a multi-second compile must not seed the admission
    # gate's estimate; warm steps do
    eng.submit([1, 5, 9], max_new=2)
    eng.run_to_completion()
    assert eng.step_seconds_ema is not None and eng.step_seconds_ema > 0
    # ...and the estimate reflects warm steps, not compile time: warm
    # steps on this tiny model are far under a second
    assert eng.step_seconds_ema < 1.0


def test_step_histogram_times_the_whole_step_and_ema_the_compiled_call(monkeypatch):
    """``serve_step_seconds{kind}`` observes the whole ``step()`` call,
    the interval of its ``serve.step`` span, admission and the gauges
    included; the admission gate's EMA keeps timing the compiled call."""
    clock = FakeClock()
    eng = _engine(metrics=True, clock=clock)
    admit, gauges = eng._admit, eng._update_gauges
    monkeypatch.setattr(eng, "_admit", lambda: (clock.advance(3.0), admit())[1])
    monkeypatch.setattr(eng, "_update_gauges", lambda: (clock.advance(0.5), gauges())[1])
    real_chunk, real_decode = eng._chunk_step, eng._decode_step
    monkeypatch.setattr(eng, "_chunk_step", lambda k: (clock.advance(0.25), real_chunk(k))[1])
    monkeypatch.setattr(eng, "_decode_step", lambda k: (clock.advance(0.25), real_decode(k))[1])
    eng.submit([1, 5, 9], max_new=4)
    steps = 0
    while eng.step():
        steps += 1
    hist = eng.metrics.get("serve_step_seconds")
    assert hist.labels("mixed").count + hist.labels("decode").count == steps
    assert hist.labels("mixed").sum + hist.labels("decode").sum == pytest.approx(3.75 * steps)
    assert eng.step_seconds_ema == pytest.approx(0.25)


# --------------------------------------------------------- graceful drain


def test_drain_closes_intake_and_finishes_in_flight():
    eng = _engine(paged=True)
    rids = [eng.submit([1, 5 + i, 9], max_new=4) for i in range(3)]
    reqs = [eng.scheduler.get(rid) for rid in rids]
    done = eng.drain()
    assert {r.rid for r in done} == set(rids)
    assert all(r.done and r.reason == "max_new" for r in reqs)
    assert eng.kv.drained()
    with pytest.raises(RuntimeError, match="draining"):
        eng.submit([1, 2], max_new=2)


# ----------------------------------------------------- unified timestamps


def test_one_clock_for_requests_traces_and_deadlines():
    clock = FakeClock(100.0)
    tracer = Tracer(clock=clock)
    eng = _engine(tracer=tracer, metrics=True)
    assert eng.clock is clock  # explicit tracer clock wins everywhere
    assert eng.scheduler.clock is clock
    clock.advance(1.25)
    rid = eng.submit([1, 5, 9], max_new=2)
    req = eng.scheduler.get(rid)
    assert req.t_submit == pytest.approx(101.25)
    # the tracer's submit instant is the same reading, in its µs timebase
    sub = [e for e in tracer.events_for(rid) if e["name"] == "submit"]
    assert sub[0]["ts"] == pytest.approx(1.25e6)
    eng.run_to_completion()
    fin = [e for e in tracer.events_for(rid) if e["name"] == "finish"]
    assert fin and fin[0]["args"]["reason"] == "max_new"


# ------------------------------------------------------ fairness (DRR)


def _drain_order(sched, max_rounds=10_000):
    """Admit one request at a time (slots complete instantly), recording
    admission order — the service order a single-slot engine would see.
    Rounds that admit nothing are legal under DRR (a big request is
    still accruing deficit), so only a convergence cap stops the loop."""
    order = []
    rounds = 0
    while sched.has_queued() or sched.has_active():
        rounds += 1
        assert rounds < max_rounds, "admission did not converge"
        for slot, req in sched.admissible():
            order.append(req)
            sched.complete(slot)
    return order


def test_drr_bounds_hot_tenant_starvation():
    """A hot tenant's flood delays another tenant's head by at most
    ceil(cost / quantum) of its own requests — not its whole backlog."""
    q = 32
    sched = Scheduler(1, policy="drr", quantum=q)
    hot = [sched.submit([1] * 8, 8, adapter_id=1) for _ in range(10)]
    cold = sched.submit([2] * 8, 8, adapter_id=2)
    order = _drain_order(sched)
    rids = [r.rid for r in order]
    assert sorted(rids) == sorted(hot + [cold])
    bound = math.ceil(16 / q)  # cold request cost = 8 + 8
    assert rids.index(cold) <= bound
    # within-tenant FIFO is preserved
    hot_order = [r for r in rids if r in hot]
    assert hot_order == hot


def test_drr_starvation_bound_property():
    """Property-style sweep: random costs, arrival mixes and quanta —
    the cold tenant's head is always admitted within ceil(cost/quantum)
    hot admissions, and per-tenant FIFO always holds."""
    for seed in range(20):
        rng = random.Random(seed)
        q = rng.choice([8, 32, 128])
        sched = Scheduler(1, policy="drr", quantum=q)
        hot = []
        for _ in range(rng.randrange(3, 12)):
            n_p = rng.randrange(1, 30)
            hot.append(
                sched.submit([1] * n_p, rng.randrange(1, 30), adapter_id=1)
            )
        n_p, n_new = rng.randrange(1, 30), rng.randrange(1, 30)
        cold = sched.submit([2] * n_p, n_new, adapter_id=2)
        order = [r.rid for r in _drain_order(sched)]
        assert sorted(order) == sorted(hot + [cold])
        hot_before = order.index(cold)
        assert hot_before <= math.ceil((n_p + n_new) / q), (
            f"seed {seed}: cold head waited behind {hot_before} hot "
            f"requests (cost {n_p + n_new}, quantum {q})"
        )
        assert [r for r in order if r in hot] == hot


def test_drr_forfeits_deficit_when_backlog_empties():
    sched = Scheduler(1, policy="drr", quantum=100)
    sched.submit([1] * 4, 4, adapter_id=1)
    _drain_order(sched)
    sched.admissible()  # empty backlog: the banked 92 tokens forfeit
    assert 1 not in sched._deficit
    # a later giant request accumulates from zero: three rounds of 100
    # to cover cost 300, not two rounds topping up a stale bank
    big = sched.submit([1] * 150, 150, adapter_id=1)
    sched.admissible()
    assert sched.slot_of(big) is None
    sched.admissible()
    assert sched.slot_of(big) is None
    sched.admissible()
    assert sched.slot_of(big) is not None


def test_fifo_policy_unchanged():
    sched = Scheduler(1, policy="fifo")
    a = [sched.submit([1] * 50, 50, adapter_id=1) for _ in range(5)]
    b = sched.submit([2], 1, adapter_id=2)
    order = [r.rid for r in _drain_order(sched)]
    assert order == a + [b]  # strict global arrival order, no weighting


def test_drr_engine_end_to_end():
    """The fairness policy composes with the real paged engine: every
    request finishes, outputs match the FIFO engine's for the same
    prompts (admission order changes; per-request greedy output cannot)."""
    prompts = {1: [[1, 5, 9], [1, 6, 9]], 2: [[1, 7, 9]]}
    outs = {}
    for policy in ("fifo", "drr"):
        eng = _engine(paged=True, slots=2, fairness=policy, quantum=8)
        rid_of = {}
        for tenant, ps in prompts.items():
            for p in ps:
                rid_of[eng.submit(p, max_new=4)] = (tenant, tuple(p))
        done = eng.drain()
        assert all(r.reason == "max_new" for r in done)
        assert eng.kv.drained()
        outs[policy] = {rid_of[r.rid]: r.out for r in done}
    assert outs["fifo"] == outs["drr"]
