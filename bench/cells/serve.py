"""Serving cell: clients driving ``ServeEngine.submit`` and
``ServeEngine.step``, with the deltas put into the engine as the mix's
``adapter`` mode says (``bench/adapters``).

In a closed loop each client sends its next request the moment its last
one finishes; the set-up fills every client and steps the engine until it
has run a decode megastep as well as the mixed prefill step, so both
compiled programs exist and the cache holds the traffic's contexts. In an
open loop requests arrive at the times the mix draws, and are submitted
at the first step boundary after their arrival; the set-up serves the
first ``clients`` requests at once until both programs have run, lets them
finish, then starts the arrival clock and steps for ``lead_s`` seconds.
The window then opens on the next step boundary and closes on the first
boundary after ``--seconds``. A token's time is the end of the step that
emitted it; a request's latency counts from its submission (closed) or
its arrival (open).

After the window the served tokens of a sample of the window's finished
requests, drawn from the seed and holding the longest, are scored by the
plain f32 reference over prompt and served tokens, with the request's own
tenant's delta: the number compared is the widest gap by which a served
token's logit lies below the reference's best at its position (greedy
decoding serves the best).
"""

from __future__ import annotations

import time

import numpy as np

from bench import adapters, program, traffic, weights
from bench.cells.base import (Cell, Compiles, annotate, compare, free_program, memory_peak, now,
                              open_window, traced)
from bench.common import percentile
from bench.costs import model as costs
from bench.reference import qwen2

DONE_OK = ("eos", "max_new")


class Tracked:
    __slots__ = ("req", "tenant", "t_submit", "times", "seen_prefill", "t_done")

    def __init__(self, req, tenant, t_submit):
        self.req, self.tenant, self.t_submit = req, tenant, t_submit
        self.times: list[float] = []
        self.seen_prefill = 0
        self.t_done = None


class Loop:
    """The cell's clients: a closed loop of ``clients``, or open-loop
    arrivals once ``start_clock`` has been called."""

    def __init__(self, served, requests, arrivals: dict, cfg: dict):
        self.served, self.requests, self.cfg = served, requests, cfg
        self.engine = served.engine
        self.closed = arrivals["process"] == "closed"
        self.clients = arrivals.get("clients", 0)
        self.t_base = None  # the arrival clock's zero (open loop)
        self.next = None  # the next request not yet submitted (open loop)
        self.live: list[Tracked] = []
        self.done: list[Tracked] = []
        self.steps: list[dict] = []

    def submit(self, r: traffic.Request, t_arrival: float | None = None) -> None:
        t = now()
        rid = self.engine.submit(r.prompt, max_new=r.max_new, **self.served.submit_kwargs(r.tenant))
        req = self.engine.scheduler.get(rid)
        self.live.append(Tracked(req, r.tenant, t if t_arrival is None else t_arrival))

    def fill(self) -> None:
        """Every client sends a request (an open loop's warm-up burst)."""
        with annotate("bench.submit"):
            while len(self.live) < self.clients:
                self.submit(next(self.requests))

    def start_clock(self) -> None:
        self.t_base, self.next = now(), next(self.requests)

    def arrive(self) -> None:
        """Submit every request whose arrival time has passed."""
        with annotate("bench.submit"):
            while self.t_base + self.next.arrival <= now():
                self.submit(self.next, self.t_base + self.next.arrival)
                self.next = next(self.requests)

    def step(self) -> dict:
        if self.t_base is not None:
            self.arrive()
            if not self.live:  # nothing to serve before the next arrival
                t0 = now()
                with annotate("bench.idle"):
                    time.sleep(max(0.0, self.t_base + self.next.arrival - t0))
                rec = _record(t0, now())
                self.steps.append(rec)
                return rec
        before = [(t.req.prefilled, len(t.req.out)) for t in self.live]
        t0 = now()
        with annotate("bench.step"):
            self.engine.step()
        rec = _record(t0, now())
        with annotate("bench.emit"):
            self._account(rec, before)
        finished = [t for t in self.live if t.req.done]
        if finished:
            self.live = [t for t in self.live if not t.req.done]
            self.done += finished
            if self.closed:
                with annotate("bench.submit"):
                    for _ in finished:
                        self.submit(next(self.requests))
        self.steps.append(rec)
        return rec

    def _account(self, rec: dict, before: list) -> None:
        """The step's tokens, work and each slot's cache frontier, from the
        requests' progress."""
        t1 = rec["t1"]
        for tr, (p0, o0) in zip(self.live, before):
            req = tr.req
            p1, o1 = req.prefilled, len(req.out)
            plen = len(req.prompt)
            e = o1 - o0
            tr.times.extend([t1] * e)
            rec["emitted"] += e
            start = max(p0, tr.seen_prefill)
            if p1 > start:  # prompt positions start .. p1-1 ran this step
                rec["kind"] = "mixed"
                f = costs.serve_positions(self.cfg, start, p1, heads=min(e, 1))
                rec["flops"] += f
                rec["prefill_flops"] += f
                tr.seen_prefill = p1
                rec["frontier"].append(p1)
            elif e > 0:  # decode: inputs at positions P+o0-1 .. P+o0+e-2
                pos = plen + o0 - 1
                rec["flops"] += costs.serve_positions(self.cfg, pos, pos + e, heads=e)
                rec["decode_ctx"] += [pos + 1 + i for i in range(e)]
                rec["frontier"].append(pos + 1)
            else:  # no progress: the cache as it stood
                rec["frontier"].append(p0 if p0 < plen else plen + o0 - 1)
            if p1 > p0 or e > 0:
                rec["active"] += 1
            if req.done:
                tr.t_done = t1


def _record(t0: float, t1: float) -> dict:
    return {"t0": t0, "t1": t1, "kind": "decode", "emitted": 0, "flops": 0,
            "prefill_flops": 0, "active": 0, "decode_ctx": [], "frontier": []}


def window_stats(loop: Loop, t_open: float, t_close: float) -> dict:
    """What the window (t_open, t_close] holds: its steps, every gap between
    two tokens of a request both inside it, the time to first token of each
    request whose first token lands inside it, and the requests that ended
    inside it."""

    def inside(t):
        return t is not None and t_open < t <= t_close

    steps = [s for s in loop.steps if inside(s["t1"])]
    reqs = loop.done + loop.live
    gaps, ttft = [], []
    for tr in reqs:
        ts = tr.times
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if inside(a) and inside(b)]
        if ts and inside(ts[0]):
            ttft.append((ts[0] - tr.t_submit) * 1e3)
    ended = [tr for tr in loop.done if inside(tr.t_done)]
    return {"steps": steps, "gaps_ms": gaps, "ttft_ms": ttft, "ended": ended,
            "failed": [tr for tr in ended if tr.req.reason not in DONE_OK]}


def check_sample(ended: list, seed: int, want: dict) -> list:
    """Finished requests to hold against the reference: the longest, then
    others in an order drawn from the seed, until the sample holds
    ``requests`` requests and ``min_tokens`` served tokens."""
    ok = [tr for tr in ended if tr.req.reason in DONE_OK]
    if not ok:
        return []
    ok.sort(key=lambda tr: -(len(tr.req.prompt) + len(tr.req.out)))
    rest = ok[1:]
    order = traffic.rng_of(seed, 2).permutation(len(rest))
    sample = [ok[0]]
    for i in order:
        if len(sample) >= want["requests"] and sum(len(t.req.out) for t in sample) >= want["min_tokens"]:
            break
        sample.append(rest[i])
    return sample


def served_gaps(params, delta, sample, cfg, length, mm="f32", against=None) -> list[float]:
    """Per sampled request, the widest gap between the reference's best
    logit and that of the served token (or, with ``against``, of the token
    the ``mm`` precision puts first). ``delta(tenant)`` gives the
    request's selection and values."""
    out = []
    d = weights.dims(cfg)
    for tr in sample:
        idx, vals = delta(tr.tenant)
        prompt, served = tr.req.prompt, tr.req.out
        ref = np.asarray(qwen2.served_logits(params, idx, vals, prompt, served, d, length))
        if against is None:
            toks = np.asarray(served)
        else:
            low = np.asarray(qwen2.served_logits(params, idx, vals, prompt, served, d, length, mm=mm))
            toks = low.argmax(-1)
        out.append(float(np.max(ref.max(-1) - ref[np.arange(len(toks)), toks])))
    return out


def _numbers(gaps: list[float]) -> dict:
    return {"served_gap": max(gaps) if gaps else float("inf")}


def run(cell: Cell) -> dict:
    import jax

    cfg, mix = cell.cfg, cell.mix
    compiles = Compiles()
    model = program.model(cfg)
    params = weights.make_params(cfg, cfg["init"], cell.seed)
    weights.check_layout(params, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    t_weights = now()
    served = adapters.build(cell, model, params)
    delta = served.delta
    del params
    arrivals = traffic.arrivals_of(mix)
    if arrivals["process"] != "closed":
        arrivals = dict(arrivals, clients=cfg["engine"]["slots"])
    loop = Loop(served, traffic.serve_requests(mix, cfg["vocab_size"], cell.seed), arrivals, cfg)
    t_built = now()

    # set-up: the traffic itself until the decode megastep has run
    loop.fill()
    while not any(s["kind"] == "decode" and s["emitted"] for s in loop.steps):
        if len(loop.steps) > mix["max_setup_steps"]:
            raise SystemExit("bench: no decode megastep during set-up")
        loop.step()
    if not loop.closed:  # the warm-up burst ends, then arrivals run for lead_s
        while loop.live:
            loop.step()
        loop.start_clock()
        while now() - loop.t_base < arrivals["lead_s"]:
            loop.step()

    seconds = cell.window_seconds
    with traced(cell) as trace_dir:
        t_open = open_window()
        with annotate("bench.window"):
            while True:
                rec = loop.step()
                if rec["t1"] - t_open >= seconds:
                    break
    t_close = loop.steps[-1]["t1"]
    peak = memory_peak(cell.devices)
    ws = window_stats(loop, t_open, t_close)
    span = t_close - t_open
    e2e = {"serve_tokens_per_s": sum(s["emitted"] for s in ws["steps"]) / span}
    if ws["gaps_ms"]:
        e2e["itl_p95_ms"] = percentile(ws["gaps_ms"], 0.95)
    if ws["ttft_ms"]:
        e2e["ttft_p50_ms"] = percentile(ws["ttft_ms"], 0.5)
    sample = check_sample(ws["ended"], cell.seed, mix["check"])
    result = {
        "attempted": len(ws["ended"]),
        "failed": len(ws["failed"]),
        "memory_peak_bytes": peak,
        "setup_s": t_open - cell.t_start,
        "e2e": e2e,
        "layer_ctx": {"steps": ws["steps"], "window_host_s": span, "memory_peak_bytes": peak},
        "trace_dir": trace_dir,
        "notes": {"itl_gaps": len(ws["gaps_ms"]), "first_tokens": len(ws["ttft_ms"]),
                  "window_steps": len(ws["steps"]),
                  "window_compiles": compiles.between(t_open, t_close),
                  "step_s_min_max": [min(s["t1"] - s["t0"] for s in ws["steps"]),
                                     max(s["t1"] - s["t0"] for s in ws["steps"])],
                  "mixed_steps": sum(s["kind"] == "mixed" for s in ws["steps"]),
                  "setup_phases_s": {"weights": t_weights - cell.t_start,
                                     "engine": t_built - t_weights, "fill": t_open - t_built},
                  "sample_requests": len(sample),
                  "sample_tokens": sum(len(t.req.out) for t in sample)},
    }
    del served, loop
    free_program()

    # the reference, once the window has closed and the engine is freed
    params = weights.make_params(cfg, cfg["init"], cell.seed)
    length = cfg["engine"]["max_len"]
    gaps = served_gaps(params, delta, sample, cfg, length)
    result["correct"], result["checks"] = compare(_numbers(gaps), cell.limits)
    result["notes"]["per_request_gap"] = gaps
    if "control_mm" in cell.hooks:
        # the reference in a lower precision, put in the program's place and
        # judged by the same comparison
        low = served_gaps(params, delta, sample, cfg, length, mm=cell.hooks["control_mm"],
                          against=True)
        ok, checks = compare(_numbers(low), cell.limits)
        result["control"] = {"correct": ok, "checks": checks}
    return result
