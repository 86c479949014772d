"""What every cell driver shares: the run's context, the measured window's
clock and tracing, and the comparison that decides ``correct``."""

from __future__ import annotations

import contextlib
import gc
import math
import os
import time
from dataclasses import dataclass, field

from bench.common import ROOT

# a traced run measures at most this much of its window: the trace stays
# small enough to read well inside the run's time limit
TRACE_SECONDS = 12.0
# where a traced run writes its trace, inside the checkout
TRACE_DIR = ROOT / "chiprun_out" / "bench" / "trace"


@dataclass
class Cell:
    spec: dict
    workload: dict
    cfg: dict
    mix: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list
    # faults and controls (bench/faults.py, bench/control.py) replace parts of
    # the program through these; the benchmark's own runs set none
    hooks: dict = field(default_factory=dict)

    @property
    def window_seconds(self) -> float:
        return min(self.seconds, TRACE_SECONDS) if self.trace else self.seconds


@contextlib.contextmanager
def traced(cell: Cell):
    """Profile the body when the run is traced; the reduction is read after."""
    if not cell.trace:
        yield None
        return
    import jax

    d = str(TRACE_DIR / cell.workload["name"])
    os.makedirs(d, exist_ok=True)
    jax.profiler.start_trace(d)
    try:
        yield d
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def now() -> float:
    return time.perf_counter()


class Compiles:
    """Times at which JAX finished tracing, lowering or compiling a program in
    this process, so that a run can show that none fell inside its window."""

    def __init__(self):
        from jax import monitoring

        self.times: list[float] = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_):
        if name.startswith("/jax/core/compile/"):
            self.times.append(now())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 < t <= t1 for t in self.times)


def open_window() -> float:
    """The window's first instant: the set-up's garbage is collected and the
    survivors frozen out of later collections, so that a collection of
    set-up objects cannot stall a step inside the window."""
    gc.collect()
    gc.freeze()
    return now()


def free_program() -> None:
    """After the window: the program's objects, set-up's frozen ones among
    them, are collected, so that the reference runs on a freed chip."""
    gc.unfreeze()
    gc.collect()


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def compare(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct when every number is finite and
    at or under its limit."""
    checks, ok = {}, True
    for name, value in values.items():
        limit = limits[name]
        good = value is not None and math.isfinite(value) and value <= limit
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def norm_gap(program: dict, reference: dict, leaves: list[str]) -> float:
    """Worst leaf's gap between the program's norm and the reference's, over
    the larger of that leaf's reference norm and the median leaf's."""
    import numpy as np

    ref = {n: float(np.linalg.norm(np.asarray(reference[n], np.float64))) for n in leaves}
    med = float(np.median(list(ref.values())))
    gaps = [abs(float(np.linalg.norm(np.asarray(program[n], np.float64))) - ref[n])
            / max(ref[n], med) for n in leaves]
    return max(gaps)
