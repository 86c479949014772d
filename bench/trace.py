"""Profiler capture of the measured window and its reduction to device busy
time, kernel time and idle gaps.

The window is one ``bench.window`` host span; the benchmark's own
``TraceAnnotation`` spans (``bench.step``, ``bench.feed``, ``bench.emit``,
…) label what the host was doing in each idle gap of the device. Device
operations are the events of the device planes' ``XLA Ops`` lines.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

WINDOW = "bench.window"
OPS_LINES = ("XLA Ops",)


@dataclass
class Event:
    name: str
    start: float  # seconds on the trace's clock
    end: float


@dataclass
class Reduced:
    window: tuple[float, float]
    ops: list[Event]  # device operations, clipped to the window
    host: list[Event]  # the benchmark's host spans
    chips: int = 1

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_intervals(self) -> list[tuple[float, float]]:
        merged: list[list[float]] = []
        for e in sorted(self.ops, key=lambda e: e.start):
            if merged and e.start <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e.end)
            else:
                merged.append([e.start, e.end])
        return [(a, b) for a, b in merged]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(b - a for a, b in self.busy_intervals()) / max(self.chips, 1)

    def kernel_calls(self):
        """(event, output shape, operand shapes) of every Pallas kernel call."""
        out = []
        for e in self.ops:
            call = kernel_call(e)
            if call is not None:
                out.append((e, *call))
        return out

    def top_ops(self, n: int = 10) -> list[list]:
        by: dict[str, float] = {}
        for e in self.ops:
            key = op_family(e)
            if key in CONTAINERS:  # their bodies' operations are listed themselves
                continue
            by[key] = by.get(key, 0.0) + (e.end - e.start) / max(self.chips, 1)
        return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        t0, t1 = self.window
        edges = [t0] + [x for iv in self.busy_intervals() for x in iv] + [t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_label((a + b) / 2), b - a] for a, b in gaps[:n]]

    def host_label(self, t: float) -> str:
        inside = [e for e in self.host if e.start <= t <= e.end and e.name != WINDOW]
        if not inside:
            return "host:outside-bench-spans"
        return min(inside, key=lambda e: e.end - e.start).name


KERNEL = 'custom_call_target="tpu_custom_call"'
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
CONTAINERS = ("while", "conditional", "call")


def op_family(e: Event) -> str:
    """A stable name for grouping an operation: its HLO instruction name
    without the number (``%fusion.12 = …`` -> ``fusion``); a Pallas kernel,
    which carries no name of its own, by its output shape."""
    head = re.sub(r"[.]\d+$", "", e.name.split(" = ")[0].lstrip("%"))
    if KERNEL in e.name:
        out = _SHAPE.search(e.name.split(" = ", 1)[-1])
        return f"kernel {out.group(0) if out else head}"
    return head


def shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    return [(dt, tuple(int(x) for x in dims.split(",") if x)) for dt, dims in _SHAPE.findall(text)]


def kernel_call(e: Event):
    """(output shape, operand shapes) of a Pallas kernel event, read from
    its HLO text, or None for any other operation."""
    if KERNEL not in e.name or " = " not in e.name:
        return None
    rhs = e.name.split(" = ", 1)[1]
    out, _, rest = rhs.partition("custom-call(")
    args = rest.split("), custom_call_target", 1)[0]
    outs = shapes(out)
    return (outs[0] if outs else None), shapes(args)


def latest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: str) -> Reduced:
    """Read the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(latest_xplane(trace_dir))
    host, ops, chips = [], [], 0
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name in OPS_LINES:
                chips += 1  # a chip is a device plane with operations
                ops += [Event(ev.name, ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]
            elif not device:
                host += [Event(ev.name, ev.start_ns * 1e-9,
                               (ev.start_ns + ev.duration_ns) * 1e-9)
                         for ev in line.events if ev.name.startswith("bench.")]
    spans = [e for e in host if e.name == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW} span in the trace")
    w = (spans[-1].start, spans[-1].end)
    clipped = [Event(e.name, max(e.start, w[0]), min(e.end, w[1]))
               for e in ops if e.end > w[0] and e.start < w[1]]
    return Reduced(w, clipped, host, chips=max(chips, 1))
