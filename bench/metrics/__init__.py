"""Per-layer metric readers, one module per metric, named as the metric.

Each module has ``read(ctx) -> float | None``. A reader that finds nothing
to read returns None and the metric is left out of the result line; a
share of a roofline or a peak is never reported as 0 for want of data.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass
class Context:
    cell: object  # bench.cells.base.Cell
    trace: object  # bench.trace.Reduced of the traced window
    peak: dict  # bench/peaks.json entry of the chip
    window_host_s: float = 0.0
    steps: object = None  # train: count of window steps; serve: step records
    step_flops: int = 0
    memory_peak_bytes: int = 0
