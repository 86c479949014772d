"""Shared arithmetic of the kernel roofline readers."""

from __future__ import annotations

from bench.costs.kernels import roofline_seconds


def share(ctx, cost_of_call) -> float | None:
    """Roofline share of one kernel over the traced window: the least time
    of its calls over their device time. ``cost_of_call(out, args)`` gives
    (operations, bytes) of a kernel call from its output and operand
    shapes, or None for another kernel's call."""
    if ctx.trace is None:
        return None
    least = seconds = 0.0
    for event, out, args in ctx.trace.kernel_calls():
        cost = cost_of_call(out, args)
        if cost is not None:
            least += roofline_seconds(*cost, ctx.peak)[0]
            seconds += event.end - event.start
    return 100.0 * least / seconds if seconds > 0 else None
