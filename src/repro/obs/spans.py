"""Phase spans on the profiler's clock (DESIGN §13).

``span(name, **counts)`` is a ``jax.profiler.TraceAnnotation``: a host
span that lands in the same trace as the device's operations whenever a
profiler runs (``--profile-dir``, or a benchmark's traced window), and
costs about a microsecond when none does. Spans mark the phases of one
serving or training step, never a slot or a token. A count known only at
the end of a phase rides on its span through ``set_metadata``; counts come
back from ``jax.profiler.ProfileData`` as the event's ``stats``.

This is the one module of ``repro.obs`` that imports jax, and the one
place the program calls the profiler's annotation API. The request
``Tracer`` (``obs.trace``) is a separate, per-request lifecycle record on
the engine's own clock.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

# ServeEngine.step: the whole call, then its phases in order
SERVE_STEP = "serve.step"
SERVE_EXPIRE = "serve.expire"  # deadline sweep
SERVE_ADMIT = "serve.admit"  # adapter-revision check, queue -> slots
SERVE_RESERVE = "serve.reserve"  # paged pre-reservation, preemption
SERVE_PLAN = "serve.plan"  # chunk plan or slot arrays, tenant stacks
SERVE_PUT = "serve.put"  # host -> device uploads, block tables
SERVE_DISPATCH = "serve.dispatch"  # the jitted call returning
SERVE_FETCH = "serve.fetch"  # the step's one device -> host fetch
SERVE_EMIT = "serve.emit"  # replay into the request lifecycle
SERVE_GAUGES = "serve.gauges"

# Trainer.run: one step, then its phases in order
TRAIN_STEP = "train.step"
TRAIN_FEED = "train.feed"  # next(data_iter)
TRAIN_PUT = "train.put"  # the batch to the device
TRAIN_DISPATCH = "train.dispatch"
TRAIN_FETCH = "train.fetch"  # the step's one device -> host fetch
TRAIN_LOG = "train.log"  # history, NaN guard, stragglers, checkpoint


def span(name: str, **counts: int) -> TraceAnnotation:
    """A host span named ``name`` carrying ``counts``; use as ``with``."""
    return TraceAnnotation(name, **counts)
