"""Production meshes. A FUNCTION, not a module constant — importing this
module never touches jax device state (device count is locked at first
jax init, and only dryrun.py is allowed to fake 512 devices).

Single pod: (data=16, model=16) = 256 chips (v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is a
second data-parallel tier (grad all-reduce crosses DCI), proving the specs
shard coherently across pods.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules and the
    vocab-sharded ``jnp.take`` leave placement to GSPMD, which the
    installed JAX's default (explicit axes) refuses."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist (tests / CPU examples): 1-D data mesh."""
    n = jax.device_count()
    return make_mesh((n,), ("data",))


def make_serve_mesh(tp: int):
    """Serving mesh with a ``model`` axis of size ``tp`` over the local
    devices: ``("model",)`` when TP consumes every device, else
    ``("data", "model")`` with the spare devices on a leading data axis
    (replica room for a future data-parallel serving tier; today's
    engine only populates the model axis).

    Raises ``ValueError`` up front when ``tp`` does not divide the device
    count — the serving launcher turns that into a readable SystemExit
    instead of a GSPMD error three layers down."""
    n = jax.device_count()
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if n % tp:
        raise ValueError(f"tp={tp} does not divide the {n} local devices")
    if tp == n:
        return make_mesh((tp,), ("model",))
    return make_mesh((n // tp, tp), ("data", "model"))


# TPU v5e structural constants for the roofline (DESIGN.md §5).
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (~per direction)
