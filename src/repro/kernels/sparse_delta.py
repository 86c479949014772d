"""Pallas TPU kernels for the NeuroAda bypass apply (paper Eq. 4, footnote 2).

Computes ``yΔ[m, o] = Σ_j val[j, o] · x[m, idx[j, o]]`` without
materialising the ``(M, k, d_out)`` gathered tensor the pure-jnp path
creates, and without a lane gather, which Mosaic cannot lower at these
widths. Each grid cell instead densifies the delta for one ``(bk, bn)``
source-row × output-column tile in VMEM — ``S[i, o] = Σ_j val[j, o] ·
[idx[j, o] == i]``, k compares per element on the VPU — and contracts the
``(bm, bk)`` activation tile with it on the MXU. The top-k indices of a
column are distinct, so ``S`` holds each value exactly and the product is
the gather, accumulated in f32. The dense delta exists one tile at a time
in VMEM, never in HBM: the paper's memory claim holds, while the MXU does
a dense matmul's worth of work for the bypass.

Grid (M/bm, N/bn, K/bk), the K axis accumulating in a VMEM f32 scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def pick_block(dim: int, cap: int = 512) -> int:
    """Largest of 512/256/128, at most ``cap``, that tiles ``dim``; ``dim``
    itself (a full block, always legal) when none does."""
    return next((s for s in (512, 256, 128) if s <= cap and dim % s == 0), dim)


def delta_tile(idx, val, k0, bk: int, dtype, stride: int = 1):
    """(bk, bn) dense delta for source rows ``k0 + stride·r``, r < bk.

    ``idx``/``val`` are the (k, bn) column tile of the bypass. Exact in
    ``dtype`` when ``val`` is: each column holds its k distinct entries."""
    rows = k0 + stride * jax.lax.broadcasted_iota(jnp.int32, (bk, idx.shape[1]), 0)
    s = jnp.zeros(rows.shape, jnp.float32)
    for j in range(idx.shape[0]):  # k is static and small (1..~32)
        s = s + jnp.where(rows == idx[j:j + 1], val[j:j + 1].astype(jnp.float32), 0.0)
    return s.astype(dtype)


def _delta_kernel(x_ref, idx_ref, val_ref, y_ref, acc_ref, *, bk: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # (bm, bk)
    s = delta_tile(idx_ref[...], val_ref[...], kk * bk, bk, x.dtype)
    acc_ref[...] += jnp.dot(x, s, preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _flush():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def sparse_delta_pallas(
    x: jax.Array,
    idx: jax.Array,
    val: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """x (M, d_in) · Delta(idx, val) (k, d_out) -> (M, d_out)."""
    m, d_in = x.shape
    k, d_out = idx.shape
    bm = min(block_m, m)
    bn = min(block_n, d_out)
    bk = pick_block(d_in)
    if m % bm or d_out % bn:
        raise ValueError(f"M={m}, d_out={d_out} must tile by ({bm}, {bn})")
    return pl.pallas_call(
        functools.partial(_delta_kernel, bk=bk),
        grid=(m // bm, d_out // bn, d_in // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((k, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((k, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, d_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="sparse_delta",
    )(x, idx, val)


def _delta_batched_kernel(x_ref, idx_ref, val_ref, aid_ref, y_ref, acc_ref, *, bk: int):
    """Per-row adapter selection: row m applies adapter aid[m]'s k bypasses.

    The adapter count N is static and small, so each cell contracts the
    activation tile with each adapter's delta tile and keeps the rows
    that adapter owns — no per-row dynamic gather."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # (bm, bk)
    idx = idx_ref[...]  # (n, k, bn) int32
    val = val_ref[...]  # (n, k, bn)
    aid = aid_ref[...]  # (bm, 1) int32
    acc = acc_ref[...]
    for a in range(idx.shape[0]):
        s = delta_tile(idx[a], val[a], kk * bk, bk, x.dtype)
        contrib = jnp.dot(x, s, preferred_element_type=jnp.float32)
        acc = acc + jnp.where(aid == a, contrib, 0.0)
    acc_ref[...] = acc

    @pl.when(kk == pl.num_programs(2) - 1)
    def _flush():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def sparse_delta_batched_pallas(
    x: jax.Array,
    idx: jax.Array,
    val: jax.Array,
    aid: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """x (M, d_in) · Delta-stack (N, k, d_out) selected by aid (M,) -> (M, d_out)."""
    m, d_in = x.shape
    n_ad, k, d_out = idx.shape
    bm = min(block_m, m)
    bn = min(block_n, d_out)
    bk = pick_block(d_in)
    if m % bm or d_out % bn:
        raise ValueError(f"M={m}, d_out={d_out} must tile by ({bm}, {bn})")
    return pl.pallas_call(
        functools.partial(_delta_batched_kernel, bk=bk),
        grid=(m // bm, d_out // bn, d_in // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((n_ad, k, bn), lambda i, j, kk: (0, 0, j)),
            pl.BlockSpec((n_ad, k, bn), lambda i, j, kk: (0, 0, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, d_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="sparse_delta_batched",
    )(x, idx, val, aid[:, None])


def _dval_kernel(x_ref, idx_ref, dy_ref, dval_ref, *, bk: int):
    """dval[j, o] = Σ_m dy[m, o] · x[m, idx[j, o]], accumulated over the
    K and M tiles: the gather is a one-hot contraction on the MXU."""
    kk, mm = pl.program_id(1), pl.program_id(2)

    @pl.when((kk == 0) & (mm == 0))
    def _init():
        dval_ref[...] = jnp.zeros_like(dval_ref)

    x = x_ref[...]  # (bm, bk)
    idx = idx_ref[...]  # (k, bn)
    dy = dy_ref[...].astype(jnp.float32)  # (bm, bn)
    rows = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, idx.shape[1]), 0)
    for j in range(idx.shape[0]):
        onehot = (rows == idx[j:j + 1]).astype(x.dtype)  # (bk, bn)
        xg = jnp.dot(x, onehot, preferred_element_type=jnp.float32)  # (bm, bn)
        dval_ref[j:j + 1, :] += jnp.sum(xg * dy, axis=0, keepdims=True)


def sparse_delta_dval_pallas(
    x: jax.Array,
    idx: jax.Array,
    dy: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Backward for val: (M,d_in),(k,d_out),(M,d_out) -> (k,d_out) f32."""
    m, d_in = x.shape
    k, d_out = idx.shape
    bm = min(block_m, m)
    bn = min(block_n, d_out)
    bk = pick_block(d_in)
    if m % bm or d_out % bn:
        raise ValueError(f"M={m}, d_out={d_out} must tile by ({bm}, {bn})")
    # n-parallel outer; the K and M reductions accumulate into the
    # resident (k, bn) output block
    return pl.pallas_call(
        functools.partial(_dval_kernel, bk=bk),
        grid=(d_out // bn, d_in // bk, m // bm),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, kk, i: (i, kk)),
            pl.BlockSpec((k, bn), lambda j, kk, i: (0, j)),
            pl.BlockSpec((bm, bn), lambda j, kk, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((k, bn), lambda j, kk, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((k, d_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="delta_dval",
    )(x, idx, dy)
