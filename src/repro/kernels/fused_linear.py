"""Fused base-matmul + NeuroAda delta Pallas kernel.

``y = x @ W (+ bias) + Σ_j val[j,:]·x[:, idx[j,:]]`` in a single pass: the
MXU computes the frozen matmul tile-by-tile over K, and each K-tile also
contributes the bypass entries whose source index falls inside it, as a
second MXU product against the tile's densified delta
(:func:`~repro.kernels.sparse_delta.delta_tile`). The output tile is
written once — versus the unfused path's extra HBM read of ``x`` and
read-modify-write of ``y``.

Grid: (M/bm parallel, N/bn parallel, K/bk sequential-accumulate in a VMEM
f32 scratch). ``bk`` is the largest of 512/256/128 (at most ``block_k``)
that tiles K.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_delta import delta_tile, pick_block


def _fused_kernel(x_ref, w_ref, idx_ref, val_ref, b_ref, y_ref, acc_ref, *, bk: int, has_bias: bool):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]  # (bm, bk)
    s = delta_tile(idx_ref[...], val_ref[...], kk * bk, bk, x.dtype)
    acc_ref[...] += jnp.dot(
        x, w_ref[...], preferred_element_type=jnp.float32
    ) + jnp.dot(x, s, preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _flush():
        out = acc_ref[...]
        if has_bias:
            out = out + b_ref[...].astype(jnp.float32)
        y_ref[...] = out.astype(y_ref.dtype)


def fused_linear_pallas(
    x: jax.Array,
    w: jax.Array,
    idx: jax.Array,
    val: jax.Array,
    bias: jax.Array | None = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """x (M,K) @ w (K,N) + delta(idx,val (k,N)) [+ bias (N,)] -> (M,N)."""
    m, kdim = x.shape
    kd2, n = w.shape
    assert kdim == kd2, (x.shape, w.shape)
    k = idx.shape[0]
    bm, bn, bk = min(block_m, m), min(block_n, n), pick_block(kdim, block_k)
    if m % bm or n % bn or kdim % bk:
        raise ValueError(f"shapes {(m, kdim, n)} must tile by {(bm, bk, bn)}")
    grid = (m // bm, n // bn, kdim // bk)
    has_bias = bias is not None
    # bias rides as a (1, N) row: a rank-1 block is under the chip's tiling
    b = (bias if has_bias else jnp.zeros((n,), x.dtype)).reshape(1, n)
    return pl.pallas_call(
        functools.partial(_fused_kernel, bk=bk, has_bias=has_bias),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((k, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((k, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, w, idx, val, b)
