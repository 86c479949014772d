"""Fused dequant × matmul + NeuroAda sparse-delta Pallas kernel.

``y = dequant(Wq) @ x (+ bias) + Σ_j val[j,:]·x[:, idx[j,:]]`` in one pass:
each K-tile of the packed base weight is dequantized *in VMEM* — int8 codes
(or NF4 nibbles) × per-block scales — immediately before it feeds the MXU,
so the dense fp weight never exists in HBM. The bypass entries whose source
index falls inside the K-tile ride the same accumulator (a second MXU
product against the tile's densified delta), exactly like
``fused_linear.py``; the output tile is written once.

HBM traffic per (bm, bn) output tile drops from ``bk·bn·4`` bytes of fp32
weight to ``bk·bn`` (int8) or ``bk·bn/2 + scales`` (NF4) per K step — the
whole point of serving N tenants off one quantized base.

Grid: (M/bm parallel, N/bn parallel, K/bk sequential-accumulate). ``block``
(scale granularity) must divide ``bk`` so each K-tile owns whole scale rows;
the scales ride as a (K/bk, bk/block, N) view so their block spans its full
middle axis.

NF4 packs rows 2r and 2r+1 of a tile into the low and high nibble of one
byte. Re-interleaving them in VMEM is a sublane shuffle Mosaic does not
lower, so the wrapper splits ``x`` into its even and odd columns instead,
and each K step runs two half-height products:
``x_even @ W_lo + x_odd @ W_hi`` (and the bypass likewise). The codebook
lookup is a 16-way select over static code constants on the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_delta import delta_tile, pick_block
from repro.quant.qtensor import NF4_CODES


def _nf4_values(codes: jax.Array) -> jax.Array:
    wt = jnp.zeros(codes.shape, jnp.float32)
    for c, v in enumerate(NF4_CODES):  # 16 static selects on the VPU
        wt = jnp.where(codes == c, jnp.float32(v), wt)
    return wt


def _fused_q_kernel(*refs, bk: int, block: int, qdtype: str, has_bias: bool):
    nf4 = qdtype == "nf4"
    if nf4:
        xe_ref, xo_ref, data_ref, scales_ref, idx_ref, val_ref, b_ref, y_ref, acc_ref = refs
    else:
        x_ref, data_ref, scales_ref, idx_ref, val_ref, b_ref, y_ref, acc_ref = refs
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sc = scales_ref[0].astype(jnp.float32)  # (bk / block, bn)
    idx, val = idx_ref[...], val_ref[...]
    if nf4:
        data = data_ref[...].astype(jnp.int32)  # (bk/2, bn), two codes a byte
        s = jnp.repeat(sc, block // 2, axis=0)
        parts = (
            (xe_ref[...], data & 0xF, 0),
            (xo_ref[...], (data >> 4) & 0xF, 1),
        )
        parts = [(x, _nf4_values(c) * s, par) for x, c, par in parts]
        stride, rows = 2, bk // 2
    else:
        parts = [(x_ref[...], data_ref[...].astype(jnp.float32)
                  * jnp.repeat(sc, block, axis=0), 0)]
        stride, rows = 1, bk
    acc = acc_ref[...]
    for x, wt, par in parts:
        d = delta_tile(idx, val, kk * bk + par, rows, x.dtype, stride)
        acc += jnp.dot(
            x.astype(jnp.float32), wt, preferred_element_type=jnp.float32
        ) + jnp.dot(x, d, preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(kk == pl.num_programs(2) - 1)
    def _flush():
        out = acc_ref[...]
        if has_bias:
            out = out + b_ref[...].astype(jnp.float32)
        y_ref[...] = out.astype(y_ref.dtype)


def fused_linear_q_pallas(
    x: jax.Array,
    data: jax.Array,
    scales: jax.Array,
    idx: jax.Array,
    val: jax.Array,
    bias: jax.Array | None = None,
    *,
    qdtype: str = "int8",
    block: int = 64,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """x (M,K) × packed base (K,N) + delta(idx,val (k,N)) [+ bias] -> (M,N).

    ``data`` is int8 (K, N) or uint8 (K/2, N) NF4-packed; ``scales`` is
    (K/block, N) float32. Output dtype follows ``x``.
    """
    m, kdim = x.shape
    n = data.shape[-1]
    k = idx.shape[0]
    bm, bn, bk = min(block_m, m), min(block_n, n), pick_block(kdim, block_k)
    if bk % block:
        raise ValueError(f"K tile {bk} must be a multiple of scale block {block}")
    if m % bm or n % bn or kdim % bk:
        raise ValueError(f"shapes {(m, kdim, n)} must tile by {(bm, bk, bn)}")
    nf4 = qdtype == "nf4"
    packed_rows = bk // 2 if nf4 else bk
    has_bias = bias is not None
    b = (bias if has_bias else jnp.zeros((n,), x.dtype)).reshape(1, n)
    xs = (x[:, 0::2], x[:, 1::2]) if nf4 else (x,)
    x_spec = pl.BlockSpec((bm, packed_rows), lambda i, j, kk: (i, kk))
    return pl.pallas_call(
        functools.partial(
            _fused_q_kernel, bk=bk, block=block, qdtype=qdtype,
            has_bias=has_bias,
        ),
        grid=(m // bm, n // bn, kdim // bk),
        in_specs=[x_spec] * len(xs) + [
            pl.BlockSpec((packed_rows, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bk // block, bn), lambda i, j, kk: (kk, 0, j)),
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
    )(*xs, data, scales.reshape(kdim // bk, bk // block, n), idx, val, b)


# --------------------------------------------------- TP-sharded dispatch


def matmul_q_cols_sharded(x2d, qw, mesh, *, interpret: bool = False):
    """Column-sharded ``x @ dequant(Wq)`` for the vocab-sharded serving
    head: ``data`` and ``scales`` both carry d_out last, so they split
    over ``model`` together while the activation replicates. Each shard
    runs the fused dequant×matmul kernel (zero bypass) on its local
    column slice; the output stays vocab-sharded and the sampler's argmax
    triggers the GSPMD all-gather.

    Only the col-parallel case lives here: a row-parallel quant matmul
    would split d_in across scale-block boundaries and need an in-body
    psum — serving's quantized row-parallel weights take the fused path
    with their deltas instead, where GSPMD owns the layout.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.collectives import tp_shard_map
    from repro.kernels import ops

    meta = (qw.qdtype, qw.block, interpret)

    def body(x_l, d_l, s_l):
        n = d_l.shape[-1]
        idx = jnp.zeros((1, n), jnp.int32)
        val = jnp.zeros((1, n), x_l.dtype)
        return ops._fused_linear_q(meta, x_l, d_l, s_l, idx, val, None)

    col = P(None, "model")
    return tp_shard_map(
        body, mesh, in_specs=(P(None, None), col, col), out_specs=col
    )(x2d, qw.data, qw.scales)
