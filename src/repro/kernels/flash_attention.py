"""Pallas TPU flash-attention forward kernel (fused online softmax).

The §Roofline analysis shows dense-train/prefill memory terms dominated by
the XLA flash *scan*'s f32 accumulator: (B,H,Sq,hd) doesn't fit VMEM, so
every KV-chunk step re-reads/re-writes it from HBM (nc sweeps per layer).
This kernel is the structural fix: grid over (batch·head, q-block), KV
swept in the innermost grid dim while (m, l, acc) live in VMEM scratch —
q/k/v are each read from HBM exactly once and the output written once.

Target: TPU MXU (q-block × kv-block matmuls, 128-aligned). Validated in
interpret mode vs models/attention.dense_attention (tests/kernels). The
causal variant masks per-tile with broadcasted iotas; fully-masked tiles
cost compute but no extra HBM (skipping them needs a dynamic grid — noted
as future work in EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, causal: bool,
    bq: int, bk: int, scale: float,
):
    kv_step = pl.program_id(2)

    @pl.when(kv_step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)  # (bk, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)
    if causal:
        q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0
        )
        k_pos = kv_step * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if causal:
        p = jnp.where(q_pos >= k_pos, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kv_step == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def flash_attention_fwd_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q (BH, Sq, hd); k, v (BH, Skv, hd) — heads pre-folded into batch.

    Each (batch·head, q-block) grid cell holds its (m, l, acc) in VMEM for
    the whole KV sweep. VMEM/cell ≈ bq·(hd·4·2 + bk·… ) ≪ 16 MB at 128².
    """
    bh, sq, hd = q.shape
    skv = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    if sq % bq or skv % bk:
        raise ValueError(f"Sq={sq}, Skv={skv} must tile by ({bq}, {bk})")
    grid = (bh, sq // bq, skv // bk)
    scale = hd**-0.5
    return pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, causal=causal, bq=bq, bk=bk, scale=scale
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, hd), jnp.float32),  # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_attn_fwd",
    )(q, k, v)


def flash_attention_gqa_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Model-layout wrapper: q (B,S,H,hd), k/v (B,S,Hkv,hd) — GQA heads are
    expanded by indexing k/v per q-head group (no materialised repeat on
    TPU: the BH fold makes each head an independent grid row)."""
    b, sq, h, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = (
        jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, skv, hd)
    )
    vf = (
        jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, skv, hd)
    )
    o = flash_attention_fwd_pallas(
        qf, kf, vf, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return o.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
