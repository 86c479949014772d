"""Pallas TPU paged prefill-attention kernel (query chunk × block-pool KV).

Chunked prefill (DESIGN §11) feeds the serving step a per-slot *query
chunk*: up to ``C`` prompt tokens whose k/v were just written into the
slot's paged blocks, attending over everything the slot has cached so
far — prior chunks AND the in-chunk causal prefix. The stop-the-world
prefill this replaces ran a dense ``(B, S_bucket, S_bucket)`` causal
softmax per pow2 bucket; this kernel is the paged, bounded-latency
version: grid ``(slot, kv-head, page)``, the chunk's GQA queries ride as
a ``(C·G, hd)`` register tile against each ``(page_size, hd)`` KV page,
and the online-softmax state ``(m, l, acc)`` accumulates in f32 VMEM
scratch across the page sweep — each cached byte is read from HBM once
per chunk.

Per-slot scalars ride in as *scalar-prefetch* operands so the k/v
BlockSpec index maps can aim each page's DMA at its physical block
before the body runs:

* ``table``        (B, n_pages) — logical page → physical block
  (out-of-range sentinel = unallocated; clamped in the wrapper, always
  masked because the engine never lets ``kv_valid_len`` cross an
  unallocated page);
* ``q_offset``     (B,) — the chunk's first logical position (slots sit
  at different prefill/decode frontiers, so masking is per-slot);
* ``kv_valid_len`` (B,) — the slot's cache frontier *after* the chunk's
  writes (``q_offset + q_len``).

Masking is two-sided: column ``c`` is visible to query ``i`` iff
``c <= q_offset + i`` (intra-chunk causality — query ``i`` sits at
logical position ``q_offset + i``) and ``c < kv_valid_len`` (pad queries
``i >= q_len`` of a short chunk attend only real cache; their rows are
discarded downstream). A decode slot in the mixed batch is just the
degenerate chunk ``q_len = 1``: the mask collapses to the §10 decode
kernel's frontier mask.

VMEM per cell: ``page·hd·8`` B (k/v pages in f32) + ``C·G·(hd + page)·4``
B (q tile + scores) + scratch ``C·G·(hd + 2)·4`` B — ≈ 600 KB at
``C=64, G=4, hd=128, page=16``, far under the 16 MB budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (
    SEMANTICS,
    lane_view,
    softmax_flush,
    softmax_init,
    softmax_scratch,
    softmax_step,
)

def _paged_prefill_attn_kernel(
    table_ref, qoff_ref, vl_ref, *refs, page: int, g: int, scale: float,
    quant: bool, n_pages: int, hkv: int,
):
    """Grid cell (slot, kv-head, page); int8 pools bring their flat
    per-(block, kv-head) scales beside the table (DESIGN §15)."""
    if quant:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    slot, h_, p_step = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(p_step == 0)
    def _init():
        softmax_init(m_ref, l_ref, acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (C·G, hd)
    cg = q.shape[0]
    # columns are *logical* positions; rows fold (query, group): row r is
    # query r // g, so its causal frontier is q_offset + r // g
    col = p_step * page + jax.lax.broadcasted_iota(jnp.int32, (cg, page), 1)
    qpos = qoff_ref[slot] + jax.lax.broadcasted_iota(
        jnp.int32, (cg, page), 0
    ) // g
    valid = (col <= qpos) & (col < vl_ref[slot])
    ks = vs = None
    if quant:
        sc = table_ref[slot * n_pages + p_step] * hkv + h_
        ks, vs = ks_ref[sc], vs_ref[sc]
    softmax_step(
        q, k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32), valid,
        m_ref, l_ref, acc_ref, scale=scale, ks=ks, vs=vs,
    )

    @pl.when(p_step == pl.num_programs(2) - 1)
    def _flush():
        softmax_flush(o_ref, l_ref, acc_ref)


def paged_prefill_attention_pallas(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    table: jax.Array,
    q_offset,
    kv_valid_len,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Chunked-prefill GQA attention against a paged block pool.

    q (B, C, H, hd); k_pool, v_pool (N, P, Hkv, hd); table (B, n_pages)
    int32 (out-of-range = unallocated, clamped here — such pages always
    sit past ``kv_valid_len``); q_offset, kv_valid_len scalar or (B,).
    Query ``i`` of slot ``b`` sees column ``c`` iff
    ``c <= q_offset[b] + i`` and ``c < kv_valid_len[b]``. Returns
    (B, C, H, hd); rows ``i >= q_len`` are well-defined but meaningless
    (the caller discards them).
    """
    b, c, h, hd = q.shape
    n, page, hkv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    if h % hkv:
        raise ValueError(f"H={h} must be a multiple of Hkv={hkv}")
    if table.shape[0] != b:
        raise ValueError(f"table rows {table.shape[0]} != batch {b}")
    g = h // hkv
    n_pages = table.shape[1]
    qoff = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1), (b,))
    vl = jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32).reshape(-1), (b,))
    tbl = jnp.minimum(table.astype(jnp.int32), n - 1).reshape(-1)
    # fold (query, group) into one row axis: (B, Hkv, C·G, hd)
    qg = q.reshape(b, c, hkv, g, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, hkv, c * g, hd)
    quant = k_scale is not None
    prefetch = (tbl, qoff, vl)
    if quant:
        prefetch += (k_scale.reshape(-1), v_scale.reshape(-1))

    def kv_map(b_, h_, p_, table_ref, *_):
        return (table_ref[b_ * n_pages + p_], 0, h_)

    q_spec = pl.BlockSpec((1, 1, c * g, hd), lambda b_, h_, p_, *_: (b_, h_, 0, 0))
    kv_spec = pl.BlockSpec((1, page, hd), kv_map)
    out = pl.pallas_call(
        functools.partial(
            _paged_prefill_attn_kernel, page=page, g=g, scale=hd**-0.5,
            quant=quant, n_pages=n_pages, hkv=hkv,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, hkv, n_pages),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=softmax_scratch(c * g, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, c * g, hd), q.dtype),
        compiler_params=SEMANTICS,
        interpret=interpret,
        name="paged_prefill_attn",
    )(*prefetch, qg, lane_view(k_pool), lane_view(v_pool))
    out = out.reshape(b, hkv, c, g, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, c, h, hd)


# --------------------------------------------------- TP-sharded dispatch


def paged_prefill_attention_sharded(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array, table: jax.Array,
    q_offset, kv_valid_len, mesh,
    *, k_scale: jax.Array | None = None, v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Tensor-parallel dispatch of :func:`paged_prefill_attention_pallas`.

    Same partition as the decode twin: the (B, C, H, hd) query chunk
    splits along H (group-major, so head h's kv-head h // G lands on the
    same shard), the pool along its kv-head axis; table / q_offset /
    kv_valid_len replicate as scalar-prefetch operands. Each shard runs
    the identical page-sweep grid on its slice and the o-proj's
    row-parallel psum merges the head outputs downstream.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.collectives import tp_shard_map

    qo = jnp.broadcast_to(jnp.asarray(q_offset), (q.shape[0],))
    vl = jnp.broadcast_to(jnp.asarray(kv_valid_len), (q.shape[0],))
    h = P(None, None, "model", None)
    pool = P(None, None, "model", None)

    if k_scale is not None:
        def body_q(q_l, k_l, v_l, t_l, qo_l, vl_l, ks_l, vs_l):
            return paged_prefill_attention_pallas(
                q_l, k_l, v_l, t_l, qo_l, vl_l,
                k_scale=ks_l, v_scale=vs_l, interpret=interpret,
            )

        sc = P(None, "model")
        return tp_shard_map(
            body_q, mesh,
            in_specs=(
                h, pool, pool, P(None, None), P(None), P(None), sc, sc
            ),
            out_specs=h,
        )(q, k_pool, v_pool, table, qo, vl, k_scale, v_scale)

    def body(q_l, k_l, v_l, t_l, qo_l, vl_l):
        return paged_prefill_attention_pallas(
            q_l, k_l, v_l, t_l, qo_l, vl_l, interpret=interpret
        )

    return tp_shard_map(
        body, mesh,
        in_specs=(h, pool, pool, P(None, None), P(None), P(None)),
        out_specs=h,
    )(q, k_pool, v_pool, table, qo, vl)
