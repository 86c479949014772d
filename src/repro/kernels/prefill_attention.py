"""Pallas TPU paged prefill-attention kernel (query chunk × block-pool KV).

Chunked prefill (DESIGN §11) feeds the serving step a per-slot *query
chunk*: up to ``C`` prompt tokens whose k/v were just written into the
slot's paged blocks, attending over everything the slot has cached so
far — prior chunks AND the in-chunk causal prefix. A decode slot in the
mixed batch is the degenerate chunk ``q_len = 1``, an idle or stalled
slot the empty chunk ``q_len = 0``.

The work is ragged: it follows each slot's real query rows and live KV
positions, not the call's ``(B, C)`` shape. The grid is
``(slot, q-tile)``; a q-tile is ``TQ`` queries of the chunk with their
GQA group folded in, a ``(TQ·G, hd)`` row tile per kv head (``TQ`` a
multiple of 8 near ``128 / G``, dividing ``C``: 16 queries, 96 rows at
G = 6). A tile whose first query lies at or past ``q_len`` does no work
and writes zeros, and its q index map clamps to the slot's last live
tile, so it fetches nothing. A live tile sweeps the slot's pages in a
``fori_loop`` of KV blocks, each ``128 // page`` pages (128 lane-dense
score columns at page 16), up to ``min(kv_valid_len, q_offset +
tile_end)``: the frontier and the tile's causal bound. Pages come from
the pools, left in HBM, by double-buffered async copies routed through
the block table, one copy per page holding every kv head's lanes; each
block is one online-softmax update of each head's f32 ``(m, l, acc)``.

Per-slot scalars ride in as *scalar-prefetch* operands (SMEM):

* ``table``        (B, n_pages) flattened — logical page → physical
  block (out-of-range sentinel = unallocated; clamped in the wrapper,
  always masked because the engine never lets ``kv_valid_len`` cross an
  unallocated page);
* ``q_offset``     (B,) — the chunk's first logical position;
* ``kv_valid_len`` (B,) — the slot's cache frontier *after* the chunk's
  writes, so ``q_len = kv_valid_len − q_offset``.

Masking is two-sided: column ``c`` is visible to query ``i`` iff
``c <= q_offset + i`` (intra-chunk causality) and ``c < kv_valid_len``.
Rows of queries ``i >= q_len`` come out as zeros, finite whatever the
VMEM held: the caller carries them through ``wo`` and the MLP before it
discards them.

Precision: q·kᵀ takes q and k as they are when both share a dtype (a
bf16 product is exact in f32), else both in f32, always accumulating in
f32; scores, probabilities and the accumulator are f32, and ``p`` enters
the P·V dot in f32 at the default dot precision, as the page-sweep
kernel this replaced did. On a TPU v5e that precision passes f32
operands through the MXU as bf16 (relative error about 2e-3 against
1e-7 at ``Precision.HIGHEST``, which costs this kernel a tenth more time).
int8 pools dequantize each page by its per-(block, kv-head) scales,
applied to the scores and probabilities.

VMEM per cell at qwen2-1.5b (C 256, G 6, Hkv 2, hd 128, page 16): the q
and output tiles double-buffered ``4·2·96·128·2`` B, two KV buffers of
two blocks ``2·2·128·256·2`` B, the f32 accumulators ``2·96·128·4`` B and
the (96, 1) statistics, lane-padded — under 1 MB, far inside the 16 MB
budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import lane_view, softmax_init, softmax_step

_LANES = 128


def _tiles(c: int, g: int, page: int, n_pages: int) -> tuple[int, int]:
    """(TQ, pages per KV block) from the shapes: TQ is the largest
    multiple of 8 that divides ``c`` with ``TQ·g <= 128`` rows (at least
    8), else the whole chunk; a KV block spans 128 columns."""
    tq = next((t for t in range(max(8, _LANES // g // 8 * 8), 0, -8) if c % t == 0), c)
    return tq, max(1, min(_LANES // page, n_pages))


def _paged_prefill_attn_kernel(
    table_ref, qoff_ref, vl_ref, *refs, page: int, g: int, tq: int, ppb: int,
    scale: float, quant: bool, n_pages: int, hkv: int, hd: int,
):
    """Grid cell (slot, q-tile), every kv head; int8 pools bring their
    flat per-(block, kv-head) scales beside the table (DESIGN §15)."""
    if quant:
        ks_ref, vs_ref, *refs = refs
    q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref = refs
    slot, t = pl.program_id(0), pl.program_id(1)
    qoff, vl = qoff_ref[slot], vl_ref[slot]
    q0 = t * tq                                  # the tile's first query
    rows, bk = tq * g, ppb * page

    @pl.when(q0 >= vl - qoff)
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)

    def phys(blk, j):
        return table_ref[slot * n_pages + jnp.minimum(blk * ppb + j, n_pages - 1)]

    def copies(blk, buf):
        """The block's page copies, all kv heads' lanes of each page; every
        page of a block is fetched (clamped to the table) so no column
        reads stale VMEM."""
        return [
            pltpu.make_async_copy(src.at[phys(blk, j)], dst.at[buf, j], sem.at[buf])
            for j in range(ppb) for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf))
        ]

    @pl.when(q0 < vl - qoff)
    def _sweep():
        # columns this tile can see: the frontier and its causal bound
        n_blk = pl.cdiv(jnp.minimum(vl, qoff + q0 + tq), bk)
        for cp in copies(0, 0):
            cp.start()
        softmax_init(m_ref, l_ref, acc_ref)
        # row r is query q0 + r // g at logical position qoff + q0 + r // g
        qpos = qoff + q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0) // g
        cdt = q_ref.dtype if q_ref.dtype == kbuf.dtype else jnp.float32  # int8: f32

        def body(blk, carry):
            buf = blk % 2

            @pl.when(blk + 1 < n_blk)
            def _prefetch():
                for cp in copies(blk + 1, 1 - buf):
                    cp.start()

            for cp in copies(blk, buf):
                cp.wait()
            col = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
            valid = (col <= qpos) & (col < vl)
            for h_ in range(hkv):
                lanes = slice(h_ * hd, (h_ + 1) * hd)
                # (ppb, page, hd) -> (bk, hd) columns of this head
                kb = kbuf[buf, :, :, lanes].astype(cdt).reshape(bk, hd)
                vb = vbuf[buf, :, :, lanes].astype(jnp.float32).reshape(bk, hd)
                ks = vs = None
                if quant:
                    pg = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) // page
                    ks = jnp.zeros((1, bk), jnp.float32)
                    vs = jnp.zeros((1, bk), jnp.float32)
                    for j in range(ppb):
                        sc = phys(blk, j) * hkv + h_
                        ks = jnp.where(pg == j, ks_ref[sc], ks)
                        vs = jnp.where(pg == j, vs_ref[sc], vs)
                # one online-softmax update of this head's (m, l, acc) per block
                softmax_step(q_ref[0, h_].astype(cdt), kb, vb, valid, m_ref.at[h_],
                             l_ref.at[h_], acc_ref.at[h_], scale=scale, ks=ks, vs=vs)
            return carry

        jax.lax.fori_loop(0, n_blk, body, 0)
        real = q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 0) // g < vl - qoff
        for h_ in range(hkv):
            out = acc_ref[h_] / jnp.maximum(l_ref[h_], 1e-30)
            o_ref[0, h_] = jnp.where(real, out, 0.0).astype(o_ref.dtype)


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
    (B, C, H, hd); rows ``i >= q_len = kv_valid_len − q_offset`` are zero.
    """
    b, c, h, hd = q.shape
    n, page, hkv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    if h % hkv:
        raise ValueError(f"H={h} must be a multiple of Hkv={hkv}")
    if table.shape[0] != b:
        raise ValueError(f"table rows {table.shape[0]} != batch {b}")
    g = h // hkv
    n_pages = table.shape[1]
    tq, ppb = _tiles(c, g, page, n_pages)
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

    def q_map(b_, t_, tbl_ref, qoff_ref, vl_ref, *_):
        # past the slot's last live tile: stay on it, so no fetch is issued
        last = jnp.maximum(pl.cdiv(vl_ref[b_] - qoff_ref[b_], tq) - 1, 0)
        return (b_, 0, jnp.minimum(t_, last), 0)

    rows = tq * g
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    kv_buf = pltpu.VMEM((2, ppb, page, hkv * hd), k_pool.dtype)
    out = pl.pallas_call(
        functools.partial(
            _paged_prefill_attn_kernel, page=page, g=g, tq=tq, ppb=ppb,
            scale=hd**-0.5, quant=quant, n_pages=n_pages, hkv=hkv, hd=hd,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, c // tq),
            in_specs=[pl.BlockSpec((1, hkv, rows, hd), q_map), pool_spec, pool_spec],
            out_specs=pl.BlockSpec((1, hkv, rows, hd), lambda b_, t_, *_: (b_, 0, t_, 0)),
            scratch_shapes=[
                kv_buf, kv_buf, pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hkv, rows, 1), jnp.float32),    # running max
                pltpu.VMEM((hkv, rows, 1), jnp.float32),    # running denom
                pltpu.VMEM((hkv, rows, hd), jnp.float32),   # f32 accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, c * g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
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
    the same ragged sweep on its slice and the o-proj's
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
