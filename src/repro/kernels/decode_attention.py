"""Pallas TPU batched decode-attention kernels (Sq = 1, per-slot valid len).

The serving decode hot path previously ran ``dense_attention`` over the
full ``(B, max_len)`` cache with a masked softmax: every step materialises
a ``(B, KV, G, 1, max_len)`` score tensor in f32 and re-reads the whole
cache through XLA's generic einsum. This kernel is the roofline-shaped
replacement: grid over (slot, kv-head), the GQA group rides as a
``(G, hd)`` register tile against each ``(block_s, hd)`` KV chunk, and the
online-softmax state ``(m, l, acc)`` lives in VMEM scratch in f32 for the
whole sweep — each cache byte is read from HBM exactly once per step.

Per-slot ``kv_valid_len`` masks the tail of the cache (continuous batching
slots sit at different positions), so one compiled kernel serves every
slot mix. VMEM per cell: ``block_s·hd·(2·4)B`` (k/v chunks in f32) +
``G·(hd+block_s)·4B`` + scratch ``G·(hd+2)·4B`` — ≈ 140 KB at
``block_s=128, hd=128, G=8``, far under the 16 MB budget, leaving the
pipeline room to double-buffer the KV chunk DMA.

:func:`paged_decode_attention_pallas` is the block-table variant for the
paged serving core (DESIGN §10): the KV arrays are a shared block *pool*
``(num_blocks, page_size, Hkv, hd)`` and each slot's logical pages route
through a ``(B, n_pages)`` block table. The table (and the per-slot valid
lengths) ride in as scalar-prefetch operands so the k/v BlockSpec index
maps can compute the physical page DMA source *before* the body runs —
the grid is (slot, kv-head, page) and the page dimension accumulates the
same online-softmax scratch as the dense-slot kernel. Sentinel table
entries (unallocated pages) clamp to a resident block; their columns sit
past the slot's frontier and mask to zero.

Layout for the chip's (8, 128) tiling: KV is viewed as ``(…, Hkv·hd)``
(:func:`lane_view`), so a block takes one kv head as ``hd`` = 128 lanes
instead of a width-1 slice of the Hkv axis; per-slot valid lengths, the
(flattened) block table and int8 scales ride in SMEM as scalar-prefetch
operands. The online-softmax update is shared with the prefill kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def lane_view(kv: jax.Array) -> jax.Array:
    """(…, Hkv, hd) -> (…, Hkv·hd): a free reshape that lets a KV block be
    ``(…, rows, hd)`` at lane offset ``h·hd``. A block of width 1 on the
    kv-head axis would sit under the chip's (8, 128) tiling."""
    return kv.reshape(*kv.shape[:-2], kv.shape[-2] * kv.shape[-1])


def softmax_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def softmax_step(q, kb, vb, valid, m_ref, l_ref, acc_ref, *, scale, ks=None, vs=None):
    """One online-softmax update of the f32 (m, l, acc) scratch.

    q (R, hd) f32 against a (cols, hd) KV tile; ``valid`` (R, cols) masks
    columns. ``ks``/``vs`` are int8-cache dequant scales that broadcast
    against the (R, cols) scores — a scalar per page or a (1, cols) row —
    applied to the scores and to the probabilities before the PV product,
    which is the same as dequantizing the tile."""
    s = jax.lax.dot_general(
        q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if ks is not None:
        s = s * ks
    s = jnp.where(valid, s, _NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if vs is not None:
        p = p * vs
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new


def softmax_flush(o_ref, l_ref, acc_ref):
    o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def softmax_scratch(rows: int, hd: int):
    return [
        pltpu.VMEM((rows, 1), jnp.float32),    # running max
        pltpu.VMEM((rows, 1), jnp.float32),    # running denom
        pltpu.VMEM((rows, hd), jnp.float32),   # f32 accumulator
    ]


SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _decode_attn_kernel(
    vl_ref, *refs, block_s: int, scale: float, quant: bool, group: int,
    n_groups: int, hkv: int,
):
    """Grid cell (slot, kv-head, KV chunk). int8 caches bring their
    per-(slot, row group, kv-head) scales as flat scalar-prefetch
    operands; each chunk expands them to one (1, block_s) row for the
    scores and one for the probabilities."""
    if quant:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    slot, h_, s_step = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(s_step == 0)
    def _init():
        softmax_init(m_ref, l_ref, acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, hd)
    g = q.shape[0]
    col = s_step * block_s + jax.lax.broadcasted_iota(jnp.int32, (g, block_s), 1)
    valid = col < vl_ref[slot]                   # per-slot cache frontier
    ks = vs = None
    if quant:
        grp = jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1) // group
        base = (slot * n_groups + s_step * (block_s // group)) * hkv + h_
        ks = jnp.zeros((1, block_s), jnp.float32)
        vs = jnp.zeros((1, block_s), jnp.float32)
        for gi in range(block_s // group):
            ks = jnp.where(grp == gi, ks_ref[base + gi * hkv], ks)
            vs = jnp.where(grp == gi, vs_ref[base + gi * hkv], vs)
    softmax_step(
        q, k_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32), valid,
        m_ref, l_ref, acc_ref, scale=scale, ks=ks, vs=vs,
    )

    @pl.when(s_step == pl.num_programs(2) - 1)
    def _flush():
        softmax_flush(o_ref, l_ref, acc_ref)


def decode_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_valid_len,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    block_s: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Single-token GQA attention against a slot cache.

    q (B, 1, H, hd); k, v (B, Smax, Hkv, hd); kv_valid_len scalar or (B,)
    int — positions ``>= kv_valid_len[b]`` are masked out. Returns
    (B, 1, H, hd). Smax is padded up to a ``block_s`` multiple here (pad
    columns are always masked: ``kv_valid_len <= Smax``).

    With ``k_scale``/``v_scale`` (B, Smax // group, Hkv) the cache is int8
    and each KV tile is dequantized in VMEM against its scale rows; Smax
    must then be a whole number of scale groups (``init_cache`` rounds it
    up) so the KV block never straddles a partial group.
    """
    b, sq, h, hd = q.shape
    if sq != 1:
        raise ValueError(f"decode attention needs Sq=1, got {sq}")
    skv, hkv = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"H={h} must be a multiple of Hkv={hkv}")
    g = h // hkv
    vl = jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32).reshape(-1), (b,))
    quant = k_scale is not None
    group = skv // k_scale.shape[1] if quant else 1
    if quant and group * k_scale.shape[1] != skv:
        raise ValueError(f"Smax={skv} not a whole number of scale groups")
    bs = min(block_s, skv)
    if quant and bs % group:
        raise ValueError(f"KV block {bs} not a multiple of scale group {group}")
    pad = (-skv) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if quant:
            gpad = (skv + pad) // group - k_scale.shape[1]
            k_scale = jnp.pad(k_scale, ((0, 0), (0, gpad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, gpad), (0, 0)))
    prefetch = (vl,)
    if quant:
        prefetch += (k_scale.reshape(-1), v_scale.reshape(-1))
    kv_spec = pl.BlockSpec((1, bs, hd), lambda b_, h_, s_, *_: (b_, s_, h_))
    q_spec = pl.BlockSpec((1, 1, g, hd), lambda b_, h_, s_, *_: (b_, h_, 0, 0))
    out = pl.pallas_call(
        functools.partial(
            _decode_attn_kernel, block_s=bs, scale=hd**-0.5, quant=quant,
            group=group, n_groups=(skv + pad) // group, hkv=hkv,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, hkv, (skv + pad) // bs),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=softmax_scratch(g, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        compiler_params=SEMANTICS,
        interpret=interpret,
    )(*prefetch, q.reshape(b, hkv, g, hd), lane_view(k), lane_view(v))
    return out.reshape(b, 1, h, hd)


# ----------------------------------------------------------- paged variant


def _paged_decode_attn_kernel(
    table_ref, vl_ref, *refs, page: int, scale: float, quant: bool,
    n_pages: int, hkv: int,
):
    """Grid cell (slot, kv-head, page). int8 pools bring their flat
    per-(block, kv-head) scales beside the table: the body resolves this
    cell's scale with the same table lookup the DMA index map used."""
    if quant:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    slot, h_, p_step = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(p_step == 0)
    def _init():
        softmax_init(m_ref, l_ref, acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # (G, hd)
    g = q.shape[0]
    # columns are *logical* positions: page index × page size + offset —
    # the physical block the data came from is irrelevant to masking
    col = p_step * page + jax.lax.broadcasted_iota(jnp.int32, (g, page), 1)
    valid = col < vl_ref[slot]                   # per-slot cache frontier
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


def paged_decode_attention_pallas(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    table: jax.Array,
    kv_valid_len,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Single-token GQA attention against a paged block pool.

    q (B, 1, H, hd); k_pool, v_pool (N, P, Hkv, hd); table (B, n_pages)
    int32 mapping each slot's logical pages to physical blocks
    (out-of-range entries = unallocated, clamped — always masked because
    reservation keeps ``kv_valid_len`` within allocated pages);
    kv_valid_len scalar or (B,). Returns (B, 1, H, hd).

    Grid (slot, kv-head, page): the block table (flattened) is a
    scalar-prefetch operand, so the k/v index maps resolve the *physical*
    block for each (slot, page) cell ahead of the DMA — the pool is never
    gathered into a contiguous per-slot cache. With ``k_scale``/``v_scale``
    (N, Hkv) the pools are int8: the scales prefetch alongside the table
    and each page tile dequantizes in VMEM (DESIGN §15).
    """
    b, sq, h, hd = q.shape
    if sq != 1:
        raise ValueError(f"decode attention needs Sq=1, got {sq}")
    n, page, hkv = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    if h % hkv:
        raise ValueError(f"H={h} must be a multiple of Hkv={hkv}")
    if table.shape[0] != b:
        raise ValueError(f"table rows {table.shape[0]} != batch {b}")
    g = h // hkv
    n_pages = table.shape[1]
    vl = jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32).reshape(-1), (b,))
    # clamp the sentinel in the wrapper: index maps must name a resident
    # block, and clamped pages lie past the frontier anyway
    tbl = jnp.minimum(table.astype(jnp.int32), n - 1).reshape(-1)
    quant = k_scale is not None
    prefetch = (tbl, vl)
    if quant:
        prefetch += (k_scale.reshape(-1), v_scale.reshape(-1))

    def kv_map(b_, h_, p_, table_ref, *_):
        return (table_ref[b_ * n_pages + p_], 0, h_)

    q_spec = pl.BlockSpec((1, 1, g, hd), lambda b_, h_, p_, *_: (b_, h_, 0, 0))
    kv_spec = pl.BlockSpec((1, page, hd), kv_map)
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_attn_kernel, page=page, scale=hd**-0.5, quant=quant,
            n_pages=n_pages, hkv=hkv,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, hkv, n_pages),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=softmax_scratch(g, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        compiler_params=SEMANTICS,
        interpret=interpret,
    )(*prefetch, q.reshape(b, hkv, g, hd), lane_view(k_pool), lane_view(v_pool))
    return out.reshape(b, 1, h, hd)


# --------------------------------------------------- TP-sharded dispatch


def decode_attention_sharded(
    q: jax.Array, k: jax.Array, v: jax.Array, kv_valid_len, mesh,
    *, k_scale: jax.Array | None = None, v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Tensor-parallel dispatch of :func:`decode_attention_pallas`.

    The kernel grid is (slot, kv-head, KV-chunk) — kv-heads are embarrassingly
    parallel — so each TP shard runs the SAME kernel on its local kv-head
    slice of q and the cache (q heads group-major: head h serves kv-head
    h // G, so the (B, 1, H, hd) query splits along H exactly like the
    cache splits along Hkv). Output stays head-sharded; the row-parallel
    o-proj psum right after absorbs the merge, so no collective runs here.
    Quantized-cache scales (B, groups, Hkv) split along their trailing
    kv-head axis, riding the same partition as the pool they describe.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.collectives import tp_shard_map

    vl = jnp.broadcast_to(jnp.asarray(kv_valid_len), (q.shape[0],))
    h = P(None, None, "model", None)

    if k_scale is not None:
        def body_q(q_l, k_l, v_l, vl_l, ks_l, vs_l):
            return decode_attention_pallas(
                q_l, k_l, v_l, vl_l, k_scale=ks_l, v_scale=vs_l,
                interpret=interpret,
            )

        sc = P(None, None, "model")
        return tp_shard_map(
            body_q, mesh, in_specs=(h, h, h, P(None), sc, sc), out_specs=h
        )(q, k, v, vl, k_scale, v_scale)

    def body(q_l, k_l, v_l, vl_l):
        return decode_attention_pallas(q_l, k_l, v_l, vl_l, interpret=interpret)

    return tp_shard_map(
        body, mesh, in_specs=(h, h, h, P(None)), out_specs=h
    )(q, k, v, vl)


def paged_decode_attention_sharded(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array, table: jax.Array,
    kv_valid_len, mesh,
    *, k_scale: jax.Array | None = None, v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Tensor-parallel dispatch of :func:`paged_decode_attention_pallas`.

    The block pool partitions along its kv-head axis (every shard holds
    ALL pages, but only its head slice of each — the ÷TP capacity win),
    the block table and valid lengths replicate, and each shard sweeps
    its local pool with the same (slot, kv-head, page) grid. Quantized
    pools bring their (N, Hkv) scales along, split on the kv-head axis
    like the pool rows they describe.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.collectives import tp_shard_map

    vl = jnp.broadcast_to(jnp.asarray(kv_valid_len), (q.shape[0],))
    h = P(None, None, "model", None)
    pool = P(None, None, "model", None)

    if k_scale is not None:
        def body_q(q_l, k_l, v_l, t_l, vl_l, ks_l, vs_l):
            return paged_decode_attention_pallas(
                q_l, k_l, v_l, t_l, vl_l, k_scale=ks_l, v_scale=vs_l,
                interpret=interpret,
            )

        sc = P(None, "model")
        return tp_shard_map(
            body_q, mesh,
            in_specs=(h, pool, pool, P(None, None), P(None), sc, sc),
            out_specs=h,
        )(q, k_pool, v_pool, table, vl, k_scale, v_scale)

    def body(q_l, k_l, v_l, t_l, vl_l):
        return paged_decode_attention_pallas(
            q_l, k_l, v_l, t_l, vl_l, interpret=interpret
        )

    return tp_shard_map(
        body, mesh,
        in_specs=(h, pool, pool, P(None, None), P(None)), out_specs=h,
    )(q, k_pool, v_pool, table, vl)
