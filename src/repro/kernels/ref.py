"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the allclose sweeps in tests/kernels/ and the
default execution path on backends without Mosaic (this CPU container).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sparse_delta_ref(x: jax.Array, idx: jax.Array, val: jax.Array) -> jax.Array:
    """yΔ[m, o] = Σ_j val[j, o] · x[m, idx[j, o]].

    x: (M, d_in); idx/val: (k, d_out) -> (M, d_out).
    """
    xg = x[:, idx]  # (M, k, d_out)
    return jnp.sum(xg * val.astype(x.dtype), axis=-2)


def sparse_delta_dval_ref(x: jax.Array, idx: jax.Array, dy: jax.Array) -> jax.Array:
    """dval[j, o] = Σ_m dy[m, o] · x[m, idx[j, o]]."""
    xg = x[:, idx]  # (M, k, d_out)
    return jnp.einsum("mko,mo->ko", xg.astype(jnp.float32), dy.astype(jnp.float32))


def sparse_delta_dx_ref(idx: jax.Array, val: jax.Array, dy: jax.Array, d_in: int) -> jax.Array:
    """dx[m, i] = Σ_{(j,o): idx[j,o]=i} dy[m,o]·val[j,o] — a k·d_out scatter-add."""
    m = dy.shape[0]
    upd = dy[:, None, :].astype(jnp.float32) * val[None].astype(jnp.float32)  # (M,k,d_out)
    dx = jnp.zeros((m, d_in), jnp.float32)
    return dx.at[:, idx].add(upd)


def sparse_delta_batched_ref(
    x: jax.Array, idx: jax.Array, val: jax.Array, aid: jax.Array
) -> jax.Array:
    """Multi-tenant bypass: yΔ[m, o] = Σ_j val[aid[m], j, o] · x[m, idx[aid[m], j, o]].

    x: (M, d_in); idx/val: (N, k, d_out) adapter stacks; aid: (M,) int32.
    """
    idx_m = jnp.take(idx, aid, axis=0)  # (M, k, d_out)
    val_m = jnp.take(val, aid, axis=0)
    xg = jnp.take_along_axis(x[:, None, :], idx_m, axis=-1)  # (M, k, d_out)
    return jnp.sum(xg * val_m.astype(x.dtype), axis=-2)


def decode_attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array, kv_valid_len
) -> jax.Array:
    """Single-token GQA attention with a per-slot cache frontier.

    q (B, 1, H, hd); k, v (B, Smax, Hkv, hd); kv_valid_len scalar or (B,)
    — cache positions ``>= kv_valid_len[b]`` are masked. f32 softmax.
    """
    b, _, h, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k.astype(jnp.float32)) * hd**-0.5
    vl = jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32).reshape(-1), (b,))
    mask = jnp.arange(skv)[None, None, None, :] < vl[:, None, None, None]
    s = jnp.where(mask, s, -1e30)
    p = jnp.where(mask, jax.nn.softmax(s, axis=-1), 0.0)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v.astype(jnp.float32))
    return o.reshape(b, 1, h, hd).astype(q.dtype)


def gather_paged_kv(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Materialise a contiguous (B, n_pages·P, KV, hd) cache view from a
    (N, P, KV, hd) block pool through a (B, n_pages) block table.

    Sentinel (out-of-range) table entries clamp to the last block — their
    rows sit beyond every ``kv_valid_len`` frontier and are masked out by
    the attention that consumes the view.
    """
    n = pool.shape[0]
    tbl = jnp.minimum(table, n - 1)
    b, n_pages = table.shape
    return pool[tbl].reshape(b, n_pages * pool.shape[1], *pool.shape[2:])


def paged_decode_attention_ref(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array, table: jax.Array,
    kv_valid_len,
) -> jax.Array:
    """Block-table decode attention oracle: gather pages, then the dense
    per-slot-frontier softmax.

    q (B, 1, H, hd); k_pool/v_pool (N, P, Hkv, hd); table (B, n_pages)
    int32 (out-of-range = unallocated); kv_valid_len scalar or (B,).
    """
    k = gather_paged_kv(k_pool, table)
    v = gather_paged_kv(v_pool, table)
    return decode_attention_ref(q, k, v, kv_valid_len)


def prefill_attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array, q_offset, kv_valid_len
) -> jax.Array:
    """Chunked-prefill GQA attention against a contiguous cache view.

    q (B, C, H, hd); k, v (B, Skv, Hkv, hd); q_offset, kv_valid_len
    scalar or (B,). Query ``i`` (logical position ``q_offset[b] + i``)
    sees column ``c`` iff ``c <= q_offset[b] + i`` and
    ``c < kv_valid_len[b]`` — intra-chunk causality plus the per-slot
    cache frontier. f32 softmax; rows of pad queries
    ``i >= kv_valid_len[b] - q_offset[b]`` return zeros.
    """
    b, c, h, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, c, hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(jnp.float32)) * hd**-0.5
    qoff = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32).reshape(-1), (b,))
    vl = jnp.broadcast_to(jnp.asarray(kv_valid_len, jnp.int32).reshape(-1), (b,))
    col = jnp.arange(skv)[None, None, :]
    qpos = qoff[:, None, None] + jnp.arange(c)[None, :, None]
    real = qpos < vl[:, None, None]                   # (B, C, 1)
    mask = (col <= qpos) & (col < vl[:, None, None]) & real  # (B, C, Skv)
    mask = mask[:, None, None]                        # (B, 1, 1, C, Skv)
    s = jnp.where(mask, s, -1e30)
    p = jnp.where(mask, jax.nn.softmax(s, axis=-1), 0.0)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(b, c, h, hd).astype(q.dtype)


def paged_prefill_attention_ref(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array, table: jax.Array,
    q_offset, kv_valid_len,
) -> jax.Array:
    """Block-table chunked-prefill oracle: gather pages, dense masked
    softmax with the two-sided (causal frontier × valid length) mask.

    q (B, C, H, hd); k_pool/v_pool (N, P, Hkv, hd); table (B, n_pages)
    int32 (out-of-range = unallocated); q_offset/kv_valid_len scalar or
    (B,).
    """
    k = gather_paged_kv(k_pool, table)
    v = gather_paged_kv(v_pool, table)
    return prefill_attention_ref(q, k, v, q_offset, kv_valid_len)


# ------------------------------------------------ quantized KV (DESIGN §15)


def dequant_dense_kv(data: jax.Array, scale: jax.Array) -> jax.Array:
    """Dequantize a dense int8 slot cache: (B, S, KV, hd) codes with
    (B, S // group, KV) per-group scales → f32 values. ``group`` is
    implied by the shapes (S must divide evenly, which ``init_cache``
    guarantees by rounding S up to whole groups)."""
    s = data.shape[-3]
    ngr = scale.shape[-2]
    sg = jnp.repeat(scale.astype(jnp.float32), s // ngr, axis=-2)
    return data.astype(jnp.float32) * sg[..., None]


def gather_paged_kv_q(
    pool: jax.Array, scale: jax.Array, table: jax.Array
) -> jax.Array:
    """Quantized twin of :func:`gather_paged_kv`: gather int8 pages AND
    their per-(block, kv-head) scales through the block table, dequantize
    to a contiguous (B, n_pages·P, KV, hd) f32 view."""
    n = pool.shape[0]
    tbl = jnp.minimum(table, n - 1)
    b, n_pages = table.shape
    pages = pool[tbl].astype(jnp.float32)        # (B, n_pages, P, KV, hd)
    sc = scale[tbl].astype(jnp.float32)          # (B, n_pages, KV)
    pages = pages * sc[:, :, None, :, None]
    return pages.reshape(b, n_pages * pool.shape[1], *pool.shape[2:])


def decode_attention_q_ref(
    q, k, v, k_scale, v_scale, kv_valid_len
) -> jax.Array:
    """int8-cache decode oracle: dequantize the dense cache, then
    :func:`decode_attention_ref`."""
    return decode_attention_ref(
        q,
        dequant_dense_kv(k, k_scale),
        dequant_dense_kv(v, v_scale),
        kv_valid_len,
    )


def paged_decode_attention_q_ref(
    q, k_pool, v_pool, k_scale, v_scale, table, kv_valid_len
) -> jax.Array:
    """int8-pool paged decode oracle: gather+dequantize, dense softmax."""
    k = gather_paged_kv_q(k_pool, k_scale, table)
    v = gather_paged_kv_q(v_pool, v_scale, table)
    return decode_attention_ref(q, k, v, kv_valid_len)


def paged_prefill_attention_q_ref(
    q, k_pool, v_pool, k_scale, v_scale, table, q_offset, kv_valid_len
) -> jax.Array:
    """int8-pool chunked-prefill oracle: gather+dequantize, two-sided
    masked softmax."""
    k = gather_paged_kv_q(k_pool, k_scale, table)
    v = gather_paged_kv_q(v_pool, v_scale, table)
    return prefill_attention_ref(q, k, v, q_offset, kv_valid_len)


def fused_linear_ref(
    x: jax.Array,
    w: jax.Array,
    idx: jax.Array,
    val: jax.Array,
    bias: jax.Array | None = None,
) -> jax.Array:
    """y = x@W (+bias) + sparse delta, in float32 accumulation."""
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    y = y + sparse_delta_ref(x, idx, val).astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def topk_select_ref(w: jax.Array, k: int) -> jax.Array:
    """Per-output-unit top-k |magnitude| indices; (d_in, d_out) -> (k, d_out)."""
    _, idx = jax.lax.top_k(jnp.abs(w.astype(jnp.float32)).T, k)  # (d_out, k)
    return idx.T.astype(jnp.int32)
