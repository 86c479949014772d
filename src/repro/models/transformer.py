"""Decoder-only transformer LM (dense / MoE / VLM variants).

Layer stacks are ``lax.scan`` over stacked params (L, …) — HLO stays one
block long regardless of depth (compile time, roofline parser). The same
block function serves train, prefill, and decode; decode threads the KV
cache through scan ``xs``/``ys``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.delta import BatchedDelta, Delta
from repro.distributed.context import (
    constrain,
    constrain_inner,
    constrain_kv,
    constrain_kv_scale,
)
from repro.kernels import ops
from repro.models import moe as moe_lib
from repro.models.attention import (
    attention,
    chunk_attention,
    paged_attention,
    paged_prefill_attention,
)
from repro.models.layers import (
    KV_QUANT_GROUP,
    ad_get,
    alinear,
    apply_mrope,
    apply_rope,
    cache_update,
    cache_update_q,
    chunk_cache_update,
    chunk_cache_update_q,
    compute_dtype,
    decode_positions,
    init_linear,
    init_norm,
    paged_cache_update,
    paged_cache_update_q,
    paged_chunk_cache_update,
    paged_chunk_cache_update_q,
    rms_norm,
    softmax_cross_entropy,
)

# ------------------------------------------------------------------ params


def init_params(cfg, rng):
    dt = compute_dtype(cfg)
    L, D, F = cfg.num_layers, cfg.d_model, cfg.d_ff
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    V = cfg.padded_vocab
    keys = jax.random.split(rng, 16)

    def lin(key, shape_in, shape_out, bias=False, stack=(L,)):
        # stacked init: one draw for all layers
        w = (
            jax.random.normal(key, (*stack, shape_in, shape_out), jnp.float32)
            * shape_in**-0.5
        ).astype(dt)
        out = {"w": w}
        if bias:
            out["b"] = jnp.zeros((*stack, shape_out), dt)
        return out

    blocks = {
        "attn_norm": jnp.ones((L, D), dt),
        "wq": lin(keys[0], D, H * hd, bias=cfg.qkv_bias),
        "wk": lin(keys[1], D, KV * hd, bias=cfg.qkv_bias),
        "wv": lin(keys[2], D, KV * hd, bias=cfg.qkv_bias),
        "wo": lin(keys[3], H * hd, D),
        "mlp_norm": jnp.ones((L, D), dt),
    }
    if cfg.qk_norm:
        blocks["q_norm"] = jnp.ones((L, hd), dt)
        blocks["k_norm"] = jnp.ones((L, hd), dt)
    if cfg.num_experts:
        E = cfg.num_experts
        blocks["router"] = {"w": (
            jax.random.normal(keys[4], (L, D, E), jnp.float32) * D**-0.5
        ).astype(dt)}
        blocks["wgate"] = lin(keys[5], D, F, stack=(L, E))
        blocks["wup"] = lin(keys[6], D, F, stack=(L, E))
        blocks["wdown"] = lin(keys[7], F, D, stack=(L, E))
    else:
        blocks["wgate"] = lin(keys[5], D, F)
        blocks["wup"] = lin(keys[6], D, F)
        blocks["wdown"] = lin(keys[7], F, D)

    params = {
        "embed": {"w": (jax.random.normal(keys[8], (V, D), jnp.float32) * 0.02).astype(dt)},
        "blocks": blocks,
        "final_norm": init_norm(D, dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = init_linear(keys[9], D, V, dt)
    return params


# ------------------------------------------------------------------- block


def _mlp(cfg, p, a, x, **moe_kw):
    if cfg.num_experts:
        return moe_lib.moe_ffn(cfg, p, a, x, **moe_kw)
    h = jax.nn.silu(alinear(p, a, "wgate", x)) * alinear(p, a, "wup", x)
    h = constrain_inner(h)  # Megatron TP layout for the hidden
    return alinear(p, a, "wdown", h), jnp.float32(0.0)


def _qkv(cfg, p, a, x, positions, mrope_pos):
    b, s, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = constrain_inner(alinear(p, a, "wq", x).reshape(b, s, H, hd))
    k = constrain_inner(alinear(p, a, "wk", x).reshape(b, s, KV, hd))
    v = constrain_inner(alinear(p, a, "wv", x).reshape(b, s, KV, hd))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.mrope_sections and mrope_pos is not None:
        q = apply_mrope(q, mrope_pos, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mrope_pos, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _block_train(cfg, h, p, a, positions, mrope_pos):
    h = constrain(h)  # sequence-parallel residual layout
    x = rms_norm(h, p["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(cfg, p, a, x, positions, mrope_pos)
    o = attention(q, k, v, cfg, causal=True)
    h = h + alinear(p, a, "wo", o.reshape(*o.shape[:2], -1))
    x = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
    y, aux = _mlp(cfg, p, a, x)
    return h + y, aux


def _write_decode(c, k, v, pos, table):
    """Single-token cache write into a per-layer cache dict ``c``.

    ``c`` holds ``{"k", "v"}`` fp leaves — or the int8 quartet with
    ``{"k_scale", "v_scale"}``, in which case the quantize-on-write twins
    rebuild the touched page/group (DESIGN §15)."""
    if "k_scale" in c:
        if table is None:
            dk, sk = cache_update_q(c["k"], c["k_scale"], k, pos)
            dv, sv = cache_update_q(c["v"], c["v_scale"], v, pos)
        else:
            dk, sk = paged_cache_update_q(c["k"], c["k_scale"], k, table, pos)
            dv, sv = paged_cache_update_q(c["v"], c["v_scale"], v, table, pos)
        return {
            "k": constrain_kv(dk),
            "v": constrain_kv(dv),
            "k_scale": constrain_kv_scale(sk),
            "v_scale": constrain_kv_scale(sv),
        }
    if table is None:
        return {
            "k": constrain_kv(cache_update(c["k"], k, pos)),
            "v": constrain_kv(cache_update(c["v"], v, pos)),
        }
    return {
        "k": constrain_kv(paged_cache_update(c["k"], k, table, pos)),
        "v": constrain_kv(paged_cache_update(c["v"], v, table, pos)),
    }


def _write_chunk(c, k, v, wtable, q_offset, q_len):
    """Chunk cache write into a per-layer cache dict ``c`` (dense when
    ``wtable`` is None, else routed through the slot write tables)."""
    if "k_scale" in c:
        if wtable is None:
            dk, sk = chunk_cache_update_q(c["k"], c["k_scale"], k, q_offset, q_len)
            dv, sv = chunk_cache_update_q(c["v"], c["v_scale"], v, q_offset, q_len)
        else:
            dk, sk = paged_chunk_cache_update_q(
                c["k"], c["k_scale"], k, wtable, q_offset, q_len
            )
            dv, sv = paged_chunk_cache_update_q(
                c["v"], c["v_scale"], v, wtable, q_offset, q_len
            )
        return {
            "k": constrain_kv(dk),
            "v": constrain_kv(dv),
            "k_scale": constrain_kv_scale(sk),
            "v_scale": constrain_kv_scale(sv),
        }
    if wtable is None:
        return {
            "k": constrain_kv(chunk_cache_update(c["k"], k, q_offset, q_len)),
            "v": constrain_kv(chunk_cache_update(c["v"], v, q_offset, q_len)),
        }
    return {
        "k": constrain_kv(paged_chunk_cache_update(c["k"], k, wtable, q_offset, q_len)),
        "v": constrain_kv(paged_chunk_cache_update(c["v"], v, wtable, q_offset, q_len)),
    }


def _block_decode(cfg, h, p, a, c, pos, positions, mrope_pos):
    """One-token step. c["k"]/c["v"] (B,Smax,KV,hd); pos scalar or (B,)."""
    x = rms_norm(h, p["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(cfg, p, a, x, positions, mrope_pos)
    c = _write_decode(c, k, v, pos, None)
    o = attention(
        q, c["k"], c["v"], cfg, causal=False, kv_valid_len=pos + 1,
        k_scale=c.get("k_scale"), v_scale=c.get("v_scale"),
    )
    h = h + alinear(p, a, "wo", o.reshape(*o.shape[:2], -1))
    x = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
    y, _ = _mlp(cfg, p, a, x)
    return h + y, c


def _block_decode_paged(cfg, h, p, a, c, pos, table, positions, mrope_pos):
    """One-token step against a block pool. c["k"]/c["v"] (N,P,KV,hd);
    table (B, n_pages) routes each slot's logical pages; pos (B,)."""
    x = rms_norm(h, p["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(cfg, p, a, x, positions, mrope_pos)
    c = _write_decode(c, k, v, pos, table)
    o = paged_attention(
        q, c["k"], c["v"], table, cfg, kv_valid_len=pos + 1,
        k_scale=c.get("k_scale"), v_scale=c.get("v_scale"),
    )
    h = h + alinear(p, a, "wo", o.reshape(*o.shape[:2], -1))
    x = rms_norm(h, p["mlp_norm"], cfg.norm_eps)
    y, _ = _mlp(cfg, p, a, x)
    return h + y, c


# ----------------------------------------------------------------- forward


def _split_blocks(params, adapters):
    a_blocks = adapters.get("blocks", {}) if isinstance(adapters, dict) else {}
    return params["blocks"], a_blocks


def _head_logits(cfg, params, adapters, h):
    """Output projection + NeuroAda bypass on an untied head.

    The head matrix is adaptable like any linear (it is outside the layer
    scan, so its delta has no leading L axis); tied-embedding models have
    no head param and thus no head delta. LoRA head leaves are ignored —
    LoRA adapts block projections only.
    """
    if cfg.tie_embeddings:
        logits = jnp.dot(h, params["embed"]["w"].T)
    else:
        # untied head is adaptable and may be a quantized frozen matrix;
        # under a TP serve mesh its columns are vocab-sharded (the one
        # call site where col-parallel placement is structurally known)
        logits = ops.matmul_q(h, params["head"]["w"], tp_col_sharded=True)
    d = ad_get(adapters, "head") if isinstance(adapters, dict) else None
    if isinstance(d, BatchedDelta):
        logits = logits + ops.delta_apply_batched(h, d.idx, d.val, d.aid)
    elif isinstance(d, Delta):
        logits = logits + ops.delta_apply(h, d.idx, d.val)
    return logits


def _embed_inputs(cfg, params, batch):
    dt = compute_dtype(cfg)
    tokens = batch["tokens"]
    emb = jnp.take(params["embed"]["w"], tokens, axis=0).astype(dt)
    if cfg.family == "vlm" and "patches" in batch:
        h = jnp.concatenate([batch["patches"].astype(dt), emb], axis=1)
        positions = None
        mrope_pos = batch["positions"]  # (3,B,S_total)
    else:
        h = emb
        b, s = tokens.shape
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        mrope_pos = None
    return h, positions, mrope_pos


def forward_train(cfg, params, adapters, batch, *, remat="none"):
    h, positions, mrope_pos = _embed_inputs(cfg, params, batch)
    blocks, a_blocks = _split_blocks(params, adapters)

    def body(carry, xs):
        hh, aux = carry
        p, a = xs
        hh, aux_l = _block_train(cfg, hh, p, a, positions, mrope_pos)
        return (hh, aux + aux_l), None

    if remat != "none":
        policy = (
            jax.checkpoint_policies.nothing_saveable
            if remat == "full"
            else jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
        body = jax.checkpoint(body, policy=policy)
    (h, aux), _ = jax.lax.scan(body, (h, jnp.float32(0.0)), (blocks, a_blocks))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(cfg, params, adapters, h)
    return logits, aux / cfg.num_layers


def loss_fn(cfg, params, adapters, batch, *, remat="none"):
    logits, aux = forward_train(cfg, params, adapters, batch, remat=remat)
    if cfg.family == "vlm" and "patches" in batch:
        # only text positions carry loss
        n_img = batch["patches"].shape[1]
        logits = logits[:, n_img:]
    ce = softmax_cross_entropy(
        logits[:, :-1], batch["targets"][:, 1:], batch.get("loss_mask", None),
        real_vocab=cfg.vocab_size,
    )
    loss = ce + cfg.router_aux_coef * aux
    return loss, {"ce": ce, "aux": aux}


# ------------------------------------------------------------------- serve


def init_cache(cfg, batch: int, max_len: int, kv_dtype: str = "fp32"):
    """Dense slot cache. ``kv_dtype="int8"`` packs k/v as int8 codes with
    per-(slot, :data:`KV_QUANT_GROUP`-row group, kv-head) fp32 scales; the
    sequence axis rounds up to a whole number of groups (attention masks
    the pad rows the same way it masks unwritten ones). DESIGN §15."""
    dt = compute_dtype(cfg)
    L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    if kv_dtype == "int8":
        g = KV_QUANT_GROUP
        ngr = -(-max_len // g)
        return {
            "k": jnp.zeros((L, batch, ngr * g, KV, hd), jnp.int8),
            "v": jnp.zeros((L, batch, ngr * g, KV, hd), jnp.int8),
            "k_scale": jnp.zeros((L, batch, ngr, KV), jnp.float32),
            "v_scale": jnp.zeros((L, batch, ngr, KV), jnp.float32),
        }
    if kv_dtype != "fp32":
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r}")
    return {
        "k": jnp.zeros((L, batch, max_len, KV, hd), dt),
        "v": jnp.zeros((L, batch, max_len, KV, hd), dt),
    }


def init_paged_cache(cfg, num_blocks: int, page_size: int, kv_dtype: str = "fp32"):
    """Block-pool cache: capacity is tokens (num_blocks × page_size), not
    slots × max_len — slots own pages through a block table, not rows.
    ``kv_dtype="int8"`` packs the pools as int8 codes with one fp32 scale
    per (block, kv-head) riding beside them (DESIGN §15)."""
    dt = compute_dtype(cfg)
    L, KV, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    if kv_dtype == "int8":
        return {
            "k": jnp.zeros((L, num_blocks, page_size, KV, hd), jnp.int8),
            "v": jnp.zeros((L, num_blocks, page_size, KV, hd), jnp.int8),
            "k_scale": jnp.zeros((L, num_blocks, KV), jnp.float32),
            "v_scale": jnp.zeros((L, num_blocks, KV), jnp.float32),
        }
    if kv_dtype != "fp32":
        raise ValueError(f"unsupported kv_dtype {kv_dtype!r}")
    return {
        "k": jnp.zeros((L, num_blocks, page_size, KV, hd), dt),
        "v": jnp.zeros((L, num_blocks, page_size, KV, hd), dt),
    }


def prefill(cfg, params, adapters, batch):
    """Full forward over the prompt; returns (last-token logits, cache).

    ``batch["last_pos"]`` (B,) optionally names the final *real* token per
    sequence for right-padded batched prompts: logits are gathered there
    instead of at -1. Right pads are exact under causal attention — real
    positions never attend to them — and their garbage cache rows are
    overwritten by decode before ``kv_valid_len`` reaches them.
    """
    h, positions, mrope_pos = _embed_inputs(cfg, params, batch)
    blocks, a_blocks = _split_blocks(params, adapters)

    def body(hh, xs):
        p, a = xs
        hh = constrain(hh)
        x = rms_norm(hh, p["attn_norm"], cfg.norm_eps)
        q, k, v = _qkv(cfg, p, a, x, positions, mrope_pos)
        o = attention(q, k, v, cfg, causal=True)
        hh = hh + alinear(p, a, "wo", o.reshape(*o.shape[:2], -1))
        x = rms_norm(hh, p["mlp_norm"], cfg.norm_eps)
        y, _ = _mlp(cfg, p, a, x)
        return hh + y, (k, v)

    h, (ck, cv) = jax.lax.scan(body, h, (blocks, a_blocks))
    last = batch.get("last_pos")
    hs = h[:, -1:] if last is None else jnp.take_along_axis(h, last[:, None, None], axis=1)
    h = rms_norm(hs, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(cfg, params, adapters, h)[:, 0]
    return logits, {"k": ck, "v": cv}


class _Rows(NamedTuple):
    """Row maps between a (B, C) chunk and its flat layout of T rows.

    Slot s's ``q_len[s]`` real tokens sit at flat rows ``start[s]`` …
    ``start[s] + q_len[s] - 1``, slot after slot; rows past Σ ``q_len`` are
    pads, whose grid cell ``B·C`` lies out of range, so a gather through
    it reads zeros."""

    start: jax.Array  # (B,) first flat row of each slot
    slot: jax.Array  # (T,) slot of each flat row (B - 1 for pads)
    pos: jax.Array  # (T,) chunk index of each flat row (0 for pads)
    grid: jax.Array  # (T,) grid cell s·C + i of each flat row


def _chunk_rows(q_len, c, rows):
    """Flat row maps for a (B, c) chunk computed on ``rows`` rows, or None
    where ``rows`` covers the whole grid (the identity layout)."""
    b = q_len.shape[0]
    if rows is None or rows >= b * c:
        return None
    end = jnp.cumsum(q_len)
    start = end - q_len
    r = jnp.arange(rows)
    slot = jnp.sum(r[:, None] >= end[None, :], axis=1)  # B past Σ q_len
    real = slot < b
    slot = jnp.minimum(slot, b - 1)
    pos = jnp.where(real, r - start[slot], 0)
    return _Rows(start, slot, pos, jnp.where(real, slot * c + pos, b * c))


def _flat_adapters(a_blocks, slot):
    """Block adapters for flat rows: a tenant delta's per-slot ``aid``
    (L, B) becomes a per-row (L, 1, T), each row taking its slot's id."""

    def one(d):
        if isinstance(d, BatchedDelta):
            return d._replace(aid=d.aid[:, slot][:, None, :])
        return d

    return jax.tree.map(one, a_blocks, is_leaf=lambda d: isinstance(d, BatchedDelta))


def _chunk_forward(cfg, params, adapters, cache, batch):
    """Shared body of :func:`prefill_chunk` / :func:`verify_chunk`: run a
    (B, C) token chunk through the layer stack against a live KV cache.

    With ``batch["rows"]`` = T < B·C the row-wise work (embedding, norms,
    QKV with RoPE, ``wo``, the MLP, whose MoE routing drops no token) runs
    on a flat (1, T, D) buffer of the chunk's real tokens (DESIGN §11); only the cache write and the
    attention kernel see the (B, C) grid, sliced from the flat rows, and
    the attention output is gathered back. Returns the hidden states —
    (1, T, D) flat, else (B, C, D) — the updated cache and the row maps
    (None for the grid)."""
    dt = compute_dtype(cfg)
    tokens = batch["tokens"]
    q_offset = batch["q_offset"]
    q_len = batch["q_len"]
    table = batch.get("block_table")
    wtable = batch.get("write_table")
    b, c = tokens.shape
    rm = _chunk_rows(q_len, c, batch.get("rows"))
    blocks, a_blocks = _split_blocks(params, adapters)
    moe_kw = {}
    if rm is None:
        positions = q_offset[:, None] + jnp.arange(c)[None, :]

        def to_grid(x):
            return x

        def to_rows(x):
            return x
    else:
        tokens = jnp.take(tokens.reshape(-1), rm.grid, mode="fill", fill_value=0)[None]
        positions = (q_offset[rm.slot] + rm.pos)[None]
        a_blocks = _flat_adapters(a_blocks, rm.slot)
        # one routing group whose experts each hold every row: no real
        # token is dropped, whichever slots share the step (DESIGN §11)
        moe_kw = {"groups": 1, "cap": rm.slot.shape[0]}

        def to_grid(x):
            # slot s's chunk is the C rows from start[s], one contiguous
            # slice each; cells past q_len hold the next rows, which the
            # cache write drops and the attention output leaves unread
            x = jnp.pad(x[0], ((0, c),) + ((0, 0),) * (x.ndim - 2))
            return jax.vmap(lambda s: jax.lax.dynamic_slice_in_dim(x, s, c))(rm.start)

        def to_rows(x):
            x = x.reshape(b * c, *x.shape[2:])
            return jnp.take(x, rm.grid, axis=0, mode="fill", fill_value=0)[None]

    h = jnp.take(params["embed"]["w"], tokens, axis=0).astype(dt)
    vl = q_offset + q_len

    def body(hh, xs):
        p, a, kv = xs
        x = rms_norm(hh, p["attn_norm"], cfg.norm_eps)
        q, k, v = (to_grid(t) for t in _qkv(cfg, p, a, x, positions, None))
        if table is None:
            kv = _write_chunk(kv, k, v, None, q_offset, q_len)
            o = chunk_attention(
                q, kv["k"], kv["v"], cfg, q_offset=q_offset, kv_valid_len=vl,
                k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"),
            )
        else:
            kv = _write_chunk(kv, k, v, wtable, q_offset, q_len)
            o = paged_prefill_attention(
                q, kv["k"], kv["v"], table, cfg, q_offset=q_offset, kv_valid_len=vl,
                k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"),
            )
        o = to_rows(o)
        hh = hh + alinear(p, a, "wo", o.reshape(*o.shape[:2], -1))
        x = rms_norm(hh, p["mlp_norm"], cfg.norm_eps)
        y, _ = _mlp(cfg, p, a, x, **moe_kw)
        return hh + y, kv

    h, cache = jax.lax.scan(body, h, (blocks, a_blocks, cache))
    return h, cache, rm


def prefill_chunk(cfg, params, adapters, cache, batch):
    """Mixed prefill+decode chunk step against a live KV cache (DESIGN §11).

    Every serving slot contributes one row of a (B, C) token chunk:
    prefilling slots carry their next ``q_len`` prompt tokens, decode
    slots the degenerate chunk ``q_len = 1`` (their last sampled token),
    idle slots ``q_len = 0``. Each layer writes the chunk's k/v into the
    cache *first* (pads and idle rows drop; paged writes route through
    the write table so shared prefix pages are never rewritten), then
    attends with the two-sided mask — intra-chunk causal from
    ``q_offset`` plus the post-write frontier ``q_offset + q_len``.
    Logits are gathered at ``last_idx`` (the row's final real token), so
    a slot whose prompt completes this chunk samples its first token in
    the same compiled step that decode slots sample their next.

    batch: {"tokens": (B, C) int32, "q_offset": (B,) int32,
    "q_len": (B,) int32, "last_idx": (B,) int32,
    ["block_table"/"write_table": (B, n_pages) int32 — paged serving],
    ["rows": int — static row count T ≥ Σ ``q_len``: the dense work runs
    on T flat rows instead of B·C (the caller bounds Σ ``q_len``)]}.
    """
    h, cache, rm = _chunk_forward(cfg, params, adapters, cache, batch)
    last = batch["last_idx"]
    if rm is None:
        hs = jnp.take_along_axis(h, last[:, None, None], axis=1)
    else:
        hs = jnp.take(h[0], rm.start + last, axis=0, mode="clip")[:, None]
    hs = rms_norm(hs, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(cfg, params, adapters, hs)[:, 0]
    return logits, cache


def verify_chunk(cfg, params, adapters, cache, batch):
    """Speculative-decoding verification pass (DESIGN §12).

    The same mixed-chunk forward as :func:`prefill_chunk` — each slot's
    ``[last token, draft_1 … draft_k]`` column writes k/v at ``q_offset +
    i`` and attends through the two-sided chunk mask — but the head runs
    at EVERY chunk position instead of gathering one row, so the full
    model scores all k+1 speculative positions of every slot in one
    batched call. Returns ((B, C, V) logits, cache); rows at or beyond a
    slot's ``q_len`` are garbage the caller must mask (their writes
    already dropped in-graph).
    """
    h, cache, _ = _chunk_forward(cfg, params, adapters, cache, batch)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    return _head_logits(cfg, params, adapters, h), cache


def decode_step(cfg, params, adapters, cache, batch):
    """One new token per sequence against a (L,B,Smax,…) KV cache — or,
    when ``batch["block_table"]`` is present, against a paged
    (L,N,P,…) block pool routed through the (B, n_pages) table.

    batch: {"token": (B,) int32, "pos": () int32 — current write index,
    ["block_table": (B, n_pages) int32 — paged serving]}.
    """
    dt = compute_dtype(cfg)
    tok = batch["token"]
    pos = batch["pos"]
    table = batch.get("block_table")
    b = tok.shape[0]
    h = jnp.take(params["embed"]["w"], tok[:, None], axis=0).astype(dt)
    positions = decode_positions(pos, b)
    mrope_pos = batch.get("mrope_pos")  # (3,B,1) for VLM decode
    blocks, a_blocks = _split_blocks(params, adapters)

    def body(hh, xs):
        p, a, c = xs
        if table is None:
            hh, c = _block_decode(cfg, hh, p, a, c, pos, positions, mrope_pos)
        else:
            hh, c = _block_decode_paged(
                cfg, hh, p, a, c, pos, table, positions, mrope_pos
            )
        return hh, c

    h, cache = jax.lax.scan(body, h, (blocks, a_blocks, cache))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(cfg, params, adapters, h)[:, 0]
    return logits, cache
