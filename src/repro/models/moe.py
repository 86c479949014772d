"""Token-choice MoE FFN with sort-based dispatch (TPU-native, no TxExC
one-hot tensors).

Route: top-K gating -> stable argsort of (token,choice) assignments ->
capacity-truncated scatter into (E, C, D) expert buffers -> batched expert
FFN (einsum over E) -> gather-combine weighted by gate values. All shapes
static; capacity C = ceil(T·K/E · capacity_factor). Dropped tokens (beyond
capacity) fall back to the residual path, as in GShard/Switch.

Experts shard over the ``model`` mesh axis (EP); the dispatch scatter/gather
becomes the expert all-to-all under GSPMD. NeuroAda deltas on expert
matrices carry a leading E axis and are vmapped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.delta import BatchedDelta
from repro.distributed.context import constrain_moe
from repro.kernels import ops
from repro.models.layers import ad_get
from repro.quant.qtensor import QuantizedTensor, dequantize


def capacity(cfg, tokens: int) -> int:
    c = int(-(-tokens * cfg.experts_per_token * cfg.capacity_factor // cfg.num_experts))
    return max(c, cfg.experts_per_token)


def _route_group(cfg, xt, probs, c):
    """Sort-based dispatch within one token group. xt (Tg, D); probs (Tg,E).

    Returns (eh (E, C, D) expert buffers, combine closure state).
    """
    e, kk = cfg.num_experts, cfg.experts_per_token
    tg, dm = xt.shape
    gate, exp_idx = jax.lax.top_k(probs, kk)  # (Tg,K)
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)
    a_flat = exp_idx.reshape(tg * kk)
    g_flat = gate.reshape(tg * kk)
    order = jnp.argsort(a_flat, stable=True)
    tok_of = order // kk
    e_sorted = a_flat[order]
    g_sorted = g_flat[order]
    counts = jnp.zeros((e,), jnp.int32).at[a_flat].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(tg * kk, dtype=jnp.int32) - starts[e_sorted]
    keep = pos < c
    dest = jnp.where(keep, e_sorted * c + pos, e * c)  # OOB rows get dropped
    xs = jnp.take(xt, tok_of, axis=0)  # (TgK, D)
    buf = jnp.zeros((e * c, dm), xt.dtype).at[dest].set(xs, mode="drop")
    return buf.reshape(e, c, dm), (tok_of, dest, keep, g_sorted)


def _combine_group(out_e, route, tg, dtype):
    e, c, dm = out_e.shape
    tok_of, dest, keep, g_sorted = route
    flat = out_e.reshape(e * c, dm)
    contrib = jnp.take(flat, jnp.minimum(dest, e * c - 1), axis=0)
    contrib = jnp.where(keep[:, None], contrib, 0.0) * g_sorted[:, None].astype(dtype)
    return jnp.zeros((tg, dm), dtype).at[tok_of].add(contrib)


def moe_ffn(cfg, p, a, x, *, groups: int = 32, cap: int | None = None):
    """x (B,S,D) -> (out (B,S,D), aux_loss scalar).

    ``cap`` sets each group's per-expert capacity (default
    :func:`capacity` of the group's tokens); ``cap`` = tokens per group
    drops none.

    Group-local routing (§Perf iteration 5): tokens are split into
    ``groups`` independent routing groups (aligned with data shards), so
    the argsort/cumsum/scatter machinery is LOCAL to a shard. The only
    cross-shard communication is the canonical expert all-to-all: the
    (G~data, E~model, C, D) dispatch buffer resharding. A global sort over
    all tokens (the naive formulation) costs 100×+ more wire.
    """
    b, s, dm = x.shape
    e, kk = cfg.num_experts, cfg.experts_per_token
    t = b * s
    g = groups
    while t % g or (t // g) < kk:  # shrink until it divides (tiny inputs)
        g //= 2
        if g <= 1:
            g = 1
            break
    tg = t // g
    c = capacity(cfg, tg) if cap is None else cap
    xt = x.reshape(g, tg, dm)

    logits = jnp.einsum("gtd,de->gte", xt, p["router"]["w"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)

    eh, route = jax.vmap(lambda xg, pg: _route_group(cfg, xg, pg, c))(xt, probs)
    # eh (G, E, C, D): G sharded over data, E over model — the reshard into
    # expert-major layout is the dispatch all-to-all under GSPMD. The
    # explicit constraint keeps G data-sharded through the expert matmuls.
    eh = constrain_moe(eh)
    aid_buf = _dispatch_adapter_ids(a, route, b, s, g, e, c)
    h = jax.nn.silu(_expert_linear_g(p, a, "wgate", eh, aid_buf)) * _expert_linear_g(
        p, a, "wup", eh, aid_buf
    )
    h = constrain_moe(h)
    out_e = constrain_moe(_expert_linear_g(p, a, "wdown", h, aid_buf))  # (G, E, C, D)

    yt = jax.vmap(lambda oe, r: _combine_group(oe, r, tg, x.dtype))(out_e, route)

    exp_top1 = jnp.argmax(probs, axis=-1)  # (G,Tg)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(exp_top1.reshape(-1), e, dtype=jnp.float32), axis=0
    )
    frac_probs = jnp.mean(probs.reshape(-1, e), axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return yt.reshape(b, s, dm), aux


def _dispatch_adapter_ids(a, route, b, s, g, e, c):
    """Scatter per-sequence adapter ids through the expert dispatch.

    Multi-tenant serving (BatchedDelta leaves): expert-buffer row (e, c)
    holds a token from some sequence; its delta must come from that
    sequence's tenant. Empty buffer rows keep aid 0 — harmless, their
    activations are zero so the delta contributes zero. Router gating stays
    base-model (tenant-agnostic) by policy — see DESIGN.md §7.
    """
    d0 = next(
        (
            d
            for d in (ad_get(a, nm) for nm in ("wgate", "wup", "wdown"))
            if isinstance(d, BatchedDelta)
        ),
        None,
    )
    if d0 is None:
        return None
    tg = b * s // g
    # (B,) per sequence, or (1, T) per row of the mixed step's flat buffer
    aid = d0.aid.reshape(d0.aid.shape + (1,) * (2 - d0.aid.ndim))
    aid_t = jnp.broadcast_to(aid, (b, s)).reshape(g, tg)

    def one(aid_g, tok_of, dest):
        buf = jnp.zeros((e * c,), jnp.int32)
        buf = buf.at[dest].set(jnp.take(aid_g, tok_of), mode="drop")
        return buf.reshape(e, c)

    tok_of, dest, _, _ = route
    return jax.vmap(one)(aid_t, tok_of, dest)


def _expert_linear_g(p, a, name, eh, aid_buf=None):
    """eh (G, E, C, Din) @ w (E, Din, Dout) + vmapped NeuroAda delta."""
    w = p[name]["w"]
    if isinstance(w, QuantizedTensor):
        # expert stacks dequantize per call (the einsum contracts over E as
        # well, so the tile-fused path doesn't apply); XLA fuses the
        # dequant into the contraction and the dense copy stays transient
        w = dequantize(w).astype(eh.dtype)
    y = jnp.einsum("gecd,edf->gecf", eh, w)
    d = ad_get(a, name)
    if isinstance(d, BatchedDelta):
        yd = jax.vmap(  # over G; inner vmap over E slices the (N, E, k, F) stacks
            lambda ehg, aidg: jax.vmap(
                ops.delta_apply_batched, in_axes=(0, 1, 1, 0)
            )(ehg, d.idx, d.val, aidg)
        )(eh, aid_buf)
        y = y + yd
    elif d is not None:
        yd = jax.vmap(  # over G
            lambda ehg: jax.vmap(ops.delta_apply)(ehg, d.idx, d.val)
        )(eh)
        y = y + yd
    return y
