"""Plain float32 reference of the Qwen2 dense decoder with NeuroAda bypasses.

Written from the published description (Qwen2 technical report,
arXiv:2407.10671; the Hugging Face ``Qwen2ForCausalLM`` equations), not
from the program: RMSNorm, q/k/v projections with bias, rotary position
embedding (rotate-half), grouped-query causal softmax attention, o
projection, SwiGLU MLP, final RMSNorm and the tied embedding as the head.
Each adapted linear adds its bypass ``y[o] += Σ_j val[j, o] · x[idx[j, o]]``
(NeuroAda, arXiv:2510.18940 Eq. 3), which equals merging the values into W.

Everything is f32 at ``Precision.HIGHEST``, with no kernel, cache or
batching of the program's. It runs one layer at a time, so that it fits
beside the bf16 weights, and takes the benchmark's weights, selection and
values, never anything the program made.

``mm`` is the one matrix product of the linears and the head: ``mm_f32``
for the reference; ``mm_fp8`` (both operands rounded to float8 e4m3 under
a per-tensor scale) for the control that computes in the next precision
below the configuration's bf16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def mm_f32(x, w):
    return jnp.matmul(x, w.astype(F32), precision=HI)


def fp8_round(a):
    """``a`` rounded to float8 e4m3 under one absmax scale per tensor (its
    largest magnitude maps to 448, e4m3's largest), back in f32; the
    gradient passes straight through, as fp8 training does."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0)
    q = (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return a + jax.lax.stop_gradient(q - a)


def mm_fp8(x, w):
    return jnp.matmul(fp8_round(x), fp8_round(w.astype(F32)), precision=HI)


MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def rope(x, theta):
    """x (B, S, n, hd) at positions 0 .. S-1."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def linear(x, leaf, idx, val, mm):
    y = mm(x, leaf["w"])
    if idx is not None:
        # bypass: the (k, d_out) values read x at their selected inputs
        y = y + jnp.sum(jnp.take(x, idx, axis=-1) * val.astype(F32), axis=-2)
    if "b" in leaf:
        y = y + leaf["b"].astype(F32)
    return y


def attention(q, k, v):
    """Causal GQA softmax attention; q (B,S,H,hd), k/v (B,S,KV,hd)."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, s, kv, h // kv, hd)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, precision=HI) * hd ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HI)
    return o.reshape(b, s, h * hd)


def block(d, mm, p, idx, val, h):
    """One decoder layer. ``p`` holds the layer's weights; ``idx``/``val``
    map each adapted linear to its (k, d_out) bypass."""
    b, s, _ = h.shape
    x = rms_norm(h, p["attn_norm"], d["eps"])
    q = linear(x, p["wq"], idx["wq"], val["wq"], mm).reshape(b, s, d["H"], d["hd"])
    k = linear(x, p["wk"], idx["wk"], val["wk"], mm).reshape(b, s, d["KV"], d["hd"])
    v = linear(x, p["wv"], idx["wv"], val["wv"], mm).reshape(b, s, d["KV"], d["hd"])
    o = attention(rope(q, d["theta"]), rope(k, d["theta"]), v)
    h = h + linear(o, p["wo"], idx["wo"], val["wo"], mm)
    x = rms_norm(h, p["mlp_norm"], d["eps"])
    g = linear(x, p["wgate"], idx["wgate"], val["wgate"], mm)
    u = linear(x, p["wup"], idx["wup"], val["wup"], mm)
    return h + linear(jax.nn.silu(g) * u, p["wdown"], idx["wdown"], val["wdown"], mm)


def _layer(tree, l):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False), tree)


@functools.partial(jax.jit, static_argnames=("d", "mm"))
def _layer_fwd(blocks, idx, val, l, h, *, d, mm):
    return block(dict(d), MATMULS[mm], _layer(blocks, l), _layer(idx, l), _layer(val, l), h)


@functools.partial(jax.jit, static_argnames=("d", "mm"))
def _layer_bwd(blocks, idx, val, l, h, g, *, d, mm):
    p, i, v = _layer(blocks, l), _layer(idx, l), _layer(val, l)
    _, pull = jax.vjp(lambda hh, vv: block(dict(d), MATMULS[mm], p, i, vv, hh), h, v)
    return pull(g)


@functools.partial(jax.jit, static_argnames=("eps", "mm"))
def _head_loss(h_rows, targets, weight, final_norm, embed, *, eps, mm):
    """Σ weight · cross-entropy of a block of rows, and its gradient."""

    def f(hr):
        logits = MATMULS[mm](rms_norm(hr, final_norm, eps), embed.T)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, targets[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * weight)

    return jax.value_and_grad(f)(h_rows)


@functools.partial(jax.jit, static_argnames=("eps", "mm"))
def _head_logits(h_rows, final_norm, embed, *, eps, mm):
    return MATMULS[mm](rms_norm(h_rows, final_norm, eps), embed.T)


def _frozen(d: dict):
    return tuple(sorted(d.items()))


def hidden_states(params, idx, val, tokens, d, mm="f32", keep=False):
    """Run the layer stack over ``tokens`` (B, S); returns the last hidden
    states, and with ``keep`` every layer's input too."""
    fd = _frozen(d)
    h = jnp.take(params["embed"]["w"], tokens, axis=0).astype(F32)
    inputs = []
    for l in range(d["L"]):
        if keep:
            inputs.append(h)
        h = _layer_fwd(params["blocks"], idx, val, l, h, d=fd, mm=mm)
    return h, inputs


def loss_and_grads(params, idx, val, tokens, d, mm="f32", rows_per_block=512):
    """Mean next-token cross-entropy over every position of ``tokens``
    (B, S) and its gradient with respect to the bypass values ``val``."""
    fd = _frozen(d)
    b, s = tokens.shape
    h, inputs = hidden_states(params, idx, val, tokens, d, mm, keep=True)
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1).reshape(-1)
    weight = jnp.broadcast_to((jnp.arange(s) < s - 1).astype(F32), (b, s)).reshape(-1)
    weight = weight / (b * (s - 1))
    rows = h.reshape(b * s, -1)
    loss, grads = 0.0, []
    for r in range(0, b * s, rows_per_block):
        sl = slice(r, r + rows_per_block)
        lv, gr = _head_loss(rows[sl], targets[sl], weight[sl], params["final_norm"],
                            params["embed"]["w"], eps=d["eps"], mm=mm)
        loss += float(lv)
        grads.append(gr)
    g = jnp.concatenate(grads).reshape(b, s, -1)
    per_layer = [None] * d["L"]
    for l in reversed(range(d["L"])):
        g, per_layer[l] = _layer_bwd(params["blocks"], idx, val, l, inputs[l], g, d=fd, mm=mm)
    dval = {n: jnp.stack([per_layer[l][n] for l in range(d["L"])]) for n in val}
    return loss, dval


def linear_warmup_decay(step: int, peak: float, total: int, warmup_ratio: float) -> float:
    """Learning rate at optimizer step ``step`` (1-based): linear warm-up
    over ``warmup_ratio`` of ``total`` steps, then linear decay to 0."""
    warm = max(int(total * warmup_ratio), 1)
    return peak * min(max(min(step / warm, max(total - step, 0) / max(total - warm, 1)), 0.0), 1.0)


def train(params, idx, batches, d, hp, mm="f32"):
    """``len(batches)`` steps of AdamW on the bypass values from zero, with
    global-norm clipping, in f32. Returns the losses, the first step's
    clipped gradient and the values after the last step, per leaf."""
    val = {n: jnp.zeros(i.shape, F32) for n, i in idx.items()}
    m = {n: jnp.zeros_like(v) for n, v in val.items()}
    v2 = {n: jnp.zeros_like(v) for n, v in val.items()}
    losses, first = [], None
    for t, tokens in enumerate(batches, start=1):
        loss, g = loss_and_grads(params, idx, val, jnp.asarray(tokens), d, mm)
        losses.append(loss)
        norm = float(jnp.sqrt(sum(jnp.sum(x * x) for x in g.values())))
        scale = min(1.0, hp["grad_clip"] / max(norm, 1e-9)) if hp["grad_clip"] > 0 else 1.0
        g = {n: x * scale for n, x in g.items()}
        if first is None:
            first = g
        lr = linear_warmup_decay(t, hp["learning_rate"], hp["steps"], hp["warmup_ratio"])
        b1, b2 = hp["beta1"], hp["beta2"]
        for n in val:
            m[n] = b1 * m[n] + (1 - b1) * g[n]
            v2[n] = b2 * v2[n] + (1 - b2) * g[n] * g[n]
            u = (m[n] / (1 - b1 ** t)) / (jnp.sqrt(v2[n] / (1 - b2 ** t)) + hp["eps"])
            val[n] = val[n] - lr * (u + hp["weight_decay"] * val[n])
    return {"losses": losses, "grad1": first, "values": val}


def served_logits(params, idx, val, prompt, out, d, length, mm="f32"):
    """Logits (len(out), V) that predict each served token ``out[i]`` from
    ``prompt + out[:i]``: one causal pass over the sequence, right-padded to
    ``length`` (pads sit after every position read)."""
    seq = list(prompt) + list(out[:-1])
    tokens = jnp.zeros((1, length), jnp.int32).at[0, : len(seq)].set(jnp.asarray(seq, jnp.int32))
    h, _ = hidden_states(params, idx, val, tokens, d, mm)
    rows = h[0, len(prompt) - 1: len(prompt) - 1 + len(out)]
    return _head_logits(rows, params["final_norm"], params["embed"]["w"], eps=d["eps"], mm=mm)
