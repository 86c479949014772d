"""jit'd public wrappers around the Pallas kernels, with backend dispatch.

The backend follows the platform (:func:`get_backend`):

* ``pallas``           — Mosaic-compiled kernels, whenever JAX's default
                          backend is a TPU.
* ``jnp``              — the pure-jnp oracle path everywhere else (XLA
                          fuses it).
* ``pallas_interpret`` — kernel bodies interpreted in Python; only inside
                          :func:`use_backend`, which the test sweeps use to
                          pin kernel semantics on the CPU.

All wrappers accept arbitrary leading batch dims and handle tile padding.
The Pallas paths carry a custom VJP that reproduces the paper's sparse
backward: dval is a (k, d_out) reduction kernel, dx a k·d_out scatter-add.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import context as tp_ctx
from repro.kernels import ref
from repro.kernels.decode_attention import (
    decode_attention_pallas,
    decode_attention_sharded,
    paged_decode_attention_pallas,
    paged_decode_attention_sharded,
)
from repro.kernels.fused_linear import fused_linear_pallas
from repro.kernels.prefill_attention import (
    paged_prefill_attention_pallas,
    paged_prefill_attention_sharded,
)
from repro.kernels.quant_linear import fused_linear_q_pallas, matmul_q_cols_sharded
from repro.kernels.sparse_delta import (
    sparse_delta_batched_pallas,
    sparse_delta_dval_pallas,
    sparse_delta_pallas,
)
from repro.kernels.topk_select import topk_select_pallas
from repro.quant.qtensor import QuantizedTensor, dequantize

_BACKENDS = ("jnp", "pallas", "pallas_interpret")
_override: str | None = None


def get_backend() -> str:
    """``pallas`` on a TPU, ``jnp`` elsewhere, unless :func:`use_backend`
    scopes another. Read at trace time: a jitted function keeps the
    backend it was traced under."""
    if _override is not None:
        return _override
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


@contextlib.contextmanager
def use_backend(name: str):
    """Scope a backend — restores the previous one even when the body
    raises, so a failing test sweep can't leak the Pallas backend into
    later tests."""
    global _override
    if name not in _BACKENDS:
        raise ValueError(f"backend {name!r} not in {_BACKENDS}")
    prev, _override = _override, name
    try:
        yield
    finally:
        _override = prev


def _interpret() -> bool:
    return get_backend() == "pallas_interpret"


def _pad_to(x: jax.Array, axis: int, mult: int) -> tuple[jax.Array, int]:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


# ---------------------------------------------------------------- delta apply


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _delta_apply_pallas(x2d, idx, val, interpret):
    bm = 128 if x2d.shape[0] >= 128 else 8
    xp, m = _pad_to(x2d, 0, bm)
    ip, n = _pad_to(idx, 1, 128)
    vp, _ = _pad_to(val, 1, 128)
    y = sparse_delta_pallas(xp, ip, vp, block_m=bm, interpret=interpret)
    return y[:m, :n]


def _delta_fwd(x2d, idx, val, interpret):
    return _delta_apply_pallas(x2d, idx, val, interpret), (x2d, idx, val)


def _dval(x2d, idx, dy, interpret):
    """(k, d_out) f32 gradient of the bypass values, via the dval kernel."""
    bm = 128 if x2d.shape[0] >= 128 else 8
    xp, _ = _pad_to(x2d, 0, bm)
    dyp, _ = _pad_to(dy, 0, bm)
    ip, n = _pad_to(idx, 1, 128)
    dyp, _ = _pad_to(dyp, 1, 128)
    return sparse_delta_dval_pallas(xp, ip, dyp, block_m=bm, interpret=interpret)[:, :n]


def _delta_bwd(interpret, res, dy):
    x2d, idx, val = res
    dval = _dval(x2d, idx, dy, interpret).astype(val.dtype)
    dx = ref.sparse_delta_dx_ref(idx, val, dy, x2d.shape[1]).astype(x2d.dtype)
    didx = np.zeros(idx.shape, dtype=jax.dtypes.float0)
    return dx, didx, dval


_delta_apply_pallas.defvjp(_delta_fwd, _delta_bwd)


def delta_apply(x: jax.Array, idx: jax.Array, val: jax.Array) -> jax.Array:
    """x (..., d_in) × Delta (k, d_out) -> (..., d_out)."""
    if get_backend() == "jnp":
        xg = x[..., idx]
        return jnp.sum(xg * val.astype(x.dtype), axis=-2)
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    y = _delta_apply_pallas(x2d, idx, val, _interpret())
    return y.reshape(*lead, idx.shape[-1])


def delta_apply_batched(
    x: jax.Array, idx: jax.Array, val: jax.Array, aid: jax.Array
) -> jax.Array:
    """Multi-tenant bypass apply: per-row adapter selection from a stack.

    x (..., d_in) × stacks (N, k, d_out) selected by ``aid`` -> (..., d_out).
    ``aid`` int32 must broadcast (left-aligned) against ``x.shape[:-1]`` —
    the serving engine passes (B,) ids against (B, S, d_in) activations.
    Inference-only on the Pallas backends (no custom VJP; training uses the
    single-tenant paths).

    Under a TP serve mesh the stacks split with their host matrix — on
    d_out for column-parallel linears, while row-parallel ones see ``x``
    split on d_in — and a Mosaic kernel cannot be partitioned
    automatically, so the gather form runs there and GSPMD partitions it.
    """
    lead = x.shape[:-1]
    if aid.ndim < len(lead):
        aid = aid.reshape(aid.shape + (1,) * (len(lead) - aid.ndim))
    aid = jnp.broadcast_to(aid, lead).astype(jnp.int32)
    if get_backend() == "jnp" or tp_ctx.serve_tp() > 1:
        idx_m = jnp.take(idx, aid, axis=0)  # (..., k, d_out)
        val_m = jnp.take(val, aid, axis=0)
        xg = jnp.take_along_axis(x[..., None, :], idx_m, axis=-1)
        return jnp.sum(xg * val_m.astype(x.dtype), axis=-2)
    x2d = x.reshape(-1, x.shape[-1])
    aid1 = aid.reshape(-1)
    bm = 128 if x2d.shape[0] >= 128 else 8
    xp, m = _pad_to(x2d, 0, bm)
    ap, _ = _pad_to(aid1, 0, bm)
    ip, n = _pad_to(idx, 2, 128)
    vp, _ = _pad_to(val, 2, 128)
    y = sparse_delta_batched_pallas(
        xp, ip, vp, ap, block_m=bm, interpret=_interpret()
    )
    return y[:m, :n].reshape(*lead, idx.shape[-1])


# --------------------------------------------------------------- fused linear


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_linear_pallas(x2d, w, idx, val, bias, interpret, w_frozen):
    bm = 128 if x2d.shape[0] >= 128 else 8
    xp, m = _pad_to(x2d, 0, bm)
    y = fused_linear_pallas(xp, w, idx, val, bias, block_m=bm, interpret=interpret)
    return y[:m]


def _fused_fwd(x2d, w, idx, val, bias, interpret, w_frozen):
    y = _fused_linear_pallas(x2d, w, idx, val, bias, interpret, w_frozen)
    return y, (x2d, w, idx, val, bias)


def _fused_bwd(interpret, w_frozen, res, dy):
    x2d, w, idx, val, bias = res
    # dx: dense transpose + sparse scatter.
    dx = jnp.dot(dy, w.T) + ref.sparse_delta_dx_ref(idx, val, dy, x2d.shape[1]).astype(x2d.dtype)
    if w_frozen:
        # NeuroAda path: W never trains — statically skip the dense
        # x2d.T @ dy matmul instead of relying on DCE to remove it.
        dw = jnp.zeros(w.shape, w.dtype)
    else:
        dw = jnp.dot(x2d.T, dy).astype(w.dtype)
    dval = _dval(x2d, idx, dy, interpret).astype(val.dtype)
    dbias = None if bias is None else jnp.sum(dy, axis=0).astype(bias.dtype)
    didx = np.zeros(idx.shape, dtype=jax.dtypes.float0)
    return dx, dw, didx, dval, dbias


_fused_linear_pallas.defvjp(_fused_fwd, _fused_bwd)


def fused_linear(
    x: jax.Array,
    w: jax.Array,
    idx: jax.Array,
    val: jax.Array,
    bias: jax.Array | None = None,
    *,
    w_frozen: bool = False,
) -> jax.Array:
    """y = x@W (+bias) + delta, fused on the Pallas backends.

    ``w_frozen=True`` declares W non-trainable (the NeuroAda contract): the
    backward statically skips the dense ``dw`` matmul and returns zeros for
    it. Callers that differentiate W must leave it False.
    """
    if get_backend() == "jnp":
        # enforce the frozen contract uniformly across backends: the
        # Pallas bwd returns zero dw, so the jnp path must too
        y = jnp.dot(x, jax.lax.stop_gradient(w) if w_frozen else w)
        y = y + delta_apply(x, idx, val)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return y
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    y = _fused_linear_pallas(
        x2d, w, idx, val, bias, _interpret(), w_frozen
    )
    return y.reshape(*lead, w.shape[-1])


# ------------------------------------------------- quantized-base linears


def _q_meta(qw: QuantizedTensor):
    # interpret rides in the static meta: a traced bool would break
    # pallas_call(interpret=...) when the wrapper runs under jit (the
    # serving megastep jits the whole decode chunk).
    return (qw.qdtype, qw.block, _interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_linear_q(meta, x2d, data, scales, idx, val, bias):
    qdtype, block, interpret = meta
    bm = 128 if x2d.shape[0] >= 128 else 8
    xp, m = _pad_to(x2d, 0, bm)
    y = fused_linear_q_pallas(
        xp, data, scales, idx, val, bias,
        qdtype=qdtype, block=block, block_m=bm, interpret=interpret,
    )
    return y[:m]


def _fused_q_fwd(meta, x2d, data, scales, idx, val, bias):
    y = _fused_linear_q(meta, x2d, data, scales, idx, val, bias)
    return y, (x2d, data, scales, idx, val, bias)


def _fused_q_bwd(meta, res, dy):
    x2d, data, scales, idx, val, bias = res
    qdtype, block, interpret = meta
    # The quantized base is frozen *by construction* (int codes don't
    # differentiate): mirror fused_linear's w_frozen guard — no dense dw,
    # only dx (dense transpose vs the dequantized tile + sparse scatter)
    # and the (k, d_out) dval reduction.
    w = dequantize(QuantizedTensor(data, scales, qdtype, block, "float32"))
    dx = jnp.dot(dy, w.T).astype(x2d.dtype) + ref.sparse_delta_dx_ref(
        idx, val, dy, x2d.shape[1]
    ).astype(x2d.dtype)
    dval = _dval(x2d, idx, dy, interpret).astype(val.dtype)
    dbias = None if bias is None else jnp.sum(dy, axis=0).astype(bias.dtype)
    ddata = np.zeros(data.shape, dtype=jax.dtypes.float0)
    didx = np.zeros(idx.shape, dtype=jax.dtypes.float0)
    dscales = jnp.zeros(scales.shape, scales.dtype)  # frozen; DCE'd
    return dx, ddata, dscales, didx, dval, dbias


_fused_linear_q.defvjp(_fused_q_fwd, _fused_q_bwd)


def fused_linear_q(
    x: jax.Array,
    qw: QuantizedTensor,
    idx: jax.Array,
    val: jax.Array,
    bias: jax.Array | None = None,
) -> jax.Array:
    """y = x @ dequant(Wq) (+bias) + delta — the quantized-base fused path.

    jnp backend: dequantize + dot (XLA fuses; autodiff reaches only
    x/val/bias because the trainer never differentiates params). Pallas
    backends: tile-wise dequant in VMEM with a custom VJP that produces
    only ``dx``/``dval`` — training on a quantized base never materialises
    a dense weight gradient.
    """
    if get_backend() == "jnp":
        y = jnp.dot(x, dequantize(qw).astype(x.dtype))
        y = y + delta_apply(x, idx, val)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return y
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    y = _fused_linear_q(_q_meta(qw), x2d, qw.data, qw.scales, idx, val, bias)
    return y.reshape(*lead, qw.shape[-1])


def matmul_q(x: jax.Array, w, *, tp_col_sharded: bool = False) -> jax.Array:
    """x @ W for a plain *or* quantized W (no bypass; serving base matmul).

    With a QuantizedTensor on the Pallas backends this runs the fused
    dequant×matmul kernel with a zero bypass; on jnp it dequantizes and
    lets XLA fuse. Plain arrays pass straight to ``jnp.dot``.

    ``tp_col_sharded=True`` promises W is column-parallel over the serving
    mesh's ``model`` axis (the vocab-sharded head is the one call site):
    under a TP serve mesh the quantized kernel then dispatches through its
    shard_map wrapper, each shard sweeping its local d_out columns. The
    flag exists because a matmul can't infer col-vs-row placement from the
    operand at trace time — the caller knows the placement rule, so the
    caller says so.
    """
    if not isinstance(w, QuantizedTensor):
        return jnp.dot(x, w)
    if get_backend() == "jnp":
        return jnp.dot(x, dequantize(w).astype(x.dtype))
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    n = w.shape[-1]
    if tp_col_sharded:
        mesh = tp_ctx.serve_mesh()
        tp = tp_ctx.serve_tp()
        if mesh is not None and tp > 1 and n % tp == 0:
            y = matmul_q_cols_sharded(
                x2d, w, mesh, interpret=_interpret()
            )
            return y.reshape(*lead, n)
    # a zero bypass rides the fused kernel through the custom-VJP wrapper,
    # so the path stays differentiable (dx only) on the Pallas backends —
    # e.g. LoRA or untied-head training on a quantized base
    idx = jnp.zeros((1, n), jnp.int32)
    val = jnp.zeros((1, n), x.dtype)
    y = _fused_linear_q(_q_meta(w), x2d, w.data, w.scales, idx, val, None)
    return y.reshape(*lead, n)


# ------------------------------------------------------------ decode attention


def _serve_mesh_for_kv(num_kv_heads: int):
    """The serving mesh, when a Pallas kernel should dispatch through its
    shard_map wrapper: a TP serve mesh is live and the kv-head axis splits
    evenly across it. Returns None on the jnp backend (GSPMD partitions
    the oracle einsums itself) and for non-divisible head counts (the
    engine validates up front, so that's only reachable from ad-hoc
    callers — they get the replicated kernel, still correct)."""
    mesh = tp_ctx.serve_mesh()
    tp = tp_ctx.serve_tp()
    if mesh is None or tp <= 1 or get_backend() == "jnp":
        return None
    if num_kv_heads % tp:
        return None
    return mesh


def decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, kv_valid_len,
    k_scale: jax.Array | None = None, v_scale: jax.Array | None = None,
) -> jax.Array:
    """Batched single-token GQA attention for the serving decode hot path.

    q (B, 1, H, hd) against a (B, Smax, Hkv, hd) slot cache with per-slot
    ``kv_valid_len``. jnp backend: the gathered-einsum oracle; Pallas
    backends: the online-softmax kernel (grid slot × kv-head, f32
    accumulation in VMEM). With ``k_scale``/``v_scale`` (B, groups, Hkv)
    the cache is int8 and every path dequantizes tile-wise (DESIGN §15).
    Dispatch policy — *when* this replaces the dense masked softmax —
    lives in ``models.attention.attention``.
    """
    if get_backend() == "jnp":
        if k_scale is not None:
            return ref.decode_attention_q_ref(
                q, k, v, k_scale, v_scale, kv_valid_len
            )
        return ref.decode_attention_ref(q, k, v, kv_valid_len)
    mesh = _serve_mesh_for_kv(k.shape[-2])
    if mesh is not None:
        return decode_attention_sharded(
            q, k, v, kv_valid_len, mesh,
            k_scale=k_scale, v_scale=v_scale,
            interpret=_interpret(),
        )
    return decode_attention_pallas(
        q, k, v, kv_valid_len, k_scale=k_scale, v_scale=v_scale,
        interpret=_interpret(),
    )


def paged_decode_attention(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array, table: jax.Array,
    kv_valid_len,
    k_scale: jax.Array | None = None, v_scale: jax.Array | None = None,
) -> jax.Array:
    """Block-table decode attention for the paged serving core.

    q (B, 1, H, hd) against a (N, P, Hkv, hd) block pool routed through a
    (B, n_pages) block table with per-slot ``kv_valid_len``. jnp backend:
    gather-then-softmax oracle; Pallas backends: the scalar-prefetch
    kernel that DMAs physical pages straight from the pool (no contiguous
    gather ever materialises). With ``k_scale``/``v_scale`` (N, Hkv) the
    pool is int8 and the scales prefetch beside the table (DESIGN §15).
    """
    if get_backend() == "jnp":
        if k_scale is not None:
            return ref.paged_decode_attention_q_ref(
                q, k_pool, v_pool, k_scale, v_scale, table, kv_valid_len
            )
        return ref.paged_decode_attention_ref(q, k_pool, v_pool, table, kv_valid_len)
    mesh = _serve_mesh_for_kv(k_pool.shape[-2])
    if mesh is not None:
        return paged_decode_attention_sharded(
            q, k_pool, v_pool, table, kv_valid_len, mesh,
            k_scale=k_scale, v_scale=v_scale,
            interpret=_interpret(),
        )
    return paged_decode_attention_pallas(
        q, k_pool, v_pool, table, kv_valid_len,
        k_scale=k_scale, v_scale=v_scale,
        interpret=_interpret(),
    )


def prefill_attention(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array, table: jax.Array,
    q_offset, kv_valid_len,
    k_scale: jax.Array | None = None, v_scale: jax.Array | None = None,
) -> jax.Array:
    """Query-chunk × paged-KV attention for chunked prefill (DESIGN §11).

    q (B, C, H, hd) against a (N, P, Hkv, hd) block pool routed through a
    (B, n_pages) block table; per-slot ``q_offset`` anchors the chunk's
    intra-causal mask and ``kv_valid_len`` is the post-write cache
    frontier. jnp backend: gather-then-masked-softmax oracle; Pallas
    backends: the scalar-prefetch page-sweep kernel (physical pages DMA
    straight from the pool, online softmax in VMEM). With ``k_scale``/
    ``v_scale`` (N, Hkv) the pool is int8, dequantized per page tile.
    """
    if get_backend() == "jnp":
        if k_scale is not None:
            return ref.paged_prefill_attention_q_ref(
                q, k_pool, v_pool, k_scale, v_scale, table,
                q_offset, kv_valid_len,
            )
        return ref.paged_prefill_attention_ref(
            q, k_pool, v_pool, table, q_offset, kv_valid_len
        )
    mesh = _serve_mesh_for_kv(k_pool.shape[-2])
    if mesh is not None:
        return paged_prefill_attention_sharded(
            q, k_pool, v_pool, table, q_offset, kv_valid_len, mesh,
            k_scale=k_scale, v_scale=v_scale,
            interpret=_interpret(),
        )
    return paged_prefill_attention_pallas(
        q, k_pool, v_pool, table, q_offset, kv_valid_len,
        k_scale=k_scale, v_scale=v_scale,
        interpret=_interpret(),
    )


# ----------------------------------------------------------------- topk select


def topk_select(w: jax.Array, k: int) -> jax.Array:
    """Offline Phase-1 selection; (d_in, d_out) -> (k, d_out) int32."""
    if get_backend() == "jnp":
        return ref.topk_select_ref(w, k)
    return topk_select_pallas(w, k, interpret=_interpret())
