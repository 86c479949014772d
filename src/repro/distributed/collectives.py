"""Collectives: TP-serving shard_map plumbing + gradient-compression hooks.

**Serving (DESIGN §14).** The sharded engine leans on GSPMD for every
dense collective — row-parallel o/down matmuls psum their partial sums,
the vocab-sharded head all-gathers at the sampler's argmax — but the
Pallas kernels are opaque to the partitioner, so their sharded dispatch
wraps each kernel in :func:`tp_shard_map` over the ``model`` axis: every
shard runs the SAME grid shape on its local kv-head (or d_out-column)
slice, and the merge is absorbed by the first row-parallel matmul after
the kernel (no collective inside the mapped body). Per-megastep
collective inventory, all GSPMD-inserted: one psum per o-proj and one
per down-proj per layer, one logits all-gather per sampled position —
identical across the mixed/plain/spec/ngram megastep kinds because they
all bottom out in the same chunk/decode forwards.

**Training.** NeuroAda's primary distributed dividend is *structural*
gradient compression: the data-parallel all-reduce carries (…, k, d_out)
delta grads — k/d_in of dense traffic (4096× for LLaMA-7B at k=1). This
module adds an *optional* second stage — error-feedback int8
quantisation — for the baselines (full/masked) whose grads are still
dense, and for NeuroAda at large k.

``quantize``/``dequantize`` are pure and run *before* the pjit-inserted
all-reduce when applied inside a shard_map'd grad step; used standalone
(pjit path) they model the numerics so the EF residual machinery is tested
even where GSPMD owns the collective. Integration point:
``trainer.make_train_step(grad_transform=ef_int8(...))``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def tp_shard_map(fn, mesh, in_specs, out_specs):
    """shard_map a kernel body over the serving mesh.

    ``check_vma=False``: the bodies are opaque Pallas calls (or their
    interpret twins) — replication checking cannot see through them, and
    every output is explicitly spec'd anyway."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def tp_psum(x: jax.Array, axis_name: str = "model") -> jax.Array:
    """Merge row-parallel partial sums inside a shard_map body."""
    return jax.lax.psum(x, axis_name)


def tp_all_gather(
    x: jax.Array, axis: int = -1, axis_name: str = "model"
) -> jax.Array:
    """Rebuild a full tensor from per-shard slices (tiled along ``axis``)
    inside a shard_map body — e.g. vocab-sharded logits before a host
    fetch that wants the whole row."""
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)


class EFState(NamedTuple):
    residual: object  # error-feedback accumulator, same tree as grads


def quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def ef_int8():
    """Error-feedback int8 grad transform: (grads, state) -> (grads, state)."""

    def init(grads):
        return EFState(
            jax.tree.map(
                lambda g: None if g is None else jnp.zeros(g.shape, jnp.float32),
                grads,
                is_leaf=lambda x: x is None,
            )
        )

    def apply(grads, state: EFState):
        def one(g, r):
            if g is None:
                return None, None
            corrected = g.astype(jnp.float32) + r
            q, s = quantize(corrected)
            deq = dequantize(q, s)
            return deq.astype(g.dtype), corrected - deq

        flat = jax.tree.map(one, grads, state.residual, is_leaf=lambda x: x is None)
        new_g = jax.tree.map(
            lambda p: p[0], flat, is_leaf=lambda x: isinstance(x, tuple) or x is None
        )
        new_r = jax.tree.map(
            lambda p: p[1], flat, is_leaf=lambda x: isinstance(x, tuple) or x is None
        )
        return new_g, EFState(new_r)

    return init, apply


def collective_bytes_saved(k: int, d_in: int) -> float:
    """The paper's ratio applied to DP traffic: dense vs NeuroAda grads."""
    return d_in / k
