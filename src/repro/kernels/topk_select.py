"""Pallas kernel for NeuroAda Phase 1: per-neuron top-k |magnitude| select.

Streams the weight matrix through VMEM in (bk, bn) tiles, maintaining a
running top-k (values + global indices) per output unit in VMEM scratch.
Each tile contributes its k local argmax candidates (iterative
max-and-mask); a candidate replaces the current running minimum when
strictly larger. Selection is offline/one-shot, but kernelising it keeps
Phase 1 out of HBM-bandwidth trouble for the 405B-scale matrices where a
full |W| sort would thrash.

Output index order is unspecified (a set per column); the oracle sorts by
magnitude — tests compare as sets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_delta import pick_block

_NEG = float("-inf")


def _topk_kernel(w_ref, idx_ref, vals_ref, idxs_ref, *, k: int, bk: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, _NEG)
        idxs_ref[...] = jnp.zeros_like(idxs_ref)

    a = jnp.abs(w_ref[...].astype(jnp.float32))  # (bk, bn)
    rows = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    base = t * bk
    for _ in range(k):
        v = jnp.max(a, axis=0)  # (bn,)
        m = jnp.argmax(a, axis=0).astype(jnp.int32)  # (bn,)
        a = jnp.where(rows == m[None, :], _NEG, a)  # mask the taken entry
        # insert (v, base+m) into the running top-k where it beats the min
        cur = vals_ref[...]  # (k, bn)
        cur_min = jnp.min(cur, axis=0)
        slot = jnp.argmin(cur, axis=0).astype(jnp.int32)  # (bn,)
        take = v > cur_min
        krows = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0)
        hit = (krows == slot[None, :]) & take[None, :]
        vals_ref[...] = jnp.where(hit, v[None, :], cur)
        idxs_ref[...] = jnp.where(hit, (base + m)[None, :], idxs_ref[...])

    @pl.when(t == pl.num_programs(1) - 1)
    def _flush():
        idx_ref[...] = idxs_ref[...]


def topk_select_pallas(
    w: jax.Array,
    k: int,
    *,
    block_k: int = 512,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """w (d_in, d_out) -> idx (k, d_out) int32 (unordered per column)."""
    d_in, d_out = w.shape
    bk = pick_block(d_in, block_k)
    bn = min(block_n, d_out)
    if d_in % bk or d_out % bn:
        raise ValueError(f"{w.shape} must tile by ({bk}, {bn})")
    grid = (d_out // bn, d_in // bk)
    return pl.pallas_call(
        functools.partial(_topk_kernel, k=k, bk=bk),
        grid=grid,
        in_specs=[pl.BlockSpec((bk, bn), lambda j, t: (t, j))],
        out_specs=pl.BlockSpec((k, bn), lambda j, t: (0, j)),
        out_shape=jax.ShapeDtypeStruct((k, d_out), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((k, bn), jnp.float32),
            pltpu.VMEM((k, bn), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="topk_select",
    )(w)
