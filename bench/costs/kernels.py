"""Operations and bytes of one kernel call, from the call's shapes.

These are what the call's algorithm needs, padding included: the one-hot
or densified form a kernel may use to compute the bypass is extra work
that a roofline share built on these counts shows as lost time.
"""

from __future__ import annotations

import math


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_ops, t_bytes = ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def fused_linear(m: int, k_in: int, n: int, k: int, bias: bool, itemsize: int = 2):
    """y (m, n) = x (m, k_in) @ W (k_in, n) + bypass (k, n) [+ bias]."""
    ops = 2 * m * k_in * n + 2 * m * k * n
    nbytes = itemsize * (m * k_in + k_in * n + m * n + k * n + (n if bias else 0)) + 4 * k * n
    return ops, nbytes


def delta_dval(m: int, k_in: int, n: int, k: int, itemsize: int = 2):
    """dval (k, n) f32 = Σ_rows dy (m, n) · x (m, k_in)[idx]."""
    ops = 2 * m * k * n
    nbytes = itemsize * (m * k_in + m * n) + 4 * k * n + 4 * k * n
    return ops, nbytes


def paged_decode_attn(contexts, q_heads: int, kv_heads: int, head_dim: int,
                      page: int, itemsize: int = 2):
    """One token per slot against its ``contexts[i]`` cached positions:
    q·k and p·v over the pages those positions fill."""
    ops = 4 * q_heads * head_dim * sum(contexts)
    pages = sum(math.ceil(c / page) for c in contexts)
    nbytes = itemsize * (2 * pages * page * kv_heads * head_dim
                         + 2 * len(contexts) * q_heads * head_dim)
    return ops, nbytes


def paged_prefill_attn(frontiers, slots: int, chunk: int, q_heads: int, kv_heads: int,
                       head_dim: int, page: int, itemsize: int = 2):
    """A chunk of ``chunk`` query rows in each of ``slots`` slots (the
    call's shape, padding rows included) against the cached positions up
    to each slot's frontier ``frontiers[i]``: q·k and p·v over the pages
    those positions fill, the query tile read and the output written once."""
    ops = 4 * q_heads * chunk * head_dim * sum(frontiers)
    pages = sum(math.ceil(f / page) for f in frontiers)
    nbytes = itemsize * (2 * pages * page * kv_heads * head_dim
                         + 2 * slots * chunk * q_heads * head_dim)
    return ops, nbytes
