"""Pallas TPU kernels for the paper's compute hot-spots.

sparse_delta / fused_linear — the paper's "fused scatter-add" bypass path
(footnote 2), TPU-adapted as tile-densified MXU products (DESIGN.md §2.2);
sparse_delta_batched — the multi-tenant serving variant: N stacked adapters
selected per batch row (DESIGN.md §7);
topk_select — Alg. 1 Phase 1 offline selection;
flash_attention — fused online-softmax attention (added from the §Perf
memory-term analysis).

ops.py holds the jit'd public wrappers with backend dispatch (pallas on a
TPU, jnp elsewhere, pallas_interpret in tests); ref.py the pure-jnp oracles.
"""

from repro.kernels import ops, ref
from repro.kernels.flash_attention import (
    flash_attention_fwd_pallas,
    flash_attention_gqa_pallas,
)
from repro.kernels.fused_linear import fused_linear_pallas
from repro.kernels.sparse_delta import (
    sparse_delta_batched_pallas,
    sparse_delta_dval_pallas,
    sparse_delta_pallas,
)
from repro.kernels.topk_select import topk_select_pallas

__all__ = [
    "flash_attention_fwd_pallas",
    "flash_attention_gqa_pallas",
    "fused_linear_pallas",
    "ops",
    "ref",
    "sparse_delta_batched_pallas",
    "sparse_delta_dval_pallas",
    "sparse_delta_pallas",
    "topk_select_pallas",
]
