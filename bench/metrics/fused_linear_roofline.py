"""Roofline share of the fused base-matmul + bypass kernel in the training
step: its calls' least time (``bench.costs.kernels.fused_linear``, from
each call's shapes as the trace gives them) over its device time. Moves
train_tokens_per_s."""

from bench.costs.kernels import fused_linear
from bench.metrics._kernel import share


def cost(out, args):
    """The kernel's call in a TPU v5e trace: x bf16[M,K], W bf16[K,N],
    idx s32[k,N], val [k,N], bias row [1,N] -> [M,N]."""
    if len(args) != 5 or args[2][0] != "s32":
        return None
    (_, x), (_, w), (_, idx), _, _ = args
    if len(x) != 2 or len(w) != 2 or x[1] != w[0] or idx[-1] != w[1]:
        return None
    return fused_linear(x[0], x[1], w[1], idx[0], bias=True)


def read(ctx):
    return share(ctx, cost)
