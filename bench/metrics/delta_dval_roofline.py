"""Roofline share of the bypass-value gradient kernel in the training step:
its calls' least time (``bench.costs.kernels.delta_dval``, from each
call's shapes as the trace gives them) over its device time. Moves
train_tokens_per_s."""

from bench.costs.kernels import delta_dval
from bench.metrics._kernel import share


def cost(out, args):
    """The kernel's call in a TPU v5e trace: x bf16[M,K], idx s32[k,N],
    dy bf16[M,N] -> dval f32[k,N]."""
    if len(args) != 3 or args[1][0] != "s32" or out is None or out[0] != "f32":
        return None
    (_, x), (_, idx), (_, dy) = args
    if len(x) != 2 or len(dy) != 2 or x[0] != dy[0] or idx[-1] != dy[1]:
        return None
    return delta_dval(x[0], x[1], dy[1], idx[0])


def read(ctx):
    return share(ctx, cost)
