"""Training step's share of the chip's peak: the operations a NeuroAda step
needs (``bench.costs.model.train_step``) times the traced window's steps,
over the window's host time and the bf16 peak. Moves train_tokens_per_s."""


def read(ctx):
    if not ctx.steps or ctx.window_host_s <= 0:
        return None
    return 100.0 * ctx.steps * ctx.step_flops / ctx.window_host_s / ctx.peak["bf16_flops_per_s"]
