"""Serving's share of the chip's peak: the forward operations of the real
tokens the window's steps ran (prompt positions once, decode inputs, the
head where a token was sampled; ``bench.costs.model``) over the window's
host time and the bf16 peak. Moves serve_tokens_per_s."""


def read(ctx):
    steps = ctx.steps or []
    if not steps or ctx.window_host_s <= 0:
        return None
    return 100.0 * sum(s["flops"] for s in steps) / ctx.window_host_s / ctx.peak["bf16_flops_per_s"]
