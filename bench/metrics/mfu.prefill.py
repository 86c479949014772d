"""Prefill's share of the chip's peak: the prompt positions' forward
operations over the summed wall time of the mixed steps that ran them and
the bf16 peak. Moves ttft_p50_ms."""


def read(ctx):
    mixed = [s for s in ctx.steps or [] if s["kind"] == "mixed"]
    wall = sum(s["t1"] - s["t0"] for s in mixed)
    if not mixed or wall <= 0:
        return None
    return 100.0 * sum(s["prefill_flops"] for s in mixed) / wall / ctx.peak["bf16_flops_per_s"]
