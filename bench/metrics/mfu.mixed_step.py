"""Mixed prefill+decode steps' share of the chip's peak: the real tokens'
forward operations in those steps over their summed wall time and the
bf16 peak. Padded rows of the step's buffer never count. Moves
itl_p95_ms."""


def read(ctx):
    mixed = [s for s in ctx.steps or [] if s["kind"] == "mixed"]
    wall = sum(s["t1"] - s["t0"] for s in mixed)
    if not mixed or wall <= 0:
        return None
    return 100.0 * sum(s["flops"] for s in mixed) / wall / ctx.peak["bf16_flops_per_s"]
