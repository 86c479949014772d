"""Share of the traced serving window in which no operation ran on the
device: 1 - busy / window. Moves serve_tokens_per_s."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0 or not t.ops:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
