"""Mean number of slots that did work (took prompt tokens or emitted) per
engine step in the traced window, counted from the requests' progress.
Moves serve_tokens_per_s."""


def read(ctx):
    steps = ctx.steps or []
    return sum(s["active"] for s in steps) / len(steps) if steps else None
