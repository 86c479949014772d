"""Roofline share of the paged decode-attention kernel: the least time of
its calls in the decode megasteps of the traced window over their device
time. Each decoded token of each active slot is one slot of one call per
layer, reading the pages its context fills
(``bench.costs.kernels.paged_decode_attn``). Moves serve_tokens_per_s."""

from bench.costs.kernels import paged_decode_attn, roofline_seconds
from bench.weights import dims


def is_call(out, args) -> bool:
    """The kernel's call in a TPU v5e trace: block table s32[N], lengths
    s32[B], q bf16[B,KV,G,hd], K and V pools bf16[N,page,KV*hd]."""
    return (len(args) == 5 and [a[0] for a in args[:2]] == ["s32", "s32"]
            and len(args[2][1]) == 4 and len(args[3][1]) == 3 and len(args[4][1]) == 3)


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    seconds = sum(e.end - e.start for e, out, args in t.kernel_calls() if is_call(out, args))
    cfg = ctx.cell.cfg
    d = dims(cfg)
    contexts = [c for s in ctx.steps or [] if s["kind"] == "decode" for c in s["decode_ctx"]]
    if seconds <= 0 or not contexts:
        return None
    cost = paged_decode_attn(contexts, d["H"], d["KV"], d["hd"], cfg["engine"]["page_size"])
    return 100.0 * d["L"] * roofline_seconds(*cost, ctx.peak)[0] / seconds
