"""Roofline share of the paged prefill-attention kernel: the least time of
its calls in the mixed steps of the traced window over their device time.
Each mixed step calls it once per layer with every slot's chunk of query
rows, against the pages up to each slot's cache frontier
(``bench.costs.kernels.paged_prefill_attn``). Moves serve_tokens_per_s."""

from bench.costs.kernels import paged_prefill_attn, roofline_seconds
from bench.weights import dims


def is_call(out, args) -> bool:
    """The kernel's call in a TPU v5e trace: block table s32[N], chunk
    offsets s32[B] and frontiers s32[B], q bf16[B,KV,C*G,hd], K and V pools
    bf16[N,page,KV*hd]."""
    return (len(args) == 6 and [a[0] for a in args[:3]] == ["s32"] * 3
            and len(args[3][1]) == 4 and len(args[4][1]) == 3 and len(args[5][1]) == 3)


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    seconds = sum(e.end - e.start for e, out, args in t.kernel_calls() if is_call(out, args))
    mixed = [s for s in ctx.steps or [] if s["kind"] == "mixed"]
    if seconds <= 0 or not mixed:
        return None
    cfg = ctx.cell.cfg
    d, eng = dims(cfg), cfg["engine"]
    least = sum(roofline_seconds(*paged_prefill_attn(
        s["frontier"], eng["slots"], eng["prefill_chunk"], d["H"], d["KV"], d["hd"],
        eng["page_size"]), ctx.peak)[0] for s in mixed)
    return 100.0 * d["L"] * least / seconds
