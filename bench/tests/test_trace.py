"""The reduction from a trace to busy time, kernel time and idle gaps."""

import pytest

from bench.run import reader
from bench.trace import Event, Reduced, op_family

paged_decode_attn_roofline = reader("paged_decode_attn_roofline")
fused_linear_roofline = reader("fused_linear_roofline")

# a Pallas kernel call as a TPU v5e trace names it (shortened layouts)
KERNEL_TEXT = ('%closed_call.26 = bf16[32,2,6,128]{3,2,1,0} custom-call(s32[5120]{0} %reshape.414, '
               's32[32]{0} %broadcast_add_fusion.6, bf16[32,2,6,128]{3,2,1,0} %pad_maximum_fusion.4, '
               'bf16[5120,16,256]{2,1,0} %copy-done, bf16[5120,16,256]{2,1,0} %reshape.416), '
               'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')


def _reduced():
    ops = [
        Event("fusion.3", 0.0, 1.0),
        Event(KERNEL_TEXT, 0.5, 2.0),  # overlaps the first
        Event("fusion.4", 3.0, 4.0),
        Event("convolution.1", 6.0, 6.5),
    ]
    host = [Event("bench.window", 0.0, 8.0), Event("bench.step", 0.0, 4.5),
            Event("bench.feed", 4.5, 5.0), Event("bench.step", 5.0, 8.0),
            Event("bench.emit", 7.0, 8.0)]
    return Reduced((0.0, 8.0), ops, host)


def test_busy_time_is_the_union_of_operations():
    r = _reduced()
    assert r.busy_intervals() == [(0.0, 2.0), (3.0, 4.0), (6.0, 6.5)]
    assert r.busy_s == pytest.approx(3.5)
    assert r.window_s == 8.0


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    gaps = _reduced().idle_gaps()
    assert gaps == [["bench.feed", pytest.approx(2.0)],  # 4.0 .. 6.0, longest first
                    ["bench.emit", pytest.approx(1.5)],  # 6.5 .. 8.0, mid 7.25
                    ["bench.step", pytest.approx(1.0)]]  # 2.0 .. 3.0
    assert sum(g[1] for g in gaps) == pytest.approx(8.0 - 3.5)


def test_kernel_time_and_op_families():
    r = _reduced()
    (event, out, args), = r.kernel_calls()
    assert out == ("bf16", (32, 2, 6, 128))
    assert args == [("s32", (5120,)), ("s32", (32,)), ("bf16", (32, 2, 6, 128)),
                    ("bf16", (5120, 16, 256)), ("bf16", (5120, 16, 256))]
    assert paged_decode_attn_roofline.is_call(out, args)
    assert fused_linear_roofline.cost(out, args) is None
    assert op_family(Event("%fusion.12 = bf16[8]{0} fusion(...)", 0, 1)) == "fusion"
    top = dict((k, v) for k, v in r.top_ops())
    assert top["fusion"] == pytest.approx(2.0)
    assert top["kernel bf16[32,2,6,128]"] == pytest.approx(1.5)


def _fixture(name):
    import gzip
    import json

    from bench.common import BENCH

    with gzip.open(BENCH / "tests" / "fixtures" / f"v5e_{name}_window.json.gz", "rt") as f:
        d = json.load(f)
    return Reduced(tuple(d["window"]), [Event(*o) for o in d["ops"]],
                   [Event(*h) for h in d["host"]])


def _ctx(r):
    from bench.common import load_json, peaks, BENCH
    from bench.metrics import Context

    class Cell:
        cfg = load_json(BENCH / "configs" / "qwen2-1.5b.json")

    return Context(cell=Cell(), trace=r, peak=peaks("TPU v5 lite"))


def test_a_recorded_training_window_reduces_to_kernel_rooflines():
    r = _fixture("train")
    assert r.busy_s == pytest.approx(r.window_s, rel=0.01)  # the step keeps the chip busy
    calls = r.kernel_calls()
    dval = reader("delta_dval_roofline")
    fused = [c for c in calls if fused_linear_roofline.cost(c[1], c[2])]
    grads = [c for c in calls if dval.cost(c[1], c[2])]
    assert len(fused) + len(grads) == len(calls) and fused and grads
    # the wgate/wup calls of qwen2-1.5b at 4 x 2048 rows
    assert ("bf16", (8192, 8960)) in {c[1] for c in fused}
    for m in (fused_linear_roofline, dval):
        share = m.read(_ctx(r))
        assert 0 < share < 100


def test_a_recorded_serving_window_finds_the_prefill_kernel_only():
    r = _fixture("serve")
    calls = r.kernel_calls()
    assert calls and not any(paged_decode_attn_roofline.is_call(o, a) for _, o, a in calls)
    assert all(reader("paged_prefill_attn_roofline").is_call(o, a) for _, o, a in calls)
    assert r.top_ops(1)[0][0] == "kernel bf16[32,2,1536,128]"  # paged prefill attention
    assert set(g[0] for g in r.idle_gaps()) <= {"bench.step", "bench.emit", "bench.submit",
                                               "host:outside-bench-spans"}


PREFILL_TEXT = ('%closed_call.11 = bf16[32,2,1536,128]{3,2,1,0} custom-call(s32[5120]{0} %r.235, '
                's32[32]{0} %g.665, s32[32]{0} %c.20, bf16[32,2,1536,128]{3,2,1,0} %copy.37, '
                'bf16[5120,16,256]{2,1,0} %r.237, bf16[5120,16,256]{2,1,0} %r.239), '
                'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')


def test_the_prefill_roofline_is_the_hand_count():
    prefill = reader("paged_prefill_attn_roofline")
    r = Reduced((0.0, 1.0), [Event(PREFILL_TEXT, 0.0, 0.02), Event(PREFILL_TEXT, 0.5, 0.53),
                             Event(KERNEL_TEXT, 0.6, 0.7)], [])
    ctx = _ctx(r)
    # two mixed steps (slots' frontiers) and a decode step, which it leaves out
    ctx.steps = [{"kind": "mixed", "frontier": [100, 16, 0]},
                 {"kind": "mixed", "frontier": [17]},
                 {"kind": "decode", "frontier": [5000]}]
    # per step: 12 heads of 128 over 256 query rows against the frontiers;
    # K and V pages of 16 positions x 2 heads x 128; q and out of 32 slots
    def least(total, pages):
        ops = 4 * 12 * 256 * 128 * total
        nbytes = 2 * (2 * pages * 16 * 256 + 2 * 32 * 256 * 12 * 128)
        return max(ops / 197e12, nbytes / 819e9)

    per_step = least(116, 7 + 1) + least(17, 2)
    assert prefill.read(ctx) == pytest.approx(100 * 28 * per_step / 0.05)
    assert not prefill.is_call(*r.kernel_calls()[2][1:])  # the decode kernel's call
