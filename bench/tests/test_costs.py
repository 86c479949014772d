import pytest

from bench import common
from bench.costs import kernels, model


def _cfg(name):
    return common.load_json(common.BENCH / "configs" / f"{name}.json")


def test_parameter_counts_match_the_published_models():
    # qwen2-1.5b: the count the program printed on the chip (PR 11)
    assert model.total_params(_cfg("qwen2-1.5b")) == 1_543_714_304
    # qwen2.5-3b by hand: 36 x (77,070,336 linears + 2,560 biases + 4,096
    # norm scales) + 151,936 x 2,048 embedding + 2,048 final norm
    assert model.total_params(_cfg("qwen2.5-3b")) == 36 * (77_070_336 + 2_560 + 4_096) \
        + 151_936 * 2_048 + 2_048 == 3_085_938_688


def test_training_counts_four_p_per_token_and_no_weight_gradient():
    cfg = _cfg("qwen2.5-3b")
    b, s, k = 2, 2048, 1
    total = model.train_step(cfg, b, s, k)
    attn = 3 * b * sum(model.attention_forward(cfg, c) for c in range(1, s + 1))
    bypass = 6 * b * s * 36 * model.layer_bypass_outputs(cfg, k)
    dense = 4 * b * s * 36 * model.layer_matmul_params(cfg) + 4 * b * (s - 1) * 151_936 * 2_048
    assert total == dense + attn + bypass
    assert model.layer_bypass_outputs(cfg, k) == 2048 + 256 + 256 + 2048 + 3 * 11008 - 11008 + 2048


def test_serving_counts_two_p_per_token_and_the_head_where_sampled():
    cfg = _cfg("qwen2-1.5b")
    no_head = model.serve_token(cfg, 99, head=False)
    assert model.serve_token(cfg, 99, head=True) - no_head == 2 * 151_936 * 1_536
    assert no_head == 2 * 28 * model.layer_matmul_params(cfg) + 4 * 28 * 12 * 128 * 100
    span = model.serve_positions(cfg, 10, 20, heads=3)
    assert span == sum(model.serve_token(cfg, p, head=False) for p in range(10, 20)) \
        + 3 * 2 * 151_936 * 1_536


def test_kernel_counts_by_hand():
    ops, nbytes = kernels.fused_linear(4096, 2048, 256, 1, bias=True)
    assert ops == 2 * 4096 * 2048 * 256 + 2 * 4096 * 256
    assert nbytes == 2 * (4096 * 2048 + 2048 * 256 + 4096 * 256 + 256 + 256) + 4 * 256
    ops, nbytes = kernels.delta_dval(4096, 2048, 256, 1)
    assert ops == 2 * 4096 * 256
    assert nbytes == 2 * (4096 * 2048 + 4096 * 256) + 8 * 256
    ops, nbytes = kernels.paged_decode_attn([17, 32], 12, 2, 128, 16)
    assert ops == 4 * 12 * 128 * 49
    assert nbytes == 2 * (2 * 4 * 16 * 2 * 128 + 2 * 2 * 12 * 128)


def test_roofline_takes_the_larger_bound():
    peak = common.peaks("TPU v5 lite")
    assert kernels.roofline_seconds(197e12, 0, peak) == (pytest.approx(1.0), "compute")
    assert kernels.roofline_seconds(0, 819e9, peak) == (pytest.approx(1.0), "memory")
