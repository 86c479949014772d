"""A cell of the real benchmark cut to a size the CPU runs in seconds: the
same files, drivers, reference and limits, with tiny widths, depth,
vocabulary and traffic."""

from __future__ import annotations

import copy
import time

from bench import common
from bench.cells.base import Cell

TRAIN = "train.packed2k.qwen2-1.5b"
SERVE = "serve.merged-chat.qwen2-1.5b"
# limits of the tiny size: with a few hundred values per leaf, bf16 moves a
# leaf's norm further than at the cells' size (sound tiny runs read
# loss 0.0006-0.0017, grad 0.0025-0.0067, change 0.0015-0.0035); the tiny
# model's logits lie closer together than the published one's, so its
# served gap is held closer too (sound tiny runs read 0-0.028, the fp8
# control 0.13-0.32, seeds 1-6)
TINY_LIMITS = {"loss_gap": 0.01, "grad_gap": 0.02, "change_gap": 0.02, "served_gap": 0.06}


def tiny_cell(name: str, seed: int = 2**33 + 5, seconds: float = 0.3, mix: dict | None = None,
              **hooks) -> Cell:
    """The cell ``name`` at a tiny size; ``mix`` replaces keys of its
    traffic mix (after the tiny sizes are set)."""
    import jax

    spec = common.benchmark_spec()
    w = common.workload(spec, name)
    cfg = copy.deepcopy(common.config_file(spec, w["config"]))
    # the embedding (the tied head) keeps the logits' published spread
    cfg["init"]["embed_std"] *= (cfg["hidden_size"] / 128) ** 0.5
    cfg.update(num_hidden_layers=2, hidden_size=128, intermediate_size=256,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
    overrides, mix = mix or {}, copy.deepcopy(common.traffic_file(w["traffic"]))
    if mix["kind"] == "train":
        mix.update(batch=2, seq=64, batches=6)
    else:
        mix.update(arrivals=dict(mix["arrivals"], clients=4),
                   check={"requests": 4, "min_tokens": 40},
                   prompt=dict(mix["prompt"], median=20, min=4, max=60),
                   output=dict(mix["output"], median=8, min=2, max=16))
        cfg["engine"].update(slots=4, max_len=96, num_blocks=24, prefill_chunk=16,
                             decode_chunk=4)
    mix.update(overrides)
    limits = {k: TINY_LIMITS.get(k, v) for k, v in common.limits_file(name).items()}
    return Cell(spec=spec, workload=w, cfg=cfg, mix=mix, limits=limits,
                seed=seed, seconds=seconds, trace=False, t_start=time.perf_counter(),
                devices=jax.devices(), hooks=hooks)
