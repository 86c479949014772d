"""Whole runs of both cells at a tiny size on the CPU: sound runs come out
correct, and each fault a cell can have, planted in the timed path
underneath, makes ``correct`` come out false."""

import importlib

import pytest

from bench import faults, run
from bench.tests.tiny import SERVE, TRAIN, tiny_cell


def _result(cell):
    raw = importlib.import_module(f"bench.cells.{cell.mix['kind']}").run(cell)
    return run.result_line(cell, raw)


@pytest.mark.parametrize("name", [TRAIN, SERVE])
def test_a_sound_run_is_correct(name):
    out = _result(tiny_cell(name))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]


@pytest.mark.parametrize("fault,make", [
    ("state_unchanged", faults.unchanged_state_trainer),
    ("half_batch", faults.trainer(loss=faults.half_batch)),
])
def test_a_training_fault_is_caught(fault, make):
    out = _result(tiny_cell(TRAIN, trainer=make))
    assert not out["correct"], (fault, out["checks"])


@pytest.mark.parametrize("fault,make", [
    ("altered_token", faults.engine(sampler=faults.altered_token)),
    ("cache_unchanged", faults.engine(prefill_chunk=faults.cache_unchanged("prefill_chunk"),
                                      decode_step=faults.cache_unchanged("decode_step"))),
])
def test_a_serving_fault_is_caught(fault, make):
    out = _result(tiny_cell(SERVE, engine=make))
    assert not out["correct"], (fault, out["checks"])


# other mixes the serving driver takes as data: unmerged tenants, open-loop
# arrivals, bursty arrivals on shared prefixes
OTHER_MIXES = {
    "tenants": {"adapter": "tenants", "tenants": {"count": 3, "s": 1.0}},
    "poisson": {"arrivals": {"process": "poisson", "rate": 30.0, "lead_s": 0.2}},
    "gamma_shared_prefix": {"arrivals": {"process": "gamma", "rate": 30.0, "cv": 3.0,
                                         "lead_s": 0.2},
                            "shared_prefix": {"length": 12, "count": 2}},
}


@pytest.mark.parametrize("mix", sorted(OTHER_MIXES))
def test_a_sound_run_of_another_serving_mix_is_correct(mix):
    out = _result(tiny_cell(SERVE, seconds=0.6, mix=OTHER_MIXES[mix]))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"serve_tokens_per_s", "setup_s"} <= set(out["metrics"])


def test_an_altered_token_is_caught_among_tenants():
    out = _result(tiny_cell(SERVE, mix=OTHER_MIXES["tenants"],
                            engine=faults.engine(sampler=faults.altered_token)))
    assert not out["correct"], out["checks"]
