"""The control at a size a test run holds: the plain reference computed
with fp8 matrix products, put in the program's place, reads further from
the f32 reference than the program does, and the cell's own comparison
finds it not correct. (At the cells' own size the readings that set the
limits are taken on the chip by bench/control.py.)"""

import functools

from bench import adapters, program, traffic, weights
from bench.cells import serve, train
from bench.tests.tiny import SERVE, TRAIN, tiny_cell


@functools.lru_cache(maxsize=None)
def _train_run():
    return train.run(tiny_cell(TRAIN, seed=1, control_mm="fp8"))


@functools.lru_cache(maxsize=None)
def _serve_run():
    return serve.run(tiny_cell(SERVE, seed=1, seconds=1.0, control_mm="fp8"))


def test_fp8_control_reads_above_the_program_in_training():
    r = _train_run()
    sound = {k: v["value"] for k, v in r["checks"].items()}
    for name, c in r["control"]["checks"].items():
        assert c["value"] > sound[name], (name, c["value"], sound[name])


def test_fp8_control_is_not_correct_in_training():
    r = _train_run()
    assert r["correct"], r["checks"]
    assert r["control"]["correct"] is False, r["control"]["checks"]


def test_fp8_control_reads_above_the_program_in_serving():
    cell = tiny_cell(SERVE, seed=1)
    cfg = cell.cfg
    params = weights.make_params(cfg, cfg["init"], cell.seed)
    served = adapters.build(cell, program.model(cfg), params)
    requests = traffic.serve_requests(cell.mix, cfg["vocab_size"], cell.seed)
    loop = serve.Loop(served, requests, cell.mix["arrivals"], cfg)
    loop.fill()
    for _ in range(40):  # a fixed number of steps: the same requests finish on every run
        loop.step()
    done = [t for t in loop.done if t.req.reason in serve.DONE_OK]
    length = cfg["engine"]["max_len"]
    sound = serve.served_gaps(params, served.delta, done, cfg, length)
    control = serve.served_gaps(params, served.delta, done, cfg, length, mm="fp8", against=True)
    assert len(done) >= 8
    assert max(control) > max(sound), (control, sound)


def test_fp8_control_is_not_correct_in_serving():
    r = _serve_run()
    assert r["correct"], r["checks"]
    assert r["control"]["correct"] is False, r["control"]["checks"]
