import shutil
import subprocess
import sys

import pytest

from bench import common


def test_an_unknown_device_kind_is_refused():
    with pytest.raises(KeyError):
        common.peaks("TPU v9 imaginary")
    assert common.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_the_cpu_is_refused():
    import jax

    with pytest.raises(SystemExit):
        common.require_chips(jax, 1)


def test_a_bare_checkout_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    run = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train.packed2k.qwen2-1.5b",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode != 0
    assert run.stdout.strip() == ""
