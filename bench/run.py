#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration file
under ``bench/configs`` and a traffic mix under ``bench/traffic``; the
mix's ``kind`` picks the driver in ``bench/cells``, and each per-layer
metric is read by ``bench/metrics/<name>.py``. With ``--trace 0`` the result
holds the cell's end-to-end metrics, with ``--trace 1`` its per-layer ones.
The last line of standard output is one JSON object; the last lines of
standard error give each number compared for ``correct`` beside its limit.
With no accelerator, too few chips or no program beside it, the run exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
# the TPU runtime logs under /tmp unless told otherwise: a run writes only in its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import common  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def compile_cache() -> None:
    """The program's persistent compile cache (the directory
    JAX_COMPILATION_CACHE_DIR names, else ``.jax_cache`` at the checkout's
    root), keeping every program, so that every run of a cell in this
    checkout finds the first run's."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def make_cell(args, devices, t_start=T_START):
    from bench.cells.base import Cell

    spec = common.benchmark_spec()
    w = common.workload(spec, args.workload)
    return Cell(spec=spec, workload=w, cfg=common.config_file(spec, w["config"]),
                mix=common.traffic_file(w["traffic"]), limits=common.limits_file(w["name"]),
                seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                t_start=t_start, devices=devices)


def reader(name: str):
    """The reader module ``bench/metrics/<name>.py`` (names may hold dots)."""
    path = common.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def per_layer(cell, raw: dict, reduced) -> dict:
    """Each of the cell's per-layer metrics that its reader finds."""
    from bench.metrics import Context

    ctx = Context(cell=cell, trace=reduced, peak=common.peaks(cell.devices[0].device_kind),
                  **raw["layer_ctx"])
    out = {}
    for m in common.per_layer_for(cell.spec, cell.workload["name"]):
        value = reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(cell, raw: dict) -> dict:
    dev = cell.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": raw["memory_peak_bytes"]}
    out = {"correct": bool(raw["correct"] and raw["failed"] == 0),
           "attempted": raw["attempted"], "failed": raw["failed"]}
    if cell.trace:
        from bench import trace

        reduced = trace.load(raw["trace_dir"])
        shutil.rmtree(raw["trace_dir"], ignore_errors=True)
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        out["metrics"] = per_layer(cell, raw, reduced)
        out["device"] = device
        out["breakdown"] = {"device_ops": reduced.top_ops(10), "idle_gaps": reduced.idle_gaps(10)}
    else:
        units = {m["name"]: m["unit"] for m in cell.spec["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]} for k, v in raw["e2e"].items()}
        metrics["setup_s"] = {"value": raw["setup_s"], "unit": units["setup_s"]}
        wanted = {m["name"] for m in common.end_to_end_for(cell.spec, cell.workload["name"])}
        out["metrics"] = {k: v for k, v in metrics.items() if k in wanted}
        out["device"] = device
    out["checks"] = raw["checks"]
    return out


def run_cell(cell) -> dict:
    driver = importlib.import_module(f"bench.cells.{cell.mix['kind']}")
    raw = driver.run(cell)
    print(json.dumps({"notes": raw.get("notes", {})}, default=float), file=sys.stderr)
    return result_line(cell, raw)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import program

    program.import_program()
    compile_cache()
    import jax

    spec = common.benchmark_spec()
    w = common.workload(spec, args.workload)
    devices = common.require_chips(jax, w["chips"])
    cell = make_cell(args, devices)
    out = run_cell(cell)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
