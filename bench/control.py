#!/usr/bin/env python3
"""Readings behind the limits of ``correct``, on the chip, at a cell's own
size: one JSON line per seed and variant, in one process so that the
set-up compiles once.

    python3 bench/control.py --workload <name> --seeds 1,2,3 \
        --variants sound,program_int8,half_batch --seconds 30

- ``sound``: the program as the cell runs it, and in the same run the
  control (variant ``fp8_control``): the reference computed with float8
  matrix products, put in the program's place and judged by the cell's
  own comparison, so that it has to come out not correct;
- ``program_int8``: the program's own int8-base path switched on;
- ``half_batch`` (training): the loss over half the batch's rows.

The benchmark's own runs never run this.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # as bench/run.py


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="sound")
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    from bench import common, faults, program
    from bench import run as brun

    program.import_program()
    brun.compile_cache()
    import jax

    w = common.workload(common.benchmark_spec(), args.workload)
    devices = common.require_chips(jax, w["chips"])
    kind = common.traffic_file(w["traffic"])["kind"]
    hooks = {
        "sound": {"control_mm": "fp8"},
        "program_int8": ({"trainer": faults.int8_base_trainer} if kind == "train"
                         else {"base_dtype": "int8"}),
        "half_batch": {"trainer": faults.trainer(loss=faults.half_batch)},
    }
    for seed in [int(s) for s in args.seeds.split(",")]:
        for variant in args.variants.split(","):
            a = brun.parse(["--workload", args.workload, "--seed", str(seed),
                            "--seconds", str(args.seconds), "--trace", "0"])
            cell = brun.make_cell(a, devices, t_start=time.perf_counter())
            cell.hooks = dict(hooks[variant])
            driver = __import__(f"bench.cells.{kind}", fromlist=["run"])
            raw = driver.run(cell)
            print(json.dumps({"seed": seed, "variant": variant,
                              "correct": bool(raw["correct"] and raw["failed"] == 0),
                              "checks": raw["checks"], "failed": raw["failed"],
                              "setup_s": raw["setup_s"], "e2e": raw["e2e"],
                              "notes": raw.get("notes", {})}, default=float), flush=True)
            if "control" in raw:
                print(json.dumps({"seed": seed, "variant": "fp8_control", **raw["control"]},
                                 default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
