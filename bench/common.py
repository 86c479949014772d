"""Small pieces the whole harness shares: paths, files found by name, the
percentile, and the table of chip peaks.

The percentile is a copy of ``repro.obs.metrics.percentile`` (nearest
rank), kept here so that a change to the program cannot move the
yardstick.
"""

from __future__ import annotations

import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_file(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def limits_file(workload_name: str) -> dict:
    return load_json(BENCH / "limits" / f"{workload_name}.json")


def end_to_end_for(spec: dict, workload_name: str) -> list[dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in spec["end_to_end"]
            if workload_name in m.get("workloads", [workload_name])]


def per_layer_for(spec: dict, workload_name: str) -> list[dict]:
    """The per-layer metrics whose readers look for something in this cell:
    those that list it, and those without a list that move one of its
    end-to-end metrics."""
    e2e = {m["name"] for m in end_to_end_for(spec, workload_name)}
    out = []
    for m in spec["per_layer"]:
        if "workloads" in m:
            if workload_name in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


# ------------------------------------------------------------ arithmetic


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: sorted, index ``int(q * n)`` clamped to the
    last element."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    vs = sorted(values)
    if not vs:
        raise ValueError("percentile of an empty sequence")
    return vs[min(int(q * len(vs)), len(vs) - 1)]


# ------------------------------------------------------------------ chip


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def require_chips(jax, count: int) -> list:
    """The first ``count`` accelerator devices, or SystemExit: the
    benchmark never falls back to the CPU."""
    devices = jax.devices()
    if not devices or devices[0].platform == "cpu":
        raise SystemExit(f"bench: JAX found no accelerator (platform "
                         f"{devices[0].platform if devices else None!r})")
    if len(devices) < count:
        raise SystemExit(f"bench: the cell needs {count} chips, JAX found {len(devices)}")
    peaks(devices[0].device_kind)  # refuses a chip it has no peaks for
    return devices[:count]
