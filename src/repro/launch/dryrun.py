import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede any jax import (jax locks device count at first init).
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each runnable cell this AOT-compiles the real step function — the same
``make_train_step`` the trainer jits, or the post-merge serve steps — with
ShapeDtypeStruct inputs (zero allocation) against the production mesh, then
extracts:

* ``memory_analysis()``  — proves the sharded program fits per-device HBM,
* ``cost_analysis()``    — HLO FLOPs / bytes for the roofline,
* collective wire bytes  — parsed from optimized HLO (hlo_parse).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]
"""

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, TrainConfig, PeftConfig, cell_is_runnable, get_config
from repro.configs.registry import ARCH_IDS
from repro.distributed import sharding as shd
from repro.distributed.context import clear_activation_sharding, set_activation_sharding
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_parse import structural_costs
from repro.models import get_model
from repro.peft import get_peft
from repro.train.trainer import TrainState, make_train_step

# Tokens per device per microbatch the train dry-run aims for. The remat
# h-stack is sequence-parallel (S/TP per device), so non-FSDP archs afford
# big microbatches — and every extra microbatch re-gathers FSDP weights,
# so FSDP archs trade h-stack memory for gather traffic (§Perf iter 4).
MICROBATCH_TOKENS_FSDP = 8192
MICROBATCH_TOKENS = 8192  # µb=2 measured: -10% coll, +2.5× temp — not worth it


def auto_microbatches(shape, dp_size: int, *, fsdp: bool = False) -> int:
    target = MICROBATCH_TOKENS_FSDP if fsdp else MICROBATCH_TOKENS
    tokens_per_dev = shape.global_batch * shape.seq_len // max(dp_size, 1)
    m = 1
    while (
        tokens_per_dev // (m * 2) >= target
        and shape.global_batch % (m * 2) == 0
        and (shape.global_batch // (m * 2)) % max(dp_size, 1) == 0
    ):
        m *= 2
    return m


def _eval_shapes(fn, *args):
    return jax.eval_shape(fn, *args)


def _sds_tree(tree):
    return jax.tree.map(
        lambda x: None if x is None else jax.ShapeDtypeStruct(x.shape, x.dtype),
        tree,
        is_leaf=lambda x: x is None,
    )


def build_cell(arch: str, shape_name: str, mesh, *, peft_k: int = 1,
               remat: str = "full", variant: str = "baseline"):
    """Returns (step_fn, arg_specs, arg_shardings) for one cell."""
    cfg = get_config(arch)
    if variant != "baseline":
        cfg = apply_variant(cfg, variant)
    shape = SHAPES[shape_name]
    model = get_model(cfg)
    family = cfg.family

    if shape.mode == "train":
        dp = shd.data_axes(mesh)
        dp_size = 1
        if dp:
            import numpy as _np

            dp_size = int(_np.prod([mesh.shape[a] for a in dp]))
        pcfg = PeftConfig(method="neuroada", k=peft_k)
        peft = get_peft(pcfg)
        params_s = _eval_shapes(lambda: model.init(jax.random.PRNGKey(0)))
        fsdp = shd.needs_fsdp(params_s, mesh)
        tcfg = TrainConfig(
            remat=remat, steps=1000,
            microbatches=auto_microbatches(shape, dp_size, fsdp=fsdp),
        )
        step_fn, optimizer = make_train_step(model, peft, tcfg)

        tr_s, aux_s = _eval_shapes(
            lambda: peft.init(
                jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params_s),
                jax.random.PRNGKey(1),
            )
        )
        opt_s = _eval_shapes(optimizer.init, tr_s)
        state_s = TrainState(tr_s, opt_s, jax.ShapeDtypeStruct((), jnp.int32))
        batch_s = model.input_specs(shape)

        params_sh = shd.param_shardings(params_s, mesh, family, fsdp=fsdp)
        aux_sh = shd.adapter_shardings(params_s, aux_s, mesh, family, fsdp=fsdp)
        tr_sh = shd.adapter_shardings(params_s, tr_s, mesh, family, fsdp=fsdp)
        # optimizer state shardings mirror trainable (mu/nu same shapes)
        from repro.optim.adamw import AdamWState

        opt_sh = AdamWState(
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            jax.tree.map(lambda s: s, tr_sh, is_leaf=lambda x: x is None),
            jax.tree.map(lambda s: s, tr_sh, is_leaf=lambda x: x is None),
        )
        state_sh = TrainState(
            tr_sh, opt_sh,
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        )
        batch_sh = shd.batch_specs(batch_s, mesh, cfg)
        fn = step_fn
        args = (params_s, aux_s, state_s, batch_s)
        shardings = (params_sh, aux_sh, state_sh, batch_sh)
        return fn, args, shardings, cfg

    # serving cells run the post-merge model (zero-overhead inference —
    # Alg. 1 phase 3), so only base params are inputs.
    params_s = _eval_shapes(lambda: model.init(jax.random.PRNGKey(0)))
    params_sh = shd.param_shardings(params_s, mesh, family)
    specs = dict(model.input_specs(shape))
    if shape.mode == "prefill":
        def fn(params, batch):
            return model.prefill(params, None, batch)

        batch_sh = shd.batch_specs(specs, mesh, cfg)
        return fn, (params_s, specs), (params_sh, batch_sh), cfg

    cache_s = specs.pop("cache")

    def fn(params, cache, batch):
        return model.decode_step(params, None, cache, batch)

    cache_sh = shd.batch_specs({"cache": cache_s}, mesh, cfg)["cache"]
    batch_sh = shd.batch_specs(specs, mesh, cfg)
    return fn, (params_s, cache_s, specs), (params_sh, cache_sh, batch_sh), cfg


def apply_variant(cfg, variant: str):
    """Perf-iteration variants (EXPERIMENTS.md §Perf)."""
    if variant == "flash256":
        return cfg.replace(flash_block=256)
    if variant == "flash1024":
        return cfg.replace(flash_block=1024)
    if variant == "chunk512":
        return cfg.replace(ssm_chunk=512)
    if variant == "chunk1024":
        return cfg.replace(ssm_chunk=1024)
    if variant == "chunk128":
        return cfg.replace(ssm_chunk=128)
    raise ValueError(variant)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             peft_k: int = 1, remat: str = "full", variant: str = "baseline",
             act_variant: str = "inner_mlp", verbose: bool = True) -> dict:
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    dp = shd.data_axes(mesh)
    import numpy as _np

    dp_size = int(_np.prod([mesh.shape[a] for a in dp])) if dp else 1
    t0 = time.time()
    try:
        # Megatron-style sequence parallelism on the residual stream
        set_activation_sharding(
            dp, "model", batch_div=dp_size, seq_div=mesh.shape["model"],
            variant=act_variant,
        )
        fn, args, shardings, cfg = build_cell(
            arch, shape_name, mesh, peft_k=peft_k, remat=remat, variant=variant
        )
        with mesh:
            jitted = jax.jit(fn, in_shardings=shardings)
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
    finally:
        clear_activation_sharding()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    # while-trip-aware structural costs (XLA:CPU cost_analysis counts loop
    # bodies once; see hlo_parse.structural_costs)
    sc = structural_costs(hlo, n_dev)
    coll = sc["collectives"]
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "variant": variant,
        "compile_s": round(compile_s, 1),
        "flops_per_device": float(sc["flops"]),
        "bytes_per_device": float(sc["traffic"]),
        "xla_cost_flops": float(cost.get("flops", 0.0)),
        "xla_cost_bytes": float(cost.get("bytes accessed", 0.0)),
        "peak_mem_per_device": int(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
        ),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "arg_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "collectives": {k: v for k, v in coll.items() if k != "entry"},
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {result['mesh']} "
              f"({variant}) compiled in {compile_s:.0f}s")
        print(f"  memory_analysis: args={result['arg_bytes']/2**30:.2f}GiB "
              f"temp={result['temp_bytes']/2**30:.2f}GiB per device")
        print(f"  structural: flops/dev={result['flops_per_device']:.3e} "
              f"traffic/dev={result['bytes_per_device']:.3e}")
        print(f"  collectives (wire bytes): total={coll['total']:.3e} "
              f"per_dev={coll['per_device']:.3e}")
    return result


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--peft-k", type=int, default=1)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--act-variant", default="inner_mlp",
                    choices=("none", "sp_only", "inner_mlp", "inner_all"))
    ap.add_argument("--json", default="")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape (or --all)")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    results = []
    for a, s in cells:
        ok, why = cell_is_runnable(get_config(a), SHAPES[s])
        if not ok:
            print(f"[dryrun] SKIP {a} × {s}: {why}")
            results.append({"arch": a, "shape": s, "skipped": why})
            continue
        for mp in meshes:
            try:
                results.append(run_cell(
                    a, s, multi_pod=mp, peft_k=args.peft_k,
                    remat=args.remat, variant=args.variant,
                    act_variant=args.act_variant,
                ))
            except Exception as e:  # a failing cell is a bug — surface it
                print(f"[dryrun] FAIL {a} × {s} multi_pod={mp}: "
                      f"{type(e).__name__}: {e}")
                results.append({
                    "arch": a, "shape": s,
                    "mesh": "2x16x16" if mp else "16x16",
                    "error": f"{type(e).__name__}: {e}",
                })
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
        print(f"[dryrun] wrote {args.json}")
    failures = [r for r in results if "error" in r]
    print(f"[dryrun] {len(results)} cells, {len(failures)} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
