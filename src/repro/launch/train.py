"""Training launcher: the production entry point.

One process drives the local devices (CPU, or one TPU host); the data
loader takes its host index and count from ``jax.process_index()`` /
``jax.process_count()``. NeuroAda is the default PEFT; any method from
peft/api.py is selectable. ``main`` returns the per-step history (loss,
grad norm, …) so callers can check the run.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --task reasoning --steps 200 --peft neuroada --k 1 \
      --ckpt /tmp/run1 [--resume]
"""

from __future__ import annotations

import argparse
import logging

import jax

from repro.configs import ARCH_IDS, PAPER_ARCH_IDS, PeftConfig, TrainConfig, get_config, reduced
from repro.data.loader import DataLoader
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.peft import BASE_DTYPES, get_peft, stats
from repro.train.trainer import Trainer

log = logging.getLogger("repro.launch.train")


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=ARCH_IDS + PAPER_ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized family member for tests and rehearsals")
    ap.add_argument("--peft", default="neuroada",
                    choices=("neuroada", "lora", "bitfit", "masked", "full"))
    ap.add_argument("--base-dtype", default="fp32", choices=BASE_DTYPES,
                    help="quantize the frozen base (QLoRA-style) before "
                         "adapting — only the sparse bypass values train, "
                         "so int8/nf4 compound the paper's memory win")
    ap.add_argument("--quant-block", type=int, default=64,
                    help="rows per quantization scale block (d_in axis)")
    ap.add_argument("--k", type=int, default=1)
    ap.add_argument("--strategy", default="magnitude")
    ap.add_argument("--lora-rank", type=int, default=8)
    ap.add_argument("--task", default="reasoning",
                    choices=("lm", "reasoning", "arithmetic"))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=("none", "full", "dots"))
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--export", default="", help="save merged params here")
    ap.add_argument("--export-adapter", default="",
                    help="save the UNMERGED (indices, values) adapter here "
                         "for multi-tenant serving (neuroada only)")
    return ap.parse_args(argv)


def main(argv=None):
    enable_compile_cache()
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    args = parse_args(argv)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.base_dtype != "fp32":
        if args.peft in ("masked", "full"):
            raise SystemExit(
                f"--base-dtype {args.base_dtype} requires a frozen base; "
                f"--peft {args.peft} trains the dense weights"
            )
        from repro.peft import quantize_base
        from repro.quant import tree_bytes

        before = tree_bytes(params)
        params = quantize_base(params, args.base_dtype, block=args.quant_block)
        log.info("base quantized to %s: %.1f MB -> %.1f MB (%.2fx)",
                 args.base_dtype, before / 2**20, tree_bytes(params) / 2**20,
                 before / tree_bytes(params))

    peft = get_peft(PeftConfig(
        method=args.peft, k=args.k, strategy=args.strategy,
        lora_rank=args.lora_rank,
    ))
    tcfg = TrainConfig(
        learning_rate=args.lr, steps=args.steps, seed=args.seed,
        microbatches=args.microbatches, remat=args.remat,
        checkpoint_dir=args.ckpt, checkpoint_every=100 if args.ckpt else 0,
    )
    trainer = Trainer(model, peft, tcfg, params)
    st = stats(params, trainer.state.trainable)
    log.info("arch=%s peft=%s trainable=%s/%s (%.4f%%)",
             cfg.name, args.peft, f"{st['trainable']:,}", f"{st['total']:,}",
             100 * st["fraction"])

    start = trainer.try_resume() if args.resume else 0
    hosts = jax.process_count()
    data = DataLoader(
        args.task, cfg.vocab_size, args.batch, args.seq, seed=args.seed,
        host_id=jax.process_index(), host_count=hosts, start_step=start,
    )
    hist = trainer.run(data, steps=args.steps)
    data.close()
    log.info("done: loss %.4f -> %.4f; stragglers=%d skipped=%d",
             hist[0]["loss"], hist[-1]["loss"],
             len(trainer.monitor.flagged), trainer.nan_guard.skipped)
    if args.export:
        from repro.checkpoint.manager import save_pytree

        save_pytree(args.export, trainer.merged_params(),
                    {"arch": cfg.name, "peft": args.peft})
        log.info("merged params exported to %s", args.export)
    if args.export_adapter:
        if args.peft != "neuroada":
            raise SystemExit("--export-adapter requires --peft neuroada")
        from repro.peft import export_adapter

        # neuroada: aux is the indices tree, trainable the values tree
        export_adapter(args.export_adapter, trainer.aux, trainer.state.trainable,
                       {"arch": cfg.name, "peft": args.peft})
        log.info("unmerged adapter exported to %s", args.export_adapter)
    return hist


if __name__ == "__main__":
    main()
