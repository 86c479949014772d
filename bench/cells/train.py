"""Training cell: NeuroAda fine-tuning steps through ``Trainer.run``.

Set-up builds one ``Trainer`` from the benchmark's weights and drives it
through its first ``check_steps`` steps with the window's own call and
feed (the first compiles). The window then runs further steps on the same
object until ``--seconds`` have passed, each ending in a host read of its
metrics; it opens and closes on step boundaries. Afterwards the plain f32
reference follows the first steps from the same weights and batches, and
the program is held to it by its losses, its first gradient (read from
AdamW's first moment after step 1) and its values after the last of those
steps.
"""

from __future__ import annotations

import numpy as np

from bench import program, traffic, weights
from bench.cells.base import (Cell, Compiles, annotate, compare, free_program, memory_peak,
                              norm_gap, now, open_window, traced)
from bench.costs import model as costs
from bench.reference import qwen2


class Feed:
    """The window's feed: the set-up's batches in order, again from the
    start when they run out."""

    def __init__(self, batches):
        self.batches, self.i = batches, 0

    def __iter__(self):
        return self

    def __next__(self):
        with annotate("bench.feed"):
            b = self.batches[self.i % len(self.batches)]
            self.i += 1
            return b


def _leaves(tree) -> dict:
    return {n: np.asarray(tree["blocks"][n]["w"], np.float32) for n in weights.LINEARS}


def run(cell: Cell) -> dict:
    import jax

    from repro.configs import PeftConfig, TrainConfig
    from repro.peft import get_peft
    from repro.train import trainer as trainer_mod

    cfg, mix, tr = cell.cfg, cell.mix, cell.cfg["trainer"]
    if tr["schedule"] != "linear":
        raise SystemExit("bench: the reference follows the linear schedule only")
    compiles = Compiles()
    model = program.model(cfg)
    params = weights.make_params(cfg, cfg["init"], cell.seed)
    weights.check_layout(params, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batches = traffic.train_batches(mix, cfg["vocab_size"], cell.seed)
    t_inputs = now()
    tcfg = TrainConfig(
        learning_rate=tr["learning_rate"], schedule=tr["schedule"],
        warmup_ratio=tr["warmup_ratio"], steps=tr["steps"], beta1=tr["beta1"],
        beta2=tr["beta2"], eps=tr["eps"], weight_decay=tr["weight_decay"],
        grad_clip=tr["grad_clip"], remat=tr["remat"], checkpoint_every=0, log_every=0,
    )
    peft = get_peft(PeftConfig(method="neuroada", k=tr["k"], strategy=tr["strategy"]))
    make = cell.hooks.get("trainer", trainer_mod.Trainer)
    trainer = make(model, peft, tcfg, params, rng=weights.key_of(cell.seed))
    feed = Feed(batches)
    n_check = mix["check_steps"]
    t_built = now()

    # set-up: the first steps, through the window's own call and feed
    trainer.run(feed, steps=1)
    mu1 = _leaves(trainer.state.opt_state.mu)
    trainer.run(feed, steps=n_check)
    values = _leaves(trainer.state.trainable)
    losses = [h["loss"] for h in trainer.history[:n_check]]

    seconds = cell.window_seconds
    with traced(cell) as trace_dir:
        t_open = open_window()
        bounds = [t_open]
        with annotate("bench.window"):
            step = n_check
            while bounds[-1] - t_open < seconds:
                with annotate("bench.step"):
                    trainer.run(feed, steps=step + 1)
                step += 1
                bounds.append(now())
            jax.block_until_ready(trainer.state)
    window = trainer.history[n_check:]
    t_close = bounds[-1]
    peak = memory_peak(cell.devices)
    tokens = mix["batch"] * mix["seq"]
    result = {
        "attempted": len(window),
        "failed": sum(int(h["skipped"]) for h in window),
        "memory_peak_bytes": peak,
        "setup_s": t_open - cell.t_start,
        "e2e": {"train_tokens_per_s": len(window) * tokens / (t_close - t_open)},
        "layer_ctx": {
            "steps": len(window), "window_host_s": t_close - t_open,
            "step_flops": costs.train_step(cfg, mix["batch"], mix["seq"], tr["k"]),
            "memory_peak_bytes": peak,
        },
        "trace_dir": trace_dir,
    }
    del trainer, feed
    free_program()

    # the reference, once the window has closed and the program is freed
    ref = qwen2.train(params, weights.select_top1(params["blocks"]),
                      [b["tokens"] for b in batches[:n_check]], weights.dims(cfg), tr)
    g1 = {n: mu1[n] / (1 - tr["beta1"]) for n in weights.LINEARS}
    ref_g1 = {n: np.asarray(ref["grad1"][n]) for n in weights.LINEARS}
    counted = moving_leaves(ref_g1)
    numbers = gaps(losses, g1, values, ref, counted)
    result["correct"], result["checks"] = compare(numbers, cell.limits)
    steps_s = [b - a for a, b in zip(bounds, bounds[1:])]
    result["notes"] = {"losses": losses, "ref_losses": ref["losses"],
                       "excluded_leaves": sorted(set(weights.LINEARS) - set(counted)),
                       "window_compiles": compiles.between(t_open, t_close),
                       "step_s_min_max": [min(steps_s), max(steps_s)],
                       "setup_phases_s": {"inputs": t_inputs - cell.t_start,
                                          "trainer": t_built - t_inputs,
                                          "first_steps": t_open - t_built}}
    if "control_mm" in cell.hooks:
        # the reference in a lower precision, put in the program's place and
        # judged by the same comparison
        low = qwen2.train(params, weights.select_top1(params["blocks"]),
                          [b["tokens"] for b in batches[:n_check]], weights.dims(cfg), tr,
                          mm=cell.hooks["control_mm"])
        ok, checks = compare(gaps(
            low["losses"], {n: np.asarray(low["grad1"][n]) for n in weights.LINEARS},
            {n: np.asarray(low["values"][n]) for n in weights.LINEARS}, ref, counted),
            cell.limits)
        result["control"] = {"correct": ok, "checks": checks}
    return result


def gaps(losses, grad1, values, ref, counted) -> dict:
    """The numbers compared: the widest loss gap over the checked steps, and
    the worst leaf's norm gap of the first gradient and of the values."""
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(losses, ref["losses"])),
        "grad_gap": norm_gap(grad1, {n: np.asarray(ref["grad1"][n]) for n in counted}, counted),
        "change_gap": norm_gap(values, {n: np.asarray(ref["values"][n]) for n in counted}, counted),
    }


def moving_leaves(ref_grad: dict) -> list[str]:
    """Leaves the reference moves: a gradient norm at least a thousandth of
    the median leaf's. Below that a leaf moves under Adam by round-off."""
    norms = {n: float(np.linalg.norm(g)) for n, g in ref_grad.items()}
    med = float(np.median(list(norms.values())))
    return [n for n, v in norms.items() if v >= 1e-3 * med]
