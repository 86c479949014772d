"""Distributed trainer: pjit train step, grad accumulation, remat, NaN
guard, async checkpointing with auto-resume, straggler monitor.

The step function is PEFT-method-agnostic: it differentiates ONLY the
``trainable`` pytree (for NeuroAda that's the (…, k, d_out) delta values —
the paper's entire memory story follows from this one line). Frozen params
are a non-differentiated argument; GSPMD therefore never materialises dense
grads or dense optimizer states for them, and the DP grad all-reduce
carries only trainable bytes.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import TrainConfig
from repro.distributed.fault import NanGuard, StragglerMonitor
from repro.obs import spans
from repro.obs.spans import span
from repro.optim import adamw, apply_updates, clip_by_global_norm, get_schedule

log = logging.getLogger("repro.train")


class TrainState(NamedTuple):
    trainable: Any
    opt_state: Any
    step: jax.Array


def _where_tree(cond, a, b):
    return jax.tree.map(
        lambda x, y: None if x is None else jnp.where(cond, x, y),
        a,
        b,
        is_leaf=lambda x: x is None,
    )


def make_train_step(
    model,
    peft,
    tcfg: TrainConfig,
    *,
    optimizer=None,
    grad_transform: Callable | None = None,
):
    """Returns step(params, aux, state, batch) -> (state, metrics)."""
    if optimizer is None:
        schedule = get_schedule(
            tcfg.schedule, tcfg.learning_rate, tcfg.steps, tcfg.warmup_ratio
        )
        optimizer = adamw(
            schedule,
            b1=tcfg.beta1,
            b2=tcfg.beta2,
            eps=tcfg.eps,
            weight_decay=tcfg.weight_decay,
        )

    def loss_of(params, trainable, aux, batch):
        eff, adapters = peft.model_inputs(params, trainable, aux)
        return model.loss(eff, adapters, batch, remat=tcfg.remat)

    def grads_of(params, trainable, aux, batch):
        gfn = jax.value_and_grad(
            lambda tr: loss_of(params, tr, aux, batch), has_aux=True
        )
        if tcfg.microbatches <= 1:
            (loss, metrics), grads = gfn(trainable)
            return loss, metrics, grads
        # gradient accumulation: scan over microbatch slices
        m = tcfg.microbatches
        _AXIS1_KEYS = ("positions", "mrope_pos")  # batch dim is axis 1

        def slice_mb(path, x, i):
            if not hasattr(x, "ndim") or x.ndim == 0:
                return x
            key = str(path[-1].key) if path and hasattr(path[-1], "key") else ""
            axis = 1 if key in _AXIS1_KEYS else 0
            b = x.shape[axis] // m
            return jax.lax.dynamic_slice_in_dim(x, i * b, b, axis=axis)

        def body(carry, i):
            acc_loss, acc_metrics, acc_grads = carry
            mb = jax.tree_util.tree_map_with_path(
                lambda p, x: slice_mb(p, x, i), batch
            )
            (loss, metrics), grads = jax.value_and_grad(
                lambda tr: loss_of(params, tr, aux, mb), has_aux=True
            )(trainable)
            acc_grads = jax.tree.map(
                lambda a, g: None if a is None else a + g.astype(jnp.float32) / m,
                acc_grads,
                grads,
                is_leaf=lambda x: x is None,
            )
            acc_metrics = jax.tree.map(lambda a, x: a + x / m, acc_metrics, metrics)
            return (acc_loss + loss / m, acc_metrics, acc_grads), None

        zero_g = jax.tree.map(
            lambda t: None if t is None else jnp.zeros(t.shape, jnp.float32),
            trainable,
            is_leaf=lambda x: x is None,
        )
        zero_m = {"ce": jnp.float32(0.0), "aux": jnp.float32(0.0)}
        (loss, metrics, grads), _ = jax.lax.scan(
            body, (jnp.float32(0.0), zero_m, zero_g), jnp.arange(m)
        )
        grads = jax.tree.map(
            lambda t, g: None if t is None else g.astype(t.dtype),
            trainable,
            grads,
            is_leaf=lambda x: x is None,
        )
        return loss, metrics, grads

    def train_step(params, aux, state: TrainState, batch):
        loss, metrics, grads = grads_of(params, state.trainable, aux, batch)
        grads = peft.post_grad(grads, aux)
        if grad_transform is not None:
            grads = grad_transform(grads)
        if tcfg.grad_clip > 0:
            grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        else:
            from repro.optim import global_norm

            gnorm = global_norm(grads)
        good = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.trainable)
        new_trainable = apply_updates(state.trainable, updates)
        # NaN guard: keep old state on bad steps (but still advance step)
        new_trainable = _where_tree(good, new_trainable, state.trainable)
        new_opt = jax.tree.map(
            lambda n, o: None if n is None else jnp.where(good, n, o),
            new_opt,
            state.opt_state,
            is_leaf=lambda x: x is None,
        )
        out_metrics = dict(metrics)
        out_metrics.update(loss=loss, grad_norm=gnorm, skipped=(~good).astype(jnp.int32))
        return TrainState(new_trainable, new_opt, state.step + 1), out_metrics

    return train_step, optimizer


class Trainer:
    """Orchestration: loop + data + checkpoint/resume + fault handling."""

    def __init__(
        self,
        model,
        peft,
        tcfg: TrainConfig,
        params,
        *,
        rng=None,
        mesh=None,
        shardings=None,  # optional (params_sh, trainable_sh, batch_sh)
        grad_transform=None,
    ):
        self.model, self.peft, self.tcfg = model, peft, tcfg
        self.params = params
        rng = rng if rng is not None else jax.random.PRNGKey(tcfg.seed)
        self.trainable, self.aux = peft.init(params, rng)
        step_fn, self.optimizer = make_train_step(
            model, peft, tcfg, grad_transform=grad_transform
        )
        self.opt_state = self.optimizer.init(self.trainable)
        self.state = TrainState(self.trainable, self.opt_state, jnp.zeros((), jnp.int32))
        self.mesh = mesh
        self._step_fn = jax.jit(step_fn, donate_argnums=(2,))
        self.ckpt = (
            CheckpointManager(tcfg.checkpoint_dir) if tcfg.checkpoint_dir else None
        )
        self.monitor = StragglerMonitor()
        self.nan_guard = NanGuard(tcfg.max_skipped_steps)
        self.history: list[dict] = []

    # ------------------------------------------------------------- resume

    def try_resume(self) -> int:
        if self.ckpt is None:
            return 0
        step, tree = self.ckpt.restore_latest()
        if step is None:
            return 0
        # elastic restart: arrays are host numpy; re-shard onto current mesh
        from repro.checkpoint.manager import restore_into

        restored = restore_into(self.state.trainable, tree["trainable"])
        opt = restore_into(self.state.opt_state, tree["opt_state"])
        self.state = TrainState(restored, opt, jnp.asarray(step, jnp.int32))
        log.info("resumed from step %d", step)
        return step

    # --------------------------------------------------------------- loop

    def run(self, data_iter, steps: int | None = None) -> list[dict]:
        steps = steps if steps is not None else self.tcfg.steps
        start = int(jax.device_get(self.state.step))
        for i in range(start, steps):
            with span(spans.TRAIN_STEP):
                self._step(i, data_iter)
        if self.ckpt is not None:
            self.save(steps)
            self.ckpt.wait()
        return self.history

    def _step(self, i: int, data_iter) -> None:
        """Step ``i``: one compiled call and ONE device→host fetch, of the
        step's metrics dict."""
        with span(spans.TRAIN_FEED):
            batch = next(data_iter)
        with span(spans.TRAIN_PUT):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
        self.monitor.start()
        with span(spans.TRAIN_DISPATCH):
            self.state, metrics = self._step_fn(
                self.params, self.aux, self.state, batch
            )
        with span(spans.TRAIN_FETCH):
            metrics = jax.device_get(metrics)
        with span(spans.TRAIN_LOG):
            metrics = {k: float(v) for k, v in metrics.items()}
            slow = self.monitor.stop(i)
            self.nan_guard.record(bool(metrics["skipped"]))
            metrics["step"] = i
            metrics["straggler"] = slow
            self.history.append(metrics)
            if self.tcfg.log_every and i % self.tcfg.log_every == 0:
                log.info(
                    "step %d loss %.4f gnorm %.3f%s",
                    i,
                    metrics["loss"],
                    metrics["grad_norm"],
                    " [STRAGGLER]" if slow else "",
                )
            if (
                self.ckpt is not None
                and self.tcfg.checkpoint_every
                and (i + 1) % self.tcfg.checkpoint_every == 0
            ):
                self.save(i + 1)

    def save(self, step: int):
        self.ckpt.save(
            step,
            {"trainable": self.state.trainable, "opt_state": self.state.opt_state},
            metadata={"peft": self.peft.method},
        )

    def merged_params(self):
        """Alg. 1 phase 3: export inference weights."""
        return self.peft.merge(self.params, self.state.trainable, self.aux)
