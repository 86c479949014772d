"""Faults planted in the program's timed path, and the program's own
lower-precision path, for the control readings (``bench/control.py``) and
the harness's tests. The benchmark's own runs never use them.

Each is a hook of ``bench.cells.base.Cell``: ``trainer`` or ``engine`` is
a factory called in place of ``Trainer`` or ``ServeEngine``.
"""

from __future__ import annotations


class Proxy:
    """The program's model with some of its methods replaced."""

    def __init__(self, model, **methods):
        self._model, self._methods = model, methods

    def __getattr__(self, name):
        return self._methods.get(name) or getattr(self._model, name)


def trainer(**patch):
    """A ``Trainer`` whose model has ``patch[name](model)`` as its ``name``."""
    from repro.train.trainer import Trainer

    def make(model, *args, **kw):
        return Trainer(Proxy(model, **{k: f(model) for k, f in patch.items()}), *args, **kw)

    return make


def half_batch(model):
    """The loss over the first half of the batch's rows only."""

    def loss(params, adapters, batch, remat="none"):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return model.loss(params, adapters, half, remat=remat)

    return loss


def unchanged_state_trainer(model, peft, tcfg, params, **kw):
    """A ``Trainer`` whose step returns its state unchanged (the step count
    aside) and the metrics of the real step."""
    import jax

    from repro.train.trainer import Trainer, make_train_step

    t = Trainer(model, peft, tcfg, params, **kw)
    step, _ = make_train_step(model, peft, tcfg)
    t._step_fn = jax.jit(lambda p, a, s, b: (s._replace(step=s.step + 1), step(p, a, s, b)[1]))
    return t


def int8_base_trainer(model, peft, tcfg, params, **kw):
    """The program's own int8 path: NeuroAda on an int8 (block 64) base."""
    from repro.peft import quantize_base
    from repro.train.trainer import Trainer

    return Trainer(model, peft, tcfg, quantize_base(params, "int8", block=64), **kw)


def engine(sampler=None, **patch):
    """A ``ServeEngine`` whose model has ``patch[name](model)`` as its
    ``name``, and whose sampler is ``sampler(sampler, vocab)``."""
    from repro.serve import ServeEngine

    def make(model, *args, **kw):
        e = ServeEngine(Proxy(model, **{k: f(model) for k, f in patch.items()}), *args, **kw)
        if sampler is not None:
            e.sampler = sampler(e.sampler, model.cfg.vocab_size)
        return e

    return make


def altered_token(sampler, vocab):
    """Every sampled token replaced by the next id."""
    return lambda logits, temps, key: (sampler(logits, temps, key) + 1) % vocab


def cache_unchanged(name):
    """``name`` (a model step) returns the KV cache it was given."""

    def patch(model):
        step = getattr(model, name)
        return lambda p, a, cache, batch: (step(p, a, cache, batch)[0], cache)

    return patch
