import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import PeftConfig, TrainConfig, get_config, reduced
from repro.data.loader import DataLoader, peek_batch
from repro.models import get_model
from repro.peft import get_peft
from repro.train.trainer import Trainer, make_train_step


def _setup(method="neuroada", **tkw):
    cfg = reduced(get_config("qwen2-1.5b"))
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    peft = get_peft(PeftConfig(method=method, k=2))
    tcfg = TrainConfig(
        learning_rate=3e-3, steps=30, log_every=0, checkpoint_every=0, **tkw
    )
    return cfg, m, params, peft, tcfg


def test_loss_decreases():
    cfg, m, params, peft, tcfg = _setup()
    tr = Trainer(m, peft, tcfg, params)
    data = DataLoader("reasoning", cfg.vocab_size, 16, 32, seed=1)
    hist = tr.run(data, steps=30)
    data.close()
    assert np.mean([h["loss"] for h in hist[-5:]]) < np.mean(
        [h["loss"] for h in hist[:5]]
    )
    assert not any(h["skipped"] for h in hist)


def test_grad_accumulation_equivalence():
    """microbatches=4 grads == full-batch grads (same update direction)."""
    cfg, m, params, peft, _ = _setup()
    rng = jax.random.PRNGKey(0)
    trainable, aux = peft.init(params, rng)
    batch = {k: jnp.asarray(v) for k, v in peek_batch("lm", cfg.vocab_size, 8, 16).items()}

    outs = {}
    for mb in (1, 4):
        tcfg = TrainConfig(learning_rate=1e-3, microbatches=mb, grad_clip=0.0, steps=10)
        step_fn, opt = make_train_step(m, peft, tcfg)
        from repro.train.trainer import TrainState

        state = TrainState(trainable, opt.init(trainable), jnp.zeros((), jnp.int32))
        new_state, metrics = step_fn(params, aux, state, batch)
        outs[mb] = (metrics["loss"], new_state.trainable)
    np.testing.assert_allclose(float(outs[1][0]), float(outs[4][0]), rtol=1e-4)
    l1 = jax.tree.leaves(outs[1][1])
    l4 = jax.tree.leaves(outs[4][1])
    for a, b in zip(l1, l4):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=5e-3
        )


def test_nan_guard_skips_bad_step():
    cfg, m, params, peft, tcfg = _setup()
    tr = Trainer(m, peft, tcfg, params)
    bad = peek_batch("lm", cfg.vocab_size, 8, 16)
    # poison: NaN loss mask propagates into the loss
    bad["loss_mask"] = np.full((8, 15), np.nan, np.float32)
    # snapshot before the step: the state buffers are donated
    state0 = jax.tree.map(lambda x: np.asarray(x, np.float32), tr.state.trainable)
    tr.state, metrics = tr._step_fn(
        tr.params, tr.aux, tr.state, {k: jnp.asarray(v) for k, v in bad.items()}
    )
    assert int(metrics["skipped"]) == 1
    for a, b in zip(jax.tree.leaves(state0), jax.tree.leaves(tr.state.trainable)):
        np.testing.assert_array_equal(a, np.asarray(b, np.float32))


def test_merged_params_match_adapter_forward():
    cfg, m, params, peft, tcfg = _setup()
    tr = Trainer(m, peft, tcfg, params)
    data = DataLoader("reasoning", cfg.vocab_size, 8, 32, seed=2)
    tr.run(data, steps=10)
    data.close()
    batch = {k: jnp.asarray(v) for k, v in peek_batch("reasoning", cfg.vocab_size, 4, 32).items()}
    eff, ad = peft.model_inputs(params, tr.state.trainable, tr.aux)
    lg_ad, _ = m.forward(eff, ad, batch)
    lg_merged, _ = m.forward(tr.merged_params(), None, batch)
    np.testing.assert_allclose(
        np.asarray(lg_ad, np.float32), np.asarray(lg_merged, np.float32), atol=0.15
    )  # bf16 rounding on merge


def test_one_fetch_per_step_and_history_unchanged(monkeypatch):
    """Each step's metrics come to the host in ONE ``jax.device_get`` of
    the whole dict (plus one read of the step counter per ``run`` call);
    no metric is fetched on its own, and the history holds the same
    numbers as fetching each metric with ``float``."""
    from jax._src.array import ArrayImpl

    cfg, m, params, peft, tcfg = _setup()
    batches = [peek_batch("lm", cfg.vocab_size, 4, 16, seed=i) for i in range(3)]
    ref = Trainer(m, peft, tcfg, params)
    want = []
    for b in batches:
        ref.state, metrics = ref._step_fn(
            ref.params, ref.aux, ref.state, {k: jnp.asarray(v) for k, v in b.items()}
        )
        want.append({k: float(v) for k, v in metrics.items()})

    tr = Trainer(m, peft, tcfg, params)
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: (calls.append(1), real(x))[1])

    def refuse(self, *a):
        raise AssertionError("a device value was fetched on its own")

    for conv in ("__float__", "__int__", "__bool__", "__index__"):
        monkeypatch.setattr(ArrayImpl, conv, refuse)
    hist = tr.run(iter(batches), steps=3)
    assert len(calls) == 1 + 3
    assert [{k: h[k] for k in w} for h, w in zip(hist, want)] == want
    assert [h["step"] for h in hist] == [0, 1, 2]
