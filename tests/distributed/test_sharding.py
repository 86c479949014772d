"""Sharding rule unit tests + an 8-device pjit integration test (subprocess
so the fake device count never leaks into other tests)."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced
from repro.distributed.sharding import (
    batch_specs,
    data_axes,
    delta_spec_from,
    spec_for_param,
)


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 4, "model": 4}


MESH = FakeMesh()
REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_col_row_rules_fsdp():
    assert spec_for_param("blocks/wq/w", (8, 64, 32), MESH, "dense", fsdp=True) == P(
        None, "data", "model"
    )
    assert spec_for_param("blocks/wo/w", (8, 32, 64), MESH, "dense", fsdp=True) == P(
        None, "model", "data"
    )
    assert spec_for_param("blocks/wq/b", (8, 32), MESH, "dense", fsdp=True) == P(None, "model")
    assert spec_for_param("blocks/wo/b", (8, 64), MESH, "dense", fsdp=True) == P(None, None)


def test_col_row_rules_tp_only():
    assert spec_for_param("blocks/wq/w", (8, 64, 32), MESH, "dense") == P(
        None, None, "model"
    )
    assert spec_for_param("blocks/wo/w", (8, 32, 64), MESH, "dense") == P(
        None, "model", None
    )


def test_embed_vocab_sharded():
    assert spec_for_param("embed/w", (1024, 64), MESH, "dense", fsdp=True) == P("model", "data")
    assert spec_for_param("embed/w", (1024, 64), MESH, "dense") == P("model", None)


def test_moe_expert_parallel():
    assert spec_for_param("blocks/wgate/w", (4, 8, 64, 32), MESH, "moe", fsdp=True) == P(
        None, "model", "data", None
    )
    assert spec_for_param("blocks/wgate/w", (4, 8, 64, 32), MESH, "moe") == P(
        None, "model", None, None
    )


def test_nondivisible_falls_back_to_replicated():
    assert spec_for_param("blocks/wq/w", (8, 63, 30), MESH, "dense") == P(
        None, None, None
    )


def test_ssm_rules():
    assert spec_for_param("blocks/A_log", (8, 64, 16), MESH, "ssm") == P(
        None, "model", None
    )
    assert spec_for_param("blocks/conv_w", (8, 4, 64), MESH, "ssm") == P(
        None, None, "model"
    )


def test_delta_spec_inherits_dout():
    w = P(None, "data", "model")
    assert delta_spec_from(w, (8, 1, 32)) == P(None, None, "model")
    assert delta_spec_from(P(None, "model", "data"), (8, 1, 64)) == P(None, None, "data")
    # moe: (L,E,k,F) inherits E
    assert delta_spec_from(P(None, "model", "data", None), (4, 8, 2, 32)) == P(
        None, "model", None, None
    )


def test_data_axes():
    assert data_axes(MESH) == ("data",)

    class PodMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 4, "model": 4}

    assert data_axes(PodMesh()) == ("pod", "data")


_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from repro.configs import get_config, reduced, PeftConfig, TrainConfig
    from repro.models import get_model
    from repro.peft import get_peft
    from repro.train.trainer import TrainState, make_train_step
    from repro.distributed import sharding as shd
    from repro.data.loader import peek_batch

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = reduced(get_config("qwen2-1.5b")).replace(d_model=64, vocab_size=512)
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    peft = get_peft(PeftConfig(method="neuroada", k=2))
    trainable, aux = peft.init(params, jax.random.PRNGKey(1))
    tcfg = TrainConfig(learning_rate=1e-3, steps=10)
    step_fn, opt = make_train_step(m, peft, tcfg)
    state = TrainState(trainable, opt.init(trainable), jnp.zeros((), jnp.int32))
    batch = {k: jnp.asarray(v) for k, v in peek_batch("lm", cfg.vocab_size, 8, 16).items()}

    p_sh = shd.param_shardings(params, mesh, cfg.family)
    with mesh:
        # distributed step
        params_d = jax.device_put(params, p_sh)
        jstep = jax.jit(step_fn)
        state_d, metrics_d = jstep(params_d, aux, state, batch)
    # single-device reference
    state_r, metrics_r = step_fn(params, aux, state, batch)
    out = {
        "loss_d": float(metrics_d["loss"]),
        "loss_r": float(metrics_r["loss"]),
        "max_diff": max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(state_d.trainable),
                            jax.tree.leaves(state_r.trainable))
        ),
    }
    print("RESULT:" + json.dumps(out))
    """
)


@pytest.mark.slow
def test_8device_pjit_matches_single_device():
    proc = subprocess.run(
        [sys.executable, "-c", _SUBPROC],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
             "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"},
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert abs(out["loss_d"] - out["loss_r"]) < 1e-3
    assert out["max_diff"] < 5e-2  # bf16 accumulation-order noise


# --------------------------------------------- canonical spec form (§14)


def test_single_axis_entries_are_canonical():
    """Regression: P('x') and P(('x',)) mean the same placement but
    compare unequal — every rule must emit the bare-name form."""
    from repro.distributed.sharding import canonical_axes, canonical_spec

    assert canonical_axes(("model",)) == "model"
    assert canonical_axes("model") == "model"
    assert canonical_axes(("data", "model")) == ("data", "model")
    assert canonical_axes(None) is None
    assert canonical_spec(P(("model",), None)) == P("model", None)
    # multi-axis entries survive canonicalization untouched
    assert canonical_spec(P(("data", "model"), None)) == P(("data", "model"), None)
    # every public rule funnels through it: no entry is ever a 1-tuple
    for spec in (
        spec_for_param("blocks/wq/w", (8, 64, 32), MESH, "dense"),
        spec_for_param("embed/w", (1024, 64), MESH, "dense"),
        delta_spec_from(P(None, None, "model"), (8, 2, 32)),
    ):
        assert all(not (isinstance(e, tuple) and len(e) == 1) for e in spec)


# --------------------------- delta placement: untied heads, expert stacks


def test_delta_spec_untied_head():
    # untied head/w (d_model, V) is col-parallel: vocab-sharded d_out
    wspec = spec_for_param("head/w", (64, 1024), MESH, "dense")
    assert wspec == P(None, "model")
    # training delta (k, V) inherits the vocab sharding
    assert delta_spec_from(wspec, (2, 1024)) == P(None, "model")
    # serving tenant stack (N, k, V): N replicated, vocab still sharded
    assert delta_spec_from(wspec, (4, 2, 1024)) == P(None, None, "model")


def test_delta_spec_serving_stacks():
    """The store's stacked trees insert a tenant axis after the layer
    axis; leading weight entries must land on their original dims."""
    # dense blocks: weight (L, d_in, d_out) -> stack (L, N, k, d_out)
    wspec = spec_for_param("blocks/wq/w", (8, 64, 32), MESH, "dense")
    assert delta_spec_from(wspec, (8, 4, 2, 32)) == P(None, None, None, "model")
    # moe experts: weight (L, E, d_in, F) is expert-parallel on E; the
    # stack (L, N, E, k, F) must keep "model" on E, NOT on the tenant N
    wspec = spec_for_param("blocks/wgate/w", (4, 8, 64, 32), MESH, "moe")
    assert wspec == P(None, "model", None, None)
    assert delta_spec_from(wspec, (4, 8, 2, 32)) == P(None, "model", None, None)
    assert delta_spec_from(wspec, (4, 3, 8, 2, 32)) == P(
        None, None, "model", None, None
    )


def test_param_shardings_quantized_base():
    """QuantizedTensor leaves: rules fire on the logical shape, then
    re-fit to the packed data/scales children (col axis survives)."""
    from repro.distributed.sharding import param_shardings
    from repro.quant.qtensor import quantize

    from repro.launch.mesh import make_serve_mesh

    mesh = make_serve_mesh(jax.device_count())
    w = np.random.default_rng(0).standard_normal((64, 32)).astype(np.float32)
    params = {"blocks": {"wq": {"w": quantize(jax.numpy.asarray(w), "int8", block=16)}}}
    sh = param_shardings(params, mesh, "dense", fsdp=False)
    qsh = sh["blocks"]["wq"]["w"]
    assert qsh.data.spec == P(None, "model")
    assert qsh.scales.spec == P(None, "model")
