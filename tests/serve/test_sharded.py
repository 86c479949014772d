"""Tensor-parallel sharded serving (DESIGN §14).

In-process: construction-time validation (mesh factory divisibility, head
divisibility) that must fail readably before any placement. Subprocess
(forced 8-device host platform, so the fake device count never leaks):
the tp2 invariants test — token parity, ONE device→host transfer per
megastep, per-shard pool bytes = total / tp, the tp gauges — and the
slow full parity grid: tp ∈ {1, 2, 4} × paged/dense × plain/multitenant
× int8 base × spec/ngram drafters, greedy outputs token-identical.
"""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.launch.mesh import make_serve_mesh

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
_ENV = {"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
        "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"}


def _run(script: str, timeout: int = 600) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=timeout,
        env=_ENV, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    return json.loads(line[len("RESULT:"):])


# --------------------------------------------------- construction validation


def test_make_serve_mesh_validates():
    with pytest.raises(ValueError, match="tp must be >= 1"):
        make_serve_mesh(0)
    import jax

    n = jax.device_count()
    with pytest.raises(ValueError, match="does not divide"):
        make_serve_mesh(n + 1)
    mesh = make_serve_mesh(n)  # tp == all devices: pure ("model",) mesh
    assert mesh.axis_names == ("model",)
    assert mesh.shape["model"] == n


def test_engine_rejects_nondivisible_heads():
    """Head-count validation fires before any device placement, so a fake
    mesh exercises it without multi-device jax state."""
    import jax

    from repro.configs import get_config, reduced
    from repro.models import get_model
    from repro.serve import ServeEngine

    class FakeMesh:
        axis_names = ("model",)
        shape = {"model": 3}

    cfg = reduced(get_config("qwen2-1.5b")).replace(dtype="float32")
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="num_kv_heads"):
        ServeEngine(m, params, mesh=FakeMesh())

    class NoModelMesh:
        axis_names = ("data",)
        shape = {"data": 2}

    with pytest.raises(ValueError, match="'model' axis"):
        ServeEngine(m, params, mesh=NoModelMesh())


def test_launcher_rejects_bad_tp():
    from repro.launch.serve import main

    with pytest.raises(SystemExit, match="--tp must be >= 1"):
        main(["--reduced", "--tp", "0"])
    # device-count divisibility surfaces as SystemExit, not a ValueError
    with pytest.raises(SystemExit, match="--tp 7"):
        main(["--reduced", "--tp", "7"])


# ------------------------------------------------------- subprocess helpers

_PRELUDE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from repro.configs import get_config, reduced
    from repro.core.adapt import init_adapters
    from repro.launch.mesh import make_serve_mesh
    from repro.models import get_model
    from repro.serve import AdapterStore, ServeEngine

    # tp=4 needs 4 kv heads; 8 q heads keep GQA grouping intact
    cfg = reduced(get_config("qwen2-1.5b")).replace(
        dtype="float32", num_kv_heads=4, num_heads=8
    )
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    PROMPTS = [[1, 17, 25], [1, 40, 41, 42], [3, 5]]

    def make_store():
        store = AdapterStore()
        for seed in (1, 2):
            idx, val = init_adapters(params, 2, rng=jax.random.PRNGKey(seed))
            val = jax.tree.map(
                lambda i, v: None if v is None else 0.05 * jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(seed), v.size),
                    v.shape,
                ),
                idx, val, is_leaf=lambda x: x is None,
            )
            store.register(idx, val)
        return store

    def run(tp, store=None, **kw):
        mesh = make_serve_mesh(tp) if tp > 1 else None
        eng = ServeEngine(
            model, params, slots=2, max_len=64, decode_chunk=2,
            prefill_chunk=8, adapter_store=store, mesh=mesh, **kw,
        )
        n_t = store.num_adapters if store is not None else 0
        for i, p in enumerate(PROMPTS):
            eng.submit(p, max_new=6, adapter_id=1 + i % n_t if n_t else 0)
        reqs = eng.run_to_completion()
        return eng, [r.out for r in sorted(reqs, key=lambda r: r.rid)]
    """
)

_INVARIANTS = _PRELUDE + textwrap.dedent(
    """
    _, out1 = run(1, paged=True)
    eng1 = ServeEngine(model, params, slots=2, max_len=64, paged=True)

    # count raw device_get calls across a full tp=2 serve run
    real_get = jax.device_get
    calls = {"n": 0}
    def counting_get(x):
        calls["n"] += 1
        return real_get(x)
    jax.device_get = counting_get
    try:
        eng2, out2 = run(2, paged=True)
    finally:
        jax.device_get = real_get

    snap = eng2.metrics.snapshot()
    out = {
        "tokens_match": out1 == out2,
        "device_gets": calls["n"],
        "transfers": eng2.transfers,
        "steps": int(
            sum(s["value"] for s in snap["serve_steps_total"]["series"])
        ),
        "pool_total_tp2": eng2.kv.pool_bytes(),
        "pool_shard_tp2": eng2.kv.pool_bytes_per_shard(),
        "pool_total_tp1": eng1.kv.pool_bytes(),
        "g_tp": eng2.metrics.value("serve_tp_size"),
        "g_shard_bytes": eng2.metrics.value(
            "serve_pool_bytes_per_shard", "fp32"
        ),
    }
    print("RESULT:" + json.dumps(out))
    """
)


def test_tp2_parity_transfers_and_pool_bytes():
    out = _run(_INVARIANTS)
    assert out["tokens_match"], "tp=2 greedy tokens diverge from tp=1"
    # the one-transfer-per-megastep invariant holds under the mesh: every
    # raw device_get during the run is one of the engine's counted fetches
    assert out["device_gets"] == out["transfers"] == out["steps"]
    # kv-head partition halves the per-shard pool, total unchanged
    assert out["pool_total_tp2"] == out["pool_total_tp1"]
    assert out["pool_shard_tp2"] * 2 == out["pool_total_tp2"]
    assert out["g_tp"] == 2
    assert out["g_shard_bytes"] == out["pool_shard_tp2"]


_GRID = _PRELUDE + textwrap.dedent(
    """
    CASES = {
        "paged_plain": dict(paged=True),
        "dense_plain": dict(paged=False),
        "paged_mt": dict(paged=True, store=True),
        "paged_int8": dict(paged=True, base_dtype="int8"),
        "paged_spec_int8": dict(paged=True, draft="int8", spec_k=2),
        "dense_ngram": dict(paged=False, draft="ngram", spec_k=2),
        "dense_mt_int8": dict(paged=False, store=True, base_dtype="int8"),
    }
    mism = {}
    for name, kw in CASES.items():
        kw = dict(kw)
        store = make_store() if kw.pop("store", False) else None
        outs = {}
        for tp in (1, 2, 4):
            _, outs[tp] = run(tp, store=store, **kw)
        bad = [tp for tp in (2, 4) if outs[tp] != outs[1]]
        if bad:
            mism[name] = {str(tp): outs[tp] for tp in (1, *bad)}
    print("RESULT:" + json.dumps({"mismatches": mism}))
    """
)


@pytest.mark.slow
def test_tp_grid_token_parity():
    out = _run(_GRID)
    assert out["mismatches"] == {}, out["mismatches"]


_KERNELS = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from repro.kernels.decode_attention import (
        decode_attention_pallas, decode_attention_sharded,
        paged_decode_attention_pallas, paged_decode_attention_sharded,
    )
    from repro.kernels.prefill_attention import (
        paged_prefill_attention_pallas, paged_prefill_attention_sharded,
    )
    from repro.kernels.quant_linear import matmul_q_cols_sharded
    from repro.launch.mesh import make_serve_mesh
    from repro.quant.qtensor import dequantize, quantize

    mesh = make_serve_mesh(2)
    r = np.random.default_rng(0)
    B, H, KV, hd, S = 2, 8, 4, 16, 32
    f = lambda *s: r.standard_normal(s).astype(np.float32)
    out = {}

    q = f(B, 1, H, hd); k = f(B, S, KV, hd); v = f(B, S, KV, hd)
    vl = np.array([7, 29], np.int32)
    ref = decode_attention_pallas(q, k, v, vl, interpret=True)
    got = jax.jit(
        lambda *a: decode_attention_sharded(*a, mesh, interpret=True)
    )(q, k, v, vl)
    out["decode"] = float(jnp.max(jnp.abs(ref - got)))

    N, P_ = 8, 8
    kp = f(N, P_, KV, hd); vp = f(N, P_, KV, hd)
    table = np.array([[0, 2, 4, 8], [1, 3, 8, 8]], np.int32)
    vl = np.array([7, 15], np.int32)  # inside the two allocated pages
    ref = paged_decode_attention_pallas(q, kp, vp, table, vl, interpret=True)
    got = jax.jit(
        lambda *a: paged_decode_attention_sharded(*a, mesh, interpret=True)
    )(q, kp, vp, table, vl)
    out["paged_decode"] = float(jnp.max(jnp.abs(ref - got)))

    C = 4
    qc = f(B, C, H, hd)
    qoff = np.array([3, 10], np.int32)
    vlc = qoff + C
    ref = paged_prefill_attention_pallas(
        qc, kp, vp, table, qoff, vlc, interpret=True
    )
    got = jax.jit(
        lambda *a: paged_prefill_attention_sharded(*a, mesh, interpret=True)
    )(qc, kp, vp, table, qoff, vlc)
    out["paged_prefill"] = float(jnp.max(jnp.abs(ref - got)))

    x = f(4, 32)
    qw = quantize(f(32, 64), "int8", block=16)
    ref = jnp.dot(x, dequantize(qw))
    got = jax.jit(
        lambda xx: matmul_q_cols_sharded(xx, qw, mesh, interpret=True)
    )(x)
    out["matmul_q"] = float(jnp.max(jnp.abs(ref - got)))
    print("RESULT:" + json.dumps(out))
    """
)


def test_sharded_kernel_wrappers_match_replicated():
    out = _run(_KERNELS)
    for name, diff in out.items():
        assert diff < 1e-4, f"{name}: sharded kernel diverges by {diff}"
