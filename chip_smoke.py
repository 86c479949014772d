#!/usr/bin/env python3
"""Chip smoke test: NeuroAda fine-tune, then multi-tenant serving, on a TPU.

Drives the paper's own path once, through the entry points a user calls,
at the full published widths of qwen2-1.5b (28 layers, d 1536, 12/2 heads
of 128, d_ff 8960, vocab 151936, bf16) from seeded random weights, all in
this one process (a chip belongs to one process at a time):

1. train — ``repro.launch.train.main``: NeuroAda k=1, batch 4 × seq 512,
   3 steps with full rematerialisation (a described v5e compile puts the
   step at 3.1 GB of arguments plus 1.6 GB of temporaries with it, 12.3 GB
   without), exporting the unmerged adapter. The losses must be finite.
2. serve — ``repro.launch.serve.main``: the paged engine, 8 slots × 2048
   tokens, tenant 1 = the adapter just trained, tenant 2 = a seeded
   synthetic one; 6 prompts of 64–1024 tokens split among base and both
   tenants, 32 new tokens each. Every request must finish.
3. check — one prompt's prefill and first-decode logits through the
   ``pallas`` kernels against the ``jnp`` reference path, on the chip;
   the max absolute difference must stay under ``LOGIT_TOL``.

``--four-chips`` runs only the tensor-parallel comparison instead: the
serving engine on ``make_serve_mesh(2)`` (one of qwen2-1.5b's 2 kv heads
per shard) against the tp=1 engine, same params, tenants and prompt,
first-step logits within ``LOGIT_TOL``.

With no TPU, or run from a directory without the repo's ``src/``, it
exits non-zero and prints no result. The last line of stdout is the JSON
result. Artifacts — the exported adapters and the serve run's per-request
log — go to ``chiprun_out/chip_smoke/``.

    python chip_smoke.py [--four-chips]
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"
OUT = ROOT / "chiprun_out" / "chip_smoke"
ARCH = "qwen2-1.5b"
SEED = 0
# prompt lengths and tenants (0 = base) of the serve phase
PROMPT_LENS = (64, 1024, 192, 512, 320, 768)
PROMPT_TENANTS = (0, 1, 2, 0, 1, 2)
# the logit check: one 200-token prompt, prefilled as one 256-token chunk
CHECK_LEN, CHECK_CHUNK = 200, 256
# Max |Δ| between two bf16 paths through 28 layers. Random-init logits
# have a std of ≈ 0.78 (unit-RMS hidden · 0.02-std tied embedding over
# d = 1536) and a bf16 spacing of 1/64 near their max; a wrong mask, head
# or page mapping moves them by O(1).
LOGIT_TOL = 0.25


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, per phase."""

    def __init__(self):
        from jax import monitoring

        self.phase = "setup"
        self.seconds: dict[str, float] = {}
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) + secs

    def start(self, phase: str) -> None:
        self.phase = phase

    def report(self, phase: str) -> None:
        print(f"[{phase}] compile seconds: {self.seconds.get(phase, 0.0):.1f}")


def seeded_values(indices, seed: int):
    """A synthetic tenant's bypass values for an index tree: 0.05·N(0, 1),
    bf16, from ``seed`` (the recipe of ``scripts/smoke.sh``)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    return jax.tree.map(
        lambda i: None if i is None else (
            0.05 * jax.random.normal(jax.random.fold_in(key, i.size), i.shape)
        ).astype(jnp.bfloat16),
        indices, is_leaf=lambda x: x is None,
    )


def prompts(lens, seed: int, vocab: int) -> list[list[int]]:
    import numpy as np

    rng = np.random.default_rng(seed)
    # ids 3.. skip the pad/bos/eos ids
    return [rng.integers(3, vocab, size=n).tolist() for n in lens]


def first_step_fn(model, n_pages: int, n: int, tenant: int):
    """The program of :func:`first_step_logits`: (params, tenant stacks,
    pool, (1, CHECK_CHUNK) tokens) -> f32 (prefill, decode) logit rows for
    an ``n``-token prompt of ``tenant`` in slot 0's ``n_pages`` pages."""
    import jax
    import jax.numpy as jnp

    from repro.core.delta import BatchedDelta

    def run(params, idx, val, cache, tokens):
        aid = jnp.full((1,), tenant, jnp.int32)
        aid_l = jnp.broadcast_to(aid, (model.cfg.num_layers, 1))
        adapters = {
            key: jax.tree.map(
                lambda i, v, a=(aid_l if key == "blocks" else aid):
                None if i is None else BatchedDelta(i, v, a),
                idx[key], val[key], is_leaf=lambda x: x is None,
            )
            for key in idx
        }
        table = jnp.arange(n_pages, dtype=jnp.int32)[None]
        logits0, cache = model.prefill_chunk(params, adapters, cache, {
            "tokens": tokens, "q_offset": jnp.zeros((1,), jnp.int32),
            "q_len": jnp.full((1,), n, jnp.int32),
            "last_idx": jnp.full((1,), n - 1, jnp.int32),
            "block_table": table, "write_table": table,
        })
        logits1, _ = model.decode_step(params, adapters, cache, {
            "token": tokens[:, 0], "pos": jnp.full((1,), n, jnp.int32),
            "block_table": table,
        })
        return logits0.astype(jnp.float32), logits1.astype(jnp.float32)

    return run


def first_step_logits(engine, prompt: list[int], tenant: int):
    """Prefill ``prompt`` for ``tenant`` as one chunk into slot 0's pages of
    a fresh engine's pool, then decode one step fed the prompt's first
    token: returns the f32 (prefill, decode) logit rows.

    Traced anew on every call, so the kernel backend in scope at the call
    is the one that runs; under a TP engine it runs inside the engine's
    sharding scope on its placed params, tenants and pool."""
    import jax
    import jax.numpy as jnp

    run = jax.jit(first_step_fn(engine.model, engine.kv.max_pages, len(prompt), tenant))
    idx, val = engine._stacked()
    tokens = jnp.zeros((1, CHECK_CHUNK), jnp.int32).at[0, :len(prompt)].set(
        jnp.asarray(prompt)
    )
    if engine.mesh is None:
        return run(engine.params, idx, val, engine.kv.data, tokens)
    from jax.sharding import NamedSharding, PartitionSpec as P

    tokens = jax.device_put(tokens, NamedSharding(engine.mesh, P()))
    return engine._sharded_call(run, engine.params, idx, val, engine.kv.data, tokens)


def compare(name: str, ref, got) -> None:
    import numpy as np

    worst = 0.0
    for which, r, g in zip(("prefill", "decode"), ref, got):
        r, g = np.asarray(r), np.asarray(g)
        if not (np.isfinite(r).all() and np.isfinite(g).all()):
            fail(f"{name}: non-finite {which} logits")
        diff = float(np.max(np.abs(r - g)))
        worst = max(worst, diff)
        print(f"[check] {name} {which} logits: max|diff| {diff:.5f} "
              f"(max|ref| {float(np.max(np.abs(r))):.3f}, argmax "
              f"{'agrees' if r.argmax() == g.argmax() else 'differs'})")
    print(f"[check] {name}: max|diff| {worst:.5f} vs tolerance {LOGIT_TOL}")
    if worst > LOGIT_TOL:
        fail(f"{name}: logits differ by {worst:.5f} > {LOGIT_TOL}")


def phase_train(clock: CompileClock) -> pathlib.Path:
    from repro.launch import train

    clock.start("train")
    adapter = OUT / "tenant1.npz"
    hist = train.main([
        "--arch", ARCH, "--peft", "neuroada", "--k", "1",
        "--batch", "4", "--seq", "512", "--steps", "3", "--seed", str(SEED),
        "--remat", "full", "--export-adapter", str(adapter),
    ])
    losses = [h["loss"] for h in hist]
    print(f"[train] losses: {losses}")
    if len(losses) != 3 or not all(math.isfinite(x) for x in losses):
        fail(f"training losses {losses}: want 3 finite values")
    if any(h["skipped"] for h in hist):
        fail("the NaN guard skipped a training step")
    clock.report("train")
    return adapter


def phase_serve(clock: CompileClock, trained: pathlib.Path) -> list[pathlib.Path]:
    from repro.configs import get_config
    from repro.launch import serve
    from repro.peft import export_adapter, load_adapter

    clock.start("serve")
    idx, _ = load_adapter(str(trained))
    synthetic = OUT / "tenant2.npz"
    export_adapter(str(synthetic), idx, seeded_values(idx, 2), {"arch": ARCH})
    vocab = get_config(ARCH).vocab_size
    log = OUT / "serve.log"
    # the per-request lines hold every prompt token: keep them in a file
    with open(log, "w") as f, contextlib.redirect_stdout(f):
        reqs = serve.main([
            "--arch", ARCH, "--adapters", f"{trained},{synthetic}",
            "--prompts", ";".join(",".join(map(str, p))
                                  for p in prompts(PROMPT_LENS, SEED, vocab)),
            "--adapter-ids", ",".join(map(str, PROMPT_TENANTS)),
            "--slots", "8", "--max-len", "2048", "--max-new", "32",
        ])
    print(f"[serve] per-request log: {log.relative_to(ROOT)}")
    if len(reqs) != len(PROMPT_LENS):
        fail(f"{len(reqs)} requests came back for {len(PROMPT_LENS)} prompts")
    for tenant in sorted(set(PROMPT_TENANTS)):
        mine = [r for r in reqs if r.adapter_id == tenant]
        done = [r for r in mine if r.done and r.reason in ("eos", "max_new")]
        print(f"[serve] tenant {tenant}: {len(done)}/{len(mine)} requests "
              f"finished, {sum(len(r.out) for r in mine)} tokens emitted")
    unfinished = [r.rid for r in reqs if not (r.done and r.reason in ("eos", "max_new"))]
    if unfinished:
        fail(f"requests {unfinished} did not finish")
    clock.report("serve")
    return [trained, synthetic]


def tenant_store(params, adapters):
    from repro.serve import AdapterStore

    store = AdapterStore(base_params=params)
    for idx, val in adapters:
        store.register(idx, val)
    return store


def phase_check(clock: CompileClock, adapter_files: list[pathlib.Path]) -> None:
    import jax

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import get_model
    from repro.peft import load_adapter
    from repro.serve import ServeEngine

    clock.start("check")
    model = get_model(get_config(ARCH))
    params = model.init(jax.random.PRNGKey(SEED))
    store = tenant_store(params, [load_adapter(str(f)) for f in adapter_files])
    engine = ServeEngine(model, params, slots=1, max_len=CHECK_CHUNK * 2,
                         adapter_store=store, paged=True)
    prompt = prompts((CHECK_LEN,), SEED + 1, model.cfg.vocab_size)[0]
    with ops.use_backend("jnp"):
        ref = first_step_logits(engine, prompt, tenant=1)
    got = first_step_logits(engine, prompt, tenant=1)
    compare("pallas vs jnp (tenant 1)", ref, got)
    clock.report("check")


def phase_four_chips(clock: CompileClock) -> None:
    import jax

    from repro.configs import get_config
    from repro.core.adapt import init_adapters
    from repro.launch.mesh import make_serve_mesh
    from repro.models import get_model
    from repro.serve import ServeEngine

    clock.start("tp2")
    model = get_model(get_config(ARCH))
    params = model.init(jax.random.PRNGKey(SEED))
    idx, _ = init_adapters(params, 1)
    store_args = [(idx, seeded_values(idx, s)) for s in (1, 2)]
    prompt = prompts((CHECK_LEN,), SEED + 1, model.cfg.vocab_size)[0]
    logits = {}
    for tp in (1, 2):
        mesh = make_serve_mesh(tp) if tp > 1 else None
        engine = ServeEngine(
            model, params, slots=1, max_len=CHECK_CHUNK * 2, paged=True,
            adapter_store=tenant_store(params, store_args), mesh=mesh,
        )
        if mesh is not None:
            print(f"[tp2] mesh {dict(mesh.shape)}")
        logits[tp] = first_step_logits(engine, prompt, tenant=1)
        del engine
        gc.collect()
    compare("tp2 vs tp1 (tenant 1)", logits[1], logits[2])
    clock.report("tp2")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=2 vs tp=1 serving comparison "
                         "(needs four chips)")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        fail(f"no repro package under {SRC}: run from a checkout of the repo")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        fail(f"need {want} chips, JAX found {len(devices)}")
    from repro.kernels import ops

    backend = ops.get_backend()
    print(f"kernel backend: {backend}; devices: {len(devices)} × "
          f"{devices[0].device_kind}")
    if backend != "pallas":
        fail(f"kernel backend {backend!r} on a TPU, want 'pallas'")
    OUT.mkdir(parents=True, exist_ok=True)
    clock = CompileClock()
    if args.four_chips:
        phase_four_chips(clock)
    else:
        trained = phase_train(clock)
        gc.collect()
        tenants = phase_serve(clock, trained)
        gc.collect()
        phase_check(clock, tenants)
    stats = devices[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
