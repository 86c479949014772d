"""Serving launcher: one base model, N tenants, batched multi-tenant decode.

Single-tenant (merged params, zero runtime overhead):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      [--params merged.npz] --prompts "1,17,25;1,40,41" --max-new 16

Multi-tenant (unmerged adapters from ``train --export-adapter``; requests
cycle through the tenants unless ``--adapter-ids`` pins them):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --adapters a.npz,b.npz --prompts "1,17,25;1,40,41" [--adapter-ids 1,2]

The engine defaults to the paged KV cache (block pool + block tables +
shared-prefix reuse, DESIGN §10); ``--dense`` restores the dense
slots×max_len layout. Prefill is chunked into the serving step
(``--prefill-chunk`` tokens per mixed step, DESIGN §11): a long prompt
never stalls the other streams' decode. ``--draft
{int8,nf4,merged,ngram}`` turns on speculative decoding inside the
decode megastep (DESIGN §12):
a cheap drafter proposes ``--spec-k`` tokens per slot per round, the
full model verifies all k+1 positions in one batched chunk pass, and
greedy outputs stay token-identical to ``--draft off``. Flag
combinations are validated up front with
readable ``SystemExit`` messages — a bad ``--page-size`` should not
surface as a jit-time shape error three layers down.

Observability (DESIGN §13): ``--metrics-out m.prom`` (Prometheus text;
``.json`` for the snapshot form) and ``--trace-out t.json`` (Chrome
trace-event JSON, Perfetto-loadable; ``.jsonl`` for line-delimited)
dump the run's metrics registry and request-lifecycle trace on exit;
``--metrics-every N`` prints a one-line metrics digest every N serve
steps; ``--profile-dir d/`` wraps the run in a ``jax.profiler`` trace
capture for TensorBoard/XProf, where the engine's phase spans
(``serve.step`` and its phases) lie beside the device's operations and
each Pallas kernel carries its name. All of it is host-side — the one
device→host transfer per megastep is unchanged.

A batch run's ``main`` returns its finished requests (tenant, prompt,
output tokens, end reason) so callers can check what was served.
"""

from __future__ import annotations

import argparse
import os

import jax

from repro.configs import ARCH_IDS, PAPER_ARCH_IDS, get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.peft import BASE_DTYPES
from repro.serve import AdapterStore, ServeEngine


def validate_args(args) -> None:
    """Reject bad flag combinations before any compilation starts."""
    if getattr(args, "tp", 1) < 1:
        raise SystemExit(f"--tp must be >= 1, got {args.tp}")
    if args.decode_chunk < 1:
        raise SystemExit(f"--decode-chunk must be >= 1, got {args.decode_chunk}")
    if args.prefill_chunk < 1:
        raise SystemExit(
            f"--prefill-chunk must be >= 1, got {args.prefill_chunk}"
        )
    if args.max_new < 1:
        raise SystemExit(f"--max-new must be >= 1, got {args.max_new}")
    from repro.serve import DRAFT_MODES

    if args.draft not in DRAFT_MODES:
        raise SystemExit(
            f"--draft {args.draft!r} must be one of {', '.join(DRAFT_MODES)}"
        )
    if args.spec_k < 1:
        raise SystemExit(f"--spec-k must be >= 1, got {args.spec_k}")
    from repro.serve.kv_cache import KV_DTYPES

    kv_dtype = getattr(args, "kv_dtype", "fp32")
    if kv_dtype not in KV_DTYPES:
        raise SystemExit(
            f"--kv-dtype {kv_dtype!r} must be one of {', '.join(KV_DTYPES)}"
        )
    if args.draft == "merged" and not args.adapters:
        raise SystemExit(
            "--draft merged drafts with the mean of the registered tenants "
            "and so needs --adapters; use --draft int8/nf4 for a "
            "single-model (quantized self-draft) setup"
        )
    if args.metrics_every < 0:
        raise SystemExit(
            f"--metrics-every must be >= 0, got {args.metrics_every}"
        )
    serve_mode = getattr(args, "serve", False)
    port = getattr(args, "port", None)
    if port is not None:
        if not serve_mode:
            raise SystemExit("--port needs --serve")
        if not 0 <= port <= 65535:
            raise SystemExit(f"--port must be in [0, 65535], got {port}")
    queue_limit = getattr(args, "queue_limit", None)
    if queue_limit is not None and queue_limit < 1:
        raise SystemExit(f"--queue-limit must be >= 1, got {queue_limit}")
    from repro.serve import POLICIES

    fairness = getattr(args, "fairness", "fifo")
    if fairness not in POLICIES:
        raise SystemExit(
            f"--fairness {fairness!r} must be one of {', '.join(POLICIES)}"
        )
    prompt_fields = [p for p in args.prompts.split(";") if p]
    for p in prompt_fields:
        if not any(t.strip() for t in p.split(",")):
            raise SystemExit(f"--prompts entry {p!r} holds no token ids")
    has_prompts = bool(prompt_fields)
    if not has_prompts and not serve_mode:
        for flag, val in (
            ("--metrics-out", args.metrics_out),
            ("--trace-out", args.trace_out),
            ("--profile-dir", args.profile_dir),
        ):
            if val:
                raise SystemExit(
                    f"{flag} needs a serve run to observe; --prompts is empty"
                )
    if args.profile_dir:
        parent = os.path.dirname(os.path.abspath(args.profile_dir))
        if not os.path.isdir(parent):
            raise SystemExit(
                f"--profile-dir parent {parent!r} does not exist"
            )
    for flag, path in (
        ("--metrics-out", args.metrics_out),
        ("--trace-out", args.trace_out),
    ):
        if path:
            parent = os.path.dirname(os.path.abspath(path))
            if not os.path.isdir(parent):
                raise SystemExit(f"{flag} parent {parent!r} does not exist")
    if args.dense:
        if args.paged:
            raise SystemExit("--paged and --dense are mutually exclusive")
        if args.page_size is not None:
            raise SystemExit("--page-size is a paged-engine flag; drop --dense")
        if args.num_blocks is not None:
            raise SystemExit("--num-blocks is a paged-engine flag; drop --dense")
        return
    page = 16 if args.page_size is None else args.page_size
    if page < 1 or page & (page - 1):
        raise SystemExit(f"--page-size must be a power of two, got {page}")
    min_blocks = -(-args.max_len // page)
    if args.num_blocks is not None and args.num_blocks < min_blocks:
        raise SystemExit(
            f"--num-blocks {args.num_blocks} cannot hold one max-length "
            f"request: --max-len {args.max_len} needs {min_blocks} pages "
            f"of {page}"
        )


def _metrics_line(engine, step: int) -> str:
    """One-line digest of the live registry for ``--metrics-every``."""
    v = engine.metrics.value
    fin = engine.metrics.get("serve_requests_finished_total")
    sub = engine.metrics.get("serve_requests_submitted_total")
    line = (
        f"[metrics] step={step}"
        f" finished={int(fin.total)}/{int(sub.total)}"
        f" queue={int(v('serve_queue_depth'))}"
        f" active={int(v('serve_slots_active'))}"
        f" transfers={int(v('serve_transfers_total'))}"
        f" compiles={int(v('serve_jit_compiles'))}"
    )
    if engine.paged:
        line += (
            f" pool={int(v('serve_pool_blocks_used'))}"
            f"/{int(v('serve_pool_blocks_used') + v('serve_pool_blocks_free'))}"
        )
    return line


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b",
                    choices=ARCH_IDS + PAPER_ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--params", default="", help="npz from train --export")
    ap.add_argument("--adapters", default="",
                    help="comma-separated npz files from train --export-adapter; "
                         "each becomes a tenant (adapter id 1..N, 0 = base)")
    ap.add_argument("--adapter-ids", default="",
                    help="comma-separated adapter id per prompt "
                         "(default: cycle 1..N over tenants, 0 when none)")
    ap.add_argument("--prompts", default="1,17,25;1,40,41,42")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="tokens decoded per jitted megastep call (1 = "
                         "classic per-token loop; greedy outputs are "
                         "identical across chunk sizes, sampled ones "
                         "follow a different rng stream)")
    ap.add_argument("--prefill-chunk", type=int, default=256,
                    help="per-step prefill token budget: admitted prompts "
                         "are consumed this many tokens per mixed step "
                         "while decode slots keep advancing (capped at "
                         "--max-len; greedy outputs are identical across "
                         "chunk sizes)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus sampling mass (0 = off); applies to "
                         "temperature>0 rows, greedy rows are untouched")
    ap.add_argument("--base-dtype", default="fp32", choices=BASE_DTYPES,
                    help="serve every tenant off one quantized frozen base")
    ap.add_argument("--quant-block", type=int, default=64,
                    help="scale-block rows; must match the --quant-block "
                         "the adapters were trained against")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: block pool + block tables + "
                         "shared-prefix reuse (already the default; "
                         "conflicts with --dense)")
    ap.add_argument("--dense", action="store_true",
                    help="dense slots×max_len KV cache (the pre-paged layout)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV block (power of two; default 16)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size in blocks (default: slots × "
                         "ceil(max_len / page_size), the dense-equivalent "
                         "token budget)")
    ap.add_argument("--kv-dtype", default="fp32",
                    help="KV cache storage dtype (DESIGN §15): int8 packs "
                         "k/v as symmetric-absmax codes with per-page "
                         "(paged) or per-row-group (dense) fp32 scales — "
                         "~3.9x smaller pool per token, attention "
                         "dequantizes in-kernel; fp32 = exact baseline")
    ap.add_argument("--draft", default="off",
                    help="speculative decoding drafter (DESIGN §12): "
                         "int8/nf4 = quantized self-draft of the frozen "
                         "base, merged = base + mean of tenant deltas "
                         "(needs --adapters), ngram = model-free prompt "
                         "lookup (zero draft forwards; wins wherever "
                         "verification is cheap and output repetitive), "
                         "off = plain decode. Greedy outputs are "
                         "token-identical to --draft off")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards (DESIGN §14): base weights "
                         "Megatron-split, the KV pool partitioned along "
                         "kv-heads (per-shard pool bytes = total / tp), "
                         "greedy outputs token-identical to --tp 1. Must "
                         "divide the local device count and the model's "
                         "head counts")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per speculative round; the full "
                         "model verifies all k+1 positions in one batched "
                         "chunk pass")
    ap.add_argument("--metrics-out", default="",
                    help="dump the metrics registry here on exit: .json = "
                         "snapshot (nested, with histogram p50/p95), any "
                         "other extension = Prometheus text exposition")
    ap.add_argument("--trace-out", default="",
                    help="dump the request-lifecycle trace here on exit: "
                         ".jsonl = one event per line, any other extension "
                         "= Chrome trace-event JSON (load in Perfetto)")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="print a one-line metrics digest every N serve "
                         "steps (0 = off)")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler device trace of the run "
                         "into this directory (TensorBoard/XProf)")
    ap.add_argument("--serve", action="store_true",
                    help="run the async streaming front end (DESIGN §16) "
                         "instead of a batch run: SSE token streaming on "
                         "POST /v1/generate, cancellation, /metrics, "
                         "graceful drain on POST /admin/shutdown. "
                         "--prompts is ignored; requests come over HTTP")
    ap.add_argument("--port", type=int, default=None,
                    help="front-end TCP port (needs --serve; 0 = ephemeral, "
                         "default 8000)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound the admission backlog: submits beyond this "
                         "depth are shed (HTTP 503 + Retry-After under "
                         "--serve, QueueFullError from the API)")
    ap.add_argument("--fairness", default="fifo",
                    help="admission policy: fifo = global arrival order, "
                         "drr = per-tenant deficit round robin (a hot "
                         "tenant cannot starve the others)")
    args = ap.parse_args(argv)
    validate_args(args)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    mesh = None
    if args.tp > 1:
        from repro.launch.mesh import make_serve_mesh

        try:
            mesh = make_serve_mesh(args.tp)
        except ValueError as e:
            raise SystemExit(f"--tp {args.tp}: {e}") from None
        for name, heads in (
            ("num_kv_heads", cfg.num_kv_heads), ("num_heads", cfg.num_heads)
        ):
            if heads % args.tp:
                raise SystemExit(
                    f"--tp {args.tp} does not divide {name}={heads} for "
                    f"--arch {args.arch}"
                )
        print(f"serving tensor-parallel over {args.tp} shards "
              f"(mesh {dict(mesh.shape)})")

    model = get_model(cfg)
    if args.params:
        from repro.checkpoint.manager import load_pytree

        params = jax.tree.map(jax.numpy.asarray, load_pytree(args.params))
    else:
        params = model.init(jax.random.PRNGKey(0))

    if args.base_dtype != "fp32":
        from repro.peft import quantize_base
        from repro.quant import tree_bytes

        before = tree_bytes(params)
        params = quantize_base(params, args.base_dtype, block=args.quant_block)
        print(f"base quantized to {args.base_dtype}: "
              f"{before / 2**20:.1f} MB -> {tree_bytes(params) / 2**20:.1f} MB")

    store = None
    if args.adapters:
        from repro.peft import load_adapter

        store = AdapterStore(base_params=params)
        for path in args.adapters.split(","):
            aid = store.register(*load_adapter(path), name=path)
            print(f"tenant {aid}: {path}")

    tracer = None
    if args.trace_out:
        from repro.obs import Tracer

        tracer = Tracer()
    engine = ServeEngine(
        model, params, slots=args.slots, max_len=args.max_len,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        adapter_store=store, decode_chunk=args.decode_chunk,
        prefill_chunk=args.prefill_chunk,
        paged=not args.dense,
        page_size=16 if args.page_size is None else args.page_size,
        num_blocks=args.num_blocks,
        kv_dtype=args.kv_dtype,
        draft=args.draft, spec_k=args.spec_k,
        tracer=tracer, mesh=mesh,
        queue_limit=args.queue_limit, fairness=args.fairness,
    )
    if args.serve:
        _serve_http(engine, args, tracer)
        return
    prompts = [p for p in args.prompts.split(";") if p]
    n_tenants = store.num_adapters if store is not None else 0
    if args.adapter_ids:
        ids = [int(t) for t in args.adapter_ids.split(",")]
        if len(ids) != len(prompts):
            raise SystemExit(
                f"--adapter-ids has {len(ids)} entries for {len(prompts)} prompts"
            )
    else:
        ids = [1 + i % n_tenants if n_tenants else 0 for i in range(len(prompts))]
    for p, aid in zip(prompts, ids):
        engine.submit([int(t) for t in p.split(",") if t],
                      max_new=args.max_new, adapter_id=aid)
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    try:
        reqs = engine.scheduler.in_flight()
        steps = 0
        while engine.step():
            steps += 1
            if args.metrics_every and steps % args.metrics_every == 0:
                print(_metrics_line(engine, steps))
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()
            print(f"device profile captured to {args.profile_dir}")
    for req in reqs:
        tenant = "base" if req.adapter_id == 0 else f"tenant{req.adapter_id}"
        print(f"req{req.rid} [{tenant}]: prompt={req.prompt} -> {req.out}")
    if args.draft != "off" and engine.spec_drafted:
        rate = engine.spec_accepted / engine.spec_drafted
        print(f"spec[{args.draft} k={args.spec_k}]: "
              f"drafted={engine.spec_drafted} "
              f"accepted={engine.spec_accepted} ({rate:.0%}) "
              f"emitted={engine.spec_emitted}")
    _dump_obs(engine, tracer, args)
    return reqs


def _dump_obs(engine, tracer, args) -> None:
    """Flush --metrics-out / --trace-out (after the drain in serve mode,
    so the dumps cover every request the server handled)."""
    if args.metrics_out:
        if args.metrics_out.endswith(".json"):
            text = engine.metrics.dump_json()
        else:
            text = engine.metrics.expose()
        with open(args.metrics_out, "w") as f:
            f.write(text)
        print(f"metrics written to {args.metrics_out}")
    if args.trace_out:
        tracer.write(args.trace_out)
        print(f"trace written to {args.trace_out} ({len(tracer)} events)")


def _serve_http(engine, args, tracer) -> None:
    """--serve: run the async streaming front end until a graceful
    shutdown (POST /admin/shutdown or Ctrl-C) drains the engine."""
    import asyncio

    from repro.serve import ServeFrontend

    front = ServeFrontend(
        engine, port=8000 if args.port is None else args.port
    )

    async def run():
        port = await front.start()
        print(f"serving on http://{front.host}:{port} "
              f"(POST /v1/generate streams SSE; POST /admin/shutdown drains)",
              flush=True)
        try:
            await front.serve()
        except KeyboardInterrupt:
            await front.shutdown()
            await front.serve()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    print("server drained")
    _dump_obs(engine, tracer, args)


if __name__ == "__main__":
    main()
