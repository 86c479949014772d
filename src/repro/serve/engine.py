"""Multi-tenant batched serving engine — thin host orchestration layer.

The subsystem splits along its natural seams:

* :mod:`repro.serve.scheduler` — admission, slot assignment, chunk
  planning, slot state as dense arrays (host-side, no jax);
* :mod:`repro.serve.kv_cache`  — the dense slot cache and the paged
  block pool: placement only, every cache write happens in-graph;
* :mod:`repro.serve.steps`     — the compiled steps, device graphs only;
* :mod:`repro.serve.sampler`   — sampling fused into those steps;
* :mod:`repro.serve.adapters`  — the tenant registry: N unmerged NeuroAda
  ``(indices, values)`` trees stacked (and cached) for the batched
  kernel path, so one frozen base serves every tenant.

The engine jits ONE function per step kind from
:func:`repro.serve.steps.build_steps` and dispatches by kind:
``chunkstep`` while any admitted prompt still owes chunks (DESIGN §11:
decode slots advance a token while prefilling slots consume their next
``prefill_chunk`` prompt tokens, one compiled shape for every prompt
length), else ``megastep`` (DESIGN §9: a ``lax.scan`` over up to
``decode_chunk`` tokens with sampling, EOS and per-slot masking
in-graph), or with ``draft != "off"`` (DESIGN §12) ``spec_megastep``
(model drafter) or ``ngram_megastep`` (prompt lookup). What an engine
lacks — tenant adapters, paged block tables, a drafter — goes in as
None, so each engine compiles one program per kind. Every compiled step
costs exactly ONE device→host transfer; finished slots are masked
no-ops until the step drains, and freed slots re-admit at step
boundaries. With ``decode_chunk=1`` the megastep reproduces the
per-token loop exactly, so chunking is a pure throughput knob.

With ``paged=True`` (DESIGN §10) the dense slot cache becomes a shared
block pool: admission is block-aware (same-tenant page-aligned prefixes
dedup against refcounted shared blocks), and step boundaries
pre-reserve every position a compiled step can write — the speculative
horizon included, which makes rollback a position rewind — preempting
the *youngest* request back to the queue head on OOM.

Observability (DESIGN §13) is host-side by construction: every counter,
gauge, histogram and trace span derives from the bundle a compiled step
already hands back or from host bookkeeping, so it cannot change the
one-transfer contract and adds no traced inputs (the compile-count
regression test pins that). ``metrics=False`` swaps in the no-op
registry; ``tracer=None`` (the default) skips lifecycle tracing.
``step`` marks its phases (expire, admit, reserve, plan, put, dispatch,
fetch, emit, gauges) with profiler spans (``repro.obs.spans``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs.clock as _clock
from repro.obs import MetricsRegistry, NullRegistry, Tracer
from repro.obs import spans
from repro.obs.spans import span
from repro.serve.adapters import AdapterStore
from repro.serve.kv_cache import KV_DTYPES, DraftKVCache, KVCache, PagedKVCache
from repro.serve.sampler import Sampler
from repro.serve.scheduler import (
    POLICIES,
    QueueFullError,
    RateLimitedError,
    Request,
    Scheduler,
    chunk_rows,
)
from repro.serve.steps import build_steps

__all__ = [
    "QueueFullError",
    "RateLimitedError",
    "Request",
    "ServeEngine",
]


def _finite_or_raise(name: str, value):
    """None passes through; anything else must coerce to a finite float."""
    if value is None:
        return None
    try:
        value = float(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"{name} must be a finite number, got {value!r}"
        ) from None
    if not math.isfinite(value):
        raise ValueError(f"{name} must be a finite number, got {value!r}")
    return value


class ServeEngine:
    def __init__(
        self,
        model,
        params,
        *,
        slots: int = 4,
        max_len: int = 256,
        eos_id: int = 2,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        rng=None,
        adapter_store: AdapterStore | None = None,
        base_dtype: str = "fp32",
        quant_block: int = 64,
        decode_chunk: int = 1,
        prefill_chunk: int = 256,
        paged: bool = False,
        page_size: int = 16,
        num_blocks: int | None = None,
        kv_dtype: str = "fp32",
        draft: str = "off",
        spec_k: int = 4,
        metrics: "MetricsRegistry | bool | None" = None,
        tracer: Tracer | None = None,
        mesh=None,
        queue_limit: int | None = None,
        fairness: str = "fifo",
        quantum: int = 256,
        chaos=None,
        clock=None,
    ):
        if model.cfg.family not in ("dense", "moe", "vlm"):
            # engine currently drives KV-cache LMs; SSM/hybrid/encdec decode
            # through their model APIs directly (see examples).
            raise ValueError(f"ServeEngine supports KV LMs, got {model.cfg.family}")
        if decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, got {decode_chunk}")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if paged and (page_size < 1 or page_size & (page_size - 1)):
            raise ValueError(f"page_size must be a power of two, got {page_size}")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f"kv_dtype {kv_dtype!r} not in {KV_DTYPES}")
        from repro.peft import BASE_DTYPES, quantize_base
        from repro.serve.draft import DRAFT_MODES, build_draft_params

        if base_dtype not in BASE_DTYPES:
            raise ValueError(f"base_dtype {base_dtype!r} not in {BASE_DTYPES}")
        if draft not in DRAFT_MODES:
            raise ValueError(f"draft {draft!r} not in {DRAFT_MODES}")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if draft == "merged" and (
            adapter_store is None or adapter_store.num_adapters == 0
        ):
            raise ValueError(
                "draft='merged' needs an adapter store with registered tenants"
            )
        # ---- tensor-parallel serving mesh (DESIGN §14) -------------------
        # Validated BEFORE any placement: a bad head count must fail here
        # with a readable message, not as a GSPMD error inside the first
        # compiled step three layers down.
        self.mesh = mesh
        self.tp = 1
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"serve mesh needs a 'model' axis, got {mesh.axis_names}"
                )
            self.tp = int(mesh.shape["model"])
            cfg = model.cfg
            if cfg.num_kv_heads % self.tp:
                raise ValueError(
                    f"tp={self.tp} does not divide num_kv_heads="
                    f"{cfg.num_kv_heads} — the KV pool partitions along the "
                    "kv-head axis, so heads must split evenly"
                )
            if cfg.num_heads % self.tp:
                raise ValueError(
                    f"tp={self.tp} does not divide num_heads={cfg.num_heads}"
                )
        if base_dtype != "fp32":
            # one quantized base serves every tenant: the decode/prefill
            # matmuls run the fused dequant path, tenant deltas apply on
            # top. quant_block must match the base the adapters were
            # trained against (launch --quant-block).
            params = quantize_base(params, base_dtype, block=quant_block)
        self.model = model
        self.params = self._place(params)
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.temperature = temperature
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        if mesh is not None:
            # PRNG keys from jax.random are committed to device 0; the
            # multi-device compiled steps need them replicated. A
            # replicated key stays replicated through random.split.
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            self.rng = jax.device_put(self.rng, NamedSharding(mesh, P()))
        self.store = adapter_store
        self.decode_chunk = decode_chunk
        # the chunk buffer width IS the per-step prefill token budget: a
        # mixed step consumes at most this many prompt tokens across all
        # slots, bounding per-step latency at budget + one decode token
        # per decode slot. One compiled shape serves every prompt length.
        self.prefill_chunk = min(prefill_chunk, max_len)
        self.paged = paged
        self.kv_dtype = kv_dtype
        self.draft = draft
        self.spec_k = spec_k
        # one metrics registry per engine unless the caller shares one;
        # ``metrics=False`` swaps in the no-op registry (bench baseline).
        # The former ad-hoc tallies (transfers, preemptions, spec counts)
        # live in the registry now, re-exported as read-only properties.
        if metrics is None or metrics is True:
            self.metrics = MetricsRegistry()
        elif metrics is False:
            self.metrics = NullRegistry()
        else:
            self.metrics = metrics
        self.tracer = tracer
        self._queued_ts: dict[int, float] = {}  # rid -> tracer enqueue ts

        # ---- request lifecycle (DESIGN §16) ------------------------------
        # ONE monotonic clock for every lifecycle timestamp: Request
        # stamps, TTFT/ITL observation, deadline arithmetic and trace
        # events. Default order: an explicit clock= wins, else the
        # tracer's (so spans and histograms literally share a source),
        # else the process-wide repro.obs.clock.
        if clock is not None:
            self.clock = clock
        elif tracer is not None:
            self.clock = tracer.clock
        else:
            self.clock = _clock.now
        if fairness not in POLICIES:
            raise ValueError(f"fairness {fairness!r} not in {POLICIES}")
        self.chaos = chaos
        self.draining = False  # graceful shutdown: intake closed
        # seconds-per-step EMA (None until measured): the deadline-aware
        # admission gate's service-time estimate — a queued request that
        # cannot even reach its first token before its deadline is shed
        # instead of admitted (see _expire_deadlines). The first step of
        # each kind pays JIT compilation, so it never feeds the EMA —
        # seeding with a multi-second compile would make the gate shed
        # every deadline-bearing request until the estimate decays.
        self.step_seconds_ema: float | None = None
        self._step_timed: set[str] = set()

        self.scheduler = Scheduler(
            slots, policy=fairness, queue_limit=queue_limit,
            quantum=quantum, clock=self.clock,
        )
        if paged:
            max_pages = -(-max_len // page_size)
            if num_blocks is None:
                # capacity-equivalent default: same token budget the dense
                # layout would reserve, now shared instead of per-slot
                num_blocks = slots * max_pages
            self.kv = PagedKVCache(
                model, slots, max_len, page_size, num_blocks, mesh=mesh,
                kv_dtype=kv_dtype,
            )
        else:
            self.kv = KVCache(model, slots, max_len, mesh=mesh, kv_dtype=kv_dtype)
        self.sampler = Sampler(model.cfg.vocab_size, top_k=top_k, top_p=top_p)

        # speculative decoding (DESIGN §12): the drafter is derived from
        # the served params once at construction — a quantized self-draft
        # (shared outright when the base is already packed in the same
        # scheme) or the merged mean-of-tenants model — and keeps its own
        # dense KV scratch advanced lock-step with the verified frontier.
        if draft in ("int8", "nf4", "merged"):
            self.draft_params = self._place(build_draft_params(
                self.params, draft, store=adapter_store, quant_block=quant_block
            ))
            self.draft_kv = DraftKVCache(model, slots, max_len, mesh=mesh)
        else:
            # off, or the model-free ngram drafter: no params, no scratch —
            # ngram proposals come from the slot's own committed tokens
            self.draft_params = None
            self.draft_kv = None

        # the mixed step's dense work runs on this many flat token rows,
        # the most a chunk plan can fill, not on the (slots, C) buffer
        self.chunk_rows = chunk_rows(slots, self.prefill_chunk)
        steps = build_steps(
            model, lambda: self.sampler, eos_id=eos_id, max_len=max_len,
            decode_chunk=decode_chunk, spec_k=spec_k, rows=self.chunk_rows,
        )

        # one jitted function per step kind; ``compile_counts`` reads their
        # jit caches, and a cache that grows after warmup is a recompile
        self._jitted: dict[str, object] = {}

        def _jit(name):
            # a sharded engine calls each step inside _sharded_call's scope
            j = self._jitted[name] = jax.jit(steps[name])
            return j if mesh is None else functools.partial(self._sharded_call, j)

        self._chunkstep = _jit("chunkstep")
        self._megastep = _jit("megastep")
        if draft == "ngram":
            # model-free drafter: no drafter cache to feed, so the mixed
            # step runs without one; only decode is speculative
            self._ngram_megastep = _jit("ngram_megastep")
        elif draft != "off":
            self._spec_megastep = _jit("spec_megastep")
        self._obs_init()

    # ------------------------------------------------- sharded dispatch

    def _place(self, tree):
        """Megatron placement of a frozen (possibly packed) param tree
        over the serving mesh: col-parallel qkv/up, row-parallel o/down,
        vocab-sharded embed/head; QuantizedTensor leaves fit the spec to
        their packed data/scales children. fsdp=False — serving shards
        for compute, never for optimizer-state capacity. No mesh: the
        tree unchanged."""
        if self.mesh is None:
            return tree
        from repro.distributed.sharding import param_shardings

        shardings = param_shardings(
            tree, self.mesh, self.model.cfg.family, fsdp=False
        )
        return jax.device_put(tree, shardings)

    def _sharded_call(self, fn, *args):
        """Run one compiled step inside the TP sharding scope.

        Sets the process-global serve mesh (read by the Pallas kernel
        dispatch and ``constrain_kv``) and the Megatron activation layout
        (``inner_all``: heads/FFN hidden shard over ``model``), enters the
        mesh so bare-``P`` constraints resolve, and restores the previous
        context even when tracing raises — tp=1 engines and trainers
        coexisting in this process see none of it."""
        from repro.distributed import context as dist_ctx

        snap = dist_ctx.snapshot()
        dist_ctx.set_serve_mesh(self.mesh)
        dist_ctx.set_activation_sharding(
            None, "model", seq_div=self.tp, variant="inner_all"
        )
        try:
            with self.mesh:
                return fn(*args)
        finally:
            dist_ctx.restore(snap)

    @staticmethod
    def _adapter_args(stacked, aid):
        """The ``(aidx, aval, aid)`` triple a compiled step takes for its
        tenant deltas, or None on an engine without a store."""
        if stacked is None:
            return None
        return (*stacked, jnp.asarray(aid))

    def _stacked(self):
        """The tenant stacks, placed for this engine's mesh (tp=1: the
        raw cached stacks, unchanged)."""
        if self.store is None:
            return None
        if self.mesh is None:
            return self.store.stacked()
        return self.store.stacked_placed(
            self.mesh, self.params, self.model.cfg.family
        )

    # ------------------------------------------------ observability (§13)

    def _obs_init(self) -> None:
        """Bind every metric child once: the hot path touches pre-bound
        instruments only (a float add, or a bisect for histograms) —
        never a registry lookup. All series share the ``serve_`` prefix;
        per-step-kind series carry ``kind`` ∈ mixed|decode|spec, request
        series ``tenant`` (adapter id as a string, ``0`` = base)."""
        reg = self.metrics
        self._c_transfers = reg.counter(
            "serve_transfers_total",
            "Device-to-host fetches (exactly one per compiled step).",
        )
        steps = reg.counter(
            "serve_steps_total", "Compiled serving steps.", labels=("kind",)
        )
        toks = reg.counter(
            "serve_tokens_total", "Tokens emitted.", labels=("kind",)
        )
        secs = reg.histogram(
            "serve_step_seconds", "Compiled-step wall time.", labels=("kind",)
        )
        kinds = ("mixed", "decode", "spec")
        self._c_step = {k: steps.labels(k) for k in kinds}
        self._c_tokens = {k: toks.labels(k) for k in kinds}
        self._h_step = {k: secs.labels(k) for k in kinds}
        self._c_submitted = reg.counter(
            "serve_requests_submitted_total",
            "Requests accepted by submit().",
            labels=("tenant",),
        )
        self._c_admitted = reg.counter(
            "serve_requests_admitted_total",
            "Queue-to-slot admissions (re-admissions after preemption "
            "included).",
            labels=("tenant",),
        )
        self._c_finished = reg.counter(
            "serve_requests_finished_total",
            "Completed requests by termination reason.",
            labels=("tenant", "reason"),
        )
        shed = reg.counter(
            "serve_requests_shed_total",
            "Requests refused at intake or admission (never a slot): "
            "bounded-queue overflow, tenant rate limit, or a deadline "
            "that cannot be met.",
            labels=("reason",),
        )
        self._c_shed = {
            k: shed.labels(k) for k in ("queue_full", "rate_limit", "deadline")
        }
        cancelled = reg.counter(
            "serve_requests_cancelled_total",
            "cancel() calls that found a live request (mid-queue, "
            "mid-prefill or mid-decode).",
            labels=("phase",),
        )
        self._c_cancelled = {
            k: cancelled.labels(k) for k in ("queued", "prefill", "decode")
        }
        expired = reg.counter(
            "serve_deadline_expired_total",
            "Requests evicted by the boundary deadline sweep.",
            labels=("phase",),
        )
        self._c_expired = {
            k: expired.labels(k) for k in ("queued", "prefill", "decode")
        }
        pre = reg.counter(
            "serve_preemptions_total",
            "Block-pool OOM evictions back to the queue head.",
            labels=("phase",),
        )
        self._c_preempt = {
            "decode": pre.labels("decode"),
            "prefill": pre.labels("prefill"),
        }
        self._c_tenant_tokens = reg.counter(
            "serve_tenant_tokens_total",
            "Tokens emitted per tenant (adapter id 0 = base).",
            labels=("tenant",),
        )
        self._h_ttft = reg.histogram(
            "serve_ttft_seconds", "Submit-to-first-token latency."
        )
        self._h_itl = reg.histogram(
            "serve_itl_seconds",
            "Inter-token latency (host arrival; tokens sharing a "
            "megastep split its wall evenly).",
        )
        self._g_queue = reg.gauge(
            "serve_queue_depth", "Requests waiting for a slot."
        )
        self._g_active = reg.gauge(
            "serve_slots_active", "Slots holding an admitted request."
        )
        self._g_tenants = reg.gauge(
            "serve_tenants_registered", "Adapters in the tenant store."
        )
        self._g_compiles = reg.gauge(
            "serve_jit_compiles",
            "Compiled variants across all step functions (jit cache "
            "entries); flat after warmup.",
        )
        self._g_stack_builds = reg.gauge(
            "serve_adapter_stack_builds",
            "Full tenant-tree re-stacks (should track register/remove "
            "count, not step count).",
        )
        # static placement facts, set once: the bench's sharded section
        # reads these to show per-shard pool bytes = unsharded / TP
        self._g_tp = reg.gauge(
            "serve_tp_size",
            "Tensor-parallel shards serving this engine (1 = unsharded).",
        )
        # effective *packed* bytes — int8 codes + fp32 scales — labeled by
        # storage dtype so fp32/int8 twins stay distinguishable when they
        # share a registry (DESIGN §15)
        self._g_pool_bytes = reg.gauge(
            "serve_pool_bytes",
            "Effective packed KV cache/pool bytes (data + scales) across "
            "all shards (logical total).",
            labels=("kv_dtype",),
        ).labels(self.kv_dtype)
        self._g_pool_bytes_shard = reg.gauge(
            "serve_pool_bytes_per_shard",
            "Effective packed KV cache/pool bytes ONE shard holds "
            "(total / TP sharded).",
            labels=("kv_dtype",),
        ).labels(self.kv_dtype)
        self._g_tp.set(self.tp)
        self._g_pool_bytes.set(self.kv.pool_bytes())
        self._g_pool_bytes_shard.set(self.kv.pool_bytes_per_shard())
        if self.paged:
            self._g_pool_used = reg.gauge(
                "serve_pool_blocks_used", "KV pool blocks allocated."
            )
            self._g_pool_free = reg.gauge(
                "serve_pool_blocks_free", "KV pool blocks on the free list."
            )
            self._g_pool_shared = reg.gauge(
                "serve_pool_shared_blocks",
                "Blocks referenced by >1 slot (live prefix reuse).",
            )
            self._c_prefix_hit = reg.counter(
                "serve_prefix_pages_hit_total",
                "Admission prompt pages dedup'd against resident blocks.",
            )
            self._c_prefix_fresh = reg.counter(
                "serve_prefix_pages_fresh_total",
                "Admission prompt pages freshly allocated.",
            )
            self._scraped_prefix = (0, 0)
        if self.draft != "off":
            self._c_spec_drafted = reg.counter(
                "serve_spec_drafted_total", "Drafter proposals (all slots)."
            )
            self._c_spec_accepted = reg.counter(
                "serve_spec_accepted_total", "Proposals the verifier accepted."
            )
            self._c_spec_emitted = reg.counter(
                "serve_spec_emitted_total",
                "Tokens emitted through the speculative path.",
            )
            self._h_spec_accept = reg.histogram(
                "serve_spec_accept_len",
                "Accepted-prefix length per live slot-round (0..spec_k).",
                buckets=tuple(float(i) for i in range(self.spec_k + 1)),
            )

    def _update_gauges(self) -> None:
        """Refresh the point-in-time gauges after a step (pure host state:
        queue depth, slot occupancy, pool free-list, jit cache sizes —
        no device traffic)."""
        self._g_queue.set(self.scheduler.queue_depth)
        self._g_active.set(sum(r is not None for r in self.scheduler.active))
        self._g_compiles.set(self.compile_count())
        if self.store is not None:
            self._g_tenants.set(self.store.num_adapters)
            self._g_stack_builds.set(self.store.stack_builds)
        if self.paged:
            self._g_pool_used.set(self.kv.used_blocks)
            self._g_pool_free.set(self.kv.free_blocks)
            self._g_pool_shared.set(self.kv.shared_blocks)
            hits, fresh = self.kv.prefix_page_hits, self.kv.prefix_page_fresh
            h0, f0 = self._scraped_prefix
            self._c_prefix_hit.inc(hits - h0)
            self._c_prefix_fresh.inc(fresh - f0)
            self._scraped_prefix = (hits, fresh)

    def compile_counts(self) -> dict[str, int]:
        """Jit cache size per registered step kind. Every entry is one
        traced compilation; a steady-state engine compiles each kind it
        runs once, so the counts must be flat across steps after warmup
        (the regression test drives mixed, decode and spec steps and
        asserts exactly that)."""
        return {name: int(j._cache_size()) for name, j in self._jitted.items()}

    def compile_count(self) -> int:
        return sum(self.compile_counts().values())

    def _emit_token(self, req: Request, tok: int, kind: str, now: float) -> None:
        """Append one emitted token and record its latency metrics: the
        first token per request observes TTFT, later ones ITL (tokens
        sharing one compiled step land host-side together and split the
        gap evenly via the caller's ``now`` spreading)."""
        req.out.append(tok)
        if len(req.out) == 1:
            self._h_ttft.observe(now - req.t_submit)
            if self.tracer is not None:
                self.tracer.instant(req.rid, "first_token")
        elif req.t_last:
            self._h_itl.observe(now - req.t_last)
        req.t_last = now
        self._c_tenant_tokens.labels(str(req.adapter_id)).inc()

    def _finish(self, slot: int, req: Request) -> None:
        """Complete a request that ran to its in-graph stop: classify the
        termination reason the same way the compiled mask fired it
        (EOS | max_new | cache full, in that order)."""
        if req.out and req.out[-1] == self.eos_id:
            reason = "eos"
        elif len(req.out) >= req.max_new:
            reason = "max_new"
        else:
            reason = "cache_full"
        self._terminate(slot, req, reason)

    def _terminate(self, slot: int | None, req: Request, reason: str) -> None:
        """The ONE exit path every request takes (DESIGN §16 state
        machine): stamp the terminal reason, count it, trace it, and
        reclaim whatever the request held — its slot and cache pages when
        admitted (``slot`` given: the same ``complete`` + ``evict`` pair
        preemption uses, minus the re-queue), nothing when it dies in the
        queue (``slot=None``)."""
        req.reason = reason
        req.done = True
        self._c_finished.labels(str(req.adapter_id), reason).inc()
        if self.tracer is not None:
            now = self.tracer.now()
            t_q = self._queued_ts.pop(req.rid, None)
            if t_q is not None and slot is None:
                # died queued: close the open queued span first
                self.tracer.span(req.rid, "queued", t_q, now)
            self.tracer.instant(
                req.rid, "finish", ts=now, reason=reason, tokens=len(req.out)
            )
        if slot is not None:
            self.scheduler.complete(slot)
            self.kv.evict(slot)

    # ---------------------------------------- registry-backed telemetry

    @property
    def transfers(self) -> int:
        """Device→host fetches: one per compiled step (registry-backed;
        the transfer-counting tests pin it against ``jax.device_get``)."""
        return int(self._c_transfers.value)

    @property
    def preemptions(self) -> int:
        """Block-pool OOM evictions (paged only), all phases."""
        return int(
            self._c_preempt["decode"].value + self._c_preempt["prefill"].value
        )

    @property
    def preemptions_mid_prefill(self) -> int:
        """… of which the victim was still mid-prefill."""
        return int(self._c_preempt["prefill"].value)

    @property
    def spec_drafted(self) -> int:
        return int(self._c_spec_drafted.value) if self.draft != "off" else 0

    @property
    def spec_accepted(self) -> int:
        return int(self._c_spec_accepted.value) if self.draft != "off" else 0

    @property
    def spec_emitted(self) -> int:
        return int(self._c_spec_emitted.value) if self.draft != "off" else 0

    # ------------------------------------------------------------- intake

    def submit(
        self,
        prompt: list[int],
        max_new: int = 32,
        *,
        adapter_id: int = 0,
        temperature: float | None = None,
        deadline: float | None = None,
        timeout: float | None = None,
    ) -> int:
        """Enqueue one request. ``timeout`` (seconds from now) is sugar
        for an absolute ``deadline`` on the engine clock; a request whose
        deadline passes — queued or admitted — is evicted at the next
        step boundary with reason="deadline". Raises ValueError on a
        malformed request, :class:`QueueFullError` /
        :class:`RateLimitedError` on shed (both carry ``retry_after``),
        RuntimeError once :meth:`drain` has closed intake."""
        if not prompt:
            raise ValueError("empty prompt")
        if max_new <= 0:
            raise ValueError(f"max_new must be positive, got {max_new}")
        if self.draining:
            raise RuntimeError("engine is draining: intake closed")
        if len(prompt) > self.max_len - 1:
            raise ValueError(f"prompt length {len(prompt)} >= max_len {self.max_len}")
        n_reg = self.store.num_adapters if self.store is not None else 0
        if not 0 <= adapter_id <= n_reg:
            raise ValueError(
                f"adapter_id {adapter_id} not registered (have {n_reg} + base)"
            )
        # coerce/validate the numeric knobs HERE: temperature flows into a
        # float32 slot array and deadline/timeout into clock arithmetic —
        # a non-numeric value must be a 400-class ValueError at intake,
        # never a crash inside step() (which would kill the whole server)
        temperature = _finite_or_raise("temperature", temperature)
        deadline = _finite_or_raise("deadline", deadline)
        timeout = _finite_or_raise("timeout", timeout)
        if timeout is not None:
            if timeout <= 0:
                raise ValueError(f"timeout must be positive, got {timeout}")
            deadline = self.clock() + timeout
        if deadline is not None and self.step_seconds_ema is not None:
            # deadline-aware admission: even if admitted IMMEDIATELY the
            # request needs ~one compiled step to produce a token — if the
            # deadline can't cover that, shed now instead of queue-then-
            # evict (the client's retry budget is better spent elsewhere)
            if deadline - self.clock() < self.step_seconds_ema:
                self._c_shed["deadline"].inc()
                raise QueueFullError(
                    self.scheduler.queue_depth,
                    self.scheduler.queue_limit,
                    retry_after=0.0,
                    reason="deadline unreachable: "
                    f"{max(deadline - self.clock(), 0.0):.3f}s left, "
                    f"steps take ~{self.step_seconds_ema:.3f}s",
                )
        temp = self.temperature if temperature is None else temperature
        try:
            rid = self.scheduler.submit(
                prompt, max_new, adapter_id=adapter_id, temperature=temp,
                store_rev=self.store.removals if self.store is not None else 0,
                deadline=deadline,
            )
        except QueueFullError:
            self._c_shed["queue_full"].inc()
            raise
        except RateLimitedError:
            self._c_shed["rate_limit"].inc()
            raise
        self._c_submitted.labels(str(adapter_id)).inc()
        self._g_queue.set(self.scheduler.queue_depth)
        if self.tracer is not None:
            ts = self.tracer.now()
            self.tracer.instant(
                rid, "submit", ts=ts, prompt_tokens=len(prompt),
                max_new=max_new, tenant=adapter_id,
            )
            self._queued_ts[rid] = ts
        return rid

    def set_rate_limit(
        self, adapter_id: int, rate: float, burst: float | None = None
    ) -> None:
        """Per-tenant token-bucket admission limit (pass-through to the
        scheduler): sustained ``rate`` submits/sec with ``burst`` head-
        room; violators get :class:`RateLimitedError` with retry_after."""
        self.scheduler.set_rate_limit(adapter_id, rate, burst=burst)

    # -------------------------------------------- cancellation & deadlines

    def cancel(self, rid: int) -> bool:
        """Cancel one request wherever it is — mid-queue, mid-prefill or
        mid-decode — reclaiming everything it holds (slot, cache pages,
        refcounts) at host level, exactly like a preemption minus the
        re-queue. Idempotent: False when the rid is unknown or already
        terminal. Safe between steps only (the front end routes cancels
        through the engine thread's command queue for exactly this
        reason)."""
        req = self.scheduler.get(rid)
        if req is None or req.done:
            return False
        req.cancelled = True
        slot = self.scheduler.slot_of(rid)
        if slot is None:
            phase = "queued"
            self.scheduler.remove_queued(rid)
        else:
            phase = "prefill" if req.mid_prefill else "decode"
        self._c_cancelled[phase].inc()
        self._terminate(slot, req, "cancelled")
        self._g_queue.set(self.scheduler.queue_depth)
        return True

    def _expire_deadlines(self) -> None:
        """Boundary sweep: every in-flight request whose deadline has
        passed — queued or admitted — is evicted with reason="deadline".
        Runs before admission so an expired queued request never takes a
        slot it would immediately give back."""
        now = self.clock()
        for req in self.scheduler.expired_queued(now):
            self._c_expired["queued"].inc()
            self._terminate(None, req, "deadline")
        for slot, req in enumerate(self.scheduler.active):
            if (
                req is not None
                and req.deadline is not None
                and req.deadline <= now
            ):
                self._c_expired["prefill" if req.mid_prefill else "decode"].inc()
                self._terminate(slot, req, "deadline")
        self._g_queue.set(self.scheduler.queue_depth)

    def drain(self) -> list[Request]:
        """Graceful shutdown: close intake (further submits raise), run
        the engine until every in-flight request reaches a terminal
        state, return them. Metrics/trace dumps are the caller's to
        flush — the engine only guarantees the pool is fully drained."""
        self.draining = True
        return self.run_to_completion()

    def _check_adapter_ids(self) -> None:
        """Requests freeze their adapter id at submit; a store.remove()
        after that shifts ids under them — including *middle* removals
        that keep every id in range but re-point it at another tenant.
        Each request is stamped with the store's removal revision at
        submit; any stale-revision request still naming a tenant fails
        loudly instead of silently decoding with the wrong delta."""
        if self.store is None:
            return
        rev = self.store.removals
        for req in self.scheduler.in_flight():
            if req.adapter_id > 0 and req.store_rev != rev:
                raise RuntimeError(
                    f"request {req.rid} holds adapter_id {req.adapter_id} "
                    "validated against a store revision that has since seen "
                    "remove() — ids shifted; drain in-flight requests before "
                    "removing tenants"
                )

    def _try_place(self, slot: int, req: Request) -> bool:
        """Block-aware admission gate (paged): reserve the prompt's pages
        (shared prefix pages dedup against live, already-written blocks)
        PLUS the first decode chunk's headroom, or refuse. Without the
        headroom a constrained pool thrashes: the request prefills, the
        chunk reservation comes up short, and the freshly admitted
        request — the youngest — is the first preempted, burning one full
        prefill per generated token. A successful prefix dedup fast-
        forwards the request's chunk walk past the resident pages — their
        k/v are already in the pool, so only the private tail (and at
        least the final basis token, which samples the next one) still
        runs through the mixed step."""
        toks = req.prompt + req.out
        shared_lead = self.kv.admit(slot, toks, req.adapter_id)
        if shared_lead is None:
            return False
        if not self.kv.reserve(
            slot, min(len(toks) + self._decode_horizon(), self.max_len)
        ):
            self.kv.evict(slot)  # full rollback: prompt pages + partials
            return False
        if self.draft_kv is None:
            req.prefilled = min(shared_lead, req.prefill_target - 1)
        # under MODEL drafting the chunk walk re-runs shared-prefix
        # tokens: the main cache's writes on shared pages drop through the
        # write-table sentinel (their contents are already exact), but the
        # drafter's dense scratch has no block sharing and must ingest
        # every basis token itself or it drafts against holes. Correctness
        # would survive a cold drafter — acceptance would not. The ngram
        # drafter has no scratch (proposals come from the token history),
        # so it keeps the fast-forward.
        return True

    def _admit(self) -> None:
        """Token-budget admission: queued requests enter free slots with
        zero prefill progress — the mixed chunk steps that follow consume
        their prompts ``prefill_chunk`` tokens at a time. No compilation,
        no splice, no pow2 buckets: admission is pure bookkeeping."""
        placed = self.scheduler.admissible(
            self._try_place if self.paged else None
        )
        for slot, req in placed:
            self._c_admitted.labels(str(req.adapter_id)).inc()
            if self.tracer is not None:
                now = self.tracer.now()
                t_q = self._queued_ts.pop(req.rid, now)
                self.tracer.span(req.rid, "queued", t_q, now)
                self.tracer.instant(
                    req.rid, "admitted", ts=now, slot=slot,
                    resume=bool(req.out),
                    prefill_target=req.prefill_target,
                    prefilled=req.prefilled,
                )

    # --------------------------------------------------------------- step

    def step(self) -> bool:
        """One compiled step over all active slots. False when fully idle.

        While any admitted prompt still owes chunks this is a mixed
        prefill+decode step (one prompt chunk under the token budget,
        one token per decode slot); otherwise it is a decode megastep
        over up to ``decode_chunk`` tokens. Either way: one jitted call,
        one device→host transfer. ``serve_step_seconds`` observes the
        whole call, the interval of its ``serve.step`` span.
        """
        t_call = self.clock()
        with span(spans.SERVE_STEP):
            kind = self._step()
            if kind is not None:
                self._h_step[kind].observe(self.clock() - t_call)
        return kind is not None

    def _step(self) -> str | None:
        """The body of :meth:`step`: its kind, or None when idle."""
        self.rng, k_step = jax.random.split(self.rng)
        if self.chaos is not None:
            # faults land at the exact boundary real ones do: before the
            # sweep (a stormed deadline expires THIS step) and before
            # admission (stolen pool blocks refuse placements THIS step)
            self.chaos.on_step(self)
        with span(spans.SERVE_EXPIRE):
            self._expire_deadlines()
        with span(spans.SERVE_ADMIT):
            self._check_adapter_ids()
            self._admit()
        if not self.scheduler.has_active():
            if self.chaos is not None:
                # this step's own injections may have just terminated the
                # last request; hand any stolen pool blocks back before
                # reporting idle (nobody will call step() again)
                self.chaos.release(self)
            return None
        t0 = self.clock()
        if self.scheduler.has_prefilling():
            kind = "mixed"
            self._chunk_step(k_step)
        elif self.draft != "off":
            kind = "spec"
            self._spec_decode_step(k_step)
        else:
            kind = "decode"
            self._decode_step(k_step)
        # step accounting is pure host arithmetic on the clocks and
        # free-lists the step already maintained — no device traffic
        dt = self.clock() - t0
        self._c_step[kind].inc()
        # EMA of compiled-step wall time feeds deadline-aware admission:
        # a request whose deadline cannot cover even one more step is
        # refused instead of admitted-then-evicted (DESIGN §16). The
        # first observation per step kind is the JIT compile and is
        # discarded; later recompile spikes (>10x the estimate) are too.
        if kind not in self._step_timed:
            self._step_timed.add(kind)
        elif self.step_seconds_ema is None:
            self.step_seconds_ema = dt
        elif dt < 10.0 * self.step_seconds_ema:
            self.step_seconds_ema = 0.9 * self.step_seconds_ema + 0.1 * dt
        with span(spans.SERVE_GAUGES):
            self._update_gauges()
        return kind

    # ------------------------------------------------- mixed chunk step

    def _chunk_step(self, key) -> None:
        """One mixed prefill+decode step (DESIGN §11): carve the chunk
        plan, pre-reserve the positions it writes (paged), run the one
        compiled mixed graph, then replay emissions into the Request
        lifecycle and register freshly written prefix pages for dedup."""
        tr0 = self.tracer.now() if self.tracer is not None else 0.0
        if self.paged:
            with span(spans.SERVE_RESERVE):
                self._reserve(1)
        with span(spans.SERVE_PLAN) as sp:
            plan = self.scheduler.chunk_plan(self.prefill_chunk, self.kv.pos_host)
            stacked = self._stacked()
            # the rows the device computes against those holding a token
            counts = {"rows": self.chunk_rows, "real_rows": int(plan["q_len"].sum())}
            if self.paged:
                # pages the prefill attention kernel sweeps (each slot with
                # a query, up to its frontier) against the whole table
                vl = (plan["q_offset"] + plan["q_len"])[plan["q_len"] > 0]
                counts["kv_pages"] = int((-(-vl // self.kv.page_size)).sum())
                counts["kv_pages_grid"] = self.kv.table.size
            sp.set_metadata(**counts)
        # a model drafter's scratch rides the mixed step; ngram has none
        dcache = self.draft_kv.data if self.draft_kv is not None else None
        with span(spans.SERVE_PUT):
            adapters = self._adapter_args(stacked, plan["aid"])
            table = self.kv.table_device() if self.paged else None
            wtable = self.kv.write_table_device() if self.paged else None
            inputs = [jnp.asarray(plan[k]) for k in
                      ("tokens", "q_offset", "q_len", "last_idx", "temps")]
        with span(spans.SERVE_DISPATCH):
            self.kv.data, dcache, pos_dev, toks_dev = self._chunkstep(
                self.params, self.draft_params, adapters, table, wtable,
                self.kv.data, dcache, *inputs, key,
            )
        if self.draft_kv is not None:
            self.draft_kv.data = dcache
        # ONE device→host transfer for the whole mixed step: the sampled
        # token vector. Positions advance deterministically to
        # q_offset + q_len, so the host mirrors them without a fetch.
        with span(spans.SERVE_FETCH):
            toks = jax.device_get(toks_dev)
        with span(spans.SERVE_EMIT) as sp:
            self._c_transfers.inc()
            self.kv.sync(pos_dev, plan["q_offset"] + plan["q_len"])
            now = self.clock()
            tr1 = self.tracer.now() if self.tracer is not None else 0.0
            n_emit = 0
            for s, req in enumerate(self.scheduler.active):
                if req is None:
                    continue
                take = int(plan["q_len"][s])
                if take and req.mid_prefill:
                    if self.tracer is not None:
                        self.tracer.span(
                            req.rid, "prefill_chunk", tr0, tr1, tokens=take,
                            offset=int(plan["q_offset"][s]),
                        )
                    req.prefilled += take
                    if self.paged:
                        self.kv.mark_prefilled(s, req.prefilled)
                elif take and self.tracer is not None:
                    # decode slot riding the mixed step as a one-token chunk
                    self.tracer.span(req.rid, "decode", tr0, tr1, tokens=1,
                                     mixed=True)
                if plan["emit"][s]:
                    n_emit += 1
                    self._emit_token(req, int(toks[s]), "mixed", now)
                    self._maybe_finish(s, req)
            self._c_tokens["mixed"].inc(n_emit)
            sp.set_metadata(emitted=n_emit)

    def _decode_horizon(self) -> int:
        """Worst-case per-megastep position advance of one decode slot:
        one token per scan step plain; K accepted drafts + the bonus per
        round speculative. Step boundaries pre-reserve pages to this
        horizon so the compiled bodies never allocate — which is exactly
        what makes speculative rejection free: every row a rejected draft
        wrote is already owned, so rollback is a position rewind."""
        if self.draft == "off":
            return self.decode_chunk
        return self.decode_chunk * (self.spec_k + 1)

    def _reserve(self, horizon: int) -> None:
        """Pre-reserve every position the next compiled step can write
        (paged): each decode slot gets pages covering ``pos + horizon``
        (capped at ``max_len``) — one token for the mixed step, the full
        ``decode_chunk`` for the megastep; prefill chunks land in pages
        admission already placed, so mid-prefill slots need nothing. On
        shortfall the youngest admitted request — possibly itself
        mid-prefill — is preempted back to the queue head (its progress
        resets with its pages; it re-prefills over ``prompt + out`` later
        and its greedy continuation is identical) and the round retries.
        A single admitted request always fits (``num_blocks`` covers one
        max-length request by construction).
        """
        while True:
            short = False
            for s, req in enumerate(self.scheduler.active):
                if req is None or req.mid_prefill:
                    continue
                target = min(int(self.kv.pos_host[s]) + horizon, self.max_len)
                if not self.kv.reserve(s, target):
                    short = True
                    break
            if not short:
                return
            self._preempt_youngest()

    def _preempt_youngest(self) -> None:
        victim = self.scheduler.youngest_active()
        if sum(r is not None for r in self.scheduler.active) <= 1:
            raise RuntimeError(
                "paged KV pool cannot hold a single request's chunk — "
                "num_blocks too small for max_len (validated at init; "
                "this indicates refcount leakage)"
            )
        req = self.scheduler.active[victim]
        phase = "prefill" if req.mid_prefill else "decode"
        self._c_preempt[phase].inc()
        if self.tracer is not None:
            now = self.tracer.now()
            self.tracer.instant(
                req.rid, "preempt", phase=phase, slot=victim,
                tokens_done=len(req.out),
            )
            # re-queued at the front: the next "queued" span starts here
            self._queued_ts[req.rid] = now
        self.scheduler.preempt(victim)
        self.kv.evict(victim)

    # ---------------------------------------------------- decode megastep

    def _megastep_args(self, st, stacked, key):
        """The tenant triple, read table and slot-state inputs that both
        decode megasteps take, uploaded (call inside ``serve.put``)."""
        adapters = self._adapter_args(stacked, st["aid"])
        table = self.kv.table_device() if self.paged else None
        inputs = (
            jnp.asarray(st["tokens"]), self.kv.pos,
            jnp.asarray(st["active"]), jnp.asarray(st["remaining"]),
            jnp.asarray(st["temps"]), key,
        )
        return adapters, table, inputs

    def _decode_step(self, key) -> None:
        """One decode megastep over all active slots: up to
        ``decode_chunk`` tokens per slot in one compiled call."""
        tr0 = self.tracer.now() if self.tracer is not None else 0.0
        if self.paged:
            with span(spans.SERVE_RESERVE):
                self._reserve(self.decode_chunk)
        with span(spans.SERVE_PLAN):
            st = self.scheduler.slot_arrays()
            stacked = self._stacked()
        with span(spans.SERVE_PUT):
            adapters, table, inputs = self._megastep_args(st, stacked, key)
        with span(spans.SERVE_DISPATCH):
            self.kv.data, *fetched = self._megastep(
                self.params, adapters, table, self.kv.data, *inputs
            )
        pos_dev = fetched[0]
        # ONE device→host transfer for the whole chunk (all slots, all
        # steps): emitted tokens + mask, final positions, survivor mask.
        with span(spans.SERVE_FETCH):
            pos_np, active_np, toks, emits = jax.device_get(fetched)
        with span(spans.SERVE_EMIT) as sp:
            self._c_transfers.inc()
            now = self.clock()
            tr1 = self.tracer.now() if self.tracer is not None else 0.0
            self.kv.sync(pos_dev, pos_np)
            n_emit = 0
            for t in range(self.decode_chunk):
                for s, req in enumerate(self.scheduler.active):
                    if req is not None and emits[t, s]:
                        self._emit_token(req, int(toks[t, s]), "decode", now)
                        n_emit += 1
            self._c_tokens["decode"].inc(n_emit)
            if self.tracer is not None:
                for s, req in enumerate(self.scheduler.active):
                    if req is not None:
                        self.tracer.span(
                            req.rid, "decode", tr0, tr1,
                            tokens=int(emits[:, s].sum()),
                        )
            for s, req in enumerate(self.scheduler.active):
                if req is not None and not active_np[s]:
                    # the in-graph mask already encodes EOS/max_new/cache-
                    # full; completing off it keeps host and device
                    # lifecycles identical
                    self._finish(s, req)
            sp.set_metadata(emitted=n_emit)

    def _spec_decode_step(self, key) -> None:
        """One speculative decode megastep (DESIGN §12): ``decode_chunk``
        draft/verify/accept rounds over all active slots in one compiled
        call, then replay the (round, slot, K+1) emission bundle into the
        Request lifecycle exactly like the plain megastep replays its
        (chunk, slots) matrix."""
        tr0 = self.tracer.now() if self.tracer is not None else 0.0
        if self.paged:
            with span(spans.SERVE_RESERVE):
                self._reserve(self._decode_horizon())
        ngram = self.draft == "ngram"
        with span(spans.SERVE_PLAN):
            st = self.scheduler.slot_arrays()
            stacked = self._stacked()
            if ngram:
                # rebuild the token history on the host: hist[s, :len(seq)]
                # is the committed sequence, and pos[s] == len(seq) - 1 at
                # every decode boundary (the current input token is
                # seq[-1]) — the invariant the in-graph matcher and
                # appender rely on
                hist = np.zeros((self.slots, self.max_len), np.int32)
                for s, req in enumerate(self.scheduler.active):
                    if req is not None:
                        seq = req.prompt + req.out
                        hist[s, : len(seq)] = seq
        with span(spans.SERVE_PUT):
            adapters, table, inputs = self._megastep_args(st, stacked, key)
            if ngram:
                hist = jnp.asarray(hist)
        with span(spans.SERVE_DISPATCH):
            if ngram:
                self.kv.data, *fetched = self._ngram_megastep(
                    self.params, adapters, table, self.kv.data, hist, *inputs
                )
            else:
                self.kv.data, self.draft_kv.data, *fetched = self._spec_megastep(
                    self.params, self.draft_params, adapters, table,
                    self.kv.data, self.draft_kv.data, *inputs,
                )
        pos_dev = fetched[0]
        # still ONE device→host transfer for the whole megastep: positions,
        # survivor mask, candidate tokens + emit mask, acceptance counts,
        # round-entry live masks — one fetch of the bundle
        with span(spans.SERVE_FETCH):
            pos_np, active_np, toks, emits, accs, lives = jax.device_get(fetched)
        with span(spans.SERVE_EMIT) as sp:
            self._c_transfers.inc()
            now = self.clock()
            tr1 = self.tracer.now() if self.tracer is not None else 0.0
            self.kv.sync(pos_dev, pos_np)
            n_emit = 0
            slot_rounds = [0] * self.slots
            slot_tokens = [0] * self.slots
            slot_accepted = [0] * self.slots
            for r in range(self.decode_chunk):
                for s, req in enumerate(self.scheduler.active):
                    if req is None:
                        continue
                    if lives[r, s]:
                        acc = int(accs[r, s])
                        req.spec_drafted += self.spec_k
                        req.spec_accepted += acc
                        self._c_spec_drafted.inc(self.spec_k)
                        self._c_spec_accepted.inc(acc)
                        self._h_spec_accept.observe(acc)
                        slot_rounds[s] += 1
                        slot_accepted[s] += acc
                    for j in range(self.spec_k + 1):
                        if emits[r, s, j]:
                            self._emit_token(req, int(toks[r, s, j]), "spec", now)
                            self._c_spec_emitted.inc()
                            n_emit += 1
                            slot_tokens[s] += 1
            self._c_tokens["spec"].inc(n_emit)
            if self.tracer is not None:
                for s, req in enumerate(self.scheduler.active):
                    if req is not None:
                        self.tracer.span(
                            req.rid, "spec_round", tr0, tr1,
                            rounds=slot_rounds[s], accepted=slot_accepted[s],
                            tokens=slot_tokens[s],
                        )
            for s, req in enumerate(self.scheduler.active):
                if req is not None and not active_np[s]:
                    self._finish(s, req)
            sp.set_metadata(emitted=n_emit)

    def _maybe_finish(self, slot: int, req: Request) -> None:
        if (
            req.out[-1] == self.eos_id
            or len(req.out) >= req.max_new
            or self.kv.full(slot)
        ):
            self._finish(slot, req)

    def run_to_completion(self) -> list[Request]:
        """Drain everything in flight: queued AND already-admitted active
        slots (the seed engine dropped the latter from its snapshot)."""
        reqs = self.scheduler.in_flight()
        while self.step():
            pass
        return reqs
