"""Multi-tenant batched serving engine — thin orchestration layer.

The subsystem splits along its natural seams:

* :mod:`repro.serve.scheduler` — FIFO admission, slot assignment, chunk
  planning, slot state as dense arrays (host-side, no jax);
* :mod:`repro.serve.kv_cache`  — the dense slot cache and the paged
  block pool: placement only, every cache write happens in-graph;
* :mod:`repro.serve.sampler`   — greedy/temperature/top-k sampling fused
  into the jitted calls;
* :mod:`repro.serve.adapters`  — the tenant registry: N unmerged NeuroAda
  ``(indices, values)`` trees stacked (and cached) for the batched kernel
  path.

One frozen base model serves every tenant: each compiled step applies
each slot's ``(k, d_out)`` delta in-flight via ``ops.delta_apply_batched``
(jnp oracle or Pallas per-slot gather) instead of merging weights ahead
of time.

Prefill is **chunked and fused into the serving step** (DESIGN §11): the
scheduler carves each admitted prompt into ``prefill_chunk``-token
chunks under a per-step token budget, and while any slot owes prompt
chunks the engine runs ONE jitted mixed step — decode slots advance one
token while prefilling slots consume their next chunk, writing k/v
straight into their cache rows/paged blocks and sampling a first token
the step their prompt completes. No step runs longer than the budget
plus one decode token per slot, so a long prompt can no longer stall
every in-flight stream behind a stop-the-world prefill; and because the
mixed buffer has ONE compiled shape, the per-pow2-bucket prefill graphs
(and their splice subsystem) are gone.

Once no prompt chunks are owed, decode runs as a **megastep**: one
jitted ``lax.scan`` over up to ``decode_chunk`` tokens, carrying (kv
cache, last tokens, per-slot positions, active mask, max_new budget) as
device state with sampling, EOS detection, cache advance and per-slot
masking all in-graph. Every compiled step — mixed or megastep — costs
exactly ONE device→host transfer; finished slots become masked no-ops
until the chunk drains, and freed slots re-admit at step boundaries.
With ``decode_chunk=1`` the megastep reproduces the per-token loop
exactly (same tokens, same Request lifecycle), so chunking is a pure
throughput knob (see DESIGN §9).

With ``draft != "off"`` (DESIGN §12) the decode megastep runs
**speculative** rounds instead of single-token iterations: a cheap
drafter (quantized self-draft via :mod:`repro.serve.draft`, the merged
mean-of-tenants model, or the model-free ``ngram`` prompt lookup that
costs zero draft forwards) proposes ``spec_k`` tokens per slot — a
model drafter from its own dense KV scratch, ngram from the slot's
committed token history — the full model scores all k+1 positions as ONE
verify chunk through the §11 chunk forward, and rejection sampling
commits a verified prefix — exact greedy token-match on temp-0 slots, so
greedy outputs are token-identical to plain decode. Rollback is a pure
per-slot position rewind: step boundaries pre-reserve the
``decode_chunk × (spec_k + 1)`` horizon, so every row a rejected draft
wrote is already owned and simply gets overwritten. Still one jitted
call and ONE device→host transfer per megastep.

With ``paged=True`` (DESIGN §10) the dense slot cache becomes a shared
block pool: capacity is ``num_blocks × page_size`` tokens actually in
flight, not ``slots × max_len`` reservations. Admission is block-aware
(a request leaves the queue only when the pool covers its prompt, with
same-tenant page-aligned prefixes deduplicated against refcounted shared
blocks), step boundaries pre-reserve every position a compiled body can
write — preempting the *youngest* request back to the queue head on OOM
(mid-prefill victims included: they re-prefill over ``prompt + out``
later and continue identically) — and both the read and write block
tables ride the compiled steps as device state.

Observability (DESIGN §13) is host-side by construction: every counter,
gauge, histogram and trace span derives from state a compiled step
already hands back in its one device→host bundle (emitted tokens,
positions, survivor masks, acceptance counts) or from pure host
bookkeeping (queue depth, pool free-list, wall clocks). Instrumentation
therefore cannot change the ONE-transfer-per-megastep contract — the
transfer-counting tests run with metrics and tracing enabled — and it
adds no traced inputs, so the compiled graphs are byte-identical with
observability on or off (the compile-count regression test pins that).
``metrics=False`` swaps in the no-op registry; ``tracer=None`` (the
default) skips lifecycle tracing entirely. ``step`` also marks its
phases (expire, admit, reserve, plan, put, dispatch, fetch, emit,
gauges) with profiler spans (``repro.obs.spans``), recorded whenever a
profiler runs and about a microsecond each when none does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs.clock as _clock
from repro.core.delta import BatchedDelta
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
        if mesh is not None:
            # Megatron placement over the frozen (possibly packed) base:
            # col-parallel qkv/up, row-parallel o/down, vocab-sharded
            # embed/head; QuantizedTensor leaves fit the spec to their
            # packed data/scales children. fsdp=False — serving shards for
            # compute, never for optimizer-state capacity.
            from repro.distributed.sharding import param_shardings

            params = jax.device_put(
                params,
                param_shardings(params, mesh, model.cfg.family, fsdp=False),
            )
        self.model = model
        self.params = params
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
            self.draft_params = build_draft_params(
                self.params, draft, store=adapter_store, quant_block=quant_block
            )
            if mesh is not None:
                from repro.distributed.sharding import param_shardings

                self.draft_params = jax.device_put(
                    self.draft_params,
                    param_shardings(
                        self.draft_params, mesh, model.cfg.family, fsdp=False
                    ),
                )
            self.draft_kv = DraftKVCache(model, slots, max_len, mesh=mesh)
        else:
            # off, or the model-free ngram drafter: no params, no scratch —
            # ngram proposals come from the slot's own committed tokens
            self.draft_params = None
            self.draft_kv = None

        L = model.cfg.num_layers
        eos, mlen, chunk = eos_id, max_len, decode_chunk
        # the mixed step's dense work runs on this many flat token rows,
        # the most a chunk plan can fill, not on the (slots, C) buffer
        self.chunk_rows = rows = chunk_rows(slots, self.prefill_chunk)

        def batched_adapters(aidx, aval, aid):
            # blocks leaves ride the layer scan: their aid copy carries a
            # leading L axis so scan slices every xs leaf uniformly.
            aid_l = jnp.broadcast_to(aid[None, :], (L, aid.shape[0]))
            out = {}
            for key, sub_i in aidx.items():
                a = aid_l if key == "blocks" else aid
                out[key] = jax.tree.map(
                    lambda i, v, a=a: None if i is None else BatchedDelta(i, v, a),
                    sub_i, aval[key], is_leaf=lambda x: x is None,
                )
            return out

        def chunkstep(p, adapters, table, wtable, cache, tokens, q_offset,
                      q_len, last_idx, temps, key):
            """Compiled mixed prefill+decode step (DESIGN §11).

            One (slots, prefill_chunk) token buffer: prefilling slots
            carry their next prompt chunk, decode slots the degenerate
            one-token chunk, idle/stalled slots ``q_len = 0`` no-ops.
            K/v land in-graph (write table gates shared paged blocks),
            logits gather at each row's last real token, sampling is
            fused — the (slots,) token vector is the step's single host
            transfer. Positions advance to ``q_offset + q_len`` for
            every role (decode +1, prefill +take, idle frozen).
            """
            batch = {"tokens": tokens, "q_offset": q_offset,
                     "q_len": q_len, "last_idx": last_idx, "rows": rows}
            if table is not None:
                batch["block_table"] = table
                batch["write_table"] = wtable
            logits, cache = model.prefill_chunk(p, adapters, cache, batch)
            toks = self.sampler(logits, temps, key)
            return cache, q_offset + q_len, toks

        def chunkstep_plain(p, cache, *args):
            return chunkstep(p, None, None, None, cache, *args)

        def chunkstep_ad(p, aidx, aval, aid, cache, *args):
            adapters = batched_adapters(aidx, aval, aid)
            return chunkstep(p, adapters, None, None, cache, *args)

        def chunkstep_paged_plain(p, table, wtable, cache, *args):
            return chunkstep(p, None, table, wtable, cache, *args)

        def chunkstep_paged_ad(p, aidx, aval, aid, table, wtable, cache, *args):
            adapters = batched_adapters(aidx, aval, aid)
            return chunkstep(p, adapters, table, wtable, cache, *args)

        def megastep(p, adapters, table, cache, tok, pos, active, remaining,
                     temps, key):
            """Compiled decode loop over up to ``chunk`` tokens.

            Device-state carry: (cache, last tokens, per-slot pos, active
            mask, max_new budget). Finished/empty slots are masked no-ops:
            their token and position freeze, and their cache writes land on
            a stale row (dense) or their own already-reserved page (paged)
            that the overwrite-before-attend invariant makes unobservable —
            empty paged slots carry sentinel table rows, so their writes
            drop entirely. ``table`` (paged engines) is device state for
            the whole chunk: chunk boundaries pre-reserve every position
            the loop can write, so no allocation happens in-graph. Ys: the
            (chunk, slots) emitted-token matrix plus its emit mask — the
            step's single host transfer.
            """

            def body(carry, k_t):
                cache, tok, pos, active, remaining = carry
                batch = {"token": tok, "pos": pos}
                if table is not None:
                    batch["block_table"] = table
                logits, cache = model.decode_step(p, adapters, cache, batch)
                nxt = self.sampler(logits, temps, k_t)
                emitted = active
                tok = jnp.where(active, nxt, tok)
                pos = jnp.where(active, pos + 1, pos)
                remaining = jnp.where(active, remaining - 1, remaining)
                # mirror of the host Request lifecycle: EOS | max_new | cache
                # full — evaluated post-advance, exactly like _maybe_finish
                active = (
                    active & (tok != eos) & (remaining > 0) & (pos < mlen - 1)
                )
                return (cache, tok, pos, active, remaining), (tok, emitted)

            keys = jax.random.split(key, chunk)
            (cache, tok, pos, active, remaining), (toks, emits) = jax.lax.scan(
                body, (cache, tok, pos, active, remaining), keys
            )
            return cache, pos, active, toks, emits

        def megastep_plain(p, cache, tok, pos, active, remaining, temps, key):
            return megastep(
                p, None, None, cache, tok, pos, active, remaining, temps, key
            )

        def megastep_ad(
            p, aidx, aval, aid, cache, tok, pos, active, remaining, temps, key
        ):
            adapters = batched_adapters(aidx, aval, aid)
            return megastep(
                p, adapters, None, cache, tok, pos, active, remaining, temps, key
            )

        def megastep_paged_plain(
            p, table, cache, tok, pos, active, remaining, temps, key
        ):
            return megastep(
                p, None, table, cache, tok, pos, active, remaining, temps, key
            )

        def megastep_paged_ad(
            p, aidx, aval, aid, table, cache, tok, pos, active, remaining,
            temps, key,
        ):
            adapters = batched_adapters(aidx, aval, aid)
            return megastep(
                p, adapters, table, cache, tok, pos, active, remaining, temps,
                key,
            )

        K = spec_k

        def spec_chunkstep(p, dp, adapters, table, wtable, cache, dcache,
                           tokens, q_offset, q_len, last_idx, temps, key):
            """Mixed prefill+decode step with the drafter riding along.

            The drafter consumes the SAME (slots, C) token buffer into its
            own dense KV scratch — its logits are dead code XLA prunes, so
            drafting adds one cache-write pass to prefill, not a second
            head. Still one compiled call, one host transfer: by the time
            decode starts, the drafter's cache mirrors every verified
            position (prefix-share fast-forward is disabled under
            drafting for exactly this reason — see ``_try_place``).
            """
            batch = {"tokens": tokens, "q_offset": q_offset,
                     "q_len": q_len, "last_idx": last_idx, "rows": rows}
            if table is not None:
                batch["block_table"] = table
                batch["write_table"] = wtable
            logits, cache = model.prefill_chunk(p, adapters, cache, batch)
            dbatch = {"tokens": tokens, "q_offset": q_offset,
                      "q_len": q_len, "last_idx": last_idx, "rows": rows}
            _, dcache = model.prefill_chunk(dp, None, dcache, dbatch)
            toks = self.sampler(logits, temps, key)
            return cache, dcache, q_offset + q_len, toks

        def spec_chunkstep_plain(p, dp, cache, dcache, *args):
            return spec_chunkstep(p, dp, None, None, None, cache, dcache, *args)

        def spec_chunkstep_ad(p, dp, aidx, aval, aid, cache, dcache, *args):
            adapters = batched_adapters(aidx, aval, aid)
            return spec_chunkstep(
                p, dp, adapters, None, None, cache, dcache, *args
            )

        def spec_chunkstep_paged_plain(p, dp, table, wtable, cache, dcache,
                                       *args):
            return spec_chunkstep(
                p, dp, None, table, wtable, cache, dcache, *args
            )

        def spec_chunkstep_paged_ad(p, dp, aidx, aval, aid, table, wtable,
                                    cache, dcache, *args):
            adapters = batched_adapters(aidx, aval, aid)
            return spec_chunkstep(
                p, dp, adapters, table, wtable, cache, dcache, *args
            )

        def spec_verify_round(p, adapters, table, cache, tok, pos, active,
                              remaining, temps, d_t, q_t, k_acc, k_res):
            """Shared verify/accept/commit half of one speculative round
            (DESIGN §12), drafter-agnostic: takes the (S, K) proposals
            ``d_t`` and their drafter distributions ``q_t`` from whichever
            drafter produced them.

            ``q_t`` is the drafter's (S, K, V) distribution tensor, or
            ``None`` for a deterministic drafter (ngram): a deterministic
            proposal's distribution is the one-hot δ_d, so q(d) ≡ 1 and
            the gather is skipped — the accept rule degenerates to
            u < p(d) and the residual max(0, p − δ_d) to p with the d
            column zeroed.

            (1) The full model scores [tok, d_1..d_K] as ONE verify chunk —
            k/v for all K+1 positions land in pre-reserved rows/pages in
            the same pass; q_len clamps at the cache edge so no row writes
            past max_len (emission never reaches the clamped rows: the
            cache-full trigger fires first), and paged writes go through
            the READ table — verify rows are decode-region positions the
            slot owns, never shared prefix pages. (2) Standard rejection
            sampling accepts a prefix (u·q(d) < p(d), exact greedy
            token-match when temp = 0 via one-hot distributions), the
            first rejection resamples from max(0, p−q), a full accept
            draws the bonus from row K. (3) The host-lifecycle stop
            conditions (EOS | max_new | cache full) replay per emitted
            token, truncating the commit at the first trigger exactly
            where the per-token loop stops. Rollback is a per-slot ``pos``
            advance of n_emit ≤ K+1: the rejected suffix's rows sit beyond
            the new frontier in rows the slot already owns, unobservable
            until overwritten — no table edit, no allocation, no
            device→host traffic.
            """
            C = K + 1
            S = d_t.shape[0]
            ctokens = jnp.concatenate([tok[:, None], d_t], axis=1)
            q_len = jnp.where(active, jnp.minimum(C, mlen - pos), 0)
            vbatch = {"tokens": ctokens, "q_offset": pos, "q_len": q_len}
            if table is not None:
                vbatch["block_table"] = table
                vbatch["write_table"] = table
            vlogits, cache = model.verify_chunk(p, adapters, cache, vbatch)
            p_t = self.sampler.probs(
                vlogits.reshape(S * C, -1), jnp.repeat(temps, C)
            ).reshape(S, C, -1)  # target distribution at every position

            # rejection-sample an accepted prefix: a = |accepted|
            u = jax.random.uniform(k_acc, (S, K))
            p_d = jnp.take_along_axis(p_t[:, :K], d_t[..., None], -1)[..., 0]
            if q_t is None:
                acc = u < p_d  # q(d) ≡ 1 for a deterministic drafter
            else:
                q_d = jnp.take_along_axis(q_t, d_t[..., None], -1)[..., 0]
                acc = u * jnp.maximum(q_d, 1e-30) < p_d
            a = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)

            # ONE replacement draw per slot, from row a — only the first
            # rejected column's residual is ever consumed, and at a full
            # accept (a = K) row K *is* the bonus row, so a single (S, V)
            # categorical replaces the per-column (S, K, V) machinery. The
            # residual max(0, p−q) normalised (equal dists degenerate to
            # p); q one-hot means p with the d column zeroed.
            rows = jnp.arange(S)
            p_sel = p_t[rows, a]
            if q_t is None:
                # scatter 0 at the rejected proposal; a = K drops (no-op)
                d_rej = jnp.where(
                    a < K, d_t[rows, jnp.minimum(a, K - 1)], p_t.shape[-1]
                )
                res = p_sel.at[rows, d_rej].set(0.0, mode="drop")
            else:
                q_sel = jnp.where(
                    (a < K)[:, None], q_t[rows, jnp.minimum(a, K - 1)], 0.0
                )
                res = jnp.maximum(p_sel - q_sel, 0.0)
            res = jnp.where(
                jnp.sum(res, axis=-1, keepdims=True) > 0, res, p_sel
            )
            repl = jax.random.categorical(k_res, jnp.log(res)).astype(
                jnp.int32
            )

            # candidate stream: accepted drafts then the correction
            idxs = jnp.arange(C)[None, :]
            d_pad = jnp.concatenate(
                [d_t, jnp.zeros((S, 1), jnp.int32)], axis=1
            )
            cand = jnp.where(idxs < a[:, None], d_pad, repl[:, None])
            # stop triggers replayed per emitted token, post-advance — the
            # same EOS | max_new | cache-full order as the per-token body;
            # the triggering token IS emitted, then everything after it in
            # the round is rolled back too
            j1 = idxs + 1
            trig = (
                (cand == eos)
                | (remaining[:, None] - j1 <= 0)
                | (pos[:, None] + j1 >= mlen - 1)
            )
            can = (idxs <= a[:, None]) & active[:, None]
            hit = can & trig
            before = jnp.cumsum(hit.astype(jnp.int32), axis=1)
            emit = can & (before - hit.astype(jnp.int32) == 0)
            n_emit = jnp.sum(emit.astype(jnp.int32), axis=1)

            last = jnp.take_along_axis(
                cand, jnp.maximum(n_emit - 1, 0)[:, None], axis=1
            )[:, 0]
            tok = jnp.where(n_emit > 0, last, tok)
            pos = pos + n_emit  # the rollback: rejected rows sit beyond
            remaining = remaining - n_emit
            active = active & ~jnp.any(hit & emit, axis=1)
            return cache, tok, pos, active, remaining, (cand, emit, a)

        def spec_megastep(p, dp, adapters, table, cache, dcache, tok, pos,
                          active, remaining, temps, key):
            """Compiled speculative decode loop: ``chunk`` draft/verify
            rounds per call (DESIGN §12), model drafter.

            Each round, the drafter runs K+1 one-token steps from the
            verified frontier, proposing d_1..d_K and recording its
            sampling distribution per proposal (the K+1-th step only
            back-fills d_K's k/v so an all-accept round leaves no hole),
            then hands them to the shared verify/accept/commit half. Ys
            per round: (slots, K+1) candidate tokens + emit mask,
            acceptance counts, and the round-entry live mask — with the
            final positions and survivor mask, the megastep's single host
            transfer.
            """

            def round_body(carry, k_t):
                cache, dcache, tok, pos, active, remaining = carry
                live = active
                k_draft, k_acc, k_res = jax.random.split(k_t, 3)

                def draft_body(c, k_i):
                    dcache, dtok, dpos = c
                    dl, dcache = model.decode_step(
                        dp, None, dcache, {"token": dtok, "pos": dpos}
                    )
                    p_d = self.sampler.probs(dl, temps)
                    nxt = self.sampler(dl, temps, k_i)
                    return (dcache, nxt, dpos + 1), (nxt, p_d)

                (dcache, _, _), (drafts, p_draft) = jax.lax.scan(
                    draft_body, (dcache, tok, pos),
                    jax.random.split(k_draft, K + 1),
                )
                d_t = drafts[:K].T  # (S, K); the K+1-th is cache-fill only
                q_t = p_draft[:K].transpose(1, 0, 2)  # (S, K, V)
                cache, tok, pos, active, remaining, ys = spec_verify_round(
                    p, adapters, table, cache, tok, pos, active, remaining,
                    temps, d_t, q_t, k_acc, k_res,
                )
                return (
                    (cache, dcache, tok, pos, active, remaining),
                    (*ys, live),
                )

            keys = jax.random.split(key, chunk)
            (cache, dcache, tok, pos, active, remaining), ys = jax.lax.scan(
                round_body, (cache, dcache, tok, pos, active, remaining), keys
            )
            toks, emits, accs, lives = ys
            return cache, dcache, pos, active, toks, emits, accs, lives

        def ngram_megastep(p, adapters, table, cache, hist, tok, pos,
                           active, remaining, temps, key):
            """Compiled speculative decode loop, model-free ngram drafter
            (prompt lookup, DESIGN §12): drafting costs ZERO forwards, so
            a round is one batched verify pass for up to K+1 tokens.

            ``hist`` is the (slots, max_len) committed token sequence
            (prompt + emitted), aligned with ``pos``: hist[s, pos[s]] is
            the slot's current input token. Each round matches the most
            recent *earlier* occurrence j of the current token and
            proposes the continuation hist[j+1..] — wrapped with period
            pos − j where it runs past the frontier, so a period-p cycle
            (the attractor greedy decode settles into) extrapolates to a
            full K-token window instead of stalling at the p known
            followers. Deterministic proposal → the drafter distribution
            is a one-hot, so the accept rule degenerates to u < p(d) on
            sampled rows and exact token-match on greedy rows; the output
            distribution stays exactly the target's. Committed tokens
            append to hist in-graph, so later rounds in the same call
            match against them too. No match proposes token 0 — it simply
            gets rejected and the round still emits the verified
            correction.
            """
            idx_h = jnp.arange(mlen)

            def round_body(carry, k_t):
                cache, hist, tok, pos, active, remaining = carry
                live = active
                k_acc, k_res = jax.random.split(k_t)
                # most recent j < pos with hist[j] == current token; the
                # continuation hist[j+1 .. j+K] wraps with period pos − j
                # past the frontier: a period-p cycle's nearest match sits
                # only p back with p known followers, and the wrap
                # extrapolates the cycle to the full K-token window
                eq = (hist == tok[:, None]) & (idx_h[None, :] < pos[:, None])
                j = jnp.max(jnp.where(eq, idx_h[None, :], -1), axis=1)
                period = jnp.maximum(pos - j, 1)
                cols = j[:, None] + 1 + jnp.mod(
                    jnp.arange(K)[None, :], period[:, None]
                )
                d_t = jnp.where(
                    (j >= 0)[:, None],
                    jnp.take_along_axis(
                        hist, jnp.clip(cols, 0, mlen - 1), axis=1
                    ),
                    0,
                )
                pos0 = pos
                cache, tok, pos, active, remaining, ys = spec_verify_round(
                    p, adapters, table, cache, tok, pos, active, remaining,
                    temps, d_t, None, k_acc, k_res,
                )
                cand, emit, a = ys
                # append the committed tokens at pos0+1.. so later rounds
                # (and the next match) see them; non-emitted columns drop
                S = d_t.shape[0]
                wpos = jnp.where(
                    emit, pos0[:, None] + 1 + jnp.arange(K + 1)[None, :], mlen
                )
                hist = hist.at[jnp.arange(S)[:, None], wpos].set(
                    cand, mode="drop"
                )
                return (
                    (cache, hist, tok, pos, active, remaining),
                    (*ys, live),
                )

            keys = jax.random.split(key, chunk)
            (cache, hist, tok, pos, active, remaining), ys = jax.lax.scan(
                round_body, (cache, hist, tok, pos, active, remaining), keys
            )
            toks, emits, accs, lives = ys
            return cache, pos, active, toks, emits, accs, lives

        def spec_megastep_plain(p, dp, cache, dcache, *args):
            return spec_megastep(p, dp, None, None, cache, dcache, *args)

        def spec_megastep_ad(p, dp, aidx, aval, aid, cache, dcache, *args):
            adapters = batched_adapters(aidx, aval, aid)
            return spec_megastep(p, dp, adapters, None, cache, dcache, *args)

        def spec_megastep_paged_plain(p, dp, table, cache, dcache, *args):
            return spec_megastep(p, dp, None, table, cache, dcache, *args)

        def spec_megastep_paged_ad(p, dp, aidx, aval, aid, table, cache,
                                   dcache, *args):
            adapters = batched_adapters(aidx, aval, aid)
            return spec_megastep(p, dp, adapters, table, cache, dcache, *args)

        def ngram_megastep_plain(p, cache, hist, *args):
            return ngram_megastep(p, None, None, cache, hist, *args)

        def ngram_megastep_ad(p, aidx, aval, aid, cache, hist, *args):
            adapters = batched_adapters(aidx, aval, aid)
            return ngram_megastep(p, adapters, None, cache, hist, *args)

        def ngram_megastep_paged_plain(p, table, cache, hist, *args):
            return ngram_megastep(p, None, table, cache, hist, *args)

        def ngram_megastep_paged_ad(p, aidx, aval, aid, table, cache, hist,
                                    *args):
            adapters = batched_adapters(aidx, aval, aid)
            return ngram_megastep(p, adapters, table, cache, hist, *args)

        # every compiled step function registers here by name: the jit
        # caches are the source of truth for compile counting
        # (``compile_counts`` sums their entry counts — a cache that grows
        # after warmup is a recompile regression).
        self._jitted: dict[str, object] = {}

        def _jit(name, fn):
            j = jax.jit(fn)
            self._jitted[name] = j
            if mesh is None:
                return j

            # sharded engine: every compiled call runs inside a SCOPED
            # sharding context (serve mesh + TP activation layout),
            # snapshot/restored around the call — a tp=1 engine or a
            # trainer in the same process must never observe this state
            def call(*args, _j=j):
                return self._sharded_call(_j, *args)

            return call

        self._chunkstep_plain = _jit("chunkstep_plain", chunkstep_plain)
        self._chunkstep_ad = _jit("chunkstep_ad", chunkstep_ad)
        self._chunkstep_paged_plain = _jit(
            "chunkstep_paged_plain", chunkstep_paged_plain
        )
        self._chunkstep_paged_ad = _jit("chunkstep_paged_ad", chunkstep_paged_ad)
        self._megastep_plain = _jit("megastep_plain", megastep_plain)
        self._megastep_ad = _jit("megastep_ad", megastep_ad)
        self._megastep_paged_plain = _jit(
            "megastep_paged_plain", megastep_paged_plain
        )
        self._megastep_paged_ad = _jit("megastep_paged_ad", megastep_paged_ad)
        if draft == "ngram":
            # model-free drafter: no drafter cache to feed, so mixed
            # prefill+decode steps stay on the PLAIN chunkstep graphs —
            # only the decode megastep family is speculative
            self._ngram_megastep_plain = _jit(
                "ngram_megastep_plain", ngram_megastep_plain
            )
            self._ngram_megastep_ad = _jit("ngram_megastep_ad", ngram_megastep_ad)
            self._ngram_megastep_paged_plain = _jit(
                "ngram_megastep_paged_plain", ngram_megastep_paged_plain
            )
            self._ngram_megastep_paged_ad = _jit(
                "ngram_megastep_paged_ad", ngram_megastep_paged_ad
            )
        elif draft != "off":
            self._spec_chunkstep_plain = _jit(
                "spec_chunkstep_plain", spec_chunkstep_plain
            )
            self._spec_chunkstep_ad = _jit("spec_chunkstep_ad", spec_chunkstep_ad)
            self._spec_chunkstep_paged_plain = _jit(
                "spec_chunkstep_paged_plain", spec_chunkstep_paged_plain
            )
            self._spec_chunkstep_paged_ad = _jit(
                "spec_chunkstep_paged_ad", spec_chunkstep_paged_ad
            )
            self._spec_megastep_plain = _jit(
                "spec_megastep_plain", spec_megastep_plain
            )
            self._spec_megastep_ad = _jit("spec_megastep_ad", spec_megastep_ad)
            self._spec_megastep_paged_plain = _jit(
                "spec_megastep_paged_plain", spec_megastep_paged_plain
            )
            self._spec_megastep_paged_ad = _jit(
                "spec_megastep_paged_ad", spec_megastep_paged_ad
            )
        self._obs_init()

    # ------------------------------------------------- sharded dispatch

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
        """Per-step-function jit cache sizes. Every entry is one traced
        compilation; a steady-state engine compiles each live variant
        once, so totals must be flat across steps after warmup (the
        regression test drives mixed, decode and spec steps and asserts
        exactly that)."""
        out = {}
        for name, fn in self._jitted.items():
            size = getattr(fn, "_cache_size", None)
            out[name] = int(size()) if size is not None else 0
        return out

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
        spec = self.draft_kv is not None  # ngram prefills like plain
        with span(spans.SERVE_PUT):
            lead = [self.params]
            if spec:
                lead.append(self.draft_params)
            if stacked is not None:
                lead += [*stacked, jnp.asarray(plan["aid"])]
            if self.paged:
                lead += [self.kv.table_device(), self.kv.write_table_device()]
            inputs = [jnp.asarray(plan[k]) for k in
                      ("tokens", "q_offset", "q_len", "last_idx", "temps")]
        caches = [self.kv.data, self.draft_kv.data] if spec else [self.kv.data]
        fn = getattr(
            self,
            ("_spec_chunkstep" if spec else "_chunkstep")
            + ("_paged" if self.paged else "")
            + ("_ad" if stacked is not None else "_plain"),
        )
        with span(spans.SERVE_DISPATCH):
            out = fn(*lead, *caches, *inputs, key)
        if spec:
            self.kv.data, self.draft_kv.data, pos_dev, toks_dev = out
        else:
            self.kv.data, pos_dev, toks_dev = out
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
            args = (
                self.kv.data, jnp.asarray(st["tokens"]), self.kv.pos,
                jnp.asarray(st["active"]), jnp.asarray(st["remaining"]),
                jnp.asarray(st["temps"]), key,
            )
            if self.paged:
                args = (self.kv.table_device(),) + args
            if stacked is not None:
                args = (*stacked, jnp.asarray(st["aid"])) + args
        fn = getattr(
            self,
            "_megastep"
            + ("_paged" if self.paged else "")
            + ("_ad" if stacked is not None else "_plain"),
        )
        with span(spans.SERVE_DISPATCH):
            out = fn(self.params, *args)
        self.kv.data, pos_dev = out[0], out[1]
        # ONE device→host transfer for the whole chunk (all slots, all
        # steps): emitted tokens + mask, final positions, survivor mask.
        with span(spans.SERVE_FETCH):
            pos_np, active_np, toks, emits = jax.device_get(out[1:])
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
            lead = [self.params] if ngram else [self.params, self.draft_params]
            if stacked is not None:
                lead += [*stacked, jnp.asarray(st["aid"])]
            if self.paged:
                lead.append(self.kv.table_device())
            if ngram:
                caches = [self.kv.data, jnp.asarray(hist)]
            else:
                caches = [self.kv.data, self.draft_kv.data]
            inputs = (
                jnp.asarray(st["tokens"]), self.kv.pos,
                jnp.asarray(st["active"]), jnp.asarray(st["remaining"]),
                jnp.asarray(st["temps"]), key,
            )
        fn = getattr(
            self,
            ("_ngram_megastep" if ngram else "_spec_megastep")
            + ("_paged" if self.paged else "")
            + ("_ad" if stacked is not None else "_plain"),
        )
        with span(spans.SERVE_DISPATCH):
            out = fn(*lead, *caches, *inputs)
        if ngram:
            self.kv.data, pos_dev = out[0], out[1]
            fetched = out[1:]
        else:
            self.kv.data, self.draft_kv.data, pos_dev = out[0], out[1], out[2]
            fetched = out[2:]
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
