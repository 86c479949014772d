"""Serving micro-bench: decode throughput vs slots × tenants × chunk × cache,
plus tail-latency under mixed prefill+decode load.

Compares merged serving (Alg. 1 phase 3 — the zero-overhead single-tenant
path) against unmerged multi-tenant serving (per-slot batched delta apply)
on the reduced dense arch, the per-token decode loop (``decode_chunk=1``)
against the fused decode megastep, the dense slot cache against the paged
block pool, on fp32 and int8 bases. Times are CPU wall — the structural
claims (one jitted call and one device→host transfer per *chunk*; paged
capacity bounded by tokens in flight, not slots × max_len) hold on any
backend.

The mixed-workload section measures what chunked prefill (DESIGN §11) is
for: one long-prompt tenant arriving mid-decode of eight short streams.
Per-token timestamps give TTFT for the long request and inter-token
latency (ITL) p50/p95 for the short streams, chunked
(``prefill_chunk=8``) against stop-the-world (``prefill_chunk=max_len``:
the whole prompt in one step, every decode stream stalled behind it —
the head-of-line behaviour the bucketed prefill had).

The paged capacity section *asserts* the structural wins: with mixed-length
prompts the paged engine holds concurrently a workload whose dense
reservation (requests × max_len) overflows the dense pool several times
over, and K same-prefix same-tenant requests keep more logical tokens in
flight than the pool physically stores (one refcounted prefix copy).

The speculative section (DESIGN §12) benches the in-megastep drafters
against their plain ``--draft off`` twins on longer decode windows,
recording acceptance rate, drafted-vs-emitted counts and the
spec-vs-plain tok/s ratio per configuration. The model drafters (merged
and int8 self-draft) run on the standard window and document the
backend economics — on this op-overhead-bound CPU oracle a same-size
drafter pays ~k forwards to save k, so they land under 1x; the
model-free ngram drafter (zero draft forwards) runs on a 4x window
timed deep into generation, where greedy decode has settled into its
attractor and lookup proposals land.

Besides the ``name,us_per_call,derived`` CSV schema of benchmarks.run, the
full grid lands in ``BENCH_serving.json`` (tok/s per configuration, the
megastep-vs-per-token and paged-vs-dense ratios, the spec-decode columns,
and the chunked-vs-stop-the-world latency columns) so the perf trajectory
is machine-readable.
"""

from __future__ import annotations

import json
import os
import pathlib
import random
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np

from benchmarks.common import bench_model
from repro.core.adapt import init_adapters, merge_adapters
from repro.obs import percentile
from repro.serve import AdapterStore, QueueFullError, ServeEngine

MAX_LEN = 128
JSON_PATH = pathlib.Path("BENCH_serving.json")


def _adapter(params, seed, k=2, scale=0.05):
    idx, val = init_adapters(params, k, rng=jax.random.PRNGKey(seed))
    val = jax.tree.map(
        lambda i, v: None
        if v is None
        else scale
        * jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(seed), v.size), v.shape
        ),
        idx,
        val,
        is_leaf=lambda x: x is None,
    )
    return idx, val


def _run_engine(m, params, *, slots, store, n_tenants, chunk, steps,
                base_dtype="fp32", paged=False, max_len=MAX_LEN,
                draft="off", spec_k=4, windows=3, warm_out=0,
                kv_dtype="fp32"):
    # eos outside the vocab: a greedy sample hitting the default eos_id
    # mid-window would idle its slot for the rest of the timed window
    eng = ServeEngine(
        m, params, slots=slots, max_len=max_len, adapter_store=store,
        decode_chunk=chunk, base_dtype=base_dtype, eos_id=1 << 20,
        paged=paged, draft=draft, spec_k=spec_k, kv_dtype=kv_dtype,
    )
    for i in range(slots):
        aid = 1 + i % n_tenants if n_tenants else 0
        eng.submit([1, 3 + i, 7, 2 + i], max_new=max_len - 8, adapter_id=aid)
    # count tokens over a stable Request snapshot: in_flight() drops
    # completed requests, which would corrupt the count for long windows
    reqs = eng.scheduler.in_flight()
    eng.step()  # admission + chunked prefill (compiles the mixed step)
    while eng.scheduler.has_prefilling():
        eng.step()
    eng.step()  # first decode megastep: compile it outside the timed window
    # ``warm_out`` > 0 decodes until the deepest slot has emitted that
    # many tokens before timing: the ngram legs measure the steady-state
    # regime where generation has settled into its attractor (the regime
    # lookup drafting exists for) instead of the chaotic opening tokens
    while warm_out and max(len(r.out) for r in reqs) < warm_out:
        eng.step()
    # equal decode budget per config: ``steps`` per-token steps' worth
    n_calls = max(steps // chunk, 1)
    # best of ``windows`` timed windows: a single scheduler hiccup or GC
    # pause on a shared box lands in ONE window and is discarded instead
    # of inflating a 3-call average 5x (the PR-5 bench shipped a 22ms
    # outlier row this way); min-wall is the structural cost
    best = fallback = None
    for _ in range(windows):
        tok0 = sum(len(r.out) for r in reqs)
        t0 = time.perf_counter()
        for _ in range(n_calls):
            eng.step()
        wall = time.perf_counter() - t0
        toks = sum(len(r.out) for r in reqs) - tok0
        if not toks:
            continue
        fallback = fallback or (wall, toks)
        # a window in which a slot completed times a partially idle
        # engine (the scan still runs every round for the emptier batch):
        # prefer all-slots-live windows, fall back if none survived
        if sum(r is not None for r in eng.scheduler.active) < slots:
            continue
        if best is None or wall < best[0]:
            best = (wall, toks)
    wall, toks = best or fallback
    res = {
        "us_per_call": wall / n_calls * 1e6,
        "tok_s": toks / wall,
        "tokens": toks,
    }
    if draft != "off":
        # one source of truth: the registry series behind the engine's
        # spec_* properties (DESIGN §13) — what --metrics-out exports is
        # exactly what this bench records
        v = eng.metrics.value
        drafted = int(v("serve_spec_drafted_total"))
        accepted = int(v("serve_spec_accepted_total"))
        res.update(
            drafted=drafted, accepted=accepted,
            emitted=int(v("serve_spec_emitted_total")),
            acceptance=round(accepted / max(drafted, 1), 3),
        )
    return res


def run(*, steps: int = 24) -> list[str]:
    out = []
    records = []
    cfg, m, params = bench_model("qwen2-1.5b")
    adapters = [_adapter(params, seed) for seed in (1, 2, 3, 4)]
    merged = merge_adapters(params, *adapters[0])

    def bench(slots, chunk, *, mode, n_tenants=0, base="fp32", paged=False):
        if mode == "merged":
            p, store = merged, None
        else:
            p = params
            store = AdapterStore()
            for ad in adapters[:n_tenants]:
                store.register(*ad)
        r = _run_engine(
            m, p, slots=slots, store=store, n_tenants=n_tenants,
            chunk=chunk, steps=steps, base_dtype=base, paged=paged,
        )
        cache = "paged" if paged else "dense"
        rec = {"slots": slots, "chunk": chunk, "mode": mode,
               "tenants": n_tenants, "base": base, "cache": cache, **r}
        records.append(rec)
        out.append(
            f"serve.decode.slots{slots}.chunk{chunk}.{mode}{n_tenants or ''}"
            f"{'.int8' if base != 'fp32' else ''}"
            f"{'.paged' if paged else ''},{r['us_per_call']:.0f},"
            f"tok_s={r['tok_s']:.1f}"
        )
        return rec

    for slots in (1, 4, 8):
        for chunk in (1, 8):
            bench(slots, chunk, mode="merged")
            for n_tenants in (1, 4):
                bench(slots, chunk, mode="unmerged", n_tenants=n_tenants)
    for chunk in (1, 8):  # quantized frozen base, multi-tenant
        bench(4, chunk, mode="unmerged", n_tenants=2, base="int8")
    # paged twins of the dense columns (same workload, block-pool cache)
    for slots in (1, 4, 8):
        bench(slots, 8, mode="merged", paged=True)
        bench(slots, 8, mode="unmerged", n_tenants=4, paged=True)
    bench(4, 8, mode="unmerged", n_tenants=2, base="int8", paged=True)

    # megastep win over the per-token loop, per (slots, mode, base) config
    ratios = []
    by_key = {}
    for r in records:
        if r["cache"] != "dense":
            continue
        by_key.setdefault(
            (r["slots"], r["mode"], r["tenants"], r["base"]), {}
        )[r["chunk"]] = r
    for (slots, mode, tenants, base), chunks in sorted(by_key.items()):
        if 1 not in chunks or 8 not in chunks:
            continue
        ratio = chunks[8]["tok_s"] / chunks[1]["tok_s"]
        ratios.append({"slots": slots, "mode": mode, "tenants": tenants,
                       "base": base, "chunk8_vs_chunk1_tok_s": round(ratio, 3)})
        out.append(
            f"serve.decode.slots{slots}.{mode}{tenants or ''}"
            f"{'.int8' if base != 'fp32' else ''}.speedup,0,"
            f"chunk8_vs_chunk1={ratio:.2f}x"
        )

    # paged vs dense, same (slots, mode, tenants, base, chunk) column
    paged_ratios = []
    by_cache = {}
    for r in records:
        key = (r["slots"], r["chunk"], r["mode"], r["tenants"], r["base"])
        by_cache.setdefault(key, {})[r["cache"]] = r
    for key, caches in sorted(by_cache.items()):
        if "dense" not in caches or "paged" not in caches:
            continue
        slots, chunk, mode, tenants, base = key
        ratio = caches["paged"]["tok_s"] / caches["dense"]["tok_s"]
        paged_ratios.append({
            "slots": slots, "chunk": chunk, "mode": mode, "tenants": tenants,
            "base": base, "paged_vs_dense_tok_s": round(ratio, 3),
        })
        out.append(
            f"serve.decode.slots{slots}.{mode}{tenants or ''}"
            f"{'.int8' if base != 'fp32' else ''}.paged_ratio,0,"
            f"paged_vs_dense={ratio:.2f}x"
        )

    # speculative decoding: drafter proposes k per round, full model
    # verifies k+1 per slot in one chunk pass (DESIGN §12). Each spec
    # megastep call emits up to chunk*(k+1) tokens per slot, so the legs
    # run on longer windows than the main grid; every spec row carries
    # acceptance + drafted/emitted counts and its tok/s ratio against the
    # plain (--draft off) twin at the same slots/cache/tenants/window.
    spec_records = []
    spec_len = 2 * MAX_LEN

    def spec_store(n_tenants):
        s = AdapterStore()
        for ad in adapters[:n_tenants]:
            s.register(*ad)
        return s

    def spec_bench(slots, n_tenants, *, draft, paged=False, spec_k=4,
                   max_len=spec_len, warm_out=0):
        cache = "paged" if paged else "dense"
        key = (slots, n_tenants, cache, max_len, warm_out)
        store = spec_store(n_tenants) if n_tenants else None
        if key not in plain_twins:
            plain_twins[key] = _run_engine(
                m, params, slots=slots, store=store,
                n_tenants=n_tenants, chunk=8, steps=steps, paged=paged,
                max_len=max_len, warm_out=warm_out,
            )
        base_r = plain_twins[key]
        # 2 calls x 2 windows: a spec call can emit 8*(k+1) tokens per
        # slot, so longer windows would exhaust the max_new budget
        r = _run_engine(
            m, params, slots=slots, store=store,
            n_tenants=n_tenants, chunk=8, steps=16, paged=paged,
            max_len=max_len, draft=draft, spec_k=spec_k, windows=2,
            warm_out=warm_out,
        )
        ratio = r["tok_s"] / base_r["tok_s"]
        rec = {"slots": slots, "tenants": n_tenants, "cache": cache,
               "draft": draft, "spec_k": spec_k, "max_len": max_len,
               "warm_out": warm_out,
               "plain_tok_s": round(base_r["tok_s"], 1),
               "spec_vs_plain_tok_s": round(ratio, 3), **r}
        spec_records.append(rec)
        out.append(
            f"serve.spec.slots{slots}.{draft}{n_tenants}.{cache},"
            f"{r['us_per_call']:.0f},tok_s={r['tok_s']:.1f}"
            f"_accept={r['acceptance']:.2f}"
            f"_drafted={r['drafted']}_emitted={r['emitted']}"
            f"_vs_plain={ratio:.2f}x"
        )
        return rec

    plain_twins = {}
    for paged in (False, True):
        for slots in (4, 8):
            spec_bench(slots, 1, draft="merged", paged=paged)
    # acceptance comparison: quantized self-draft (int8 drafts, fp32
    # verifies) and a cross-tenant merged drafter (mean of 4 deltas
    # drafting for per-tenant targets)
    spec_bench(4, 1, draft="int8")
    spec_bench(4, 4, draft="merged")
    # model-free ngram drafter (zero draft forwards — the drafter that
    # wins on this op-overhead-bound backend, where a same-size model
    # drafter pays k forwards to save k): measured deep into generation
    # (warm_out) where decode has settled into its attractor and lookup
    # proposals actually land, on a 4x window so the deep regime exists
    for paged in (False, True):
        for slots_ in (4, 8):
            spec_bench(slots_, 0, draft="ngram", paged=paged,
                       max_len=4 * MAX_LEN, warm_out=220)

    # chunked admission: cost of admitting a mixed-length batch through
    # the one-shape mixed step (no per-bucket compiles)
    eng = ServeEngine(m, params, slots=4, max_len=MAX_LEN)
    for plen in (3, 9, 17, 30):
        eng.submit(list(np.arange(1, plen + 1)), max_new=2)
    t0 = time.perf_counter()
    eng.run_to_completion()
    out.append(f"serve.prefill.chunked_admit4,{(time.perf_counter() - t0) * 1e6:.0f},")

    mixed = _mixed_workload(m, params, out)
    capacity = _capacity_demo(m, params, out)
    quant_kv = _quant_kv_section(out, steps=steps)
    lifecycle = _lifecycle_section(m, params, out)
    sharded = _sharded_section(out)

    JSON_PATH.write_text(json.dumps(
        {"arch": cfg.name, "max_len": MAX_LEN, "decode_steps_budget": steps,
         "results": records, "speedups": ratios,
         "paged_vs_dense": paged_ratios, "speculative": spec_records,
         "mixed_workload": mixed, "capacity": capacity,
         "quant_kv": quant_kv,
         "lifecycle": lifecycle,
         "sharded": sharded},
        indent=2,
    ))
    out.append(f"serve.json_written,0,{JSON_PATH}")
    return out


def _latency_run(m, params, *, prefill_chunk, long_len=112, short_new=18,
                 n_short=8):
    """One long-prompt tenant arriving mid-decode of ``n_short`` short
    streams; per-token wall-clock timestamps for TTFT/ITL percentiles.

    ``prefill_chunk=MAX_LEN`` reproduces stop-the-world head-of-line
    behaviour (the whole prompt in one step, every stream stalled for the
    step's duration); small chunks bound the per-step latency at
    budget + one decode token per stream.
    """
    eng = ServeEngine(m, params, slots=n_short + 1, max_len=MAX_LEN,
                      eos_id=1 << 20, decode_chunk=1, paged=True,
                      prefill_chunk=prefill_chunk)
    shorts = [eng.submit([1, 3 + i, 7], max_new=short_new)
              for i in range(n_short)]
    # warm up: admit + prefill the short streams, compile both graphs
    eng.step()
    while eng.scheduler.has_prefilling():
        eng.step()
    eng.step()
    reqs = {r.rid: r for r in eng.scheduler.in_flight()}
    long_rid = eng.submit(list(np.arange(1, long_len + 1)), max_new=2)
    reqs[long_rid] = next(
        r for r in eng.scheduler.in_flight() if r.rid == long_rid
    )
    counts = {rid: len(r.out) for rid, r in reqs.items()}
    t_submit = time.perf_counter()
    # seed each short stream with a baseline stamp: the first gap after
    # the long prompt lands must include the admission step's stall
    stamps: dict[int, list[float]] = {
        rid: ([t_submit] if rid in shorts else []) for rid in reqs
    }
    t0 = t_submit
    while eng.step():
        now = time.perf_counter()
        for rid, r in reqs.items():
            for _ in range(len(r.out) - counts[rid]):
                stamps[rid].append(now)
            counts[rid] = len(r.out)
    wall = time.perf_counter() - t0
    gaps = []
    for rid in shorts:
        ts = stamps[rid]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    gaps.sort()
    # the n_short seeded baseline stamps are not tokens
    toks = sum(len(ts) for ts in stamps.values()) - n_short
    pick = lambda q: percentile(gaps, q) * 1e3  # shared obs rank math
    return {
        "prefill_chunk": prefill_chunk,
        "long_len": long_len,
        "ttft_long_ms": (stamps[long_rid][0] - t_submit) * 1e3,
        "itl_p50_ms": pick(0.50),
        "itl_p95_ms": pick(0.95),
        "itl_max_ms": gaps[-1] * 1e3,
        "tok_s": toks / wall,
        "tokens": toks,
        "wall": wall,
        "gaps": len(gaps),
    }


def _mixed_workload(m, params, out):
    """Chunked vs stop-the-world prefill under the head-of-line workload
    the chunking exists for; emits both columns plus the p95 ITL
    improvement and the tok/s ratio (should be ≈1: chunking does no extra
    work — it splits the same prefill across bounded steps, and the
    decode tokens it overlaps reduce the pure-decode tail one for one).
    The modes run five times each, INTERLEAVED (stw, chunked, stw, …) so
    box-load drift hits both equally; latency stats come from each mode's
    lowest-p95 pass (a single load spike otherwise masquerades as the
    structural stall) and throughput pools tokens/wall across all five
    passes — CPU-wall noise otherwise swamps the gap in either stat."""
    runs = {"stw": [], "chunked": []}
    for _ in range(5):
        runs["stw"].append(_latency_run(m, params, prefill_chunk=MAX_LEN))
        runs["chunked"].append(_latency_run(m, params, prefill_chunk=8))

    def best(rs):
        r = dict(min(rs, key=lambda r: r["itl_p95_ms"]))
        r["tok_s"] = sum(x["tokens"] for x in rs) / sum(x["wall"] for x in rs)
        return r

    stw = best(runs["stw"])
    chunked = best(runs["chunked"])
    improvement = stw["itl_p95_ms"] / chunked["itl_p95_ms"]
    tok_ratio = chunked["tok_s"] / stw["tok_s"]
    for name, r in (("stop_the_world", stw), ("chunked8", chunked)):
        out.append(
            f"serve.mixed.{name},{r['itl_p95_ms'] * 1e3:.0f},"
            f"itl_p50={r['itl_p50_ms']:.2f}ms_p95={r['itl_p95_ms']:.2f}ms"
            f"_ttft={r['ttft_long_ms']:.1f}ms_tok_s={r['tok_s']:.1f}"
        )
    out.append(
        f"serve.mixed.p95_improvement,0,"
        f"chunked_vs_stw={improvement:.2f}x_tok_s_ratio={tok_ratio:.3f}"
    )
    return {
        "stop_the_world": stw, "chunked": chunked,
        "p95_itl_improvement": round(improvement, 3),
        "tok_s_ratio": round(tok_ratio, 3),
    }


def _capacity_demo(m, params, out):
    """The paged structural wins, asserted via pool accounting.

    Concurrency: 12 mixed-length requests run simultaneously on a pool
    holding the token budget dense reserves for 4 slots — the workload's
    dense reservation (12 × max_len) is 3× the pool. Prefix sharing: 8
    same-tenant requests over a 64-token system prompt keep more logical
    tokens in flight than the pool physically stores.
    """
    page, num_blocks = 16, 4 * MAX_LEN // 16  # dense 4-slot token budget
    eng = ServeEngine(m, params, slots=12, max_len=MAX_LEN, eos_id=1 << 20,
                      decode_chunk=8, paged=True, page_size=page,
                      num_blocks=num_blocks)
    lens = [4, 8, 12, 16, 20, 24, 28, 32, 8, 12, 16, 20]
    for i, plen in enumerate(lens):
        eng.submit(list(np.arange(1, plen + 1) + i), max_new=16)
    eng.step()
    n_active = sum(r is not None for r in eng.scheduler.active)
    dense_reservation = n_active * MAX_LEN
    pool_tokens = num_blocks * page
    assert n_active == 12, f"paged admission held {n_active}/12"
    assert dense_reservation > 2 * pool_tokens
    used_mid = int(eng.kv.used_blocks)
    eng.run_to_completion()
    assert eng.kv.free_blocks == eng.kv.num_blocks
    out.append(
        f"serve.paged.capacity,0,concurrent=12of12"
        f"_densewould={dense_reservation}tok_pool={pool_tokens}tok"
    )

    # prefix sharing: one refcounted copy of a 64-token system prompt
    prefix = list(np.arange(1, 65))
    eng = ServeEngine(m, params, slots=8, max_len=MAX_LEN, eos_id=1 << 20,
                      decode_chunk=8, paged=True, page_size=page,
                      num_blocks=num_blocks)
    for i in range(8):
        eng.submit(prefix + [100 + i], max_new=16)
    # step 1: the prefix *writer* admits alone and lands its pages; step 2:
    # the 7 sharers admit against the now-written prefix and skip it
    eng.step()
    eng.step()
    logical = sum(int(p) for p in eng.kv.pos_host) + 8  # +1 pending tok each
    physical = int(eng.kv.used_blocks) * page
    shared = eng.kv.refcount[eng.kv.refcount > 1]
    assert len(shared) == len(prefix) // page and (shared == 8).all()
    assert logical > pool_tokens, (logical, pool_tokens)
    assert physical < logical
    eng.run_to_completion()
    assert eng.kv.free_blocks == eng.kv.num_blocks
    out.append(
        f"serve.paged.prefix_share,0,8x{len(prefix)}tok_prefix"
        f"_logical={logical}tok_physical={physical}tok"
    )
    return {
        "page_size": page, "num_blocks": num_blocks,
        "pool_tokens": pool_tokens,
        "mixed_len_concurrent": 12,
        "dense_reservation_equiv": dense_reservation,
        "mixed_len_used_blocks_mid": used_mid,
        "prefix_requests": 8, "prefix_tokens": len(prefix),
        "prefix_logical_tokens": logical,
        "prefix_physical_tokens": physical,
    }


def _quant_kv_section(out, *, steps):
    """int8 KV cache (DESIGN §15): capacity, throughput, composed memory.

    Runs on a float32-dtype twin of the bench model so the ``fp32``
    kv_dtype genuinely stores 4-byte values — the honest baseline for
    the packed-bytes claims (the main grid's bf16 cache would halve the
    headline for a reason that has nothing to do with quantization).

    The capacity leg *asserts* the structural win: on the same pool-byte
    budget the int8 engine admits >= 2x the concurrently active requests
    and holds >= 2x the tokens-in-flight capacity per pool byte. Both
    engines' pool bytes are cross-checked against the labeled
    ``serve_pool_bytes`` gauge so this JSON, the smoke script, and the
    metrics exposition all read one number. The drift columns record
    greedy agreement between the twins (the hard logit-drift bounds live
    in tests/serve/test_quant_kv.py); the throughput and composed legs
    document the tok/s cost of dequant-on-read and the full int8-base +
    int8-KV serving footprint, extending the quantized-base memory table.
    """
    from repro.quant import tree_bytes

    cfg_q, m_q, params_q = bench_model("qwen2-1.5b", dtype="float32")
    page = 16

    def pool_bytes_for(num_blocks, kv_dtype):
        tree = jax.eval_shape(
            lambda: m_q.init_paged_cache(num_blocks, page, kv_dtype=kv_dtype)
        )
        return sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree.leaves(tree)
        )

    # ---- asserted capacity: same pool-byte budget, 2x+ the requests ----
    nb_fp = 16  # 256-token fp32 pool
    budget = pool_bytes_for(nb_fp, "fp32")
    nb_i8 = budget // (pool_bytes_for(nb_fp, "int8") // nb_fp)
    assert pool_bytes_for(nb_i8, "int8") <= budget
    assert nb_i8 >= 2 * nb_fp, (
        f"int8 pool holds {nb_i8} blocks on the fp32 {nb_fp}-block byte "
        "budget; expected >= 2x tokens-in-flight per pool byte"
    )

    def admit_run(kv_dtype, num_blocks):
        eng = ServeEngine(
            m_q, params_q, slots=24, max_len=MAX_LEN, eos_id=1 << 20,
            decode_chunk=8, paged=True, page_size=page,
            num_blocks=num_blocks, kv_dtype=kv_dtype,
        )
        assert eng.kv.pool_bytes() == pool_bytes_for(num_blocks, kv_dtype)
        # one source of truth: the labeled gauge reads the same number
        assert eng.metrics.value("serve_pool_bytes", kv_dtype) == (
            eng.kv.pool_bytes()
        )
        for i in range(24):
            eng.submit(list(np.arange(1, 33) + i), max_new=8)
        eng.step()
        active = sum(r is not None for r in eng.scheduler.active)
        reqs = eng.scheduler.in_flight()
        eng.run_to_completion()
        assert eng.kv.free_blocks == eng.kv.num_blocks
        return eng, active, [r.out for r in reqs]

    eng_fp, active_fp, outs_fp = admit_run("fp32", nb_fp)
    eng_i8, active_i8, outs_i8 = admit_run("int8", nb_i8)
    assert active_i8 >= 2 * active_fp, (
        f"int8 admitted {active_i8} vs fp32 {active_fp} on the same "
        "pool-byte budget; expected >= 2x concurrent requests"
    )
    exact = sum(a == b for a, b in zip(outs_fp, outs_i8))
    agree = [
        sum(1 for x, y in zip(a, b) if x == y) / max(len(a), 1)
        for a, b in zip(outs_fp, outs_i8)
    ]
    out.append(
        f"serve.quant_kv.capacity,0,blocks={nb_i8}vs{nb_fp}"
        f"_budget={budget}B_active={active_i8}vs{active_fp}"
        f"_exact_outputs={exact}of{len(outs_fp)}"
    )

    # ---- throughput: int8-KV twin of the slots=4/chunk=8 paged column --
    r_fp = _run_engine(m_q, params_q, slots=4, store=None, n_tenants=0,
                       chunk=8, steps=steps, paged=True)
    r_i8 = _run_engine(m_q, params_q, slots=4, store=None, n_tenants=0,
                       chunk=8, steps=steps, paged=True, kv_dtype="int8")
    tok_ratio = r_i8["tok_s"] / r_fp["tok_s"]
    out.append(
        f"serve.quant_kv.decode,{r_i8['us_per_call']:.0f},"
        f"tok_s={r_i8['tok_s']:.1f}_vs_fp32={tok_ratio:.2f}x"
    )

    # ---- composed: int8 base + int8 KV, the full packed footprint ------
    r_both = _run_engine(m_q, params_q, slots=4, store=None, n_tenants=0,
                         chunk=8, steps=steps, paged=True,
                         base_dtype="int8", kv_dtype="int8")
    from repro.peft import quantize_base

    params_bytes = tree_bytes(params_q)
    params_bytes_i8 = tree_bytes(quantize_base(params_q, "int8", block=64))
    out.append(
        f"serve.quant_kv.composed_int8,{r_both['us_per_call']:.0f},"
        f"tok_s={r_both['tok_s']:.1f}"
        f"_params={params_bytes_i8}B_pool={eng_i8.kv.pool_bytes()}B"
    )
    return {
        "page_size": page, "max_len": MAX_LEN,
        "capacity": {
            "pool_byte_budget": budget,
            "blocks_fp32": nb_fp, "blocks_int8": int(nb_i8),
            "pool_bytes_fp32": eng_fp.kv.pool_bytes(),
            "pool_bytes_int8": eng_i8.kv.pool_bytes(),
            "tokens_in_flight_fp32": nb_fp * page,
            "tokens_in_flight_int8": int(nb_i8) * page,
            "active_fp32": active_fp, "active_int8": active_i8,
            "claim": ">=2x concurrent requests and tokens-in-flight per "
                     "pool byte on the same budget (asserted)",
        },
        "drift": {
            "requests": len(outs_fp),
            "exact_output_matches": exact,
            "mean_token_agreement": round(float(np.mean(agree)), 3),
            "note": "greedy agreement fp32-vs-int8 twins; logit-drift "
                    "bounds pinned in tests/serve/test_quant_kv.py",
        },
        "decode": {
            "fp32": {k: round(v, 1) for k, v in r_fp.items()},
            "int8_kv": {k: round(v, 1) for k, v in r_i8.items()},
            "int8_vs_fp32_tok_s": round(tok_ratio, 3),
        },
        "composed_int8_base_int8_kv": {
            **{k: round(v, 1) for k, v in r_both.items()},
            "params_bytes_fp32": params_bytes,
            "params_bytes_int8": params_bytes_i8,
            "pool_bytes_int8": eng_i8.kv.pool_bytes(),
            "pool_bytes_fp32_equiv": eng_fp.kv.pool_bytes(),
        },
    }


_SHARDED_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, json, time
    from repro.configs import get_config, reduced
    from repro.launch.mesh import make_serve_mesh
    from repro.models import get_model
    from repro.serve import ServeEngine

    # compute-heavier than the oracle-reduced dims: at d_model=64 every
    # op is launch overhead and the collectives' fixed cost swamps the
    # split compute (~0.35x); at 256/1024 the matmuls amortize it and the
    # second host device genuinely parallelizes (>1x on 2 forced devices)
    cfg = reduced(get_config("qwen2-1.5b")).replace(
        dtype="float32", d_model=256, num_heads=8, num_kv_heads=4,
        d_ff=1024, vocab_size=4096,
    )
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))

    def run_one(tp):
        mesh = make_serve_mesh(tp) if tp > 1 else None
        eng = ServeEngine(m, params, slots=4, max_len=128, decode_chunk=8,
                          paged=True, eos_id=1 << 20, mesh=mesh)
        for i in range(4):
            eng.submit([1, 3 + i, 7, 2 + i], max_new=120)
        reqs = eng.scheduler.in_flight()
        eng.step()
        while eng.scheduler.has_prefilling():
            eng.step()
        eng.step()  # compile the decode megastep outside the window
        best = None
        for _ in range(3):
            tok0 = sum(len(r.out) for r in reqs)
            t0 = time.perf_counter()
            for _ in range(3):
                eng.step()
            wall = time.perf_counter() - t0
            toks = sum(len(r.out) for r in reqs) - tok0
            if toks and (best is None or wall < best[0]):
                best = (wall, toks)
        wall, toks = best
        return {
            "tok_s": round(toks / wall, 1),
            "pool_bytes": eng.kv.pool_bytes(),
            "pool_bytes_per_shard": eng.kv.pool_bytes_per_shard(),
            "tp": int(eng.metrics.value("serve_tp_size")),
            "transfers": eng.transfers,
        }

    res = {"tp1": run_one(1), "tp2": run_one(2)}
    res["tok_s_ratio_tp2_vs_tp1"] = round(
        res["tp2"]["tok_s"] / res["tp1"]["tok_s"], 3
    )
    print("RESULT:" + json.dumps(res))
    """
)


def _lifecycle_section(m, params, out):
    """Request-lifecycle robustness columns (DESIGN §16): what the
    production front end's admission machinery costs and delivers.

    Open-loop Poisson arrivals (seeded ``random.Random`` in *step* time —
    each engine step advances virtual time by one unit, so arrivals never
    wait on service and the offered trace replays exactly) are pushed at
    a bounded-queue engine slightly past its service rate. Half the
    offered requests carry a tight deadline calibrated from a measured
    solo run, half a generous one. Recorded:

    * **shed rate** — fraction of offered load refused at the door
      (bounded queue 503s plus deadline-unreachable refusals, keyed by
      which), the backpressure story in one number;
    * **goodput under deadline** — of everything offered, the fraction
      that reached a natural terminal state (``max_new``) vs evicted at
      a boundary sweep (``deadline``): admitting work that cannot finish
      is the failure mode this column watches;
    * **cancel-reclaim latency** — host wall time for ``cancel(rid)`` on
      a mid-decode request, which synchronously frees the slot and its
      pages (p50/p95 over every victim; the pool audit asserts the
      blocks actually came back).
    """
    eng = ServeEngine(m, params, slots=4, max_len=MAX_LEN, eos_id=1 << 20,
                      decode_chunk=4, paged=True, queue_limit=6,
                      metrics=True)
    # warm: compile both megasteps, then calibrate a solo service time on
    # a second (warm) run so compile time never inflates the deadlines
    eng.submit([1, 5, 9], max_new=16)
    eng.run_to_completion()
    eng.submit([1, 5, 9], max_new=16)
    t0 = time.perf_counter()
    eng.run_to_completion()
    t_solo = time.perf_counter() - t0
    # tight finishes solo but not behind a queue; loose always finishes
    tight, loose = 2.0 * t_solo, 30.0 * t_solo

    rng = random.Random(0)
    # service rate is ~1 req/step (4 slots × 16 new @ chunk 4): offer
    # 1.6× that so the bounded queue genuinely fills and sheds
    n_offered, rate = 48, 1.6
    t, arrivals = 0.0, []
    for _ in range(n_offered):
        t += rng.expovariate(rate)
        arrivals.append(t)
    reqs, shed = [], {"queue_full": 0, "deadline_unreachable": 0}
    step_i, next_arr = 0, 0
    while next_arr < n_offered or eng.scheduler.in_flight():
        while next_arr < n_offered and arrivals[next_arr] <= step_i:
            timeout = tight if rng.random() < 0.5 else loose
            try:
                rid = eng.submit([1, 2 + next_arr % 7, 9], max_new=16,
                                 timeout=timeout)
                reqs.append(eng.scheduler.get(rid))
            except QueueFullError as e:
                key = ("deadline_unreachable" if e.reason else "queue_full")
                shed[key] += 1
            next_arr += 1
        eng.step()
        step_i += 1
    reasons = {}
    for r in reqs:
        assert r.done and r.reason is not None
        reasons[r.reason] = reasons.get(r.reason, 0) + 1
    n_shed = sum(shed.values())
    assert len(reqs) + n_shed == n_offered
    assert eng.kv.drained(), "lifecycle bench leaked pool blocks"
    shed_rate = n_shed / n_offered
    goodput = reasons.get("max_new", 0) / n_offered
    out.append(
        f"serve.lifecycle.open_loop,0,offered={n_offered}"
        f"_shed={n_shed}_rate={shed_rate:.2f}_goodput={goodput:.2f}"
    )

    # cancel-reclaim latency: victims cancelled mid-decode, one at a time
    lat_us = []
    for i in range(4):
        eng.submit([1, 3 + i, 9, 5], max_new=48)
    eng.step()
    while eng.scheduler.has_prefilling():
        eng.step()
    eng.step()  # into decode
    for req in [r for r in eng.scheduler.in_flight()]:
        free0 = eng.kv.free_blocks
        t0 = time.perf_counter()
        assert eng.cancel(req.rid)
        lat_us.append((time.perf_counter() - t0) * 1e6)
        assert eng.kv.free_blocks > free0
    eng.run_to_completion()
    assert eng.kv.drained()
    p50 = percentile(lat_us, 0.5)
    p95 = percentile(lat_us, 0.95)
    out.append(f"serve.lifecycle.cancel_reclaim,{p50:.0f},p95={p95:.0f}us")
    return {
        "offered": n_offered, "arrival_rate_per_step": rate,
        "queue_limit": 6, "slots": 4, "steps": step_i,
        "deadline_tight_s": round(tight, 4),
        "deadline_loose_s": round(loose, 4),
        "shed": shed, "shed_rate": round(shed_rate, 3),
        "reasons": reasons, "goodput": round(goodput, 3),
        "cancel_reclaim_us": {
            "p50": round(p50, 1), "p95": round(p95, 1), "n": len(lat_us),
        },
    }


def _sharded_section(out):
    """Tensor-parallel serving (DESIGN §14) in a subprocess: the device
    count is process-global, so tp=1 and tp=2 both run under the SAME
    forced-2-device host platform — the tok/s ratio compares identical
    XLA runtimes, isolating the cost of the collectives. The structural
    claim is the pool partition: per-shard bytes = total / TP."""
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT],
        capture_output=True, text=True, timeout=900, env=env,
    )
    if proc.returncode != 0:
        out.append("serve.sharded.tp2,0,FAILED")
        return {"error": proc.stderr[-1000:]}
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][0]
    res = json.loads(line[len("RESULT:"):])
    res["claim"] = (
        "per-shard KV pool bytes = unsharded / TP; greedy tokens "
        "identical to tp=1 (pinned by tests/serve/test_sharded.py)"
    )
    out.append(
        f"serve.sharded.tp2,0,tok_s={res['tp2']['tok_s']}"
        f"_ratio={res['tok_s_ratio_tp2_vs_tp1']}"
        f"_shard_bytes={res['tp2']['pool_bytes_per_shard']}"
    )
    return res


if __name__ == "__main__":
    print("\n".join(run()))
