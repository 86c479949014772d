"""Chunked prefill fused into the serving step (DESIGN §11).

The engine replaces stop-the-world bucketed prefill with mixed
prefill+decode chunk steps: one compiled graph advances decode slots a
token while prefilling slots consume their next prompt chunk. These
tests pin the contract: greedy outputs identical across every
``prefill_chunk`` (and to the dense engine), ONE compiled shape — no
per-prompt-length recompiles, ONE device→host transfer per mixed step,
decode streams that keep emitting while a long prompt prefills,
mid-prefill preemption that resumes exactly, prefix sharing that spans
multiple chunks (with the sharer's chunk walk skipping resident pages),
the paged prefill-attention kernel wired e2e under interpret mode, and
the flat row layout of the mixed step's dense work: a plan never holds
more real tokens than the rows the step computes, and the flat rows
reproduce the (B, C) grid's logits and cache writes.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.adapt import init_adapters
from repro.core.delta import BatchedDelta
from repro.kernels import ops
from repro.models import get_model
from repro.serve import AdapterStore, ServeEngine
from repro.serve import scheduler
from repro.serve.scheduler import Scheduler, chunk_rows

_NO_EOS = 1 << 20
_CACHE = {}


def _model(arch="qwen2-1.5b"):
    if arch not in _CACHE:
        cfg = reduced(get_config(arch)).replace(dtype="float32")
        m = get_model(cfg)
        _CACHE[arch] = (cfg, m, m.init(jax.random.PRNGKey(0)))
    return _CACHE[arch]


def _adapter(params, seed, k=2, scale=0.05):
    idx, val = init_adapters(params, k, rng=jax.random.PRNGKey(seed))
    val = jax.tree.map(
        lambda i, v: None
        if v is None
        else scale
        * jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(seed), v.size), v.shape
        ),
        idx, val, is_leaf=lambda x: x is None,
    )
    return idx, val


def _store(params, arch="qwen2-1.5b"):
    if ("store", arch) not in _CACHE:
        store = AdapterStore()
        store.register(*_adapter(params, seed=1))
        store.register(*_adapter(params, seed=2))
        _CACHE["store", arch] = store
    return _CACHE["store", arch]


_PROMPTS = [[1, 5, 9], list(range(1, 21)), list(range(2, 33)), [1, 7],
            list(range(3, 15))]


def _run(m, params, *, prefill_chunk, paged, store=None, decode_chunk=3,
         max_len=64):
    eng = ServeEngine(
        m, params, slots=2, max_len=max_len, eos_id=_NO_EOS,
        adapter_store=store, decode_chunk=decode_chunk,
        prefill_chunk=prefill_chunk, paged=paged,
    )
    n_ad = store.num_adapters if store is not None else 0
    for i, p in enumerate(_PROMPTS):
        eng.submit(p, max_new=4 + i, adapter_id=(1 + i % n_ad) if n_ad else 0)
    return [r.out for r in eng.run_to_completion()], eng


# ---------------------------------------------------------------- parity


@pytest.mark.parametrize("variant", ["plain", "multitenant"])
def test_chunk_size_invisible_to_greedy_outputs(variant):
    """Prompts spanning many lengths decode token-identically whatever
    the prefill chunk — including chunks smaller than every prompt — on
    both cache layouts."""
    cfg, m, params = _model()
    store = _store(params) if variant == "multitenant" else None
    ref, _ = _run(m, params, prefill_chunk=64, paged=False, store=store)
    for paged in (False, True):
        for chunk in (3, 8, 64):
            got, eng = _run(
                m, params, prefill_chunk=chunk, paged=paged, store=store
            )
            assert got == ref, (paged, chunk)
            if paged:
                assert eng.kv.free_blocks == eng.kv.num_blocks


# ------------------------------------------------------ compile counting


def test_unified_step_compiles_once_per_mode():
    """The mixed chunk buffer has ONE compiled shape: prompts crossing
    every old pow2 bucket reuse a single compilation per (paged,
    adapter-mode) — the per-bucket prefill graphs are gone."""
    cfg, m, params = _model()
    store = _store(params)
    for paged in (False, True):
        eng = ServeEngine(m, params, slots=2, max_len=64, eos_id=_NO_EOS,
                          decode_chunk=2, prefill_chunk=8, paged=paged)
        for p in _PROMPTS:  # lengths 2..32: four pow2 buckets at min 16
            eng.submit(p, max_new=3)
        eng.run_to_completion()
        counts = eng.compile_counts()
        assert counts["chunkstep"] == 1
        assert counts["megastep"] == 1
        # adapter-mode twin: one more compile, not one per bucket
        eng2 = ServeEngine(m, params, slots=2, max_len=64, eos_id=_NO_EOS,
                           decode_chunk=2, prefill_chunk=8, paged=paged,
                           adapter_store=store)
        for p in _PROMPTS:
            eng2.submit(p, max_new=3, adapter_id=1)
        eng2.run_to_completion()
        assert eng2.compile_counts()["chunkstep"] == 1


# --------------------------------------------------- transfer accounting


def test_mixed_step_one_transfer(monkeypatch):
    """A mixed prefill+decode step costs exactly ONE device→host fetch
    (the sampled token vector) — positions mirror host-side."""
    cfg, m, params = _model()
    eng = ServeEngine(m, params, slots=2, max_len=64, eos_id=_NO_EOS,
                      decode_chunk=4, prefill_chunk=4, paged=True)
    eng.submit([1, 5, 9, 2], max_new=30)
    eng.step()  # admit + prefill the short stream
    eng.submit(list(range(1, 25)), max_new=4)  # 24 tokens: 6 mixed steps
    calls = []
    real = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: (calls.append(1), real(x))[1]
    )
    for _ in range(6):
        assert eng.step()
    assert len(calls) == 6
    long_req = eng.scheduler.active[1]
    assert long_req is not None and len(long_req.out) == 1  # just emitted


# ------------------------------------------------------- no-stall shape


def test_long_prompt_does_not_stall_decode_streams():
    """While a long prompt is consumed chunk by chunk, every decode slot
    keeps emitting one token per step — the head-of-line stall the
    stop-the-world prefill used to impose is gone."""
    cfg, m, params = _model()
    eng = ServeEngine(m, params, slots=3, max_len=64, eos_id=_NO_EOS,
                      decode_chunk=1, prefill_chunk=4, paged=True)
    s1 = eng.submit([1, 5, 9], max_new=40)
    s2 = eng.submit([1, 6, 9], max_new=40)
    eng.step()  # the 4-token budget covers one 3-token prompt per step
    eng.step()
    reqs = {r.rid: r for r in eng.scheduler.in_flight()}
    assert not eng.scheduler.has_prefilling()  # both streams decoding
    long_rid = eng.submit(list(range(1, 29)), max_new=4)  # 7 chunks of 4
    long_req = None
    for step in range(7):
        before = [len(reqs[s1].out), len(reqs[s2].out)]
        eng.step()
        if long_req is None:
            long_req = next(
                r for r in eng.scheduler.in_flight() if r.rid == long_rid
            )
        assert len(reqs[s1].out) == before[0] + 1  # decode never stalled
        assert len(reqs[s2].out) == before[1] + 1
        assert len(long_req.out) == (1 if step == 6 else 0)
    # prompt complete: first token emitted the same step the last chunk ran
    assert len(long_req.out) == 1


# ------------------------------------------------ preemption mid-prefill


def test_preempt_mid_prefill_matches_uncontended():
    """Pool OOM between chunks preempts the youngest request while its
    prompt is still being consumed; it re-prefills from scratch later and
    finishes with greedy output identical to an uncontended run."""
    cfg, m, params = _model()
    a_prompt, b_prompt = [1, 5, 9, 2], list(range(1, 25))

    def solo(prompt, max_new):
        eng = ServeEngine(m, params, slots=1, max_len=36, eos_id=_NO_EOS,
                          decode_chunk=4, prefill_chunk=4, paged=True,
                          page_size=4)
        eng.submit(prompt, max_new=max_new)
        return eng.run_to_completion()[0].out

    want = [solo(a_prompt, 20), solo(b_prompt, 4)]
    eng = ServeEngine(m, params, slots=2, max_len=36, eos_id=_NO_EOS,
                      decode_chunk=4, prefill_chunk=4, paged=True,
                      page_size=4, num_blocks=9)
    eng.submit(a_prompt, max_new=20)
    eng.step()  # A admitted and prefilled; B arrives mid-decode
    eng.submit(b_prompt, max_new=4)
    got = [r.out for r in eng.run_to_completion()]
    assert eng.preemptions_mid_prefill >= 1  # B was evicted between chunks
    assert got == want
    assert eng.kv.free_blocks == eng.kv.num_blocks
    assert not eng.kv.refcount.any()


# ------------------------------------------- prefix sharing across chunks


def test_prefix_sharing_spans_multiple_chunks():
    """A shared prefix longer than the prefill chunk still dedups: the
    writer lands it chunk by chunk, the sharer admits once the pages are
    written and SKIPS its resident prefix — only the private tail runs
    through the mixed step."""
    cfg, m, params = _model()
    prefix = list(range(1, 25))  # 6 pages at page_size=4, 3 chunks of 8
    eng = ServeEngine(m, params, slots=2, max_len=48, eos_id=_NO_EOS,
                      decode_chunk=2, prefill_chunk=8, paged=True,
                      page_size=4)
    eng.submit(prefix + [100], max_new=6)
    eng.submit(prefix + [101], max_new=6)
    # writer takes 3 chunk steps + the private token; the sharer waits at
    # the queue head until the prefix pages are actually written
    for _ in range(3):
        eng.step()
        assert sum(r is not None for r in eng.scheduler.active) == 1
    eng.step()  # prefix fully written -> sharer admits, skips 24 tokens
    sharer = eng.scheduler.active[1]
    assert sharer is not None and sharer.prefilled >= 24
    shared = eng.kv.refcount[eng.kv.refcount > 1]
    assert len(shared) == 6 and (shared == 2).all()
    assert eng.kv.used_blocks == 8  # 7 writer pages + 1 private sharer page
    got = [r.out for r in eng.run_to_completion()]
    assert eng.kv.free_blocks == eng.kv.num_blocks
    # sharing and skipping are invisible to the tokens
    dense = ServeEngine(m, params, slots=2, max_len=48, eos_id=_NO_EOS,
                        decode_chunk=2, prefill_chunk=8)
    dense.submit(prefix + [100], max_new=6)
    dense.submit(prefix + [101], max_new=6)
    assert [r.out for r in dense.run_to_completion()] == got


# --------------------------------------------------- kernel path wiring


def test_chunked_prefill_kernel_path_on_interpret():
    """The paged prefill-attention kernel carries the whole engine e2e
    (interpret mode) and reproduces the jnp-backend tokens — int8 base
    and tenant deltas included."""
    cfg, m, params = _model()
    store = AdapterStore()
    store.register(*_adapter(params, seed=3))

    def go(chunk):
        eng = ServeEngine(m, params, slots=2, max_len=32, eos_id=_NO_EOS,
                          adapter_store=store, base_dtype="int8",
                          decode_chunk=2, prefill_chunk=chunk, paged=True,
                          page_size=8)
        eng.submit(list(range(1, 19)), max_new=4, adapter_id=1)
        eng.submit([1, 5, 9], max_new=4, adapter_id=1)
        return [r.out for r in eng.run_to_completion()]

    want = go(32)  # jnp backend: gather + dense masked softmax
    with ops.use_backend("pallas_interpret"):
        got = go(8)  # chunked through the Pallas kernel
    assert got == want


# ---------------------------------------------------- flat row layout


def test_chunk_plan_never_exceeds_the_step_rows(monkeypatch):
    """Over many steps of random arrivals, prompt lengths and output
    lengths, no plan holds more than ``budget + slots - 1`` real tokens
    — within the ``chunk_rows`` the mixed step computes — and a plan held
    to fewer rows than it needs raises instead of dropping tokens."""
    rng = random.Random(7)
    for slots, budget in ((5, 8), (32, 256), (3, 1)):
        rows = chunk_rows(slots, budget)
        assert rows % 8 == 0 or rows == slots * budget
        assert budget + slots - 1 <= rows <= slots * budget
        sched = Scheduler(slots)
        kv_pos = np.zeros((slots,), np.int32)
        most = 0
        for _ in range(300):
            for _ in range(rng.randrange(3)):
                sched.submit([1] * rng.randint(1, 3 * budget), max_new=rng.randint(1, 6))
            sched.admissible()
            if not sched.has_prefilling():
                for s, req in enumerate(sched.active):  # one decode step
                    if req is not None:
                        req.out.append(1)
                        kv_pos[s] += 1
                        if len(req.out) >= req.max_new:
                            sched.complete(s)
                continue
            plan = sched.chunk_plan(budget, kv_pos)
            real = int(plan["q_len"].sum())
            assert 0 < real <= budget + slots - 1
            most = max(most, real)
            for s, req in enumerate(sched.active):
                if req is None:
                    continue
                kv_pos[s] = plan["q_offset"][s] + plan["q_len"][s]
                if req.mid_prefill:
                    req.prefilled += int(plan["q_len"][s])
                if plan["emit"][s]:
                    req.out.append(1)
                    if len(req.out) >= req.max_new:
                        sched.complete(s)
        assert most > budget  # prompts and decode tokens shared a step
        sched.submit([1] * (2 * budget), max_new=1)
        sched.admissible()
        while not sched.has_prefilling():
            sched.complete(next(s for s, r in enumerate(sched.active) if r is not None))
            sched.admissible()
        need = int(sched.chunk_plan(budget, kv_pos)["q_len"].sum())
        with monkeypatch.context() as mp:
            mp.setattr(scheduler, "chunk_rows", lambda slots, budget: need - 1)
            with pytest.raises(RuntimeError, match="past its"):
                sched.chunk_plan(budget, kv_pos)


# slot 0 prefills a full chunk at offset 5, slots 1 and 4 decode, slot 2
# is idle, slot 3 stalled mid-prompt (q_len 0 at offset 6)
_FLAT_LEN = np.array([8, 1, 0, 0, 1], np.int32)
_FLAT_OFF = np.array([5, 9, 0, 6, 3], np.int32)
_FLAT_B, _FLAT_C, _FLAT_MAX, _FLAT_PAGE = 5, 8, 16, 4


def _flat_case(case):
    """A model, its adapters, a cache of random contents and a mixed
    chunk batch in the layout above, for one row-layout parity case; the
    ``moe`` cases run reduced phi3.5-moe at its own capacity factor."""
    arch = "phi3.5-moe-42b-a6.6b" if case.startswith("moe") else "qwen2-1.5b"
    cfg, m, params = _model(arch)
    key = jax.random.PRNGKey(11)
    b, c = _FLAT_B, _FLAT_C
    n_pages = _FLAT_MAX // _FLAT_PAGE
    if case == "dense":
        cache = m.init_cache(b, _FLAT_MAX)
    else:
        cache = m.init_paged_cache(
            b * n_pages, _FLAT_PAGE, kv_dtype="int8" if case == "paged_int8" else "fp32"
        )

    def fill(i, x):
        k = jax.random.fold_in(key, i)
        if x.dtype == jnp.int8:
            return jax.random.randint(k, x.shape, -127, 128, jnp.int32).astype(jnp.int8)
        if x.ndim == 4:  # int8 scales
            return jax.random.uniform(k, x.shape, x.dtype, 0.005, 0.02)
        return jax.random.normal(k, x.shape, x.dtype)

    cache = {n: fill(i, x) for i, (n, x) in enumerate(sorted(cache.items()))}
    batch = {
        "tokens": jax.random.randint(key, (b, c), 1, cfg.vocab_size),
        "q_offset": jnp.asarray(_FLAT_OFF),
        "q_len": jnp.asarray(_FLAT_LEN),
        "last_idx": jnp.asarray(np.maximum(_FLAT_LEN - 1, 0)),
    }
    if case != "dense":
        table = jnp.arange(b * n_pages, dtype=jnp.int32).reshape(b, n_pages)[::-1]
        batch["block_table"] = batch["write_table"] = table
    adapters = None
    if case.endswith("tenants"):
        aidx, aval = _store(params, arch).stacked()
        aid = jnp.asarray([1, 2, 0, 1, 2], jnp.int32)
        aid_l = jnp.broadcast_to(aid[None], (cfg.num_layers, b))
        adapters = {
            k: jax.tree.map(
                lambda i, v, a=(aid_l if k == "blocks" else aid): None
                if i is None else BatchedDelta(i, v, a),
                aidx[k], aval[k], is_leaf=lambda x: x is None,
            )
            for k in aidx
        }
    return cfg, m, params, adapters, cache, batch


@pytest.mark.parametrize(
    "case", ["dense", "paged", "paged_int8", "tenants", "verify", "moe", "moe_tenants"]
)
def test_flat_rows_match_the_chunk_grid(case):
    """The mixed step's dense work on ``rows`` = T < B·C flat rows gives
    the logits and the cache writes of the (B, C) grid, for a layout with
    a full prefill chunk at an offset, decode, idle and stalled slots;
    ``verify_chunk``, whose rows carry no budget, and T = B·C lower to
    today's grid program unchanged. The flat rows' MoE routing drops no
    token: at the model's own capacity factor they give what a grid whose
    experts hold every token gives, where the grid at that factor drops."""
    cfg, m, params, adapters, cache, batch = _flat_case(
        "paged" if case == "verify" else case
    )
    b, c = _FLAT_B, _FLAT_C
    rows = chunk_rows(b, c)
    assert int(_FLAT_LEN.sum()) <= rows < b * c
    if case == "verify":
        for fn in (m.verify_chunk, m.prefill_chunk):
            def lower(extra, fn=fn):
                return jax.jit(
                    lambda cache, batch: fn(params, None, cache, {**batch, **extra})
                ).lower(cache, batch).as_text()

            assert lower({"rows": b * c}) == lower({})
        return

    def step(model, rows):
        return jax.jit(
            lambda cache, batch: model.prefill_chunk(params, adapters, cache, {**batch, "rows": rows})
        )(cache, batch)

    got_logits, got_cache = step(m, rows)
    live = _FLAT_LEN > 0  # idle and stalled slots sample nothing
    if cfg.num_experts:
        dropping, _ = step(m, None)
        assert not np.allclose(np.asarray(dropping)[live], np.asarray(got_logits)[live])
        m = get_model(cfg.replace(capacity_factor=cfg.num_experts / cfg.experts_per_token))
    want_logits, want_cache = step(m, None)
    np.testing.assert_allclose(
        np.asarray(got_logits)[live], np.asarray(want_logits)[live], rtol=1e-5, atol=1e-5
    )
    for name in want_cache:
        np.testing.assert_allclose(
            np.asarray(got_cache[name], np.float32),
            np.asarray(want_cache[name], np.float32),
            rtol=1e-5, atol=1e-5, err_msg=name,
        )
