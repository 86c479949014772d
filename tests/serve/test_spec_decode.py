"""Speculative decoding semantics: drafting must be invisible externally.

The spec megastep compiles ``decode_chunk`` draft/verify/accept rounds
into one jitted call (DESIGN §12): the drafter proposes k tokens per
slot, the full model scores all k+1 positions as one verify chunk, and a
rejection-sampled prefix commits while the rest rolls back via a pure
position rewind. These tests pin the contract: token-for-token greedy
parity with ``draft="off"`` across (plain, multi-tenant, int8-base,
model-free ngram) × (dense, paged) × (EOS mid-round, max_new mid-round,
cache full mid-round), still exactly one device→host transfer per
megastep, the speculative-sampling distribution guarantee for
temperature slots (model drafter AND deterministic one-hot drafter),
the acceptance telemetry, and drafter-construction sharing/validation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.adapt import init_adapters
from repro.models import get_model
from repro.serve import AdapterStore, ServeEngine, build_draft_params

_NO_EOS = 1 << 20
_CACHE = {}


def _model():
    if "m" not in _CACHE:
        cfg = reduced(get_config("qwen2-1.5b")).replace(dtype="float32")
        m = get_model(cfg)
        _CACHE["m"] = (cfg, m, m.init(jax.random.PRNGKey(0)))
    return _CACHE["m"]


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


def _store(params):
    if "store" not in _CACHE:
        store = AdapterStore()
        store.register(*_adapter(params, seed=1))
        store.register(*_adapter(params, seed=2))
        _CACHE["store"] = store
    return _CACHE["store"]


def _run(m, params, *, draft, spec_k=4, chunk=8, eos_id=_NO_EOS, store=None,
         base_dtype="fp32", paged=False):
    """5 requests on 2 slots: slot eviction + re-admission mid-run, and
    max_new values that land mid-round for every spec_k."""
    eng = ServeEngine(
        m, params, slots=2, max_len=64, eos_id=eos_id, adapter_store=store,
        base_dtype=base_dtype, decode_chunk=chunk, paged=paged,
        draft=draft, spec_k=spec_k,
    )
    n_ad = store.num_adapters if store is not None else 0
    for i, max_new in enumerate((3, 7, 12, 5, 9)):
        eng.submit(
            [1, 5 + i, 9, 2], max_new=max_new,
            adapter_id=(1 + i % n_ad) if n_ad else 0,
        )
    return [r.out for r in eng.run_to_completion()]


@pytest.mark.parametrize("variant", ["plain", "multitenant", "int8"])
def test_spec_greedy_parity(variant):
    """Drafted greedy decode must be token-identical to --draft off: the
    emitted stream is always the full model's, the drafter only moves the
    acceptance rate. int8 uses the quantized self-draft (shared packed
    base), multitenant the merged mean-of-tenants drafter."""
    cfg, m, params = _model()
    store = _store(params) if variant == "multitenant" else None
    base = "int8" if variant == "int8" else "fp32"
    draft = "merged" if variant == "multitenant" else "int8"
    ref = _run(m, params, draft="off", store=store, base_dtype=base)
    assert [len(o) for o in ref] == [3, 7, 12, 5, 9]  # max_new mid-round
    got = _run(m, params, draft=draft, store=store, base_dtype=base)
    assert got == ref
    got_paged = _run(
        m, params, draft=draft, spec_k=2, store=store, base_dtype=base,
        paged=True,
    )
    assert got_paged == ref


def test_spec_eos_mid_round():
    """EOS landing inside an accepted prefix: the triggering token is
    emitted and everything drafted after it rolls back, exactly like the
    per-token loop stopping there."""
    cfg, m, params = _model()
    ref = _run(m, params, draft="off")
    eos = ref[2][4]  # a token the greedy decode actually emits mid-stream
    cut = _run(m, params, draft="off", eos_id=eos)
    assert any(len(c) < len(r) for c, r in zip(cut, ref))
    assert _run(m, params, draft="int8", eos_id=eos) == cut
    assert _run(m, params, draft="nf4", spec_k=3, eos_id=eos) == cut


@pytest.mark.parametrize("paged", [False, True])
def test_spec_cache_full_mid_round(paged):
    """A slot hitting max_len-1 inside a round: the verify chunk's q_len
    clamp keeps writes inside the cache and emission stops exactly where
    the per-token loop stops."""
    cfg, m, params = _model()

    def go(draft):
        eng = ServeEngine(m, params, slots=1, max_len=16, eos_id=_NO_EOS,
                          decode_chunk=8, paged=paged, draft=draft, spec_k=4)
        eng.submit([1, 5, 9, 2], max_new=64)  # wants more than the cache
        return [r.out for r in eng.run_to_completion()]

    ref = go("off")
    assert len(ref[0]) == 16 - 4  # prefill ends at pos=4; stops at pos 15
    assert go("int8") == ref


def test_spec_one_transfer_per_megastep(monkeypatch):
    """The spec megastep still costs exactly ONE device→host transfer:
    the (positions, survivor mask, candidates, emit mask, acceptance,
    live) bundle for all rounds and slots comes back in one fetch."""
    cfg, m, params = _model()
    eng = ServeEngine(m, params, slots=2, max_len=64, eos_id=_NO_EOS,
                      decode_chunk=2, draft="int8", spec_k=2)
    eng.submit([1, 5, 9, 2], max_new=40)
    eng.submit([1, 6, 9, 2], max_new=40)
    eng.step()  # admission + the one mixed prefill step (first tokens out)
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: (calls.append(1), real(x))[1])
    before = eng.transfers
    n0 = len(eng.scheduler.active[0].out)
    for _ in range(3):
        assert eng.step()  # spec decode only: no admission happens
    assert len(calls) == 3
    assert eng.transfers - before == 3
    # every round emits at least one token (the correction/bonus), at most
    # spec_k+1; 3 megasteps of 2 rounds each
    n = len(eng.scheduler.active[0].out) - n0
    assert 3 * 2 <= n <= 3 * 2 * 3


def test_spec_acceptance_stats_exact_drafter():
    """A merged drafter over ONE tenant is the served model itself: every
    greedy draft must be accepted, and the per-request counters must sum
    to the engine totals."""
    cfg, m, params = _model()
    store = AdapterStore()
    store.register(*_adapter(params, seed=1))
    eng = ServeEngine(m, params, slots=2, max_len=64, eos_id=_NO_EOS,
                      adapter_store=store, decode_chunk=4, draft="merged",
                      spec_k=3)
    for i in range(2):
        eng.submit([1, 5 + i, 9, 2], max_new=20, adapter_id=1)
    reqs = eng.run_to_completion()
    assert eng.spec_drafted > 0
    assert eng.spec_accepted == eng.spec_drafted  # exact drafter
    assert sum(r.spec_drafted for r in reqs) == eng.spec_drafted
    assert sum(r.spec_accepted for r in reqs) == eng.spec_accepted
    # mixed prefill emits the first token of each stream; the rest flow
    # through the spec path
    assert eng.spec_emitted == sum(len(r.out) - 1 for r in reqs)


def test_spec_sampling_matches_target_distribution():
    """The speculative-sampling guarantee: with temperature on, the first
    token a round emits is distributed per the FULL model's (filtered)
    next-token distribution, not the drafter's — accept, residual
    resample and bonus compose back to exactly p."""
    cfg, m, params = _model()
    eng = ServeEngine(m, params, slots=1, max_len=32, eos_id=_NO_EOS,
                      temperature=1.0, top_k=8, decode_chunk=1,
                      draft="nf4", spec_k=3)
    eng.submit([1, 5, 9, 2], max_new=8)
    eng.step()  # mixed prefill: samples the first token, fills both caches
    st = eng.scheduler.slot_arrays()
    tok = jnp.asarray(st["tokens"])
    temps = jnp.asarray(st["temps"])
    # the exact target distribution at this state, in closed form
    logits, _ = m.decode_step(
        eng.params, None, eng.kv.data, {"token": tok, "pos": eng.kv.pos}
    )
    p = np.asarray(eng.sampler.probs(logits, temps))[0]
    # replay the compiled spec megastep from the SAME state under many keys
    args = (tok, eng.kv.pos, jnp.asarray(st["active"]),
            jnp.asarray(st["remaining"]), temps)
    n = 400
    counts = np.zeros(cfg.vocab_size)
    for i in range(n):
        out = eng._spec_megastep(
            eng.params, eng.draft_params, None, None, eng.kv.data,
            eng.draft_kv.data, *args, jax.random.PRNGKey(i),
        )
        toks, emits = np.asarray(out[4]), np.asarray(out[5])
        assert emits[0, 0, 0]  # an active slot emits >= 1 token per round
        counts[toks[0, 0, 0]] += 1
    freq = counts / n
    assert freq[p == 0].sum() == 0.0  # never outside the top_k filter
    tv = 0.5 * np.abs(freq - p).sum()
    assert tv < 0.12, (tv, freq[p > 0], p[p > 0])


@pytest.mark.parametrize("paged", [False, True])
def test_ngram_greedy_parity(paged):
    """The model-free prompt-lookup drafter: zero draft forwards, greedy
    outputs still token-identical to --draft off on dense and paged
    caches — including multi-tenant slots (the ngram megastep verifies
    through the same batched-adapter path as the plain one)."""
    cfg, m, params = _model()
    ref = _run(m, params, draft="off")
    assert _run(m, params, draft="ngram") == ref
    got = _run(m, params, draft="ngram", spec_k=2, paged=paged)
    assert got == ref
    store = _store(params)
    ref_mt = _run(m, params, draft="off", store=store)
    assert _run(m, params, draft="ngram", store=store, paged=paged) == ref_mt


def test_ngram_has_no_drafter_state():
    """ngram builds no drafter params and no drafter scratch cache, and
    (unlike the model drafters) keeps the shared-prefix prefill
    fast-forward: with nothing to ingest the basis tokens into, skipping
    resident pages is safe."""
    cfg, m, params = _model()
    assert build_draft_params(params, "ngram") is None
    eng = ServeEngine(m, params, slots=2, max_len=64, eos_id=_NO_EOS,
                      draft="ngram", spec_k=4)
    assert eng.draft_params is None and eng.draft_kv is None
    # drafting still emits through the spec path and records telemetry
    eng.submit([1, 5, 9, 2], max_new=12)
    reqs = eng.run_to_completion()
    assert eng.spec_drafted > 0
    assert eng.spec_emitted == sum(len(r.out) - 1 for r in reqs)


def test_ngram_accepts_on_cyclic_output():
    """On a stream that has settled into a short cycle the lookup
    proposals match the target's greedy continuation, so acceptance must
    be substantial — this is the regime the drafter exists for."""
    cfg, m, params = _model()
    eng = ServeEngine(m, params, slots=1, max_len=256, eos_id=_NO_EOS,
                      decode_chunk=8, draft="ngram", spec_k=4)
    eng.submit([1, 5, 9, 2], max_new=240)
    reqs = eng.run_to_completion()
    assert len(reqs[0].out) == 240
    # the early chaotic phase rejects; deep into the sequence the cycle
    # extrapolation lands. Overall acceptance well above noise level.
    assert eng.spec_accepted / eng.spec_drafted > 0.10


def test_ngram_sampling_matches_target_distribution():
    """Speculative sampling with a DETERMINISTIC drafter (q = one-hot):
    accept w.p. p(d), residual = p minus the d column — the emitted
    first token still composes back to exactly the target's filtered
    distribution."""
    cfg, m, params = _model()
    eng = ServeEngine(m, params, slots=1, max_len=32, eos_id=_NO_EOS,
                      temperature=1.0, top_k=8, decode_chunk=1,
                      draft="ngram", spec_k=3)
    eng.submit([1, 5, 9, 2], max_new=8)
    eng.step()  # mixed prefill: samples the first token
    st = eng.scheduler.slot_arrays()
    tok = jnp.asarray(st["tokens"])
    temps = jnp.asarray(st["temps"])
    logits, _ = m.decode_step(
        eng.params, None, eng.kv.data, {"token": tok, "pos": eng.kv.pos}
    )
    p = np.asarray(eng.sampler.probs(logits, temps))[0]
    req = next(r for r in eng.scheduler.active if r is not None)
    hist = np.zeros((1, eng.max_len), np.int32)
    seq = req.prompt + req.out
    hist[0, : len(seq)] = seq
    args = (jnp.asarray(hist), tok, eng.kv.pos, jnp.asarray(st["active"]),
            jnp.asarray(st["remaining"]), temps)
    n = 400
    counts = np.zeros(cfg.vocab_size)
    for i in range(n):
        out = eng._ngram_megastep(
            eng.params, None, None, eng.kv.data, *args, jax.random.PRNGKey(i)
        )
        toks, emits = np.asarray(out[3]), np.asarray(out[4])
        assert emits[0, 0, 0]
        counts[toks[0, 0, 0]] += 1
    freq = counts / n
    assert freq[p == 0].sum() == 0.0
    tv = 0.5 * np.abs(freq - p).sum()
    assert tv < 0.12, (tv, freq[p > 0], p[p > 0])


def test_spec_draft_params_shared_when_base_packed():
    """int8 base + int8 draft: the drafter shares the packed tree outright
    (self-draft, zero extra memory); fp32 base + int8 draft builds a
    quantized copy; merged without tenants is rejected."""
    from repro.peft import quantize_base
    from repro.quant import any_quantized

    cfg, m, params = _model()
    qp = quantize_base(params, "int8", block=64)
    assert build_draft_params(qp, "int8") is qp
    dp = build_draft_params(params, "int8")
    assert dp is not params and any_quantized(dp)
    # mismatched schemes never re-quantize codes: nf4 draft of an int8
    # base dequantizes first, then packs nf4
    assert any_quantized(build_draft_params(qp, "nf4"))
    assert build_draft_params(params, "off") is None
    with pytest.raises(ValueError, match="merged"):
        build_draft_params(params, "merged", store=None)
    with pytest.raises(ValueError, match="merged"):
        build_draft_params(params, "merged", store=AdapterStore())


def test_spec_engine_validation():
    cfg, m, params = _model()
    with pytest.raises(ValueError, match="draft"):
        ServeEngine(m, params, draft="fp8")
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(m, params, draft="int8", spec_k=0)
    with pytest.raises(ValueError, match="merged"):
        ServeEngine(m, params, draft="merged")  # no store registered


def test_launcher_rejects_bad_spec_flags():
    """validate_args dies with a readable SystemExit before any model
    build or compilation."""
    from repro.launch.serve import main

    for argv in (
        ["--spec-k", "0"],
        ["--draft", "fp8"],
        ["--draft", "merged"],  # merged needs --adapters
    ):
        with pytest.raises(SystemExit):
            main(argv)
