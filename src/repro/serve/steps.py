"""The serving engine's compiled steps: device graphs only.

:func:`build_steps` closes four step functions over one model, the
engine's sampler and its static sizes; ``ServeEngine`` jits each once and
dispatches by kind: ``chunkstep`` (mixed prefill+decode, DESIGN §11),
``megastep`` (decode loop, §9), ``spec_megastep`` (model drafter, §12)
and ``ngram_megastep`` (prompt lookup drafter). What an engine lacks
goes in as None: ``adapters`` (the ``(aidx, aval, aid)`` tenant triple),
``table``/``wtable`` (paged block tables), ``dp``/``dcache`` (drafter
params and scratch). ``jax.jit`` takes None as an empty pytree, so each
engine compiles one program per kind. Host state (scheduler, cache
placement, metrics) stays in the engine; nothing here imports it.
"""

from __future__ import annotations

from collections.abc import Callable

import jax
import jax.numpy as jnp

from repro.core.delta import BatchedDelta
from repro.serve.sampler import Sampler


def batched_adapters(num_layers: int, adapters):
    """Wrap the stacked tenant trees as per-slot :class:`BatchedDelta`
    leaves (None passes through). ``blocks`` leaves ride the layer scan:
    their ``aid`` copy carries a leading L axis so scan slices every xs
    leaf uniformly."""
    if adapters is None:
        return None
    aidx, aval, aid = adapters
    aid_l = jnp.broadcast_to(aid[None, :], (num_layers, aid.shape[0]))
    out = {}
    for key, sub_i in aidx.items():
        a = aid_l if key == "blocks" else aid
        out[key] = jax.tree.map(
            lambda i, v, a=a: None if i is None else BatchedDelta(i, v, a),
            sub_i, aval[key], is_leaf=lambda x: x is None,
        )
    return out


def build_steps(
    model,
    get_sampler: Callable[[], Sampler],
    *,
    eos_id: int,
    max_len: int,
    decode_chunk: int,
    spec_k: int,
    rows: int,
) -> dict[str, Callable]:
    """The four step functions, un-jitted, keyed by kind. ``rows`` is the
    mixed step's flat token row count (``scheduler.chunk_rows``).
    ``get_sampler()`` runs while each step traces, so a sampler the
    engine is given after construction is the one its steps compile."""
    L = model.cfg.num_layers
    eos, mlen, chunk, K = eos_id, max_len, decode_chunk, spec_k

    def chunkstep(p, dp, adapters, table, wtable, cache, dcache, tokens,
                  q_offset, q_len, last_idx, temps, key):
        """Compiled mixed prefill+decode step (DESIGN §11).

        One (slots, prefill_chunk) token buffer: prefilling slots
        carry their next prompt chunk, decode slots the degenerate
        one-token chunk, idle/stalled slots ``q_len = 0`` no-ops.
        K/v land in-graph (write table gates shared paged blocks),
        logits gather at each row's last real token, sampling is
        fused — the (slots,) token vector is the step's single host
        transfer. Positions advance to ``q_offset + q_len`` for
        every role (decode +1, prefill +take, idle frozen).

        A model drafter (``dp``) consumes the SAME buffer into its own
        dense KV scratch ``dcache``: its logits are dead code XLA prunes,
        so drafting adds one cache-write pass, not a second head, and
        by decode the scratch mirrors every verified position (hence no
        prefix fast-forward under model drafting: ``_try_place``).
        """
        adapters = batched_adapters(L, adapters)
        sampler = get_sampler()
        batch = {"tokens": tokens, "q_offset": q_offset,
                 "q_len": q_len, "last_idx": last_idx, "rows": rows}
        dbatch = dict(batch)  # the drafter's dense scratch has no tables
        if table is not None:
            batch["block_table"] = table
            batch["write_table"] = wtable
        logits, cache = model.prefill_chunk(p, adapters, cache, batch)
        if dp is not None:
            _, dcache = model.prefill_chunk(dp, None, dcache, dbatch)
        toks = sampler(logits, temps, key)
        return cache, dcache, q_offset + q_len, toks

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
        adapters = batched_adapters(L, adapters)
        sampler = get_sampler()

        def body(carry, k_t):
            cache, tok, pos, active, remaining = carry
            batch = {"token": tok, "pos": pos}
            if table is not None:
                batch["block_table"] = table
            logits, cache = model.decode_step(p, adapters, cache, batch)
            nxt = sampler(logits, temps, k_t)
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
        sampler = get_sampler()
        ctokens = jnp.concatenate([tok[:, None], d_t], axis=1)
        q_len = jnp.where(active, jnp.minimum(C, mlen - pos), 0)
        vbatch = {"tokens": ctokens, "q_offset": pos, "q_len": q_len}
        if table is not None:
            vbatch["block_table"] = table
            vbatch["write_table"] = table
        vlogits, cache = model.verify_chunk(p, adapters, cache, vbatch)
        p_t = sampler.probs(
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
        srows = jnp.arange(S)
        p_sel = p_t[srows, a]
        if q_t is None:
            # scatter 0 at the rejected proposal; a = K drops (no-op)
            d_rej = jnp.where(
                a < K, d_t[srows, jnp.minimum(a, K - 1)], p_t.shape[-1]
            )
            res = p_sel.at[srows, d_rej].set(0.0, mode="drop")
        else:
            q_sel = jnp.where(
                (a < K)[:, None], q_t[srows, jnp.minimum(a, K - 1)], 0.0
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
        adapters = batched_adapters(L, adapters)
        sampler = get_sampler()

        def round_body(carry, k_t):
            cache, dcache, tok, pos, active, remaining = carry
            live = active
            k_draft, k_acc, k_res = jax.random.split(k_t, 3)

            def draft_body(c, k_i):
                dcache, dtok, dpos = c
                dl, dcache = model.decode_step(
                    dp, None, dcache, {"token": dtok, "pos": dpos}
                )
                p_d = sampler.probs(dl, temps)
                nxt = sampler(dl, temps, k_i)
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

    def ngram_megastep(p, adapters, table, cache, hist, tok, pos, active,
                       remaining, temps, key):
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
        adapters = batched_adapters(L, adapters)
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

    return {
        "chunkstep": chunkstep,
        "megastep": megastep,
        "spec_megastep": spec_megastep,
        "ngram_megastep": ngram_megastep,
    }
