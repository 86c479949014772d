"""Paged prefill-attention kernel: interpret-mode parity with the oracle.

The kernel serves the mixed prefill+decode serving step (DESIGN §11):
per-slot query chunks against the shared block pool, block tables and
per-slot (q_offset, kv_valid_len) as scalar prefetch, intra-chunk causal
masking on top of the cache frontier. The sweeps cover GQA group sizes,
ragged offsets/lengths (idle slots, decode slots as degenerate one-token
chunks, frontiers on page and KV-block boundaries and at the table's
end, q-tiles whose causal bound ends inside a KV block), shared and
sentinel table entries, and bf16 inputs; pad rows come out as zeros.
The oracle itself is pinned against plain dense causal attention on a
contiguous cache.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.prefill_attention import paged_prefill_attention_pallas
from repro.models.attention import dense_attention


# (q_offset, q_len) of the ragged layout's fixed slots, in 12 pages of 16
# with a 32-query chunk: an idle slot; a stalled prefill (cache, no query);
# decode slots whose frontier falls on a page boundary inside a KV block,
# on a block boundary and mid-block; a chunk whose frontier is the
# table's last page; a chunk whose q-tiles end inside a KV block. A last
# slot is drawn at random.
RAGGED_SLOTS = [(0, 0), (40, 0), (47, 1), (127, 1), (150, 1), (160, 32), (120, 25)]
PAGE, N_PAGES, CHUNK = 16, 12, 32


def ragged_case(rng, h, hkv, hd, nblk, dtype):
    """q, pools, table and per-slot (q_offset, kv_valid_len) of the ragged
    layout; table entries past a slot's frontier are the sentinel."""
    qoff = [o for o, _ in RAGGED_SLOTS] + [int(rng.integers(0, PAGE * N_PAGES - CHUNK + 1))]
    vl = [o + n for o, n in RAGGED_SLOTS] + [qoff[-1] + int(rng.integers(1, CHUNK + 1))]
    b = len(qoff)
    q = jnp.asarray(rng.normal(size=(b, CHUNK, h, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(nblk, PAGE, hkv, hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(nblk, PAGE, hkv, hd)), dtype)
    table = rng.integers(0, nblk, size=(b, N_PAGES))
    for i, v in enumerate(vl):
        table[i, -(-v // PAGE):] = nblk
    return (q, kp, vp, jnp.asarray(table, jnp.int32), jnp.asarray(qoff, jnp.int32),
            jnp.asarray(vl, jnp.int32))


def assert_pad_rows_zero(out, qoff, vl):
    """Every row is finite; rows of queries ``i >= q_len`` (all of an idle
    or stalled slot's) are zero."""
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    pad = np.arange(out.shape[1])[None, :] >= (np.asarray(vl) - np.asarray(qoff))[:, None]
    assert not out[pad].any()


@pytest.mark.parametrize("g", [1, 2, 4, 6])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_prefill_kernel_matches_ref(g, dtype):
    rng = np.random.default_rng(7 + g)
    q, kp, vp, table, qoff, vl = ragged_case(rng, g * 2, 2, 16, 40, dtype)
    want = ref.paged_prefill_attention_ref(q, kp, vp, table, qoff, vl)
    got = paged_prefill_attention_pallas(
        q, kp, vp, table, qoff, vl, interpret=True
    )
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )
    assert_pad_rows_zero(got, qoff, vl)


def test_paged_prefill_kernel_shared_and_sentinel_pages():
    """Two slots routing through the SAME physical blocks must read the
    same values; sentinel (unallocated) entries clamp and stay masked
    behind the valid length."""
    rng = np.random.default_rng(11)
    b, c, h, hkv, hd, nblk, page, npages = 2, 6, 4, 2, 8, 9, 4, 4
    q = jnp.asarray(rng.normal(size=(b, c, h, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nblk, page, hkv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nblk, page, hkv, hd)), jnp.float32)
    # slot 1 shares slot 0's first two pages; tails diverge, last page of
    # slot 0 is the out-of-range sentinel (never reached: vl stops before)
    table = jnp.asarray([[3, 5, 1, nblk], [3, 5, 7, 2]], jnp.int32)
    qoff = jnp.asarray([8, 6], jnp.int32)
    vl = jnp.asarray([12, 12], jnp.int32)
    want = ref.paged_prefill_attention_ref(q, kp, vp, table, qoff, vl)
    got = paged_prefill_attention_pallas(
        q, kp, vp, table, qoff, vl, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_mixed_roles_one_call():
    """A decode slot is the degenerate chunk q_len = 1: its single real
    row must equal the decode-attention oracle over the same pool."""
    rng = np.random.default_rng(3)
    b, c, h, hkv, hd, nblk, page, npages = 2, 4, 4, 2, 8, 8, 4, 4
    q = jnp.asarray(rng.normal(size=(b, c, h, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nblk, page, hkv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nblk, page, hkv, hd)), jnp.float32)
    table = jnp.asarray(rng.integers(0, nblk, size=(b, npages)), jnp.int32)
    # slot 0 decodes at position 9 (q_len 1); slot 1 prefills a 4-chunk
    qoff = jnp.asarray([9, 4], jnp.int32)
    vl = jnp.asarray([10, 8], jnp.int32)
    got = paged_prefill_attention_pallas(
        q, kp, vp, table, qoff, vl, interpret=True
    )
    dec = ref.paged_decode_attention_ref(
        q[:1, :1], kp, vp, table[:1], jnp.asarray([10], jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(got[0, 0]), np.asarray(dec[0, 0]), atol=2e-5, rtol=2e-5
    )


def test_prefill_ref_matches_dense_causal_attention():
    """On a contiguous cache whose frontier equals the chunk end, the
    chunked oracle at q_offset=0 IS plain dense causal attention."""
    rng = np.random.default_rng(5)
    b, s, h, hkv, hd = 2, 12, 4, 2, 16

    class _Cfg:  # dense_attention only reads nothing from cfg
        pass

    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
    want = dense_attention(q, k, v, causal=True)
    got = ref.prefill_attention_ref(
        q, k, v, jnp.zeros((b,), jnp.int32), jnp.full((b,), s, jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_vector_q_offset_matches_shifted_scalar():
    """dense_attention's per-slot q_offset must reproduce the scalar
    variant row by row."""
    rng = np.random.default_rng(9)
    b, sq, skv, h, hkv, hd = 3, 4, 16, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(b, sq, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, skv, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, skv, hkv, hd)), jnp.float32)
    offs = jnp.asarray([0, 5, 11], jnp.int32)
    got = dense_attention(q, k, v, causal=True, q_offset=offs)
    for i, o in enumerate([0, 5, 11]):
        want = dense_attention(
            q[i : i + 1], k[i : i + 1], v[i : i + 1], causal=True, q_offset=o
        )
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(want[0]), atol=2e-6, rtol=2e-6
        )
