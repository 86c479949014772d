"""int8-KV attention kernels (DESIGN §15): interpret-mode parity sweeps.

Every quantized kernel must reproduce its dequant-then-attend oracle in
``ref.py``: the dense decode kernel dequantizes per-16-row-group scale
tiles in VMEM, the paged decode/prefill kernels read per-(block, kv-head)
scales from scalar prefetch next to the block table. The sweeps cover GQA
group sizes, per-slot frontiers, shared/sentinel table entries, block
invariance, and the quantize-on-write helpers the serving cache uses
(roundtrip error bound + rebuild determinism — the property that keeps
preemption re-prefill exact).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import (
    decode_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.prefill_attention import paged_prefill_attention_pallas
from repro.models.layers import (
    KV_QUANT_GROUP,
    chunk_cache_update_q,
    dequant_kv_page,
    paged_chunk_cache_update_q,
    quant_kv_page,
)

RNG = np.random.default_rng(31)


def _quant_dense(x, group=KV_QUANT_GROUP):
    """(B, S, KV, hd) fp32 -> int8 codes + (B, S // group, KV) scales."""
    b, s, kv, hd = x.shape
    codes, scales = quant_kv_page(jnp.asarray(x.reshape(b, s // group, group, kv, hd)))
    return codes.reshape(b, s, kv, hd), scales


def _dense_case(b, skv, h, hkv, hd):
    q = jnp.asarray(RNG.normal(size=(b, 1, h, hd)), jnp.float32)
    k = RNG.normal(size=(b, skv, hkv, hd)).astype(np.float32)
    v = RNG.normal(size=(b, skv, hkv, hd)).astype(np.float32)
    kc, ks = _quant_dense(k)
    vc, vs = _quant_dense(v)
    vl = jnp.asarray(RNG.integers(1, skv + 1, size=(b,)), jnp.int32)
    return q, kc, vc, ks, vs, vl


def _paged_case(b, nblk, page, npages, h, hkv, hd):
    q = jnp.asarray(RNG.normal(size=(b, 1, h, hd)), jnp.float32)
    kp = RNG.normal(size=(nblk, page, hkv, hd)).astype(np.float32)
    vp = RNG.normal(size=(nblk, page, hkv, hd)).astype(np.float32)
    kc, ks = quant_kv_page(jnp.asarray(kp))
    vc, vs = quant_kv_page(jnp.asarray(vp))
    table = np.asarray(
        RNG.permutation(nblk)[: b * npages].reshape(b, npages)
    )
    table[:, 0] = table[0, 0]  # shared prefix block across slots
    vl = RNG.integers(1, npages * page + 1, size=(b,)).astype(np.int32)
    for i in range(b):  # unallocated tail pages carry the OOB sentinel
        table[i, -(-int(vl[i]) // page):] = nblk
    return q, kc, vc, ks, vs, jnp.asarray(table, jnp.int32), jnp.asarray(vl)


# --------------------------------------------------------- quantize helpers


def test_quant_roundtrip_error_bound():
    """Symmetric absmax at 8 bits: roundtrip error <= absmax / 254 per
    (group, kv-head), zeros exact."""
    x = jnp.asarray(RNG.normal(size=(5, 16, 2, 32)), jnp.float32)
    codes, scales = quant_kv_page(x)
    back = dequant_kv_page(codes, scales)
    absmax = jnp.max(jnp.abs(x), axis=(-3, -1), keepdims=True)
    assert float(jnp.max(jnp.abs(back - x) / absmax)) <= 1 / 254 + 1e-6
    z, zs = quant_kv_page(jnp.zeros((2, 16, 2, 8)))
    assert not np.asarray(z).any()
    np.testing.assert_array_equal(np.asarray(dequant_kv_page(z, zs)), 0.0)


def test_chunk_write_rebuild_deterministic():
    """Writing the same chunk sequence into a fresh int8 cache twice
    yields bit-identical codes AND scales — the quantize-on-write
    determinism that makes preemption re-prefill exact (DESIGN §15)."""
    b, s, kv, hd, g = 2, 64, 2, 16, KV_QUANT_GROUP
    data = jnp.zeros((b, s, kv, hd), jnp.int8)
    scale = jnp.zeros((b, s // g, kv), jnp.float32)
    chunks = [
        jnp.asarray(RNG.normal(size=(b, 24, kv, hd)), jnp.float32),
        jnp.asarray(RNG.normal(size=(b, 24, kv, hd)), jnp.float32),
    ]
    qoff = jnp.asarray([0, 3], jnp.int32)
    qlen = jnp.asarray([24, 21], jnp.int32)

    def replay():
        d, sc = data, scale
        off = qoff
        for ch in chunks:
            d, sc = chunk_cache_update_q(d, sc, ch, off, qlen)
            off = off + qlen
        return d, sc

    d1, s1 = replay()
    d2, s2 = replay()
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    # and the frontier region dequantizes to ~the written values
    back = ref.dequant_dense_kv(d1, s1)
    want = jnp.concatenate(chunks, axis=1)
    err = jnp.abs(back[0, :48] - want[0])
    assert float(jnp.max(err)) < 0.05


def test_chunk_write_excludes_stale_rows_from_scale():
    """Rows at/past the frontier are zeroed before the per-group absmax
    recompute: a huge stale value left by a prior owner must not inflate
    the fresh writer's scale."""
    b, s, kv, hd, g = 1, 32, 1, 8, KV_QUANT_GROUP
    stale = jnp.full((b, s, kv, hd), 100.0)
    codes, scales = _quant_dense(np.asarray(stale))
    new = jnp.asarray(RNG.normal(size=(b, 8, kv, hd)), jnp.float32)
    d, sc = chunk_cache_update_q(
        codes, scales, new, jnp.zeros((b,), jnp.int32),
        jnp.full((b,), 8, jnp.int32),
    )
    # first group's scale reflects only the 8 fresh rows, not the 100s
    assert float(sc[0, 0, 0]) <= float(jnp.max(jnp.abs(new))) / 127 + 1e-6
    back = ref.dequant_dense_kv(d, sc)
    assert float(jnp.max(jnp.abs(back[0, :8] - new[0]))) < 0.05


def test_paged_chunk_write_respects_sentinel():
    """Sentinel write-table entries drop the write: shared prefix pages
    another slot owns keep their exact codes and scales."""
    nblk, page, kv, hd = 4, 16, 2, 8
    pool = jnp.asarray(RNG.normal(size=(nblk, page, kv, hd)), jnp.float32)
    codes, scales = quant_kv_page(pool)
    new = jnp.asarray(RNG.normal(size=(1, 16, kv, hd)), jnp.float32)
    wtable = jnp.asarray([[nblk, nblk]], jnp.int32)  # owns nothing
    d, sc = paged_chunk_cache_update_q(
        codes, scales, new, wtable,
        jnp.zeros((1,), jnp.int32), jnp.full((1,), 16, jnp.int32),
    )
    np.testing.assert_array_equal(np.asarray(d), np.asarray(codes))
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(scales))


# ------------------------------------------------------------ kernel sweeps

DENSE_CASES = [
    # (B, Smax, H, Hkv, hd) — Smax always whole 16-row groups
    (2, 64, 1, 1, 16),
    (2, 64, 4, 1, 16),
    (1, 128, 4, 2, 32),
    (3, 96, 4, 4, 64),
]


@pytest.mark.parametrize("case", DENSE_CASES)
def test_decode_kernel_q_matches_ref(case):
    q, kc, vc, ks, vs, vl = _dense_case(*case)
    want = ref.decode_attention_q_ref(q, kc, vc, ks, vs, vl)
    got = decode_attention_pallas(
        q, kc, vc, vl, k_scale=ks, v_scale=vs, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize("block_s", [32, 64, 128])
def test_decode_kernel_q_block_invariance(block_s):
    q, kc, vc, ks, vs, vl = _dense_case(2, 128, 4, 2, 32)
    base = decode_attention_pallas(
        q, kc, vc, vl, k_scale=ks, v_scale=vs, block_s=128, interpret=True
    )
    got = decode_attention_pallas(
        q, kc, vc, vl, k_scale=ks, v_scale=vs, block_s=block_s, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(base), atol=1e-5, rtol=1e-5
    )


def test_decode_kernel_q_rejects_ragged_scales():
    q, kc, vc, ks, vs, vl = _dense_case(2, 64, 4, 2, 16)
    with pytest.raises(ValueError):
        decode_attention_pallas(
            q, kc, vc, vl, k_scale=ks[:, :-1], v_scale=vs[:, :-1],
            interpret=True,
        )


PAGED_CASES = [
    # (B, nblk, page, npages, H, Hkv, hd)
    (1, 6, 16, 2, 1, 1, 16),
    (2, 10, 16, 4, 4, 1, 16),
    (3, 12, 8, 4, 4, 4, 32),
    (2, 8, 16, 3, 4, 2, 64),
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_decode_kernel_q_matches_ref(case):
    q, kc, vc, ks, vs, table, vl = _paged_case(*case)
    want = ref.paged_decode_attention_q_ref(q, kc, vc, ks, vs, table, vl)
    got = paged_decode_attention_pallas(
        q, kc, vc, table, vl, k_scale=ks, v_scale=vs, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
    )


# (q_offset, q_len) of a ragged mixed step, 32-query chunks in 12 pages of
# 16: idle, stalled, decode on a page boundary, on a KV-block boundary
# and mid-block, a chunk to the table's last page, q-tiles ending inside
# a KV block
RAGGED_SLOTS = [(0, 0), (40, 0), (47, 1), (127, 1), (150, 1), (160, 32), (120, 25)]


@pytest.mark.parametrize(
    "case", PAGED_CASES[:2] + [(len(RAGGED_SLOTS), 96, 16, 12, 12, 2, 16)]
)
def test_paged_prefill_kernel_q_matches_ref(case):
    """Random chunks, and (at G = 6) the ragged layout, whose pad rows
    and idle slots must come out as finite zeros."""
    b, nblk, page, npages, h, hkv, hd = case
    _, kc, vc, ks, vs, table, _ = _paged_case(*case)
    ragged = b == len(RAGGED_SLOTS)
    c = 32 if ragged else 8
    q = jnp.asarray(RNG.normal(size=(b, c, h, hd)), jnp.float32)
    if ragged:
        qoff = jnp.asarray([o for o, _ in RAGGED_SLOTS], jnp.int32)
        vl = qoff + jnp.asarray([n for _, n in RAGGED_SLOTS], jnp.int32)
        table = np.array(table)
        for i, v in enumerate(np.asarray(vl)):
            table[i, -(-int(v) // page):] = nblk
        table = jnp.asarray(table)
    else:
        qoff = jnp.asarray(
            RNG.integers(0, page * npages - c + 1, size=(b,)), jnp.int32
        )
        vl = qoff + jnp.asarray(RNG.integers(1, c + 1, size=(b,)), jnp.int32)
    want = ref.paged_prefill_attention_q_ref(
        q, kc, vc, ks, vs, table, qoff, vl
    )
    got = paged_prefill_attention_pallas(
        q, kc, vc, table, qoff, vl, k_scale=ks, v_scale=vs, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
    )
    out = np.asarray(got)
    assert np.isfinite(out).all()
    pad = np.arange(c)[None, :] >= np.asarray(vl - qoff)[:, None]
    assert not out[pad].any()


def test_quantized_attention_close_to_fp32():
    """End-to-end accuracy: int8-cache attention tracks the fp32-cache
    answer within the drift budget DESIGN §15 documents (unit-normal
    values, absmax grouping → output drift well under 1e-1)."""
    b, skv, h, hkv, hd = 2, 128, 4, 2, 32
    q = jnp.asarray(RNG.normal(size=(b, 1, h, hd)), jnp.float32)
    k = RNG.normal(size=(b, skv, hkv, hd)).astype(np.float32)
    v = RNG.normal(size=(b, skv, hkv, hd)).astype(np.float32)
    kc, ks = _quant_dense(k)
    vc, vs = _quant_dense(v)
    vl = jnp.asarray([67, 128], jnp.int32)
    exact = ref.decode_attention_ref(q, jnp.asarray(k), jnp.asarray(v), vl)
    quant = ref.decode_attention_q_ref(q, kc, vc, ks, vs, vl)
    assert float(jnp.max(jnp.abs(exact - quant))) < 0.05
