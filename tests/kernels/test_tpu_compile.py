"""Compile every kernel of the Pallas dispatch for a TPU v5e, here, without
the chip.

The TPU compiler ships with the installed JAX and compiles for a described
``v5e:2x2`` topology: Mosaic then refuses what the interpret-mode sweeps
cannot see (unaligned blocks, gathers it has no lowering for, VMEM
overruns). Each case lowers the ``ops`` wrapper under the ``pallas``
backend at qwen2-1.5b widths (d 1536, 12/2 heads of 128, d_ff 8960, bf16)
and the shapes the training and serving entry points give it: batch 4 ×
seq 512 for training, 8 slots × max_len 2048 in 16-token pages with a
256-token prefill chunk and three adapter stacks for serving.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and a test run with several
workers must collect the same tests in each.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import ops
from repro.quant.qtensor import QuantizedTensor

D, F, H, HKV, HD = 1536, 8960, 12, 2, 128
BF16 = jnp.bfloat16
TRAIN_M = 4 * 512
SLOTS, MAX_LEN, PAGE, CHUNK, TENANTS = 8, 2048, 16, 256, 3
N_PAGES = MAX_LEN // PAGE
N_BLOCKS = SLOTS * N_PAGES
# (d_in, d_out) of the qwen2-1.5b projections: wq/wo, wk/wv, gate/up, down
LINEARS = [(D, D), (D, HKV * HD), (D, F), (F, D)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep this file's compiles out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *specs, kernels=1):
    """Lower ``fn`` under the Pallas backend and compile it for the chip;
    asserts the program holds ``kernels`` Pallas calls and returns its
    optimized HLO text."""
    with ops.use_backend("pallas"):
        compiled = jax.jit(fn).lower(*specs).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    return text


def _sds(sharding):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return make


@pytest.mark.parametrize("d_in,d_out", LINEARS)
def test_fused_linear_train_step(one_chip, d_in, d_out):
    """NeuroAda training linear (k=1): fused forward plus the dval kernel
    of the custom VJP."""
    s = _sds(one_chip)

    def loss(x, w, idx, val, b):
        y = ops.fused_linear(x, w, idx, val, b, w_frozen=True)
        return jnp.sum(y.astype(jnp.float32))

    _compile(
        jax.value_and_grad(loss, argnums=(0, 3)),
        s((4, 512, d_in), BF16), s((d_in, d_out), BF16),
        s((1, d_out), jnp.int32), s((1, d_out), BF16), s((d_out,), BF16),
        kernels=2,
    )


def test_delta_apply_train_step(one_chip):
    s = _sds(one_chip)

    def loss(x, idx, val):
        return jnp.sum(ops.delta_apply(x, idx, val).astype(jnp.float32))

    _compile(
        jax.value_and_grad(loss, argnums=(0, 2)),
        s((TRAIN_M, D), BF16), s((1, D), jnp.int32), s((1, D), BF16),
        kernels=2,
    )


@pytest.mark.parametrize(
    "rows,d_in,d_out",
    [(1, di, do) for di, do in LINEARS] + [(CHUNK, D, F)],
    ids=lambda v: str(v),
)
def test_delta_apply_batched_serve(one_chip, rows, d_in, d_out):
    """Multi-tenant bypass on the decode (1 row per slot) and mixed
    prefill (a 256-token chunk per slot) shapes."""
    s = _sds(one_chip)
    _compile(
        ops.delta_apply_batched,
        s((SLOTS, rows, d_in), BF16), s((TENANTS, 1, d_out), jnp.int32),
        s((TENANTS, 1, d_out), BF16), s((SLOTS,), jnp.int32),
    )


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_attention(one_chip, kv_dtype):
    s = _sds(one_chip)
    pool_dt = BF16 if kv_dtype == "bf16" else jnp.int8
    args = [
        s((SLOTS, 1, H, HD), BF16),
        s((N_BLOCKS, PAGE, HKV, HD), pool_dt), s((N_BLOCKS, PAGE, HKV, HD), pool_dt),
        s((SLOTS, N_PAGES), jnp.int32), s((SLOTS,), jnp.int32),
    ]
    if kv_dtype == "int8":
        args += [s((N_BLOCKS, HKV), jnp.float32)] * 2
    _compile(ops.paged_decode_attention, *args)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_prefill_attention(one_chip, kv_dtype):
    s = _sds(one_chip)
    pool_dt = BF16 if kv_dtype == "bf16" else jnp.int8
    args = [
        s((SLOTS, CHUNK, H, HD), BF16),
        s((N_BLOCKS, PAGE, HKV, HD), pool_dt), s((N_BLOCKS, PAGE, HKV, HD), pool_dt),
        s((SLOTS, N_PAGES), jnp.int32), s((SLOTS,), jnp.int32), s((SLOTS,), jnp.int32),
    ]
    if kv_dtype == "int8":
        args += [s((N_BLOCKS, HKV), jnp.float32)] * 2
    _compile(ops.prefill_attention, *args)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_dense_decode_attention(one_chip, kv_dtype):
    s = _sds(one_chip)
    kv_dt = BF16 if kv_dtype == "bf16" else jnp.int8
    args = [
        s((SLOTS, 1, H, HD), BF16),
        s((SLOTS, MAX_LEN, HKV, HD), kv_dt), s((SLOTS, MAX_LEN, HKV, HD), kv_dt),
        s((SLOTS,), jnp.int32),
    ]
    if kv_dtype == "int8":
        args += [s((SLOTS, MAX_LEN // 16, HKV), jnp.float32)] * 2
    _compile(ops.decode_attention, *args)


def _qtensor_specs(s, d_in, d_out, qdtype, block=64):
    rows = d_in // 2 if qdtype == "nf4" else d_in
    dt = jnp.uint8 if qdtype == "nf4" else jnp.int8
    return s((rows, d_out), dt), s((d_in // block, d_out), jnp.float32)


@pytest.mark.parametrize("qdtype", ["int8", "nf4"])
def test_fused_linear_q_train_step(one_chip, qdtype):
    """Quantized frozen base under NeuroAda training: fused dequant
    forward plus the dval kernel."""
    s = _sds(one_chip)
    data, scales = _qtensor_specs(s, D, F, qdtype)

    def loss(x, data, scales, idx, val):
        qw = QuantizedTensor(data, scales, qdtype, 64, "bfloat16")
        return jnp.sum(ops.fused_linear_q(x, qw, idx, val).astype(jnp.float32))

    _compile(
        jax.value_and_grad(loss, argnums=(0, 4)),
        s((TRAIN_M, D), BF16), data, scales,
        s((1, F), jnp.int32), s((1, F), BF16),
        kernels=2,
    )


@pytest.mark.parametrize("qdtype", ["int8", "nf4"])
def test_matmul_q_serve(one_chip, qdtype):
    """Quantized base matmul of multi-tenant serving (decode rows)."""
    s = _sds(one_chip)
    data, scales = _qtensor_specs(s, F, D, qdtype)

    def fn(x, data, scales):
        return ops.matmul_q(x, QuantizedTensor(data, scales, qdtype, 64, "bfloat16"))

    _compile(fn, s((SLOTS, 1, F), BF16), data, scales)


@pytest.mark.parametrize("d_in,d_out", [(D, F), (F, D)])
def test_topk_select(one_chip, d_in, d_out):
    """Phase-1 top-1 selection over a projection's magnitudes."""
    s = _sds(one_chip)
    _compile(lambda w: ops.topk_select(w, 1), s((d_in, d_out), BF16))


def test_paged_decode_attention_tp2(topo):
    """The tensor-parallel dispatch on a two-chip ``model`` axis: each
    shard runs the kernel on its one kv head."""
    from repro.distributed import context as tp_ctx

    mesh = jax.sharding.Mesh(np.array(topo.devices[:2]), ("model",))
    heads = NamedSharding(mesh, P(None, None, "model", None))
    rep = NamedSharding(mesh, P())

    def sds(shape, dtype, sh):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    tp_ctx.set_serve_mesh(mesh)
    try:
        text = _compile(
            ops.paged_decode_attention,
            sds((SLOTS, 1, H, HD), BF16, heads),
            sds((N_BLOCKS, PAGE, HKV, HD), BF16, heads),
            sds((N_BLOCKS, PAGE, HKV, HD), BF16, heads),
            sds((SLOTS, N_PAGES), jnp.int32, rep), sds((SLOTS,), jnp.int32, rep),
        )
    finally:
        tp_ctx.clear_serve_mesh()
    assert "all-gather" not in text


@pytest.mark.parametrize("tp,kernels", [(1, 8), (2, 1)], ids=["tp1", "tp2"])
def test_mixed_serving_step(topo, tp, kernels):
    """The whole multi-tenant mixed prefill step at full depth: on one chip
    with the seven batched-delta kernels and paged prefill attention in the
    layer scan, and tensor-parallel over the ``model`` axis of a 2x2 mesh.
    Mosaic kernels cannot be partitioned automatically, so under TP each
    one must sit in a shard_map (interpret mode on virtual CPU devices
    cannot show this); there only the attention kernel remains."""
    from jax.sharding import AxisType, Mesh

    from repro.configs import get_config
    from repro.core.adapt import init_adapters
    from repro.core.delta import BatchedDelta
    from repro.distributed import context as dist_ctx
    from repro.distributed.sharding import (
        adapter_shardings,
        cache_shardings,
        param_shardings,
    )
    from repro.models import get_model

    devices = np.array(topo.devices[:4 if tp > 1 else 1]).reshape(-1, tp)
    mesh = Mesh(devices, ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    model = get_model(get_config("qwen2-1.5b"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    idx1 = jax.eval_shape(lambda p: init_adapters(p, 1)[0], params)

    def stacked(dtype):  # base + tenants, as AdapterStore.stacked lays out
        return {
            key: jax.tree.map(
                lambda x, ax=(1 if key == "blocks" else 0): None if x is None
                else jax.ShapeDtypeStruct(
                    x.shape[:ax] + (TENANTS,) + x.shape[ax:], dtype or x.dtype
                ),
                sub, is_leaf=lambda x: x is None,
            )
            for key, sub in idx1.items()
        }

    idx, val = stacked(None), stacked(BF16)
    cache = jax.eval_shape(lambda: model.init_paged_cache(N_BLOCKS, PAGE))
    rep = NamedSharding(mesh, P())

    def placed(tree, shardings):
        return jax.tree.map(
            lambda x, sh: None if x is None
            else jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            tree, shardings, is_leaf=lambda x: x is None,
        )

    def step(params, idx, val, cache, aid, table, tokens, q_offset, q_len):
        aid_l = jnp.broadcast_to(aid[None], (model.cfg.num_layers, SLOTS))
        adapters = {
            key: jax.tree.map(
                lambda i, v, a=(aid_l if key == "blocks" else aid): None
                if i is None else BatchedDelta(i, v, a),
                idx[key], val[key], is_leaf=lambda x: x is None,
            )
            for key in idx
        }
        return model.prefill_chunk(params, adapters, cache, {
            "tokens": tokens, "q_offset": q_offset, "q_len": q_len,
            "last_idx": q_len - 1, "block_table": table, "write_table": table,
        })

    def vec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    snap = dist_ctx.snapshot()
    if tp > 1:  # the scope ServeEngine._sharded_call sets
        dist_ctx.set_serve_mesh(mesh)
        dist_ctx.set_activation_sharding(None, "model", seq_div=tp, variant="inner_all")
    try:
        with mesh:
            text = _compile(
                step,
                placed(params, param_shardings(params, mesh, "dense", fsdp=False)),
                placed(idx, adapter_shardings(params, idx, mesh, "dense", fsdp=False)),
                placed(val, adapter_shardings(params, val, mesh, "dense", fsdp=False)),
                placed(cache, cache_shardings(cache, mesh)),
                vec((SLOTS,)), vec((SLOTS, N_PAGES)), vec((SLOTS, CHUNK)),
                vec((SLOTS,)), vec((SLOTS,)),
                kernels=kernels,
            )
    finally:
        dist_ctx.restore(snap)
    assert ("all-reduce" in text) == (tp > 1)  # the row-parallel merges
