"""The benchmark's own inputs of a model: weights, the NeuroAda selection
and a tenant's delta, made on the device from ``--seed`` in one jitted
call each.

The program receives these arrays; the plain reference reads the same
arrays, never anything the program made from them. The parameter tree has
the layout of the program's dense decoder (``repro.models`` init), which
``check_layout`` compares against the program's own ``eval_shape``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the adapted matrices of a dense decoder block: every ``…/w`` linear
# (embeddings are excluded, and the tied head is the embedding)
LINEARS = ("wq", "wk", "wv", "wo", "wgate", "wup", "wdown")


def key_of(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number, 64-bit seeds included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def dims(cfg: dict) -> dict:
    """Sizes of a Qwen2-family ``config.json`` under short names."""
    h = cfg["num_attention_heads"]
    return {
        "L": cfg["num_hidden_layers"], "D": cfg["hidden_size"], "H": h,
        "KV": cfg["num_key_value_heads"], "hd": cfg["hidden_size"] // h,
        "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"],
    }


def linear_shapes(cfg: dict) -> dict[str, tuple[int, int]]:
    """(d_in, d_out) of each adapted linear of one layer."""
    d = dims(cfg)
    q, kv = d["H"] * d["hd"], d["KV"] * d["hd"]
    return {"wq": (d["D"], q), "wk": (d["D"], kv), "wv": (d["D"], kv),
            "wo": (q, d["D"]), "wgate": (d["D"], d["F"]),
            "wup": (d["D"], d["F"]), "wdown": (d["F"], d["D"])}


def _params(cfg: dict, init: dict, key):
    d = dims(cfg)
    L, D = d["L"], d["D"]
    bf = jnp.bfloat16
    keys = iter(jax.random.split(key, 32))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(bf)

    def norm(shape):
        lo, hi = init["norm_range"]
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi).astype(bf)

    blocks = {"attn_norm": norm((L, D)), "mlp_norm": norm((L, D))}
    for name, (din, dout) in linear_shapes(cfg).items():
        leaf = {"w": normal((L, din, dout), din ** -0.5)}
        if name in ("wq", "wk", "wv"):
            leaf["b"] = normal((L, dout), init["bias_std"])
        blocks[name] = leaf
    return {
        "embed": {"w": normal((d["V"], D), init["embed_std"])},
        "blocks": blocks,
        "final_norm": norm((D,)),
    }


def make_params(cfg: dict, init: dict, seed: int):
    """Seeded bf16 weights on the default device, in one jitted call."""
    return jax.jit(lambda k: _params(cfg, init, k))(key_of(seed))


def check_layout(params, program_shapes) -> None:
    """Refuse weights whose tree, shapes or dtypes differ from what the
    program's own init would build."""
    ours = jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)), params)
    theirs = jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)), program_shapes)
    if ours != theirs:
        raise SystemExit(f"bench: weight layout differs from the program's:\n"
                         f"  bench   {ours}\n  program {theirs}")


@jax.jit
def select_top1(blocks):
    """NeuroAda phase 1 with k = 1 by magnitude: per output neuron, the input
    index of the largest |w| (ties to the lower index). (L, 1, d_out) int32
    per adapted linear."""
    return {n: jnp.argmax(jnp.abs(blocks[n]["w"].astype(jnp.float32)), axis=-2)[:, None, :]
            .astype(jnp.int32) for n in LINEARS}


def tenant_values(cfg: dict, std: float, seed: int, tenant: int | None = None):
    """A tenant's seeded bypass values, bf16, (L, 1, d_out) per linear: the
    cell's one tenant, or tenant ``tenant`` of many."""
    L = dims(cfg)["L"]

    def build(key):
        keys = jax.random.split(key, len(LINEARS))
        return {n: (std * jax.random.normal(k, (L, 1, dout), jnp.float32)).astype(jnp.bfloat16)
                for k, (n, (_, dout)) in zip(keys, linear_shapes(cfg).items())}

    key = jax.random.fold_in(key_of(seed), 7)
    if tenant is not None:
        key = jax.random.fold_in(key, tenant)
    return jax.jit(build)(key)


def program_tree(params, leaves: dict):
    """``leaves`` (one array per adapted linear) as an adapter tree aligned
    with the program's parameter tree: None everywhere else."""
    tree = jax.tree.map(lambda _: None, params)
    tree["blocks"] = {k: ({"w": leaves[k], **({"b": None} if "b" in v else {})}
                          if isinstance(v, dict) else None)
                      for k, v in params["blocks"].items()}
    return tree
