"""Operations a dense decoder step needs, counted from shapes.

Only the work the algorithm needs for real tokens counts: the matrix
products of the linears and the tied head, the bypass, and causal
attention over each token's actual context. Padded rows, recomputation
(rematerialisation) and the frozen weights' gradients never count, so a
utilisation built on these counts stays under 100% whatever the program
executes.
"""

from __future__ import annotations

from bench.weights import dims, linear_shapes


def layer_matmul_params(cfg: dict) -> int:
    return sum(din * dout for din, dout in linear_shapes(cfg).values())


def layer_bypass_outputs(cfg: dict, k: int) -> int:
    """Bypass values of one layer: k per output neuron of every linear."""
    return k * sum(dout for _, dout in linear_shapes(cfg).values())


def total_params(cfg: dict) -> int:
    """Every parameter: linears, q/k/v biases, norms, and the embedding
    (which is also the tied head)."""
    d = dims(cfg)
    q, kv = d["H"] * d["hd"], d["KV"] * d["hd"]
    per_layer = layer_matmul_params(cfg) + q + 2 * kv + 2 * d["D"]
    return d["L"] * per_layer + d["V"] * d["D"] + d["D"]


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's linears and the (tied) head."""
    d = dims(cfg)
    return d["L"] * layer_matmul_params(cfg) + d["V"] * d["D"]


def attention_forward(cfg: dict, context: int) -> int:
    """Causal attention of one token that sees ``context`` positions, all
    layers: q·k and p·v, 2 operations a multiply-add each."""
    d = dims(cfg)
    return 4 * d["L"] * d["H"] * d["hd"] * context


def train_step(cfg: dict, batch: int, seq: int, k: int) -> int:
    """One NeuroAda training step on (batch, seq) packed tokens with a
    frozen base: forward 2·P per token, the input gradient 2·P per token,
    no weight gradient; the bypass forward, its value gradient and its
    input gradient; attention forward and its backward for activations
    (twice the forward). The head runs at the positions with a target."""
    d = dims(cfg)
    t = batch * seq
    layers = 4 * t * d["L"] * layer_matmul_params(cfg)
    head = 4 * batch * (seq - 1) * d["V"] * d["D"]
    bypass = 6 * t * d["L"] * layer_bypass_outputs(cfg, k)
    attn = 3 * batch * sum(attention_forward(cfg, c) for c in range(1, seq + 1))
    return layers + head + bypass + attn


def serve_token(cfg: dict, position: int, head: bool) -> int:
    """Forward of one token at ``position`` (it sees position + 1 keys); the
    head only where a token is sampled. A merged delta costs nothing."""
    d = dims(cfg)
    ops = 2 * d["L"] * layer_matmul_params(cfg) + attention_forward(cfg, position + 1)
    return ops + (2 * d["V"] * d["D"] if head else 0)


def serve_positions(cfg: dict, start: int, stop: int, heads: int) -> int:
    """Forward of the positions start .. stop-1 of one sequence, with the
    head at ``heads`` of them."""
    d = dims(cfg)
    n = stop - start
    attn = 4 * d["L"] * d["H"] * d["hd"] * (stop * (stop + 1) - start * (start + 1)) // 2
    return 2 * n * d["L"] * layer_matmul_params(cfg) + attn + heads * 2 * d["V"] * d["D"]
