"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes the cell's inputs from ``--seed``.

Every seed gets the same sizes and arrivals in the same order: lengths
and gaps between arrivals come from a fixed grid of quantiles of the
mix's distribution, ``block`` of them at a time, each block shuffled by a
stream that no seed changes; the seed draws the token ids. So every run
does the same work on other tokens, and a closed loop, whose steps follow
the requests' lengths and not the clock, runs the same sequence of steps
whatever its seed.

Two kinds of mix:

- ``train``: ``batches`` batches of ``batch`` rows of ``seq`` tokens,
  documents of ``doc_length`` packed back to back, ``separator`` between
  them, tokens Zipf(``tokens.s``) over the ids above ``tokens.reserved``.
- ``serve``: an endless stream of requests, each a prompt of ``prompt``
  length and a ``max_new`` of ``output`` length. Optional keys:
  ``shared_prefix`` (``length``, ``count``): every prompt starts with one
  of ``count`` prefixes, in turn; ``tenants`` (``count``, ``s``): each
  request names a tenant 1 .. count drawn Zipf(s); ``arrivals``: a
  ``closed`` loop of ``clients`` (the default), or open-loop ``poisson``
  or ``gamma`` (coefficient of variation ``cv``) arrivals at ``rate``
  requests per second.

Length distributions (``dist``): ``lognormal`` (``median``, ``sigma``),
``uniform`` and ``fixed`` (``value``), each clipped to [``min``, ``max``].
Token distributions: ``uniform`` and ``zipf`` (``s``).
"""

from __future__ import annotations

import math
import statistics
from typing import NamedTuple

import numpy as np

# the stream that orders the lengths, the same for every seed
ORDER = 0


class Request(NamedTuple):
    prompt: list
    max_new: int
    tenant: int  # 0: the cell's one adapter (or the base)
    arrival: float | None  # seconds after the arrival clock starts; None in a closed loop


def rng_of(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def _quantiles(n: int) -> list[float]:
    return [(i + 0.5) / n for i in range(n)]


def grid(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``dist``, clipped to
    [min, max]."""
    kind = dist["dist"]
    if kind == "lognormal":
        nd = statistics.NormalDist()
        mu = math.log(dist["median"])
        out = [math.exp(mu + dist["sigma"] * nd.inv_cdf(q)) for q in _quantiles(n)]
    elif kind == "uniform":
        out = [dist["min"] + q * (dist["max"] - dist["min"]) for q in _quantiles(n)]
    elif kind == "fixed":
        out = [dist["value"]] * n
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(out), dist["min"], dist["max"]).astype(np.int64)


def gap_grid(arrivals: dict, n: int) -> np.ndarray:
    """``n`` gaps between arrivals at the quantiles of a gamma distribution
    with mean 1 / rate and the given coefficient of variation (poisson:
    1, exponential gaps)."""
    from scipy.stats import gamma

    cv = 1.0 if arrivals["process"] == "poisson" else arrivals["cv"]
    shape = 1.0 / cv ** 2
    return gamma.ppf(_quantiles(n), shape, scale=1.0 / (arrivals["rate"] * shape))


def _zipf_cdf(n: int, s: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** -s
    return np.cumsum(p / p.sum())


class Tokens:
    """Token ids above ``reserved``, drawn from ``rng``."""

    def __init__(self, spec: dict, vocab: int, rng: np.random.Generator):
        self.rng, self.vocab, self.reserved = rng, vocab, spec["reserved"]
        self.cdf = None
        if spec["dist"] == "zipf":
            self.cdf = _zipf_cdf(vocab - self.reserved, spec["s"])
        elif spec["dist"] != "uniform":
            raise ValueError(f"unknown token distribution {spec['dist']!r}")

    def __call__(self, n: int) -> np.ndarray:
        if self.cdf is None:
            return self.rng.integers(self.reserved, self.vocab, size=n)
        ids = np.searchsorted(self.cdf, self.rng.random(n)) + self.reserved
        return np.minimum(ids, self.vocab - 1)


def train_batches(mix: dict, vocab: int, seed: int) -> list[dict]:
    """The training cell's batches: {"tokens", "targets"} (batch, seq) int32,
    loss on every position (targets are the tokens; the loss shifts them)."""
    order = rng_of(ORDER, 10)
    tokens = Tokens(mix["tokens"], vocab, rng_of(seed, 0))
    b, s = mix["batch"], mix["seq"]
    lengths = grid(mix["doc_length"], mix["block"])
    out, pending = [], []
    for _ in range(mix["batches"]):
        rows = np.empty((b, s), np.int64)
        for r in range(b):
            filled = 0
            while filled < s:
                if not pending:
                    pending = list(order.permutation(lengths))
                n = min(int(pending.pop()), s - filled)
                rows[r, filled: filled + n] = tokens(n)
                filled += n
                if filled < s:
                    rows[r, filled] = mix["separator"]
                    filled += 1
        toks = rows.astype(np.int32)
        out.append({"tokens": toks, "targets": toks.copy()})
    return out


def arrivals_of(mix: dict) -> dict:
    return mix.get("arrivals", {"process": "closed", "clients": 1})


def serve_requests(mix: dict, vocab: int, seed: int):
    """Endless ``Request``s for the serving cell, in the order they are
    handed out (to the clients of a closed loop, or at their arrival
    times)."""
    n = mix["block"]
    order = rng_of(ORDER, 11)
    tokens = Tokens(mix["tokens"], vocab, rng_of(seed, 1))
    prompts, outputs = grid(mix["prompt"], n), grid(mix["output"], n)
    arr = arrivals_of(mix)
    gaps = None if arr["process"] == "closed" else gap_grid(arr, n)
    ten = mix.get("tenants")
    tenant_order = rng_of(ORDER, 13)
    if ten:  # tenant ids at the quantiles of Zipf(s) over 1 .. count
        cdf = _zipf_cdf(ten["count"], ten["s"])
        tenant_ids = np.minimum(np.searchsorted(cdf, _quantiles(n)), ten["count"] - 1) + 1
    pre = mix.get("shared_prefix")
    if pre:
        ptok = Tokens(mix["tokens"], vocab, rng_of(seed, 3))
        prefixes = [ptok(pre["length"]).tolist() for _ in range(pre["count"])]
    gap_order = rng_of(ORDER, 12)
    t, i = 0.0, 0
    while True:
        block_gaps = gap_order.permutation(gaps) if gaps is not None else None
        block_ten = tenant_order.permutation(tenant_ids) if ten else np.zeros(n, np.int64)
        for j, (p, o) in enumerate(zip(order.permutation(prompts), order.permutation(outputs))):
            prompt = tokens(int(p)).tolist()
            if pre:
                prompt = prefixes[i % pre["count"]] + prompt
            arrival = None
            if block_gaps is not None:
                t += float(block_gaps[j])
                arrival = t
            yield Request(prompt, int(o), int(block_ten[j]), arrival)
            i += 1
