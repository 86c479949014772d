from collections import Counter

import numpy as np

from bench import common, traffic

VOCAB = 1000


def _mix(name):
    return common.traffic_file(name)


def test_train_batches_are_a_function_of_the_seed():
    mix = dict(_mix("packed2k"), batches=3, seq=256)
    a = traffic.train_batches(mix, VOCAB, 2**40 + 3)
    b = traffic.train_batches(mix, VOCAB, 2**40 + 3)
    c = traffic.train_batches(mix, VOCAB, 7)
    assert all((x["tokens"] == y["tokens"]).all() for x, y in zip(a, b))
    assert any((x["tokens"] != y["tokens"]).any() for x, y in zip(a, c))


def test_train_batches_pack_documents_with_separators():
    mix = dict(_mix("packed2k"), batches=4)
    out = traffic.train_batches(mix, VOCAB, 11)
    rows = np.concatenate([b["tokens"] for b in out])
    assert rows.shape == (4 * mix["batch"], mix["seq"])
    # documents run back to back, no longer than the longest, one separator between
    for row in rows:
        cuts = np.flatnonzero(row == mix["separator"])
        pieces = np.diff(np.concatenate([[-1], cuts, [len(row)]])) - 1
        assert pieces.max() <= mix["doc_length"]["max"]
    assert (rows == mix["separator"]).sum() >= len(rows)
    body = rows[rows != mix["separator"]]
    assert body.min() >= mix["tokens"]["reserved"] and body.max() < VOCAB
    assert len({r.tobytes() for r in rows}) == len(rows)  # every row differs
    assert all((b["tokens"] == b["targets"]).all() for b in out)


def test_train_tokens_follow_zipf():
    mix = dict(_mix("packed2k"), batches=2)
    toks = np.concatenate([b["tokens"].ravel() for b in traffic.train_batches(mix, VOCAB, 5)])
    toks = toks[toks != mix["separator"]] - mix["tokens"]["reserved"]
    counts = Counter(toks.tolist())
    # Zipf(1.2): rank 1 is 2^1.2 = 2.3 times as frequent as rank 2
    assert 1.9 < counts[0] / counts[1] < 2.7


def test_length_grid_matches_its_distribution():
    dist = _mix("merged-chat")["prompt"]
    g = traffic.grid(dist, 1001)
    assert g.min() >= dist["min"] and g.max() <= dist["max"]
    assert abs(np.median(g) - dist["median"]) <= 1
    # sigma: the 84th percentile of a lognormal is median * e^sigma
    assert abs(np.percentile(g, 84.13) / dist["median"] - np.exp(dist["sigma"])) < 0.05


def test_every_seed_serves_the_same_sizes_in_the_same_order():
    mix = _mix("merged-chat")
    n = 3 * mix["block"]

    def take(seed):
        gen = traffic.serve_requests(mix, VOCAB, seed)
        return [next(gen) for _ in range(n)]

    a, b = take(1), take(2**35 + 9)
    assert [(len(r.prompt), r.max_new) for r in a] == [(len(r.prompt), r.max_new) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]  # other tokens
    # each block of the stream holds the whole grid of lengths
    block = [len(r.prompt) for r in a[: mix["block"]]]
    assert sorted(block) == sorted(traffic.grid(mix["prompt"], mix["block"]).tolist())
    assert take(1) == a
    ids = np.concatenate([np.asarray(r.prompt) for r in a])
    assert ids.min() >= mix["tokens"]["reserved"] and ids.max() < VOCAB


def _take(mix, seed, n):
    gen = traffic.serve_requests(mix, VOCAB, seed)
    return [next(gen) for _ in range(n)]


def test_open_loop_arrivals_keep_their_rate_for_every_seed():
    base = _mix("merged-chat")
    n = base["block"]
    poisson = dict(base, arrivals={"process": "poisson", "rate": 4.0, "lead_s": 1})
    a, b = _take(poisson, 1, 2 * n), _take(poisson, 2**36 + 1, 2 * n)
    assert [r.arrival for r in a] == [r.arrival for r in b]
    gaps = np.diff([0.0] + [r.arrival for r in a])
    assert (gaps > 0).all() and abs(gaps[:n].mean() - 0.25) < 0.02
    bursty = dict(base, arrivals={"process": "gamma", "rate": 4.0, "cv": 3.0, "lead_s": 1})
    g = np.diff([0.0] + [r.arrival for r in _take(bursty, 1, n)])
    assert abs(g.mean() - 0.25) < 0.05 and g.std() / g.mean() > 1.5
    assert all(r.arrival is None for r in _take(base, 1, 4))  # a closed loop has none


def test_tenants_follow_zipf_in_the_same_order_for_every_seed():
    mix = dict(_mix("merged-chat"), tenants={"count": 16, "s": 1.0})
    a, b = _take(mix, 1, 4 * mix["block"]), _take(mix, 9, 4 * mix["block"])
    ids = [r.tenant for r in a]
    assert ids == [r.tenant for r in b]
    counts = Counter(ids)
    assert set(ids) <= set(range(1, 17)) and counts[1] == max(counts.values())
    assert counts[1] > 2 * counts[4]
    assert all(r.tenant == 0 for r in _take(_mix("merged-chat"), 1, 4))


def test_prompts_share_prefixes_in_turn():
    mix = dict(_mix("merged-chat"), shared_prefix={"length": 40, "count": 3})
    reqs = _take(mix, 5, 9)
    heads = [tuple(r.prompt[:40]) for r in reqs]
    assert len(set(heads)) == 3 and heads[:3] == heads[3:6] == heads[6:]
    plain = _take(_mix("merged-chat"), 5, 9)
    assert [len(r.prompt) - 40 for r in reqs] == [len(r.prompt) for r in plain]


def test_uniform_and_fixed_lengths():
    u = traffic.grid({"dist": "uniform", "min": 10, "max": 110}, 100)
    assert u.min() >= 10 and u.max() <= 110 and abs(u.mean() - 60) <= 1
    f = traffic.grid({"dist": "fixed", "value": 77, "min": 1, "max": 100}, 5)
    assert f.tolist() == [77] * 5
