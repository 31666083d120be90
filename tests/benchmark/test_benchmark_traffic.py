"""The traffic generators: the same seed gives the same inputs, another
seed gives the same set of sizes in another order, and large seeds work."""

import json
import os

import numpy as np
import pytest

from benchmark import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BIG = 2**31 + 12345


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return traffic.with_dry(json.load(f), True)


@pytest.mark.parametrize("cfg,mix", [("resnet50", "train_b128"),
                                     ("gpt2-small", "train_seq1024_b4")])
def test_train_batches_are_made_from_the_seed(cfg, mix):
    cfg, mix = _cfg(cfg), traffic.load(ROOT, mix, dry=True)
    a = traffic.train_batches(cfg, mix, BIG)
    b = traffic.train_batches(cfg, mix, BIG)
    c = traffic.train_batches(cfg, mix, BIG + 1)
    assert len(a) == mix["host_batches"]
    for (xa, ya, _), (xb, yb, _), (xc, _, _) in zip(a, b, c):
        assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
        assert not np.array_equal(xa, xc)
        assert xa.dtype == np.float32 and ya.dtype == np.float32
    # rows that all differ, within and across the first batches
    rows = np.concatenate([x.reshape(len(x), -1) for x, _, _ in a])
    assert len(np.unique(rows, axis=0)) == len(rows)


def test_one_hot_rows_match_their_ids():
    cfg, mix = _cfg("gpt2-small"), traffic.load(ROOT, "train_seq1024_b4", True)
    x, y, (ids, targets) = traffic.train_batches(cfg, mix, 7)[0]
    assert x.shape == ids.shape + (cfg["vocab_size"],)
    assert np.array_equal(x.argmax(-1), ids) and np.all(x.sum(-1) == 1)
    assert np.array_equal(y.argmax(-1), targets)
    assert np.array_equal(ids[:, 1:], targets[:, :-1])


def test_serve_requests_same_sizes_every_seed_other_ids():
    cfg, mix = _cfg("gpt2-small"), traffic.load(ROOT, "serve_offline", False)
    cfg["vocab_size"] = 50257
    a = traffic.serve_requests(cfg, mix, 1)
    b = traffic.serve_requests(cfg, mix, 1)
    c = traffic.serve_requests(cfg, mix, BIG)
    assert a == b and a != c
    size = lambda r: (len(r["tokens"]), r["max_new_tokens"])
    assert list(map(size, a)) == list(map(size, c))     # the same work
    shared = mix["shared_prefix_tokens"]
    assert all(r["tokens"][:shared] == a[0]["tokens"][:shared] for r in a)
    own = [len(r["tokens"]) - shared for r in a]
    lim = mix["own_prompt_tokens"]
    assert min(own) >= lim["min"] and max(own) <= lim["max"]
    assert 0.7 * lim["median"] < np.median(own) < 1.3 * lim["median"]
    assert len({tuple(r["tokens"]) for r in a}) == len(a)      # all distinct
    # no request can run past the model's positions
    assert max(len(r["tokens"]) + r["max_new_tokens"] for r in a) < 1024
