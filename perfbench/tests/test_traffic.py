import json
import os

import numpy as np

from perfbench import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cell(name):
    with open(os.path.join(HERE, "workloads", name + ".json")) as f:
        return json.load(f)


def test_corpus_repeats_for_a_seed_and_differs_between_seeds():
    p = {**cell("tbig-ende.train-1chip")["corpus"], "sentences": 2000}
    a = traffic.seq2seq_corpus(p, 3000000019, 37000)
    b = traffic.seq2seq_corpus(p, 3000000019, 37000)
    c = traffic.seq2seq_corpus(p, 7, 37000)
    for x, y in zip(a[0] + a[1], b[0] + b[1]):
        assert np.array_equal(x, y)
    assert any(len(x) != len(y) or not np.array_equal(x, y) for x, y in zip(a[0], c[0]))
    # Every seed asks for the same work: the same multiset of (source, target) lengths.
    pairs = lambda s: sorted((len(x), len(y)) for x, y in zip(*s))  # noqa: E731
    assert pairs(a) == pairs(c)


def test_corpus_is_framed_and_inside_its_limits():
    p = {**cell("tbig-ende.train-1chip")["corpus"], "sentences": 5000}
    src, tgt = traffic.seq2seq_corpus(p, 1, 37000)
    lens = np.array([len(x) for x in src + tgt])
    assert lens.min() >= p["min_len"] and lens.max() <= p["max_len"]
    assert 24 <= np.median(lens) <= 30
    for x in src[:50]:
        assert x[0] == 36998 and x[-1] == 36999 and x[1:-1].min() >= 1 and x[1:-1].max() < 36998


def test_requests_repeat_and_every_seed_asks_for_the_same_work():
    for name in ("sc2-3b.chat-saturated", "sc2-3b.chat-steady"):
        p = cell(name)["traffic"]
        a = traffic.open_loop_requests(p, 3000000019, 49152, 20.0, 51.0)
        b = traffic.open_loop_requests(p, 3000000019, 49152, 20.0, 51.0)
        c = traffic.open_loop_requests(p, 8, 49152, 20.0, 51.0)
        assert len(a) == len(b) == len(c) and all(
            x["due_s"] == y["due_s"] and x["max_new"] == y["max_new"] and np.array_equal(x["ids"], y["ids"])
            for x, y in zip(a, b)
        )
        # Another seed: other token ids, the same sizes at the same times in the same order.
        assert all(
            x["due_s"] == y["due_s"] and x["max_new"] == y["max_new"] and len(x["ids"]) == len(y["ids"])
            for x, y in zip(a, c)
        )
        assert any(not np.array_equal(x["ids"], y["ids"]) for x, y in zip(a, c))
        window = [x for x in a if x["phase"] == "window"]
        if p["arrivals"] == "poisson":
            assert len(window) == round(p["rate_rps"] * 51.0)
            assert all(20.0 <= x["due_s"] < 71.0 for x in window)
            assert all(x["due_s"] < 20.0 for x in a if x["phase"] == "warmup")
            assert all(x["due_s"] <= y["due_s"] for x, y in zip(a, a[1:]))
        pr, out = p["prompt"], p["output"]
        assert all(pr["min"] <= len(x["ids"]) + 1 <= pr["max"] for x in a)
        assert all(out["min"] <= x["max_new"] <= out["max"] for x in a)
