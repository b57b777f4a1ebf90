import json
import os

import numpy as np
import pytest

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


# What the PARENT's generator (before ``backlog_total``, PR 30's tree) gives for the two saturated
# cells, any seed: the first five prompt and output lengths, the output tokens the first
# ``backlog_requests`` ask for in all, and the first ids of the first and the last of them at seed
# 3000000019. A later edit of the generator that moves these moves the cells.
GOLDEN = {
    "sc2-3b.chat-saturated": {
        "vocab": 49152, "first": 600, "total": 2400, "prompts": [386, 111, 516, 497, 568], "outputs": [97, 100, 81, 110, 123],
        "asked_by_first": 116245, "asked_in_all": 456944, "ids_first": [33993, 30547, 45379, 18950],
        "ids_last_of_first": [28325, 4128, 31933, 8448], "appended_prompts": [360, 310, 280, 759, 682],
        "appended_outputs": [255, 175, 512, 298, 200]},
    "laguna-s.agent-saturated": {
        "vocab": 50176, "first": 800, "total": 1600, "prompts": [2948, 1478, 1031, 324, 2392], "outputs": [462, 151, 214, 390, 81],
        "asked_by_first": 181229, "asked_in_all": 355927, "ids_first": [34701, 31183, 46324, 19344],
        "ids_last_of_first": [41719, 23488, 35776, 34230], "appended_prompts": [2812, 3584, 2091, 1085, 519],
        "appended_outputs": [271, 230, 121, 285, 512]},
}


def same_request(x, y):
    return (x["due_s"] == y["due_s"] and x["phase"] == y["phase"] and x["max_new"] == y["max_new"]
            and x["ids"].dtype == y["ids"].dtype and np.array_equal(x["ids"], y["ids"]))


@pytest.mark.parametrize("name", sorted(GOLDEN))
@pytest.mark.parametrize("seed", [3000000019, 7])
def test_a_backlog_grows_at_its_end_and_leaves_its_first_requests_alone(name, seed):
    g, p = GOLDEN[name], cell(name)["traffic"]
    assert p["backlog_requests"] == g["first"] and p["backlog_total"] == g["total"]
    short = {k: v for k, v in p.items() if k != "backlog_total"}
    a = traffic.open_loop_requests(short, seed, g["vocab"], 40.0, 51.0)
    b = traffic.open_loop_requests(p, seed, g["vocab"], 40.0, 51.0)
    assert len(a) == g["first"] and len(b) == g["total"]
    assert all(same_request(x, y) for x, y in zip(a, b))
    # ... and a total equal to the first part appends nothing.
    c = traffic.open_loop_requests({**short, "backlog_total": g["first"]}, seed, g["vocab"], 40.0, 51.0)
    assert len(c) == g["first"] and all(same_request(x, y) for x, y in zip(a, c))
    with pytest.raises(ValueError):
        traffic.open_loop_requests({**short, "backlog_total": g["first"] - 1}, seed, g["vocab"], 40.0, 51.0)


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_the_appended_requests_keep_the_range_and_every_seed_gets_the_same_sizes(name):
    g, p = GOLDEN[name], cell(name)["traffic"]
    a = traffic.open_loop_requests(p, 3000000019, g["vocab"], 40.0, 51.0)[g["first"]:]
    b = traffic.open_loop_requests(p, 7, g["vocab"], 40.0, 51.0)[g["first"]:]
    assert len(a) == len(b) == g["total"] - g["first"]
    pr, out = p["prompt"], p["output"]
    for x, y in zip(a, b):
        assert x["due_s"] == y["due_s"] == 0.0 and x["phase"] == y["phase"] == "window"
        assert x["max_new"] == y["max_new"] and len(x["ids"]) == len(y["ids"])
        assert pr["min"] <= len(x["ids"]) + 1 <= pr["max"] and out["min"] <= x["max_new"] <= out["max"]
        assert x["ids"].min() >= 3 and x["ids"].max() < g["vocab"]
    assert any(not np.array_equal(x["ids"], y["ids"]) for x, y in zip(a, b))
    # The appended sizes are a stream of their own, not the first requests once more.
    first = traffic.open_loop_requests(p, 7, g["vocab"], 40.0, 51.0)[:len(a)]
    assert [len(x["ids"]) for x in first] != [len(x["ids"]) for x in a[:len(first)]]
    assert abs(np.median([len(x["ids"]) + 1 for x in a]) / pr["median"] - 1) < 0.1


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_golden_lengths_of_the_saturated_cells(name):
    g, p = GOLDEN[name], cell(name)["traffic"]
    for seed in (3000000019, 11):
        r = traffic.open_loop_requests(p, seed, g["vocab"], 40.0, 51.0)
        assert [len(x["ids"]) + 1 for x in r[:5]] == g["prompts"] and [x["max_new"] for x in r[:5]] == g["outputs"]
        n = g["first"]
        assert [len(x["ids"]) + 1 for x in r[n:n + 5]] == g["appended_prompts"]
        assert [x["max_new"] for x in r[n:n + 5]] == g["appended_outputs"]
        assert sum(x["max_new"] for x in r[:n]) == g["asked_by_first"] and sum(x["max_new"] for x in r) == g["asked_in_all"]
    r = traffic.open_loop_requests(p, 3000000019, g["vocab"], 40.0, 51.0)
    assert r[0]["ids"][:4].tolist() == g["ids_first"] and r[g["first"] - 1]["ids"][:4].tolist() == g["ids_last_of_first"]


def test_the_steady_cell_is_what_the_parent_generated():
    """The Poisson path is untouched by ``backlog_total`` (the key is not read there)."""
    p = cell("sc2-3b.chat-steady")["traffic"]
    a = traffic.open_loop_requests(p, 3000000019, 49152, 20.0, 51.0)
    b = traffic.open_loop_requests({**p, "backlog_total": 9999}, 3000000019, 49152, 20.0, 51.0)
    assert len(a) == len(b) == 170 and all(same_request(x, y) for x, y in zip(a, b))
    assert [len(x["ids"]) + 1 for x in a[:5]] == [106, 1023, 577, 623, 412] and [x["max_new"] for x in a[:5]] == [512, 140, 95, 512, 157]
