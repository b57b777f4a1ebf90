"""The sessions generator (``perfbench/traffic_sessions.py``): every seed asks
for the same work, and a turn's prompt begins with the whole of the turn
before it (its prompt, then its answer's ids), all behind one system prompt."""

import json
import os

import numpy as np

from perfbench.traffic_sessions import session_requests

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _traffic():
    with open(os.path.join(HERE, "workloads", "sc2-3b.sessions-shared-prefix.json")) as f:
        return json.load(f)["traffic"]


def test_every_seed_asks_for_the_same_work():
    p = _traffic()
    a, b = session_requests(p, 1, 49152, 60, 51), session_requests(p, 2, 49152, 60, 51)
    shape = [(r["due_s"], r["phase"], len(r["ids"]), r["max_new"], r["session"], r["turn"]) for r in a]
    assert shape == [(r["due_s"], r["phase"], len(r["ids"]), r["max_new"], r["session"], r["turn"]) for r in b]
    assert any(not np.array_equal(x["ids"], y["ids"]) for x, y in zip(a, b))


def test_a_turn_resends_the_history_behind_one_system_prompt():
    p = _traffic()
    reqs = session_requests(p, 7, 49152, 60, 51)
    system = reqs[0]["ids"][: p["system_tokens"] - 1]
    by_session = {}
    for r in reqs:
        by_session.setdefault(r["session"], []).append(r)
        assert np.array_equal(r["ids"][: len(system)], system)
    assert len(by_session) == round(p["session_rate"] * 60) + round(p["session_rate"] * 51)
    for turns in by_session.values():
        turns.sort(key=lambda r: r["turn"])
        assert p["turns"]["min"] <= len(turns) <= p["turns"]["max"]
        for before, after in zip(turns, turns[1:]):
            grown = len(after["ids"]) - len(before["ids"]) - before["max_new"]
            assert np.array_equal(after["ids"][: len(before["ids"])], before["ids"])
            assert p["user"]["min"] <= grown <= p["user"]["max"]
            assert after["due_s"] - before["due_s"] >= p["think_s"]["min"]
    assert [r["due_s"] for r in reqs] == sorted(r["due_s"] for r in reqs)
