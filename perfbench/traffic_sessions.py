"""Seeded multi-turn sessions: chat sessions that open with one shared system
prompt and resend their whole history each turn (the shape of Mooncake's
conversation traces, arXiv:2407.00079), offered on a fixed schedule.

As in ``perfbench/traffic.py``, every seed asks for the SAME work: the number
of sessions, their arrival gaps, turns, lengths and think gaps come from the
cell's ``shape_seed``; the seed draws the token ids (the system prompt's, and
each user message's and answer's).
"""

from __future__ import annotations

import numpy as np

from perfbench.traffic import _rng, lognormal_lengths


def session_requests(p: dict, seed: int, vocab: int, warmup_s: float, seconds: float) -> list[dict]:
    """Turns of sessions that arrive as a Poisson process, sorted by due time.

    ``p``: ``session_rate`` (sessions a second), ``turns`` {min, max} (uniform),
    ``system_tokens`` (the prompt every session opens with, BOS included),
    ``user`` and ``answer`` {median, sigma, min, max} (log-normal lengths of a
    turn's new message and of the answer it asks for), ``think_s`` {mean, min}
    (the gap from a turn's due time to the next turn's: ``min`` plus an
    exponential of mean ``mean - min``), ``shape_seed``. Sessions arrive in
    two phases as ``open_loop_requests``' Poisson arrivals do: round(rate x
    warmup_s) during the warm-up and round(rate x seconds) in the window, their
    gaps scaled to fill each phase. Turn ``k`` of a session asks, after the
    system prompt, for the session's user messages and answers up to its own
    user message: the history a chat client resends. A turn's ``phase`` is the
    phase its DUE time falls in ("after" past the window: never sent).

    Each request: due_s, phase, ids (int32 in [3, vocab), without the BOS the
    scheduler prepends), max_new, session, turn.
    """
    shape_rng = _rng(p["shape_seed"], 0)
    ids_rng = _rng(seed, 2)
    system = ids_rng.integers(3, vocab, size=int(p["system_tokens"]) - 1, dtype=np.int32)
    rate, turns_p, think = p["session_rate"], p["turns"], p["think_s"]
    user, answer = p["user"], p["answer"]
    reqs = []
    session = 0
    for begins, lasts in ((0.0, warmup_s), (warmup_s, seconds)):
        n = round(rate * lasts)
        gaps = shape_rng.exponential(1.0, size=n + 1)
        gaps *= lasts / gaps.sum()
        starts = begins + np.cumsum(gaps[:n])
        turns = shape_rng.integers(turns_p["min"], turns_p["max"] + 1, size=n)
        for start, k in zip(starts, turns):
            users = lognormal_lengths(shape_rng, int(k), user["median"], user["sigma"], user["min"], user["max"])
            answers = lognormal_lengths(shape_rng, int(k), answer["median"], answer["sigma"], answer["min"], answer["max"])
            thinks = think["min"] + shape_rng.exponential(think["mean"] - think["min"], size=int(k))
            history, due = system, float(start)
            for t in range(int(k)):
                history = np.concatenate([history, ids_rng.integers(3, vocab, size=int(users[t]), dtype=np.int32)])
                phase = "warmup" if due < warmup_s else "window" if due < warmup_s + seconds else "after"
                reqs.append({"due_s": due, "phase": phase, "ids": history, "max_new": int(answers[t]),
                             "session": session, "turn": t})
                # The next turn resends this answer; its ids are the seed's, not the model's.
                history = np.concatenate([history, ids_rng.integers(3, vocab, size=int(answers[t]), dtype=np.int32)])
                due += float(thinks[t])
            session += 1
    reqs.sort(key=lambda r: r["due_s"])
    return reqs
