"""Seeded traffic: one general generator per kind of load, driven by a cell's parameters.

Every seed asks for the SAME work: sizes and arrival gaps are drawn from the
cell's fixed ``shape_seed``; the seed draws the token ids (and, elsewhere, the
weights), and for a corpus the order of the sentences. Requests come in the
same order for every seed: when the seed ordered them too, runs of two seeds
differed by 5 to 10 % in every serving metric where two runs of one seed
differed by 0.2 to 1 % (my chip runs, PR 24), because a window sees only part
of the backlog and a tail is set by which long prompt meets which queue.
"""

from __future__ import annotations

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    # --seed may exceed 2**31; SeedSequence takes any non-negative integer.
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def lognormal_lengths(rng, n: int, median: float, sigma: float, lo: int, hi: int) -> np.ndarray:
    """``n`` integer lengths, log-normal about ``median``, clipped to [lo, hi]."""
    x = rng.lognormal(mean=np.log(median), sigma=sigma, size=n)
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def zipf_ids(rng, n: int, lo: int, hi: int, exponent: float = 1.0) -> np.ndarray:
    """``n`` token ids in [lo, hi), rank r drawn with probability ~ r**-exponent."""
    ranks = np.arange(1, hi - lo + 1, dtype=np.float64)
    cdf = np.cumsum(ranks**-exponent)
    cdf /= cdf[-1]
    return (lo + np.searchsorted(cdf, rng.random(n), side="left")).astype(np.int32)


def seq2seq_corpus(p: dict, seed: int, vocab: int):
    """A parallel corpus as two lists of int32 arrays framed BOS .. EOS.

    ``p``: sentences, median, sigma, min_len, max_len (framed lengths),
    ratio_sigma (target length = source length x lognormal(0, ratio_sigma)),
    zipf_exponent, shape_seed. Ids: 0 is pad, BOS = vocab - 2, EOS = vocab - 1
    (the repo's tokenizer convention), words Zipf over [1, vocab - 2).
    """
    shape_rng = _rng(p["shape_seed"], 0)
    n = int(p["sentences"])
    lo, hi = int(p["min_len"]) - 2, int(p["max_len"]) - 2  # words, without the frame
    src_len = lognormal_lengths(shape_rng, n, p["median"] - 2, p["sigma"], lo, hi)
    ratio = shape_rng.lognormal(0.0, p["ratio_sigma"], size=n)
    tgt_len = np.clip(np.rint(src_len * ratio), lo, hi).astype(np.int64)
    order = _rng(seed, 1).permutation(n)  # same multiset of pairs, another order
    src_len, tgt_len = src_len[order], tgt_len[order]
    bos, eos = vocab - 2, vocab - 1
    ids_rng = _rng(seed, 2)

    def side(lengths):
        ends = np.cumsum(lengths + 2)
        starts = ends - (lengths + 2)
        flat = np.empty(int(ends[-1]), np.int32)
        words = np.ones(flat.shape, bool)
        words[starts] = words[ends - 1] = False
        flat[starts], flat[ends - 1] = bos, eos
        flat[words] = zipf_ids(ids_rng, int(lengths.sum()), 1, vocab - 2, p["zipf_exponent"])
        return np.split(flat, ends[:-1])

    return side(src_len), side(tgt_len)


def open_loop_requests(p: dict, seed: int, vocab: int, warmup_s: float, seconds: float) -> list[dict]:
    """Requests of an open loop, sorted by due time: a warm-up phase of
    ``warmup_s`` seconds, then the window's phase of ``seconds`` seconds.

    ``p``: prompt {median, sigma, min, max}, output {median, sigma, min, max},
    shape_seed, and arrivals "backlog" (``backlog_requests`` of them, all due
    at 0) or "poisson" (``rate_rps``). Under "poisson" each phase holds a fixed
    number of requests, round(rate x its length), whose exponential gaps are
    scaled to fill the phase exactly; so every seed's window holds the same
    multiset of sizes and of gaps, in another order. Lengths count the BOS the
    scheduler prepends, so ``ids`` has ``prompt_len - 1`` entries. Each request:
    due_s, phase ("warmup" or "window"), ids (int32 in [3, vocab)), max_new.

    A backlog grows at its END: ``backlog_total`` (absent = ``backlog_requests``)
    appends ``backlog_total - backlog_requests`` requests, due at 0 like the
    rest, whose sizes come from a stream of their own (``_rng(shape_seed, 3)``)
    and whose ids continue on the seed's stream. The first ``backlog_requests``
    stay what they were draw for draw, so a deeper queue leaves the window of
    today's program the requests it had: writing a larger ``backlog_requests``
    would move every draw (the gaps take ``n + 1`` numbers before the first
    length).
    """
    shape_rng = _rng(p["shape_seed"], 0)
    ids_rng = _rng(seed, 2)
    pr, out = p["prompt"], p["output"]
    appended = 0
    if p["arrivals"] == "backlog":
        phases = [("window", int(p["backlog_requests"]), 0.0, 0.0)]
        appended = int(p.get("backlog_total", p["backlog_requests"])) - int(p["backlog_requests"])
        if appended < 0:
            raise ValueError("backlog_total is below backlog_requests: a backlog grows at its end only")
    elif p["arrivals"] == "poisson":
        phases = [("warmup", round(p["rate_rps"] * warmup_s), 0.0, warmup_s),
                  ("window", round(p["rate_rps"] * seconds), warmup_s, seconds)]
    else:
        raise ValueError(f"unknown arrivals {p['arrivals']!r}")
    reqs = []

    def lengths(rng, n):
        return (lognormal_lengths(rng, n, pr["median"], pr["sigma"], pr["min"], pr["max"]),
                lognormal_lengths(rng, n, out["median"], out["sigma"], out["min"], out["max"]))

    def add(phase, due, prompt_len, out_len):
        for d, pl, ol in zip(due, prompt_len, out_len):
            reqs.append({
                "due_s": float(d),
                "phase": phase,
                "ids": ids_rng.integers(3, vocab, size=int(pl) - 1, dtype=np.int32),
                "max_new": int(ol),
            })

    for phase, n, begins, lasts in phases:
        gaps = shape_rng.exponential(1.0, size=n + 1)
        gaps *= lasts / gaps.sum()  # n arrivals strictly inside the phase; the last gap is its tail
        add(phase, begins + np.cumsum(gaps[:n]), *lengths(shape_rng, n))
    if appended:
        add("window", np.zeros(appended), *lengths(_rng(p["shape_seed"], 3), appended))
    return reqs
