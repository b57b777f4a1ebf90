"""Kind ``serve_sessions``: multi-turn chat sessions offered to one
``ContinuousScheduler`` whose prefix cache is on, each turn due on the
session's schedule whatever the scheduler does with it (an open loop).

Sessions open with one shared system prompt and resend their whole history
each turn (``perfbench/traffic_sessions.py``), so a turn's prompt begins with
what the cache holds from the session's turn before (and every session's with
the system prompt). The loop, its diagnostics and the rows are
``serve_open_loop``'s; what differs is the traffic, the prefix cache
(``perfbench/program_api_sessions.py``), the check (a second turn served
from the cache) and the population: the turns due inside the window.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from perfbench import program_api as api
from perfbench import program_api_sessions, program_api_spans, stalls, traffic_sessions
from perfbench.kinds.serve_open_loop import LOGIT_REL_TOL, LoadGenerator, _request_rows, _warm_shapes

# The keys and values a second turn's slot holds, against the reference's: the
# largest |difference| of a layer's keys (or values) over their largest
# |value|, the worst over layers. A diffuse attention reads a misplaced block
# or a row written one position late as little more than rounding in the
# logits; the rows themselves differ by a whole row's size. The limit lies
# between the program's reading on the chip and the planted faults'
# (PERF.md section 6).
ROWS_REL_TOL = 0.1


def _rel(got, want) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


def check(ctx, config, cell, sched, before_steps=None) -> dict:
    """``sequences`` sessions of two turns through the scheduler, the cache
    on: each first turn (the shared system prompt and a user message) is
    answered, so the cache adopts its prompt's blocks; each second turn (the
    first, its answer and another message) is admitted with those blocks
    aliased and its suffix prefilled at their end, then fed its prompt's tail
    and ``decode_tokens`` greedy tokens. Its logits against the reference's
    full forward over the whole history, the keys and values its slot's
    blocks hold for every position against the reference's; and the cache has
    to have served every whole block of the first turn's prompt from the pool.
    ``before_steps``: ``program_api_sessions.admitted_logits``'s."""
    c = cell["check"]
    m = config["model"]
    vocab = m["target_vocab_size"]
    reference = importlib.import_module(f"perfbench.reference.{config['family']}")
    rng = np.random.default_rng([ctx.seed, 11])
    block = cell["deployment"].get("kv_block", 16)

    def message():
        return [int(t) for t in rng.integers(3, vocab, size=int(rng.integers(c["user_tokens"] // 2, c["user_tokens"] + 1)))]

    system = [api.IdTokenizer.bos_id] + [int(t) for t in rng.integers(3, vocab, size=c["system_tokens"])]
    turns = []
    for _ in range(c["sequences"]):
        ids = system + message()
        turns.append((ids, ids + program_api_sessions.answer(sched, ids, c["answer_tokens"]) + message()))
    rows_family = importlib.import_module(f"perfbench.reference.{config['family']}_rows")
    rels, row_rels, hits, short = [], [], [], []
    for first_turn, ids in turns:
        got = program_api_sessions.admitted_logits(sched, ids, c["decode_tokens"], before_steps)
        full = ids + got["fed"][len(ids) - got["first"]:]
        # Padded at the end to whole 256s, so that a few lengths compile: a
        # causal model's earlier rows do not see what follows them.
        padded = full + [0] * (-len(full) % 256)
        want = np.asarray(reference.logits(sched.params, np.asarray([padded], np.int32), m)[0], np.float32)
        want = want[got["first"]:len(full)]
        rels.append(_rel(got["logits"], want))
        # Every row the slot read, as its blocks hold them, against the
        # reference's keys and values at the same positions.
        want_rows = rows_family.rows(sched.params, np.asarray([padded], np.int32), m)
        row_rels.append(max(_rel(g, np.asarray(w, np.float32)[: len(g)])
                            for layer, want_layer in zip(got["rows"], want_rows) for g, w in zip(layer, want_layer)))
        hits.append([got["hit"], got["aliased"]])
        if not got["hit"] == got["aliased"] >= len(first_turn) // block * block:
            short.append(got["hit"])
    rel, rows_rel = max(rels), max(row_rels)
    ok = bool(np.isfinite(rel) and rel <= LOGIT_REL_TOL and rows_rel <= ROWS_REL_TOL and not short)
    return {"ok": ok, "rel": rel, "rel_by_session": rels, "tolerance_rel": LOGIT_REL_TOL,
            "rows_rel": rows_rel, "rows_rel_by_session": row_rels, "rows_tolerance_rel": ROWS_REL_TOL,
            "hit_and_aliased_tokens": hits, "hits_short": len(short), "second_turn_tokens": [len(t[1]) for t in turns]}


def run(ctx, config: dict, cell: dict) -> dict:
    m = config["model"]
    traffic_p, dep = cell["traffic"], dict(cell["deployment"])
    warmup_s, drain_limit_s = cell["warmup_s"], cell["drain_limit_s"]
    vocab = m["target_vocab_size"]
    params = api.init_lm_params(config, ctx.seed)
    ctx.mark("weights")
    spans: dict[int, dict] = {}
    sched, tel = program_api_sessions.make_scheduler(
        params, config, dep, cell["prefix_cache"], lambda s: spans.__setitem__(s["order"], s))
    # Every prefill shape a turn can take: its prompt less what the cache
    # holds, rounded down to a power of two (``warm`` gives the range).
    warmed, lengths = _warm_shapes(sched, {"prompt": cell["warm"]}, vocab, np.random.default_rng([ctx.seed, 5]))
    ctx.mark("scheduler and one request of each prompt shape")
    verdict = check(ctx, config, cell, sched)
    ctx.mark("check")
    ctx.say("check", {**verdict, "warmed_prompt_lengths": lengths, "warm_answers": warmed})
    spans.clear()
    hits_before = program_api_sessions.prefix_tokens(sched)

    horizon = warmup_s + ctx.seconds
    requests = traffic_sessions.session_requests(traffic_p, ctx.seed, vocab, warmup_s, ctx.seconds)
    t_start = time.perf_counter() + 0.05
    gen = LoadGenerator(sched, requests, t_start)
    t_open, t_close = t_start + warmup_s, t_start + horizon
    gen.stop_at = t_close
    gc_clock = stalls.GcClock()
    gen.start()
    steps: list[tuple] = []
    admit_s = 0.0
    answers: list[dict] = []
    tokens_t0 = tokens_t1 = None
    trace_at = t_close - min(cell["trace"]["for_s"], 0.3 * ctx.seconds)
    tracing = False
    _, pool_blocks = api.pool_usage(sched)
    while True:
        now = time.perf_counter()
        if tokens_t0 is None and now >= t_open:
            ctx.window_opens()
            tokens_t0 = api.generated_tokens(tel)
        if tokens_t1 is None and now >= t_close:
            ctx.window_closes()
            tokens_t1 = api.generated_tokens(tel)
            if tracing:
                ctx.trace_stop()
        if ctx.trace and not tracing and tokens_t0 is not None and now >= trace_at:
            ctx.trace_start()
            tracing = True
        if tokens_t1 is not None and (not sched.busy or now - ctx.t1 >= drain_limit_s):
            break
        with ctx.span("sched.admit"):
            t_a = time.perf_counter()
            sched.admit()
            t_b = time.perf_counter()
        with ctx.span("sched.step"):
            active = sched.active_count
            sched.step()
            t_c = time.perf_counter()
        if active:
            steps.append((t_b, t_c, active, api.pool_usage(sched)[0], sched.backlog, api.generated_tokens(tel),
                          time.thread_time()))
        if tokens_t0 is not None and tokens_t1 is None:
            admit_s += t_b - t_a
        with ctx.span("sched.drain"):
            answers.extend(sched.drain_ready())
        if not sched.busy:
            with ctx.span("loop.idle"):
                time.sleep(0.0005)
    gen.stop_at = float("-inf")
    gen.join()
    gc_clock.close()

    t0, t1 = ctx.t0, ctx.t1
    rows = _request_rows(requests, spans, t_start, t1)
    population = [r for r in rows if r["phase"] == "window"]
    failed = [r for r in population if not r["answered"] or r["error"] is not None]
    wrong_len = [r for r in population if r["answered"] and r["error"] is None and r["new_tokens"] != r["asked"]]
    for r in failed:  # a failed turn misses every limit: it counts as the longest wait allowed
        r["ttft_due_s"] = (t1 - r["due"]) + drain_limit_s
        r["tpot_s"] = None
    in_window = [s for s in steps if t0 <= s[0] and s[1] <= t1]
    admits = program_api_spans.spans("serve.admit", t0, t1) or []
    hits_after = program_api_sessions.prefix_tokens(sched)
    sent = [r["t_submit"] for r in requests if "t_submit" in r]

    def spans_of(name):
        return [(sp["t0_mono"], sp["dur_s"]) for sp in program_api_spans.spans(name, t_start, t1) or ()]

    gaps = stalls.longest_gaps([(st[1], st[6]) for st in steps], t_start, (t0, t1), {
        "gc": [g[:2] for g in gc_clock.events], "device_wait": spans_of("step.fetch"), "admit": spans_of("serve.admit")})
    # Output tokens per second in each 5-second slice since the traffic began.
    slices, edge, last_tokens = [], t_start + 5.0, 0.0
    for s in steps:
        while s[1] >= edge:
            slices.append(round((s[5] - last_tokens) / 5.0, 1))
            last_tokens, edge = s[5], edge + 5.0
    ctx.say("serve", {
        "arrivals": "sessions", "sessions": len({r["session"] for r in requests}), "offered": len(requests),
        "submitted": sum(1 for r in requests if "order" in r), "population": len(population), "failed": len(failed),
        "wrong_length": len(wrong_len), "answers_with_error": sum(1 for a in answers if "error" in a),
        "steps_in_window": len(in_window), "admit_share_of_window": admit_s / (t1 - t0),
        "tok_s_by_5s_slice_since_traffic_began": slices, "warmup_s": warmup_s,
        "submitting_took_s": max(sent) - t_start if sent else None, "longest_gaps_between_step_ends": gaps,
        "completed_rps": len([r for r in rows if r["answered"] and t0 <= r.get("t_done", -1) <= t1]) / (t1 - t0),
        "backlog_first_third_mean": float(np.mean([s[4] for s in in_window[: max(len(in_window) // 3, 1)]])) if in_window else None,
        "backlog_last_third_mean": float(np.mean([s[4] for s in in_window[-max(len(in_window) // 3, 1):]])) if in_window else None,
        "backlog_at_close": in_window[-1][4] if in_window else None,
        "admissions_in_window": len(admits),
        "prompt_tokens_in_window": sum(a.get("prompt_tokens", 0) for a in admits),
        "prefix_hit_tokens_in_window": sum(a.get("prefix_hit_tokens", 0) for a in admits),
        "prefix_tokens_since_warm": {k: hits_after[k] - hits_before[k] for k in hits_after},
        "pool_blocks": pool_blocks, "pool_blocks_in_use_max": max((s[3] for s in in_window), default=None),
        "gc_in_window_ms": 1e3 * sum(g[1] for g in gc_clock.events if t0 <= g[0] <= t1),
        "scheduler_stats": {k: sched.stats[k] for k in ("admitted", "steps", "max_active", "prompt_tokens",
                                                        "prefix_hit_tokens", "prefill_forwards", "kv_preempted")},
    })
    compared = {"logits_off_rel": [verdict["rel"], "<=", LOGIT_REL_TOL], "rows_off_rel": [verdict["rows_rel"], "<=", ROWS_REL_TOL],
                "hits_short": [verdict["hits_short"], "<=", 0],
                "answers_of_another_length": [len(wrong_len), "<=", 0]}
    return {
        "correct": bool(verdict["ok"] and not wrong_len),
        "why_not_correct": ([] if verdict["rel"] <= LOGIT_REL_TOL else [f"second turns' logits off by {verdict['rel']:.3g} of the largest, over {LOGIT_REL_TOL:g}"])
        + ([] if verdict["rows_rel"] <= ROWS_REL_TOL else [f"second turns' cached rows off by {verdict['rows_rel']:.3g} of the largest, over {ROWS_REL_TOL:g}"])
        + ([f"{verdict['hits_short']} second turns not served their first turn's blocks from the pool"] if verdict["hits_short"] else [])
        + ([f"{len(wrong_len)} answers of another length than asked"] if wrong_len else []),
        "compared": compared,
        "attempted": len(population),
        "failed": len(failed),
        "window_s": t1 - t0,
        "serve": {
            "arrivals": "sessions",
            "population": population,
            "steps": in_window,
            "all_steps": steps,
            "num_slots": dep["num_slots"],
            "pool_blocks": pool_blocks,
            "tokens_at_t0": tokens_t0,
            "tokens_at_t1": tokens_t1,
        },
    }
