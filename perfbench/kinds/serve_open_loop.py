"""Kind ``serve_open_loop``: requests offered to one ``ContinuousScheduler`` on
a schedule fixed by the cell (a backlog due at once, or Poisson arrivals at a
fixed rate), whatever the scheduler does with them.

One process: the load generator is a thread that sleeps until each request is
due and calls ``submit``; it does no JAX work. The main thread runs the loop
``cli/serve.py`` runs (``admit``, ``step``, ``drain_ready``). The same traffic
runs for the cell's ``warmup_s`` before the window opens, so the window sees a
filled pool and a standing queue, not a cold start.
"""

from __future__ import annotations

import importlib
import threading
import time

import numpy as np

from perfbench import program_api as api
from perfbench import program_api_spans, stalls, traffic

# The program computes in bfloat16 and keeps keys and values in bfloat16; the
# reference computes in float32. Over 30 layers the rounding of a logit adds up
# to about 1 % of the largest logit (my chip run, PR 24: 0.012 at worst); the
# limit is 3 % of the largest |logit| of the compared rows, the bound
# chip_smoke.py holds the two decode kernels to against each other. A cache
# that dropped or misplaced a position, a wrong rotary angle, or a missing
# bias moves logits by a large part of their size.
LOGIT_REL_TOL = 0.03


def check(ctx, config, cell, sched) -> dict:
    """Prefill then decode through the scheduler's own jitted pool programs,
    against the reference's full forward pass over the same tokens."""
    c = cell["check"]
    m = config["model"]
    reference = importlib.import_module(f"perfbench.reference.{config['family']}")
    rng = np.random.default_rng([ctx.seed, 11])
    n, steps = c["prompt_tokens"], c["decode_tokens"]
    prompts = rng.integers(3, m["target_vocab_size"], size=(c["sequences"], n), dtype=np.int32)
    prompts[:, 0] = api.IdTokenizer.bos_id
    got = api.pool_forward_logits(sched, prompts, steps)  # (R, steps + 1, V)
    fed = got[:, :steps].argmax(-1).astype(np.int32)  # the greedy tokens the program fed itself
    full = np.concatenate([prompts, fed], axis=1)
    want = np.asarray(reference.logits(sched.params, full, m, first=n - 1), np.float32)
    scale = float(np.abs(want).max())
    diff = float(np.abs(got - want).max())
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    ok = bool(np.isfinite(got).all() and diff <= LOGIT_REL_TOL * scale)
    return {"ok": ok, "max_abs_diff": diff, "max_abs_logit": scale, "rel": diff / scale,
            "argmax_agree": agree, "tolerance_rel": LOGIT_REL_TOL, "positions": int(got.shape[1])}


def backlog_guard(cell_name: str, offered: int, in_window: list, t0: float, t1: float):
    """A backlog cell measures a queue that is never empty. The least
    ``sched.backlog`` over the window's steps, and, where it reads 0 (or the
    window holds no step at all: the pool was idle), why the run is not
    ``correct``: the fault is the cell's depth, not the program's speed."""
    least = min((s[4] for s in in_window), default=0)
    if least > 0:
        return least, None
    ran_out = next((s[1] for s in in_window if s[4] == 0), t0)
    return least, (f"the backlog of {offered} requests ran out {t1 - ran_out:.1f} s before the window closed: the cell "
                   f"{cell_name} is too short for this program; a benchmark PR extends backlog_total")


class LoadGenerator(threading.Thread):
    """Submits each request when it is due; records when it really did."""

    def __init__(self, sched, requests, t_start):
        super().__init__(name="perfbench-loadgen", daemon=True)
        self.sched, self.requests, self.t_start = sched, requests, t_start
        self.stop_at = float("inf")  # requests due at or after this are not sent

    def run(self):
        for r in self.requests:
            due = self.t_start + r["due_s"]
            while True:
                now = time.perf_counter()
                if due >= self.stop_at:
                    return
                if now >= due:
                    break
                time.sleep(min(due - now, 0.02))
            r["t_submit"] = time.perf_counter()
            r["order"] = self.sched.submit({"prompt": " ".join(map(str, r["ids"])), "max_new": r["max_new"]})


def _warm_shapes(sched, traffic_p, vocab, rng):
    """One request at each power of two of the prompt range and at its ends,
    so that every prefill shape, the step and the picks are compiled."""
    lo, hi = traffic_p["prompt"]["min"], traffic_p["prompt"]["max"]
    lengths = sorted({lo, hi, *[1 << k for k in range(1, 20) if lo <= (1 << k) <= hi]})
    for n in lengths:
        ids = rng.integers(3, vocab, size=n - 1)
        sched.submit({"prompt": " ".join(map(str, ids)), "max_new": 2})
    while sched.busy:
        sched.admit()
        sched.step()
    return len(sched.drain_ready()), lengths


def _request_rows(requests, spans, t_start, t1) -> list[dict]:
    """One row per request offered: when it was due, how late it was sent, and,
    from the scheduler's span of it, when it was admitted, first answered and
    done (the span's times start at submit)."""
    rows = []
    for r in requests:
        due = t_start + r["due_s"]
        sp = spans.get(r.get("order"))  # no order: the generator never got to send it
        row = {"due": due, "phase": r["phase"], "late_s": r.get("t_submit", t1) - due, "asked": r["max_new"],
               "prompt_tokens": len(r["ids"]) + 1, "answered": sp is not None, "error": None}
        if sp is not None:
            row["error"] = sp.get("error")
            row["new_tokens"] = sp.get("new_tokens")
            row["queue_s"] = sp.get("queue_s")
            row["t_admit"] = r["t_submit"] + sp.get("queue_s", 0.0)
            row["t_done"] = r["t_submit"] + sp["total_s"]
            if sp.get("ttft_s") is not None:
                row["t_first"] = r["t_submit"] + sp["ttft_s"]
                row["ttft_due_s"] = row["t_first"] - due
                if sp.get("new_tokens", 0) > 1:
                    row["tpot_s"] = (sp["total_s"] - sp["ttft_s"]) / (sp["new_tokens"] - 1)
        rows.append(row)
    return rows


def run(ctx, config: dict, cell: dict) -> dict:
    m = config["model"]
    traffic_p, dep = cell["traffic"], cell["deployment"]
    warmup_s, drain_limit_s = cell["warmup_s"], cell["drain_limit_s"]
    vocab = m["target_vocab_size"]
    params = api.init_lm_params(config, ctx.seed)
    ctx.mark("weights")
    spans: dict[int, dict] = {}
    sched, tel = api.make_scheduler(params, config, dep, lambda s: spans.__setitem__(s["order"], s))
    warmed, lengths = _warm_shapes(sched, traffic_p, vocab, np.random.default_rng([ctx.seed, 5]))
    ctx.mark("scheduler and one request of each prompt shape")
    verdict = check(ctx, config, cell, sched)
    ctx.mark("check")
    ctx.say("check", {**verdict, "warmed_prompt_lengths": lengths, "warm_answers": warmed})
    spans.clear()

    horizon = warmup_s + ctx.seconds
    requests = traffic.open_loop_requests(traffic_p, ctx.seed, vocab, warmup_s, ctx.seconds)
    backlog = traffic_p["arrivals"] == "backlog"
    t_start = time.perf_counter() + 0.05
    gen = LoadGenerator(sched, requests, t_start)
    t_open, t_close = t_start + warmup_s, t_start + horizon
    gen.stop_at = t_close
    gc_clock = stalls.GcClock()
    gen.start()
    # Per sched.step() with a slot active: (t_before, t_after, active, blocks_in_use, backlog, tokens so far,
    # this thread's CPU time so far)
    steps: list[tuple] = []
    admit_s = 0.0
    answers: list[dict] = []
    tokens_t0 = tokens_t1 = None
    # The traced slice is the END of the window, so that stopping the profiler
    # (which halts this loop for seconds) falls after the window has closed.
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
            if backlog:
                break  # a backlog is not drained: the window's work is counted, the rest dropped
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
    if backlog:
        population = [r for r in rows if r["answered"] and t0 <= r["t_done"] <= t1]
    else:
        population = [r for r in rows if r["phase"] == "window"]  # due inside the window, as scheduled
    failed = [r for r in population if not r["answered"] or r["error"] is not None]
    wrong_len = [r for r in population if r["answered"] and r["error"] is None and r["new_tokens"] != r["asked"]]
    for r in failed:  # a failed request misses every limit: it counts as the longest wait allowed
        r["ttft_due_s"] = (t1 - r["due"]) + drain_limit_s
        r["tpot_s"] = None
    in_window = [s for s in steps if t0 <= s[0] and s[1] <= t1]
    ok_rows = [r for r in population if r.get("ttft_due_s") is not None and r["error"] is None and r["answered"]]

    def med(key, scale=1e3):
        v = sorted(r[key] for r in ok_rows if r.get(key) is not None)
        return scale * v[len(v) // 2] if v else None

    backlog_min, ran_out = backlog_guard(ctx.workload, len(requests), in_window, t0, t1) if backlog else (None, None)
    sent = [r["t_submit"] for r in requests if "t_submit" in r]

    def spans_of(name):
        return [(sp["t0_mono"], sp["dur_s"]) for sp in program_api_spans.spans(name, t_start, t1) or ()]

    gaps = stalls.longest_gaps([(st[1], st[6]) for st in steps], t_start, (t0, t1), {
        "gc": [g[:2] for g in gc_clock.events], "device_wait": spans_of("step.fetch"), "admit": spans_of("serve.admit")})
    third = max(len(in_window) // 3, 1)
    # Output tokens per second in each 5-second slice since the traffic began
    # (warm-up included): the warm-up is long enough when the slices of the
    # window agree with each other.
    slices, edge, last_tokens = [], t_start + 5.0, 0.0
    for s in steps:
        while s[1] >= edge:
            slices.append(round((s[5] - last_tokens) / 5.0, 1))
            last_tokens, edge = s[5], edge + 5.0
    ctx.say("serve", {
        "arrivals": traffic_p["arrivals"], "submitted": sum(1 for r in requests if "order" in r), "offered": len(requests),
        "population": len(population), "failed": len(failed), "wrong_length": len(wrong_len),
        "answers_with_error": sum(1 for a in answers if "error" in a),
        "completed_rps": len([r for r in rows if r["answered"] and t0 <= r.get("t_done", -1) <= t1]) / (t1 - t0),
        "ttft_due_p50_ms": med("ttft_due_s"), "tpot_p50_ms": med("tpot_s"), "queue_p50_ms": med("queue_s"),
        "tok_s_by_5s_slice_since_traffic_began": slices, "warmup_s": warmup_s,
        "steps_in_window": len(in_window), "admit_share_of_window": admit_s / (t1 - t0),
        "backlog_first_third_mean": float(np.mean([s[4] for s in in_window[:third]])) if in_window else None,
        "backlog_last_third_mean": float(np.mean([s[4] for s in in_window[-third:]])) if in_window else None,
        "backlog_at_close": in_window[-1][4] if in_window else None,
        "backlog_min_in_window": backlog_min,
        "submitting_took_s": max(sent) - t_start if sent else None,
        "longest_gaps_between_step_ends": gaps,
        "gc_in_window_ms": 1e3 * sum(g[1] for g in gc_clock.events if t0 <= g[0] <= t1),
        "scheduler_stats": {k: sched.stats[k] for k in ("admitted", "steps", "max_active", "prompt_tokens",
                                                        "prefill_forwards", "kv_preempted", "retries")},
    })
    compared = {"logits_off_rel": [verdict["rel"], "<=", LOGIT_REL_TOL], "answers_of_another_length": [len(wrong_len), "<=", 0]}
    if backlog:
        compared["backlog_min_in_window"] = [backlog_min, ">=", 1]
    return {
        "correct": bool(verdict["ok"] and not wrong_len and ran_out is None),
        "why_not_correct": ([] if verdict["ok"] else [f"logits off by {verdict['rel']:.3g} of the largest, over {LOGIT_REL_TOL:g}"])
        + ([f"{len(wrong_len)} answers of another length than asked"] if wrong_len else [])
        + ([ran_out] if ran_out else []),
        "compared": compared,
        "attempted": len(population),
        "failed": len(failed),
        "window_s": t1 - t0,
        "serve": {
            "arrivals": traffic_p["arrivals"],
            "population": population,
            "steps": in_window,
            "all_steps": steps,
            "num_slots": dep["num_slots"],
            "pool_blocks": pool_blocks,
            "tokens_at_t0": tokens_t0,
            "tokens_at_t1": tokens_t1,
        },
    }
