"""The device's own clock, read from the program's ``scheduler.step`` spans
(``perfbench/program_api_spans.py``), without a profiler.

The scheduler keeps one decode step queued behind the one that runs, so the
device goes from step t-1 straight into whatever was enqueued between the two
steps (the admissions' prefills with their pool copies) and then into step t.
A step's **device gap** is the end of its ``step.fetch`` minus the end of the
previous step's ``step.fetch``: the device's time for exactly that work, and
the span says what the work was (``prefills``, ``prefill_tokens``: what was
enqueued between the step before and this one).

A gap is *usable* where both ends are the device's: the step was ``ahead``
(the device had work queued when it was enqueued; where not, its ``drain``
says why), its own fetch and the previous step's each lasted ``MIN_FETCH_S``
or more (both times the device was still at work when the host came to
wait), the gap does not span ``trace_host[0]`` (the profiler's start halts
the loop, as in ``sched.itl_p99_ms``), and it is shorter than ``PAUSE_S``: the
machine now and then pauses for 2 to 5 s inside one fetch (PERF.md section 6),
where a step with three 2,048-token prefills before it takes half a second.
``plain`` is the median usable gap of the steps with no prefill before them.

What a step's admissions held the device for is its gap over ``plain``, and,
where the gap before it is a plain step's, what that one reads over ``plain``
too: the fetch between the two waits beside the prefill that has just begun,
and where it ends late (``starcoder2-3b``: by 0.4 ms at the median, by 2.9 ms
under the profiler, now and then by a whole prefill; the other models not at
all: PERF.md, PR 37) what it is late by lies in the gap before. The sum is
the device's: it agrees with the trace's module events between the same
fetch ends (``perfbench/tests/admit_crosscheck.py``).

A program whose spans carry no ``prefills`` (an earlier commit) gives
``None``, and the readers then report nothing.
"""

from __future__ import annotations

import statistics

from perfbench import program_api_spans as api

MIN_FETCH_S = 0.0002
PAUSE_S = 1.0


def window_steps(record: dict) -> list[dict] | None:
    """The window's ``scheduler.step`` spans that describe a fetched plain
    step (they carry ``prefills``), oldest first."""
    if record.get("serve") is None:
        return None
    steps = [s for s in api.window_spans(record, "scheduler.step") or () if "prefills" in s]
    return steps or None


def device_gaps(record: dict) -> tuple[list[dict], float] | None:
    """``(gaps, plain_s)``: one row a step of the window that has a step
    before it: ``gap_s`` from ``start`` to ``end`` (on the ``perf_counter``
    clock), the step's ``prefills`` and ``prefill_tokens``, and ``why_not``,
    ``None`` where the gap is usable, else the step's ``drain``,
    ``short_fetch``, ``profiler_start`` or ``pause``. ``None`` where no plain
    gap is usable: nothing can then be read against it."""
    steps = window_steps(record)
    if steps is None:
        return None
    fetches = api.children(steps, "step.fetch")
    halted = (record.get("trace_host") or (None,))[0]
    rows, before = [], None
    for s in steps:
        fetch = max(fetches.get(s["span"], ()), key=lambda f: f["t0_mono"] + f["dur_s"], default=None)
        if fetch is None:
            before = None
            continue
        end = fetch["t0_mono"] + fetch["dur_s"]
        if before is not None:
            why_not = None
            if not s.get("ahead"):
                why_not = s.get("drain", "not_ahead")
            elif min(fetch["dur_s"], before["dur_s"]) < MIN_FETCH_S:
                why_not = "short_fetch"
            elif halted is not None and before["end"] <= halted <= end:
                why_not = "profiler_start"
            elif end - before["end"] >= PAUSE_S:
                why_not = "pause"
            rows.append({"gap_s": end - before["end"], "start": before["end"], "end": end, "prefills": int(s["prefills"]),
                         "prefill_tokens": int(s.get("prefill_tokens", 0)), "why_not": why_not})
        before = {"end": end, "dur_s": fetch["dur_s"]}
    plain = [r["gap_s"] for r in rows if r["why_not"] is None and r["prefills"] == 0]
    return (rows, statistics.median(plain)) if plain else None


def admissions(rows: list[dict], plain: float) -> list[dict]:
    """One entry a step with a prefill or more before it: its ``prefills`` and
    ``prefill_tokens``; ``gaps``, the rows that hold the device's time for
    them (the step's own and, where that is a plain step's, the one before
    it); ``extra_s``, those gaps over ``plain``; and ``why_not``, ``None``
    where every such gap and the one before the step are usable, else the
    first cause (``no_step_before`` where the window has none)."""
    out = []
    for i, row in enumerate(rows):
        if row["prefills"] < 1:
            continue
        prev = rows[i - 1] if i and rows[i - 1]["end"] == row["start"] else None
        why_not = row["why_not"] or ("no_step_before" if prev is None else prev["why_not"])
        gaps = [row] if prev is None or prev["prefills"] else [prev, row]
        out.append({"prefills": row["prefills"], "prefill_tokens": row["prefill_tokens"], "gaps": gaps, "why_not": why_not,
                    "extra_s": sum(g["gap_s"] - plain for g in gaps)})
    return out
