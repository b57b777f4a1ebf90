"""Host work of one decode step: over the window's ``scheduler.step`` spans
(the program's own, all of ``step()`` with a slot active), the median of a
span's duration minus its ``step.fetch`` children, which are the wait for the
device. What is left is the host's: expiry and block allocation, building the
inputs, the dispatches, the slot walk. A note gives the spans' own median
beside the benchmark's timing of the same calls from outside
(``prog.decode_step_ms``): inside and outside have to agree. Nothing where the
program records no such spans."""

import statistics

from perfbench import program_api_spans as api


def read(record):
    s = record.get("serve")
    if s is None:
        return None
    steps = api.window_spans(record, "scheduler.step")
    if not steps:
        return None
    fetch = api.children(steps, "step.fetch")
    host = [st["dur_s"] - sum(f["dur_s"] for f in fetch.get(st["span"], ())) for st in steps if st["span"] in fetch]
    if not host:
        return None
    outside = [b - a for a, b, active, *_ in s.get("steps", ()) if active > 0]
    api.say("sched.step", {"spans_in_window": len(steps), "span_median_ms": 1e3 * statistics.median(st["dur_s"] for st in steps),
                           "outside_calls": len(outside),
                           "outside_median_ms": 1e3 * statistics.median(outside) if outside else None})
    return 1e3 * statistics.median(host)
