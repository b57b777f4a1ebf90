"""What one admission holds every decoding slot for, on the device: over the
window's steps with a prefill or more enqueued before them, the median of
what their device gaps read over ``plain`` (``perfbench/device_gaps.py``:
the step's own gap and, where it is a plain step's, the one before it) for
each of the step's ``prefills``. That is ``_slot_prefill_paged`` with its
copies of the pools and whatever else an admission enqueues, read from the
program's own spans on the fetch-to-fetch clock. The note gives ``plain_ms``,
the admissions read and those left out (by why a gap is not usable: a
``drain``, a fetch that found the device done, the profiler's start), the
most prefills before one step, and ``[steps, median ms]`` by
``prefill_tokens`` over the steps with exactly one prefill. Nothing where the
program's spans carry no ``prefills``."""

import collections
import statistics

from perfbench import device_gaps
from perfbench import program_api_spans as api


def read(record):
    got = device_gaps.device_gaps(record)
    if got is None:
        return None
    rows, plain = got
    admitted = device_gaps.admissions(rows, plain)
    usable = [a for a in admitted if a["why_not"] is None]
    if not usable:
        return None
    left_out: collections.Counter = collections.Counter()
    by_tokens = collections.defaultdict(list)
    for a in admitted:
        if a["why_not"] is not None:
            left_out[a["why_not"]] += a["prefills"]
        elif a["prefills"] == 1:
            by_tokens[a["prefill_tokens"]].append(1e3 * a["extra_s"])
    api.say("prog.admit_dev", {
        "plain_ms": 1e3 * plain, "plain_gaps": sum(1 for r in rows if r["why_not"] is None and not r["prefills"]),
        "admissions_read": sum(a["prefills"] for a in usable), "admissions_left_out": left_out,
        "max_prefills_before_one_step": max(a["prefills"] for a in admitted),
        "one_prefill_steps_and_median_ms_by_prefill_tokens": {
            str(k): [len(v), statistics.median(v)] for k, v in sorted(by_tokens.items())},
        "spans_dropped_by_buffer": api.dropped()})
    return 1e3 * statistics.median(a["extra_s"] / a["prefills"] for a in usable)
