"""Gap between two output tokens of one request, 99th percentile over every
such gap of the window, those of requests still running at its close included.

On the plain decode path a decoding slot emits one token every step, at the
end of the step's fetch. So the gaps are the distances between the ends of
``step.fetch`` in consecutive ``scheduler.step`` spans, each counted as often
as the later step's ``continued`` says: the slots that emitted a token there
which was not their first. (A request whose first token was picked at its
admission has its first gap counted from the step before that admission:
longer than it was by less than a step.) An admission between two steps (its
prefill, its pool copy) lengthens that one gap for every decoding slot, which
a per-request mean averages away. In a traced run the one gap that spans
``trace_host[0]`` is left out: starting the profiler halts the loop there.
Nearest rank, weighted. Nothing where the program records no such spans."""

import math

from perfbench import program_api_spans as api


def read(record):
    if record.get("serve") is None:
        return None
    steps = api.window_spans(record, "scheduler.step")
    if not steps:
        return None
    fetch = api.children(steps, "step.fetch")
    # (end of the step's last fetch, slots that emitted a token that was not their first)
    ends = [(max(f["t0_mono"] + f["dur_s"] for f in fetch[s["span"]]), int(s.get("continued", 0)))
            for s in steps if s["span"] in fetch]
    halted = (record.get("trace_host") or (None,))[0]
    gaps = [(b - a, n) for (a, _), (b, n) in zip(ends, ends[1:])
            if n > 0 and not (halted is not None and a <= halted <= b)]
    total = sum(n for _, n in gaps)
    if not total:
        return None
    gaps.sort()
    api.say("sched.itl", {"token_gaps": total, "beyond_p99": total - math.ceil(0.99 * total), "step_gaps": len(gaps),
                          "p50_ms": 1e3 * _weighted(gaps, total, 0.5), "max_ms": 1e3 * gaps[-1][0],
                          "spans_dropped_by_buffer": api.dropped()})
    return 1e3 * _weighted(gaps, total, 0.99)


def _weighted(gaps, total, q):
    """Nearest rank over sorted (gap, count) pairs."""
    rank, seen = math.ceil(q * total), 0
    for gap, n in gaps:
        seen += n
        if seen >= rank:
            return gap
    return gaps[-1][0]
