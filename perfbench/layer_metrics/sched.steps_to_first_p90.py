"""Per request, the number of ``sched.step()`` calls that ended between its
admission and its first token (the prompt tail walks one token a step),
90th percentile over the answered requests due inside the window."""

import bisect

from perfbench.stats import percentile


def read(record):
    s = record.get("serve")
    if s is None:
        return None
    ends = [st[1] for st in s["all_steps"]]
    vals = [
        bisect.bisect_right(ends, r["t_first"]) - bisect.bisect_right(ends, r["t_admit"])
        for r in s["population"] if r.get("t_first") is not None
    ]
    return percentile(vals, 90) if vals else None
