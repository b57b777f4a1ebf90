"""Median wall time of a ``sched.step()`` call of the window that had a slot
active (host clock around the call, from the benchmark's own loop; the call
ends in the pick's fetch, which synchronises)."""

import statistics


def read(record):
    s = record.get("serve")
    if s is None:
        return None
    d = [st[1] - st[0] for st in s["steps"] if st[2] > 0]
    return 1e3 * statistics.median(d) if d else None
