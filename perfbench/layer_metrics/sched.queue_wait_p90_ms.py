"""Submit to slot admission (``queue_s`` of the scheduler's request span),
90th percentile over the answered requests due inside the window."""

from perfbench.stats import percentile


def read(record):
    s = record.get("serve")
    if s is None:
        return None
    vals = [r["queue_s"] for r in s["population"] if r.get("queue_s") is not None]
    return 1e3 * percentile(vals, 90) if vals else None
