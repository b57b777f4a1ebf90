"""Time from when a request was DUE to its first generated token, 90th percentile
over every request due inside the window (a failed request counts as the drain
limit). The scheduler's ``ttft_s`` starts at submit; the generator's lateness
is added."""

from perfbench.stats import percentile


def read(record):
    s = record.get("serve")
    if s is None or not s["population"]:
        return None
    return 1e3 * percentile([r["ttft_due_s"] for r in s["population"]], 90)
