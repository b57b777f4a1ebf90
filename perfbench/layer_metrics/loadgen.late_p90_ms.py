"""How late the load generator submitted: submit time minus due time, 90th
percentile over the requests due inside the window."""

from perfbench.stats import percentile


def read(record):
    s = record.get("serve")
    if s is None or not s["population"]:
        return None
    return 1e3 * percentile([r["late_s"] for r in s["population"]], 90)
