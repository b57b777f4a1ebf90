"""Per request, (time of last token - time of first token) / (output tokens - 1);
90th percentile over the cell's population (requests due inside the window, or,
under a backlog, requests completed inside it)."""

from perfbench.stats import percentile


def read(record):
    s = record.get("serve")
    if s is None:
        return None
    vals = [r["tpot_s"] for r in s["population"] if r.get("tpot_s") is not None]
    return 1e3 * percentile(vals, 90) if vals else None
