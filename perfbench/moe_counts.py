"""What the expert layers and the window layers counted, from the program's
own ``scheduler.step`` spans (``perfbench/program_api_spans.py``).

The scheduler puts ``attn_pos_full`` and ``attn_pos_band`` on every step's
span (the positions a full and a window layer attend, summed over the step's
active slots), and every few steps ``moe_assign``, ``moe_hit``, ``moe_steps``
and ``moe_tokens``: router picks that landed on an expert held here, experts
that received a token (both summed over the expert layers), the steps those
cover and the slots those steps fed. A program that records none of these
(an earlier commit, a model without such layers) gives ``None``.
"""

from __future__ import annotations

from perfbench import program_api_spans as api

MOE_KEYS = ("moe_assign", "moe_hit", "moe_steps", "moe_tokens")
ATTN_KEYS = ("attn_pos_full", "attn_pos_band")


def _sums(spans, keys):
    rows = [s for s in spans or [] if all(k in s for k in keys)]
    if not rows:
        return None
    out = {k: float(sum(s[k] for s in rows)) for k in keys}
    out["spans"] = len(rows)
    return out


def window_moe(record: dict) -> dict | None:
    """Sums of the expert counts over the window's step spans."""
    if record.get("serve") is None:
        return None
    got = _sums(api.window_spans(record, "scheduler.step"), MOE_KEYS)
    return got if got and got["moe_steps"] > 0 else None


def slice_attention(record: dict) -> dict | None:
    """Sums of the attended positions over the step spans that began in the
    traced slice, and how many they are."""
    if record.get("serve") is None or record.get("trace_host") is None or None in record["trace_host"]:
        return None
    t0, t1 = record["trace_host"]
    return _sums(api.spans("scheduler.step", t0, t1), ATTN_KEYS)


def slice_pool_steps(record: dict) -> int:
    """Decode steps in the traced slice: the pool-step program's module events."""
    tr = record.get("trace")
    return 0 if tr is None else sum(1 for name, _ in tr["module_events"] if "_pool_step_paged_flash" in name)


def kernel_seconds(record: dict, kernel: str) -> float:
    """Device seconds of the operations named ``kernel`` in the traced slice."""
    import re

    tr = record.get("trace")
    if tr is None:
        return 0.0
    named = re.compile(rf"^%?{re.escape(kernel)}(\.\d+)* ")
    return sum(t for name, t, _ in tr["ops"] if named.match(name))
