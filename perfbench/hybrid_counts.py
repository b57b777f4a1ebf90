"""What a model with layers that keep no KV rows counted, from the program's
own ``scheduler.step`` spans (``perfbench/program_api_spans.py``); beside
``perfbench/moe_counts.py``, whose sums need that file's own keys.

The scheduler puts ``attn_pos_full`` on every step's span of a model with
layer kinds (the positions a full layer attends, summed over the step's
active slots; ``attn_pos_band`` beside it only where a kind has a window), and
every few steps, with the expert counts, ``moe_max_load``: the rows the
most-loaded held expert received, summed over the expert layers and the
counted steps. A program that records none of these (an earlier commit, a
model without such layers) gives ``None``.
"""

from __future__ import annotations

from perfbench import program_api_spans as api


def slice_positions(record: dict) -> dict | None:
    """Sums of the attended positions over the step spans that began in the
    traced slice: ``attn_pos_full``, and ``attn_pos_band`` (0.0 where no span
    has it: no layer has a window)."""
    if record.get("serve") is None or record.get("trace_host") is None or None in record["trace_host"]:
        return None
    t0, t1 = record["trace_host"]
    rows = [s for s in api.spans("scheduler.step", t0, t1) or [] if "attn_pos_full" in s]
    if not rows:
        return None
    return {"attn_pos_full": float(sum(s["attn_pos_full"] for s in rows)),
            "attn_pos_band": float(sum(s.get("attn_pos_band", 0) for s in rows)), "spans": len(rows)}


def window_load(record: dict) -> dict | None:
    """Sums of ``moe_max_load``, ``moe_assign`` and ``moe_steps`` over the
    window's step spans that carry all three."""
    if record.get("serve") is None:
        return None
    keys = ("moe_max_load", "moe_assign", "moe_steps")
    rows = [s for s in api.window_spans(record, "scheduler.step") or [] if all(k in s for k in keys)]
    if not rows:
        return None
    return {k: float(sum(s[k] for s in rows)) for k in keys} | {"spans": len(rows)}
