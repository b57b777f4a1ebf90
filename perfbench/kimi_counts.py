"""What a model of delta-rule and latent layers counted, from the program's own
``scheduler.step`` spans (``perfbench/program_api_spans.py``); beside
``perfbench/hybrid_counts.py``, whose sums need that file's own keys.

Every step's span carries ``active`` (the slots the step fed: the states
``kda_step`` updates) and, for a model with layer kinds, ``attn_pos_full``
(the positions a layer that attends the whole cache reads, summed over those
slots: what the latent layer reads). A program that records neither gives
``None``.
"""

from __future__ import annotations

from perfbench import program_api_spans as api


def slice_steps(record: dict) -> dict | None:
    """Sums of ``active`` and ``attn_pos_full`` over the step spans that began
    in the traced slice, and how many they are."""
    if record.get("serve") is None or record.get("trace_host") is None or None in record["trace_host"]:
        return None
    t0, t1 = record["trace_host"]
    rows = [s for s in api.spans("scheduler.step", t0, t1) or [] if "active" in s and "attn_pos_full" in s]
    if not rows:
        return None
    return {"active": float(sum(s["active"] for s in rows)),
            "attn_pos_full": float(sum(s["attn_pos_full"] for s in rows)), "spans": len(rows)}
