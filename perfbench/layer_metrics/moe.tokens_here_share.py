"""The share of the tokens an expert layer routed that have a pick held on this
chip: ``moe_tokens_here`` / (``moe_tokens`` x expert layers) over the window's
``scheduler.step`` spans, in % (the program's counts, read every few steps;
``moe_tokens``: the rows the counted steps fed). Under group-limited routing it
is the share of the rows that a dispatch sending a token once a chip moves to
this chip. Nothing where the program counts no such tokens (an earlier commit,
a model without expert layers)."""

from perfbench import flops_bytes_moe, program_api_spans


def read(record):
    if record.get("serve") is None:
        return None
    rows = [s for s in program_api_spans.window_spans(record, "scheduler.step") or []
            if "moe_tokens_here" in s and "moe_tokens" in s]
    tokens = sum(s["moe_tokens"] for s in rows)
    if not tokens:
        return None
    return 100.0 * sum(s["moe_tokens_here"] for s in rows) / (tokens * flops_bytes_moe.expert_layers(record["config"]["model"]))
