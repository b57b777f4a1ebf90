"""Share of the held experts that received a token in a decode step, over the
window: ``moe_hit`` / (``moe_steps`` x expert layers x experts held), from the
program's counts on its ``scheduler.step`` spans. The bytes a step streams
follow it. Nothing where the program counts no experts."""

from perfbench import flops_bytes_moe, moe_counts


def read(record):
    counts = moe_counts.window_moe(record)
    if counts is None:
        return None
    m = record["config"]["model"]
    return 100.0 * counts["moe_hit"] / (counts["moe_steps"] * flops_bytes_moe.expert_layers(m) * m["moe_experts_held"])
