"""Router picks of a token that landed on an expert held here, a layer:
``moe_assign`` / (``moe_tokens`` x expert layers) over the window, from the
program's counts (``moe_tokens``: the slots the counted steps fed). With half
of the experts here it reads near half of the picks a token; the full count
would mean the share is not a share. Nothing where the program counts no
experts."""

from perfbench import flops_bytes_moe, moe_counts


def read(record):
    counts = moe_counts.window_moe(record)
    if counts is None or not counts["moe_tokens"]:
        return None
    return counts["moe_assign"] / (counts["moe_tokens"] * flops_bytes_moe.expert_layers(record["config"]["model"]))
