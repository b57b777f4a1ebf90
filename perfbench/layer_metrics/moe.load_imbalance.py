"""How uneven the routed load is, over the window: the rows the most-loaded
held expert received over an even share of the picks, ``moe_max_load`` /
(``moe_assign`` / experts held), both summed over the expert layers and the
counted decode steps (the program's counts on its ``scheduler.step`` spans).
1.0 is an even load; a grouped product's time follows its longest group.
Nothing where the program does not count the most-loaded expert."""

from perfbench import hybrid_counts


def read(record):
    counts = hybrid_counts.window_load(record)
    if counts is None or not counts["moe_assign"]:
        return None
    m = record["config"]["model"]
    held = m.get("moe_experts_held") or m["moe_experts"]
    return counts["moe_max_load"] / (counts["moe_assign"] / held)
