"""Slots occupied over slots configured, sampled after each ``step()`` of the
window, mean."""


def read(record):
    s = record.get("serve")
    if s is None or not s["steps"]:
        return None
    return 100.0 * sum(st[2] for st in s["steps"]) / len(s["steps"]) / s["num_slots"]
