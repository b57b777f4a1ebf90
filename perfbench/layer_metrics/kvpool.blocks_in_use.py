"""Peak blocks in use over blocks in the pool during the window, from the pool's
own accounting, sampled after each ``step()``."""


def read(record):
    s = record.get("serve")
    if s is None or not s["steps"]:
        return None
    return 100.0 * max(st[3] for st in s["steps"]) / s["pool_blocks"]
