"""Share of the traced slice in which no operation ran on the device: 1 - the
union of the device-op intervals over the slice (perfbench/trace_reduce.py)."""


def read(record):
    tr = record.get("trace")
    if tr is None or record.get("serve") is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
