"""The share of the device's time that goes to admissions instead of decode
steps: what the window's steps with a prefill or more before them read over
``plain`` (``perfbench/device_gaps.py``: ``admissions``, each ``extra_s`` no
less than 0), summed, over the sum of all usable device gaps. Nothing where
the program's spans carry no ``prefills``."""

from perfbench import device_gaps


def read(record):
    got = device_gaps.device_gaps(record)
    if got is None:
        return None
    rows, plain = got
    extra = sum(max(0.0, a["extra_s"]) for a in device_gaps.admissions(rows, plain) if a["why_not"] is None)
    return 100.0 * extra / sum(r["gap_s"] for r in rows if r["why_not"] is None)
