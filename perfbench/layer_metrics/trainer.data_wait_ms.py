"""What the trainer's loop waits for its next batch: median duration of the
``train.data_wait`` spans begun inside the window, the trainer's own span
around each ``next()`` of the iterator it consumes.

A median, not a share: what the trainer iterates in this cell is the
benchmark's ``WindowedBatches``, whose ``next()`` also counts lengths and, once
in a traced run, synchronises and starts the profiler; and the last ``next()``
begun in the window is the one that closes it, with a synchronisation. The
outside metric (``trainer.data_wait``) leaves those out, so the two need not
agree. As many spans begin in the window as it has steps: the first step's
began before it opened. Nothing where the program records no such spans."""

import statistics

from perfbench import program_api_spans as api


def read(record):
    t = record.get("train")
    if t is None:
        return None
    waits = api.window_spans(record, "train.data_wait")
    if not waits:
        return None
    d = sorted(w["dur_s"] for w in waits)
    api.say("trainer.data_wait", {"spans_in_window": len(d), "steps": t["steps"], "max_ms": 1e3 * d[-1],
                                  "sum_share_of_window": sum(d) / record["window_s"]})
    return 1e3 * statistics.median(d)
