"""Device time of one train step: the median duration of the train-step module's
events on the trace's ``XLA Modules`` line. Without such a line: host clock,
window over steps (printed as a note by which it was)."""

import statistics


def read(record):
    t, tr = record.get("train"), record.get("trace")
    if t is None:
        return None
    if tr is not None:
        d = [s for name, s in tr["module_events"] if "train_step" in name]
        if d:
            return 1e3 * statistics.median(d)
    return 1e3 * record["window_s"] / max(t["steps"], 1)
