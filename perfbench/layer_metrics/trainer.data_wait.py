"""Share of the window spent inside ``next()`` of the batch iterator the trainer
consumes (the dataset's own, prefetching)."""


def read(record):
    t = record.get("train")
    return None if t is None else 100.0 * t["data_wait_s"] / record["window_s"]
