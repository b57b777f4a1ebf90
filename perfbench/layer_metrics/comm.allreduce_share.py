"""The gradient all-reduce's share of the device's work in the traced slice of
a training run across chips: device seconds of the operations whose names
begin ``all-reduce`` (the ``-start`` and ``-done`` halves of an asynchronous
one too) over the device seconds of ALL operations, both summed over the
chips the trace holds (``perfbench/trace_reduce.py`` gives each operation's
seconds as the mean over the chips: the ratio is the same). A sum over
operations, not a union: where the all-reduce runs beside compute both count.
Nothing in a serving run, without a trace, or where no operation has such a
name (one chip)."""


def read(record):
    tr = record.get("trace")
    if tr is None or record.get("train") is None:
        return None
    total = sum(t for _, t, _ in tr["ops"])
    reduce_s = sum(t for name, t, _ in tr["ops"] if name.lstrip("%").startswith("all-reduce"))
    if total <= 0 or reduce_s <= 0:
        return None
    return 100.0 * reduce_s / total
