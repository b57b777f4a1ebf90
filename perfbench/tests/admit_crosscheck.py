"""The fetch-to-fetch clock (``perfbench/device_gaps.py``) against the device's
own trace, on one clock. Run by hand on the chip:

    python3 -m perfbench.tests.admit_crosscheck --workload <cell> --seed <n> --seconds <s> [--dump <file>]

A traced run of the cell, as ``perfbench.tests.traced_run`` makes one. The
program's ``step.fetch`` spans are host events of the profiler's trace, so the
slice's device gaps can be laid over the ``XLA Modules`` events between the
same two fetch ends. The note ``admit_crosscheck`` gives two pairs, each the
spans' reading beside the modules': ``plain`` (the median usable gap with no
prefill before its step) against the module time of one pool step with its
picks, and what an admission's gaps read over ``plain`` for each prefill
(``device_gaps.admissions``) against the module time the prefill and whatever
else lies between the same fetch ends add.
Both take the slice's steps only, so the first of each pair is not the
window's ``prog.admit_dev_ms``. They should agree within 5 %: where they do
not, the rule that calls a gap usable is wrong, and ``--dump`` writes what a
look at it needs: every gap of the window as ``device_gaps`` rows, and for the
slice's gaps the fetch durations and the module events inside each.

Edits no file of the benchmark: it wraps two functions of ``perfbench.run``
and ``perfbench.trace_reduce`` for this process and then calls ``run.main``.
"""

from __future__ import annotations

import json
import statistics
import sys

from perfbench import device_gaps, run, trace_reduce
from perfbench import program_api_spans as api

METRIC = "prog.admit_dev_ms"  # the reader whose call carries the run's record here


def clock_offset(fetch_spans: list[dict], host_events: list[tuple]) -> float | None:
    """Trace clock minus ``perf_counter`` clock: the same fetches are spans of
    the buffer and host events of the trace, so the shift of the two lists
    against each other under which their durations agree gives it."""
    if not fetch_spans or not host_events:
        return None
    best = None
    for shift in range(-3, 4):
        pairs = [(s, host_events[i + shift]) for i, s in enumerate(fetch_spans) if 0 <= i + shift < len(host_events)]
        if len(pairs) < len(fetch_spans) // 2:
            continue
        off = statistics.median(h[1] - s["t0_mono"] for s, h in pairs)
        # A mirror event lies inside its span: a little shorter, never 50 us off.
        agree = sum(1 for s, h in pairs if abs((h[2] - h[1]) - s["dur_s"]) < 5e-5) / len(pairs)
        if best is None or agree > best[0]:
            best = (agree, off)
    return best[1] if best and best[0] >= 0.9 else None


def crosscheck(record: dict, planes: dict, dump: str | None = None) -> dict | None:
    t0, t1 = record["trace_host"]
    if t0 is None or t1 is None:
        return None
    sliced = dict(record, t0=t0, t1=t1, trace_host=(None, None))
    got = device_gaps.device_gaps(sliced)
    steps = device_gaps.window_steps(sliced)
    devices = [d for d in planes["devices"].values() if d["modules"]]
    if got is None or len(devices) != 1:
        return None
    rows, plain = got
    fetches = sorted((f for fs in api.children(steps, "step.fetch").values() for f in fs), key=lambda f: f["t0_mono"])
    host = sorted((h for h in planes["host"] if h[0] == "step.fetch"), key=lambda h: h[1])
    off = clock_offset(fetches, host)
    if off is None:
        return {"error": "the buffer's fetches and the trace's could not be matched", "buffer": len(fetches), "trace": len(host)}
    modules = devices[0]["modules"]

    def inside(row):  # the module events between the row's two fetch ends
        return [(n, e - s) for n, s, e in modules if row["start"] + off < (s + e) / 2 <= row["end"] + off]

    laid = [dict(row, modules=[[n.split("(")[0], d] for n, d in inside(row)]) for row in rows]
    if dump:
        with open(dump, "w") as f:
            json.dump({"window": (device_gaps.device_gaps(record) or ([],))[0], "slice": laid,
                       "slice_fetch_ms": [1e3 * f_["dur_s"] for f_ in fetches]}, f)
    plain_rows = [r for r in laid if r["why_not"] is None and r["prefills"] == 0]
    if not plain_rows:
        return None
    step_mod = statistics.median(sum(d for _, d in r["modules"]) for r in plain_rows)
    names: dict[str, list[float]] = {}
    for r in plain_rows:
        for n, d in r["modules"]:
            names.setdefault(n, []).append(d)
    out = {"slice_steps": len(steps), "clock_offset_s": off, "plain_gaps": len(plain_rows),
           "plain_ms": {"spans": 1e3 * plain, "modules": 1e3 * step_mod, "spans_over_modules": plain / step_mod},
           "modules_in_a_plain_gap_median_ms": {n: [len(v) / len(plain_rows), 1e3 * statistics.median(v)] for n, v in sorted(names.items())}}
    # An admission's gaps (its step's and, where plain, the one before) over plain, both ways.
    admitted = [a for a in device_gaps.admissions(laid, plain) if a["why_not"] is None]
    if admitted:
        spans_ms = 1e3 * statistics.median(a["extra_s"] / a["prefills"] for a in admitted)
        mods_ms = 1e3 * statistics.median(
            sum(sum(d for _, d in g["modules"]) - step_mod for g in a["gaps"]) / a["prefills"] for a in admitted)
        alone = [d for a in admitted for g in a["gaps"] for n, d in g["modules"] if "_slot_prefill" in n]
        out["admission_steps"] = len(admitted)
        out["admit_dev_ms"] = {"spans": spans_ms, "modules": mods_ms, "spans_over_modules": spans_ms / mods_ms,
                               "prefill_module_alone_median_ms": 1e3 * statistics.median(alone) if alone else None}
    return out


def main() -> int:
    reduce_dir, load_reader = trace_reduce.reduce_dir, run.load_reader
    held = {}
    dump = None
    if "--dump" in sys.argv:
        at = sys.argv.index("--dump")
        dump = sys.argv[at + 1]
        del sys.argv[at:at + 2]

    def reduce_and_keep(trace_dir, span_names):
        path = trace_reduce.find_xplane(trace_dir)
        if path is not None:
            held["planes"] = trace_reduce.read_planes(path, {"step.fetch"})
        return reduce_dir(trace_dir, span_names)

    def reader_with_crosscheck(directory, metric):
        read = load_reader(directory, metric)
        if metric != METRIC or read is None:
            return read

        def read_and_check(record):
            if "planes" in held:
                try:
                    found = crosscheck(record, held["planes"], dump) or {"error": "nothing usable in the slice"}
                except Exception as e:  # noqa: BLE001 - the run's result line is worth more than this note
                    found = {"error": repr(e)}
                api.say("admit_crosscheck", found)
            return read(record)

        return read_and_check

    trace_reduce.reduce_dir, run.load_reader = reduce_and_keep, reader_with_crosscheck
    sys.argv = [sys.argv[0], *sys.argv[1:], "--trace", "1"]
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
