"""A traced run of one cell that also shows what the benchmark's own command
does not: the end-to-end metrics of the traced run (what tracing costs when it
is on), and the device's idle seconds named by the PROGRAM's spans
(``step.*``, ``admit.*``), which ``trace_reduce.read_planes`` drops when it is
given the benchmark's span names. Run by hand on the chip:

    python3 -m perfbench.tests.traced_run --workload <cell> --seed <n> --seconds <s>

Edits no file of the benchmark: it wraps three functions of ``perfbench.run``
and ``perfbench.trace_reduce`` for this process and then calls ``run.main``.
"""

from __future__ import annotations

import json
import statistics
import sys

from perfbench import run, trace_reduce

STEP_PHASES = ("step.prepare", "step.build", "step.dispatch", "step.fetch", "step.bookkeep")
ADMIT_PHASES = ("admit.encode", "admit.blocks", "admit.prefill_dispatch", "admit.first_pick", "prefix.match",
                "prefix.restore", "prefix.insert")
# Leaves first: a moment of idle time goes to the first of these names whose span covers it.
PROGRAM_SPANS = (*STEP_PHASES, *ADMIT_PHASES, "train.data_wait", "train.step", "scheduler.step", "train.fit")


def idle_overlap(planes: dict, outer_names) -> dict | None:
    """The device's idle seconds inside the traced window, split over the host
    spans by OVERLAP (``trace_reduce.reduce`` gives a whole gap to the span
    over its middle, which is right for naming gaps and wrong for a budget by
    phase: one gap runs from a step's bookkeeping through the next step's
    prepare and build into its dispatch). A moment goes to the program's
    innermost span over it, else to the benchmark's span over it, else to
    ``no_span``."""
    devices = [d for d in planes["devices"].values() if d["ops"] or d["modules"]]
    window = [h for h in planes["host"] if h[0] == trace_reduce.WINDOW_SPAN]
    if len(devices) != 1 or not window:
        return None
    lo, hi = window[0][1], window[0][2]
    busy = trace_reduce._clip(trace_reduce._union([(a, b) for _, a, b in devices[0]["ops"] or devices[0]["modules"]]), lo, hi)
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    order = {n: i for i, n in enumerate((*PROGRAM_SPANS, *sorted(outer_names)))}
    spans = sorted((h for h in planes["host"] if h[0] in order), key=lambda h: h[1])
    out: dict[str, float] = {}
    for a, b in gaps:
        # Cut the gap at every span edge inside it; each piece has one owner.
        inside = [h for h in spans if h[2] > a and h[1] < b]
        cuts = sorted({a, b, *[t for h in inside for t in h[1:] if a < t < b]})
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            cover = [h for h in inside if h[1] <= mid <= h[2]]
            who = min(cover, key=lambda h: order[h[0]])[0] if cover else "no_span"
            out[who] = out.get(who, 0.0) + (y - x)
    return {"idle_s": sum(b - a for a, b in gaps), "gaps": len(gaps),
            "by_span": sorted(([k, v] for k, v in out.items()), key=lambda r: -r[1])}


def main() -> int:
    reduce_dir, wanted, load_reader = trace_reduce.reduce_dir, run.wanted_metrics, run.load_reader

    def reduce_and_attribute(trace_dir, span_names):
        path = trace_reduce.find_xplane(trace_dir)
        if path is not None:
            planes = trace_reduce.read_planes(path, None)
            names = set(span_names) | set(PROGRAM_SPANS) | {trace_reduce.WINDOW_SPAN}
            red = trace_reduce.reduce(planes, names)
            host = [h for h in planes["host"] if h[0] in PROGRAM_SPANS]
            by_name: dict[str, list[float]] = {}
            for name, a, b in host:
                by_name.setdefault(name, []).append(b - a)
            if red is not None:
                print(json.dumps({"note": "idle_by_program_span", "window_s": red["window_s"], "busy_s": red["busy_s"],
                                  "idle_by_span": red["idle_by_span"], "longest_gaps": red["longest_gaps"],
                                  "idle_overlap": idle_overlap(planes, span_names),
                                  "program_host_events": {k: {"n": len(v), "sum_s": sum(v), "median_ms": 1e3 * statistics.median(v)}
                                                          for k, v in sorted(by_name.items())}}), flush=True)
        return reduce_dir(trace_dir, span_names)

    def both_sections(section, cell):
        return wanted("end_to_end", cell) + wanted("per_layer", cell) if section == "per_layer" else wanted(section, cell)

    def either_directory(directory, metric):
        return load_reader("layer_metrics", metric) or load_reader("end_to_end", metric)

    trace_reduce.reduce_dir, run.wanted_metrics, run.load_reader = reduce_and_attribute, both_sections, either_directory
    sys.argv = [sys.argv[0], *sys.argv[1:], "--trace", "1"]
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
