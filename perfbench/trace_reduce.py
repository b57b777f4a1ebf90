"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer metrics read.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else. Busy time is
the UNION of the intervals in which an operation ran on a device (overlapping
events are not counted twice), clipped to the traced window; the window is the
benchmark's own host span ``perfbench.trace`` (on the trace's clock), or the
span of the device's events where the host span cannot be placed against them.
Idle gaps are the window minus the union, each named by the innermost host span
of the benchmark that covers its middle.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "perfbench.trace"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


_HLO = re.compile(r"^%?(?P<name>\S+) = \(?(?P<shape>\w+\[[\d,]*\])[^ ]* (?:[^ ]+ )*?(?P<opcode>[\w\-]+)\(")


def op_label(text: str) -> str:
    """A device event's name is the whole HLO instruction. The label keeps what
    tells operations apart and drops what tells instances apart: the op's name
    without its number, the opcode, the (first) output shape, a fusion's kind.
    ``%_pool_step_paged_flash.104 = bf16[48,2,12,128]{..} custom-call(..)`` ->
    ``_pool_step_paged_flash custom-call bf16[48,2,12,128]``."""
    m = _HLO.match(text)
    if m is None:
        return text[:120]
    kind = re.search(r"kind=(\w+)", text)
    base = re.sub(r"[.\d]+$", "", m["name"])
    return " ".join(filter(None, [base, m["opcode"] if m["opcode"] != base else "", m["shape"], kind and kind[1]]))


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def read_planes(path: str, span_names=None) -> dict:
    """{"devices": {plane: {"ops": [(name, start, end)], "modules": [...]}},
    "host": [(name, start, end)]} with times in seconds on the trace's clock.
    Host events are kept only for names in ``span_names`` (all when None)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, dict] = {}
    host: list[tuple[str, float, float]] = []
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:") and "TPU" in name.upper() or name.startswith("/device:GPU"):
            dev = devices.setdefault(name, {"ops": [], "modules": [], "lines": []})
            for line in plane.lines:
                dev["lines"].append(line.name)
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                key = "ops" if line.name == OPS_LINE else "modules"
                for e in line.events:
                    s = e.start_ns * 1e-9
                    dev[key].append((e.name, s, s + e.duration_ns * 1e-9))
        elif name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if span_names is None or e.name in span_names:
                        s = e.start_ns * 1e-9
                        host.append((e.name, s, s + e.duration_ns * 1e-9))
    return {"devices": devices, "host": host}


def reduce(planes: dict, span_names) -> dict | None:
    """The reduced trace: window, busy seconds (mean over devices), time per
    operation name and per module name, idle seconds by covering host span."""
    devices = {k: v for k, v in planes["devices"].items() if v["ops"] or v["modules"]}
    if not devices:
        return None
    first = min(s for d in devices.values() for _, s, _ in d["ops"] or d["modules"])
    last = max(e for d in devices.values() for _, _, e in d["ops"] or d["modules"])
    spans = [h for h in planes["host"] if h[0] in span_names]
    window = [h for h in planes["host"] if h[0] == WINDOW_SPAN]
    source = "device_events"
    lo, hi = first, last
    if window:
        wlo, whi = window[0][1], window[0][2]
        # The host span places the window only if the device's events lie in it.
        if wlo - 0.05 <= first and last <= whi + 0.05:
            lo, hi, source = wlo, whi, "host_span"
    busy, gaps_by, gap_list = [], {}, []
    op_time: dict[str, list[float]] = {}
    mod_time: dict[str, list[float]] = {}
    for dev in devices.values():
        events = dev["ops"] or dev["modules"]
        merged = _clip(_union([(s, e) for _, s, e in events]), lo, hi)
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [t for ab in merged for t in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a <= 0:
                continue
            mid = (a + b) / 2
            cover = [h for h in spans if h[1] <= mid <= h[2] and h[0] != WINDOW_SPAN]
            who = min(cover, key=lambda h: h[2] - h[1])[0] if cover else "no_span"
            gaps_by[who] = gaps_by.get(who, 0.0) + (b - a)
            gap_list.append((who, b - a))
        for key, table in (("ops", op_time), ("modules", mod_time)):
            for name, s, e in dev[key]:
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    t = table.setdefault(name, [0.0, 0])
                    t[0] += e - s
                    t[1] += 1
    n = len(devices)
    return {
        "window_s": hi - lo,
        "window_source": source,
        "busy_s": sum(busy) / n,
        "devices": n,
        "ops": sorted(([k, v[0] / n, v[1]] for k, v in op_time.items()), key=lambda r: -r[1]),
        "ops_by_label": _by_label(op_time, n),
        "modules": sorted(([k, v[0] / n, v[1]] for k, v in mod_time.items()), key=lambda r: -r[1]),
        "module_events": sorted(
            (name, e - s) for d in devices.values() for name, s, e in d["modules"] if s >= lo and e <= hi
        ),
        "idle_by_span": sorted(([k, v / n] for k, v in gaps_by.items()), key=lambda r: -r[1]),
        "longest_gaps": sorted(gap_list, key=lambda r: -r[1])[:10],
        "lines": sorted({ln for d in devices.values() for ln in d["lines"]}),
    }


def _by_label(op_time: dict, n: int) -> list:
    out: dict[str, float] = {}
    for name, (seconds, _) in op_time.items():
        label = op_label(name)
        out[label] = out.get(label, 0.0) + seconds / n
    return sorted(([k, v] for k, v in out.items()), key=lambda r: -r[1])


def reduce_dir(trace_dir: str, span_names) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce(read_planes(path, set(span_names) | {WINDOW_SPAN}), set(span_names))
