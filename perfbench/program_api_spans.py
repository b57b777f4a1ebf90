"""The second file of the benchmark that imports the system under test: the
spans the program itself recorded (``transformer_tpu.obs.trace.buffer()``).

The program keeps its closed spans in memory, process-wide, each with
``t0_mono``: ``time.perf_counter()`` at its start, the clock of the
benchmark's ``t0``/``t1``, its ``steps`` tuples and ``trace_host``. A metric
reader is loaded after the run and gets the run's ``record``, which holds
neither the scheduler nor the trainer; it reads the buffer through here. A
program without such a buffer (an earlier commit) gives ``None``, and the
reader then reports nothing.
"""

from __future__ import annotations

import json


def _buffer():
    try:
        from transformer_tpu.obs import trace
    except ImportError:
        return None
    get = getattr(trace, "buffer", None)
    return None if get is None else get()


def spans(name: str, t0: float, t1: float) -> list[dict] | None:
    """The closed spans called ``name`` that BEGAN in ``[t0, t1]`` on the
    ``perf_counter`` clock, oldest first; ``None`` where the program keeps no
    buffer. A span's end is ``t0_mono + dur_s``."""
    buf = _buffer()
    if buf is None:
        return None
    out = [s for s in buf.snapshot() if s.get("name") == name and t0 <= s.get("t0_mono", float("-inf")) <= t1]
    out.sort(key=lambda s: s["t0_mono"])
    return out


def window_spans(record: dict, name: str) -> list[dict] | None:
    """``spans`` over the run's measured window."""
    if record.get("t0") is None or record.get("t1") is None:
        return None
    return spans(name, record["t0"], record["t1"])


def children(parents: list[dict], name: str) -> dict[str, list[dict]]:
    """The closed spans called ``name`` whose parent is one of ``parents``,
    by the parent's span id, oldest first."""
    wanted = {p["span"] for p in parents}
    out: dict[str, list[dict]] = {}
    for s in spans(name, float("-inf"), float("inf")) or []:
        if s.get("parent") in wanted:
            out.setdefault(s["parent"], []).append(s)
    return out


def dropped() -> int:
    """Spans the buffer has dropped since the process began (it is bounded)."""
    buf = _buffer()
    return 0 if buf is None else int(buf.dropped)


def say(label: str, fields: dict) -> None:
    """A note line of a reader, like ``Context.say``: a line of standard
    output before the last."""
    print(json.dumps({"note": label, **fields}, default=float), flush=True)
