"""What held the serving loop up: the longest gaps between the ends of two
consecutive ``step()`` calls, each split by where the time went. A saturated
run throws a pause of 2 to 5 s now and then (PERF.md section 6, PR 31): one
such pause inside the window costs 4 to 9 % of ``serve_tok_s`` and lifts
``tpot_p90_ms`` by a fifth, so every run says what its longest gaps were made
of: garbage collection (``gc.callbacks``), the wait for the device (the
program's ``step.fetch`` spans), admissions (``serve.admit``), and the CPU
time the loop's thread used in it: a gap that is none of the three and used no
CPU was spent blocked or descheduled, which no code of this repo can cure.
"""

from __future__ import annotations

import gc
import time


class GcClock:
    """Start, length and generation of every garbage collection from now on
    (a callback a collection: some twenty-five in a 51-second window)."""

    def __init__(self):
        self.events: list[tuple[float, float, int]] = []
        self._began = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._began = time.perf_counter()
        elif self._began is not None:
            self.events.append((self._began, time.perf_counter() - self._began, info["generation"]))
            self._began = None

    def close(self):
        gc.callbacks.remove(self._on)


def overlap_s(a: float, b: float, intervals) -> float:
    """Seconds of ``[a, b]`` covered by ``(start, length)`` intervals that do not overlap each other."""
    return sum(max(0.0, min(b, s + d) - max(a, s)) for s, d in intervals)


def longest_gaps(step_ends: list[tuple[float, float]], origin: float, window: tuple[float, float], parts: dict,
                 top: int = 3) -> list[dict]:
    """The ``top`` longest gaps between consecutive step ends, longest first.
    ``step_ends``: ``(perf_counter, thread_time)`` at the end of each step.
    A gap: when it began (seconds after ``origin``), whether it lies in the
    window, its length, the CPU time the thread used in it, and the
    milliseconds of it inside each of ``parts`` (name -> ``(start, length)``
    intervals on the ``perf_counter`` clock)."""
    gaps = sorted(zip(step_ends, step_ends[1:]), key=lambda ab: ab[0][0] - ab[1][0])[:top]
    return [{"at_s": round(a - origin, 2), "in_window": window[0] <= a and b <= window[1], "ms": 1e3 * (b - a),
             "loop_thread_cpu_ms": 1e3 * (cpu_b - cpu_a),
             **{f"{name}_ms": 1e3 * overlap_s(a, b, iv) for name, iv in parts.items()}} for (a, cpu_a), (b, cpu_b) in gaps]
