"""The arithmetic that names a pause: overlaps, the longest gaps between step
ends with their parts, and the collector's clock."""

import gc

import pytest

from perfbench import stalls


def test_overlap_counts_only_what_lies_inside():
    assert stalls.overlap_s(10.0, 12.0, [(9.0, 0.5), (9.5, 1.0), (11.0, 0.25), (11.9, 5.0), (13.0, 1.0)]) == pytest.approx(0.5 + 0.25 + 0.1)
    assert stalls.overlap_s(10.0, 12.0, []) == 0.0


def test_longest_gaps_come_longest_first_with_their_parts():
    ends = [(100.00, 5.00), (100.02, 5.01), (100.04, 5.02), (103.04, 5.03), (103.06, 5.04), (103.56, 5.54), (103.58, 5.55)]
    parts = {"gc": [(100.05, 2.9)], "device_wait": [(100.021, 0.018), (103.07, 0.48)], "admit": []}
    got = stalls.longest_gaps(ends, origin=60.0, window=(100.03, 200.0), parts=parts, top=2)
    assert [g["at_s"] for g in got] == [40.04, 43.06] and [g["in_window"] for g in got] == [True, True]
    assert got[0]["ms"] == pytest.approx(3000.0) and got[0]["gc_ms"] == pytest.approx(2900.0) and got[0]["device_wait_ms"] == 0.0
    assert got[0]["loop_thread_cpu_ms"] == pytest.approx(10.0) and got[1]["loop_thread_cpu_ms"] == pytest.approx(500.0)
    assert got[1]["ms"] == pytest.approx(500.0) and got[1]["device_wait_ms"] == pytest.approx(480.0) and got[1]["admit_ms"] == 0.0
    every = {g["at_s"]: g["in_window"] for g in stalls.longest_gaps(ends, 60.0, (100.03, 200.0), parts, top=9)}
    assert len(every) == 6 and every[40.0] is False and every[40.02] is False and every[43.56] is True  # two began before it opened
    assert stalls.longest_gaps([(100.0, 1.0)], 60.0, (0.0, 1.0), parts) == []


def test_the_collector_is_timed_until_its_clock_is_closed():
    clock = stalls.GcClock()
    try:
        gc.collect()
        assert clock.events and all(length >= 0.0 and gen in (0, 1, 2) for _, length, gen in clock.events)
        assert clock.events[-1][2] == 2
    finally:
        clock.close()
    n = len(clock.events)
    gc.collect()
    assert len(clock.events) == n  # closed: no longer listening
