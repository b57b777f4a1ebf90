"""The three readers of the fetch-to-fetch clock (``perfbench/device_gaps.py``)
on a hand-made buffer, in ``test_span_readers.py``'s manner: what they compute
on steps with 0, 1 and 3 prefills before them, which gaps they leave out, and
that they report nothing (and do not raise) where the program's spans carry no
``prefills``."""

import json

import pytest

from perfbench import device_gaps, program_api_spans
from perfbench.run import load_reader
from perfbench.tests.test_span_readers import SERVE, TRAIN, FakeBuffer, span

NAMES = ["prog.admit_dev_ms", "sched.admit_dev_share", "sched.ahead_share"]


@pytest.fixture
def buffer(monkeypatch):
    buf = FakeBuffer([])
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: buf)
    return buf.spans


def step(end, fetch_ms=8.0, ahead=1, prefills=0, tokens=0, **attrs):
    """A ``scheduler.step`` whose one fetch lasted ``fetch_ms`` and ended at ``end``."""
    t0, sid = end - 1e-3 * fetch_ms - 0.003, f"step@{end}"
    counts = {"active": 48, "overstepped": 0, "ahead": ahead, "prefills": prefills, "prefill_tokens": tokens, **attrs}
    return [span("scheduler.step", t0, end - t0 + 0.001, sid, **counts),
            span("step.fetch", end - 1e-3 * fetch_ms, 1e-3 * fetch_ms, parent=sid)]


def notes(capsys):
    return {n["note"]: n for n in map(json.loads, capsys.readouterr().out.strip().splitlines())}


def run_of_steps(buffer):
    """Steps of 10 ms; one admission of 256 tokens costs 20 ms, one of 512
    costs 30, three together 90; and every way a gap is left out."""
    t = 110.0
    buffer += step(t, ahead=0, drain="idle", prefills=1, tokens=256)  # the window's first: no step before it
    for gap, kw in [
        (0.010, {}), (0.010, {}),
        # The fetch between these two ended 2 ms late, beside the prefill that
        # had just begun: 2 + 18 ms over plain are the admission's 20.
        (0.012, {}), (0.028, dict(prefills=1, tokens=256)),
        (0.010, {}),
        (0.040, dict(prefills=1, tokens=512)),
        (0.100, dict(prefills=3, tokens=1024)),  # the gap before it is an admission's: its own alone
        (0.010, dict(overstepped=2)),
        # The admission's first pick had waited for the device: the step fetched
        # next is found done (a fetch of 0.05 ms), the one after it was not
        # ahead, and an admission behind that one has no usable gap before it.
        (0.055, dict(fetch_ms=0.05)),
        (0.012, dict(ahead=0, drain="first_pick", prefills=1, tokens=512)),
        (0.030, dict(prefills=1, tokens=256)),
        (0.010, {}),
        # A fetch that found the device done spoils its own gap and the next one.
        (0.035, dict(fetch_ms=0.05, prefills=2, tokens=640)),
        (0.010, {}),
        (0.010, {}),
    ]:
        t += gap
        buffer += step(t, **kw)
    buffer += [span("scheduler.step", t + 0.001, 0.0005, active=0)]  # every slot expired: no fetch, no counts
    return t


def test_nothing_to_read_gives_nothing(buffer, monkeypatch):
    reads = [load_reader("layer_metrics", n) for n in NAMES]
    for read in reads:
        for record in (SERVE, TRAIN, {"t0": 1.0, "t1": 2.0}):
            assert read(dict(record)) is None  # an empty buffer
    # An earlier program: steps with ``ahead`` and no ``prefills``.
    for end in (110.0, 110.01, 110.02):
        sid = f"old@{end}"
        buffer += [span("scheduler.step", end - 0.009, 0.0095, sid, active=4, ahead=1, overstepped=0),
                   span("step.fetch", end - 0.008, 0.008, parent=sid)]
    assert all(read(dict(SERVE)) is None for read in reads)
    assert device_gaps.device_gaps(dict(SERVE)) is None
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: None)  # a program without a buffer
    assert all(read(dict(record)) is None for read in reads for record in (SERVE, TRAIN))


def test_gaps_with_0_1_and_3_prefills(buffer, capsys):
    run_of_steps(buffer)
    rows, plain = device_gaps.device_gaps(SERVE)
    assert len(rows) == 15 and plain == pytest.approx(0.010)
    assert [r["why_not"] for r in rows] == [None] * 8 + ["short_fetch", "first_pick", None, None, "short_fetch", "short_fetch", None]
    admitted = device_gaps.admissions(rows, plain)
    assert [len(a["gaps"]) for a in admitted] == [2, 2, 1, 2, 1, 2]
    assert [a["why_not"] for a in admitted] == [None, None, None, "first_pick", "first_pick", "short_fetch"]
    # (2 + 18) / 1, (0 + 30) / 1, 90 / 3: the median is 30.
    assert load_reader("layer_metrics", "prog.admit_dev_ms")(SERVE) == pytest.approx(30.0)
    note = notes(capsys)["prog.admit_dev"]
    assert note["plain_ms"] == pytest.approx(10.0) and note["plain_gaps"] == 7
    assert note["admissions_read"] == 5 and note["admissions_left_out"] == {"first_pick": 2, "short_fetch": 2}
    assert note["max_prefills_before_one_step"] == 3
    by_tokens = note["one_prefill_steps_and_median_ms_by_prefill_tokens"]
    assert by_tokens == {"256": [1, pytest.approx(20.0)], "512": [1, pytest.approx(30.0)]}
    # 20 + 30 + 90 ms of admissions in 11 usable gaps of 270 ms.
    assert load_reader("layer_metrics", "sched.admit_dev_share")(SERVE) == pytest.approx(100 * 140 / 270)
    # 16 steps, two of them not ahead; 2 rows of 16 x 48 + 2 overstepped.
    assert load_reader("layer_metrics", "sched.ahead_share")(SERVE) == pytest.approx(100 * 14 / 16)
    note = notes(capsys)["sched.ahead"]
    assert note["steps"] == 16 and note["not_ahead_by_drain"] == {"idle": 1, "first_pick": 1}
    assert note["overstepped_rows"] == 2 and note["overstepped_share_pct"] == pytest.approx(100 * 2 / (16 * 48 + 2))


def test_the_gap_across_the_profilers_start_is_left_out(buffer, capsys):
    run_of_steps(buffer)
    # The profiler starts inside the three-prefill gap (110.110 .. 110.210).
    traced = dict(SERVE, trace_host=(110.2, 112.0))
    rows, plain = device_gaps.device_gaps(traced)
    assert [r["why_not"] for r in rows].count("profiler_start") == 1 and plain == pytest.approx(0.010)
    assert load_reader("layer_metrics", "prog.admit_dev_ms")(traced) == pytest.approx(25.0)
    assert notes(capsys)["prog.admit_dev"]["admissions_left_out"] == {"first_pick": 2, "short_fetch": 2, "profiler_start": 3}
    assert load_reader("layer_metrics", "sched.admit_dev_share")(traced) == pytest.approx(100 * 50 / 170)


def test_no_admission_in_the_window(buffer):
    for i in range(4):
        buffer += step(110.0 + 0.01 * i)
    assert load_reader("layer_metrics", "prog.admit_dev_ms")(SERVE) is None
    assert load_reader("layer_metrics", "sched.admit_dev_share")(SERVE) == 0.0
    assert load_reader("layer_metrics", "sched.ahead_share")(SERVE) == 100.0
    # No usable plain gap: neither of the two device metrics.
    del buffer[:]
    buffer += step(110.0) + step(110.03, prefills=1, tokens=64)
    assert load_reader("layer_metrics", "prog.admit_dev_ms")(SERVE) is None
    assert load_reader("layer_metrics", "sched.admit_dev_share")(SERVE) is None


def test_a_pause_is_no_work_of_the_device(buffer, capsys):
    """The machine pauses for seconds inside one fetch now and then: such a
    gap is left out, and so is the admission right behind it."""
    t = 110.0
    buffer += step(t)
    for gap, kw in [(0.010, {}), (0.010, {}), (3.010, {}), (0.030, dict(prefills=1, tokens=256)), (0.010, {}),
                    (0.013, {}), (0.027, dict(prefills=1, tokens=256)), (0.010, {})]:
        t += gap
        buffer += step(t, **kw)
    assert [r["why_not"] for r in device_gaps.device_gaps(SERVE)[0]].count("pause") == 1
    assert load_reader("layer_metrics", "prog.admit_dev_ms")(SERVE) == pytest.approx(20.0)
    assert notes(capsys)["prog.admit_dev"]["admissions_left_out"] == {"pause": 1}
    assert load_reader("layer_metrics", "sched.admit_dev_share")(SERVE) == pytest.approx(100 * 0.020 / 0.110)


def test_crosscheck_lays_the_gaps_over_the_module_events(buffer, tmp_path):
    """``perfbench/tests/admit_crosscheck.py`` on a hand-made trace: the
    profiler's clock runs 1,000 s ahead of ``perf_counter``, a pool step is
    two modules of 9.5 + 0.3 ms, a prefill one of 19 ms."""
    from perfbench.tests import admit_crosscheck

    end = run_of_steps(buffer)
    off, host, modules = 1000.0, [], []
    fetches = sorted((s for s in buffer if s["name"] == "step.fetch"), key=lambda s: s["t0_mono"])
    before = None
    for f, st in zip(fetches, [s for s in buffer if s["name"] == "scheduler.step" and "prefills" in s]):
        host.append(("step.fetch", f["t0_mono"] + off + 2e-6, f["t0_mono"] + f["dur_s"] + off - 2e-6))
        t = f["t0_mono"] + f["dur_s"] + off - 1e-4  # the picks end a little before the host sees them
        modules += [("jit__pick_pool(5)", t - 0.0003, t), ("jit__pool_step_paged_flash(9)", t - 0.0098, t - 0.0003)]
        if before is not None:
            modules += [("jit__slot_prefill_paged(3)", before + 0.019 * k, before + 0.019 * (k + 1)) for k in range(st["prefills"])]
        before = t
    planes = {"host": host + [("other", 1.0, 2.0)], "devices": {"/device:TPU:0": {"ops": [], "modules": modules, "lines": []}}}
    record = dict(SERVE, trace_host=(109.0, end + 1.0))
    got = admit_crosscheck.crosscheck(record, planes, str(tmp_path / "gaps.json"))
    dumped = json.loads((tmp_path / "gaps.json").read_text())
    assert len(dumped["window"]) == len(dumped["slice"]) == 15 and len(dumped["slice_fetch_ms"]) == 16
    assert [m[0] for m in dumped["slice"][3]["modules"]].count("jit__slot_prefill_paged") == 1
    assert got["clock_offset_s"] == pytest.approx(off, abs=1e-5) and got["slice_steps"] == 16
    assert got["plain_gaps"] == 7 and got["admission_steps"] == 3
    assert got["plain_ms"]["spans"] == pytest.approx(10.0) and got["plain_ms"]["modules"] == pytest.approx(9.8)
    names = got["modules_in_a_plain_gap_median_ms"]
    assert names["jit__pool_step_paged_flash"] == [1.0, pytest.approx(9.5)] and names["jit__pick_pool"] == [1.0, pytest.approx(0.3)]
    assert got["admit_dev_ms"]["spans"] == pytest.approx(30.0) and got["admit_dev_ms"]["modules"] == pytest.approx(19.0)
    assert got["admit_dev_ms"]["prefill_module_alone_median_ms"] == pytest.approx(19.0)
    # Host events that are not the buffer's fetches: no offset, and it says so.
    planes["host"] = [("step.fetch", 5.0 + i, 5.5 + i) for i in range(16)]
    assert "could not be matched" in admit_crosscheck.crosscheck(record, planes)["error"]
