"""The seven readers of the program's own spans and kernel names, on a
hand-made ``record`` and a hand-made buffer: what they compute, and that they
report nothing (and do not raise) where the program recorded nothing."""

import json
import os

import pytest

from perfbench import program_api_spans, trace_reduce
from perfbench.run import load_reader

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = ["sched.step_host_ms", "sched.admit_ms", "sched.itl_p99_ms", "sched.emit_share", "kern.paged_attn_ms",
         "kern.fused_ffn_ms", "trainer.data_wait_ms"]


class FakeBuffer:
    def __init__(self, spans):
        self.spans, self.dropped = spans, 0

    def snapshot(self):
        return list(self.spans)


@pytest.fixture
def buffer(monkeypatch):
    buf = FakeBuffer([])
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: buf)
    return buf.spans


def span(name, t0, dur, sid=None, parent=None, **attrs):
    s = {"kind": "trace.span", "name": name, "t0_mono": t0, "dur_s": dur, "span": sid or f"{name}@{t0}", **attrs}
    if parent:
        s["parent"] = parent
    return s


def step(t0, fetch_ends, dur, **counts):
    """A ``scheduler.step`` at ``t0`` whose fetches (10 ms each) end at ``fetch_ends``."""
    sid = f"step@{t0}"
    return [span("scheduler.step", t0, dur, sid, lane="scheduler", **counts)] + [
        span("step.fetch", e - 0.010, 0.010, parent=sid) for e in fetch_ends]


SERVE = {"serve": {"steps": []}, "t0": 100.0, "t1": 200.0, "trace_host": (150.0, 152.0), "trace": None}
TRAIN = {"train": {"steps": 3}, "t0": 100.0, "t1": 200.0, "window_s": 100.0, "trace": None}


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_gives_nothing(name, buffer, monkeypatch):
    read = load_reader("layer_metrics", name)
    for record in (SERVE, TRAIN, {"t0": 1.0, "t1": 2.0}):
        assert read(dict(record)) is None  # an empty buffer
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: None)  # a program without a buffer
    for record in (SERVE, TRAIN):
        assert read(dict(record)) is None


def test_step_host_and_emit_share(buffer):
    buffer += step(99.0, [99.04], 0.05, active=9, emitted=9)  # before the window
    buffer += step(110.0, [110.030], 0.034, active=4, emitted=2, continued=2, walked=2)  # 34 - 10 = 24 ms of host
    buffer += step(111.0, [111.020, 111.035], 0.040, active=4, emitted=3, continued=1, walked=1)  # two groups: 40 - 20
    buffer += step(112.0, [112.030], 0.036, active=2, emitted=2, continued=2, walked=0)  # 26
    buffer += [span("scheduler.step", 113.0, 0.001, active=1)]  # all slots expired: no fetch, no counts
    assert load_reader("layer_metrics", "sched.step_host_ms")(SERVE) == pytest.approx(24.0)
    assert load_reader("layer_metrics", "sched.emit_share")(SERVE) == pytest.approx(100.0 * 7 / 10)
    assert load_reader("layer_metrics", "sched.emit_share")(TRAIN) is None


def test_admit_median_over_the_window(buffer):
    buffer += [span("serve.admit", 90.0, 9.0), span("serve.admit", 120.0, 0.012), span("serve.admit", 130.0, 0.020),
               span("serve.admit", 199.9, 0.5), span("admit.encode", 120.0, 0.001)]
    assert load_reader("layer_metrics", "sched.admit_ms")(SERVE) == pytest.approx(20.0)


def test_token_gaps_weighted_by_the_slots_that_continued(buffer, capsys):
    ends = [110.00, 110.04, 110.08, 110.20, 110.24]  # gaps 40, 40, 120 (an admission), 40 ms
    continued = [0, 50, 50, 48, 49]
    for e, c in zip(ends, continued):
        buffer += step(e - 0.035, [e], 0.036, active=50, emitted=c, continued=c, walked=0)
    # 197 token gaps: 149 of 40 ms, 48 of 120 ms; the 99th percentile is among the long ones.
    assert load_reader("layer_metrics", "sched.itl_p99_ms")(SERVE) == pytest.approx(120.0)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["note"] == "sched.itl" and note["token_gaps"] == 197 and note["step_gaps"] == 4
    assert note["p50_ms"] == pytest.approx(40.0) and note["beyond_p99"] == 1
    # The gap that spans the start of the profiler is left out: here the long one.
    traced = dict(SERVE, trace_host=(110.10, 112.0))
    assert load_reader("layer_metrics", "sched.itl_p99_ms")(traced) == pytest.approx(40.0)
    # Steps that only walked prompt tails have no token gap.
    del buffer[:]
    buffer += step(110.0, [110.03], 0.04, active=2, emitted=0, continued=0, walked=2)
    buffer += step(111.0, [111.03], 0.04, active=2, emitted=1, continued=0, walked=1)
    assert load_reader("layer_metrics", "sched.itl_p99_ms")(SERVE) is None


def test_data_wait_median(buffer, capsys):
    buffer += [span("train.data_wait", 99.0, 3.0), span("train.data_wait", 110.0, 0.0002),
               span("train.data_wait", 120.0, 0.0004), span("train.data_wait", 199.0, 2.5, end=True)]
    assert load_reader("layer_metrics", "trainer.data_wait_ms")(TRAIN) == pytest.approx(0.4)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["spans_in_window"] == note["steps"] == 3
    assert load_reader("layer_metrics", "trainer.data_wait_ms")(SERVE) is None


ATTN = ("%paged_flash_attention.{n} = bf16[48,2,12,128]{{3,2,1,0}} custom-call(s32[48,128]{{1,0}} %copy-done.3, "
        "bf16[6145,16,2,128]{{3,2,1,0}} %bitcast.1178), custom_call_target=\"tpu_custom_call\"")
FFN = "%fused_ln_ffn.{n} = bf16[48,3072]{{1,0}} custom-call(bf16[48,3072]{{1,0}} %x), custom_call_target=\"tpu_custom_call\""
OLD = "%_pool_step_paged_flash.{n} = bf16[48,2,12,128]{{3,2,1,0}} custom-call(bf16[6145,16,2,128]{{3,2,1,0}} %b)"


def traced(ops):
    modules = [("jit__pool_step_paged_flash(123)", 0.045)] * 4 + [("jit__slot_prefill_paged(7)", 0.02)]
    return dict(SERVE, trace={"ops": ops, "module_events": modules})


def test_kernel_time_per_step_from_names():
    ops = [[ATTN.format(n=n), 0.004, 4] for n in (1, 2, 30)] + [[FFN.format(n=n), 0.0006, 4] for n in (1, 2)] + [
        ["%paged_flash_attention_other.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 9.0, 4],
        ["%fusion.7 = f32[8]{0} fusion(f32[8]{0} %paged_flash_attention.1), kind=kLoop", 9.0, 4]]
    record = traced(ops)
    assert load_reader("layer_metrics", "kern.paged_attn_ms")(record) == pytest.approx(1e3 * 0.012 / 4)
    assert load_reader("layer_metrics", "kern.fused_ffn_ms")(record) == pytest.approx(1e3 * 0.0012 / 4)
    # The new name is what breakdown.device_ops will show.
    assert trace_reduce.op_label(ATTN.format(n=3)) == "paged_flash_attention custom-call bf16[48,2,12,128]"
    assert trace_reduce.op_label(FFN.format(n=3)) == "fused_ln_ffn custom-call bf16[48,3072]"
    # An earlier commit names every Mosaic call after the jitted function: nothing to read.
    old = traced([[OLD.format(n=n), 0.004, 4] for n in (1, 2)])
    assert load_reader("layer_metrics", "kern.paged_attn_ms")(old) is None
    assert load_reader("layer_metrics", "kern.fused_ffn_ms")(old) is None
    assert load_reader("layer_metrics", "kern.paged_attn_ms")(dict(traced(ops), serve=None)) is None


def test_kernel_time_on_the_recorded_small_trace_is_nothing():
    path = os.path.join(HERE, "data", "small.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace checked in")
    reduced = trace_reduce.reduce(trace_reduce.read_planes(path, {"small.step", "perfbench.trace"}), {"small.step"})
    record = dict(SERVE, trace=reduced)
    assert load_reader("layer_metrics", "kern.paged_attn_ms")(record) is None
    assert load_reader("layer_metrics", "kern.fused_ffn_ms")(record) is None


def test_spans_come_from_the_programs_buffer():
    """Against the real buffer: by name and by start, oldest first."""
    from transformer_tpu.obs.trace import Tracer, buffer as real

    real().clear()
    tracer = Tracer()
    import time

    a = time.perf_counter()
    with tracer.span("x.outer") as outer:
        with tracer.span("x.inner"):
            pass
    with tracer.span("x.outer"):
        pass
    b = time.perf_counter()
    got = program_api_spans.spans("x.outer", a, b)
    assert [s["name"] for s in got] == ["x.outer", "x.outer"] and got[0]["t0_mono"] <= got[1]["t0_mono"]
    assert program_api_spans.spans("x.outer", b, b + 1) == []
    kids = program_api_spans.children(got, "x.inner")
    assert list(kids) == [outer.ctx.span_id] and len(kids[outer.ctx.span_id]) == 1
    assert program_api_spans.dropped() == 0
