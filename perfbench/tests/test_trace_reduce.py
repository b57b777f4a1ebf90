import os

import pytest

from perfbench import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = {"sched.step", "sched.admit"}


def test_union_gaps_and_names_on_made_up_planes():
    planes = {
        "devices": {"/device:TPU:0": {"lines": ["XLA Ops", "XLA Modules"], "modules": [("jit_f(1)", 1.0, 1.6)], "ops": [
            ("fusion.1", 1.0, 1.2), ("fusion.2", 1.1, 1.3),  # overlap: the union is 0.3, not 0.4
            ("copy.1", 1.5, 1.6),
        ]}},
        "host": [("perfbench.trace", 0.9, 2.0), ("sched.step", 0.95, 1.45), ("sched.admit", 1.6, 1.95)],
    }
    r = trace_reduce.reduce(planes, SPANS)
    assert r["window_source"] == "host_span" and r["window_s"] == pytest.approx(1.1)
    assert r["busy_s"] == pytest.approx(0.4)
    assert dict((n, s) for n, s, _ in r["ops"])["fusion.1"] == pytest.approx(0.2)
    idle = dict(r["idle_by_span"])
    # gaps: 0.9-1.0 (middle 0.95, sched.step), 1.3-1.5 (sched.step), 1.6-2.0 (sched.admit)
    assert idle["sched.step"] == pytest.approx(0.3) and idle["sched.admit"] == pytest.approx(0.4)
    assert r["module_events"] == [("jit_f(1)", pytest.approx(0.6))]


def test_no_device_events_reduce_to_nothing():
    assert trace_reduce.reduce({"devices": {}, "host": []}, SPANS) is None


def test_the_trace_recorded_on_the_chip():
    """tests/data/small.xplane.pb: 20 dispatches of one 2048^3 bf16 matmul on a
    TPU v5e, each inside a host span, recorded by record_small_trace.py on the
    chip in PR 24. The expected numbers are in small.expected.json beside it."""
    import json

    path = os.path.join(HERE, "data", "small.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace checked in")
    with open(os.path.join(HERE, "data", "small.expected.json")) as f:
        want = json.load(f)
    r = trace_reduce.reduce(trace_reduce.read_planes(path, {"small.step", "perfbench.trace"}), {"small.step"})
    assert r["devices"] == 1 and "XLA Ops" in r["lines"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0 < r["busy_s"] < r["window_s"]
    assert sum(n for _, _, n in r["modules"]) >= want["dispatches"] - 1  # the first may start before the span
    assert r["ops_by_label"][0][0] == "fusion bf16[2048,2048] kOutput"


def test_labels_drop_what_tells_instances_apart():
    text = ("%_pool_step_paged_flash.104 = bf16[48,2,12,128]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call(s32[48,128]{1,0} "
            "%copy-done.3, bf16[6145,16,2,128]{3,2,1,0} %bitcast.1178), custom_call_target=\"tpu_custom_call\"")
    assert trace_reduce.op_label(text) == "_pool_step_paged_flash custom-call bf16[48,2,12,128]"
    text = "%fusion.39 = (f32[37000,1024]{1,0:T(8,128)}, f32[]{:T(128)}) fusion(f32[37000,1024]{1,0} %p), kind=kOutput, calls=%f"
    assert trace_reduce.op_label(text) == "fusion f32[37000,1024] kOutput"
    assert trace_reduce.op_label("%copy-done = bf16[8]{0} copy-done((bf16[8]{0}, u32[]{:S(2)}) %copy-start)") == "copy-done bf16[8]"
