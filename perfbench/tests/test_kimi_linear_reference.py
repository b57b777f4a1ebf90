"""The Kimi Linear reference against the program at a tiny size on the CPU, the
parts of the mathematics its comparison has to see (the controls of PERF.md
section 6), the counts of ``flops_bytes_kimi.py``, and the delta-rule and
latent readers on a hand-made buffer and the small trace recorded on the chip
(``record_small_kimi_trace.py``)."""

import json
import os

import jax
import numpy as np
import pytest

from perfbench import flops_bytes_kimi as fb
from perfbench import kimi_counts, moe_counts, program_api_spans, trace_reduce
from perfbench import program_api as api
from perfbench.kernel_time import kernel_ms_per_step
from perfbench.kinds.serve_open_loop import LOGIT_REL_TOL
from perfbench.reference import kimi_linear_lm
from perfbench.run import load_reader

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "kimi-linear-48b-a3b.reasoning-saturated"
READERS = ["kern.kda_step_ms", "kern.kda_step_roofline", "kern.latent_attn_roofline"]


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    config, cell = load("configs", "kimi-linear-48b-a3b.json"), load("workloads", CELL + ".json")
    config["model"].update(cell["rehearse"]["model"])
    # The routed branch at Glorot size: in float32 no near-tie parts the two
    # computations, and a fault of the router then shows at its full size.
    config["model"].update(moe_out_init_scale=1.0)
    params = api.init_lm_params(config, 5)
    dep = {**cell["deployment"], **cell["rehearse"]["deployment"]}
    sched, _ = api.make_scheduler(params, config, dep, None)
    prompts = np.random.default_rng(1).integers(3, 256, (2, 20)).astype(np.int32)  # longer than one block of 16
    got = api.pool_forward_logits(sched, prompts, 3)
    full = np.concatenate([prompts, got[:, :3].argmax(-1).astype(np.int32)], axis=1)
    return config["model"], params, got, full


def rel(got, want):
    want = np.asarray(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_prefill_and_decode_through_the_pool_programs(tiny):
    model, params, got, full = tiny
    assert rel(got, kimi_linear_lm.logits(params, full, model, first=19)) < 1e-4
    assert model["num_layers"] == 5 and len(fb.kda_layers(model)) == 4 and len(fb.latent_layers(model)) == 1  # the 3 : 1 after the dense layer


@pytest.mark.parametrize("alter", [
    {"no_decay": True}, {"beta_one": True}, {"no_delta": True}, {"zero_state_at": 16}, {"no_conv_silu": True},
    {"no_shared_key": True}, {"no_latent_norm": True}, {"no_select_bias": True},
])
def test_the_check_refuses_a_part_left_out(alter, tiny):
    """The kind's limit (3 % of the largest logit) leaves room for bfloat16; a
    part of the mathematics left out of the reference moves the logits past
    it. The chip's readings of the same controls are in PERF.md section 6."""
    model, params, got, full = tiny
    assert rel(got, kimi_linear_lm.logits(params, full, model, first=19, alter=alter)) > LOGIT_REL_TOL


def test_counts_at_the_published_widths():
    c = load("configs", "kimi-linear-48b-a3b.json")["model"]
    kda, = {json.dumps(k) for k in fb.kda_layers(c)}
    assert len(fb.kda_layers(c)) == 4 and len(fb.latent_layers(c)) == 1
    assert fb.kda_state_bytes_per_slot_per_layer(json.loads(kda)) == 32 * 128 * 128 * 4 == 2_097_152
    assert fb.kda_conv_bytes_per_slot_per_layer(c, json.loads(kda)) == 3 * 3 * 4096 * 2 == 73_728
    assert fb.state_bytes_per_slot(c) == 8_683_520 and fb.latent_bytes_per_position(c) == (512 + 64) * 2 == 1152
    assert fb.kda_step_bytes(c, 256) == 256 * 4 * 2 * 2_097_152 and fb.latent_attention_bytes(c, 2300 * 256) == 1152 * 2300 * 256
    # 4.283e9 parameters: the table of ISSUE 35, part by part
    d, w = 2304, 4096
    kda_mixer = 3 * d * w + 3 * 4 * w + 2 * (d * 128 + 128 * w) + d * 32 + 32 + w + 128 + w * d
    mla_mixer = d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + 32 * 128 * d
    expert_layer = 128 * 3 * d * 1024 + 3 * d * 1024 + d * 256 + 256
    want = 4 * kda_mixer + mla_mixer + 3 * d * 9216 + 4 * expert_layer + 5 * 2 * d + d + 2 * 81920 * d
    assert fb.kimi_params(c) == want == 4_282_936_192 and round(fb.kimi_params(c) * 2 / 1e9, 2) == 8.57
    assert round(kda_mixer / 1e6, 2) == 39.51 and round(mla_mixer / 1e6, 2) == 29.11


def test_parameter_count_is_the_programs(tiny):
    model, params, _, _ = tiny
    assert fb.kimi_params(model) == sum(x.size for x in jax.tree_util.tree_leaves(params))


# ------------------------------------------------------------- the readers


class FakeBuffer:
    def __init__(self, spans):
        self.spans, self.dropped = spans, 0

    def snapshot(self):
        return list(self.spans)


def step(t0, **counts):
    return {"kind": "trace.span", "name": "scheduler.step", "t0_mono": t0, "dur_s": 0.01, "span": f"s{t0}", **counts}


def record_with(trace, model):
    return {"serve": {"steps": []}, "t0": 100.0, "t1": 200.0, "trace_host": (150.0, 152.0), "trace": trace,
            "config": {"model": model}, "peaks": {"hbm_bytes_per_s": 819e9}}


@pytest.fixture(scope="module")
def reduced():
    path = os.path.join(HERE, "tests", "data", "small_kimi.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no small trace of the delta-rule and latent kernels was recorded")
    return trace_reduce.reduce(trace_reduce.read_planes(path, {"sched.step", "perfbench.trace"}), {"sched.step"})


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_gives_nothing(name, monkeypatch):
    """The parent has neither kernel and another model counts no such layers:
    the readers return nothing and do not raise."""
    model = load("configs", "kimi-linear-48b-a3b.json")["model"]
    read = load_reader("layer_metrics", name)
    other = trace_reduce.reduce(trace_reduce.read_planes(
        os.path.join(HERE, "tests", "data", "small_moe.xplane.pb"), {"sched.step", "perfbench.trace"}), {"sched.step"})
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: FakeBuffer([step(150.5, active=3, attn_pos_full=900)]))
    for trace in (None, other):  # no trace; a trace that holds neither kernel
        assert read(record_with(trace, model)) is None
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: None)  # a program without a buffer
    assert read(record_with(None, model)) is None
    assert read({"t0": 1.0, "t1": 2.0, "train": {"steps": 3}, "trace": other}) is None  # not a serving cell


def test_the_three_readers_on_the_recorded_small_trace(monkeypatch, reduced):
    """Two ``kda_step`` calls and one ``paged_latent_attention`` a step, six
    steps run, recorded on the chip; the counts come from a hand-made buffer."""
    want = load("tests", "data", "small_kimi.expected.json")
    model = load("configs", "kimi-linear-48b-a3b.json")["model"]
    record = record_with(reduced, model)
    kda_s, latent_s = moe_counts.kernel_seconds(record, "kda_step"), moe_counts.kernel_seconds(record, "paged_latent_attention")
    assert kda_s == pytest.approx(want["kda_step_s"]) and latent_s == pytest.approx(want["paged_latent_attention_s"])
    steps = moe_counts.slice_pool_steps(record)  # the module events that lie whole inside the traced window
    assert kda_s > 0 and latent_s > 0 and steps == want["pool_steps"] and steps in (5, 6)
    spans = [step(110.0, active=99, attn_pos_full=999),  # before the slice
             step(150.5, active=250, attn_pos_full=600_000), step(151.0, active=256, attn_pos_full=610_000),
             step(151.5, active=7),  # no count of positions: not a model with layer kinds
             step(250.0, active=10**6, attn_pos_full=10**9)]  # after the window
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: FakeBuffer(spans))
    assert kimi_counts.slice_steps(record) == {"active": 506.0, "attn_pos_full": 1_210_000.0, "spans": 2}
    assert load_reader("layer_metrics", "kern.kda_step_ms")(record) == pytest.approx(1e3 * kda_s / steps) == pytest.approx(
        kernel_ms_per_step(record, "kda_step", "_pool_step_paged_flash"))
    assert load_reader("layer_metrics", "kern.kda_step_roofline")(record) == pytest.approx(
        100 * 506 * 4 * 2 * 2_097_152 / 819e9 / kda_s)
    assert load_reader("layer_metrics", "kern.latent_attn_roofline")(record) == pytest.approx(
        100 * 1152 * 1_210_000 / 819e9 / latent_s)
