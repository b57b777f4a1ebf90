"""The LFM2 reference against the program at a tiny size on the CPU, the
faults of structure its comparison has to catch, the counts of
``flops_bytes_hybrid.py``, the hybrid, load and all-reduce readers on a
hand-made buffer and the small traces recorded on the chip, and the
``train_steps_mesh`` kind's rehearsal on four host devices."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import flops_bytes_hybrid as fb
from perfbench import hybrid_counts, moe_counts, program_api_spans, trace_reduce
from perfbench import program_api as api
from perfbench.kinds.serve_open_loop import LOGIT_REL_TOL
from perfbench.reference import lfm2_lm
from perfbench.run import load_reader

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lfm2-8b-a1b.longform-saturated"


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    config, cell = load("configs", "lfm2-8b-a1b.json"), load("workloads", CELL + ".json")
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
    assert rel(got, lfm2_lm.logits(params, full, model, first=19)) < 1e-4
    assert model["num_layers"] == 8 and fb.layers_by_state(model) == (2, 0, 6)  # the rehearsal keeps the 1 : 3


def _gate_c_left_out(m):
    def conv(p, h):
        b, _, u = jnp.split(h @ lfm2_lm._f(p["in"]["kernel"]), 3, axis=-1)
        w, z, n = lfm2_lm._f(p["conv"]["kernel"]), b * u, h.shape[1]
        c = sum(w[j] * jnp.pad(z, ((0, 0), (2 - j, 0), (0, 0)))[:, :n] for j in range(3))
        return c @ lfm2_lm._f(p["out"]["kernel"])

    m.setattr(lfm2_lm, "short_conv", conv)


def _taps_reversed(m):
    plain = lfm2_lm.short_conv
    m.setattr(lfm2_lm, "short_conv", lambda p, h: plain({**p, "conv": {"kernel": p["conv"]["kernel"][::-1]}}, h))


def _bias_left_out_of_the_choice(m):
    plain = lfm2_lm.route
    m.setattr(lfm2_lm, "route", lambda p, h, *a: plain(
        {**p, "router": {**p["router"], "bias": 0.0 * p["router"]["bias"]}}, h, *a))


def _weights_taken_from_score_plus_bias(m):
    def route(p, h, top_k, scale, eps):
        s = jax.nn.sigmoid(h @ lfm2_lm._f(p["router"]["kernel"])) + lfm2_lm._f(p["router"]["bias"])
        picked, chosen = jax.lax.top_k(s, top_k)
        return chosen, scale * picked / (picked.sum(-1, keepdims=True) + eps)

    m.setattr(lfm2_lm, "route", route)


def _qk_normalisation_left_out(m):
    m.setattr(lfm2_lm, "rms_norm", lambda p, x, eps: x if x.ndim == 4 else (
        x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * lfm2_lm._f(p["scale"])))


@pytest.mark.parametrize("fault", [_gate_c_left_out, _taps_reversed, _bias_left_out_of_the_choice,
                                   _weights_taken_from_score_plus_bias, _qk_normalisation_left_out])
def test_the_check_refuses_a_structural_fault(fault, tiny, monkeypatch):
    """The kind's limit (3 % of the largest logit) leaves room for bfloat16; a
    mechanism left out of the reference moves the logits far past it. The
    selection bias is drawn a tenth wide here (the harness's 0.05 off zero is
    what the chip's controls measure: PERF.md section 6), so that it decides
    picks at this size as a balanced checkpoint's does."""
    model, params, got, full = tiny
    wide = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 10.0 if [getattr(k, "key", "") for k in path][-2:] == ["router", "bias"] else x, params)
    sched, _ = api.make_scheduler(wide, {"model": model}, {"num_slots": 4, "max_total": 64, "kv_layout": "paged",
                                                           "kv_block": 16, "decode_kernel": "paged_flash"}, None)
    got = api.pool_forward_logits(sched, full[:, :20], 3)
    full = np.concatenate([full[:, :20], got[:, :3].argmax(-1).astype(np.int32)], axis=1)
    assert rel(got, lfm2_lm.logits(wide, full, model, first=19)) < 1e-4
    fault(monkeypatch)
    jax.clear_caches()  # the reference's jitted layers close over the patched functions
    assert rel(got, lfm2_lm.logits(wide, full, model, first=19)) > 3 * LOGIT_REL_TOL
    monkeypatch.undo()
    jax.clear_caches()


def test_counts_at_the_published_widths():
    c = load("configs", "lfm2-8b-a1b.json")["model"]
    assert fb.layers_by_state(c) == (4, 0, 12)
    assert fb.kv_bytes_per_position_per_layer(c) == 2 * 8 * 64 * 2 == 2048
    assert fb.kv_bytes_per_token(c) == 8192 and 16 * fb.kv_bytes_per_position_per_layer(c) == 32768  # 24,576 B + if all attended
    assert fb.state_bytes_per_slot(c) == 12 * 2 * 2048 * 2 == 98304
    assert fb.hybrid_attention_bytes(c, 1000.0) == 2048 * 4 * 1000 and fb.hybrid_attention_bytes(c, 1000.0, 77.0) == 2048 * 4 * 1000
    windowed = {**c, "attention_kinds": [c["attention_kinds"][0], {**c["attention_kinds"][1], "window": 128}]}
    assert fb.layers_by_state(windowed) == (0, 4, 12) and fb.hybrid_attention_bytes(windowed, 1000.0, 77.0) == 2048 * 4 * 77
    # 5.40e9 parameters: the table of ISSUE 33, part by part
    conv, attn = 3 * 2048 * 2048 + 3 * 2048 + 2048 * 2048, 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    expert_layer = 32 * 3 * 2048 * 1792 + 2048 * 32 + 32
    want = 12 * conv + 4 * attn + 2 * 3 * 2048 * 7168 + 14 * expert_layer + 16 * 2 * 2048 + 2048 + 65536 * 2048
    assert fb.lfm2_params(c) == want == 5_399_129_024 and round(fb.lfm2_params(c) * 2 / 1e9, 2) == 10.80
    assert 12 * conv == 201_400_320 and 14 * expert_layer == 4_933_419_456


def test_parameter_count_is_the_programs(tiny):
    model, params, _, _ = tiny
    assert fb.lfm2_params(model) == sum(x.size for x in jax.tree_util.tree_leaves(params))


# ------------------------------------------------------------- the readers


class FakeBuffer:
    def __init__(self, spans):
        self.spans, self.dropped = spans, 0

    def snapshot(self):
        return list(self.spans)


def step(t0, **counts):
    return {"kind": "trace.span", "name": "scheduler.step", "t0_mono": t0, "dur_s": 0.01, "span": f"s{t0}", "active": 2, **counts}


@pytest.fixture(scope="module")
def reduced():
    path = os.path.join(HERE, "tests", "data", "small_moe.xplane.pb")
    return trace_reduce.reduce(trace_reduce.read_planes(path, {"sched.step", "perfbench.trace"}), {"sched.step"})


def record_with(trace, model):
    return {"serve": {"steps": []}, "t0": 100.0, "t1": 200.0, "trace_host": (150.0, 152.0), "trace": trace,
            "config": {"model": model}, "peaks": {"hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("name", ["kern.paged_attn_hybrid_roofline", "moe.load_imbalance", "comm.allreduce_share"])
def test_nothing_to_read_gives_nothing(name, monkeypatch, reduced):
    """The parent records none of the counts and runs no all-reduce: the
    readers return nothing and do not raise."""
    model = load("configs", "lfm2-8b-a1b.json")["model"]
    read = load_reader("layer_metrics", name)
    parents = [step(120.0, emitted=1, moe_assign=8, moe_hit=4, moe_steps=1, moe_tokens=2), step(150.5, emitted=1)]
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: FakeBuffer(parents))
    for trace in (None, reduced):
        assert read(record_with(trace, model)) is None
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: None)  # a program without a buffer
    assert read(record_with(None, model)) is None
    assert read({"t0": 1.0, "t1": 2.0, "train": {"steps": 3}, "trace": reduced}) is None  # no operation named all-reduce
    assert read({"t0": 1.0, "t1": 2.0, "train": {"steps": 3}}) is None


def test_hybrid_and_load_readers_on_the_recorded_small_trace(monkeypatch, reduced):
    """The small trace recorded on the chip for the expert readers (three
    calls of ``paged_flash_attention`` a step); the counts come from a
    hand-made buffer."""
    model = load("configs", "lfm2-8b-a1b.json")["model"]
    record = record_with(reduced, model)
    attn_s = moe_counts.kernel_seconds(record, "paged_flash_attention")
    assert attn_s == pytest.approx(load("tests", "data", "small_moe.expected.json")["paged_flash_attention_s"])
    spans = [step(110.0, moe_assign=6400, moe_max_load=300, moe_hit=448, moe_steps=4, moe_tokens=400, attn_pos_full=999),
             step(150.5, attn_pos_full=60_000),  # the steps that began in the traced slice
             step(151.0, moe_assign=1600, moe_max_load=100, moe_hit=448, moe_steps=1, moe_tokens=100, attn_pos_full=40_000),
             step(160.0, moe_assign=999, moe_hit=9, moe_steps=1, moe_tokens=1),  # no count of the most-loaded expert
             step(250.0, moe_assign=10**6, moe_max_load=10**6, moe_steps=1, attn_pos_full=10**6)]  # after the window
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: FakeBuffer(spans))
    assert hybrid_counts.slice_positions(record) == {"attn_pos_full": 100_000.0, "attn_pos_band": 0.0, "spans": 2}
    assert hybrid_counts.window_load(record) == {"moe_max_load": 400.0, "moe_assign": 8000.0, "moe_steps": 5.0, "spans": 2}
    hybrid = load_reader("layer_metrics", "kern.paged_attn_hybrid_roofline")(record)
    assert hybrid == pytest.approx(100 * 2048 * 4 * 100_000 / 819e9 / attn_s)  # 4 of the 16 layers attend
    assert load_reader("layer_metrics", "moe.load_imbalance")(record) == pytest.approx(400 / (8000 / 32)) == 1.6


def test_allreduce_share_sums_the_operations_named_all_reduce():
    """Names as the four-chip trace has them (PERF.md section 5): the fused
    and the asynchronous halves count, a reduce-scatter or an all-gather does not."""
    read = load_reader("layer_metrics", "comm.allreduce_share")
    ops = [["%fusion.1 = bf16[256,1024]{1,0} fusion(...)", 0.70, 10],
           ["%all-reduce.7 = f32[1024,4096]{1,0} all-reduce(f32[1024,4096]{1,0} %fusion.3), replica_groups={{0,1,2,3}}", 0.12, 5],
           ["%all-reduce-start.2 = f32[37000,1024]{1,0} all-reduce-start(...)", 0.02, 5],
           ["%all-reduce-done.2 = f32[37000,1024]{1,0} all-reduce-done(...)", 0.06, 5],
           ["%all-gather.1 = f32[8]{0} all-gather(...)", 0.05, 5],
           ["%reduce-scatter.1 = f32[8]{0} reduce-scatter(...)", 0.05, 5]]
    record = {"train": {"steps": 5}, "trace": {"ops": ops}}
    assert read(record) == pytest.approx(100 * 0.20 / 1.00)
    assert read({**record, "train": None}) is None and read({"train": {"steps": 5}, "trace": None}) is None


def test_allreduce_share_on_the_recorded_small_trace():
    """Four chips, one program: a matmul and an all-reduce of its result,
    recorded on the chip (``record_small_allreduce_trace.py``)."""
    path = os.path.join(HERE, "tests", "data", "small_allreduce.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no four-chip trace was recorded")
    want = load("tests", "data", "small_allreduce.expected.json")
    reduced = trace_reduce.reduce(trace_reduce.read_planes(path, {"perfbench.trace"}), set())
    assert reduced["devices"] == want["devices"] == 4
    got = load_reader("layer_metrics", "comm.allreduce_share")({"train": {"steps": want["steps"]}, "trace": reduced})
    assert got == pytest.approx(want["allreduce_share"]) and 0 < got < 100


# --------------------------------------------------------- the mesh kind


def test_train_steps_mesh_rehearses_on_four_host_devices():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    run = subprocess.run([sys.executable, "-m", "perfbench.run", "--workload", "tbig-ende.train-dp4", "--seed", "3000000019",
                          "--seconds", "3", "--trace", "0", "--rehearse"], cwd=os.path.dirname(HERE), env=env,
                         capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-2000:]
    lines = [json.loads(l) for l in run.stdout.splitlines() if l.startswith("{")]
    last = lines[-1]
    assert last["rehearsal"] and last["correct"] and last["device"]["count"] == 4
    assert "train_tok_s" in last["would_report"] and "setup_s" in last["would_report"]
    train = next(l for l in lines if l.get("note") == "train")
    assert train["mesh"] == {"data": 4} and train["mesh_devices"] == 4 and train["last_loss"] < train["first_loss"]
    # The comparison that runs over the mesh: one sharded step on a global batch against the reference on all of it.
    over_mesh = next(l for l in lines if l.get("note") == "mesh_check")
    assert over_mesh["ok"] and over_mesh["step"]["weight"] == over_mesh["tokens"]
    assert set(over_mesh["compared"]) == {"mesh_step_tokens_off", "mesh_step_global_grad_rel", "mesh_step_loss_rel"}
    assert over_mesh["compared"]["mesh_step_global_grad_rel"][0] < 1e-4


def test_mesh_check_controls_come_out_not_correct():
    """A step that saw one chip's quarter of the rows (the all-reduce left out,
    or 3 of 4 quarters never trained on) fails the comparison over the mesh;
    the whole batch passes it."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    run = subprocess.run([sys.executable, os.path.join(HERE, "tests", "mesh_check_controls.py"), "--seeds",
                          "3300000001,3300000002", "--rehearse"], cwd=os.path.dirname(HERE), env=env,
                         capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-2000:]
    lines = [json.loads(l) for l in run.stdout.splitlines() if l.startswith("{")]
    cases = [l for l in lines if "case" in l]
    assert len(cases) == 12 and lines[-1]["not_as_wanted"] == 0 and lines[-1]["chips"] == 4
    for l in cases:
        grad = l["compared"]["mesh_step_global_grad_rel"]
        assert l["correct"] == (l["case"] == "whole")
        assert (grad[0] < 1e-4) if l["case"] == "whole" else (grad[0] > 5 * grad[2])
