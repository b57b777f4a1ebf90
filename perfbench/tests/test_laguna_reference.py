"""The Laguna reference against the program at a tiny size on the CPU, the
faults of structure its comparison has to catch, the byte counts of
``flops_bytes_moe.py``, and the expert and band readers on a hand-made buffer
and the small trace recorded on the chip (``record_small_moe_trace.py``)."""

import json
import os

import jax
import numpy as np
import pytest

from perfbench import flops_bytes_moe as fb
from perfbench import moe_counts, program_api_spans, trace_reduce
from perfbench import program_api as api
from perfbench.kinds.serve_open_loop import LOGIT_REL_TOL
from perfbench.reference import laguna_lm
from perfbench.run import load_reader

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "laguna-s.agent-saturated"
READERS = ["moe.expert_ms", "kern.moe_expert_roofline", "moe.experts_hit_share", "moe.local_picks_per_token",
           "kern.paged_attn_band_roofline"]


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    config, cell = load("configs", "laguna-s-2.1.json"), load("workloads", CELL + ".json")
    config["model"].update(cell["rehearse"]["model"])
    params = api.init_lm_params(config, 5)
    dep = {**cell["deployment"], **cell["rehearse"]["deployment"]}
    sched, _ = api.make_scheduler(params, config, dep, None)
    prompts = np.random.default_rng(1).integers(3, 256, (2, 16)).astype(np.int32)  # twice the rehearsal's window
    got = api.pool_forward_logits(sched, prompts, 3)
    full = np.concatenate([prompts, got[:, :3].argmax(-1).astype(np.int32)], axis=1)
    return config["model"], params, got, full


def rel(got, want):
    want = np.asarray(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_prefill_and_decode_through_the_pool_programs(tiny):
    model, params, got, full = tiny
    assert rel(got, laguna_lm.logits(params, full, model, first=15)) < 1e-4


def _no_gate(m):
    plain = laguna_lm.attention
    m.setattr(laguna_lm, "attention", lambda p, h, kind: plain(
        {**p, "gate": {"kernel": 0.0 * p["gate"]["kernel"]}}, h, kind) * 2.0)  # sigmoid(0) = 1/2: times 2 is no gate


def _no_band(m):
    plain = laguna_lm.attention
    m.setattr(laguna_lm, "attention", lambda p, h, kind: plain(p, h, {**kind, "window": 0}))


def _no_routed_scale(m):
    plain = laguna_lm.experts
    m.setattr(laguna_lm, "experts", lambda p, h, top_k, offset, scale: plain(p, h, top_k, offset, 1.0))


def _no_shared_expert(m):
    plain = laguna_lm.experts
    zero = lambda p: jax.tree_util.tree_map(lambda x: 0.0 * x, p)  # noqa: E731
    m.setattr(laguna_lm, "experts", lambda p, h, *a: plain({**p, "shared": zero(p["shared"])}, h, *a))


def _rotary_over_the_whole_head(m):
    plain = laguna_lm.rotary
    m.setattr(laguna_lm, "rotary", lambda x, kind: plain(x, {**kind, "rotary_share": 1.0}))


@pytest.mark.parametrize("fault", [_no_gate, _no_band, _no_routed_scale, _no_shared_expert, _rotary_over_the_whole_head])
def test_the_check_refuses_a_structural_fault(fault, tiny, monkeypatch):
    """The kind's limit (3 % of the largest logit) leaves room for bfloat16; a
    mechanism left out of the reference moves the logits far past it."""
    model, params, got, full = tiny
    fault(monkeypatch)
    jax.clear_caches()  # the reference's jitted layers close over the patched functions
    assert rel(got, laguna_lm.logits(params, full, model, first=15)) > 3 * LOGIT_REL_TOL
    monkeypatch.undo()
    jax.clear_caches()


def test_byte_counts_at_the_published_widths():
    c = load("configs", "laguna-s-2.1.json")["model"]
    assert fb.expert_weight_bytes(c) == 3 * 3072 * 1024 * 2 == 18_874_368
    assert fb.experts_hit_bytes(c, 91 * 4) == 364 * 18_874_368
    assert fb.kv_bytes_per_position_per_layer(c) == 4096 and fb.layers_by_kind(c) == (2, 3) and fb.expert_layers(c) == 4
    assert fb.banded_attention_bytes(c, 1000, 512) == 4096 * (2 * 1000 + 3 * 512)
    assert 5 * fb.kv_bytes_per_position_per_layer(c) == 20_480  # keys and values a token, as the file states
    # 5.57e9 parameters: the table of ISSUE 28, part by part
    assert fb.laguna_params(c) == 5_572_076_544
    assert round(fb.laguna_params(c) * 2 / 1e9, 2) == 11.14


def test_parameter_count_is_the_programs(tiny):
    model, params, _, _ = tiny
    assert fb.laguna_params(model) == sum(x.size for x in jax.tree_util.tree_leaves(params))


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


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_gives_nothing(name, monkeypatch, reduced):
    model = load("configs", "laguna-s-2.1.json")["model"]
    read = load_reader("layer_metrics", name)
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: FakeBuffer([step(120.0, emitted=1)]))  # no such counts
    for trace in (None, reduced):
        assert read(record_with(trace, model)) is None or name == "moe.expert_ms"
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: None)  # a program without a buffer
    assert read(record_with(None, model)) is None
    assert read({"t0": 1.0, "t1": 2.0, "train": {}}) is None


def test_readers_on_the_recorded_small_trace(monkeypatch, reduced):
    """Six steps of a function named as the pool step is, each with two calls
    of ``moe_expert_ffn`` and three of ``paged_flash_attention``, recorded on
    the chip (five of the module's events lie whole inside the traced
    window); the counts come from a hand-made buffer."""
    want = load("tests", "data", "small_moe.expected.json")
    model = load("configs", "laguna-s-2.1.json")["model"]
    record = record_with(reduced, model)
    steps = moe_counts.slice_pool_steps(record)
    assert steps == want["pool_steps"] == 5 and want["steps"] == 6
    moe_s = moe_counts.kernel_seconds(record, "moe_expert_ffn")
    attn_s = moe_counts.kernel_seconds(record, "paged_flash_attention")
    assert moe_s == pytest.approx(want["moe_expert_ffn_s"]) and attn_s == pytest.approx(want["paged_flash_attention_s"])
    assert 0 < moe_s < want["busy_s"] and 0 < attn_s < want["busy_s"]
    spans = [step(110.0, moe_assign=640, moe_hit=1456, moe_steps=4, moe_tokens=32, attn_pos_full=999, attn_pos_band=999),
             step(150.5, attn_pos_full=60_000, attn_pos_band=16_000),  # the one step that began in the traced slice
             step(151.0, moe_assign=160, moe_hit=364, moe_steps=1, moe_tokens=8, attn_pos_full=40_000, attn_pos_band=4_000),
             step(250.0, moe_assign=10**6, moe_hit=10**6, moe_steps=1, moe_tokens=1)]  # after the window
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: FakeBuffer(spans))
    read = {n: load_reader("layer_metrics", n)(record) for n in READERS}
    assert read["moe.expert_ms"] == pytest.approx(1e3 * moe_s / steps)
    assert read["moe.experts_hit_share"] == pytest.approx(100 * 1820 / (5 * 4 * 128))  # 71.1 %: 91 experts a layer
    assert read["moe.local_picks_per_token"] == pytest.approx(800 / (40 * 4)) == 5.0
    hit_in_slice = 1820 / 5 * steps  # the window's mean a step times the slice's steps
    assert read["kern.moe_expert_roofline"] == pytest.approx(100 * hit_in_slice * 18_874_368 / 819e9 / moe_s)
    assert read["kern.paged_attn_band_roofline"] == pytest.approx(100 * 4096 * (2 * 100_000 + 3 * 20_000) / 819e9 / attn_s)
