"""The Nemotron-H reference's counts (``flops_bytes_nemotron.py``) against the
program's tree, and the state-space and latent-expert readers on a hand-made
buffer and reduced trace: what each reads, and that each gives nothing where
there is nothing to read (the parent, another model). The reference against
the program is ``tests/test_nemotron_h.py``'s."""

import json
import os

import jax
import pytest

from perfbench import flops_bytes_nemotron as fb
from perfbench import program_api_spans
from perfbench.run import load_reader

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "nemotron-3-super.chat-saturated"
READERS = ["kern.ssm_step_ms", "kern.ssm_step_roofline", "kern.latent_expert_roofline"]


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def model():
    return load("configs", "nemotron-3-super.json")["model"]


def test_the_counts_at_the_published_widths():
    c = model()
    assert len(fb.ssm_layers(c)) == 5
    assert fb.ssm_state_bytes_per_slot_per_layer(fb.ssm_layers(c)[0]) == 128 * 64 * 128 * 4 == 4_194_304
    assert fb.ssm_conv_bytes_per_slot_per_layer(c, fb.ssm_layers(c)[0]) == 3 * (8192 + 2 * 8 * 128) * 2 == 61_440
    assert fb.state_bytes_per_slot(c) == 5 * (4_194_304 + 61_440) == 21_278_720
    assert fb.ssm_step_bytes(c, 256) == 256 * 2 * 21_278_720
    assert fb.latent_experts_hit_bytes(c, 10) == 10 * 2 * 1024 * 2688 * 2
    assert fb.nemotron_params(c) == 4_648_163_712  # 9.30 GB in bfloat16


def test_parameter_count_is_the_programs():
    from transformer_tpu.config import ModelConfig
    from transformer_tpu.models.transformer import transformer_init

    c = model()
    c.update(load("workloads", CELL + ".json")["rehearse"]["model"])
    cfg = ModelConfig(**c)
    shapes = jax.eval_shape(lambda k: transformer_init(k, cfg), jax.random.PRNGKey(0))
    assert fb.nemotron_params(c) == sum(x.size for x in jax.tree_util.tree_leaves(shapes))


class FakeBuffer:
    def __init__(self, spans):
        self.spans, self.dropped = spans, 0

    def snapshot(self):
        return list(self.spans)


def step(t0, **counts):
    return {"kind": "trace.span", "name": "scheduler.step", "t0_mono": t0, "dur_s": 0.01, "span": f"s{t0}", **counts}


def record_with(trace, m):
    return {"serve": {"steps": []}, "t0": 100.0, "t1": 200.0, "trace_host": (150.0, 152.0), "trace": trace,
            "config": {"model": m}, "peaks": {"hbm_bytes_per_s": 819e9}}


def small_trace(kernels):
    """Four pool steps, each with one call of every kernel named, 2 ms each."""
    ops = [(f"%{k}.{i} = f32[256,128,64,128]{{3,2,1,0}} custom-call(...)", 0.002, 0.0)
           for i in range(4) for k in kernels]
    return {"ops": ops, "module_events": [("jit__pool_step_paged_flash", 0.03)] * 4}


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_gives_nothing(name, monkeypatch):
    """The parent has no ``ssm_step``; a model without a latent width gives
    no latent-expert share: the readers return nothing and do not raise."""
    read = load_reader("layer_metrics", name)
    spans = [step(150.5, active=3, attn_pos_full=900), step(120.0, moe_assign=10, moe_hit=5, moe_steps=1, moe_tokens=2)]
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: FakeBuffer(spans))
    for trace in (None, small_trace(["paged_flash_attention"])):
        assert read(record_with(trace, model())) is None
    if name != "kern.ssm_step_ms":  # the two shares need the program's counts
        monkeypatch.setattr(program_api_spans, "_buffer", lambda: None)  # a program without a buffer
        assert read(record_with(small_trace(["ssm_step", "moe_expert_ffn"]), model())) is None


def test_the_three_readers_on_a_hand_made_trace(monkeypatch):
    spans = [step(110.0, active=99, attn_pos_full=999),  # before the slice
             step(150.5, active=250, attn_pos_full=60_000), step(151.0, active=256, attn_pos_full=61_000),
             step(120.0, moe_assign=5000, moe_hit=600, moe_steps=1, moe_tokens=256),
             step(130.0, moe_assign=5000, moe_hit=620, moe_steps=1, moe_tokens=256)]
    monkeypatch.setattr(program_api_spans, "_buffer", lambda: FakeBuffer(spans))
    record = record_with(small_trace(["ssm_step", "moe_expert_ffn"]), model())
    assert load_reader("layer_metrics", "kern.ssm_step_ms")(record) == pytest.approx(2.0)
    assert load_reader("layer_metrics", "kern.ssm_step_roofline")(record) == pytest.approx(
        100 * 506 * 2 * 21_278_720 / 819e9 / 0.008)
    assert load_reader("layer_metrics", "kern.latent_expert_roofline")(record) == pytest.approx(
        100 * 610 * 4 * 2 * 1024 * 2688 * 2 / 819e9 / 0.008)
    other = dict(model(), moe_latent_dim=0)
    assert load_reader("layer_metrics", "kern.latent_expert_roofline")(record_with(record["trace"], other)) is None
