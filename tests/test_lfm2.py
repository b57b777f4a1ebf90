"""A model some of whose layers keep no KV rows (a gated short convolution in
attention's place, its state a fixed row a slot beside the attention layers'
paged KV), with q/k normalisation and a sigmoid router that chooses by score
plus bias over gated experts all held here: the program against the plain
reference (``perfbench/reference/lfm2_lm.py``) at a small size on the CPU,
and each mechanism against its closed form."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import flops_bytes_hybrid
from perfbench.reference import lfm2_lm
from transformer_tpu.config import AttentionKind, ModelConfig, config_from_json, config_to_json
from transformer_tpu.models.transformer import transformer_apply, transformer_init
from transformer_tpu.ops.attention import mha_apply, mha_init
from transformer_tpu.ops.ffn import ffn_apply
from transformer_tpu.ops.moe import moe_apply_dropless, moe_init
from transformer_tpu.ops.short_conv import init_conv_state, short_conv_apply, short_conv_init

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 256


def _json(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


def tiny_model() -> dict:
    """The cell's rehearsal size: the published structure at toy widths."""
    model = _json("configs", "lfm2-8b-a1b.json")["model"]
    model.update(_json("workloads", "lfm2-8b-a1b.longform-saturated.json")["rehearse"]["model"])
    return model


@pytest.fixture(scope="module")
def model():
    return tiny_model()


@pytest.fixture(scope="module")
def cfg(model):
    return ModelConfig(**model)


@pytest.fixture(scope="module")
def params(cfg):
    from perfbench.program_api import _roughen

    key = jax.random.PRNGKey(3)
    return _roughen(transformer_init(key, cfg), key)


def _scheduler(cfg, params, **kw):
    from perfbench.program_api import IdTokenizer
    from transformer_tpu.serve.scheduler import ContinuousScheduler

    kw = {"num_slots": 4, "max_total": 64, "kv_layout": "paged", "kv_block": 16, "decode_kernel": "paged_flash", **kw}
    return ContinuousScheduler(params, cfg, IdTokenizer(), **kw)


def _forward(params, ids, cfg):
    return jax.jit(transformer_apply, static_argnums=3)(params, None, ids, cfg)


def _close(got, want, rel=2e-5):
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < rel * np.abs(np.asarray(want)).max()


# ---------------------------------------------------------------- the config


def test_config_from_json_is_hashable_round_trips_and_names_its_state_layers(model, cfg):
    assert isinstance(model["layer_pattern"], list) and isinstance(model["attention_kinds"][0], dict)
    assert hash(cfg) == hash(ModelConfig(**model))
    assert config_from_json(ModelConfig, config_to_json(cfg)) == cfg
    kinds = [cfg.layer_kind(i) for i in range(8)]
    assert [k.conv_kernel for k in kinds] == [3, 3, 0, 3, 3, 3, 0, 3]
    assert cfg.state_layers == (0, 1, 3, 4, 5, 7) and cfg.head_dim == 16
    assert (cfg.moe_score, cfg.moe_select_bias, cfg.qk_norm, cfg.experts_held) == ("sigmoid", True, True, 8)
    jax.jit(lambda x, c: x * c.num_layers, static_argnames="c")(1.0, cfg)  # a static argument
    assert ModelConfig().state_layers == () and AttentionKind("full").conv_kernel == 0


def test_the_configuration_file_states_the_published_model_and_one_cut():
    file = _json("configs", "lfm2-8b-a1b.json")
    pub, m = file["published"], file["model"]
    assert file["reduced"] == ["num_hidden_layers"] and pub["num_hidden_layers"] == 24 and file["num_hidden_layers"] == 16
    assert all(file[k] == v for k, v in pub.items() if k != "num_hidden_layers")
    assert pub["layer_types"][:16] == ["conv", "conv", "full_attention", "conv"] * 4 == [
        m["layer_pattern"][i % 4] for i in range(16)]
    assert (m["d_model"], m["dff"], m["moe_dff"], m["moe_experts"], m["moe_experts_held"], m["moe_top_k"]) == (
        pub["hidden_size"], pub["intermediate_size"], pub["moe_intermediate_size"], 32, 32, pub["num_experts_per_tok"])
    assert m["target_vocab_size"] == pub["vocab_size"] and m["moe_leading_dense"] == pub["num_dense_layers"]
    assert m["num_heads"] * m["head_size"] == m["d_model"] and m["layernorm_epsilon"] == pub["norm_eps"]
    assert next(k for k in m["attention_kinds"] if k["name"] == "conv")["conv_kernel"] == pub["conv_L_cache"]
    for item in ("head size", "router", "q and k normalisation", "short convolution", "embedding and head"):
        assert len(file["assumed"][item]) > 40  # each with its reason
    assert "two pipeline stages" in file["deployment"]


@pytest.mark.parametrize("bad", [
    {"moe_score": "tanh"},
    {"moe_dispatch": "capacity"},
    {"moe_score": "softmax"},  # a selection bias goes with sigmoid scores
    {"decoder_only": False},
    {"attention_kinds": [{"name": "conv", "conv_kernel": 1}, {"name": "full_attention"}]},
])
def test_config_refuses(model, bad):
    with pytest.raises(ValueError):
        ModelConfig(**{**model, **bad})


def test_parameters_are_a_convolution_or_an_attention_mixer_and_no_bias_but_the_routers(cfg, params):
    layers = params["decoder"]["layers"]
    assert [("conv" in l, "self_mha" in l) for l in layers] == [(i % 4 != 2, i % 4 == 2) for i in range(8)]
    conv = layers[0]["conv"]
    assert (conv["in"]["kernel"].shape, conv["conv"]["kernel"].shape, conv["out"]["kernel"].shape) == ((64, 192), (3, 64), (64, 64))
    mha = layers[2]["self_mha"]
    assert mha["q_norm"]["scale"].shape == mha["k_norm"]["scale"].shape == (16,)
    assert "ffn" in layers[0] and "ffn" in layers[1] and all("moe" in l for l in layers[2:])
    router = layers[2]["moe"]["router"]
    assert router["bias"].shape == (8,) and router["bias"].dtype == jnp.float32 and float(jnp.abs(router["bias"]).max()) > 0
    biases = [p for p, _ in jax.tree_util.tree_flatten_with_path(params)[0] if getattr(p[-1], "key", "") == "bias"]
    assert len(biases) == 6 and all(getattr(p[-2], "key", "") == "router" for p in biases)
    assert "final" not in params  # the head is the embedding's transpose


def test_lfm2_params_counts_the_programs_tree_at_the_chips_size():
    m = _json("configs", "lfm2-8b-a1b.json")["model"]
    tree = jax.eval_shape(lambda k: transformer_init(k, ModelConfig(**m)), jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(tree)
    assert sum(int(np.prod(x.shape)) for x in leaves) == flops_bytes_hybrid.lfm2_params(m) == 5_399_129_024
    assert sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves) > 10.5e9  # the chip holds them in bfloat16
    assert flops_bytes_hybrid.layers_by_state(m) == (4, 0, 12)
    assert flops_bytes_hybrid.kv_bytes_per_token(m) == 8192 and flops_bytes_hybrid.state_bytes_per_slot(m) == 98304


# ------------------------------------------- the convolution, q/k norm, router


@pytest.mark.parametrize("tokens", [1, 2, 3, 17])
def test_one_token_convolution_against_the_full_sequence_form(tokens):
    p = short_conv_init(jax.random.PRNGKey(0), 32, 3)
    h = jax.random.normal(jax.random.PRNGKey(tokens), (2, tokens, 32))
    whole, end = short_conv_apply(p, h)
    state, outs = init_conv_state(2, 32, 3, jnp.float32), []
    for t in range(tokens):  # the decode step: one row against the last two gated inputs
        y, state = short_conv_apply(p, h[:, t : t + 1], state)
        outs.append(y)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), whole, atol=2e-6)
    np.testing.assert_allclose(state, end, atol=1e-6)
    _close(whole, lfm2_lm.short_conv(p, h), 1e-5)
    # ... and in two chunks, the second with the first's state as its left edge
    if tokens > 1:
        a, mid = short_conv_apply(p, h[:, :1])
        b, last = short_conv_apply(p, h[:, 1:], mid)
        np.testing.assert_allclose(jnp.concatenate([a, b], axis=1), whole, atol=2e-6)
        np.testing.assert_allclose(last, end, atol=1e-6)


def test_convolution_is_causal_depthwise_and_gated_on_both_sides():
    p = short_conv_init(jax.random.PRNGKey(0), 8, 3)
    eye = jnp.concatenate([jnp.eye(8)] * 3, axis=1)  # B = C = u = h
    p = {**p, "in": {"kernel": eye}, "out": {"kernel": jnp.eye(8)}}
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 8))
    y, _ = short_conv_apply(p, h)
    z, w = np.asarray(h[0] ** 2), np.asarray(p["conv"]["kernel"])
    want = np.stack([sum(w[j] * (z[t - 2 + j] if t - 2 + j >= 0 else 0.0) for j in range(3)) for t in range(5)])
    np.testing.assert_allclose(y[0], np.asarray(h[0]) * want, atol=1e-6)


def test_qk_normalisation_divides_each_head_by_its_rms_before_the_rotation():
    p = mha_init(jax.random.PRNGKey(0), 32, 4, num_kv_heads=2, use_bias=False, qk_norm=True)
    plain = {k: v for k, v in p.items() if k not in ("q_norm", "k_norm")}
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 32))
    normed, _, _ = mha_apply(p, x, x, causal=True, rope=True, qk_norm_epsilon=1e-5)
    assert np.abs(np.asarray(normed) - np.asarray(mha_apply(plain, x, x, causal=True, rope=True)[0])).max() > 1e-2
    # A query or key kernel ten times as large changes nothing once each head is normalised.
    big = {**p, "query": {"kernel": p["query"]["kernel"] * 10.0}, "key": {"kernel": p["key"]["kernel"] * 10.0}}
    np.testing.assert_allclose(mha_apply(big, x, x, causal=True, rope=True, qk_norm_epsilon=1e-5)[0], normed, atol=2e-5)
    _close(normed, lfm2_lm.attention(p, x, 10000.0, 1e-5), 1e-5)


def _sigmoid_layer(bias):
    p = moe_init(jax.random.PRNGKey(0), 32, 64, 8, activation="swiglu", select_bias=True)
    p["router"]["bias"] = jnp.asarray(bias, jnp.float32)
    return p


def test_sigmoid_selection_with_a_bias_against_a_plain_loop():
    """A bias large enough to change the choice: expert 6 is chosen by every
    token; its weight is still its own score over the chosen scores' sum."""
    bias = np.zeros(8, np.float32)
    bias[6] = 10.0
    p, x = _sigmoid_layer(bias), jax.random.normal(jax.random.PRNGKey(1), (9, 32))
    kw = {"num_experts": 8, "top_k": 2, "score": "sigmoid", "renorm_epsilon": 1e-6}
    y, counts = moe_apply_dropless(p, x, **kw)
    s = np.asarray(jax.nn.sigmoid(x @ p["router"]["kernel"]), np.float64)
    want = np.zeros((9, 32))
    for t in range(9):
        chosen = np.argsort(-(s[t] + bias))[:2]
        assert 6 in chosen
        for e in chosen:
            one = {n: {"kernel": p[n]["kernel"][e]} for n in ("gate", "in", "out")}
            want[t] += s[t, e] / (s[t, chosen].sum() + 1e-6) * np.asarray(ffn_apply(one, x[t : t + 1], "swiglu"))[0]
    np.testing.assert_allclose(y, want, atol=3e-6)
    assert int(counts[0]) == 18 and int(counts[2]) == 9  # every pick held here; expert 6 got every token
    _close(y, lfm2_lm.experts(p, x, 2, 0, 1.0, 1e-6), 1e-5)
    # The choice moved and the weights did not take the bias in: with it in they would be near 1 for expert 6.
    unbiased, _ = moe_apply_dropless(_sigmoid_layer(np.zeros(8)), x, **kw)
    assert np.abs(np.asarray(y) - np.asarray(unbiased)).max() > 1e-3


@pytest.mark.parametrize("tokens", [5, 40, 300])
def test_no_token_is_dropped_when_every_token_picks_one_expert(tokens):
    bias = np.zeros(8, np.float32)
    bias[5] = 10.0
    p = _sigmoid_layer(bias)
    x = jax.random.normal(jax.random.PRNGKey(2), (tokens, 32))
    y, counts = moe_apply_dropless(p, x, num_experts=8, top_k=1, score="sigmoid", renorm_epsilon=0.0)
    assert counts.tolist() == [tokens, 1, tokens, tokens]
    one = {n: {"kernel": p[n]["kernel"][5]} for n in ("gate", "in", "out")}
    np.testing.assert_allclose(y, ffn_apply(one, x, "swiglu"), atol=3e-6)  # weight s / s = 1: the expert alone


# ------------------------------------------------ the model, the reference


def test_full_forward_against_the_reference(model, cfg, params):
    ids = np.random.default_rng(0).integers(3, VOCAB, (2, 37)).astype(np.int32)
    got, _ = transformer_apply(params, None, jnp.asarray(ids), cfg)
    want = lfm2_lm.logits(params, ids, model)
    assert got.shape == want.shape == (2, 37, VOCAB)
    _close(got, want)


def test_prefill_then_decode_through_the_pool_programs_against_the_reference(model, cfg, params):
    """Prompts that end inside a block (21 of 16), prefilled whole through
    ``_slot_prefill_paged``, then four tokens through ``_pool_step_paged_flash``
    with the other slots fed PAD at index 0."""
    from perfbench.program_api import pool_forward_logits, pool_usage

    sched = _scheduler(cfg, params)
    assert [set(c) - {"moe_counts"} for c in sched.pool.caches] == [
        {"k", "v"} if i % 4 == 2 else {"conv_state"} for i in range(8)]  # a convolution layer has no K/V pool
    assert sched.pool.caches[0]["conv_state"].shape == (4, 2, 64)
    prompts = np.random.default_rng(1).integers(3, VOCAB, (2, 21)).astype(np.int32)
    got = pool_forward_logits(sched, prompts, 4)
    full = np.concatenate([prompts, got[:, :4].argmax(-1).astype(np.int32)], axis=1)
    _close(got, lfm2_lm.logits(params, full, model, first=20))
    assert pool_usage(sched)[0] <= 1  # the pool is left idle (the sink block aside)


def _prefill(sched, slot, ids, first):
    from transformer_tpu.serve import scheduler as S

    pool = sched.pool
    sched._paged_ensure(slot, first + len(ids))
    logits, pool.caches = S._slot_prefill_paged(
        sched.params, pool.caches, pool.alloc.table_device(), jnp.int32(slot), jnp.asarray([ids], jnp.int32),
        jnp.int32(first), sched.cfg, sched.prefill_chunk, pool.block_tokens, pool.buf_len)
    return np.asarray(logits[0], np.float32)


def _step(sched, toks, index):
    from transformer_tpu.serve import scheduler as S

    pool = sched.pool
    logits, pool.caches = S._pool_step_paged_flash(
        sched.params, pool.caches, pool.alloc.table_device(), jnp.asarray(index, jnp.int32),
        jnp.asarray(toks, jnp.int32), sched.cfg, pool.block_tokens, sched._kernel_interpret)
    return np.asarray(logits, np.float32)


def test_a_second_prefill_chunk_and_the_prompt_tail_walk_continue_the_slots_state(model, cfg, params):
    """16 tokens prefilled at ``first = 0``, 8 more at ``first = 16`` (the
    convolution's left edge is the slot's own state, the attention reads the
    slot's blocks), then 5 walked through the step, a neighbour stepping
    beside it all the while."""
    sched = _scheduler(cfg, params)
    ids = np.random.default_rng(4).integers(3, VOCAB, (2, 29)).astype(np.int32)
    want = np.asarray(lfm2_lm.logits(params, ids, model))
    for slot in (0, 2):
        _close(_prefill(sched, slot, ids[slot // 2, :16], 0), want[slot // 2, 15])
    _close(_prefill(sched, 0, ids[0, 16:24], 16), want[0, 23])
    _close(_prefill(sched, 2, ids[1, 16:24], 16), want[1, 23])
    toks, index = np.zeros(4, np.int32), np.zeros(4, np.int32)
    for t in range(24, 29):
        for slot in (0, 2):
            sched._paged_ensure(slot, t + 1)
        toks[[0, 2]], index[[0, 2]] = ids[:, t], t
        got = _step(sched, toks, index)
        _close(got[[0, 2]], want[:, t])


def test_a_freed_slots_state_never_reaches_the_next_request_nor_a_neighbour(model, cfg, params):
    rng = np.random.default_rng(5)
    first, second, other = (rng.integers(3, VOCAB, n).astype(np.int32) for n in (19, 16, 24))
    sched = _scheduler(cfg, params)
    # Slot 2 is idle throughout (fed PAD at index 0): whatever its state row holds stays there.
    marked = [dict(c, conv_state=c["conv_state"].at[2].set(7.0)) if "conv_state" in c else c for c in sched.pool.caches]
    sched.pool.caches = marked
    _prefill(sched, 1, first, 0)
    _prefill(sched, 3, other[:23], 0)
    toks, index = np.zeros(4, np.int32), np.zeros(4, np.int32)
    toks[[1, 3]], index[[1, 3]] = (7, other[23]), (19, 23)
    sched._paged_ensure(1, 20)
    sched._paged_ensure(3, 24)
    got = _step(sched, toks, index)  # slots 1 and 3 roll their states side by side
    _close(got[3], np.asarray(lfm2_lm.logits(params, other[None], model))[0, -1])
    assert np.abs(np.asarray(sched.pool.caches[0]["conv_state"][1])).max() > 0
    sched.pool.alloc.free_slot(1)
    got = _prefill(sched, 1, second, 0)  # the same slot again: its state row holds the first request's tail
    fresh = _prefill(_scheduler(cfg, params), 1, second, 0)
    np.testing.assert_array_equal(got, fresh)
    _close(got, np.asarray(lfm2_lm.logits(params, second[None], model))[0, -1])
    for c in sched.pool.caches:
        if "conv_state" in c:
            np.testing.assert_array_equal(np.asarray(c["conv_state"][2]), 7.0)


@pytest.mark.parametrize("deployment", [
    {"kv_layout": "paged", "decode_kernel": "paged_flash"},
    {"kv_layout": "paged", "decode_kernel": "xla"},
    {"kv_layout": "dense", "decode_kernel": "xla"},
    {"kv_layout": "paged", "decode_kernel": "paged_flash", "prefill_chunk": 8},
], ids=["paged_flash", "paged_xla", "dense_xla", "chunked_prefill"])
def test_scheduler_answers_are_the_full_forwards_greedy_tokens(cfg, params, deployment):
    """Five requests over four slots (one waits for a freed slot) through the
    first period of the model, each prompt prefilled to 16 and walking its
    tail: every answered token is the full forward's greedy choice."""
    import dataclasses

    short = dataclasses.replace(cfg, num_layers=4)
    some = {**params, "decoder": {**params["decoder"], "layers": params["decoder"]["layers"][:4]}}
    sched = _scheduler(short, some, **deployment)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(3, VOCAB, n - 1) for n in (20, 17, 30, 19, 23)]
    for ids in prompts:
        sched.submit({"prompt": " ".join(map(str, ids)), "max_new": 5})
    answers = [[int(t) for t in a["continuation"].split()] for a in sched.run([])]
    fed = np.zeros((5, 36), np.int32)  # padded at the end: a causal model's earlier positions do not see it
    for row, (ids, out) in enumerate(zip(prompts, answers)):
        assert len(out) == 5
        fed[row, : len(ids) + 6] = [1, *ids, *out]
    logits, _ = _forward(some, jnp.asarray(fed), short)
    for row, (ids, out) in enumerate(zip(prompts, answers)):
        assert np.asarray(logits[row, len(ids) : len(ids) + 5].argmax(-1)).tolist() == out


# ------------------------------------------------------ refusals and counts


def test_prefix_cache_speculation_forks_and_a_mesh_refuse_a_stateful_layer(cfg, params):
    from transformer_tpu.serve.prefix_cache import PrefixCache

    with pytest.raises(ValueError, match="cached prefix holds rows a position and no snapshot.*a state-space layer's state"):
        PrefixCache(cfg, block_tokens=16)
    plain = ModelConfig(num_layers=1, d_model=32, num_heads=2, dff=64, input_vocab_size=64, target_vocab_size=64,
                        decoder_only=True, position_scheme="rope", dtype="float32", max_position=64)
    with pytest.raises(ValueError, match="no snapshot of the state a slot keeps beside them"):
        _scheduler(cfg, params, prefix_cache=PrefixCache(plain, block_tokens=16))
    with pytest.raises(ValueError, match="rejected draft.*a state-space layer's state.*cannot be rolled back"):
        _scheduler(cfg, params, speculate_k=2)
    with pytest.raises(ValueError, match="a short convolution, a delta-rule or a state-space layer.*state a slot beside it"):
        _scheduler(cfg, params, kv_layout="dense", decode_kernel="xla", mesh=2)
    sched = _scheduler(cfg, params)
    sched._paged_ensure(0, 16)
    sched.pool.alloc.extend(1, bid=int(sched.pool.alloc.table_device()[0, 0]))  # slot 1 shares slot 0's block
    with pytest.raises(ValueError, match="copy-on-write fork.*at the fork's position is not kept"):
        sched._paged_cow(1, 0, 16)


def test_step_spans_count_positions_the_most_loaded_expert_and_the_state(cfg, params, monkeypatch):
    from transformer_tpu.obs.telemetry import Telemetry
    from transformer_tpu.obs.trace import buffer
    from transformer_tpu.serve import scheduler as S

    monkeypatch.setattr(S, "_MOE_READ_EVERY", 3)
    tel = Telemetry(interval=1e12)
    sched = _scheduler(cfg, params, telemetry=tel)
    before = len(buffer().snapshot())
    rng = np.random.default_rng(2)
    for n in (20, 11, 30):
        sched.submit({"prompt": " ".join(map(str, rng.integers(3, VOCAB, n - 1))), "max_new": 6})
    assert len(sched.run([])) == 3
    steps = [s for s in buffer().snapshot()[before:] if s["name"] == "scheduler.step"]
    # Positions for a model with layer kinds though none has a window; no band then.
    assert steps and all("attn_pos_full" in s and "attn_pos_band" not in s for s in steps)
    assert steps[0]["active"] == 3 and steps[0]["attn_pos_full"] == 17 + 9 + 17
    read = [s for s in steps if "moe_steps" in s]
    assert read and all(s["moe_steps"] == 3 for s in read)
    for s in read:
        layers, held, top_k = 6, 8, 2
        assert s["moe_assign"] == s["moe_tokens"] * layers * top_k  # every expert is here: no pick falls elsewhere
        assert s["moe_assign"] / held <= s["moe_max_load"] <= s["moe_tokens"] * layers
    reg = tel.registry
    assert reg.counter("serve_moe_max_load_total").value == sum(s["moe_max_load"] for s in read)
    assert reg.gauge("serve_state_layers", "").value == 6
    assert reg.gauge("serve_state_bytes_per_slot", "").value == 6 * 2 * 64 * 4


# ------------------- heads that pack two a lane row, through the scheduler
#
# The cell's rehearsal model has 2 KV heads of 16, which never meet the rule
# that lays the published 8 x 64 out as 4 x 128: these models' heads DO pack
# (2 KV heads of 64 in float32: one lane row), so the CPU suite runs the pool
# kept by lane rows, its writers and readers, and the streamed route over it.


def _packing(model, **over) -> tuple[ModelConfig, dict]:
    kinds = [{**k, "num_heads": 4} if k["name"] == "full_attention" else k for k in model["attention_kinds"]]
    cfg = ModelConfig(**{**model, "num_layers": 4, "head_size": 64, "attention_kinds": kinds, **over})
    return cfg, transformer_init(jax.random.PRNGKey(5), cfg)


def _attention_only(model, **over):
    """The attention mixer alone (speculation and the prefix cache refuse a
    stateful layer): q/k normalisation, GQA, the rotary base, and the two
    leading dense SwiGLU layers (so no expert layer is left)."""
    return _packing(model, **{"num_layers": 2, "layer_pattern": ["full_attention"], **over})


def _answers(cfg, params, requests, **deployment):
    sched = _scheduler(cfg, params, num_slots=2, **deployment)
    out = sched.run([dict(r) for r in requests])
    assert all(r.get("continuation") for r in out), out
    return [r["continuation"] for r in out], sched


def _requests(lengths, **extra):
    rng = np.random.default_rng(11)
    return [{"prompt": " ".join(map(str, rng.integers(3, VOCAB, n - 1))), "max_new": 5, **extra} for n in lengths]


def test_heads_that_pack_are_kept_by_lane_rows_and_answer_alike_on_every_route(model):
    """Conv, conv, attention, conv with 2 KV heads of 64: the attention
    layer's pool is (blocks, 16, 1, 128), the fused step streams it (the
    gauges say so), and five requests over two slots (a slot freed and
    admitted again, prompts prefilled to 16 that walk their tails) answer
    byte for byte as the gather step and the dense layout do."""
    from transformer_tpu.obs.telemetry import Telemetry

    cfg, params = _packing(model)
    requests = _requests((20, 17, 30, 19, 23))
    tel = Telemetry(interval=1e12)
    got, sched = _answers(cfg, params, requests, telemetry=tel)
    pools = [c for c in sched.pool.caches if "k" in c]
    assert [c["k"].shape[2:] for c in pools] == [(1, 128)] and cfg.kv_heads * cfg.head_dim == 128
    assert tel.registry.gauge("serve_attn_layers_streamed", "").value == 1
    assert tel.registry.gauge("serve_attn_layers_tiled", "").value == 0
    assert got == _answers(cfg, params, requests, decode_kernel="xla")[0]
    assert got == _answers(cfg, params, requests, kv_layout="dense", decode_kernel="xla")[0]


@pytest.mark.parametrize("speculate_k", [0, 1], ids=["plain", "speculate_1"])
def test_heads_that_pack_through_speculation_and_the_prefix_cache(model, speculate_k):
    """The attention-only twin, greedy and seeded-sampled, plain and with
    ``speculate_k=1`` (verify rows, S_q = 2, on the streamed route), a prefix
    cache attached (the second wave's hits alias device blocks): the fused
    step, the gather step and the dense layout give the same bytes."""
    from transformer_tpu.serve.prefix_cache import PrefixCache

    cfg, params = _attention_only(model)
    waves = [_requests((20, 34)), _requests((20, 34, 27), temperature=0.8, seed=4)]

    def serve(**deployment):
        sched = _scheduler(cfg, params, num_slots=2, speculate_k=speculate_k,
                           prefix_cache=PrefixCache(cfg, block_tokens=16, budget_mb=8), **deployment)
        return [[r["continuation"] for r in sched.run([dict(r) for r in wave])] for wave in waves], sched

    got, sched = serve()
    assert sched.pool.caches[0]["k"].shape[2:] == (1, 128)
    assert 0 < sched.stats["prefix_hit_tokens"] == sched.stats["prefix_alias_tokens"]
    assert got == serve(decode_kernel="xla")[0]
    assert got == serve(kv_layout="dense", decode_kernel="xla")[0]
    sched.pool.alloc.check_consistency()


def test_a_block_of_a_pool_kept_by_lane_rows_is_the_host_format_byte_for_byte(model):
    """Spill-to-host reads a pool block by heads, (1, B, H, D), the dense
    export's bytes; a host-tier hit writes it back into whatever rows the
    pool keeps; the same request then answers as before."""
    from transformer_tpu.serve.prefix_cache import PrefixCache

    cfg, params = _attention_only(model)
    request = _requests((40,))
    ids = [1, *map(int, request[0]["prompt"].split())]

    def serve(**deployment):
        cache = PrefixCache(cfg, block_tokens=16, budget_mb=8)
        sched = _scheduler(cfg, params, num_slots=2, prefix_cache=cache, **deployment)
        return sched.run([dict(request[0])])[0]["continuation"], sched, cache

    want, _, dense_cache = serve(kv_layout="dense", decode_kernel="xla")
    got, sched, cache = serve()
    assert got == want and sched.pool.caches[0]["k"].shape[2:] == (1, 128)
    assert cache.release_device_blocks(1 << 30) == 2  # both whole blocks of the prompt go to the host trie
    hit, dense_hit = cache.match(ids), dense_cache.match(ids)
    assert hit.tokens == dense_hit.tokens == 32
    for ours, theirs in zip(hit.stacked(64), dense_hit.stacked(64)):
        for key in ("k", "v"):
            assert ours[key].shape == (1, 32, 2, 64)
            np.testing.assert_array_equal(ours[key], theirs[key])
    hit.release(), dense_hit.release()
    before = sched.stats["prefix_alias_tokens"]
    assert sched.run([dict(request[0])])[0]["continuation"] == want  # restored through _pool_write_blocks
    assert sched.stats["prefix_alias_tokens"] == before and sched.stats["prefix_hit_tokens"] >= 32


def test_an_int8_pool_keeps_its_heads_and_the_tiled_route(model):
    from transformer_tpu.obs.telemetry import Telemetry

    cfg, params = _attention_only(model, num_layers=1, kv_cache_int8=True)
    tel = Telemetry(interval=1e12)
    sched = _scheduler(cfg, params, telemetry=tel)
    assert sched.pool.caches[0]["k"].shape[2:] == (2, 64) and sched.pool.caches[0]["k_scale"].shape[2:] == (2, 1)
    assert tel.registry.gauge("serve_attn_layers_streamed", "").value == 0
    assert tel.registry.gauge("serve_attn_layers_tiled", "").value == 1


# ------------------------- the other served configurations' programs stand


def _lowered_serving_programs(cell_name: str) -> str:
    """sha256 over the lowered text of a cell's pool step and 16-token
    prefill at its rehearsal size (the CPU's lowering: kernels inlined)."""
    from transformer_tpu.serve import scheduler as S

    cell = _json("workloads", cell_name + ".json")
    m = _json("configs", cell["config"] + ".json")["model"]
    m.update(cell["rehearse"]["model"])
    cfg = ModelConfig(**m)
    sched = _scheduler(cfg, jax.eval_shape(lambda k: transformer_init(k, cfg), jax.random.PRNGKey(0)))
    pool, table = sched.pool, sched.pool.alloc.table_device()
    vec, i32 = jnp.zeros((4,), jnp.int32), jnp.int32(0)
    texts = [
        S._pool_step_paged_flash.lower(sched.params, pool.caches, table, vec, vec, cfg, 16, True).as_text(),
        S._slot_prefill_paged.lower(sched.params, pool.caches, table, i32, jnp.zeros((1, 16), jnp.int32), i32, cfg,
                                    0, 16, pool.buf_len).as_text(),
    ]
    return hashlib.sha256("\n".join(texts).encode()).hexdigest()


def test_the_other_served_configurations_lower_as_recorded():
    """``starcoder2-3b``'s step and prefill as the commit before this model
    lowered them (nothing of a layer kind, a router form or a state is in
    them); ``laguna-s-2.1``'s as this one does: its step gained the count of
    the most-loaded expert's rows, a maximum over the group sizes an expert
    layer, and nothing else (PERF.md, PR 33). A change of shared code moves
    these on purpose: record them again (tests/fixtures/lowered_serving_programs.json)."""
    with open(os.path.join(ROOT, "tests", "fixtures", "lowered_serving_programs.json")) as f:
        recorded = json.load(f)
    for cell in ("sc2-3b.chat-saturated", "laguna-s.agent-saturated"):
        assert _lowered_serving_programs(cell) == recorded[cell], cell
