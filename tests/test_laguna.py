"""A model whose layers are of several kinds (window and full attention with
their own head counts, per-head gate, partial and YaRN rotary, RMSNorm, no
biases) over a dropless shared-plus-routed expert layer of which this chip
holds a share: the program against the plain reference
(``perfbench/reference/laguna_lm.py``) at a small size on the CPU, and each
mechanism against its closed form."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import laguna_lm
from transformer_tpu.config import AttentionKind, ModelConfig, config_from_json, config_to_json
from transformer_tpu.kernels.flash_attention import paged_attention
from transformer_tpu.models.transformer import transformer_apply, transformer_init
from transformer_tpu.ops.ffn import ffn_apply
from transformer_tpu.ops.moe import dropless_tile_rows, moe_apply_dropless, moe_init
from transformer_tpu.ops.nn import norm_apply, norm_init
from transformer_tpu.ops.positional import apply_rope, kind_rope, rope_inv_freq

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW = 8


def tiny_model() -> dict:
    """The cell's rehearsal size: the published structure at toy widths."""
    with open(os.path.join(ROOT, "perfbench", "configs", "laguna-s-2.1.json")) as f:
        model = json.load(f)["model"]
    with open(os.path.join(ROOT, "perfbench", "workloads", "laguna-s.agent-saturated.json")) as f:
        model.update(json.load(f)["rehearse"]["model"])
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


# ---------------------------------------------------------------- the config


def test_config_from_json_lists_is_hashable_and_round_trips(model, cfg):
    assert isinstance(model["layer_pattern"], list) and isinstance(model["attention_kinds"][0], dict)
    assert hash(cfg) == hash(ModelConfig(**model))
    assert config_from_json(ModelConfig, config_to_json(cfg)) == cfg
    assert cfg.head_dim == 32 and cfg.d_model // cfg.num_heads == 16
    kinds = [cfg.layer_kind(i) for i in range(5)]
    assert [k.name for k in kinds] == ["full", "sliding", "sliding", "sliding", "full"]
    assert [k.num_heads for k in kinds] == [4, 6, 6, 6, 4] and [k.window for k in kinds] == [0, WINDOW, WINDOW, WINDOW, 0]
    jax.jit(lambda x, c: x * c.num_layers, static_argnames="c")(1.0, cfg)  # a static argument


def test_a_model_of_one_kind_describes_itself():
    plain = ModelConfig(num_heads=4, attention_window=7)
    assert plain.layer_kind(3) == AttentionKind("", 4, 7)
    assert plain.head_dim == plain.d_model // 4


@pytest.mark.parametrize("bad", [
    {"layer_pattern": ["full", "other"]},
    {"layer_pattern": []},
    {"moe_dispatch": "capacity"},
    {"moe_expert_offset": 12},
    {"decoder_only": False},
    {"moe_out_init_scale": 0.0},
    {"moe_router_init_scale": -1.0},
])
def test_config_refuses(model, bad):
    with pytest.raises(ValueError):
        ModelConfig(**{**model, **bad})


def test_capacity_dispatch_refuses_a_share_of_the_experts():
    with pytest.raises(ValueError, match="dropless"):
        ModelConfig(moe_experts=4, moe_experts_held=2)


def test_init_scales_move_the_router_and_the_routed_out_kernels_alone():
    """``moe_router_init_scale`` / ``moe_out_init_scale``: the same draw times
    the factor, every other leaf as it was; 1.0 is the Glorot draw itself."""
    key, gated = jax.random.PRNGKey(5), dict(experts_held=4, activation="swiglu", shared_dff=16)
    plain = moe_init(key, 32, 16, 8, **gated)
    shaped = moe_init(key, 32, 16, 8, router_scale=3.0, out_scale=0.125, **gated)
    moved = {("router", "kernel"): 3.0, ("out", "kernel"): 0.125}
    flat = lambda p: {tuple(k.key for k in path): x for path, x in jax.tree_util.tree_flatten_with_path(p)[0]}  # noqa: E731
    for path, x in flat(shaped).items():
        np.testing.assert_allclose(x, flat(plain)[path] * moved.get(path, 1.0), rtol=1e-6)
    full = ModelConfig(**{**tiny_model(), "moe_router_init_scale": 1.0, "moe_out_init_scale": 1.0})
    layer = transformer_init(key, full)["decoder"]["layers"][1]["moe"]
    limit = math.sqrt(6.0 / (full.d_model + full.moe_experts))  # Glorot's own bound
    assert limit * 0.9 < float(jnp.abs(layer["router"]["kernel"]).max()) <= limit


def test_parameters_have_each_kinds_heads_and_no_bias(cfg, params):
    layers = params["decoder"]["layers"]
    assert [l["self_mha"]["query"]["kernel"].shape for l in layers] == [(64, h, 32) for h in (4, 6, 6, 6, 4)]
    assert all(l["self_mha"]["key"]["kernel"].shape == (64, 2, 32) for l in layers)
    assert [l["self_mha"]["gate"]["kernel"].shape[1] for l in layers] == [4, 6, 6, 6, 4]
    assert "ffn" in layers[0] and all("moe" in l for l in layers[1:])
    moe = layers[1]["moe"]
    assert moe["router"]["kernel"].shape == (64, 16) and moe["in"]["kernel"].shape == (8, 64, 32)
    names = {str(getattr(p[-1], "key", "")) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert "bias" not in names and names == {"kernel", "scale", "table"}


# ------------------------------------------------------ rotary, norm, experts


def test_yarn_frequencies_against_the_closed_form():
    full = AttentionKind("full", 48, 0, 500000.0, 0.5, 128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    kw = kind_rope(full)
    got = rope_inv_freq(64, **{k: v for k, v in kw.items() if k.startswith("yarn") or k == "base"})
    rot, base = 64, 500000.0
    f = base ** (-np.arange(32) / 32.0)
    lo = math.floor(rot * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(base)))
    hi = math.ceil(rot * math.log(8192 / (1 * 2 * math.pi)) / (2 * math.log(base)))
    assert (lo, hi) == (9, 18)
    ramp = np.clip((np.arange(32) - lo) / (hi - lo), 0, 1)
    np.testing.assert_allclose(got, f * (1 - ramp) + f / 128 * ramp, rtol=1e-6)
    assert np.allclose(got[:10], f[:10]) and np.allclose(got[18:], f[18:] / 128)  # kept; interpolated
    np.testing.assert_allclose(got, laguna_lm.inverse_frequencies(
        {"rope_base": base, "rotary_share": 0.5, "yarn_factor": 128.0, "yarn_original_max_position": 8192}, 128), rtol=1e-6)


def test_partial_rotary_turns_the_first_channels_and_passes_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 16))
    pos = jnp.arange(6) + 3
    got = np.asarray(apply_rope(x, pos, 500.0, rotary_share=0.5, attention_factor=1.5))
    np.testing.assert_array_equal(got[..., 8:], np.asarray(x)[..., 8:])
    ang = np.asarray(pos, np.float64)[:, None] * 500.0 ** (-np.arange(4) / 4.0)
    a, b = np.asarray(x)[..., :4], np.asarray(x)[..., 4:8]
    cos, sin = 1.5 * np.cos(ang)[None, :, None], 1.5 * np.sin(ang)[None, :, None]
    np.testing.assert_allclose(got[..., :4], a * cos - b * sin, atol=1e-5)
    np.testing.assert_allclose(got[..., 4:8], b * cos + a * sin, atol=1e-5)
    # The whole head at base 10,000 is the rotation every other model has.
    np.testing.assert_array_equal(np.asarray(apply_rope(x, pos)), np.asarray(apply_rope(x, pos, rotary_share=1.0)))


def test_rmsnorm_has_a_scale_and_no_mean():
    p = norm_init(8, kind="rmsnorm")
    assert set(p) == {"scale"}
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8)) + 2.0
    got = norm_apply({"scale": p["scale"] * 1.5}, x, 1e-6, "rmsnorm")
    np.testing.assert_allclose(got, 1.5 * x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6), rtol=1e-5)


def _share(full, offset, held):
    return {**full, **{n: {"kernel": full[n]["kernel"][offset : offset + held]} for n in ("gate", "in", "out")}}


def test_the_two_shares_and_the_shared_expert_once_are_the_uncut_layer():
    full = moe_init(jax.random.PRNGKey(0), 32, 128, 16, activation="swiglu", shared_dff=64)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 7, 32))
    kw = {"num_experts": 16, "top_k": 4, "routed_scale": 2.5}
    whole, counts = moe_apply_dropless(full, x, **kw)
    y0, c0 = moe_apply_dropless(_share(full, 0, 8), x, expert_offset=0, **kw)
    y1, c1 = moe_apply_dropless(_share(full, 8, 8), x, expert_offset=8, **kw)
    shared = ffn_apply(full["shared"], x, "swiglu")
    np.testing.assert_allclose(y0 + y1 - shared, whole, atol=2e-6)
    assert int(counts[0]) == 3 * 7 * 4 and int(c0[0]) + int(c1[0]) == 3 * 7 * 4  # every pick lands on one chip
    assert int(c0[1]) <= 8 and int(c1[1]) <= 8 and int(c0[1]) + int(c1[1]) == int(counts[1])
    # ... and is the plain sum over experts the reference computes
    want = laguna_lm.experts(full, x.reshape(-1, 32), 4, 0, 2.5).reshape(x.shape)
    np.testing.assert_allclose(whole, want, atol=2e-6)


@pytest.mark.parametrize("tokens", [5, 40, 300])
def test_no_token_is_dropped_when_every_token_picks_one_expert(tokens):
    p = moe_init(jax.random.PRNGKey(0), 32, 64, 8, activation="swiglu")
    p["router"] = {"kernel": jnp.zeros((32, 8)).at[:, 5].set(1.0)}
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (tokens, 32))) + 0.1  # every logit of expert 5 is the largest
    y, counts = moe_apply_dropless(p, x, num_experts=8, top_k=1)
    assert counts.tolist() == [tokens, 1, tokens]
    one = {n: {"kernel": p[n]["kernel"][5]} for n in ("gate", "in", "out")}
    np.testing.assert_allclose(y, ffn_apply(one, x, "swiglu"), atol=2e-6)  # the capacity path would drop most of them


def test_a_masked_token_is_routed_nowhere():
    p = moe_init(jax.random.PRNGKey(0), 32, 64, 8, activation="swiglu")
    x = jax.random.normal(jax.random.PRNGKey(2), (6, 32))
    mask = jnp.asarray([True, False, True, False, False, True])
    y, counts = moe_apply_dropless(p, x, num_experts=8, top_k=2, token_mask=mask)
    assert int(counts[0]) == 6 and not np.asarray(y)[~np.asarray(mask)].any()
    _, none = moe_apply_dropless(p, x, num_experts=8, top_k=2, token_mask=jnp.zeros(6, bool))
    assert none.tolist() == [0, 0, 0]


def test_tile_rows_follow_the_rows_an_expert_expects():
    assert dropless_tile_rows(32, 10, 256) == 16 and dropless_tile_rows(256, 10, 256) == 16
    assert dropless_tile_rows(1024, 10, 256) == 64 and dropless_tile_rows(2048, 10, 256) == 128 == dropless_tile_rows(10**5, 10, 256)


# ------------------------------------------------- the paged kernel's band

LENGTHS = [1, 15, 16, 17, 39, 40, 41, 63, 64, 65, 130, 384]
# Under a table of two streamed compute blocks and a quarter (1,152 positions):
# the band starts in the first page, mid-page, on a sub-chunk's edge (128), mid
# block and in the block before the slot's last, and reaches over two blocks.
WIDE_LENGTHS = [1, 127, 128, 129, 300, 512, 513, 700, 1024, 1025, 1152]


@pytest.mark.parametrize(
    "route,head,window,lengths,nmax",
    [(route, head, window, LENGTHS, 24) for route, head in [("streamed", 128), ("tiled", 64)] for window in (16, 40, 100)]
    + [("streamed", 128, window, WIDE_LENGTHS, 72) for window in (100, 300, 520)],
)
def test_paged_kernel_band_against_the_xla_oracle(route, head, window, lengths, nmax):
    """Lengths below, at and above the window and across page (16), sub-chunk
    (128) and compute-block (64 tiled, 512 streamed) edges; both routes of the
    kernel, and the streamed one over a table several blocks wide."""
    from transformer_tpu.kernels.paged_flash import _streamable

    assert _streamable(2, head, jnp.float32) == (route == "streamed")
    n = len(lengths)
    k = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(k[0], (n, 1, 6, head))
    kp, vp = (jax.random.normal(k[i], (1 + n * nmax, 16, 2, head)) for i in (1, 2))
    table = jnp.asarray(1 + np.arange(n * nmax).reshape(n, nmax), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    want = paged_attention(q, kp, vp, table, lengths, impl="xla", window=window)
    got = paged_attention(q, kp, vp, table, lengths, impl="paged_flash", window=window)
    np.testing.assert_allclose(got, want, atol=2e-6)
    full = paged_attention(q, kp, vp, table, lengths, impl="xla")
    short = np.asarray(lengths) <= window
    np.testing.assert_allclose(np.asarray(want)[short], np.asarray(full)[short], atol=1e-6)  # the band cuts nothing yet
    assert np.abs(np.asarray(want)[~short] - np.asarray(full)[~short]).max() > 1e-3


@pytest.mark.parametrize("lengths,nmax,window", [([5, 70, 190], 12, 33), ([130, 514, 650], 41, 130)],
                         ids=["one_block", "rows_and_band_across_sub_chunk_and_block_edges"])
def test_paged_kernel_band_on_verify_rows(lengths, nmax, window):
    """The second case: three query rows at 127-129 (a sub-chunk's edge), at 511-513 (a block's) and at 647-649 with
    the band's first position (row 0's) at 518, six positions into the second block."""
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k[0], (3, 3, 4, 128))
    kp, vp = (jax.random.normal(k[i], (1 + 3 * nmax, 16, 2, 128)) for i in (1, 2))
    table = jnp.asarray(1 + np.arange(3 * nmax).reshape(3, nmax), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    want = paged_attention(q, kp, vp, table, lengths, impl="xla", window=window)
    np.testing.assert_allclose(paged_attention(q, kp, vp, table, lengths, impl="paged_flash", window=window), want, atol=2e-6)


# ------------------------------------------------ the model, the reference


def test_full_forward_against_the_reference(model, cfg, params):
    ids = np.random.default_rng(0).integers(3, 256, (2, 3 * WINDOW)).astype(np.int32)
    got, _ = transformer_apply(params, None, jnp.asarray(ids), cfg)
    want = laguna_lm.logits(params, ids, model)
    assert got.shape == want.shape == (2, 3 * WINDOW, 256)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())


def _scheduler(cfg, params, **kw):
    from perfbench.program_api import IdTokenizer
    from transformer_tpu.serve.scheduler import ContinuousScheduler

    return ContinuousScheduler(params, cfg, IdTokenizer(), num_slots=4, max_total=64, kv_layout="paged",
                               kv_block=16, decode_kernel="paged_flash", **kw)


def test_prefill_then_decode_through_the_pool_programs_against_the_reference(model, cfg, params):
    """Prompts twice the window, prefilled whole through ``_slot_prefill_paged``
    (the band at absolute positions in its dense view), then four tokens through
    ``_pool_step_paged_flash`` (the band in the paged kernel, the expert layer
    inside the fused step)."""
    from perfbench.program_api import pool_forward_logits, pool_usage

    sched = _scheduler(cfg, params)
    prompts = np.random.default_rng(1).integers(3, 256, (2, 2 * WINDOW)).astype(np.int32)
    got = pool_forward_logits(sched, prompts, 4)
    full = np.concatenate([prompts, got[:, :4].argmax(-1).astype(np.int32)], axis=1)
    want = np.asarray(laguna_lm.logits(params, full, model, first=2 * WINDOW - 1))
    assert got.shape == want.shape == (2, 5, 256)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    assert pool_usage(sched)[0] <= 1  # the pool is left idle (the sink block aside)


def test_window_and_full_layers_share_one_pool(cfg, params):
    sched = _scheduler(cfg, params)
    shapes = {(c["k"].shape, c["v"].shape) for c in sched.pool.caches}
    assert len(sched.pool.caches) == 5 and len(shapes) == 1  # 2 KV heads of 32 in every layer
    assert sched.pool.alloc is not None and sched._band == WINDOW


@pytest.mark.parametrize("counted_before", [0, 2**31 - 5], ids=["fresh", "device_counts_wrap"])
def test_step_spans_count_attended_positions_and_expert_routing(cfg, params, monkeypatch, counted_before):
    from transformer_tpu.obs.telemetry import Telemetry
    from transformer_tpu.obs.trace import buffer
    from transformer_tpu.serve import scheduler as S

    monkeypatch.setattr(S, "_MOE_READ_EVERY", 3)
    tel = Telemetry(interval=1e12)
    sched = _scheduler(cfg, params, telemetry=tel)
    # The device's int32 totals are never reset: a server hours old has them
    # just below 2**31, and the next steps wrap them.
    sched.pool.caches[sched._moe_layer][S.MOE_COUNTS] = jnp.full((4,), counted_before, jnp.int32)
    sched._moe_read[:] = counted_before
    before = len(buffer().snapshot())
    rng = np.random.default_rng(2)
    for n in (20, 11, 30):
        sched.submit({"prompt": " ".join(map(str, rng.integers(3, 256, n - 1))), "max_new": 6})
    answers = sched.run([])
    steps = [s for s in buffer().snapshot()[before:] if s["name"] == "scheduler.step"]
    assert steps and all("attn_pos_full" in s and "attn_pos_band" in s for s in steps)
    first = steps[0]  # three slots: prefilled 16, 8 and 16 positions, each one more after this step
    assert first["active"] == 3 and first["attn_pos_full"] == 17 + 9 + 17
    assert first["attn_pos_band"] == 3 * WINDOW and all(s["attn_pos_band"] <= s["attn_pos_full"] for s in steps)
    read = [s for s in steps if "moe_steps" in s]
    assert len(read) == len(steps) // 3 and all(s["moe_steps"] == 3 for s in read)
    for s in read:
        layers, held, top_k = 4, 8, 4
        assert 0 < s["moe_assign"] <= s["moe_tokens"] * layers * top_k
        assert 0 < s["moe_hit"] <= s["moe_steps"] * layers * held
        # The most-loaded expert's rows: no fewer than an even share, no more than every slot's.
        assert s["moe_assign"] / held <= s["moe_max_load"] <= s["moe_tokens"] * layers
    reg = tel.registry
    assert reg.counter("serve_moe_assignments_total").value == sum(s["moe_assign"] for s in read)
    assert reg.counter("serve_moe_experts_hit_total").value == sum(s["moe_hit"] for s in read)
    assert reg.counter("serve_moe_max_load_total").value == sum(s["moe_max_load"] for s in read)
    assert len(answers) == 3 and all(len(a["continuation"].split()) == 6 for a in answers)


def test_a_model_without_such_layers_counts_none_of_this():
    from perfbench.program_api import IdTokenizer
    from transformer_tpu.obs.trace import buffer
    from transformer_tpu.serve.scheduler import ContinuousScheduler

    plain = ModelConfig(num_layers=1, d_model=32, num_heads=2, dff=64, input_vocab_size=64, target_vocab_size=64,
                        decoder_only=True, position_scheme="rope", norm_scheme="pre", dtype="float32", max_position=64)
    sched = ContinuousScheduler(transformer_init(jax.random.PRNGKey(0), plain), plain, IdTokenizer(), num_slots=2,
                                max_total=32, kv_layout="paged", kv_block=16, decode_kernel="paged_flash")
    before = len(buffer().snapshot())
    sched.run([{"prompt": "5 6 7", "max_new": 3}])
    steps = [s for s in buffer().snapshot()[before:] if s["name"] == "scheduler.step"]
    assert steps and not any(k.startswith(("attn_pos", "moe_")) for s in steps for k in s)
    assert all(set(c) == {"k", "v"} for c in sched.pool.caches)


def test_rolling_window_option_still_refuses_the_fused_step():
    from transformer_tpu.models.paged_decode import check_paged_flash_config

    with pytest.raises(ValueError, match="rolling"):
        check_paged_flash_config(ModelConfig(decoder_only=True, attention_window=8))
    check_paged_flash_config(dataclasses.replace(ModelConfig(**tiny_model()), num_layers=2))
