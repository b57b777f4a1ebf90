"""A model of latent attention (MLA) in every layer, its query compressed and its
shared key part turned by YaRN-blended rotary frequencies, with a dense first
layer and group-limited sigmoid routing over experts of which a share is held
here: the program against the plain reference
(``perfbench/reference/mla_moe_lm.py``) at a small size on the CPU, and each
new mechanism against its closed form."""

import itertools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import mla_moe_lm
from transformer_tpu.config import AttentionKind, ModelConfig, config_from_json, config_to_json
from transformer_tpu.models.transformer import transformer_apply, transformer_init
from transformer_tpu.ops.mla import init_latent_cache, latent_position_args, mla_apply, mla_init
from transformer_tpu.ops.moe import _route, moe_apply_dropless, moe_init
from transformer_tpu.ops.positional import kind_rope, rope_inv_freq

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "ax-k1.longdoc-saturated"
VOCAB = 256
EPS = 1e-6


def _json(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model():
    """The cell's rehearsal size: the published structure at toy widths."""
    m = _json("configs", "ax-k1.json")["model"]
    m.update(_json("workloads", CELL + ".json")["rehearse"]["model"])
    return m


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


def _close(got, want, rel=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() < rel * np.abs(want).max()


# ---------------------------------------------------------------- the config


def test_the_configuration_file_keeps_every_published_width_and_states_its_cuts():
    file = _json("configs", "ax-k1.json")
    pub, m = file["published"], file["model"]
    assert file["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (file["num_hidden_layers"], file["n_routed_experts"], file["vocab_size"]) == (6, 12, 20480)
    assert (pub["num_hidden_layers"], pub["n_routed_experts"], pub["vocab_size"]) == (61, 192, 163840)
    assert all(file[k] == v for k, v in pub.items() if k not in file["reduced"])
    (mla,) = m["attention_kinds"]
    yarn = pub["rope_scaling"]
    assert (m["d_model"], mla["num_heads"], m["dff"], m["moe_dff"], m["moe_shared_dff"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["moe_intermediate_size"] * pub["n_shared_experts"])
    assert (mla["latent_query_rank"], mla["latent_rank"], mla["latent_nope_dim"], mla["latent_shared_dim"],
            mla["latent_value_dim"]) == (pub["q_lora_rank"], pub["kv_lora_rank"], pub["qk_nope_head_dim"],
                                        pub["qk_rope_head_dim"], pub["v_head_dim"]) == (1536, 512, 128, 64, 128)
    assert (mla["rope_base"], mla["yarn_factor"], mla["yarn_original_max_position"], mla["yarn_beta_fast"],
            mla["yarn_beta_slow"], mla["yarn_mscale_all_dim"]) == (
        pub["rope_theta"], yarn["factor"], yarn["original_max_position_embeddings"], yarn["beta_fast"],
        yarn["beta_slow"], yarn["mscale_all_dim"])
    assert yarn["mscale"] == yarn["mscale_all_dim"] and mla["latent_rotary"]  # cos and sin are scaled by 1
    assert (m["moe_experts"], m["moe_experts_held"], m["moe_top_k"], m["moe_groups"], m["moe_topk_groups"]) == (
        pub["n_routed_experts"], 12, pub["num_experts_per_tok"], pub["n_group"], pub["topk_group"]) == (192, 12, 8, 8, 4)
    assert (m["moe_routed_scale"], m["moe_leading_dense"], m["layernorm_epsilon"], m["tie_output"]) == (
        pub["routed_scaling_factor"], pub["first_k_dense_replace"], pub["rms_norm_eps"], pub["tie_word_embeddings"])
    assert m["moe_score"] == pub["scoring_func"] == "sigmoid" and not m["moe_select_bias"]
    assert m["target_vocab_size"] == m["input_vocab_size"] == file["vocab_size"] == pub["vocab_size"] // 8
    assert m["layer_pattern"] == ["mla"] and m["num_layers"] == 6
    for item in ("group score", "selection bias", "weights"):
        assert len(file["assumed"][item]) > 40  # each with its reason
    for said in ("16 chips share each layer", "experts 0-11", "1/8 of the vocabulary", "layers 0-5 of 61"):
        assert said in file["deployment"]


def test_the_chips_share_is_4_166e9_parameters(cfg):
    m = _json("configs", "ax-k1.json")["model"]
    leaves = jax.tree.leaves(jax.eval_shape(lambda k: transformer_init(k, ModelConfig(**m)), jax.random.PRNGKey(0)))
    d = 7168
    mla = d * 1536 + 1536 + 1536 * 64 * 192 + d * 576 + 512 + 512 * 64 * 256 + 64 * 128 * d
    moe_layer = mla + 12 * 3 * d * 2048 + 3 * d * 2048 + d * 192 + 2 * d
    dense_layer = mla + 3 * d * 18432 + 2 * d
    assert round(mla / 1e6, 2) == 101.12 and round(moe_layer / 1e6, 1) == 675.0 and round(dense_layer / 1e6, 1) == 497.5
    want = dense_layer + 5 * moe_layer + d + 2 * 20480 * d
    assert sum(int(np.prod(x.shape)) for x in leaves) == want == 4_166_294_528
    assert round(sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves) / 1e9, 2) == 8.33


def test_config_is_hashable_round_trips_and_names_its_kinds(model, cfg):
    assert hash(cfg) == hash(ModelConfig(**model))
    assert config_from_json(ModelConfig, config_to_json(cfg)) == cfg
    assert [cfg.layer_kind(i).mixer for i in range(3)] == ["mla"] * 3 and cfg.state_layers == ()
    kind = cfg.layer_kind(0)
    assert kind.latent_rotary and kind.latent_query_rank == 32 and (cfg.moe_groups, cfg.moe_topk_groups) == (4, 2)
    assert AttentionKind("x", latent_rank=128).latent_score_factor == 1.0  # no YaRN: no factor


@pytest.mark.parametrize("bad", [
    {"moe_groups": 3},  # 8 experts do not fall into 3 equal groups
    {"moe_groups": 4, "moe_topk_groups": 5},  # more groups kept than there are
    {"moe_groups": 4, "moe_topk_groups": 0},
    {"moe_groups": 8, "moe_topk_groups": 2},  # groups of one expert: no two largest to sum
    {"moe_groups": 4, "moe_topk_groups": 1, "moe_top_k": 3},  # one group of 2 cannot hold 3 picks
    {"moe_score": "softmax", "moe_groups": 4, "moe_topk_groups": 2},
])
def test_config_refuses_groups_that_do_not_fit(model, bad):
    with pytest.raises(ValueError, match="group-limited"):
        ModelConfig(**{**model, **bad})


def test_config_refuses_a_rotated_shared_part_of_odd_width(model):
    kind = {**model["attention_kinds"][0], "latent_shared_dim": 9}
    with pytest.raises(ValueError, match="even number"):
        ModelConfig(**{**model, "attention_kinds": [kind]})


# --------------------------------------------- YaRN's frequencies and factor


def test_the_score_factor_and_the_yarn_frequencies_by_hand():
    """A.X K1's numbers: m = 0.1 x 1 x ln 32 + 1 = 1.3466, m**2 = 1.8133; and
    the 32 rotated pairs of 64 channels at base 10,000 with YaRN 32 over
    4,096 positions: pairs that turn more than 32 times keep their frequency,
    fewer than once are divided by 32, a linear ramp between."""
    kind = ModelConfig(**_json("configs", "ax-k1.json")["model"]).layer_kind(0)
    m = 0.1 * math.log(32) + 1
    assert round(m, 4) == 1.3466 and kind.latent_score_factor == pytest.approx(m * m) and round(m * m, 4) == 1.8133
    # The ramp's ends: 64 ln(4096 / (2 pi n)) / (2 ln 10000) for n = 32 and 1 turns, floor and ceil.
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    plain = [10000 ** (-2 * i / 64) for i in range(32)]
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0) for i in range(32)]
    want = [f / 32 * r + f * (1 - r) for f, r in zip(plain, ramp)]
    got = rope_inv_freq(64, **{k: v for k, v in kind_rope(kind).items() if k not in ("rotary_share", "attention_factor")})
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(mla_moe_lm.yarn_inv_freq(64, 10000.0, 32.0, 4096, 32.0, 1.0), want, rtol=1e-12)
    assert got[:10].tolist() == pytest.approx(plain[:10], rel=1e-6) and got[23:].tolist() == pytest.approx(
        [f / 32 for f in plain[23:]], rel=1e-6)
    assert mla_moe_lm.score_factor(32.0, 1.0) == pytest.approx(m * m)
    assert latent_position_args(kind)["score_factor"] == pytest.approx(m * m)


# ------------------------------------------------------------ the latent layer


def _mla_kind():
    return AttentionKind("mla", num_heads=4, latent_rank=128, latent_nope_dim=16, latent_shared_dim=8,
                         latent_value_dim=16, latent_query_rank=32, latent_rotary=True, yarn_factor=32.0,
                         yarn_original_max_position=64, yarn_mscale_all_dim=1.0)


def _mla_layer():
    p = mla_init(jax.random.PRNGKey(1), 32, 4, 128, 16, 8, 16, jnp.float32, query_scale=3.0, query_rank=32)
    key = jax.random.PRNGKey(9)
    p["q_norm"]["scale"] = 1 + 0.1 * jax.random.normal(key, (32,))
    p["kv_norm"]["scale"] = 1 + 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (128,))
    return p


def _kind_dict(kind):
    return {f: getattr(kind, f) for f in ("rope_base", "yarn_factor", "yarn_original_max_position", "yarn_beta_fast",
                                          "yarn_beta_slow", "yarn_mscale_all_dim")}


def test_the_compressed_rotated_latent_layer_against_the_reference_in_both_forms():
    """The unabsorbed form over 37 positions, then a prefill chunk of 20 and 17
    positions one at a time in the absorbed form through a latent cache: the
    rows are cached turned, each query turned at its own position."""
    kind = _mla_kind()
    p = _mla_layer()
    assert p["q_a"]["kernel"].shape == (32, 32) and p["q_b"]["kernel"].shape == (32, 4, 24) and "query" not in p
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 37, 32))
    at = latent_position_args(kind)
    whole, _ = mla_apply(p, h, None, EPS, **at)
    with jax.default_matmul_precision("highest"):
        want = mla_moe_lm.mla(p, h, EPS, _kind_dict(kind), {})
    _close(whole, want, 1e-5)
    cache = init_latent_cache(2, 40, 128, 8, jnp.float32)
    out, cache = mla_apply(p, h[:, :20], cache, EPS, **at)
    outs = [out]
    for t in range(20, 37):
        out, cache = mla_apply(p, h[:, t : t + 1], cache, EPS, **at)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), whole, atol=5e-6)
    assert int(cache["index"]) == 37 and float(jnp.abs(cache["ckv"][:, :37, 136:]).max()) == 0.0


@pytest.mark.parametrize("alter", [{"no_score_factor": True}, {"no_key_rotation": True}])
def test_the_latent_layer_reference_sees_the_factor_and_the_rotation(alter):
    kind = _mla_kind()
    p = _mla_layer()
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 32))
    got, _ = mla_apply(p, h, None, EPS, **latent_position_args(kind))
    with jax.default_matmul_precision("highest"):
        off = mla_moe_lm.mla(p, h, EPS, _kind_dict(kind), alter)
    assert np.abs(np.asarray(got) - np.asarray(off)).max() > 1e-3 * np.abs(np.asarray(got)).max()


# ---------------------------------------------------------------- the router


def _brute_force(scores, groups, keep, k):
    """Every way to keep ``keep`` of the groups, the best by the sum of the
    groups' scores (the sum of a group's two largest); every way to pick ``k``
    of the kept experts, the best by the sum of their scores."""
    per = len(scores) // groups
    group_score = [sum(sorted(scores[g * per : (g + 1) * per])[-2:]) for g in range(groups)]
    kept = max(itertools.combinations(range(groups), keep), key=lambda gs: sum(group_score[g] for g in gs))
    experts = [e for g in kept for e in range(g * per, (g + 1) * per)]
    picked = max(itertools.combinations(experts, k), key=lambda es: sum(scores[e] for e in es))
    return set(kept), set(picked)


def test_group_limited_routing_against_a_brute_force_enumeration():
    router = {"router": {"kernel": jax.random.normal(jax.random.PRNGKey(0), (16, 12))}}
    x = jax.random.normal(jax.random.PRNGKey(1), (50, 16))
    scores, gates, ids = _route(router, x, 3, "sigmoid", 1e-20, groups=4, topk_groups=2)
    _, _, plain = _route(router, x, 3, "sigmoid", 1e-20)
    s = np.asarray(scores, np.float64)
    differs = 0
    for t in range(50):
        kept, picked = _brute_force(s[t].tolist(), 4, 2, 3)
        assert set(np.asarray(ids[t]).tolist()) == picked
        assert all(int(e) // 3 in kept for e in np.asarray(ids[t]))  # never from a dropped group
        differs += set(np.asarray(plain[t]).tolist()) != picked
    assert differs > 5  # the limit changes what plain top-k would pick
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)


def test_the_gates_of_the_dropless_layer_sum_to_the_routed_scale():
    p = moe_init(jax.random.PRNGKey(0), 16, 8, 8, activation="swiglu", shared_dff=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (30, 16))
    _, gates, ids = _route(p, x, 2, "sigmoid", 1e-20, groups=4, topk_groups=2)
    np.testing.assert_allclose(2.5 * np.asarray(gates).sum(-1), 2.5, rtol=1e-6)
    chosen, weight = mla_moe_lm.route(p, x, 2, 2.5, 1e-20, 4, 2)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1), np.sort(np.asarray(ids), -1))
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 2.5, rtol=1e-6)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips share the expert layer, two experts each, the shared expert
    computed by all: the four routed parts plus the shared expert ONCE are the
    uncut reference's layer; each chip counts the tokens with a pick held there."""
    whole = moe_init(jax.random.PRNGKey(0), 64, 32, 8, activation="swiglu", shared_dff=32)
    x = jax.random.normal(jax.random.PRNGKey(2), (23, 64))
    args = dict(top_k=2, scale=2.5, eps=1e-20, groups=4, topk_groups=2)
    uncut = mla_moe_lm.moe(whole, x, args["top_k"], 0, args["scale"], args["eps"], 4, 2)
    routed_sum, shared, picks, tokens_here = 0, None, 0, 0
    for offset in (0, 2, 4, 6):
        p = {**whole, **{n: {"kernel": whole[n]["kernel"][offset : offset + 2]} for n in ("gate", "in", "out")}}
        y, counts = moe_apply_dropless(
            p, x, num_experts=8, top_k=2, expert_offset=offset, routed_scale=2.5, score="sigmoid",
            renorm_epsilon=1e-20, groups=4, topk_groups=2, activation="swiglu")
        _close(y, mla_moe_lm.moe(p, x, 2, offset, 2.5, 1e-20, 4, 2), 1e-5)  # the reference is given the same share
        routed = np.asarray(mla_moe_lm.moe(p, x, 2, offset, 2.5, 1e-20, 4, 2, shared=False))
        routed_sum = routed_sum + routed
        shared = np.asarray(y) - routed if shared is None else shared
        _close(np.asarray(y) - routed, shared, 1e-4)  # every chip computes the same shared expert
        picks += int(counts[0])
        tokens_here += int(counts[3])
        assert int(counts[3]) == int((np.abs(routed).sum(-1) > 0).sum())  # the tokens with a pick here
    assert picks == 2 * 23 and 23 <= tokens_here <= 2 * 23
    _close(routed_sum + shared, uncut, 1e-5)


# ------------------------------------------------ the model, the reference


def test_full_forward_against_the_reference(model, cfg, params):
    ids = np.random.default_rng(0).integers(3, VOCAB, (2, 70)).astype(np.int32)
    got, _ = transformer_apply(params, None, jnp.asarray(ids), cfg)
    want = mla_moe_lm.logits(params, ids, model)
    assert got.shape == want.shape == (2, 70, VOCAB)
    _close(got, want)


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


def test_prefill_then_decode_through_the_pool_programs_against_the_reference(model, cfg, params):
    """The check the cell makes, at the rehearsal size: prompts prefilled whole
    through ``_slot_prefill_paged``, then tokens through
    ``_pool_step_paged_flash`` (the latent kernel reads rows cached turned)."""
    from perfbench.program_api import pool_forward_logits, pool_usage

    sched = _scheduler(cfg, params)
    assert [set(c) - {"moe_counts"} for c in sched.pool.caches] == [{"ckv"}] * 3
    assert sched.pool.caches[1]["moe_counts"].shape == (5,)
    prompts = np.random.default_rng(1).integers(3, VOCAB, (2, 21)).astype(np.int32)
    got = pool_forward_logits(sched, prompts, 4)
    full = np.concatenate([prompts, got[:, :4].argmax(-1).astype(np.int32)], axis=1)
    _close(got, mla_moe_lm.logits(params, full, model, first=20))
    assert pool_usage(sched)[0] <= 1


def test_a_second_prefill_chunk_at_an_offset_then_the_step(model, cfg, params):
    """A chunk prefilled at ``first`` = 16 turns its rows and queries at 16 .. 23;
    the steps after it at 24 .. 28, two slots side by side."""
    sched = _scheduler(cfg, params)
    ids = np.random.default_rng(4).integers(3, VOCAB, (2, 29)).astype(np.int32)
    want = np.asarray(mla_moe_lm.logits(params, ids, model))
    for slot in (0, 2):
        _close(_prefill(sched, slot, ids[slot // 2, :16], 0), want[slot // 2, 15])
    _close(_prefill(sched, 0, ids[0, 16:24], 16), want[0, 23])
    _close(_prefill(sched, 2, ids[1, 16:24], 16), want[1, 23])
    toks, index = np.zeros(4, np.int32), np.zeros(4, np.int32)
    for t in range(24, 29):
        for slot in (0, 2):
            sched._paged_ensure(slot, t + 1)
        toks[[0, 2]], index[[0, 2]] = ids[:, t], t
        _close(_step(sched, toks, index)[[0, 2]], want[:, t])


def test_the_paged_prefill_walks_its_keys_to_the_reference(model, cfg, params, monkeypatch):
    """Blocks of 16 against the 64-position slot buffer: a prefill of 16 from
    0, then 32 from 16 (two query blocks reaching 2 and 3 key blocks of 4),
    through the paged prefill program's own body (jitted afresh, so the
    module's program and its cached traces keep blocks of 512), to the
    reference's logits."""
    from transformer_tpu.ops import mla as mla_ops
    from transformer_tpu.serve import scheduler as S

    monkeypatch.setattr(mla_ops, "_QUERY_BLOCK", 16)
    monkeypatch.setattr(mla_ops, "_KEY_BLOCK", 16)
    def body(*args):  # a function of its own: jit caches its traces by the function traced
        return S._slot_prefill_paged.__wrapped__(*args)

    prefill = jax.jit(body, static_argnums=(6, 7, 8, 9))
    sched = _scheduler(cfg, params)
    pool = sched.pool
    ids = np.random.default_rng(7).integers(3, VOCAB, (1, 48)).astype(np.int32)
    want = np.asarray(mla_moe_lm.logits(params, ids, model))
    for first, n in ((0, 16), (16, 32)):
        assert mla_ops.latent_keys_read(first, n, pool.buf_len) < 1.0
        sched._paged_ensure(1, first + n)
        logits, pool.caches = prefill(
            sched.params, pool.caches, pool.alloc.table_device(), jnp.int32(1), jnp.asarray(ids[:, first : first + n]),
            jnp.int32(first), cfg, 0, pool.block_tokens, pool.buf_len)
        _close(np.asarray(logits[0], np.float32), want[0, first + n - 1])


@pytest.mark.parametrize("deployment", [
    {"kv_layout": "paged", "decode_kernel": "paged_flash"},
    {"kv_layout": "paged", "decode_kernel": "xla"},
    {"kv_layout": "dense", "decode_kernel": "xla"},
])
def test_every_layout_serves_the_model_to_the_reference_answers(model, cfg, params, deployment):
    """Prompts of 9, 16 and 23 tokens (a power of two, and tails walked one
    position a step after a prefill of 8 and of 16): greedy answers are the
    reference's argmax over the sequence so far."""
    reqs = [{"prompt": " ".join(map(str, np.random.default_rng(i).integers(3, VOCAB, 8 + 7 * i))), "max_new": 6}
            for i in range(3)]
    sched = _scheduler(cfg, params, **deployment)
    got = [a["continuation"] for a in sched.run([dict(r) for r in reqs])]
    ids = [[1] + [int(t) for t in r["prompt"].split()] for r in reqs]
    for prompt, answer in zip(ids, got):
        seq = prompt + [int(t) for t in answer.split()]
        want = np.asarray(mla_moe_lm.logits(params, np.asarray([seq[:-1]], np.int32), model))[0].argmax(-1)
        assert want[len(prompt) - 1 :].tolist() == seq[len(prompt) :]


# -------------------------------------------------------- what is counted


def test_the_step_spans_count_positions_and_the_tokens_with_a_pick_here(cfg, params, monkeypatch):
    from transformer_tpu.obs.trace import buffer
    from transformer_tpu.serve import scheduler as S

    monkeypatch.setattr(S, "_MOE_READ_EVERY", 2)
    sched = _scheduler(cfg, params)
    before = len(buffer().snapshot())
    rng = np.random.default_rng(6)
    sched.run([{"prompt": " ".join(map(str, rng.integers(3, VOCAB, n - 1))), "max_new": 8} for n in (20, 13, 30)])
    steps = [s for s in buffer().snapshot()[before:] if s["name"] == "scheduler.step"]
    assert steps and all("attn_pos_full" in s for s in steps)
    assert steps[0]["attn_pos_full"] == 17 + 9 + 17  # prefilled 16, 8 and 16: one more each
    read = [s for s in steps if "moe_steps" in s]
    layers = cfg.num_layers - cfg.moe_leading_dense
    assert read and all(s["moe_tokens_here"] <= min(s["moe_tokens"] * layers, s["moe_assign"]) for s in read)
    assert sum(s["moe_tokens_here"] for s in read) > 0  # two of eight experts held here: some windows see none


# --------------------------------------------------------------- the controls


@pytest.mark.parametrize("alter", [
    {"no_score_factor": True}, {"no_key_rotation": True}, {"no_group_limit": True}, {"group_max": True},
    {"expert_dtype": "float8_e4m3fn"}, {"weights_dtype": "float8_e4m3fn"},
])
def test_each_control_moves_the_comparison(model, cfg, params, alter):
    """The reference with one part of the mathematics changed no longer agrees
    with the program: the comparison sees that part."""
    ids = np.random.default_rng(2).integers(3, VOCAB, (1, 40)).astype(np.int32)
    got = np.asarray(transformer_apply(params, None, jnp.asarray(ids), cfg)[0])
    want = np.asarray(mla_moe_lm.logits(params, ids, model))
    altered = np.asarray(mla_moe_lm.logits(params, ids, model, alter=alter))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 2e-5 * scale
    assert np.abs(got - altered).max() > 1e-3 * scale


def test_the_cells_programs_lower_as_recorded():
    """This model's step and prefill at the rehearsal size as first recorded
    (tests/fixtures/lowered_serving_programs.json); the other served
    configurations' hashes are pinned by tests/test_lfm2.py and
    tests/test_kimi_linear.py."""
    from tests.test_lfm2 import _lowered_serving_programs

    with open(os.path.join(ROOT, "tests", "fixtures", "lowered_serving_programs.json")) as f:
        recorded = json.load(f)
    assert _lowered_serving_programs(CELL) == recorded[CELL]


# ------------------------------------------------- the cell's routed check


@pytest.fixture(scope="module")
def bf16_config(model):
    """The rehearsal model served in bfloat16, with the published routing
    (top 8 in the best 4 of 8 groups) over 24 experts, 3 held here."""
    m = dict(model, dtype="bfloat16", param_dtype="bfloat16", d_model=256, moe_dff=128, moe_shared_dff=128,
             moe_experts=24, moe_experts_held=3, moe_top_k=8, moe_groups=8, moe_topk_groups=4)
    return {"family": "mla_moe_lm", "model": m}


@pytest.mark.parametrize("control", [None, "no_group_limit", "group_max", "experts_float8"])
def test_the_routed_check_in_bfloat16_reads_under_its_limit_and_each_control_over(bf16_config, control):
    """The cell's second comparison (``serve_open_loop_routed``): each expert
    layer alone, as served, against the float32 reference's routed part, the
    rows near a routing tie left out. The program reads well under the limit;
    the group limit off, DeepSeek-V2's group maximum and the routed experts
    at float8 each read over it."""
    import types

    from perfbench import program_api as api
    from perfbench.kinds.serve_open_loop_routed import ROUTED_REL_TOL, routed_check
    from perfbench.tests.mla_moe_controls import ROUTED_CONTROLS

    params = api.init_lm_params(bf16_config, 7)
    got = routed_check(types.SimpleNamespace(seed=7), bf16_config, {"routed_check": {"rows": [200]}},
                       alter=ROUTED_CONTROLS.get(control), params=params)
    assert got["rows"] == 200 * 2 and got["rows_left_out_near_a_tie"] < 20
    if control is None:
        assert got["ok"] and got["rel"] < ROUTED_REL_TOL / 2
    else:
        assert not got["ok"] and got["rel"] > 2 * ROUTED_REL_TOL
