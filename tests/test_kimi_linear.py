"""A model whose layers are delta-rule linear attention (KDA: a matrix state a
head that lives a slot's life in the pool) beside latent attention (MLA: one
row a position that every head reads), with a sigmoid router, a selection bias,
a shared expert and half of the experts held here: the program against the
plain reference (``perfbench/reference/kimi_linear_lm.py``) at a small size on
the CPU, and each mechanism against its closed form."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import flops_bytes_kimi
from perfbench.reference import kimi_linear_lm
from transformer_tpu.config import AttentionKind, ModelConfig, config_from_json, config_to_json
from transformer_tpu.kernels.kda_step import kda_step
from transformer_tpu.kernels.paged_latent import paged_latent_attention
from transformer_tpu.models.transformer import transformer_apply, transformer_init
from transformer_tpu.ops.kda import CHUNK, init_kda_state, kda_apply, kda_init, kda_inputs, kda_output, kda_recurrent_step
from transformer_tpu.ops.mla import init_latent_cache, latent_width, mla_apply, mla_init

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "kimi-linear-48b-a3b.reasoning-saturated"
VOCAB = 256
EPS = 1e-5


def _json(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


def tiny_model() -> dict:
    """The cell's rehearsal size: the published structure at toy widths."""
    model = _json("configs", "kimi-linear-48b-a3b.json")["model"]
    model.update(_json("workloads", CELL + ".json")["rehearse"]["model"])
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


def _close(got, want, rel=2e-5):
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < rel * np.abs(np.asarray(want)).max()


# ---------------------------------------------------------------- the config


def test_config_is_hashable_round_trips_and_names_its_mixers_and_state_layers(model, cfg):
    assert hash(cfg) == hash(ModelConfig(**model))
    assert config_from_json(ModelConfig, config_to_json(cfg)) == cfg
    assert [cfg.layer_kind(i).mixer for i in range(5)] == ["kda", "kda", "kda", "mla", "kda"]
    assert cfg.state_layers == (0, 1, 2, 4)  # the latent layer keeps rows a position: it is not one
    jax.jit(lambda x, c: x * c.num_layers, static_argnames="c")(1.0, cfg)  # a static argument
    assert AttentionKind("full").mixer == "attention" and AttentionKind("c", conv_kernel=3).mixer == "conv"
    assert (cfg.moe_score, cfg.moe_select_bias, cfg.experts_held, cfg.moe_experts) == ("sigmoid", True, 4, 8)


def test_the_configuration_file_states_the_published_model_and_its_three_cuts():
    file = _json("configs", "kimi-linear-48b-a3b.json")
    pub, m = file["published"], file["model"]
    assert file["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (file["num_hidden_layers"], file["num_experts"], file["vocab_size"]) == (5, 128, 81920)
    assert (pub["num_hidden_layers"], pub["num_experts"], pub["vocab_size"]) == (27, 256, 163840)
    assert all(file[k] == v for k, v in pub.items() if k not in file["reduced"])
    lin = pub["linear_attn_config"]
    kda, mla = m["attention_kinds"]
    assert [m["layer_pattern"][i % 4] for i in range(5)] == [
        "kda" if i + 1 in lin["kda_layers"] else "mla" for i in range(5)]  # the published lists are 1-based
    assert (kda["kda_heads"], kda["kda_head_dim"], kda["kda_conv_kernel"]) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    assert (mla["latent_rank"], mla["latent_nope_dim"], mla["latent_shared_dim"], mla["latent_value_dim"], mla["num_heads"]) == (
        pub["kv_lora_rank"], pub["qk_nope_head_dim"], pub["qk_rope_head_dim"], pub["v_head_dim"], pub["num_attention_heads"])
    assert (m["d_model"], m["dff"], m["moe_dff"], m["moe_shared_dff"], m["moe_experts"], m["moe_experts_held"], m["moe_top_k"]) == (
        pub["hidden_size"], pub["intermediate_size"], pub["moe_intermediate_size"], pub["moe_intermediate_size"], 256, 128,
        pub["num_experts_per_token"])
    assert (m["moe_routed_scale"], m["moe_leading_dense"], m["layernorm_epsilon"], m["tie_output"]) == (
        pub["routed_scaling_factor"], pub["first_k_dense_replace"], pub["rms_norm_eps"], pub["tie_word_embeddings"])
    assert m["target_vocab_size"] == m["input_vocab_size"] == file["vocab_size"]
    for item in ("gate rank", "decay", "state", "MLA", "router", "weights"):
        assert len(file["assumed"][item]) > 40  # each with its reason
    assert "two chips share each layer" in file["deployment"] and "first pipeline stage" in file["deployment"]


@pytest.mark.parametrize("bad", [
    {"name": "x", "kda_heads": 4, "kda_head_dim": 16, "conv_kernel": 3},  # two mixers in one kind
    {"name": "x", "kda_heads": 4},  # no head width
    {"name": "x", "kda_heads": 4, "kda_head_dim": 16, "kda_conv_kernel": 1},
    {"name": "x", "latent_rank": 128, "latent_nope_dim": 16},  # no shared key part, no value width
])
def test_config_refuses(model, bad):
    with pytest.raises(ValueError):
        ModelConfig(**{**model, "layer_pattern": ["x"], "attention_kinds": [bad]})


def test_parameters_are_a_kda_or_a_latent_mixer_and_no_bias_but_dt_and_the_routers(cfg, params):
    layers = params["decoder"]["layers"]
    assert [next(k for k in ("kda", "mla", "self_mha", "conv") if k in l) for l in layers] == ["kda", "kda", "kda", "mla", "kda"]
    kda, mla = layers[0]["kda"], layers[3]["mla"]
    assert {n: kda[n]["kernel"].shape for n in ("q", "q_conv", "f_a", "f_b", "beta", "g_b", "out")} == {
        "q": (64, 64), "q_conv": (4, 64), "f_a": (64, 16), "f_b": (16, 64), "beta": (64, 4), "g_b": (16, 64), "out": (64, 64)}
    assert kda["A_log"].shape == (4,) and kda["dt"]["bias"].shape == (64,) and kda["o_norm"]["scale"].shape == (16,)
    assert kda["A_log"].dtype == kda["dt"]["bias"].dtype == jnp.float32
    assert {n: mla[n]["kernel"].shape for n in ("query", "kv_a", "kv_b", "out")} == {
        "query": (64, 4, 24), "kv_a": (64, 136), "kv_b": (128, 4, 32), "out": (4, 16, 64)}
    assert "ffn" in layers[0] and all("moe" in l and "shared" in l["moe"] for l in layers[1:])
    assert layers[1]["moe"]["in"]["kernel"].shape[0] == 4 and layers[1]["moe"]["router"]["kernel"].shape == (64, 8)
    biases = [p for p, _ in jax.tree_util.tree_flatten_with_path(params)[0] if getattr(p[-1], "key", "") == "bias"]
    assert sorted(getattr(p[-2], "key", "") for p in biases) == ["dt"] * 4 + ["router"] * 4
    assert "final" in params  # an untied head


def test_kimi_params_counts_the_programs_tree_at_the_chips_size():
    m = _json("configs", "kimi-linear-48b-a3b.json")["model"]
    tree = jax.eval_shape(lambda k: transformer_init(k, ModelConfig(**m)), jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(tree)
    assert sum(int(np.prod(x.shape)) for x in leaves) == flops_bytes_kimi.kimi_params(m) == 4_282_936_192
    assert 8.5e9 < sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves) < 8.6e9  # bfloat16 but A_log, dt and the routers' biases
    assert flops_bytes_kimi.state_bytes_per_slot(m) == 4 * (2_097_152 + 73_728) == 8_683_520
    assert flops_bytes_kimi.latent_bytes_per_position(m) == 1152
    assert flops_bytes_kimi.kda_step_bytes(m, 1) == 4 * 2 * 2_097_152 and flops_bytes_kimi.latent_attention_bytes(m, 10) == 11520


# ------------------------------------------------------- the delta-rule layer


def _kda_layer(strongest=True):
    """A layer of 4 heads of 16 at the strongest decay the initialisation
    gives: ``A_log = log 16`` and ``dt = 0.1`` for every head and channel."""
    p = kda_init(jax.random.PRNGKey(0), 32, 4, 16, 4, 0, jnp.float32)
    if strongest:
        p["A_log"] = jnp.full((4,), jnp.log(16.0))
        p["dt"] = {"bias": jnp.full((64,), jnp.log(jnp.expm1(0.1)))}
    return p


@pytest.mark.parametrize("tokens", [1, 63, 64, 65, 200])
def test_chunked_kda_against_the_references_recurrence_at_every_position(tokens):
    p = _kda_layer()
    h = jax.random.normal(jax.random.PRNGKey(tokens), (2, tokens, 32))
    got, state = kda_apply(p, h, epsilon=EPS)
    want = kimi_linear_lm.kda(p, h, EPS)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    assert state["kda_state"].shape == (2, 4, 16, 16) and state["kda_conv"].shape == (2, 3, 192)
    assert bool(jnp.isfinite(state["kda_state"]).all())
    # -1.6 a position: over a chunk e**-100, which exp(+G) could not hold.
    _, _, _, g, _, _ = kda_inputs(p, h, init_kda_state(2, 4, 16, 4, jnp.float32)["kda_conv"])
    assert float(g.min()) < -1.5 and float(g.max()) <= 0.0


def test_kda_a_prompt_split_in_two_calls_equals_one_call():
    p = _kda_layer(strongest=False)
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 150, 32))
    whole, end = kda_apply(p, h)
    a, mid = kda_apply(p, h[:, :70])
    b, last = kda_apply(p, h[:, 70:], mid)
    np.testing.assert_allclose(jnp.concatenate([a, b], axis=1), whole, atol=5e-6)
    np.testing.assert_allclose(last["kda_state"], end["kda_state"], atol=2e-6)
    np.testing.assert_array_equal(last["kda_conv"], end["kda_conv"])


def test_kda_prefill_then_the_step_kernel_equals_one_call():
    """The hand-over the serving path makes: the chunked form over the prompt,
    then one position at a time through ``kda_step`` (interpret mode), the
    convolutions' inputs rolled by ``kda_inputs``."""
    p = _kda_layer(strongest=False)
    h = jax.random.normal(jax.random.PRNGKey(8), (3, 77, 32))
    whole, end = kda_apply(p, h)
    out, state = kda_apply(p, h[:, :70])
    outs = [out]
    live = jnp.array([1, 1, 1])
    for t in range(70, 77):
        x = h[:, t : t + 1]
        q, k, v, g, beta, conv = kda_inputs(p, x, state["kda_conv"])
        o, s = kda_step(state["kda_state"], q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], live)
        state = {"kda_state": s, "kda_conv": conv}
        outs.append(kda_output(p, x, o[:, None], 1e-5))
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), whole, atol=5e-6)
    np.testing.assert_allclose(state["kda_state"], end["kda_state"], atol=2e-6)


def test_kda_step_kernel_against_the_recurrence_and_a_free_slot_keeps_its_state():
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    state = jax.random.normal(ks[0], (3, 4, 16, 16))
    q, k, v = (jax.random.normal(ks[i], (3, 4, 16)) for i in (1, 2, 3))
    g = -jnp.abs(jax.random.normal(ks[4], (3, 4, 16)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (3, 4)))
    o, new = kda_step(state, q, k, v, g, beta, jnp.array([True, False, True]))
    want_o, want = kda_recurrent_step(state, q, k, v, g, beta)
    for slot in (0, 2):
        np.testing.assert_allclose(o[slot], want_o[slot], atol=1e-5)
        np.testing.assert_allclose(new[slot], want[slot], atol=1e-5)
    np.testing.assert_array_equal(new[1], state[1])
    np.testing.assert_array_equal(o[1], 0.0)


def test_kda_is_differentiable_in_its_chunked_form():
    p = _kda_layer()
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 100, 32))
    grads = jax.grad(lambda p: kda_apply(p, h)[0].sum())(p)
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(grads))
    assert float(jnp.abs(grads["q"]["kernel"]).max()) > 0 and CHUNK == 64


# ------------------------------------------------------------ the latent layer


def _mla_layer():
    return mla_init(jax.random.PRNGKey(1), 32, 4, 128, 16, 8, 16, jnp.float32)


def test_mla_full_form_against_the_reference_and_the_cached_row():
    p = _mla_layer()
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 37, 32))
    got, none = mla_apply(p, h, epsilon=EPS)
    assert none is None
    _close(got, kimi_linear_lm.mla(p, h, EPS), 1e-5)
    assert latent_width(128, 8) == 256 and latent_width(512, 64) == 640


def test_mla_absorbed_one_token_form_through_a_latent_cache_against_the_full_form():
    """A prefill chunk of 20 positions (unabsorbed, against the rows it has just
    written), then 9 positions one at a time in the absorbed form: each reads
    ONE 136-channel row a position, the value its first 128 channels."""
    p = _mla_layer()
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 29, 32))
    whole, _ = mla_apply(p, h, epsilon=EPS)
    cache = init_latent_cache(2, 40, 128, 8, jnp.float32)
    assert cache["ckv"].shape == (2, 40, 256)
    out, cache = mla_apply(p, h[:, :20], cache, EPS)
    outs = [out]
    for t in range(20, 29):
        out, cache = mla_apply(p, h[:, t : t + 1], cache, EPS)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), whole, atol=5e-6)
    assert int(cache["index"]) == 29 and float(jnp.abs(cache["ckv"][:, 29:]).max()) == 0.0
    assert float(jnp.abs(cache["ckv"][:, :29, 136:]).max()) == 0.0  # the padding lanes stay zero


def test_mla_blocks_its_query_rows_where_the_chunk_is_long(monkeypatch):
    from transformer_tpu.ops import mla as mla_ops

    p = _mla_layer()
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 32))
    whole, _ = mla_apply(p, h, epsilon=EPS)
    monkeypatch.setattr(mla_ops, "_QUERY_BLOCK", 16)
    blocked, _ = mla_ops.mla_apply(p, h, epsilon=EPS)
    np.testing.assert_allclose(blocked, whole, atol=2e-6)


@pytest.mark.parametrize("lengths", [[1, 37, 96], [16, 17, 33]])
def test_paged_latent_attention_reads_each_row_once_through_the_table(lengths):
    n, heads, width, rank, block, nmax = 3, 4, 256, 128, 16, 6
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = jax.random.normal(ks[0], (40, block, width))
    q = jax.random.normal(ks[1], (n, heads, width)) * 0.2
    table = jax.random.permutation(ks[2], jnp.arange(1, 40))[: n * nmax].reshape(n, nmax)
    lengths = jnp.asarray(lengths)
    got = paged_latent_attention(q, pool, table, lengths, rank=rank)
    rows = pool[table].reshape(n, nmax * block, width)
    scores = jnp.einsum("nhw,nlw->nhl", q, rows, precision="highest")
    scores = jnp.where(jnp.arange(nmax * block)[None, None] < lengths[:, None, None], scores, -1e9)
    want = jnp.einsum("nhl,nlr->nhr", jax.nn.softmax(scores, -1), rows[..., :rank], precision="highest")
    np.testing.assert_allclose(got, want, atol=2e-6)
    # Entries past a slot's length are never dereferenced: hostile ids there change nothing.
    hostile = jnp.where(jnp.arange(nmax)[None, :] * block >= lengths[:, None], 10**6, table)
    np.testing.assert_array_equal(paged_latent_attention(q, pool, hostile, lengths, rank=rank), got)


# ------------------------------------------------ the model, the reference


def test_full_forward_against_the_reference(model, cfg, params):
    ids = np.random.default_rng(0).integers(3, VOCAB, (2, 70)).astype(np.int32)
    got, _ = transformer_apply(params, None, jnp.asarray(ids), cfg)
    want = kimi_linear_lm.logits(params, ids, model)
    assert got.shape == want.shape == (2, 70, VOCAB)
    _close(got, want)


def test_prefill_then_decode_through_the_pool_programs_against_the_reference(model, cfg, params):
    """The check the cell makes, at the rehearsal size: prompts prefilled whole
    through ``_slot_prefill_paged`` (the chunked form, the state and the
    convolution inputs handed to the slot), then tokens through
    ``_pool_step_paged_flash`` (``kda_step`` and the latent kernel) with the
    other slots fed PAD at index 0."""
    from perfbench.program_api import pool_forward_logits, pool_usage

    sched = _scheduler(cfg, params)
    assert [set(c) - {"moe_counts"} for c in sched.pool.caches] == [
        {"ckv"} if i == 3 else {"kda_state", "kda_conv"} for i in range(5)]
    assert sched.pool.caches[0]["kda_state"].shape == (4, 4, 16, 16) and sched.pool.caches[0]["kda_state"].dtype == jnp.float32
    assert sched.pool.caches[0]["kda_conv"].shape == (4, 3, 192) and sched.pool.caches[3]["ckv"].shape[1:] == (16, 256)
    prompts = np.random.default_rng(1).integers(3, VOCAB, (2, 21)).astype(np.int32)
    got = pool_forward_logits(sched, prompts, 4)
    full = np.concatenate([prompts, got[:, :4].argmax(-1).astype(np.int32)], axis=1)
    _close(got, kimi_linear_lm.logits(params, full, model, first=20))
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
    sched = _scheduler(cfg, params)
    ids = np.random.default_rng(4).integers(3, VOCAB, (2, 29)).astype(np.int32)
    want = np.asarray(kimi_linear_lm.logits(params, ids, model))
    for slot in (0, 2):
        _close(_prefill(sched, slot, ids[slot // 2, :16], 0), want[slot // 2, 15])
    _close(_prefill(sched, 0, ids[0, 16:24], 16), want[0, 23])  # S and the convolutions' inputs are the slot's own
    _close(_prefill(sched, 2, ids[1, 16:24], 16), want[1, 23])
    toks, index = np.zeros(4, np.int32), np.zeros(4, np.int32)
    for t in range(24, 29):
        for slot in (0, 2):
            sched._paged_ensure(slot, t + 1)
        toks[[0, 2]], index[[0, 2]] = ids[:, t], t
        _close(_step(sched, toks, index)[[0, 2]], want[:, t])


def test_a_recycled_slot_answers_as_a_fresh_one_and_free_slots_keep_their_state(model, cfg, params):
    rng = np.random.default_rng(5)
    first, second, other = (rng.integers(3, VOCAB, n).astype(np.int32) for n in (19, 16, 24))
    sched = _scheduler(cfg, params)
    # Slot 2 is idle throughout (fed PAD at index 0): whatever its state holds stays there.
    sched.pool.caches = [
        dict(c, kda_state=c["kda_state"].at[2].set(7.0), kda_conv=c["kda_conv"].at[2].set(5.0)) if "kda_state" in c else c
        for c in sched.pool.caches]
    _prefill(sched, 1, first, 0)
    _prefill(sched, 3, other[:23], 0)
    toks, index = np.zeros(4, np.int32), np.zeros(4, np.int32)
    toks[[1, 3]], index[[1, 3]] = (7, other[23]), (19, 23)
    sched._paged_ensure(1, 20)
    sched._paged_ensure(3, 24)
    got = _step(sched, toks, index)  # slots 1 and 3 update their states side by side
    _close(got[3], np.asarray(kimi_linear_lm.logits(params, other[None], model))[0, -1])
    assert np.abs(np.asarray(sched.pool.caches[0]["kda_state"][1])).max() > 0
    sched.pool.alloc.free_slot(1)
    got = _prefill(sched, 1, second, 0)  # the same slot again: it holds the first request's S
    fresh = _prefill(_scheduler(cfg, params), 1, second, 0)
    np.testing.assert_array_equal(got, fresh)
    _close(got, np.asarray(kimi_linear_lm.logits(params, second[None], model))[0, -1])
    for c in sched.pool.caches:
        if "kda_state" in c:
            np.testing.assert_array_equal(np.asarray(c["kda_state"][2]), 7.0)
            np.testing.assert_array_equal(np.asarray(c["kda_conv"][2]), 5.0)


@pytest.mark.parametrize("deployment", [
    {"kv_layout": "paged", "decode_kernel": "paged_flash"},
    {"kv_layout": "paged", "decode_kernel": "xla"},
    {"kv_layout": "dense", "decode_kernel": "xla"},
])
def test_every_layout_serves_the_model_to_the_same_answers(model, cfg, params, deployment):
    reqs = [{"prompt": " ".join(map(str, np.random.default_rng(i).integers(3, VOCAB, 9 + 7 * i))), "max_new": 6} for i in range(3)]
    sched = _scheduler(cfg, params, **deployment)
    got = [a["continuation"] for a in sched.run([dict(r) for r in reqs])]
    ids = [[1] + [int(t) for t in r["prompt"].split()] for r in reqs]
    for prompt, answer in zip(ids, got):  # greedy: each token is the reference's argmax over the sequence so far
        seq = prompt + [int(t) for t in answer.split()]
        want = np.asarray(kimi_linear_lm.logits(params, np.asarray([seq[:-1]], np.int32), model))[0].argmax(-1)
        assert want[len(prompt) - 1 :].tolist() == seq[len(prompt) :]


# --------------------------------------------------------------- the experts


def test_the_shares_add_up_to_the_uncut_layer(model, params):
    """Two chips share the expert layer: experts 0-3 here, 4-7 on the other,
    the shared expert computed by both. The two routed parts plus the shared
    expert ONCE are the uncut reference's layer."""
    from transformer_tpu.ops.moe import moe_apply_dropless, moe_init

    whole = moe_init(jax.random.PRNGKey(0), 64, 32, 8, activation="swiglu", shared_dff=32, select_bias=True)
    whole["router"]["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (8,))
    x = jax.random.normal(jax.random.PRNGKey(2), (23, 64))
    uncut = kimi_linear_lm.moe(whole, x, 2, 0, 2.446, 1e-20)

    def share(offset):
        p = {**whole, **{n: {"kernel": whole[n]["kernel"][offset : offset + 4]} for n in ("gate", "in", "out")}}
        y, counts = moe_apply_dropless(
            p, x, num_experts=8, top_k=2, expert_offset=offset, routed_scale=2.446, score="sigmoid",
            renorm_epsilon=1e-20, activation="swiglu")
        _close(y, kimi_linear_lm.moe(p, x, 2, offset, 2.446, 1e-20), 1e-5)  # the reference is given the same share
        routed = kimi_linear_lm.moe(p, x, 2, offset, 2.446, 1e-20, shared=False)
        return y, routed, int(counts[0])

    (y0, r0, picks0), (y1, r1, picks1) = share(0), share(4)
    assert picks0 + picks1 == 2 * 23 and 0 < picks0 < 2 * 23  # every pick lands on one chip or the other
    shared_once = np.asarray(y0) - np.asarray(r0)
    _close(np.asarray(r0) + np.asarray(r1) + shared_once, uncut, 1e-5)
    _close(np.asarray(y1) - np.asarray(r1), shared_once, 1e-4)  # both chips compute the same shared expert


# -------------------------------------------- what is refused, what is counted


def test_prefix_cache_speculation_and_a_fork_refuse_the_model(cfg, params):
    from transformer_tpu.serve.prefix_cache import PrefixCache

    with pytest.raises(ValueError, match="stateful layer"):
        PrefixCache(cfg, block_tokens=16, budget_mb=8)
    with pytest.raises(ValueError, match="rolled back"):
        _scheduler(cfg, params, speculate_k=2)
    sched = _scheduler(cfg, params)
    sched._paged_ensure(0, 40)
    sched.pool.alloc.retain(sched.pool.alloc.table[0][0])  # a second reference: the block is shared
    with pytest.raises(ValueError, match="fork"):
        sched._paged_cow(0, 0, 20)


def test_the_scheduler_counts_the_state_the_latent_rows_and_the_prefills_chunks(cfg, params):
    from transformer_tpu.obs.telemetry import Telemetry
    from transformer_tpu.obs.trace import buffer

    tel = Telemetry(interval=1e12)
    sched = _scheduler(cfg, params, telemetry=tel, max_total=128)
    gauge = lambda name: tel.registry.gauge(name, "").value  # noqa: E731
    per_slot = 4 * (4 * 16 * 16 * 4 + 3 * 192 * 4)  # float32 at this size: the matrix and the convolutions' inputs
    assert (gauge("serve_state_layers"), gauge("serve_state_bytes_per_slot")) == (4, per_slot)
    assert (gauge("serve_latent_layers"), gauge("serve_latent_bytes_per_position")) == (1, 256 * 4)
    buffer().clear()
    prompt = " ".join(map(str, np.random.default_rng(0).integers(3, VOCAB, 70)))
    sched.run([{"prompt": prompt, "max_new": 40}])
    spans = buffer().snapshot()
    admits = [s for s in spans if s["name"] == "serve.admit"]
    assert admits and admits[-1]["prefill_tokens"] == 64  # of the 71 positions: one chunk a KDA layer
    steps = [s for s in spans if s["name"] == "scheduler.step"]
    assert all(s["active"] == 1 and s["attn_pos_full"] >= 65 for s in steps)
    # The state's two constants are gauges (above), not copies on every counted span.
    assert steps[0]["prefills"] == 1 and steps[0]["prefill_tokens"] == 64
    assert not any("state_bytes" in s or "state_layers" in s for s in steps)


# --------------------------------------------------------------- the controls


@pytest.mark.parametrize("alter", [
    {"no_decay": True}, {"beta_one": True}, {"no_delta": True}, {"zero_state_at": 16}, {"no_conv_silu": True},
    {"no_shared_key": True}, {"no_latent_norm": True}, {"no_select_bias": True},
])
def test_each_control_moves_the_comparison(model, cfg, params, alter):
    """The reference with one part of the mathematics left out no longer agrees
    with the program: the comparison sees that part."""
    ids = np.random.default_rng(2).integers(3, VOCAB, (1, 40)).astype(np.int32)
    got = np.asarray(transformer_apply(params, None, jnp.asarray(ids), cfg)[0])
    want = np.asarray(kimi_linear_lm.logits(params, ids, model))
    altered = np.asarray(kimi_linear_lm.logits(params, ids, model, alter=alter))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 2e-5 * scale
    assert np.abs(got - altered).max() > 1e-3 * scale


# ------------------------- the other served configurations' programs stand


def test_the_stateful_configurations_lower_as_recorded():
    """``lfm2-8b-a1b``'s step and prefill as the commit before this model
    lowered them (nothing of a delta-rule or a latent layer is in them), and
    this model's own as first recorded. A change of shared code moves these on
    purpose: record them again (tests/fixtures/lowered_serving_programs.json)."""
    from tests.test_lfm2 import _lowered_serving_programs

    with open(os.path.join(ROOT, "tests", "fixtures", "lowered_serving_programs.json")) as f:
        recorded = json.load(f)
    for cell in ("lfm2-8b-a1b.longform-saturated", CELL):
        assert _lowered_serving_programs(cell) == recorded[cell], cell
    assert hashlib.sha256(b"").hexdigest() != recorded[CELL]
