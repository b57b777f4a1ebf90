"""A model whose layers are a Mamba-2 state-space mixer (SSD: a float32 matrix
state a head that lives a slot's life in the pool), GQA attention that turns
nothing, or a latent expert layer (ungated relu2 experts in a latent narrower
than the model, a sigmoid router with a selection bias, a shared expert, half
of the experts held here), each layer a mixer alone or an expert layer alone:
the program against the plain reference (``perfbench/reference/
nemotron_h_lm.py``) at a small size on the CPU, and each mechanism against its
closed form."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import nemotron_h_lm
from transformer_tpu.config import AttentionKind, ModelConfig, config_from_json, config_to_json
from transformer_tpu.kernels.ssm_step import ssm_step
from transformer_tpu.models.transformer import transformer_apply, transformer_init
from transformer_tpu.ops.ssd import init_ssm_state, ssd_chunked, ssm_apply, ssm_recurrent_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "nemotron-3-super.chat-saturated"
VOCAB = 256


def _json(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


def tiny_model() -> dict:
    """The cell's rehearsal size: the published structure at toy widths."""
    model = _json("configs", "nemotron-3-super.json")["model"]
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

    return jax.jit(lambda key: _roughen(transformer_init(key, cfg), key))(jax.random.PRNGKey(3))


def _scheduler(cfg, params, **kw):
    from perfbench.program_api import IdTokenizer
    from transformer_tpu.serve.scheduler import ContinuousScheduler

    kw = {"num_slots": 4, "max_total": 64, "kv_layout": "paged", "kv_block": 16, "decode_kernel": "paged_flash", **kw}
    return ContinuousScheduler(params, cfg, IdTokenizer(), **kw)


_forward = jax.jit(lambda params, ids, cfg: transformer_apply(params, None, ids, cfg)[0], static_argnames="cfg")


def _close(got, want, rel=2e-5):
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < rel * np.abs(np.asarray(want)).max()


# ---------------------------------------------------------------- the config


def test_config_names_its_mixers_sublayers_and_state_layers(model, cfg):
    assert hash(cfg) == hash(ModelConfig(**model))
    assert config_from_json(ModelConfig, config_to_json(cfg)) == cfg
    assert "".join({"ssm": "M", "none": "E", "attention": "*"}[cfg.layer_kind(i).mixer] for i in range(11)) == "MEMEMEM*EME"
    assert cfg.state_layers == (0, 2, 4, 6, 9)
    assert AttentionKind("e", sublayers="ffn", ssm_heads=4).mixer == "none" and not AttentionKind("m", sublayers="mixer").has_ffn
    with pytest.raises(ValueError, match="state-space layer"):
        ModelConfig(**{**model, "attention_kinds": [{"name": "mamba", "ssm_heads": 3, "ssm_head_dim": 16, "ssm_groups": 2,
                                                     "ssm_state": 16}, *model["attention_kinds"][1:]]})
    with pytest.raises(ValueError, match="sublayers"):
        ModelConfig(**{**model, "attention_kinds": [*model["attention_kinds"][:2], {"name": "attention", "sublayers": "x"}]})


def test_the_configuration_file_states_the_published_model_and_its_three_cuts():
    file = _json("configs", "nemotron-3-super.json")
    pub, m = file["published"], file["model"]
    assert file["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (file["num_hidden_layers"], file["n_routed_experts"], file["vocab_size"]) == (11, 128, 32768)
    assert (pub["num_hidden_layers"], pub["n_routed_experts"], pub["vocab_size"]) == (88, 512, 131072)
    assert all(file[k] == v for k, v in pub.items() if k not in file["reduced"])
    code = {"mamba": "M", "experts": "E", "attention": "*"}
    assert "".join(code[p] for p in m["layer_pattern"]) == pub["hybrid_override_pattern"][: m["num_layers"]]
    mamba, experts, attn = m["attention_kinds"]
    assert (mamba["ssm_heads"], mamba["ssm_head_dim"], mamba["ssm_groups"], mamba["ssm_state"], mamba["ssm_conv_kernel"],
            mamba["ssm_chunk"]) == (pub["mamba_num_heads"], pub["mamba_head_dim"], pub["n_groups"], pub["ssm_state_size"],
                                    pub["conv_kernel"], pub["chunk_size"])
    assert (attn["num_heads"], m["num_kv_heads"], m["head_size"], attn["rotary_share"]) == (
        pub["num_attention_heads"], pub["num_key_value_heads"], pub["head_dim"], 0.0)
    assert (m["d_model"], m["moe_dff"], m["moe_shared_dff"], m["moe_latent_dim"], m["moe_experts"], m["moe_top_k"]) == (
        pub["hidden_size"], pub["moe_intermediate_size"], pub["moe_shared_expert_intermediate_size"], pub["moe_latent_size"],
        512, pub["num_experts_per_tok"])
    assert (m["moe_routed_scale"], m["layernorm_epsilon"], m["tie_output"], m["ffn_activation"]) == (
        pub["routed_scaling_factor"], pub["layer_norm_epsilon"], pub["tie_word_embeddings"], pub["mlp_hidden_act"])
    assert m["moe_experts_held"] == file["n_routed_experts"] and m["target_vocab_size"] == file["vocab_size"]
    for item in ("state", "router input", "attention", "weights"):
        assert len(file["assumed"][item]) > 40  # each with its reason
    assert "4 chips share each layer" in file["deployment"] and "first pipeline stage" in file["deployment"]
    assert any("multi-token" in d for d in file["departures"])


def test_parameters_are_a_mixer_or_an_expert_layer_and_no_bias_but_the_convolutions_dt_and_the_routers(cfg, params):
    layers = params["decoder"]["layers"]
    assert [sorted(l) for l in layers[:2]] == [["ln1", "ssm"], ["ln_ffn", "moe"]] and sorted(layers[7]) == ["ln1", "self_mha"]
    ssm, moe = layers[0]["ssm"], layers[1]["moe"]
    assert {n: ssm[n]["kernel"].shape for n in ("in", "conv", "out")} == {"in": (64, 64 + 128 + 4), "conv": (4, 128), "out": (64, 64)}
    assert ssm["A_log"].shape == ssm["D"].shape == ssm["dt"]["bias"].shape == (4,) and ssm["A_log"].dtype == jnp.float32
    assert {n: moe[n]["kernel"].shape for n in ("in", "out", "latent_down", "latent_up")} == {
        "in": (4, 32, 32), "out": (4, 32, 32), "latent_down": (64, 32), "latent_up": (32, 64)}
    assert "gate" not in moe and sorted(moe["shared"]) == ["in", "out"] and moe["router"]["kernel"].shape == (64, 8)
    biases = [p for p, _ in jax.tree_util.tree_flatten_with_path(params)[0] if getattr(p[-1], "key", "") == "bias"]
    assert sorted(str(getattr(p[-2], "key", "")) for p in biases) == sorted(["conv", "dt"] * 5 + ["router"] * 5)


# ------------------------------------------------------------- the mixer


def _ssm_inputs(seed, b, s, heads=4, p=8, groups=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (b, s, heads, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, heads)) - 1.0)
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=0.0, maxval=2.0))
    bmat, cmat = jax.random.normal(k[3], (b, s, groups, n)), jax.random.normal(k[4], (b, s, groups, n))
    return x, dt, a, bmat, cmat, jax.random.normal(k[5], (b, heads, p, n))


@pytest.mark.parametrize("length", [5, 16, 21])
def test_the_chunked_scan_is_the_recurrence_across_chunks_and_padding(length):
    """Chunks of 8: inside one chunk, exactly two, and two and a padded third
    (positions whose dt is 0 neither decay nor write the state)."""
    x, dt, a, bmat, cmat, s0 = _ssm_inputs(0, 2, length)

    @jax.jit
    def both(s0, x, dt, a, bmat, cmat):
        def step(s, xs):
            s, y = ssm_recurrent_step(s, *xs[:2], a, *xs[2:])[::-1]
            return s, y

        with jax.default_matmul_precision("highest"):
            steps = jax.lax.scan(step, s0, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, bmat, cmat)))
            return ssd_chunked(s0, x, dt, a, bmat, cmat, 8), steps

    (y, state), (s, want) = both(s0, x, dt, a, bmat, cmat)
    _close(y, jnp.moveaxis(want, 0, 1), 1e-5)
    _close(state, s, 1e-5)


def test_a_sequence_in_two_calls_is_the_sequence_in_one(params):
    """The state and the convolution's inputs handed on carry everything: a
    chunk of 13 then one of 6 (then one position) is the 20 positions at once."""
    p = params["decoder"]["layers"][0]["ssm"]
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 64))
    apply = jax.jit(lambda h, state: ssm_apply(p, h, state, groups=2, state_size=16, chunk=8))
    whole, end = apply(h, None)
    a, mid = apply(h[:, :13], None)
    b, mid = apply(h[:, 13:19], mid)
    c, last = apply(h[:, 19:], mid)
    _close(jnp.concatenate([a, b, c], 1), whole)
    _close(last["ssm_state"], end["ssm_state"])
    assert set(init_ssm_state(3, 4, 16, 2, 16, 4)) == set(end) and end["ssm_conv"].shape == (2, 3, 128)


@pytest.mark.parametrize("heads,p", [(8, 16), (32, 64)], ids=["toy", "nemotron_group"])
def test_the_step_kernel_is_the_recurrence_and_keeps_a_free_slots_state(heads, p):
    """Two groups of state 128 wide: 4 heads of 16 a group (a read-out row of
    64 lanes), and Nemotron's group, 16 heads of 64 (a row of 1,024 lanes)."""
    x, dt, a, bmat, cmat, s0 = _ssm_inputs(2, 3, 1, heads=heads, p=p, groups=2, n=128)
    live = jnp.array([True, False, True])
    y, state = ssm_step(s0, x[:, 0], dt[:, 0], a, bmat[:, 0], cmat[:, 0], live)
    with jax.default_matmul_precision("highest"):
        want_y, want_s = ssm_recurrent_step(s0, x[:, 0], dt[:, 0], a, bmat[:, 0], cmat[:, 0])
    _close(y[::2], want_y[::2], 1e-5)
    _close(state[::2], want_s[::2], 1e-5)
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(s0[1]))
    np.testing.assert_array_equal(np.asarray(y[1]), 0.0)


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (a jitted
    call's, the kernel's body, its branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _equations(sub)


def _loose_dots(jaxpr) -> list[str]:
    """The products in ``jaxpr`` that are not float32 at HIGHEST precision on
    both operands."""
    loose = []
    for eqn in _equations(jaxpr):
        if eqn.primitive.name == "dot_general":
            precision = eqn.params["precision"]
            if not isinstance(precision, tuple):
                precision = (precision, precision)
            exact = all(p == jax.lax.Precision.HIGHEST for p in precision)
            if not exact or eqn.params["preferred_element_type"] != jnp.float32 or eqn.outvars[0].aval.dtype != jnp.float32:
                loose.append(str(eqn))
    return loose


def test_every_product_of_the_step_kernel_is_float32_at_highest_precision():
    """The state and its recurrence are float32 as configured: a read-out on
    the MXU in one bfloat16 pass (DEFAULT) or three (HIGH) is another result."""
    x, dt, a, bmat, cmat, s0 = _ssm_inputs(2, 3, 1, heads=32, p=64, groups=2, n=128)
    jaxpr = jax.make_jaxpr(ssm_step)(s0, x[:, 0], dt[:, 0], a, bmat[:, 0], cmat[:, 0], jnp.ones((3,), bool))
    assert any(e.primitive.name == "pallas_call" for e in _equations(jaxpr.jaxpr))
    assert _loose_dots(jaxpr.jaxpr) == []


def _kernel_reading_out(precision, out):
    """The jaxpr of a kernel whose read-out, inside a branch, is one product
    of ``precision`` and result type ``out``."""
    from jax.experimental import pallas as pl

    def kernel(c_ref, s_ref, y_ref):
        @pl.when(pl.program_id(0) >= 0)
        def _read():
            y = jax.lax.dot_general(c_ref[...], s_ref[...], (((1,), (1,)), ((), ())), precision=precision,
                                    preferred_element_type=out)
            y_ref[...] = y.astype(jnp.float32)

    call = pl.pallas_call(kernel, grid=(1,), out_shape=jax.ShapeDtypeStruct((8, 1024), jnp.float32), interpret=True)
    return jax.make_jaxpr(call)(jnp.ones((8, 128)), jnp.ones((1024, 128))).jaxpr


@pytest.mark.parametrize("precision,out", [
    (None, jnp.float32), (jax.lax.Precision.DEFAULT, jnp.float32), (jax.lax.Precision.HIGH, jnp.float32),
    (jax.lax.Precision.HIGHEST, jnp.bfloat16),
], ids=["unset", "default", "high", "bfloat16_result"])
def test_the_precision_guard_refuses_a_loose_product(precision, out):
    assert len(_loose_dots(_kernel_reading_out(precision, out))) == 1
    assert _loose_dots(_kernel_reading_out(jax.lax.Precision.HIGHEST, jnp.float32)) == []


# ------------------------------------------------------- the whole model


def test_full_forward_against_the_reference(model, cfg, params):
    ids = np.random.default_rng(0).integers(3, VOCAB, (2, 23)).astype(np.int32)
    got = _forward(params, jnp.asarray(ids), cfg)
    want = nemotron_h_lm.logits(params, ids, model)
    assert got.shape == want.shape == (2, 23, VOCAB)
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
    """The check the cell makes, at the rehearsal size: prompts of 21 (not a
    power of two; the scan pads 3 positions) prefilled through
    ``_slot_prefill_paged``, then tokens through ``_pool_step_paged_flash``
    (``ssm_step``, ``paged_flash``, the grouped experts)."""
    from perfbench.program_api import pool_forward_logits, pool_usage

    sched = _scheduler(cfg, params)
    kinds = [set(c) - {"moe_counts"} for c in sched.pool.caches]
    assert kinds[0] == {"ssm_state", "ssm_conv"} and kinds[1] == set() and kinds[7] == {"k", "v"}
    assert sched.pool.caches[0]["ssm_state"].shape == (4, 4, 16, 16) and sched.pool.caches[0]["ssm_state"].dtype == jnp.float32
    prompts = np.random.default_rng(1).integers(3, VOCAB, (2, 21)).astype(np.int32)
    got = pool_forward_logits(sched, prompts, 4)
    full = np.concatenate([prompts, got[:, :4].argmax(-1).astype(np.int32)], axis=1)
    _close(got, nemotron_h_lm.logits(params, full, model, first=20))
    assert pool_usage(sched)[0] <= 1  # the pool is left idle (the sink block aside)


def test_a_recycled_slot_leaks_no_state_and_the_tail_walk_continues_it(model, cfg, params):
    """A slot that served one request serves the next as a fresh one would: the
    prefill reads zeros at position 0, whatever the slot held. A prompt
    prefilled in part then walked a token a step continues the slot's state."""
    rng = np.random.default_rng(5)
    first, second = (rng.integers(3, VOCAB, n).astype(np.int32) for n in (19, 22))
    sched = _scheduler(cfg, params)
    _prefill(sched, 1, first, 0)
    sched.pool.alloc.free_slot(1)
    want = np.asarray(nemotron_h_lm.logits(params, second[None], model))[0]
    _close(_prefill(sched, 1, second[:16], 0), want[15])  # the same slot again: it held the first request's S
    toks, index = np.zeros(4, np.int32), np.zeros(4, np.int32)
    for t in range(16, 22):  # the prompt's tail, a token a step
        sched._paged_ensure(1, t + 1)
        toks[1], index[1] = second[t], t
        _close(_step(sched, toks, index)[1], want[t])


@pytest.mark.parametrize("deployment", [
    {"kv_layout": "paged", "decode_kernel": "paged_flash", "prefill_chunk": 0},
    {"kv_layout": "dense", "decode_kernel": "xla", "prefill_chunk": 8},
])
def test_the_scheduler_serves_the_reference_s_greedy_answers(model, cfg, params, deployment):
    """Through ``ContinuousScheduler`` itself: prompts of 10, 17 and 24 (each
    prefilled to its largest power of two, the rest walked), more requests
    than slots, so slots are recycled."""
    reqs = [{"prompt": " ".join(map(str, np.random.default_rng(i).integers(3, VOCAB, 9 + 7 * (i % 3)))), "max_new": 5}
            for i in range(4)]
    got = [a["continuation"] for a in _scheduler(cfg, params, num_slots=2, **deployment).run([dict(r) for r in reqs])]
    for r, answer in zip(reqs, got):  # greedy: each token is the reference's argmax over the sequence so far
        prompt = [1] + [int(t) for t in r["prompt"].split()]
        seq = prompt + [int(t) for t in answer.split()]
        want = np.asarray(nemotron_h_lm.logits(params, np.asarray([seq[:-1]], np.int32), model))[0].argmax(-1)
        assert want[len(prompt) - 1 :].tolist() == seq[len(prompt) :]


def test_an_attention_kind_that_turns_nothing(model, cfg, params):
    """``rotary_share`` 0: the layer's q and k are not rotated, under the
    model's ``position_scheme`` ``rope``; the reference that rotates them parts."""
    from transformer_tpu.models.encoder import layer_rope

    assert layer_rope(cfg, 7) is False and layer_rope(ModelConfig(**{**model, "attention_kinds": [
        *model["attention_kinds"][:2], {"name": "attention", "rotary_share": 0.5, "sublayers": "mixer"}]}), 7)
    ids = np.random.default_rng(2).integers(3, VOCAB, (1, 24)).astype(np.int32)
    got = np.asarray(_forward(params, jnp.asarray(ids), cfg))
    rotated = np.asarray(nemotron_h_lm.logits(params, ids, model, alter={"rope_attention": True}))
    assert np.abs(got - rotated).max() > 1e-3 * np.abs(rotated).max()


def test_a_factor_on_the_attention_query_kernel_draws_it_larger_and_nothing_else(model, cfg):
    """``query_init_scale`` (seeded weights only) multiplies the attention
    layer's query kernel as drawn; every other parameter is the same draw."""
    kinds = [dict(k, query_init_scale=5.0) if k["name"] == "attention" else k for k in model["attention_kinds"]]
    scaled_cfg = ModelConfig(**{**model, "attention_kinds": kinds})
    plain, scaled = (jax.jit(lambda key, c=c: transformer_init(key, c))(jax.random.PRNGKey(3)) for c in (cfg, scaled_cfg))
    query = ("decoder", "layers", 7, "self_mha", "query", "kernel")
    for (path, a), (_, b) in zip(*(jax.tree_util.tree_flatten_with_path(t)[0] for t in (plain, scaled))):
        keys = tuple(getattr(k, "key", getattr(k, "idx", None)) for k in path)
        if keys == query:
            np.testing.assert_allclose(np.asarray(b), 5.0 * np.asarray(a), rtol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="query_init_scale must be > 0"):
        ModelConfig(**{**model, "attention_kinds": [dict(k, query_init_scale=0.0) for k in model["attention_kinds"]]})


# --------------------------------------------------------------- the experts


def test_the_four_shares_add_up_to_the_uncut_layer(model):
    """Four chips share the expert layer, two of the 8 experts each; the shared
    expert and the latent projections are computed alike by all. The four
    routed parts plus the shared expert ONCE are the uncut reference's layer
    (the up projection is linear: the sum of the projected parts is the
    projection of the sum)."""
    from transformer_tpu.ops.moe import moe_apply_dropless, moe_init

    kw = {"activation": "relu2", "shared_dff": 48, "select_bias": True, "dropless": True, "latent_dim": 32}
    whole = moe_init(jax.random.PRNGKey(0), 64, 32, 8, **kw)
    whole["router"]["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (8,))
    h = jax.random.normal(jax.random.PRNGKey(2), (40, 64))
    route = {"num_experts": 8, "top_k": 3, "routed_scale": 5.0, "score": "sigmoid", "renorm_epsilon": 1e-20,
             "activation": "relu2"}
    share_of = jax.jit(lambda held, offset: moe_apply_dropless(held, h, expert_offset=offset, **route)[0])
    with jax.default_matmul_precision("highest"):
        uncut = nemotron_h_lm.moe(whole, h, 3, 0, 5.0, 1e-20)
        parts = []
        for share in range(4):
            held = {k: v for k, v in whole.items() if k not in ("in", "out")}
            held.update({k: {"kernel": whole[k]["kernel"][2 * share : 2 * share + 2]} for k in ("in", "out")})
            parts.append(share_of(held, 2 * share))
        shared = nemotron_h_lm.relu2_mlp(whole["shared"]["in"]["kernel"], whole["shared"]["out"]["kernel"], h)
    _close(sum(parts) - 3 * shared, uncut, 1e-5)


def test_the_ungated_grouped_kernel_is_ffn_apply_with_relu2():
    from transformer_tpu.kernels.moe_ffn import moe_expert_ffn
    from transformer_tpu.ops.ffn import ffn_apply

    k = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(k[0], (32, 64))
    w_in, w_out = 0.2 * jax.random.normal(k[1], (2, 64, 128)), 0.2 * jax.random.normal(k[2], (2, 128, 64))
    got = moe_expert_ffn(x, None, w_in, w_out, jnp.array([0, 1]), jnp.int32(2), tile_rows=16, activation="relu2")
    for e in range(2):
        want = ffn_apply({"in": {"kernel": w_in[e]}, "out": {"kernel": w_out[e]}}, x[16 * e : 16 * e + 16], "relu2")
        _close(got[16 * e : 16 * e + 16], want, 1e-5)
    with pytest.raises(ValueError, match="gated"):
        moe_expert_ffn(x, w_in, w_in, w_out, jnp.array([0, 1]), jnp.int32(2), tile_rows=16, activation="relu2")


# -------------------------------------------- what is refused, what is counted


def test_prefix_cache_speculation_and_a_mesh_refuse_the_state_space_layer(cfg, params):
    from transformer_tpu.serve.prefix_cache import PrefixCache

    with pytest.raises(ValueError, match="stateful layer.*a state-space layer's state"):
        PrefixCache(cfg, block_tokens=16, budget_mb=8)
    with pytest.raises(ValueError, match="a state-space layer's state.*cannot be rolled back"):
        _scheduler(cfg, params, speculate_k=2)
    with pytest.raises(ValueError, match="a state-space layer.*state a slot beside it"):
        _scheduler(cfg, params, kv_layout="dense", decode_kernel="xla", mesh=2)


def test_the_scheduler_counts_state_resets_and_scanned_chunks(cfg, params):
    from transformer_tpu.obs.telemetry import Telemetry
    from transformer_tpu.obs.trace import buffer

    tel = Telemetry(interval=1e12)
    sched = _scheduler(cfg, params, telemetry=tel, prefill_chunk=8)
    per_slot = 5 * (4 * 16 * 16 * 4 + 3 * 128 * 4)  # float32 at this size: the matrix and the convolution's inputs
    assert tel.registry.gauge("serve_state_bytes_per_slot", "").value == per_slot
    buffer().clear()
    prompts = [" ".join(map(str, np.random.default_rng(i).integers(3, VOCAB, 19))) for i in range(2)]
    sched.run([{"prompt": p, "max_new": 3} for p in prompts])
    admits = [s for s in buffer().snapshot() if s["name"] == "serve.admit"]
    assert [(s["prefill_tokens"], s["ssm_chunks"]) for s in admits] == [(16, 2)] * 2
    assert tel.registry.counter("serve_ssm_chunks_scanned_total", "").value == 2 * 2 * 5


# --------------------------------------------------------------- the controls


@pytest.mark.parametrize("alter", [
    {"no_D": True}, {"norm_whole": True}, {"no_conv_bias": True},
    {"router_latent": True}, {"expert_dtype": "float8_e4m3fn"},
])
def test_each_control_moves_the_comparison(model, cfg, params, alter):
    """The reference with one part of the mathematics changed no longer agrees
    with the program in float32: the comparison sees that part. (The state
    kept in bfloat16 moves 24 positions at this size by 1e-4 of the largest
    logit, the program's own float32 error by 1e-6: its reading is the
    chip's, at the cell's size.)"""
    ids = np.random.default_rng(2).integers(3, VOCAB, (1, 24)).astype(np.int32)
    got = np.asarray(_forward(params, jnp.asarray(ids), cfg))
    want = np.asarray(nemotron_h_lm.logits(params, ids, model))
    altered = np.asarray(nemotron_h_lm.logits(params, ids, model, alter=alter))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 2e-5 * scale
    assert np.abs(got - altered).max() > 1e-4 * scale


def test_the_state_space_check_holds_each_layer_alone_and_sees_a_state_kept_in_bfloat16(model, params):
    """The cell's state-space check: each Mamba layer's prefill and pool steps
    against the reference's recurrence, outputs and final state. In float32
    the two agree to rounding; the reference with its state kept in bfloat16
    after the prompt parts in the state, one without ``D`` in the outputs;
    and a state the pool keeps in fewer bytes than the cell states is not
    correct whatever it reads."""
    from types import SimpleNamespace

    from perfbench.kinds.serve_open_loop_routed_state import ssm_check

    config = {"family": "nemotron_h_lm", "model": model}
    cell = {"ssm_check": {"sequences": 2, "prompt_tokens": 21, "decode_tokens": 12, "state_dtype": "float32"}}
    ctx = SimpleNamespace(seed=5)
    sound = ssm_check(ctx, config, cell, params)
    assert sound["ok"] and sound["state_kept_as"] == ["float32"] and len(sound["state_by_layer"]) == 5
    assert sound["out_rel"] < 2e-5 and sound["state_rel"] < 2e-5
    kept = ssm_check(ctx, config, cell, params, alter={"state_kept_bfloat16": 21})
    assert kept["state_rel"] > 100 * sound["state_rel"] and kept["state_rel"] > 1e-3
    assert not ssm_check(ctx, config, cell, params, alter={"no_D": True})["ok"]
    wider = {"ssm_check": dict(cell["ssm_check"], state_dtype="float64")}
    assert not ssm_check(ctx, config, wider, params)["ok"]


# ------------------------- the other served configurations' programs stand


def test_the_model_lowers_as_recorded():
    """This model's step and prefill as first recorded (the other served
    configurations' are pinned in their own files and hash as before this
    model: it gave the shared code a mixer, layers of one sublayer, ungated
    and latent experts, none of which they use). A change of shared code
    moves these on purpose: record them again
    (tests/fixtures/lowered_serving_programs.json)."""
    from tests.test_lfm2 import _lowered_serving_programs

    with open(os.path.join(ROOT, "tests", "fixtures", "lowered_serving_programs.json")) as f:
        recorded = json.load(f)
    assert _lowered_serving_programs(CELL) == recorded[CELL]
