"""Unit tests for core ops (L1) against NumPy oracles (SURVEY.md §4 plan)."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transformer_tpu.config import ModelConfig
from transformer_tpu.ops import (
    dot_product_attention,
    ffn_apply,
    ffn_init,
    make_causal_mask,
    make_padding_mask,
    make_seq2seq_masks,
    mha_apply,
    mha_init,
    sinusoidal_positional_encoding,
)
from transformer_tpu.ops.attention import init_cache
from transformer_tpu.ops.masks import NEG_INF, attention_bias
from transformer_tpu.ops.nn import dropout, layernorm_apply, layernorm_init


class TestPositionalEncoding:
    def test_matches_closed_form(self):
        """Oracle: the reference formula (positionalencoding.py:4-23) in NumPy —
        block layout [sin(angles at even channels), cos(angles at odd channels)]."""
        max_pos, d_model = 64, 16
        table = np.asarray(sinusoidal_positional_encoding(max_pos, d_model))
        pos = np.arange(max_pos)[:, None]
        i = np.arange(d_model)[None, :]
        angles = pos / np.power(10000.0, (2 * (i // 2)) / d_model)
        expected = np.concatenate([np.sin(angles[:, 0::2]), np.cos(angles[:, 1::2])], axis=-1)
        np.testing.assert_allclose(table, expected, atol=1e-5)

    def test_sized_by_positions_not_vocab(self):
        table = sinusoidal_positional_encoding(128, 32)
        assert table.shape == (128, 32)

    def test_position_zero_is_sin0_cos0(self):
        table = np.asarray(sinusoidal_positional_encoding(4, 8))
        np.testing.assert_allclose(table[0, :4], 0.0, atol=1e-7)  # sin(0)
        np.testing.assert_allclose(table[0, 4:], 1.0, atol=1e-7)  # cos(0)


class TestMasks:
    def test_padding_mask(self):
        ids = jnp.array([[5, 3, 0, 0], [1, 0, 2, 0]])
        mask = make_padding_mask(ids)
        assert mask.shape == (2, 1, 1, 4)
        np.testing.assert_array_equal(
            np.asarray(mask[:, 0, 0, :]),
            [[True, True, False, False], [True, False, True, False]],
        )

    def test_causal_mask(self):
        mask = np.asarray(make_causal_mask(4)[0, 0])
        expected = np.tril(np.ones((4, 4), dtype=bool))
        np.testing.assert_array_equal(mask, expected)

    def test_seq2seq_masks_semantics(self):
        """Parity with reference create_masks (positionalencoding.py:37-52):
        combined = causal AND target-padding; cross mask uses *source* padding."""
        inp = jnp.array([[7, 8, 0]])
        tar = jnp.array([[4, 0, 5]])
        enc, combined, cross = make_seq2seq_masks(inp, tar)
        assert enc.shape == (1, 1, 1, 3)
        assert combined.shape == (1, 1, 3, 3)
        assert cross.shape == (1, 1, 1, 3)
        np.testing.assert_array_equal(np.asarray(enc[0, 0, 0]), [True, True, False])
        np.testing.assert_array_equal(np.asarray(cross[0, 0, 0]), [True, True, False])
        # Row 2 (query pos 2): causal allows 0,1,2 but key pos 1 is pad.
        np.testing.assert_array_equal(np.asarray(combined[0, 0, 2]), [True, False, True])
        # Row 0: only key 0.
        np.testing.assert_array_equal(np.asarray(combined[0, 0, 0]), [True, False, False])

    def test_attention_bias(self):
        mask = jnp.array([[True, False]])
        bias = np.asarray(attention_bias(mask, jnp.float32))
        assert bias[0, 0] == 0.0 and bias[0, 1] == NEG_INF


def _numpy_attention(q, k, v, allowed=None):
    """fp64 NumPy oracle for softmax(qk^T/sqrt(d))v over (B,S,H,D) layout."""
    q, k, v = (np.asarray(t, dtype=np.float64) for t in (q, k, v))
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if allowed is not None:
        logits = np.where(np.asarray(allowed), logits, -1e9)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", w, v)


class TestDotProductAttention:
    def test_matches_numpy_oracle(self):
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (2, 5, 3, 8))
        k = jax.random.normal(kk, (2, 7, 3, 8))
        v = jax.random.normal(kv, (2, 7, 3, 8))
        out, _ = dot_product_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), _numpy_attention(q, k, v), atol=1e-5)

    def test_masking_blocks_positions(self):
        key = jax.random.PRNGKey(1)
        q = jax.random.normal(key, (1, 2, 1, 4))
        k = jax.random.normal(key, (1, 3, 1, 4))
        v = jax.random.normal(key, (1, 3, 1, 4))
        mask = jnp.array([True, True, False])[None, None, None, :]
        out, w = dot_product_attention(q, k, v, mask, return_weights=True)
        np.testing.assert_allclose(np.asarray(w[..., 2]), 0.0, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(out), _numpy_attention(q, k, v, mask), atol=1e-5
        )

    def test_weights_sum_to_one(self):
        q = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 2, 8))
        _, w = dot_product_attention(q, q, q, return_weights=True)
        np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)

    def test_bf16_inputs_fp32_softmax(self):
        q = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 2, 16), dtype=jnp.bfloat16)
        out, _ = dot_product_attention(q, q, q)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float64),
            _numpy_attention(q, q, q),
            atol=2e-2,
        )


class TestMultiHeadAttention:
    def test_shapes_and_param_structure(self):
        cfg = ModelConfig(d_model=32, num_heads=4, input_vocab_size=10, target_vocab_size=10)
        params = mha_init(jax.random.PRNGKey(0), cfg.d_model, cfg.num_heads)
        assert params["query"]["kernel"].shape == (32, 4, 8)
        assert params["out"]["kernel"].shape == (4, 8, 32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 32))
        out, w, _ = mha_apply(params, x, x, return_weights=True)
        assert out.shape == (2, 6, 32)
        assert w.shape == (2, 4, 6, 6)

    def test_divisibility_asserted(self):
        with pytest.raises(ValueError):
            ModelConfig(d_model=30, num_heads=4)

    def test_cache_prefill_chunk_is_causal(self):
        """Regression: writing a multi-token chunk into the cache must stay
        causal — query i may not attend new positions > i."""
        d_model, heads, seq = 16, 2, 6
        params = mha_init(jax.random.PRNGKey(0), d_model, heads)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, seq, d_model))
        full, _, _ = mha_apply(params, x, x, causal=True)
        cache = init_cache(1, seq, heads, d_model // heads, dtype=jnp.float32)
        chunk, _, cache = mha_apply(params, x[:, :4], x[:, :4], cache=cache)
        np.testing.assert_allclose(np.asarray(full[:, :4]), np.asarray(chunk), atol=1e-5)
        step, _, cache = mha_apply(params, x[:, 4:], x[:, 4:], cache=cache)
        np.testing.assert_allclose(np.asarray(full[:, 4:]), np.asarray(step), atol=1e-5)

    def test_causal_flag_combines_with_padding_mask(self):
        """causal=True must AND with a provided mask, not be skipped."""
        d_model, heads = 8, 1
        params = mha_init(jax.random.PRNGKey(0), d_model, heads)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, d_model))
        pad_mask = jnp.ones((1, 1, 1, 4), jnp.bool_)
        _, w, _ = mha_apply(params, x, x, pad_mask, causal=True, return_weights=True)
        w = np.asarray(w[0, 0])
        assert np.allclose(np.triu(w, k=1), 0.0, atol=1e-6), "future positions attended"

    def test_cache_decode_matches_full_attention(self):
        """Greedy-decode equivalence: attending step-by-step through a KV cache
        must equal causal attention over the full sequence."""
        d_model, heads, seq = 16, 2, 5
        params = mha_init(jax.random.PRNGKey(0), d_model, heads)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, seq, d_model))
        full, _, _ = mha_apply(params, x, x, causal=True)

        cache = init_cache(1, seq, heads, d_model // heads, dtype=jnp.float32)
        outs = []
        for t in range(seq):
            step, _, cache = mha_apply(params, x[:, t : t + 1], x[:, t : t + 1], cache=cache)
            outs.append(step)
        incremental = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(full), np.asarray(incremental), atol=1e-5)


class TestFFN:
    def test_matches_numpy_oracle(self):
        params = ffn_init(jax.random.PRNGKey(0), 8, 16)
        assert "gate" not in params  # ungated default matches the reference
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 8))
        out = ffn_apply(params, x)
        h = np.maximum(np.asarray(x) @ np.asarray(params["in"]["kernel"]) + np.asarray(params["in"]["bias"]), 0)
        expected = h @ np.asarray(params["out"]["kernel"]) + np.asarray(params["out"]["bias"])
        np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)

    def test_swiglu_matches_numpy_oracle(self):
        """Gated variant (Shazeer 2020): act(x W_gate) * (x W_in) W_out."""
        params = ffn_init(jax.random.PRNGKey(0), 8, 16, activation="swiglu")
        assert set(params) == {"in", "out", "gate"}
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 8))
        out = ffn_apply(params, x, activation="swiglu")
        xn = np.asarray(x, np.float64)
        g = xn @ np.asarray(params["gate"]["kernel"]) + np.asarray(params["gate"]["bias"])
        silu = g * (1.0 / (1.0 + np.exp(-g)))  # x * sigmoid(x)
        h = silu * (xn @ np.asarray(params["in"]["kernel"]) + np.asarray(params["in"]["bias"]))
        expected = h @ np.asarray(params["out"]["kernel"]) + np.asarray(params["out"]["bias"])
        np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)

    @pytest.mark.slow
    def test_swiglu_model_trains(self):
        from transformer_tpu.config import ModelConfig, TrainConfig
        from transformer_tpu.train import create_train_state, make_train_step

        cfg = ModelConfig(
            num_layers=2, d_model=32, num_heads=4, dff=64,
            input_vocab_size=50, target_vocab_size=50, max_position=16,
            dtype="float32", dropout_rate=0.0, ffn_activation="swiglu",
        )
        tc = TrainConfig(batch_size=8, sequence_length=12, warmup_steps=100)
        state = create_train_state(jax.random.PRNGKey(0), cfg, tc)
        step = jax.jit(make_train_step(cfg, tc))
        r = np.random.default_rng(0)
        src = jnp.asarray(r.integers(1, 48, (8, 12)), jnp.int32)
        tgt = jnp.asarray(r.integers(1, 48, (8, 12)), jnp.int32)
        rng = jax.random.PRNGKey(1)
        first = None
        for _ in range(40):
            state, m = step(state, src, tgt, rng)
            first = float(m["loss"]) if first is None else first
        assert float(m["loss"]) < first * 0.7

    def test_moe_rejects_gated_activation(self):
        import pytest

        from transformer_tpu.config import ModelConfig

        with pytest.raises(ValueError, match="ungated"):
            ModelConfig(moe_experts=4, ffn_activation="swiglu")


class TestLayerNorm:
    def test_matches_numpy_oracle(self):
        params = layernorm_init(16)
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 16)) * 3 + 1
        out = np.asarray(layernorm_apply(params, x))
        xn = np.asarray(x, dtype=np.float64)
        expected = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(
            xn.var(-1, keepdims=True) + 1e-6
        )
        np.testing.assert_allclose(out, expected, atol=1e-4)
        np.testing.assert_allclose(out.mean(-1), 0.0, atol=1e-5)
        np.testing.assert_allclose(out.std(-1), 1.0, atol=1e-2)


def _kept(y) -> np.ndarray:
    return np.asarray(y) != 0


def _four_sigma(p: float, n: int) -> float:
    return 4.0 * (p * (1.0 - p) / n) ** 0.5


def _chance(rate: float) -> float:
    """The share of elements on which two independent masks agree."""
    return (1 - rate) ** 2 + rate**2


class TestDropout:
    """The mask's statistics and its determinism. The benchmark's training
    check runs with dropout off, so nothing else holds these."""

    SHAPE = (64, 128, 256)
    N = int(np.prod(SHAPE))

    # The inputs and the jitted function are made once: 2M elements a call.
    @staticmethod
    @functools.lru_cache(maxsize=None)
    def _x(shape=SHAPE):
        # No element is 0, so the kept positions can be read off the output.
        return jax.random.normal(jax.random.PRNGKey(7), shape) + 3.0

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def _jitted(rate):
        return jax.jit(lambda k, v: dropout(k, v, rate, False))

    @classmethod
    def _drop(cls, key, x, rate):
        return cls._jitted(rate)(key, x)

    @pytest.mark.parametrize("rate", [0.1, 0.3])
    def test_kept_share_is_keep(self, rate):
        kept = _kept(self._drop(jax.random.PRNGKey(0), self._x(), rate))
        assert abs(kept.mean() - (1 - rate)) < _four_sigma(1 - rate, self.N)

    @pytest.mark.parametrize("rate", [0.1, 0.3])
    def test_kept_are_scaled_and_dropped_are_zero(self, rate):
        x = self._x()
        y = np.asarray(self._drop(jax.random.PRNGKey(1), x, rate))
        kept = y != 0
        np.testing.assert_allclose(y[kept], np.asarray(x)[kept] / (1 - rate), rtol=1e-6)
        assert 0 < (~kept).sum() < self.N

    @pytest.mark.parametrize("rate", [0.1, 0.3])
    def test_same_key_same_mask_twice_and_under_checkpoint(self, rate):
        x, key = self._x(), jax.random.PRNGKey(2)
        first = self._drop(key, x, rate)
        np.testing.assert_array_equal(first, self._drop(key, x, rate))
        recomputed = jax.jit(jax.checkpoint(lambda k, v: dropout(k, v, rate, False)))(key, x)
        np.testing.assert_array_equal(first, recomputed)

    @pytest.mark.parametrize("rate", [0.1, 0.3])
    def test_gradient_is_the_forward_mask_over_keep(self, rate):
        """Under ``jax.checkpoint`` too, where the backward pass makes the
        mask again instead of reading the one the forward pass wrote."""
        x, key = self._x(), jax.random.PRNGKey(3)
        kept = _kept(self._drop(key, x, rate))
        expected = kept.astype(np.float32) / np.float32(1 - rate)
        for wrap in (lambda f: f, jax.checkpoint):
            fn = wrap(lambda v: dropout(key, v, rate, False))
            grad = jax.jit(jax.grad(lambda v: fn(v).sum()))(x)
            np.testing.assert_allclose(np.asarray(grad), expected, rtol=1e-6)

    @pytest.mark.parametrize("rate", [0.1, 0.3])
    def test_split_keys_agree_only_by_chance(self, rate):
        x = self._x()
        k1, k2 = jax.random.split(jax.random.PRNGKey(4))
        agree = (_kept(self._drop(k1, x, rate)) == _kept(self._drop(k2, x, rate))).mean()
        chance = _chance(rate)
        assert abs(agree - chance) < _four_sigma(chance, self.N)

    @pytest.mark.parametrize("rate", [0.1, 0.3])
    @pytest.mark.parametrize("prefix", [(32, 128, 256), (64, 128, 128), (64, 64, 256)])
    def test_one_key_at_two_shapes_agrees_only_by_chance(self, rate, prefix):
        """The generator counts from its state, so the shape has to be part of
        the state: else a key used at two widths gives the narrower tensor
        the wider one's first bits."""
        key = jax.random.PRNGKey(5)
        wide = _kept(self._drop(key, self._x(), rate))
        narrow = _kept(self._drop(key, self._x(prefix), rate))
        common = tuple(slice(0, n) for n in prefix)
        agree = (wide[common] == narrow).mean()
        chance = _chance(rate)
        assert abs(agree - chance) < _four_sigma(chance, narrow.size)

    @pytest.mark.parametrize(
        "rate,deterministic", [(0.3, True), (0.0, False)], ids=["deterministic", "rate0"]
    )
    def test_identity_returns_the_input_and_never_reads_the_key(self, rate, deterministic):
        x = self._x((2, 3))
        assert dropout(object(), x, rate, deterministic) is x

    def test_training_needs_a_key(self):
        with pytest.raises(ValueError, match="rng key"):
            dropout(None, self._x((2, 3)), 0.1, False)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_typed_key_and_dtype(self, dtype):
        x = self._x((8, 16, 128)).astype(dtype)
        y = dropout(jax.random.key(6), x, 0.3, False)
        assert y.dtype == dtype and 0 < _kept(y).mean() < 1

    @pytest.mark.parametrize("rate", [0.1, 0.3])
    def test_batch_shards_get_different_masks(self, rate):
        """Under ``jit`` with the batch split over the 8-device mesh, XLA's
        partitioner must not hand every shard the same bits."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
        rows = NamedSharding(mesh, P("data"))
        x = jax.device_put(self._x(), rows)
        y = jax.jit(
            lambda k, v: dropout(k, v, rate, False), out_shardings=rows
        )(jax.random.PRNGKey(8), x)
        assert y.sharding.is_equivalent_to(rows, y.ndim)
        kept = _kept(y)
        assert abs(kept.mean() - (1 - rate)) < _four_sigma(1 - rate, self.N)
        shards = kept.reshape(8, -1)
        chance = _chance(rate)
        for i in range(1, 8):
            agree = (shards[0] == shards[i]).mean()
            assert abs(agree - chance) < _four_sigma(chance, shards[0].size), i


class TestDropoutInPrograms:
    """Where the mask's bits are made, read from lowered programs: the train
    step draws each site's bits with ``rng_bit_generator`` and hashes nothing
    of an activation's size; the serving programs draw nothing."""

    @staticmethod
    def _hashed_words(text: str) -> int:
        """The largest ``ui32`` tensor that a threefry round (``xor``) touches."""
        largest = 0
        for line in text.splitlines():
            if "stablehlo.xor" in line:
                for dims in re.findall(r"tensor<((?:\d+x)*)ui32>", line):
                    largest = max(largest, int(np.prod([int(d) for d in dims.split("x") if d])))
        return largest

    def test_train_step_draws_with_the_generator_and_hashes_only_keys(self):
        from transformer_tpu.config import TrainConfig
        from transformer_tpu.train import create_train_state, make_train_step

        cfg = ModelConfig(
            num_layers=2, d_model=16, num_heads=2, dff=32, input_vocab_size=40,
            target_vocab_size=48, max_position=16, dropout_rate=0.1, dtype="float32",
        )
        tc = TrainConfig(batch_size=4, sequence_length=8, epochs=1, warmup_steps=100)
        key = jax.random.PRNGKey(0)
        state = jax.eval_shape(lambda k: create_train_state(k, cfg, tc), key)
        src = jax.ShapeDtypeStruct((4, 8), jnp.int32)
        tgt = jax.ShapeDtypeStruct((4, 9), jnp.int32)
        text = jax.jit(make_train_step(cfg, tc)).lower(state, src, tgt, key).as_text()
        # Two prologues, two sublayers an encoder layer, three a decoder layer;
        # the forward pass draws each site's mask and the backward pass draws
        # it again (it keeps the key, not the mask).
        sites = 2 + cfg.num_layers * 2 + cfg.num_layers * 3
        assert text.count("stablehlo.rng_bit_generator") == 2 * sites
        # The hash runs over the key's words and the generator's state only.
        assert 0 < self._hashed_words(text) <= 4

    @pytest.mark.parametrize("program", ["_pool_step_paged_flash", "_slot_prefill_paged"])
    def test_serving_programs_draw_nothing(self, program):
        """A serving forward is ``deterministic``: ``dropout`` returns its input
        before it looks at the key, whatever the model's rate."""
        from transformer_tpu.models.transformer import transformer_init
        from transformer_tpu.serve import scheduler as sched

        cfg = ModelConfig(
            num_layers=2, d_model=16, num_heads=2, dff=32, input_vocab_size=64,
            target_vocab_size=64, max_position=64, decoder_only=True, tie_output=True,
            dtype="float32", dropout_rate=0.1,
        )
        slots, max_total, block = 2, 32, 8
        params = jax.eval_shape(lambda k: transformer_init(k, cfg), jax.random.PRNGKey(0))
        pool, table, index = sched.abstract_paged_pool(
            cfg, slots, max_total, 1 + slots * (max_total // block), block
        )
        if program == "_pool_step_paged_flash":
            toks = jax.ShapeDtypeStruct((slots,), jnp.int32)
            lowered = sched._pool_step_paged_flash.lower(
                params, pool, table, index, toks, cfg, block, True
            )
        else:
            scalar = jax.ShapeDtypeStruct((), jnp.int32)
            prompt = jax.ShapeDtypeStruct((1, 16), jnp.int32)
            lowered = sched._slot_prefill_paged.lower(
                params, pool, table, scalar, prompt, scalar, cfg, 8, block, max_total
            )
        text = lowered.as_text()
        assert "rng_bit_generator" not in text
        assert self._hashed_words(text) == 0
