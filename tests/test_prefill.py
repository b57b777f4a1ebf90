"""Single-pass chunked prefill parity (the serving fast path's correctness
contract): ingesting the prompt through ``transformer_prefill`` — whole or in
chunks, across the int8-quantized, rolling-window, and GQA cache variants —
must reproduce the token-by-token decode loop bit for bit, both in the caches
it leaves behind and in the generations that start from them."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transformer_tpu.config import PAD_ID, ModelConfig
from transformer_tpu.models import transformer_init
from transformer_tpu.models.decoder import (
    decoder_prefill,
    init_decoder_caches,
)
from transformer_tpu.models.transformer import (
    transformer_decode_step,
    transformer_prefill,
)
from transformer_tpu.train.decode import lm_generate, prefill_len_for

LM = ModelConfig(
    num_layers=2, d_model=16, num_heads=4, dff=32,
    input_vocab_size=48, target_vocab_size=48, max_position=64,
    decoder_only=True, tie_output=True, dtype="float32", dropout_rate=0.0,
)

VARIANTS = {
    "base": LM,
    "int8": dataclasses.replace(LM, kv_cache_int8=True),
    "window": dataclasses.replace(LM, attention_window=3),
    "gqa": dataclasses.replace(LM, num_kv_heads=2),
    "window_int8": dataclasses.replace(
        LM, attention_window=3, kv_cache_int8=True
    ),
}


def _prompts(key=0, batch=3, width=7):
    """Ragged PAD-right prompt batch (lens 7/5/4) — the shape generate()
    hands lm_generate."""
    ids = np.array(
        jax.random.randint(jax.random.PRNGKey(key), (batch, width), 3, 40),
        np.int32,
    )
    ids[1, 5:] = PAD_ID
    ids[2, 4:] = PAD_ID
    return jnp.asarray(ids)


@pytest.mark.parametrize("name", sorted(VARIANTS))
@pytest.mark.parametrize("chunk", [0, 3])
def test_prefill_caches_match_stepwise(name, chunk):
    """decoder_prefill must leave the caches (buffers AND index) exactly
    where feeding the same tokens one step at a time leaves them — per
    variant, whole-prompt and ragged-chunked."""
    cfg = VARIANTS[name]
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    toks = _prompts()[:, :4]  # no PAD: pure cache-write comparison
    total = 10

    step_caches = init_decoder_caches(cfg, 3, total)
    for t in range(4):
        logits_step, step_caches = transformer_decode_step(
            params, toks[:, t : t + 1], None, None, step_caches,
            jnp.int32(t), cfg,
        )

    pre_caches = init_decoder_caches(cfg, 3, total)
    x_last, pre_caches = decoder_prefill(
        params["decoder"], toks, None, None, pre_caches, cfg, chunk=chunk
    )
    logits_pre, _ = transformer_prefill(
        params, toks, None, None, init_decoder_caches(cfg, 3, total), 0, cfg,
        chunk=chunk,
    )

    for lc_step, lc_pre in zip(step_caches, pre_caches):
        assert set(lc_step) == set(lc_pre)
        assert int(lc_pre["index"]) == 4
        for k in lc_step:
            a = np.asarray(lc_step[k], np.float32)
            b = np.asarray(lc_pre[k], np.float32)
            if np.asarray(lc_step[k]).dtype == np.int8:
                # int8 codes may flip by ONE step: the chunked forward's
                # last-ulp fp differences can cross a rounding boundary.
                # The dequantized error that admits is below the int8
                # scheme's own quantization noise (pinned by the greedy /
                # sampled bit-parity tests below).
                assert np.max(np.abs(a - b)) <= 1, f"{name} cache[{k}]"
            else:
                np.testing.assert_allclose(
                    a, b, atol=2e-5, err_msg=f"{name} cache[{k}]"
                )
    # The prefill's last-position logits are the decode loop's tick-3 logits.
    np.testing.assert_allclose(
        np.asarray(logits_pre), np.asarray(logits_step), atol=2e-4,
        err_msg=name,
    )


@pytest.mark.parametrize("name", sorted(VARIANTS))
@pytest.mark.parametrize("chunk", [0, 3])
def test_lm_generate_prefill_parity_greedy(name, chunk):
    """Greedy generation from a chunked-prefilled cache is bit-identical to
    the pure token-by-token loop (prefill_len=0)."""
    cfg = VARIANTS[name]
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    ids = _prompts()
    want = lm_generate(params, ids, cfg, 6, eos_id=2)
    got = lm_generate(
        params, ids, cfg, 6, eos_id=2, prefill_len=4, prefill_chunk=chunk
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=name)


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_lm_generate_prefill_parity_sampled(name):
    """sample=True with a fixed rng: position-keyed rng folding means the
    prefilled path draws the same tokens as the loop, bit for bit."""
    cfg = VARIANTS[name]
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    ids = _prompts(key=1)
    kw = dict(
        rng=jax.random.PRNGKey(7), sample=True, temperature=0.8,
        top_k=8, top_p=0.9,
    )
    want = lm_generate(params, ids, cfg, 6, eos_id=2, **kw)
    got = lm_generate(
        params, ids, cfg, 6, eos_id=2, prefill_len=4, prefill_chunk=3, **kw
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=name)


def test_generate_text_parity(monkeypatch):
    """Text-level end-to-end: generate() with prefill enabled (the default)
    returns the same strings as with prefill forced off."""
    from transformer_tpu.data.tokenizer import SubwordTokenizer
    from transformer_tpu.train import decode as decode_mod

    tok = SubwordTokenizer.build_from_corpus(
        ["ab cd ef gh ij kl"] * 3, target_vocab_size=280
    )
    cfg = dataclasses.replace(
        LM,
        input_vocab_size=tok.model_vocab_size,
        target_vocab_size=tok.model_vocab_size,
        max_position=32,
    )
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    prompts = ["ab cd ef", "gh ij"]
    with_prefill = decode_mod.generate(
        params, cfg, tok, prompts, max_new=5, prefill_chunk=2
    )
    monkeypatch.setattr(decode_mod, "prefill_len_for", lambda *a: 0)
    without = decode_mod.generate(params, cfg, tok, prompts, max_new=5)
    assert with_prefill == without


def test_prefill_len_for_bucketing():
    """Prefill lengths bucket (power of two, or multiples of the chunk) so
    serving compiles a bounded set of prefill signatures."""
    assert prefill_len_for(0) == 0
    assert prefill_len_for(1) == 1
    assert prefill_len_for(7) == 4
    assert prefill_len_for(64) == 64
    assert prefill_len_for(65) == 64
    assert prefill_len_for(65, chunk=16) == 64
    assert prefill_len_for(15, chunk=16) == 8  # under one chunk: pow2 rule
    assert prefill_len_for(33, chunk=16) == 32
    # Chunk COUNTS round to powers of two — O(log) distinct signatures,
    # not O(max_len / chunk).
    assert prefill_len_for(50, chunk=16) == 32  # 3 chunks -> 2 chunks
    assert prefill_len_for(4096, chunk=16) == 4096
    # A typo'd negative chunk flag must behave as "no chunking", never
    # return a negative length (the scheduler slices ids[:n] with it).
    assert prefill_len_for(7, chunk=-2) == 4
    assert prefill_len_for(64, chunk=-2) == 64


def test_prefill_is_single_pass(monkeypatch):
    """The structural claim: a 64-token prompt prefills in ceil(64 / chunk)
    decoder forwards — never 64 sequential decode steps."""
    from transformer_tpu.models import decoder as decoder_mod

    calls = []
    real = decoder_mod.decoder_apply

    def counting(params, ids, *a, **kw):
        calls.append(ids.shape[1])
        return real(params, ids, *a, **kw)

    monkeypatch.setattr(decoder_mod, "decoder_apply", counting)
    cfg = dataclasses.replace(LM, max_position=80)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(
        jax.random.randint(jax.random.PRNGKey(2), (1, 64), 3, 40), jnp.int32
    )
    caches = init_decoder_caches(cfg, 1, 70)
    decoder_prefill(params["decoder"], toks, None, None, caches, cfg)
    assert calls == [64]  # one full-width pass
    calls.clear()
    caches = init_decoder_caches(cfg, 1, 70)
    decoder_prefill(params["decoder"], toks, None, None, caches, cfg, chunk=16)
    assert calls == [16, 16, 16, 16]
    calls.clear()
    caches = init_decoder_caches(cfg, 1, 70)
    # chunk <= 0 normalizes to one full-width pass (never an empty loop).
    decoder_prefill(params["decoder"], toks, None, None, caches, cfg, chunk=-2)
    assert calls == [64]


def test_rolling_prefill_chunk_cap():
    """A rolling-window cache caps prefill chunks at its buffer length (a
    wider chunk would evict positions still inside an earlier chunk token's
    band); decoder_prefill splits automatically."""
    cfg = VARIANTS["window"]
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    toks = _prompts()[:, :6]
    caches = init_decoder_caches(cfg, 3, 10)
    assert caches[0]["k"].shape[1] == 3  # rolling buffer = window slots
    # chunk=0 would mean "all 6 at once": must be capped to 3 internally.
    _, caches = decoder_prefill(
        params["decoder"], toks, None, None, caches, cfg, chunk=0
    )
    assert int(caches[0]["index"]) == 6
