"""Transformer assembly.

Counterpart of the reference's ``Transformer.py``: encoder + decoder + final
vocab projection, with masks rebuilt from raw token ids inside the forward pass
every call (``Transformer.py:21-23``). Extensions beyond the reference:

- ``cfg.tie_embeddings``: one shared embedding table for source and target
  (requires equal vocab sizes) — BASELINE.json configs[3];
- ``cfg.tie_output``: logits via the transposed embedding table instead of the
  reference's untied Dense (``Transformer.py:16,30``);
- ``cfg.decoder_only``: a causal LM with no encoder at all — forward takes the
  token sequence alone (BASELINE.json configs[4]);
- ``cfg.encoder_only``: a bidirectional encoder with the vocab head (BERT
  family) — trained with the masked-LM objective
  (``TrainConfig.objective="mlm"``, ``train/mlm.py``).
"""

from __future__ import annotations

from typing import Any

import jax

from transformer_tpu.config import PAD_ID, ModelConfig
from transformer_tpu.models.decoder import decoder_apply, decoder_init
from transformer_tpu.models.encoder import encoder_apply, encoder_init
from transformer_tpu.ops.masks import make_padding_mask
from transformer_tpu.ops.nn import Params, dense_apply, dense_init, embedding_attend


def transformer_init(key: jax.Array, cfg: ModelConfig) -> Params:
    k_enc, k_dec, k_final = jax.random.split(key, 3)
    if cfg.encoder_only:
        params = {"encoder": encoder_init(k_enc, cfg)}
    elif cfg.decoder_only:
        params: Params = {"decoder": decoder_init(k_dec, cfg)}
    else:
        encoder = encoder_init(k_enc, cfg)
        shared = None
        if cfg.tie_embeddings:
            if cfg.input_vocab_size != cfg.target_vocab_size:
                raise ValueError(
                    "tie_embeddings requires input_vocab_size == target_vocab_size "
                    f"({cfg.input_vocab_size} != {cfg.target_vocab_size})"
                )
            shared = encoder["embedding"]
        params = {"encoder": encoder, "decoder": decoder_init(k_dec, cfg, embedding=shared)}
    if not cfg.tie_output:
        params["final"] = dense_init(
            k_final, cfg.d_model, cfg.target_vocab_size, cfg.params_dtype, cfg.use_bias
        )
    return params


def _logits(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_output:
        tower = "encoder" if cfg.encoder_only else "decoder"
        return embedding_attend(params[tower]["embedding"], x)
    return dense_apply(params["final"], x)


def project_logits(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final vocab projection: (..., d_model) hiddens -> (..., V) raw logits
    (tied or untied per ``cfg.tie_output``). Public counterpart of the
    projection inside ``transformer_apply`` for callers that project slices
    (chunked loss, decode)."""
    return _logits(params, x, cfg)


def transformer_hidden_apply(
    params: Params,
    inp: jax.Array | None,
    tar: jax.Array,
    cfg: ModelConfig,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    return_weights: bool = False,
    pad_id: int = PAD_ID,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Forward pass up to (but not including) the final vocab projection:
    returns ((B, S_tgt, d_model) decoder hiddens, attention_weights).

    Split out of ``transformer_apply`` so the chunked-loss path
    (``train/loss.py chunked_cross_entropy_from_hidden``) can project and
    score the (huge) vocab logits a sequence slice at a time instead of
    materializing the full (B, S, V) tensor.
    """
    if cfg.encoder_only:
        # BERT family: the bidirectional encoder stack, padding mask only
        # (no causality — every position attends to the full sequence).
        mask = make_padding_mask(tar, pad_id)
        x, attn = encoder_apply(
            params["encoder"], tar, mask, cfg, rng, deterministic,
            return_weights,
        )
        return x, attn

    if cfg.decoder_only:
        self_mask = make_padding_mask(tar, pad_id)  # ANDed with causal inside MHA
        x, attn, _ = decoder_apply(
            params["decoder"], tar, None, self_mask, None, cfg,
            rng, deterministic, return_weights,
        )
        return x, attn

    # Encoder self-attention and decoder cross-attention both mask source
    # padding; decoder self-attention masks target padding, with causality
    # applied structurally inside MHA (``causal=True`` in decoder_layer_apply)
    # so the flash/ring kernels can skip above-diagonal blocks. Together these
    # equal the reference's three ``create_masks`` outputs
    # (``positionalencoding.py:37-52``) — see ``ops.masks.make_seq2seq_masks``
    # for the dense-mask form.
    enc_mask = make_padding_mask(inp, pad_id)
    cross_mask = enc_mask
    self_mask = make_padding_mask(tar, pad_id)
    r_enc, r_dec = (None, None) if rng is None else jax.random.split(rng)
    enc_out, enc_attn = encoder_apply(
        params["encoder"], inp, enc_mask, cfg, r_enc, deterministic, return_weights
    )
    x, dec_attn, _ = decoder_apply(
        params["decoder"], tar, enc_out, self_mask, cross_mask, cfg,
        r_dec, deterministic, return_weights,
    )
    return x, {**enc_attn, **dec_attn}


def transformer_apply(
    params: Params,
    inp: jax.Array | None,
    tar: jax.Array,
    cfg: ModelConfig,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    return_weights: bool = False,
    pad_id: int = PAD_ID,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Forward pass: (inp, tar) token ids -> (logits, attention_weights).

    ``inp`` is ignored (may be None) when ``cfg.decoder_only``; ``tar`` is then
    the causal-LM token sequence. Logits are raw (no softmax), shaped
    (B, S_tgt, target_vocab_size) — same contract as reference
    ``Transformer.py:30-32``.
    """
    x, attn = transformer_hidden_apply(
        params, inp, tar, cfg, rng, deterministic, return_weights, pad_id
    )
    return _logits(params, x, cfg), attn


def transformer_prefill(
    params: Params,
    tokens: jax.Array,
    enc_out: jax.Array | None,
    cross_mask: jax.Array | None,
    caches: list[dict[str, Any]],
    position: jax.Array | int,
    cfg: ModelConfig,
    cross_kvs: list[tuple[jax.Array, jax.Array]] | None = None,
    chunk: int = 0,
) -> tuple[jax.Array, list[dict[str, Any]]]:
    """Single-pass prompt ingestion: (B, n) tokens at absolute positions
    ``position .. position + n - 1`` -> ((B, vocab) logits for the NEXT
    position, caches holding every prompt position's K/V).

    The serving-side counterpart of ``transformer_decode_step``: where the
    step consumes ONE token per bandwidth-bound call, prefill consumes the
    whole prompt (in ``chunk``-sized pieces when ``chunk > 0``) through the
    teacher-forcing forward — O(n / chunk) MXU-saturating matmuls instead of
    O(n) sequential steps. Only the last position is projected to the vocab,
    so the (B, n, V) logits tensor is never materialized."""
    from transformer_tpu.models.decoder import decoder_prefill

    x_last, new_caches = decoder_prefill(
        params["decoder"], tokens, enc_out, cross_mask, caches, cfg,
        cross_kvs=cross_kvs, start=position, chunk=chunk,
    )
    return _logits(params, x_last[:, None, :], cfg)[:, -1, :], new_caches


def transformer_verify(
    params: Params,
    tokens: jax.Array,
    caches: list[dict[str, Any]],
    position: jax.Array | int,
    cfg: ModelConfig,
) -> tuple[jax.Array, list[dict[str, Any]]]:
    """Speculative-decoding verify forward: (B, W) candidate tokens at
    absolute positions ``position .. position + W - 1`` -> ((B, W, vocab)
    logits for EVERY fed position, updated caches).

    The multi-token sibling of ``transformer_decode_step`` built on the same
    S_q > 1 cache-write path ``transformer_prefill`` uses (offset causal
    mask from ``ops/masks.py``): one matmul-rich forward scores a drafter's
    ``k`` proposals plus the bonus position, instead of ``k + 1``
    bandwidth-bound single-token steps. Where prefill projects only the
    last position (prompt logits are never needed), verify projects ALL
    positions — ``logits[:, j]`` is the next-token distribution after the
    prefix extended by ``tokens[:, :j+1]``, which is exactly what the
    acceptance rule compares against ``tokens[:, j+1]``. W stays small
    (k + 1), so the (B, W, V) tensor never approaches the (B, S, V)
    materialization the chunked-loss path avoids. Rejected candidates roll
    back with ``ops.attention.rollback_cache`` (decoder-only: speculation
    targets the LM serving path)."""
    x, _, new_caches = decoder_apply(
        params["decoder"], tokens, None, None, None, cfg,
        rng=None, deterministic=True, caches=caches,
        position_offset=position,
    )
    return _logits(params, x, cfg), new_caches


def transformer_decode_step(
    params: Params,
    token: jax.Array,
    enc_out: jax.Array | None,
    cross_mask: jax.Array | None,
    caches: list[dict[str, Any]],
    position: jax.Array,
    cfg: ModelConfig,
    cross_kvs: list[tuple[jax.Array, jax.Array]] | None = None,
) -> tuple[jax.Array, list[dict[str, Any]]]:
    """One KV-cached autoregressive step: (B, 1) token -> (B, vocab) next-token
    logits plus updated caches. This replaces the reference's full re-encode +
    re-decode per generated token (``train.py:110``). Pass ``cross_kvs`` from
    ``precompute_cross_kvs`` to avoid re-projecting the encoder output."""
    x, _, new_caches = decoder_apply(
        params["decoder"], token, enc_out, None, cross_mask, cfg,
        rng=None, deterministic=True, caches=caches, cross_kvs=cross_kvs,
        position_offset=position,
    )
    logits = _logits(params, x, cfg)
    return logits[:, -1, :], new_caches
