"""Decoder layer and stack (also serves as the decoder-only causal LM trunk).

Counterpart of the reference's ``Decoder.py``: three post-LN sublayers — masked
self-attention, cross-attention with v=k=encoder output and q=decoder state
(``Decoder.py:29-36``), and FFN — behind the shared embed prologue. Extensions
beyond the reference:

- ``cfg.decoder_only`` drops the cross-attention sublayer entirely
  (BASELINE.json configs[4], the 4096-token causal LM);
- per-layer KV caches make autoregressive decode O(S) instead of the
  reference's O(S²) full re-run per step (``train.py:109-118``);
- causality is passed structurally (``causal=True``) so the flash/ring
  kernels can skip above-diagonal blocks.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from transformer_tpu.config import ModelConfig
from transformer_tpu.ops.attention import init_cache, mha_apply, mha_init
from transformer_tpu.ops.kda import init_kda_state, kda_apply, kda_init
from transformer_tpu.ops.mla import init_latent_cache, mla_apply, mla_init
from transformer_tpu.ops.short_conv import (
    init_conv_state,
    short_conv_apply,
    short_conv_init,
    state_buffer_keys,
)
from transformer_tpu.ops.nn import (
    Params,
    embedding_init,
    norm_apply,
    norm_init,
    remat_layer,
)
from transformer_tpu.models.encoder import (
    _ffn_sublayer_apply,
    _ffn_sublayer_init,
    _sublayer,
    _token_mask_from,
    attention_init,
    embed_prologue,
    layer_rope,
    layer_uses_moe,
)


def decoder_layer_init(
    key: jax.Array, cfg: ModelConfig, layer_index: int = 0
) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    kind = cfg.layer_kind(layer_index)
    # The layer's mixer: self-attention, or one of the three in its place.
    if kind.mixer == "conv":
        mixer = {"conv": short_conv_init(k1, cfg.d_model, kind.conv_kernel, cfg.params_dtype)}
    elif kind.mixer == "kda":
        mixer = {"kda": kda_init(
            k1, cfg.d_model, kind.kda_heads, kind.kda_head_dim, kind.kda_conv_kernel,
            kind.kda_gate_rank, cfg.params_dtype,
        )}
    elif kind.mixer == "mla":
        mixer = {"mla": mla_init(
            k1, cfg.d_model, kind.num_heads, kind.latent_rank, kind.latent_nope_dim,
            kind.latent_shared_dim, kind.latent_value_dim, cfg.params_dtype,
            query_scale=kind.latent_query_init_scale,
        )}
    else:
        mixer = {"self_mha": attention_init(k1, cfg, layer_index)}
    params: Params = {
        **mixer,
        **_ffn_sublayer_init(k3, cfg, layer_uses_moe(cfg, layer_index)),
        "ln1": norm_init(cfg.d_model, cfg.params_dtype, cfg.norm),
        "ln_ffn": norm_init(cfg.d_model, cfg.params_dtype, cfg.norm),
    }
    if not cfg.decoder_only:
        params["cross_mha"] = mha_init(
            k2, cfg.d_model, cfg.num_heads, cfg.params_dtype,
            num_kv_heads=cfg.kv_heads, head_dim=cfg.head_dim, use_bias=cfg.use_bias,
        )
        params["ln2"] = norm_init(cfg.d_model, cfg.params_dtype, cfg.norm)
    return params


def decoder_layer_apply(
    params: Params,
    x: jax.Array,
    enc_out: jax.Array | None,
    self_mask: jax.Array | None,
    cross_mask: jax.Array | None,
    cfg: ModelConfig,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    return_weights: bool = False,
    cache: dict[str, Any] | None = None,
    cross_kv: tuple[jax.Array, jax.Array] | None = None,
    layer_index: int = 0,
) -> tuple[
    jax.Array, jax.Array | None, jax.Array | None, dict[str, Any] | None, jax.Array | None
]:
    """Returns (x, self_attn_weights, cross_attn_weights, updated_cache,
    moe_aux_loss) — the aux loss is None for dense-FFN layers (see
    ``encoder_layer_apply``).

    ``cross_kv`` optionally carries this layer's pre-projected encoder K/V so
    decode steps don't re-project the static encoder output every token.
    ``layer_index`` (static) picks the layer's attention kind where the
    model's layers differ: its window and its rotary frequencies (its heads
    are the parameters' shape). A stateful layer's cache is its state and
    ``index`` (``{"conv_state"}``; a delta-rule layer's ``{"kda_state",
    "kda_conv"}``); it reads the state only past position 0, so whatever an
    earlier sequence left in it is never seen. A latent layer's is
    ``{"ckv", "index"}``. The mixer is picked by the parameters' key.
    """
    r1, r2, r3 = (None, None, None) if rng is None else jax.random.split(rng, 3)
    boxes: list[Any] = [None, None, None]
    aux_box: list = [None]

    def short_conv(h):
        state = None
        if cache is not None:
            state = jnp.where(cache["index"] > 0, cache["conv_state"], 0)
        out, state = short_conv_apply(params["conv"], h, state)
        if cache is not None:
            boxes[2] = {"conv_state": state, "index": cache["index"] + h.shape[1]}
        return out

    def kda(h):
        state = None
        if cache is not None:
            state = {
                key: jnp.where(cache["index"] > 0, cache[key], 0)
                for key in state_buffer_keys(cache)
            }
        out, state = kda_apply(params["kda"], h, state, cfg.layernorm_epsilon)
        if cache is not None:
            boxes[2] = {
                **{key: state[key].astype(cache[key].dtype) for key in state},
                "index": cache["index"] + h.shape[1],
            }
        return out

    def latent_attn(h):
        out, boxes[2] = mla_apply(params["mla"], h, cache, cfg.layernorm_epsilon)
        return out

    def self_attn(h):
        out, w, new_cache = mha_apply(
            params["self_mha"], h, h, self_mask,
            impl=cfg.attention_impl,
            causal=cache is None,  # cache path builds its own prefix mask
            window=cfg.layer_kind(layer_index).window,
            return_weights=return_weights,
            cache=cache,
            flash_block_q=cfg.flash_block_q,
            flash_block_k=cfg.flash_block_k,
            rope=layer_rope(cfg, layer_index),
            qk_norm_epsilon=cfg.layernorm_epsilon,
        )
        boxes[0], boxes[2] = w, new_cache
        return out

    mixers = {"conv": short_conv, "kda": kda, "mla": latent_attn}
    mixer = next((fn for key, fn in mixers.items() if key in params), self_attn)
    x = _sublayer(cfg, params["ln1"], x, mixer, r1, deterministic)

    if not cfg.decoder_only:
        if enc_out is None:
            raise ValueError("encoder output required unless cfg.decoder_only")

        def cross_attn(h):
            # q = decoder state, k = v = encoder output (reference ``Decoder.py:33-36``).
            out, w, _ = mha_apply(
                params["cross_mha"], h, enc_out, cross_mask,
                return_weights=return_weights,
                precomputed_kv=cross_kv,
            )
            boxes[1] = w
            return out

        x = _sublayer(cfg, params["ln2"], x, cross_attn, r2, deterministic)

    x = _sublayer(
        cfg, params["ln_ffn"], x,
        lambda h: _ffn_sublayer_apply(
            params, h, cfg, aux_box, _token_mask_from(self_mask)
        ),
        r3, deterministic,
    )
    return x, boxes[0], boxes[1], boxes[2], aux_box[0]


def decoder_init(key: jax.Array, cfg: ModelConfig, embedding: Params | None = None) -> Params:
    """``embedding`` may be a shared table (``cfg.tie_embeddings``) — the pytree
    then simply references the same arrays; jit dedups the constant."""
    keys = jax.random.split(key, cfg.num_layers + 1)
    params: Params = {
        "embedding": embedding
        if embedding is not None
        else embedding_init(keys[0], cfg.target_vocab_size, cfg.d_model, cfg.params_dtype),
        "layers": [decoder_layer_init(keys[i + 1], cfg, i) for i in range(cfg.num_layers)],
    }
    if cfg.norm_scheme == "pre":
        params["final_ln"] = norm_init(cfg.d_model, cfg.params_dtype, cfg.norm)
    return params


def decoder_apply(
    params: Params,
    ids: jax.Array,
    enc_out: jax.Array | None,
    self_mask: jax.Array | None,
    cross_mask: jax.Array | None,
    cfg: ModelConfig,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    return_weights: bool = False,
    caches: list[dict[str, Any]] | None = None,
    cross_kvs: list[tuple[jax.Array, jax.Array]] | None = None,
    position_offset: jax.Array | int = 0,
) -> tuple[jax.Array, dict[str, jax.Array], list[dict[str, Any]] | None]:
    """(B, S) ids -> (B, S, d_model). Attention maps are keyed
    ``decoder_layer{i}_block{1,2}`` for parity with the reference's dict
    (``Decoder.py:75-76``)."""
    rngs = (
        [None] * (cfg.num_layers + 1)
        if rng is None
        else list(jax.random.split(rng, cfg.num_layers + 1))
    )
    x = embed_prologue(
        params["embedding"], ids, cfg, rngs[0], deterministic, position_offset
    )
    attn_weights: dict[str, jax.Array] = {}
    new_caches: list[dict[str, Any]] | None = [] if caches is not None else None
    aux_total = None

    def call_for(layer_index):
        def layer_call(layer, x, enc_out, self_mask, cross_mask, r, cache, cross_kv):
            return decoder_layer_apply(
                layer, x, enc_out, self_mask, cross_mask, cfg,
                r, deterministic, return_weights, cache=cache, cross_kv=cross_kv,
                layer_index=layer_index,
            )

        if cfg.remat and caches is None:
            # Training-time only (decode's KV-cache path gains nothing from
            # recomputation); see cfg.remat docstring.
            return remat_layer(layer_call, cfg)
        return layer_call

    # One call per attention kind: layers of one kind share it.
    period = len(cfg.layer_pattern) or 1
    calls = [call_for(i) for i in range(min(period, cfg.num_layers))]
    for i, layer in enumerate(params["layers"]):
        x, w1, w2, new_cache, aux = calls[i % period](
            layer, x, enc_out, self_mask, cross_mask, rngs[i + 1],
            None if caches is None else caches[i],
            None if cross_kvs is None else cross_kvs[i],
        )
        if w1 is not None:
            attn_weights[f"decoder_layer{i + 1}_block1"] = w1
        if w2 is not None:
            attn_weights[f"decoder_layer{i + 1}_block2"] = w2
        if aux is not None:
            aux_total = aux if aux_total is None else aux_total + aux
        if new_caches is not None:
            new_caches.append(new_cache)
    if aux_total is not None:
        attn_weights["moe_aux_decoder"] = aux_total
    if cfg.norm_scheme == "pre":
        x = norm_apply(params["final_ln"], x, cfg.layernorm_epsilon, cfg.norm)
    return x, attn_weights, new_caches


def decoder_prefill(
    params: Params,
    tokens: jax.Array,
    enc_out: jax.Array | None,
    cross_mask: jax.Array | None,
    caches: list[dict[str, Any]],
    cfg: ModelConfig,
    cross_kvs: list[tuple[jax.Array, jax.Array]] | None = None,
    start: jax.Array | int = 0,
    chunk: int = 0,
) -> tuple[jax.Array, list[dict[str, Any]]]:
    """Single-pass teacher-forced prefill: run ``tokens`` (B, n) — sitting at
    absolute positions ``start .. start + n - 1`` — through the full decoder
    forward, writing every position's K/V into ``caches`` (the cache write
    API accepts S_q > 1; ``ops/attention.py`` builds the offset causal mask
    of a chunk attending into the cached prefix). Returns ((B, d_model)
    hidden state of the LAST position, updated caches).

    ``chunk > 0`` splits the pass into ceil(n / chunk) forward calls so
    activation memory stays bounded at long prompt lengths — the compiled
    program is O(n / chunk) matmul-rich forwards, never O(n) sequential
    decode steps. Rolling-window caches cap the chunk at the window buffer
    length (an attention-layer invariant — see ``mha_apply``)."""
    n = tokens.shape[1]
    if n < 1:
        raise ValueError(f"prefill needs at least one token, got {n}")
    chunk = chunk if chunk > 0 else n  # <= 0 = whole pass in one chunk
    if caches and "rolling" in caches[0]:
        chunk = min(chunk, caches[0]["k"].shape[1])
    x_last = None
    for off in range(0, n, chunk):
        width = min(chunk, n - off)
        x, _, caches = decoder_apply(
            params, jax.lax.slice_in_dim(tokens, off, off + width, axis=1),
            enc_out, None, cross_mask, cfg,
            rng=None, deterministic=True, caches=caches, cross_kvs=cross_kvs,
            position_offset=start + off,
        )
        x_last = x[:, -1, :]
    return x_last, caches


def init_decoder_caches(
    cfg: ModelConfig, batch_size: int, max_len: int
) -> list[dict[str, Any]]:
    """One self-attention KV cache per decoder layer (int8-quantized when
    ``cfg.kv_cache_int8``; a rolling O(window) buffer when
    ``cfg.attention_window``); for a latent layer, its cache of latent rows;
    for a stateful layer (a short convolution, a delta-rule layer), its state
    and the position it stands at. Caches start at position 0; fill the
    prompt in one pass with ``decoder_prefill`` and decode incrementally from
    there (``transformer_decode_step``)."""
    def one(i):
        kind = cfg.layer_kind(i)
        if kind.mixer in ("conv", "kda"):
            return {
                **init_layer_state(cfg, i, batch_size),
                "index": jnp.array(0, dtype=jnp.int32),
            }
        if kind.mixer == "mla":
            return init_latent_cache(
                batch_size, max_len, kind.latent_rank, kind.latent_shared_dim,
                cfg.compute_dtype,
            )
        return init_cache(
            batch_size, max_len, cfg.kv_heads, cfg.head_dim,
            cfg.compute_dtype, quantize=cfg.kv_cache_int8,
            window=cfg.attention_window,
        )

    return [one(i) for i in range(cfg.num_layers)]


def init_layer_state(cfg: ModelConfig, layer_index: int, batch_size: int) -> dict[str, Any]:
    """The state before position 0 of a layer that keeps a fixed state a
    sequence (``cfg.state_layers``), ``batch_size`` sequences (or pool slots)
    of it, under the keys ``ops.short_conv.state_buffer_keys`` lists."""
    kind = cfg.layer_kind(layer_index)
    if kind.mixer == "kda":
        return init_kda_state(
            batch_size, kind.kda_heads, kind.kda_head_dim, kind.kda_conv_kernel,
            cfg.compute_dtype,
        )
    return {
        "conv_state": init_conv_state(
            batch_size, cfg.d_model, kind.conv_kernel, cfg.compute_dtype
        )
    }


def precompute_cross_kvs(
    params: Params, enc_out: jax.Array, cfg: ModelConfig
) -> list[tuple[jax.Array, jax.Array]]:
    """Project the (static) encoder output through every layer's cross-attention
    K/V kernels once, so autoregressive decode attends against cached tensors
    instead of re-projecting per generated token."""
    from transformer_tpu.ops.attention import project_kv

    return [
        project_kv(layer["cross_mha"], enc_out, cfg.compute_dtype)
        for layer in params["layers"]
    ]
