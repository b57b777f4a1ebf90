"""Encoder layer and stack.

Counterpart of the reference's ``Encoder.py``: a post-LN residual block
(``LN(x + Drop(MHA(x)))`` then ``LN(h + Drop(FFN(h)))``, ``Encoder.py:19-29``)
stacked N deep behind an embed/scale/posenc/dropout prologue
(``Encoder.py:48-60``). Differences by design:

- optional pre-LN wiring (``norm_scheme="pre"``) for deep/long-context configs;
- the positional table is sized by ``max_position``, not vocab size
  (fixes SURVEY.md §2.3.5);
- dropout threads an explicit rng and a static ``deterministic`` flag instead
  of Keras's stateful ``training=`` mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from transformer_tpu.config import ModelConfig
from transformer_tpu.ops.attention import mha_apply, mha_init
from transformer_tpu.ops.ffn import ffn_apply, ffn_init
from transformer_tpu.ops.moe import moe_apply, moe_apply_dropless, moe_init
from transformer_tpu.ops.nn import (
    Params,
    dropout,
    embedding_init,
    embedding_lookup,
    norm_apply,
    norm_init,
    remat_layer,
)
from transformer_tpu.ops.positional import kind_rope, sinusoidal_positional_encoding


def layer_uses_moe(cfg: ModelConfig, layer_index: int) -> bool:
    """Whether layer ``layer_index`` (0-based) carries a MoE FFN: every
    ``moe_every``-th layer counting from the top of the cadence (GShard
    alternates, Switch uses every layer — ``cfg.moe_every`` choses), after
    the ``cfg.moe_leading_dense`` layers that keep the dense FFN."""
    return (
        cfg.moe_experts > 0
        and layer_index >= cfg.moe_leading_dense
        and (layer_index + 1) % cfg.moe_every == 0
    )


def attention_init(key: jax.Array, cfg: ModelConfig, layer_index: int) -> Params:
    """Self-attention parameters of layer ``layer_index``: its kind's heads."""
    return mha_init(
        key, cfg.d_model, cfg.layer_kind(layer_index).num_heads, cfg.params_dtype,
        num_kv_heads=cfg.kv_heads, head_dim=cfg.head_dim, use_bias=cfg.use_bias,
        gate=cfg.attention_gate == "per_head", qk_norm=cfg.qk_norm,
    )


def layer_rope(cfg: ModelConfig, layer_index: int) -> bool | dict:
    """``mha_apply``'s ``rope`` for layer ``layer_index``: off, or
    ``apply_rope``'s arguments for its kind (base, rotated share, YaRN; for a
    model of one kind, the plain rotation at base 10,000)."""
    return cfg.position_scheme == "rope" and kind_rope(cfg.layer_kind(layer_index))


def _ffn_sublayer_init(key: jax.Array, cfg: ModelConfig, use_moe: bool) -> dict:
    if use_moe:
        return {
            "moe": moe_init(
                key, cfg.d_model, cfg.moe_dff or cfg.dff, cfg.moe_experts,
                cfg.params_dtype, experts_held=cfg.moe_experts_held,
                activation=cfg.ffn_activation, shared_dff=cfg.moe_shared_dff,
                router_scale=cfg.moe_router_init_scale, out_scale=cfg.moe_out_init_scale,
                select_bias=cfg.moe_select_bias,
            )
        }
    return {
        "ffn": ffn_init(
            key, cfg.d_model, cfg.dff, cfg.params_dtype,
            activation=cfg.ffn_activation, use_bias=cfg.use_bias,
        )
    }


def _token_mask_from(mask: jax.Array | None) -> jax.Array | None:
    """(B|1, 1, 1, S) key-padding attention mask -> (B|1, S) token mask for
    MoE routing; any other mask shape (combined/causal) carries no usable
    per-token padding info, so routing treats all tokens as real."""
    if mask is not None and mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[-2] == 1:
        return mask[:, 0, 0, :]
    return None


def _ffn_sublayer_apply(
    params: Params,
    h: jax.Array,
    cfg: ModelConfig,
    aux_box: list,
    token_mask: jax.Array | None = None,
):
    """Dense or MoE FFN, depending on which key the layer params carry; a MoE
    layer's load-balance loss lands in ``aux_box[0]``."""
    if "moe" in params and cfg.moe_dispatch == "dropless":
        return dropless_moe(params["moe"], h, cfg, token_mask)[0]
    if "moe" in params:
        y, aux = moe_apply(
            params["moe"], h,
            num_experts=cfg.moe_experts,
            top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor,
            activation=cfg.ffn_activation,
            token_mask=token_mask,
        )
        aux_box[0] = aux
        return y
    return ffn_apply(params["ffn"], h, cfg.ffn_activation)


def dropless_moe(moe_params: Params, h: jax.Array, cfg: ModelConfig, token_mask, interpret=None):
    """``moe_apply_dropless`` with the model's routing constants."""
    return moe_apply_dropless(
        moe_params, h,
        num_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
        expert_offset=cfg.moe_expert_offset, routed_scale=cfg.moe_routed_scale,
        score=cfg.moe_score, renorm_epsilon=cfg.moe_renorm_epsilon,
        activation=cfg.ffn_activation, token_mask=token_mask, interpret=interpret,
    )


def encoder_layer_init(
    key: jax.Array, cfg: ModelConfig, layer_index: int = 0
) -> Params:
    k_mha, k_ffn = jax.random.split(key)
    return {
        "mha": attention_init(k_mha, cfg, layer_index),
        **_ffn_sublayer_init(k_ffn, cfg, layer_uses_moe(cfg, layer_index)),
        "ln1": norm_init(cfg.d_model, cfg.params_dtype, cfg.norm),
        "ln2": norm_init(cfg.d_model, cfg.params_dtype, cfg.norm),
    }


def _sublayer(cfg: ModelConfig, params_ln, x, fn, rng, deterministic):
    """Residual sublayer in post-LN (reference wiring) or pre-LN form."""
    if cfg.norm_scheme == "pre":
        y = fn(norm_apply(params_ln, x, cfg.layernorm_epsilon, cfg.norm))
        y = dropout(rng, y, cfg.dropout_rate, deterministic)
        return x + y
    y = fn(x)
    y = dropout(rng, y, cfg.dropout_rate, deterministic)
    return norm_apply(params_ln, x + y, cfg.layernorm_epsilon, cfg.norm)


def encoder_layer_apply(
    params: Params,
    x: jax.Array,
    mask: jax.Array | None,
    cfg: ModelConfig,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    return_weights: bool = False,
) -> tuple[jax.Array, jax.Array | None, jax.Array | None]:
    """Returns (x, attn_weights, moe_aux_loss) — the aux loss is None for
    dense-FFN layers and a scalar for MoE layers; returning it (rather than
    side-channeling) keeps it correct under ``jax.checkpoint``."""
    r1, r2 = (None, None) if rng is None else jax.random.split(rng)
    weights_box = [None]
    aux_box: list = [None]

    def attn(h):
        out, w, _ = mha_apply(
            params["mha"], h, h, mask,
            impl=cfg.attention_impl,
            return_weights=return_weights,
            flash_block_q=cfg.flash_block_q,
            flash_block_k=cfg.flash_block_k,
            rope=cfg.position_scheme == "rope",
        )
        weights_box[0] = w
        return out

    x = _sublayer(cfg, params["ln1"], x, attn, r1, deterministic)
    x = _sublayer(
        cfg, params["ln2"], x,
        lambda h: _ffn_sublayer_apply(params, h, cfg, aux_box, _token_mask_from(mask)),
        r2, deterministic,
    )
    return x, weights_box[0], aux_box[0]


def encoder_init(key: jax.Array, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, cfg.num_layers + 1)
    params: Params = {
        "embedding": embedding_init(keys[0], cfg.input_vocab_size, cfg.d_model, cfg.params_dtype),
        "layers": [encoder_layer_init(keys[i + 1], cfg, i) for i in range(cfg.num_layers)],
    }
    if cfg.norm_scheme == "pre":
        params["final_ln"] = norm_init(cfg.d_model, cfg.params_dtype, cfg.norm)
    return params


def embed_prologue(
    embedding: Params,
    ids: jax.Array,
    cfg: ModelConfig,
    rng: jax.Array | None,
    deterministic: bool,
    position_offset: jax.Array | int = 0,
) -> jax.Array:
    """Shared embed → ×√d_model → +posenc → dropout prologue
    (reference ``Encoder.py:51-55`` / ``Decoder.py:65-69``). ``position_offset``
    supports KV-cache decode, where the current token sits at a nonzero
    absolute position."""
    seq_len = ids.shape[1]
    if seq_len > cfg.max_position:
        raise ValueError(
            f"sequence length {seq_len} exceeds cfg.max_position "
            f"{cfg.max_position}; raise max_position to size the positional table"
        )
    x = embedding_lookup(embedding, ids, cfg.compute_dtype)
    x = x * jnp.asarray(cfg.d_model**0.5, dtype=cfg.compute_dtype)
    if cfg.position_scheme == "sinusoidal":
        # TRACED offsets (KV-cache decode, incl. speculative verify) get
        # seq_len rows of slack beyond max_position: a verify row whose
        # lookahead tokens straddle the position budget must NOT trigger
        # dynamic_slice's start-clamping, which would silently shift the
        # positions of the row's in-budget tokens (whose picks ARE
        # consumed). Static offsets (training and prefill forwards — the
        # wide, constant-heavy programs) provably stay in-bounds, so they
        # keep the exact max_position table instead of constant-folding an
        # up-to-2x-larger one into every compiled program. The sinusoid is
        # computed, so in-range rows are identical either way.
        slack = 0 if isinstance(position_offset, (int, np.integer)) else seq_len
        table = sinusoidal_positional_encoding(
            cfg.max_position + slack, cfg.d_model, cfg.compute_dtype
        )
        pos = jax.lax.dynamic_slice_in_dim(table, position_offset, seq_len, axis=0)
        x = x + pos[None, :, :]
    # "rope": nothing additive here — positions enter via q/k rotation inside
    # self-attention (ops/attention.py mha_apply).
    return dropout(rng, x, cfg.dropout_rate, deterministic)


def encoder_apply(
    params: Params,
    ids: jax.Array,
    mask: jax.Array | None,
    cfg: ModelConfig,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    return_weights: bool = False,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """(B, S) ids -> (B, S, d_model) encodings plus (optionally) per-layer
    attention maps keyed like the reference's dict (``Decoder.py:75-76`` style).
    MoE configs additionally report the summed load-balance loss under the
    reserved key ``"moe_aux_encoder"`` in the weights dict."""
    rngs = (
        [None] * (cfg.num_layers + 1)
        if rng is None
        else list(jax.random.split(rng, cfg.num_layers + 1))
    )
    x = embed_prologue(params["embedding"], ids, cfg, rngs[0], deterministic)
    attn_weights: dict[str, jax.Array] = {}
    aux_total = None

    def layer_call(layer, x, mask, r):
        return encoder_layer_apply(
            layer, x, mask, cfg, r, deterministic, return_weights
        )

    if cfg.remat:
        # Long-context lever: recompute each layer's activations in the
        # backward pass instead of keeping them live (cfg.remat docstring).
        layer_call = remat_layer(layer_call, cfg)
    for i, layer in enumerate(params["layers"]):
        x, w, aux = layer_call(layer, x, mask, rngs[i + 1])
        if w is not None:
            attn_weights[f"encoder_layer{i + 1}"] = w
        if aux is not None:
            aux_total = aux if aux_total is None else aux_total + aux
    if aux_total is not None:
        attn_weights["moe_aux_encoder"] = aux_total
    if cfg.norm_scheme == "pre":
        x = norm_apply(params["final_ln"], x, cfg.layernorm_epsilon, cfg.norm)
    return x, attn_weights
