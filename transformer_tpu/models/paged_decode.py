"""Fused paged decode forward: the batched LM step over pool slots.

The gather twins (``serve/scheduler.py`` ``_pool_step_paged`` /
``_pool_verify_paged``) run decode as ``vmap`` over per-slot batch-1
``transformer_decode_step`` calls against dense VIEWS of the pool — which
forces ``gather_block_views`` to materialize every slot's whole KV working
set in dense order before attention even starts, and leaves each sublayer's
intermediates round-tripping HBM between XLA fusions. This module is the
same step built on the fused kernels instead:

- attention consumes the pool buffers in place through the block table
  (``kernels/paged_flash.paged_flash_attention`` — no gathered view, GQA
  grouping and int8 dequant inside the kernel);
- the dense FFN sublayer runs as one residual+LN+FFN kernel
  (``ops/ffn.fused_ln_ffn`` — the dff-wide intermediate never leaves VMEM);
- everything else (embedding prologue, q/k/v/out projections, RoPE,
  LayerNorms, pool scatter) reuses the exact ops the gather path reaches
  through ``transformer_decode_step``, so the two paths share numerics
  wherever fusion doesn't force a different reduction order.

Write-then-attend: each layer scatters its freshly projected (and, for int8
pools, freshly quantized) K/V rows into the pool FIRST, then attends through
the table — the kernel's pool read hands back exactly the
quantize->dequantize round trip ``_store_kv`` returns on the dense path, so
stored rows and attended values stay bit-identical between paths. The S_q
rows just written are visible to the attention (lengths = index + S_q) with
per-row offset causality inside the kernel, which is what serves both
one-token decode (S_q = 1) and speculative verify (S_q = k + 1).

Layers of several kinds (``cfg.layer_pattern``) run side by side over ONE
pool and one block table: a kind's query heads are its parameters' shape,
its rotary frequencies come from the config, and its window is the
kernel's static band (the loop over a slot's blocks starts at the block
that holds ``length - window``). A dropless expert layer runs inside the
step too (``ops/moe.py moe_apply_dropless``: the grouped kernel reads the
experts the step's tokens hit), free slots routed nowhere; its counts of
picks held here and of experts hit accumulate in the pool pytree
(``MOE_COUNTS``), where the scheduler reads them every few steps. A
short-convolution layer (``ops/short_conv.py``) has no pool: its entry of
the pool pytree is a fixed state row a slot, ``{"conv_state": (N, L - 1,
d_model)}``, which the step reads past position 0 and rolls for the live
slots alone. A delta-rule layer (``ops/kda.py``) has none either: its entry is
the matrix state ``kda_state`` (N, H, D, D) float32 and the three
convolutions' ``kda_conv`` rows; the step runs the projections and the
one-token convolutions in XLA and ``kernels/kda_step.py`` over the states,
in place, the live slots alone. A latent layer (``ops/mla.py``) has a pool of
ONE row a position (``ckv``): the step scatters the new row (its shared key
part turned at the slot's position where the kind rotates), absorbs the
projections into the queries and reads the pool through
``kernels/paged_latent.py``. A state-space layer (``ops/ssd.py``) has no
pool either: its entry is the float32 state ``ssm_state`` (N, H, P, N_s) and
the convolution's ``ssm_conv`` rows; the step runs the projections and the
one-token convolution in XLA and ``kernels/ssm_step.py`` over the states, in
place, the live slots alone. A layer that is a mixer alone or a feed-forward
part alone runs that sublayer alone (its pool entry holds nothing where it
has no mixer).

Scope guards (the gather path remains the general fallback): decoder-only
LM configs, no model-wide ``attention_window`` (that option makes the dense
cache roll, which no paged layout serves), deterministic (dropout-free)
decode. The residual+LN+FFN kernel serves the LayerNorm-and-bias block;
an RMSNorm or bias-free dense FFN and a capacity-dispatch MoE layer keep
the XLA sublayer; their attention still runs fused.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from transformer_tpu.config import ModelConfig
from transformer_tpu.kernels.flash_attention import paged_attention
from transformer_tpu.kernels.kda_step import kda_step
from transformer_tpu.kernels.kv_pool import block_row_ids, scatter_rows
from transformer_tpu.kernels.paged_latent import paged_latent_attention
from transformer_tpu.kernels.ssm_step import ssm_step
from transformer_tpu.models.encoder import (
    _ffn_sublayer_apply,
    _sublayer,
    dropless_moe,
    embed_prologue,
    layer_rope,
)
from transformer_tpu.models.transformer import project_logits
from transformer_tpu.ops.attention import (
    _project,
    _quantize_kv,
    kv_buffer_keys,
    merge_heads,
    normalise_qk,
)
from transformer_tpu.ops.ffn import fused_ln_ffn
from transformer_tpu.ops.kda import kda_inputs, kda_output
from transformer_tpu.ops.mla import absorbed_output, absorbed_queries, latent_position_args, latent_rows
from transformer_tpu.ops.nn import Params, norm_apply
from transformer_tpu.ops.positional import apply_rope
from transformer_tpu.ops.short_conv import short_conv_apply
from transformer_tpu.ops.ssd import ssm_inputs, ssm_output

# Key of the pool pytree (in the first expert layer's dict) under which a
# dropless model's decode steps accumulate int32 [picks held here, experts
# hit, rows of the most-loaded expert, tokens with a pick here, steps]: the
# programs return (logits, pools) and nothing else.
MOE_COUNTS = "moe_counts"


def ssm_pool_step(
    params: Params, h: jax.Array, old: dict[str, jax.Array], live: jax.Array,
    groups: int, epsilon: float, interpret: bool | None = None,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One position of a state-space layer over every slot: (N, 1, M)
    normalised input and the layer's pool entry ``{"ssm_state", "ssm_conv"}``
    -> ((N, 1, M) output, the new entry). As ``kda``: a slot that is not
    ``live`` reads zeros and keeps what it held."""
    z, xs, bmat, cmat, dt, conv = ssm_inputs(
        params, h, jnp.where(live[:, None, None], old["ssm_conv"], 0), groups
    )
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    y, state = ssm_step(
        old["ssm_state"], xs[:, 0], dt[:, 0], a, bmat[:, 0], cmat[:, 0], live, interpret=interpret,
    )
    new = {
        "ssm_state": state,
        "ssm_conv": jnp.where(live[:, None, None], conv.astype(old["ssm_conv"].dtype), old["ssm_conv"]),
    }
    return ssm_output(params, h, y[:, None], xs, z, groups, epsilon), new


def check_paged_flash_config(cfg: ModelConfig) -> None:
    """Reject configs the fused path cannot serve (they keep the gather
    path): the guards are static, so the scheduler validates once at init."""
    if not cfg.decoder_only:
        raise ValueError("paged_flash decode serves decoder-only LM configs")
    if cfg.attention_window:
        raise ValueError(
            "paged_flash decode serves no rolling-window cache "
            "(attention_window); give the window layers an attention kind "
            "(cfg.layer_pattern), or use --decode_kernel xla"
        )


def _scatter_layer_kv(
    pool: dict[str, Any],
    k: jax.Array,
    v: jax.Array,
    rids: jax.Array,
) -> dict[str, Any]:
    """Write (N, S_q, H_kv, D) projections into the pool at flat rows
    ``rids`` — ``_store_kv``'s int8 layout decisions, re-aimed at pool
    scatter (codes AND their fp32 scales land together, so stale scales can
    never pair with fresh codes)."""
    n, s_q = k.shape[:2]

    def flat(t):
        return t.reshape(n * s_q, *t.shape[2:])

    if "k_scale" in pool:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        vals = {"k": flat(kq), "k_scale": flat(ks), "v": flat(vq), "v_scale": flat(vs)}
    else:
        vals = {"k": flat(k.astype(pool["k"].dtype)), "v": flat(v.astype(pool["v"].dtype))}
    return {key: scatter_rows(pool[key], rids, vals[key]) for key in kv_buffer_keys(pool)}


def paged_decode_forward(
    params: Params,
    toks: jax.Array,
    pool_caches: list[dict[str, Any]],
    table: jax.Array,
    index: jax.Array,
    cfg: ModelConfig,
    *,
    block_tokens: int,
    interpret: bool | None = None,
) -> tuple[jax.Array, list[dict[str, Any]]]:
    """One fused decode/verify forward over every pool slot.

    Args:
      params: full transformer params (decoder-only config).
      toks: (N, S_q) int32 token ids — S_q = 1 for plain decode, k + 1 for
        speculative verify (scored causally inside the row).
      pool_caches: per-layer ``init_block_pool`` buffers (a
        short-convolution layer's: its ``conv_state``).
      table: (N, nmax) int32 device block table.
      index: (N,) int32 per-slot positions BEFORE this forward; slot s's
        tokens sit at absolute positions ``index[s] .. index[s] + S_q - 1``.
      block_tokens: pool block size (static).
      interpret: Pallas interpret mode for both kernels (default: off-TPU).

    Returns ((N, S_q, vocab) logits for every fed position, updated pools).
    Free slots (index 0, all-sink tables) produce garbage logits into rows
    the host discards and write only sink rows — same contract as the
    gather twins; a short-convolution layer's state is theirs untouched.
    """
    dec = params["decoder"]
    n, s_q = toks.shape
    index = index.astype(jnp.int32)
    lengths = index + s_q
    rids = block_row_ids(table, index, s_q, block_tokens).reshape(-1)

    # Per-slot batch-1 embed, vmapped — the same call shape the gather path
    # reaches through vmap(transformer_decode_step), so traced-offset
    # handling (sinusoidal slack rows) and numerics line up exactly.
    def embed_one(ids, pos):
        return embed_prologue(dec["embedding"], ids[None], cfg, None, True, pos)[0]

    x = jax.vmap(embed_one)(toks, index)  # (N, S_q, d_model)
    dtype = x.dtype
    # A free slot sits at index 0 and feeds PAD: it is no token.
    is_token = jnp.broadcast_to((index > 0)[:, None], (n, s_q))
    moe_counts = None

    new_pools: list[dict[str, Any]] = []
    for i, layer in enumerate(dec["layers"]):
        pool = pool_caches[i]
        pool_box = [pool]
        rope, window = layer_rope(cfg, i), cfg.layer_kind(i).window

        def self_attn(h, layer=layer, pool_box=pool_box, rope=rope, window=window):
            mp = layer["self_mha"]
            q = _project(mp["query"], h, dtype)
            k = _project(mp["key"], h, dtype)
            v = _project(mp["value"], h, dtype)
            q, k = normalise_qk(mp, q, k, cfg.layernorm_epsilon)
            if rope:
                rot = jax.vmap(
                    lambda t, off: apply_rope(t[None], off + jnp.arange(s_q), **rope)[0]
                )
                q = rot(q, index)
                k = rot(k, index)
            pool = _scatter_layer_kv(pool_box[0], k, v, rids)
            pool_box[0] = pool
            quant = {"k_scale": pool["k_scale"], "v_scale": pool["v_scale"]} if "k_scale" in pool else {}
            out = paged_attention(
                q, pool["k"], pool["v"], table, lengths,
                impl="paged_flash", window=window, interpret=interpret, **quant,
            )
            return merge_heads(mp, out, h)

        def short_conv(h, layer=layer, pool_box=pool_box):
            # A slot past position 0 reads its own state; one at position 0
            # (free, fed PAD) reads zeros and keeps whatever it held.
            live = (index > 0)[:, None, None]
            old = pool_box[0]["conv_state"]
            out, state = short_conv_apply(layer["conv"], h, jnp.where(live, old, 0))
            pool_box[0] = {"conv_state": jnp.where(live, state, old)}
            return out

        def kda(h, layer=layer, pool_box=pool_box):
            # As ``short_conv``: a free slot reads zeros and keeps what it
            # held (the kernel writes a slot that is not live back unchanged).
            if s_q != 1:
                raise ValueError("a delta-rule layer steps one position at a time")
            live = index > 0
            old = pool_box[0]
            q, k, v, g, beta, conv = kda_inputs(
                layer["kda"], h, jnp.where(live[:, None, None], old["kda_conv"], 0)
            )
            o, state = kda_step(
                old["kda_state"], q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], live,
                interpret=interpret,
            )
            pool_box[0] = {
                "kda_state": state,
                "kda_conv": jnp.where(live[:, None, None], conv.astype(old["kda_conv"].dtype), old["kda_conv"]),
            }
            return kda_output(layer["kda"], h, o[:, None], cfg.layernorm_epsilon)

        def latent_attn(h, layer=layer, pool_box=pool_box, kind=cfg.layer_kind(i)):
            if s_q != 1:
                raise ValueError("a latent layer's paged kernel reads one query position a slot")
            mp, eps = layer["mla"], cfg.layernorm_epsilon
            at = latent_position_args(kind)
            # Each slot's row and query turn at the slot's own position.
            positions = index[:, None] + jnp.arange(s_q) if at["rope"] else None
            rows = latent_rows(mp, h, eps, positions, at["rope"]).astype(pool_box[0]["ckv"].dtype)
            pool_box[0] = {"ckv": scatter_rows(pool_box[0]["ckv"], rids, rows[:, 0])}
            ctx = paged_latent_attention(
                absorbed_queries(mp, h, eps, positions, **at)[:, 0], pool_box[0]["ckv"], table, lengths,
                rank=kind.latent_rank, interpret=interpret,
            )
            return absorbed_output(mp, ctx[:, None])

        def state_space(h, layer=layer, pool_box=pool_box, kind=cfg.layer_kind(i)):
            if s_q != 1:
                raise ValueError("a state-space layer steps one position at a time")
            out, pool_box[0] = ssm_pool_step(
                layer["ssm"], h, pool_box[0], index > 0, kind.ssm_groups, cfg.layernorm_epsilon, interpret
            )
            return out

        mixers = {"conv": short_conv, "kda": kda, "mla": latent_attn, "ssm": state_space}
        mixer = next((fn for key, fn in mixers.items() if key in layer), self_attn)
        if "ln1" in layer:
            x = _sublayer(cfg, layer["ln1"], x, mixer, None, True)
        new_pools.append(pool_box[0])

        if "ln_ffn" not in layer:
            continue  # a mixer alone
        if "moe" in layer and cfg.moe_dispatch == "dropless":
            counts_box: list = [None]

            def experts(h, layer=layer, counts_box=counts_box):
                y, counts_box[0] = dropless_moe(layer["moe"], h, cfg, is_token, interpret)
                return y

            x = _sublayer(cfg, layer["ln_ffn"], x, experts, None, True)
            moe_counts = counts_box[0] if moe_counts is None else moe_counts + counts_box[0]
        elif "moe" in layer or cfg.norm != "layernorm" or not cfg.use_bias:
            # Capacity dispatch, and the dense FFN of a block the fused
            # kernel does not express (RMSNorm, no biases): the XLA
            # sublayer; attention above already ran fused.
            aux_box: list = [None]
            x = _sublayer(
                cfg, layer["ln_ffn"], x,
                lambda h, layer=layer, aux_box=aux_box: _ffn_sublayer_apply(
                    layer, h, cfg, aux_box, None
                ),
                None, True,
            )
        else:
            x = fused_ln_ffn(
                layer["ln_ffn"], layer["ffn"], x,
                activation=cfg.ffn_activation,
                norm_scheme=cfg.norm_scheme,
                epsilon=cfg.layernorm_epsilon,
                interpret=interpret,
            )

    for i, old in enumerate(pool_caches):
        if MOE_COUNTS in old:
            step = jnp.concatenate([moe_counts, jnp.ones((1,), jnp.int32)])
            new_pools[i] = dict(new_pools[i], **{MOE_COUNTS: old[MOE_COUNTS] + step})
    if cfg.norm_scheme == "pre":
        x = norm_apply(dec["final_ln"], x, cfg.layernorm_epsilon, cfg.norm)
    return project_logits(params, x, cfg), new_pools
