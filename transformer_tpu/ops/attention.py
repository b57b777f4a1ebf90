"""Scaled dot-product attention and multi-head attention.

The TPU-native counterpart of the reference's ``Attention.py``:

- ``scaled_dot_product_attention`` (``Attention.py:3-34``) becomes
  ``dot_product_attention``: two einsums around an fp32 softmax, with the mask
  applied as an additive bias. XLA fuses the scale/bias/softmax chain; the
  matmuls land on the MXU.
- ``MultiHeadAttention`` (``Attention.py:36-78``) becomes ``mha_init`` /
  ``mha_apply`` over a parameter pytree. Instead of the reference's four
  ``d_model -> d_model`` Dense layers plus reshape/transpose
  (``Attention.py:46-57``), projections map directly ``d_model -> (heads,
  head_dim)`` via one einsum — no transposes in the hot path, and the ``heads``
  axis is a real array axis that tensor parallelism shards on the ``model``
  mesh axis.

Activation layout is (batch, seq, heads, head_dim) throughout.

Call convention: ``mha_apply(params, x_q, x_kv, mask)`` — query input first.
(The reference's positional order is ``(v, k, q, mask)``, ``Attention.py:59``;
self-attention calls are unaffected, cross-attention callers must pass
query=decoder state, kv=encoder output.)
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from transformer_tpu.ops.masks import attention_bias
from transformer_tpu.ops.nn import Params, glorot_uniform


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None = None,
    return_weights: bool = False,
) -> tuple[jax.Array, jax.Array | None]:
    """softmax(q·kᵀ/√d + bias)·v for (B, S, H, D) queries.

    Matches the math of reference ``Attention.py:20-32``. The softmax runs in
    fp32 even when inputs are bf16 — exp/sum in bf16 loses enough precision to
    move BLEU. Returns ``(output, weights)`` where ``weights`` is the
    (B, H, S_q, S_k) attention map when ``return_weights`` else None (the
    reference always returns it, ``Attention.py:32-34``; here it is opt-in so
    training never materializes the (B,H,S,S) tensor twice).

    Grouped-query / multi-query attention (Shazeer 2019, "One Write-Head is
    All You Need"): ``k``/``v`` may carry FEWER heads (B, S_k, H_kv, D) with
    ``H % H_kv == 0`` — each kv head serves a group of ``H/H_kv`` query
    heads. The contraction runs grouped (no materialized kv repeat).
    """
    head_dim = q.shape[-1]
    scale = head_dim**-0.5
    H, Hkv = q.shape[2], k.shape[2]
    if H == Hkv:
        # (B, S_q, H, D) x (B, S_k, H, D) -> (B, H, S_q, S_k)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        if mask is not None:
            logits = logits + attention_bias(mask, dtype=jnp.float32)
        weights = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(q.dtype), v)
        return out, (weights if return_weights else None)

    if H % Hkv:
        raise ValueError(f"query heads {H} must be a multiple of kv heads {Hkv}")
    G = H // Hkv
    B, Sq = q.shape[:2]
    qg = q.reshape(B, Sq, Hkv, G, head_dim)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
    if mask is not None:
        bias = attention_bias(mask, dtype=jnp.float32)  # (B|1, H|1, S_q|1, S_k)
        if bias.shape[1] != 1:
            raise ValueError(
                "per-head masks are unsupported with grouped kv heads"
            )
        logits = logits + bias[:, :, None]  # broadcast over (kv-head, group)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", weights.astype(q.dtype), v)
    out = out.reshape(B, Sq, H, head_dim)
    full_w = (
        weights.reshape(B, H, *weights.shape[3:]) if return_weights else None
    )
    return out, full_w


def mha_init(
    key: jax.Array,
    d_model: int,
    num_heads: int,
    param_dtype=jnp.float32,
    num_kv_heads: int | None = None,
    head_dim: int | None = None,
    use_bias: bool = True,
    gate: bool = False,
    qk_norm: bool = False,
) -> Params:
    """Parameters for multi-head attention: q/k/v projections shaped
    (d_model, heads, head_dim) and an output projection (heads, head_dim,
    d_model). Same parameter count as the reference's four Dense layers
    (``Attention.py:46-50``) — just pre-split by head.

    ``num_kv_heads < num_heads`` gives grouped-query/multi-query attention:
    k/v kernels carry only (d_model, kv_heads, head_dim) — fewer parameters
    and an ``H/H_kv``-times smaller decode KV cache.

    ``head_dim`` is ``d_model / num_heads`` unless given (a model whose heads
    are wider than that: the out projection is then (H, D, d_model) with
    ``H * D != d_model``). ``use_bias=False`` leaves the four biases out;
    ``gate`` adds the (d_model, H) kernel of a per-head output gate;
    ``qk_norm`` the two (head_dim,) scales of ``normalise_qk``."""
    head_dim = head_dim or d_model // num_heads
    kv_heads = num_kv_heads or num_heads
    kq, kk, kv, ko = jax.random.split(key, 4)

    def proj(k, heads):
        fan_out = heads * head_dim
        w = glorot_uniform(k, (d_model, fan_out), param_dtype, d_model, fan_out)
        return w.reshape(d_model, heads, head_dim)

    width = num_heads * head_dim
    params = {
        "query": {"kernel": proj(kq, num_heads)},
        "key": {"kernel": proj(kk, kv_heads)},
        "value": {"kernel": proj(kv, kv_heads)},
        "out": {
            "kernel": glorot_uniform(ko, (d_model, width), param_dtype, width, d_model)
            .reshape(d_model, num_heads, head_dim)
            .transpose(1, 2, 0),
        },
    }
    if use_bias:
        for name, heads in (("query", num_heads), ("key", kv_heads), ("value", kv_heads)):
            params[name]["bias"] = jnp.zeros((heads, head_dim), param_dtype)
        params["out"]["bias"] = jnp.zeros((d_model,), param_dtype)
    if gate:
        params["gate"] = {
            "kernel": glorot_uniform(
                jax.random.fold_in(key, 4), (d_model, num_heads), param_dtype, d_model, num_heads
            )
        }
    if qk_norm:
        params["q_norm"] = {"scale": jnp.ones((head_dim,), param_dtype)}
        params["k_norm"] = {"scale": jnp.ones((head_dim,), param_dtype)}
    return params


def _project(p: Params, x: jax.Array, dtype) -> jax.Array:
    # (B, S, M) @ (M, H, D) -> (B, S, H, D)
    y = jnp.einsum("bsm,mhd->bshd", x.astype(dtype), p["kernel"].astype(dtype))
    return y + p["bias"].astype(dtype) if "bias" in p else y


def normalise_qk(
    params: Params, q: jax.Array, k: jax.Array, epsilon: float
) -> tuple[jax.Array, jax.Array]:
    """RMSNorm over each head's channels of (B, S, H, D) q and k, each with
    its own scale, where the layer has them (``mha_init(qk_norm=True)``):
    before the rotation, so the cache holds k normalised and rotated."""
    if "q_norm" not in params:
        return q, k
    from transformer_tpu.ops.nn import norm_apply

    return (
        norm_apply(params["q_norm"], q, epsilon, "rmsnorm"),
        norm_apply(params["k_norm"], k, epsilon, "rmsnorm"),
    )


def merge_heads(params: Params, out: jax.Array, x_q: jax.Array) -> jax.Array:
    """(B, S, H, D) head outputs -> (B, S, d_model): each head multiplied by
    its sigmoid gate where the layer has one (computed from the sublayer's
    own input ``x_q``; arXiv:2505.06708's head-wise output gate), then the
    out projection."""
    dtype = out.dtype
    if "gate" in params:
        g = jnp.einsum("bsm,mh->bsh", x_q.astype(dtype), params["gate"]["kernel"].astype(dtype))
        out = out * jax.nn.sigmoid(g.astype(jnp.float32)).astype(dtype)[..., None]
    y = jnp.einsum("bshd,hdm->bsm", out, params["out"]["kernel"].astype(dtype))
    return y + params["out"]["bias"].astype(dtype) if "bias" in params["out"] else y


def project_kv(params: Params, x_kv: jax.Array, dtype=None) -> tuple[jax.Array, jax.Array]:
    """Project key/value inputs once, for reuse across decode steps via
    ``mha_apply(..., precomputed_kv=...)``."""
    dtype = dtype or x_kv.dtype
    return _project(params["key"], x_kv, dtype), _project(params["value"], x_kv, dtype)


def _kv_padding_mask(mask: jax.Array | None, impl: str) -> jax.Array | None:
    """Blockwise kernels (flash/ring/ulysses) take key-padding only: squeeze a
    broadcastable (B|1, 1, 1, S_k) allowed-mask to (B|1, S_k), or reject."""
    if mask is None:
        return None
    if mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[-2] == 1:
        return mask[:, 0, 0, :]
    raise ValueError(
        f"attention_impl={impl!r} takes a key-padding mask (B, 1, 1, S_k) "
        f"plus the structural causal flag; got a mask of shape {mask.shape}. "
        "Per-head masks are unsupported, and causality must be passed as "
        "causal=True, not folded into the mask."
    )


def mha_apply(
    params: Params,
    x_q: jax.Array,
    x_kv: jax.Array,
    mask: jax.Array | None = None,
    *,
    impl: str = "xla",
    causal: bool = False,
    window: int = 0,
    return_weights: bool = False,
    cache: dict[str, Any] | None = None,
    precomputed_kv: tuple[jax.Array, jax.Array] | None = None,
    flash_block_q: int = 128,
    flash_block_k: int = 128,
    rope: bool | dict = False,
    qk_norm_epsilon: float = 1e-6,
) -> tuple[jax.Array, jax.Array | None, dict[str, Any] | None]:
    """Multi-head attention forward.

    Args:
      params: pytree from ``mha_init``.
      x_q: (B, S_q, d_model) query-side input.
      x_kv: (B, S_k, d_model) key/value-side input (same as ``x_q`` for
        self-attention; encoder output for cross-attention).
      mask: broadcastable bool allowed-mask (B|1, 1|H, S_q|1, S_k).
      impl: "xla" | "flash" (Pallas blockwise kernel; no attention-weight
        output).
      causal: enforce causality; ANDed with any provided ``mask``.
      window: causal sliding window (needs ``causal`` — or a cache, whose
        prefix mask is causal by construction): each position attends only
        the last ``window`` positions. 0 = unbounded. Supported on every
        impl: banded mask under "xla", static band-tile skip under "flash",
        per-hop band with early ring stop under "ring", and a band in the
        per-device flash call under "ulysses"
        (tests/test_sequence_parallel.py::test_window pins the parallel
        impls against the single-device oracle).
      cache: optional decode KV cache ``{"k","v","index"}`` from
        ``init_cache``. Full-length cache (k/v shaped (B, max_len, H, D)):
        S_q is the number of new positions (1 for greedy decode, >1 for
        prefill), new k/v are written at ``index`` and attention runs
        causally over the filled prefix. Rolling cache
        (``init_cache(window=...)``, k/v shaped (B, min(window, max_len),
        H, D)): one token per step only, slot ``index % buf_len`` is
        overwritten, the slot mask is built internally (caller masks are
        rejected). Returns the updated cache.
      precomputed_kv: optional (k, v) already projected to (B, S_k, H, D) —
        used by cross-attention during decode so the static encoder output is
        projected once, not once per generated token.
      rope: rotate q and the NEWLY-projected k by their absolute positions
        (``ops.positional.apply_rope``) — self-attention only (cross-attention
        callers must leave this False; cached keys are stored rotated, so the
        decode path composes for free). Positions come from ``cache["index"]``
        when decoding, else ``arange(S_q)``. A dict gives ``apply_rope``'s
        keyword arguments (``ops.positional.kind_rope``: base, rotated share
        of the head, YaRN); ``True`` is the plain rotation at base 10,000.
      qk_norm_epsilon: of ``normalise_qk``, for a layer that has its scales.

    Returns ``(out, weights|None, cache|None)``.
    """
    if window and not causal and cache is None:
        # Same contract as flash_attention and the ring/ulysses branch:
        # a window without causality (or a cache, whose prefix mask is
        # causal by construction) would otherwise be silently ignored.
        raise ValueError(
            "window requires causal=True (or a decode cache); bidirectional "
            "local attention is not implemented"
        )
    dtype = x_q.dtype
    q = _project(params["query"], x_q, dtype)
    if precomputed_kv is not None:
        k, v = (t.astype(dtype) for t in precomputed_kv)
    else:
        k = _project(params["key"], x_kv, dtype)
        v = _project(params["value"], x_kv, dtype)
    q, k = normalise_qk(params, q, k, qk_norm_epsilon)

    if rope:
        from transformer_tpu.ops.positional import apply_rope

        offset = cache["index"] if cache is not None else 0
        positions = offset + jnp.arange(x_q.shape[1])
        rope_kw = rope if isinstance(rope, dict) else {}
        q = apply_rope(q, positions, **rope_kw)
        if precomputed_kv is None:
            k = apply_rope(k, positions, **rope_kw)

    if cache is not None:
        idx = cache["index"]
        buf_len = cache["k"].shape[1]
        s_q = x_q.shape[1]
        # Rolling window buffer (init_cache(window=...)): the buffer holds
        # only the last `buf_len <= window` positions and each step writes
        # slot idx % buf_len — decode HBM and score compute are O(window),
        # not O(max_len). Attention is permutation-invariant over kv slots,
        # so slot ORDER never matters, only which slots are valid; RoPE
        # composes because keys are cached already rotated by their
        # absolute position. Rolling-ness is carried EXPLICITLY by the
        # cache (the "rolling" key init_cache stores when built with a
        # window) — key presence is static pytree structure, so the branch
        # stays trace-time. Inferring it from buffer size would misclassify
        # a full-length cache as rolling whenever max_len <= window.
        rolling = "rolling" in cache
        if rolling and s_q > 1:
            # Chunked PREFILL into a rolling buffer. Writing the chunk first
            # and then attending the buffer (the one-token flow) would be
            # wrong here: a later chunk token's write can evict a position
            # that is still inside an earlier chunk token's band. So attend
            # FIRST — against the buffer's pre-chunk contents plus the
            # chunk's own keys — then write. Chunks are capped at buf_len so
            # the write slots are distinct (no intra-chunk eviction).
            if s_q > buf_len:
                raise ValueError(
                    f"rolling-window prefill chunks must fit the window "
                    f"buffer: got s_q={s_q} > buf_len={buf_len} (split the "
                    "prefill into chunks of at most the window size)"
                )
            if mask is not None:
                raise ValueError(
                    "rolling-window cache builds its own slot mask; a "
                    "caller mask is indexed by absolute position and "
                    "cannot compose with rotated slots"
                )
            from transformer_tpu.ops.masks import make_rolling_prefill_mask

            if "k_scale" in cache:
                k_old = cache["k"].astype(dtype) * cache["k_scale"].astype(dtype)
                v_old = cache["v"].astype(dtype) * cache["v_scale"].astype(dtype)
            else:
                k_old = cache["k"].astype(dtype)
                v_old = cache["v"].astype(dtype)
            mask = make_rolling_prefill_mask(idx, s_q, buf_len)
            slots_w = (idx + jnp.arange(s_q)) % buf_len
            new_cache, k, v = _store_kv(
                cache, k, v, lambda buf, val: buf.at[:, slots_w].set(val)
            )
            new_cache["index"] = idx + s_q
            new_cache["rolling"] = cache["rolling"]
            cache = new_cache
            k = jnp.concatenate([k_old, k], axis=1)
            v = jnp.concatenate([v_old, v], axis=1)
        else:
            if rolling:
                if mask is not None:
                    raise ValueError(
                        "rolling-window cache builds its own slot mask; a "
                        "caller mask is indexed by absolute position and "
                        "cannot compose with rotated slots"
                    )
                write_pos = idx % buf_len
            else:
                write_pos = idx
            # int8 caches (init_cache(quantize=True)) store each new
            # (position, head) row as int8 with its own fp32 scale — the
            # cache is the decode-side HBM bottleneck at long contexts, and
            # int8 reads cost 2x (vs bf16) to 4x (vs fp32) less bandwidth.
            # Dequantize below for the attention math (compute stays in the
            # model dtype; the win is memory, not FLOPs).
            new_cache, _, _ = _store_kv(
                cache, k, v,
                lambda buf, val: jax.lax.dynamic_update_slice(
                    buf, val, (0, write_pos, 0, 0)
                ),
            )
            new_cache["index"] = idx + s_q
            if "k_scale" in cache:
                k = new_cache["k"].astype(dtype) * new_cache["k_scale"].astype(dtype)
                v = new_cache["v"].astype(dtype) * new_cache["v_scale"].astype(dtype)
            else:
                k = new_cache["k"]
                v = new_cache["v"]
            if rolling:
                new_cache["rolling"] = cache["rolling"]
            cache = new_cache
            if rolling:
                # Which slots hold a REAL (already-written) position: all of
                # them once idx wraps, else slots <= idx. Every held position
                # is inside the band by construction (the newest write evicted
                # the only out-of-band one).
                slots = jnp.arange(buf_len)[None, None, None, :]
                mask = jnp.logical_or(slots <= idx, idx >= buf_len)
            else:
                # Causal decode mask over the cache buffer: new query at
                # absolute position idx+i may attend keys at positions <= idx+i
                # (prefill with s_q > 1 stays causal), combined with any
                # caller-provided mask. `window` masks the band when a sliding
                # window runs over a FULL-LENGTH (non-rolling) cache.
                from transformer_tpu.ops.masks import make_cache_prefix_mask

                valid = make_cache_prefix_mask(idx, s_q, buf_len, window=window)
                mask = valid if mask is None else jnp.logical_and(mask, valid)
        k = k.astype(dtype)
        v = v.astype(dtype)

    # Grouped-query kv heads need NO materialized repeat on any blockwise
    # path: flash and ring map each q-head to its kv group in the kernels'
    # BlockSpec index maps (kv HBM reads — and the ring's per-hop ppermute
    # payload — stay at the H_kv rate), and ulysses all-to-alls kv at its
    # own head count when divisible (seq_context.seq_parallel_attention
    # repeats only in the two documented misalignment corners).
    if impl == "flash" and cache is None:
        # Causality stays structural (a static kernel flag) so the Pallas
        # kernel can skip above-diagonal tiles instead of masking them.
        from transformer_tpu.kernels.flash_attention import flash_attention

        kv_mask = _kv_padding_mask(mask, impl)
        out = flash_attention(
            q, k, v,
            kv_mask=kv_mask,
            causal=causal,
            # The top-of-function guard rejects window without causal on
            # this (cache-free) path, so window>0 implies causal here.
            window=window,
            block_q=flash_block_q,
            block_k=flash_block_k,
        )
        weights = None
    elif impl in ("ring", "ulysses") and cache is None:
        # Stack-level sequence parallelism: the distributed engine activates a
        # SeqParallelContext around the jitted forward
        # (parallel/distributed.make_sharded_steps), and the attention core
        # runs under shard_map on the context's mesh with S split over the
        # 'seq' axis (KV chunks ride ICI via ppermute / all_to_all —
        # parallel/ring_attention.py).
        from transformer_tpu.parallel.seq_context import (
            current_seq_context,
            seq_parallel_attention,
        )

        ctx = current_seq_context()
        if ctx is None:
            raise RuntimeError(
                f"attention_impl={impl!r} needs an active sequence-parallel "
                "context: train through DistributedTrainer with "
                "MeshConfig(seq>1) (or wrap the forward in "
                "parallel.seq_context.sequence_parallel)"
            )
        kv_mask = _kv_padding_mask(mask, impl)
        if kv_mask is not None and kv_mask.shape[0] == 1 and q.shape[0] != 1:
            kv_mask = jnp.broadcast_to(kv_mask, (q.shape[0], kv_mask.shape[1]))
        out = seq_parallel_attention(
            ctx, impl, q, k, v, kv_mask, causal, window=window
        )
        weights = None
    else:
        if causal and cache is None:
            # Causality is enforced whether or not a padding mask was provided.
            from transformer_tpu.ops.masks import make_causal_mask

            cmask = make_causal_mask(x_q.shape[1], window=window)
            mask = cmask if mask is None else jnp.logical_and(mask, cmask)
        out, weights = dot_product_attention(q, k, v, mask, return_weights=return_weights)

    return merge_heads(params, out, x_q), weights, cache


def _quantize_kv(t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 per-(position, head) quantization of a (B, S, H, D)
    projection: one fp32 scale per row of ``D`` values."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def kv_buffer_keys(cache: dict[str, Any]) -> tuple[str, ...]:
    """The cache keys that hold per-position KV rows, in the cache's own
    storage layout: ``("k", "v")`` for plain caches, plus the fp32
    ``k_scale``/``v_scale`` rows for int8-quantized ones. The ONE listing of
    the layout's buffer names — ``_store_kv``, ``slice_kv_blocks``, and
    ``insert_kv_blocks`` all iterate it, so a future layout (new buffer key)
    cannot desynchronize the write, export, and restore paths. A latent
    layer's entry (``ops/mla.py``) keeps ONE buffer, ``ckv``: a row a position
    that is key and value at once. An entry that holds a fixed state a
    sequence (``ops.short_conv.state_buffer_keys``: a short convolution's
    rows, a delta-rule or a state-space layer's matrix), and the entry of a
    layer without a mixer, hold no such rows."""
    from transformer_tpu.ops.short_conv import state_buffer_keys

    if state_buffer_keys(cache) or "k" not in cache and "ckv" not in cache:
        return ()
    if "ckv" in cache:
        return ("ckv",)
    if "k_scale" in cache:
        return ("k", "k_scale", "v", "v_scale")
    return ("k", "v")


def _require_positional_buffers(cache: dict[str, Any], op: str) -> None:
    """Reject rolling-window caches from operations that address buffer rows
    by absolute position. A rolling buffer stores position ``p`` at slot
    ``p % buf_len`` and EVICTS on wrap — row ranges are neither stable nor
    complete, so block export/restore (prefix cache) and index rollback
    (speculation) are structurally unsound there. Shared by
    ``rollback_cache`` / ``slice_kv_blocks`` / ``insert_kv_blocks`` so every
    random-access path refuses with the same policy."""
    if "rolling" in cache:
        raise ValueError(
            f"{op} cannot address a rolling-window cache by position: the "
            "window buffer evicts rows on wrap (slot p % buf_len), so "
            "absolute-position rows are neither stable nor complete — serve "
            "this config without attention_window"
        )


def slice_kv_blocks(cache: dict[str, Any], start, n: int) -> dict[str, Any]:
    """Read buffer rows ``[start, start + n)`` of every KV buffer — the
    block-granular EXPORT half of the prefix cache's round trip. Rows come
    out in the cache's own storage layout (int8 codes and their fp32 scales
    slice as stored, bf16 slices as bf16), so an exported block re-inserted
    by ``insert_kv_blocks`` is bit-identical to the original write — the
    invariant that makes cross-request KV reuse byte-transparent. ``n`` must
    be static (it is a shape); ``start`` may be traced."""
    _require_positional_buffers(cache, "slice_kv_blocks")
    return {
        key: jax.lax.dynamic_slice_in_dim(cache[key], start, n, axis=1)
        for key in kv_buffer_keys(cache)
    }


def insert_kv_blocks(
    cache: dict[str, Any], blocks: dict[str, Any], start
) -> dict[str, Any]:
    """Write exported KV rows back at buffer rows ``[start, start +
    blocks_len)`` — the RESTORE half of ``slice_kv_blocks``. Blocks are
    already in storage layout, so this is a pure ``dynamic_update_slice``
    per buffer: no re-quantization, no dtype conversion, bit-identical to
    the rows the donor cache held. ``index`` (and any other bookkeeping) is
    left untouched — callers own it, same contract as ``_store_kv``."""
    _require_positional_buffers(cache, "insert_kv_blocks")
    new = dict(cache)
    for key in kv_buffer_keys(cache):
        new[key] = jax.lax.dynamic_update_slice_in_dim(
            cache[key], blocks[key], start, axis=1
        )
    return new


def _store_kv(cache, k, v, write):
    """Write new (B, S_q, H, D) k/v into a decode cache's buffers via
    ``write(buf, val) -> buf`` (the caller picks the scatter: rolling slots
    or a contiguous dynamic_update_slice). The ONE place that knows the int8
    layout — quantizing into the four k/k_scale/v/v_scale buffers — so the
    prefill and one-token write paths can never desynchronize numerics.

    Returns ``(new_cache_bufs, k_rt, v_rt)``: the updated buffers (no
    "index"/"rolling" bookkeeping — callers own that) plus the new entries
    as the read path will see them — the quantize->dequantize round trip for
    int8 caches, the inputs unchanged otherwise. Attending the chunk's own
    keys through ``k_rt`` keeps int8 decode numerics independent of whether
    a position arrived via prefill or step."""
    if "k_scale" in cache:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        vals = {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}
        new = {key: write(cache[key], vals[key]) for key in kv_buffer_keys(cache)}
        dtype = k.dtype
        return (
            new,
            kq.astype(dtype) * ks.astype(dtype),
            vq.astype(dtype) * vs.astype(dtype),
        )
    new = {
        "k": write(cache["k"], k.astype(cache["k"].dtype)),
        "v": write(cache["v"], v.astype(cache["v"].dtype)),
    }
    return new, k, v


def rollback_cache(cache: dict[str, Any], index) -> dict[str, Any]:
    """O(1) KV rollback: keep the buffers, reset ``index`` to an earlier
    position. The speculative-decoding verify step writes K/V for every
    candidate token it scores; rejected candidates are "erased" by moving
    the index back — their stale rows stay in the buffer but the offset
    causal mask (``make_cache_prefix_mask``) already hides every position
    ``>= index`` from all later reads, and the next real write overwrites
    them in place (the int8 variant re-quantizes the row, so stale scales
    can never pair with fresh codes).

    Rolling-window caches are REJECTED (``_require_positional_buffers``, the
    same policy gate the prefix cache's block slice/insert uses): a
    speculative write at position ``p`` evicts slot ``p % buf_len`` — a
    position that may still be inside the window after rollback — so index
    reset cannot restore their state. Gate speculation off for
    ``attention_window`` configs instead.
    """
    _require_positional_buffers(cache, "rollback_cache")
    return dict(cache, index=jnp.asarray(index, jnp.int32))


def init_block_pool(
    num_blocks: int,
    block_tokens: int,
    num_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
    quantize: bool = False,
) -> dict[str, Any]:
    """One layer's PAGED KV pool: the per-position buffers of
    ``init_cache``, re-shaped from one (B, max_len, H, D) run per slot
    into a single (num_blocks, block_tokens, H, D) pool every slot
    addresses through a block table (``kernels/kv_pool.py``). Buffer KEYS,
    dtypes and a token's bytes in their order are identical to the dense
    cache's — int8 codes with fp32 scales, GQA kv-head counts — so
    ``kv_buffer_keys`` iterates both and the dense <-> paged round trip is
    bit-transparent. The SHAPE of a token's page is the pool's own: heads
    narrower than a lane row that fill whole rows are kept ``128 // D`` a row,
    (num_blocks, block_tokens, H * D // 128, 128), where the decode kernel can
    stream such a page (``kernels/paged_flash.heads_per_lane_row``, the one
    rule, read from the shapes: bf16 8 x 64 -> 4 x 128; 2 x 128, 8 x 128, an
    odd count of 64 and every int8 pool keep (H, D)). A minor axis of 64
    would be padded to 128 lanes on the chip; row-major the two shapes are the
    same bytes, so writers hand ``scatter_rows`` rows by heads and readers
    take them back by heads (``kv_pool.heads_view``), the host-format
    prefix-cache block (1, B, H, D) among them. No ``index`` (per-slot
    position bookkeeping lives with the table) and no rolling variant
    (rolling windows evict absolute-position rows — the same refusal the
    prefix cache and speculative rollback enforce)."""
    from transformer_tpu.kernels.paged_flash import heads_per_lane_row

    per_row = heads_per_lane_row(num_heads, head_dim, dtype, quantize)
    shape = (num_blocks, block_tokens, num_heads // per_row, head_dim * per_row)
    if quantize:
        return {
            "k": jnp.zeros(shape, dtype=jnp.int8),
            "k_scale": jnp.zeros(shape[:3] + (1,), dtype=jnp.float32),
            "v": jnp.zeros(shape, dtype=jnp.int8),
            "v_scale": jnp.zeros(shape[:3] + (1,), dtype=jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, dtype=dtype),
        "v": jnp.zeros(shape, dtype=dtype),
    }


def init_cache(
    batch_size: int,
    max_len: int,
    num_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
    quantize: bool = False,
    window: int = 0,
) -> dict[str, Any]:
    """Fresh decode cache. The reference instead re-runs the full decoder over
    a concat-grown buffer every step (``train.py:109-118``) — a recompile bomb
    under XLA; a fixed-size cache plus ``dynamic_update_slice`` keeps decode a
    single compiled program.

    ``quantize=True`` stores k/v as int8 with one fp32 scale per
    (position, head) row (``ModelConfig.kv_cache_int8``): the cache — the
    HBM bottleneck of long-context serving — shrinks ~2x vs bf16 storage
    (~4x vs fp32) plus D/4 scale overhead; attention dequantizes on read.

    ``window > 0`` (``ModelConfig.attention_window``) allocates a ROLLING
    buffer of only min(window, max_len) slots: each decode step overwrites
    slot ``index % buf_len``, so windowed decode pays O(window) HBM and
    score compute regardless of context length. Composes with ``quantize``.
    Rolling caches carry a ``"rolling"`` sentinel key — its PRESENCE (static
    pytree structure) is what marks the cache as rolling; the stored value
    records the requested window for debugging only (the effective band is
    the buffer length, min(window, max_len))."""
    buf_len = min(window, max_len) if window else max_len
    shape = (batch_size, buf_len, num_heads, head_dim)
    if quantize:
        cache = {
            "k": jnp.zeros(shape, dtype=jnp.int8),
            "k_scale": jnp.zeros(shape[:3] + (1,), dtype=jnp.float32),
            "v": jnp.zeros(shape, dtype=jnp.int8),
            "v_scale": jnp.zeros(shape[:3] + (1,), dtype=jnp.float32),
            "index": jnp.array(0, dtype=jnp.int32),
        }
    else:
        cache = {
            "k": jnp.zeros(shape, dtype=dtype),
            "v": jnp.zeros(shape, dtype=dtype),
            "index": jnp.array(0, dtype=jnp.int32),
        }
    if window:
        cache["rolling"] = jnp.array(window, dtype=jnp.int32)
    return cache
