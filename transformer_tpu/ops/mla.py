"""Latent attention (MLA): softmax attention whose cache is ONE row a position.

No reference counterpart. The layer is DeepSeek-V2's multi-head latent
attention (arXiv:2405.04434) as Kimi Linear (arXiv:2510.26692) uses it: no
query compression and NO rotation anywhere (``mla_use_nope``), so the
"rotary" channels are a key part the heads share and nothing else. ``x`` is
the normalised input, ``H`` heads, latent rank ``r``, a head's key part made
from the latent ``n`` wide, the shared key part ``p`` wide, a head's value
``v`` wide:

    [qn_h ; qp_h] = x Wq                (H heads x (n + p))
    [c ; kp]      = x Wkva              (r + p);   c = RMSNorm_r(c)      <- the cached row
    [kn_h ; v_h]  = c Wkvb_h            (n + v) a head
    score_h(t, s) = (qn_h(t) . kn_h(s) + qp_h(t) . kp(s)) * (n + p)**-0.5,   causal softmax over s <= t
    out           = concat_h(sum_s p_h(t, s) v_h(s)) Wo

What a sequence carries: the row ``[c ; kp]`` a position, ``r + p`` channels
(512 + 64: 1,152 bytes in bfloat16, where 32 heads of keys and values would be
20,480), stored once and padded with zeros to whole lane rows (``latent_width``:
640), under the buffer key ``ckv`` (``ops.attention.kv_buffer_keys``).

Which form each path takes. More than one position (training, the dense-cache
forward, a prefill chunk against the rows to its left) takes the **unabsorbed**
form above, keys and values expanded from the rows a head, the scores a block
of ``_QUERY_BLOCK`` query rows at a time so that a 4,096-position prefill never
holds (S, S) scores for every head. One position against a cache takes the
**absorbed** form: ``q~_h = [Wkvb_h^K qn_h ; qp_h] * scale`` (r + p), ``score =
q~_h . [c ; kp]``, ``ctx_h = sum_s p c(s)`` (r), ``o_h = Wkvb_h^V ctx_h``: every
head against one row a position whose first ``r`` channels are also the value.
The dense cache's step computes it in XLA (``mla_apply``); the paged step hands
``absorbed_queries`` to ``kernels/paged_latent.py`` and the contexts to
``absorbed_output``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from transformer_tpu.ops.nn import Params, glorot_uniform

_LANES = 128
_QUERY_BLOCK = 512
_MASKED = -1e9


def latent_width(rank: int, shared: int) -> int:
    """Channels of a cached row: ``rank + shared`` padded to whole lane rows."""
    return -(-(rank + shared) // _LANES) * _LANES


def mla_init(
    key: jax.Array, d_model: int, heads: int, rank: int, nope: int, shared: int,
    value: int, param_dtype=jnp.float32, query_scale: float = 1.0,
) -> Params:
    """``query`` (M, H, n + p); ``kv_a`` (M, r + p); ``kv_norm``'s scale (r,);
    ``kv_b`` (r, H, n + v): a head's key part then its value; ``out`` (H, v, M).
    No bias. ``query_scale`` multiplies the Glorot draw of the query kernel
    (``AttentionKind.latent_query_init_scale``: seeded weights whose attention
    is peaked as a checkpoint's is)."""
    kq, ka, kb, ko = jax.random.split(key, 4)
    query = glorot_uniform(kq, (d_model, heads, nope + shared), param_dtype, d_model, heads * (nope + shared))
    if query_scale != 1.0:
        query = (query.astype(jnp.float32) * query_scale).astype(param_dtype)
    return {
        "query": {"kernel": query},
        "kv_a": {"kernel": glorot_uniform(ka, (d_model, rank + shared), param_dtype, d_model, rank + shared)},
        "kv_norm": {"scale": jnp.ones((rank,), param_dtype)},
        "kv_b": {"kernel": glorot_uniform(kb, (rank, heads, nope + value), param_dtype, rank, heads * (nope + value))},
        "out": {"kernel": glorot_uniform(ko, (heads, value, d_model), param_dtype, heads * value, d_model)},
    }


def init_latent_cache(batch: int, max_len: int, rank: int, shared: int, dtype=jnp.bfloat16) -> dict[str, Any]:
    """A dense cache of latent rows, position 0 first."""
    return {
        "ckv": jnp.zeros((batch, max_len, latent_width(rank, shared)), dtype),
        "index": jnp.array(0, dtype=jnp.int32),
    }


def init_latent_pool(num_blocks: int, block_tokens: int, rank: int, shared: int, dtype=jnp.bfloat16) -> dict[str, Any]:
    """One latent layer's PAGED pool: (num_blocks, block_tokens, lanes), a
    page's tokens down the sublanes, every row stored once (the value is a
    view of it)."""
    return {"ckv": jnp.zeros((num_blocks, block_tokens, latent_width(rank, shared)), dtype)}


def _sizes(params: Params) -> tuple[int, int, int]:
    """(rank, a head's key part from the latent, the shared key part)."""
    rank = params["kv_norm"]["scale"].shape[0]
    shared = params["kv_a"]["kernel"].shape[1] - rank
    return rank, params["query"]["kernel"].shape[2] - shared, shared


def latent_rows(params: Params, h: jax.Array, epsilon: float) -> jax.Array:
    """(B, S, M) normalised input -> the rows to cache (B, S, lanes):
    ``[RMSNorm(c) ; kp ; 0]`` in ``h``'s dtype."""
    rank, _, shared = _sizes(params)
    a = jnp.einsum("bsm,mf->bsf", h, params["kv_a"]["kernel"].astype(h.dtype))
    c = a[..., :rank].astype(jnp.float32)
    c = c * jax.lax.rsqrt((c * c).mean(-1, keepdims=True) + epsilon)
    c = (c * params["kv_norm"]["scale"].astype(jnp.float32)).astype(h.dtype)
    pad = latent_width(rank, shared) - rank - shared
    return jnp.concatenate([c, a[..., rank:], jnp.zeros((*a.shape[:-1], pad), h.dtype)], axis=-1)


def absorbed_queries(params: Params, h: jax.Array) -> jax.Array:
    """(B, S, M) -> (B, S, H, lanes): ``[Wkvb_h^K qn_h ; qp_h ; 0]`` times the
    softmax scale, so a score is a plain dot with a cached row."""
    rank, nope, shared = _sizes(params)
    dtype = h.dtype
    q = jnp.einsum("bsm,mhd->bshd", h, params["query"]["kernel"].astype(dtype))
    up = jnp.einsum("bshd,rhd->bshr", q[..., :nope], params["kv_b"]["kernel"][..., :nope].astype(dtype))
    pad = latent_width(rank, shared) - rank - shared
    full = jnp.concatenate([up, q[..., nope:], jnp.zeros((*q.shape[:-1], pad), dtype)], axis=-1)
    return (full.astype(jnp.float32) * (nope + shared) ** -0.5).astype(dtype)


def absorbed_output(params: Params, ctx: jax.Array) -> jax.Array:
    """(B, S, H, r) contexts in the latent -> (B, S, M): each head's value
    projection, then the out projection."""
    _, nope, _ = _sizes(params)
    dtype = ctx.dtype
    o = jnp.einsum("bshr,rhd->bshd", ctx, params["kv_b"]["kernel"][..., nope:].astype(dtype))
    return jnp.einsum("bshd,hdm->bsm", o, params["out"]["kernel"].astype(dtype))


def _attend_rows(params: Params, h: jax.Array, rows: jax.Array, first) -> jax.Array:
    """The unabsorbed form: queries of the chunk ``h`` (B, S, M), sitting at
    positions ``first .. first + S - 1``, against the latent rows (B, L,
    lanes) of positions 0 .. L - 1, causally. Returns (B, S, H, v)."""
    rank, nope, shared = _sizes(params)
    dtype = h.dtype
    s, length = h.shape[1], rows.shape[1]
    q = jnp.einsum("bsm,mhd->bshd", h, params["query"]["kernel"].astype(dtype))
    kv = jnp.einsum("blr,rhd->blhd", rows[..., :rank], params["kv_b"]["kernel"].astype(dtype))
    kn, v, kp = kv[..., :nope], kv[..., nope:], rows[..., rank : rank + shared]
    scale = (nope + shared) ** -0.5

    def block(args):
        qb, pos = args  # (B, Q, H, n + p), (Q,)
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", qb[..., :nope], kn, preferred_element_type=jnp.float32)
            + jnp.einsum("bqhd,bkd->bhqk", qb[..., nope:], kp, preferred_element_type=jnp.float32)
        ) * scale
        seen = jnp.arange(length)[None, :] <= pos[:, None]
        w = jax.nn.softmax(jnp.where(seen[None, None], scores, _MASKED), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w.astype(dtype), v)

    pos = first + jnp.arange(s)
    if s <= _QUERY_BLOCK or s % _QUERY_BLOCK:
        return block((q, pos))
    nb = s // _QUERY_BLOCK
    qs = jnp.moveaxis(q.reshape(q.shape[0], nb, _QUERY_BLOCK, *q.shape[2:]), 1, 0)
    out = jax.lax.map(block, (qs, pos.reshape(nb, _QUERY_BLOCK)))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape[0], s, *out.shape[3:])


def mla_apply(
    params: Params, h: jax.Array, cache: dict[str, Any] | None = None, epsilon: float = 1e-5
) -> tuple[jax.Array, dict[str, Any] | None]:
    """(B, S, M) normalised input -> ((B, S, M) output, the cache with the
    chunk's rows written at ``cache["index"]`` and the index moved on; ``None``
    without a cache: the chunk then starts the sequence)."""
    rows = latent_rows(params, h, epsilon)
    if cache is None:
        heads = _attend_rows(params, h, rows, 0)
        return jnp.einsum("bshd,hdm->bsm", heads, params["out"]["kernel"].astype(h.dtype)), None
    idx = cache["index"]
    buf = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], rows.astype(cache["ckv"].dtype), idx, axis=1)
    new_cache = {"ckv": buf, "index": idx + h.shape[1]}
    seen = buf.astype(h.dtype)
    if h.shape[1] > 1:
        heads = _attend_rows(params, h, seen, idx)
        return jnp.einsum("bshd,hdm->bsm", heads, params["out"]["kernel"].astype(h.dtype)), new_cache
    rank, _, _ = _sizes(params)
    q = absorbed_queries(params, h)  # (B, 1, H, lanes)
    scores = jnp.einsum("bshw,blw->bhsl", q, seen, preferred_element_type=jnp.float32)
    visible = jnp.arange(buf.shape[1])[None, None, None, :] <= idx
    w = jax.nn.softmax(jnp.where(visible, scores, _MASKED), axis=-1)
    ctx = jnp.einsum("bhsl,blr->bshr", w.astype(h.dtype), seen[..., :rank])
    return absorbed_output(params, ctx), new_cache
