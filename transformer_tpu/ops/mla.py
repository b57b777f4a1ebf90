"""Latent attention (MLA): softmax attention whose cache is ONE row a position.

No reference counterpart. The layer is DeepSeek-V2's multi-head latent
attention (arXiv:2405.04434). ``x`` is the normalised input, ``H`` heads,
latent rank ``r``, a head's key part made from the latent ``n`` wide, the
shared key part ``p`` wide, a head's value ``v`` wide:

    [qn_h ; qp_h] = x Wq, or RMSNorm_q(x Wqa) Wqb where the query is compressed (H heads x (n + p))
    [c ; kp]      = x Wkva              (r + p);   c = RMSNorm_r(c)      <- the cached row
    [kn_h ; v_h]  = c Wkvb_h            (n + v) a head
    score_h(t, s) = (qn_h(t) . kn_h(s) + qp_h(t) . kp(s)) * (n + p)**-0.5 * f,   causal softmax over s <= t
    out           = concat_h(sum_s p_h(t, s) v_h(s)) Wo

Two forms of it run. Kimi Linear's (arXiv:2510.26692; ``mla_use_nope``): one
query projection and NO rotation anywhere, so the "rotary" channels are a key
part the heads share and nothing else, ``f = 1``. DeepSeek-V3's, as A.X K1
configures it: the query compressed to rank ``q`` (``q_lora_rank``), ``kp``
turned at its own position and each ``qp_h`` at the query's by the kind's
rotary frequencies (YaRN's blend), ``kp`` cached turned, and ``f`` YaRN's
``mscale**2`` (``AttentionKind.latent_score_factor``). ``latent_position_args``
says which, from the layer's kind.

What a sequence carries: the row ``[c ; kp]`` a position, ``r + p`` channels
(512 + 64: 1,152 bytes in bfloat16, where 32 heads of keys and values would be
20,480), stored once and padded with zeros to whole lane rows (``latent_width``:
640), under the buffer key ``ckv`` (``ops.attention.kv_buffer_keys``).

Which form each path takes. More than one position (training, the dense-cache
forward, a prefill chunk against the rows to its left) takes the **unabsorbed**
form above, keys and values expanded from the rows a head. It walks its keys:
each block of ``_QUERY_BLOCK`` query rows (the whole chunk where it is shorter)
reads the rows in blocks of ``_KEY_BLOCK`` from position 0 up to the block
holding its last query, expands each key block's keys and values as it comes,
and carries an online softmax across them (running maximum, running sum and
accumulator in float32); rows past the chunk's end enter as zeros, and a chunk
or rows that are no whole number of blocks are padded to whole blocks. A
2,048-token prefill into a 6,144-position slot buffer so reads 10 of the 48
(query block, key block) pairs the buffer holds (``latent_keys_read``). One
position against a cache takes the **absorbed** form: ``q~_h = [Wkvb_h^K qn_h ;
qp_h] * scale`` (r + p), ``score = q~_h . [c ; kp]``, ``ctx_h = sum_s p c(s)`` (r), ``o_h = Wkvb_h^V ctx_h``: every
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
from transformer_tpu.ops.positional import apply_rope, kind_rope

_LANES = 128
_QUERY_BLOCK = 512
_KEY_BLOCK = 512
_MASKED = -1e9


def latent_width(rank: int, shared: int) -> int:
    """Channels of a cached row: ``rank + shared`` padded to whole lane rows."""
    return -(-(rank + shared) // _LANES) * _LANES


def mla_init(
    key: jax.Array, d_model: int, heads: int, rank: int, nope: int, shared: int,
    value: int, param_dtype=jnp.float32, query_scale: float = 1.0, query_rank: int = 0,
) -> Params:
    """``query`` (M, H, n + p), or with ``query_rank`` > 0 the compressed
    query: ``q_a`` (M, q), ``q_norm``'s scale (q,), ``q_b`` (q, H, n + p);
    ``kv_a`` (M, r + p); ``kv_norm``'s scale (r,); ``kv_b`` (r, H, n + v): a
    head's key part then its value; ``out`` (H, v, M). No bias.
    ``query_scale`` multiplies the Glorot draw of the query kernel (of ``q_b``
    where the query is compressed: ``AttentionKind.latent_query_init_scale``,
    seeded weights whose attention is peaked as a checkpoint's is)."""
    kq, ka, kb, ko = jax.random.split(key, 4)
    fan_in = query_rank or d_model
    query = glorot_uniform(kq, (fan_in, heads, nope + shared), param_dtype, fan_in, heads * (nope + shared))
    if query_scale != 1.0:
        query = (query.astype(jnp.float32) * query_scale).astype(param_dtype)
    if query_rank:
        kqa = jax.random.fold_in(key, 5)
        queries = {
            "q_a": {"kernel": glorot_uniform(kqa, (d_model, query_rank), param_dtype, d_model, query_rank)},
            "q_norm": {"scale": jnp.ones((query_rank,), param_dtype)},
            "q_b": {"kernel": query},
        }
    else:
        queries = {"query": {"kernel": query}}
    return {
        **queries,
        "kv_a": {"kernel": glorot_uniform(ka, (d_model, rank + shared), param_dtype, d_model, rank + shared)},
        "kv_norm": {"scale": jnp.ones((rank,), param_dtype)},
        "kv_b": {"kernel": glorot_uniform(kb, (rank, heads, nope + value), param_dtype, rank, heads * (nope + value))},
        "out": {"kernel": glorot_uniform(ko, (heads, value, d_model), param_dtype, heads * value, d_model)},
    }


def latent_position_args(kind) -> dict[str, Any]:
    """What a latent layer of ``config.AttentionKind`` ``kind`` takes besides
    its parameters: ``rope``, ``apply_rope``'s arguments where the kind
    rotates its shared key part (else None), and ``score_factor``."""
    return {"rope": kind_rope(kind) if kind.latent_rotary else None,
            "score_factor": kind.latent_score_factor}


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
    query = params["q_b"] if "q_b" in params else params["query"]
    return rank, query["kernel"].shape[2] - shared, shared


def _rms(x: jax.Array, scale: jax.Array, epsilon: float, dtype) -> jax.Array:
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + epsilon)
    return (x * scale.astype(jnp.float32)).astype(dtype)


def _rotate(x: jax.Array, positions: jax.Array, rope: dict) -> jax.Array:
    """(B, S, H, D) turned at ``positions`` (B, S) by ``apply_rope``."""
    return jax.vmap(lambda t, pos: apply_rope(t[None], pos, **rope)[0])(x, positions)


def _queries(params: Params, h: jax.Array, epsilon: float, positions, rope) -> jax.Array:
    """(B, S, M) -> (B, S, H, n + p): ``[qn_h ; qp_h]``, from ``h`` or from its
    normalised compression, the shared part turned at ``positions``."""
    dtype = h.dtype
    if "q_a" in params:
        c = jnp.einsum("bsm,mr->bsr", h, params["q_a"]["kernel"].astype(dtype))
        c = _rms(c, params["q_norm"]["scale"], epsilon, dtype)
        q = jnp.einsum("bsr,rhd->bshd", c, params["q_b"]["kernel"].astype(dtype))
    else:
        q = jnp.einsum("bsm,mhd->bshd", h, params["query"]["kernel"].astype(dtype))
    if not rope:
        return q
    _, nope, _ = _sizes(params)
    return jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], positions, rope)], axis=-1)


def latent_rows(
    params: Params, h: jax.Array, epsilon: float, positions: jax.Array | None = None, rope: dict | None = None
) -> jax.Array:
    """(B, S, M) normalised input -> the rows to cache (B, S, lanes):
    ``[RMSNorm(c) ; kp ; 0]`` in ``h``'s dtype, ``kp`` turned at
    ``positions`` (B, S) where ``rope`` is given."""
    rank, _, shared = _sizes(params)
    a = jnp.einsum("bsm,mf->bsf", h, params["kv_a"]["kernel"].astype(h.dtype))
    c = _rms(a[..., :rank], params["kv_norm"]["scale"], epsilon, h.dtype)
    kp = a[..., rank:]
    if rope:
        kp = _rotate(kp[:, :, None], positions, rope)[:, :, 0]
    pad = latent_width(rank, shared) - rank - shared
    return jnp.concatenate([c, kp, jnp.zeros((*a.shape[:-1], pad), h.dtype)], axis=-1)


def absorbed_queries(
    params: Params, h: jax.Array, epsilon: float, positions: jax.Array | None = None,
    rope: dict | None = None, score_factor: float = 1.0,
) -> jax.Array:
    """(B, S, M) -> (B, S, H, lanes): ``[Wkvb_h^K qn_h ; qp_h ; 0]`` times the
    softmax scale (and ``score_factor``), so a score is a plain dot with a
    cached row; ``qp_h`` turned at ``positions`` (B, S) where ``rope`` is given."""
    rank, nope, shared = _sizes(params)
    dtype = h.dtype
    q = _queries(params, h, epsilon, positions, rope)
    up = jnp.einsum("bshd,rhd->bshr", q[..., :nope], params["kv_b"]["kernel"][..., :nope].astype(dtype))
    pad = latent_width(rank, shared) - rank - shared
    full = jnp.concatenate([up, q[..., nope:], jnp.zeros((*q.shape[:-1], pad), dtype)], axis=-1)
    return (full.astype(jnp.float32) * ((nope + shared) ** -0.5 * score_factor)).astype(dtype)


def absorbed_output(params: Params, ctx: jax.Array) -> jax.Array:
    """(B, S, H, r) contexts in the latent -> (B, S, M): each head's value
    projection, then the out projection."""
    _, nope, _ = _sizes(params)
    dtype = ctx.dtype
    o = jnp.einsum("bshr,rhd->bshd", ctx, params["kv_b"]["kernel"][..., nope:].astype(dtype))
    return jnp.einsum("bshd,hdm->bsm", o, params["out"]["kernel"].astype(dtype))


def _blocks(s: int, length: int) -> tuple[int, int]:
    """(query rows, key rows) of a block of the walk for a chunk of ``s``
    queries against ``length`` rows: ``_QUERY_BLOCK`` and ``_KEY_BLOCK``,
    or the whole chunk and the whole of the rows where shorter."""
    return min(s, _QUERY_BLOCK), min(length, _KEY_BLOCK)


def _reach(first, end, key_block: int):
    """Key blocks of ``key_block`` rows that a query block ending at query
    ``end`` of a chunk from ``first`` reads: those holding a position up to
    its last query's."""
    return -(-(first + end) // key_block)


def latent_keys_read(first: int, s: int, length: int) -> float:
    """The share of the rows' key blocks that the unabsorbed form reads for
    a chunk of ``s`` queries from position ``first`` against ``length`` rows,
    over its query blocks. Host arithmetic by the program's rule: 10/48 for
    2,048 queries from 0 against 6,144 rows in blocks of 512."""
    block, key_block = _blocks(s, length)
    blocks = -(-s // block)
    reads = sum(_reach(first, min((i + 1) * block, s), key_block) for i in range(blocks))
    return reads / (blocks * -(-length // key_block))


def _attend_rows(
    params: Params, h: jax.Array, rows: jax.Array, first, epsilon: float,
    rope: dict | None = None, score_factor: float = 1.0,
) -> jax.Array:
    """The unabsorbed form: queries of the chunk ``h`` (B, S, M), sitting at
    positions ``first .. first + S - 1``, against the latent rows (B, L,
    lanes) of positions 0 .. L - 1, causally. Each query block (``_blocks``;
    the chunk padded to whole blocks) walks the key blocks up to its
    ``_reach`` (the rows padded to whole blocks), an online softmax carried
    across them; rows past ``first + S - 1`` enter as zeros. A ``first``
    known at tracing (the forward without a cache) makes every trip count
    static, so the walk is a scan and can be differentiated; a traced one
    makes it a ``while`` a query block. Returns (B, S, H, v)."""
    rank, nope, shared = _sizes(params)
    dtype = h.dtype
    s, length = h.shape[1], rows.shape[1]
    turned_at = jnp.broadcast_to(first + jnp.arange(s), h.shape[:2]) if rope else None
    q = _queries(params, h, epsilon, turned_at, rope)
    kv_b = params["kv_b"]["kernel"].astype(dtype)
    scale = (nope + shared) ** -0.5 * score_factor
    b, heads, value = q.shape[0], q.shape[2], kv_b.shape[2] - nope
    block, key_block = _blocks(s, length)
    blocks = -(-s // block)
    if blocks * block > s:
        q = jnp.pad(q, ((0, 0), (0, blocks * block - s), (0, 0), (0, 0)))
    if length % key_block:
        rows = jnp.pad(rows, ((0, 0), (0, key_block - length % key_block), (0, 0)))
    last = first + s - 1

    def one(qb, pos, reach):
        def step(j, carry):
            top, total, acc = carry
            keys = jax.lax.dynamic_slice_in_dim(rows, j * key_block, key_block, axis=1)
            at = j * key_block + jnp.arange(key_block)
            keys = jnp.where((at <= last)[None, :, None], keys, 0)
            kv = jnp.einsum("bkr,rhd->bkhd", keys[..., :rank], kv_b)
            scores = (
                jnp.einsum("bqhd,bkhd->bhqk", qb[..., :nope], kv[..., :nope], preferred_element_type=jnp.float32)
                + jnp.einsum("bqhd,bkd->bhqk", qb[..., nope:], keys[..., rank : rank + shared],
                             preferred_element_type=jnp.float32)
            ) * scale
            scores = jnp.where(at[None, :] <= pos[:, None], scores, _MASKED)
            new_top = jnp.maximum(top, scores.max(-1))
            w = jnp.exp(scores - new_top[..., None])
            fade = jnp.exp(top - new_top)
            acc = acc * fade[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", w.astype(dtype), kv[..., nope:], preferred_element_type=jnp.float32)
            return new_top, total * fade + w.sum(-1), acc

        init = (jnp.full((b, heads, block), _MASKED, jnp.float32), jnp.zeros((b, heads, block), jnp.float32),
                jnp.zeros((b, heads, block, value), jnp.float32))
        _, total, acc = jax.lax.fori_loop(0, reach, step, init)
        return jnp.swapaxes(acc / total[..., None], 1, 2).astype(dtype)

    pos = first + jnp.arange(blocks * block)
    ends = [min((i + 1) * block, s) for i in range(blocks)]
    if isinstance(first, int):
        out = jnp.concatenate([
            one(q[:, i * block : (i + 1) * block], pos[i * block : (i + 1) * block], _reach(first, end, key_block))
            for i, end in enumerate(ends)
        ], axis=1)
    else:
        qs = jnp.moveaxis(q.reshape(b, blocks, block, *q.shape[2:]), 1, 0)
        out = jax.lax.map(lambda a: one(a[0], a[1], _reach(first, a[2], key_block)),
                          (qs, pos.reshape(blocks, block), jnp.asarray(ends)))
        out = jnp.moveaxis(out, 0, 1).reshape(b, blocks * block, heads, value)
    return out[:, :s] if blocks * block > s else out


def mla_apply(
    params: Params, h: jax.Array, cache: dict[str, Any] | None = None, epsilon: float = 1e-5,
    rope: dict | None = None, score_factor: float = 1.0,
) -> tuple[jax.Array, dict[str, Any] | None]:
    """(B, S, M) normalised input -> ((B, S, M) output, the cache with the
    chunk's rows written at ``cache["index"]`` and the index moved on; ``None``
    without a cache: the chunk then starts the sequence). ``rope`` and
    ``score_factor``: ``latent_position_args`` of the layer's kind."""
    positions = None
    if rope:
        start = 0 if cache is None else cache["index"]
        positions = jnp.broadcast_to(start + jnp.arange(h.shape[1]), h.shape[:2])
    rows = latent_rows(params, h, epsilon, positions, rope)
    if cache is None:
        heads = _attend_rows(params, h, rows, 0, epsilon, rope, score_factor)
        return jnp.einsum("bshd,hdm->bsm", heads, params["out"]["kernel"].astype(h.dtype)), None
    idx = cache["index"]
    buf = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], rows.astype(cache["ckv"].dtype), idx, axis=1)
    new_cache = {"ckv": buf, "index": idx + h.shape[1]}
    seen = buf.astype(h.dtype)
    if h.shape[1] > 1:
        heads = _attend_rows(params, h, seen, idx, epsilon, rope, score_factor)
        return jnp.einsum("bshd,hdm->bsm", heads, params["out"]["kernel"].astype(h.dtype)), new_cache
    rank, _, _ = _sizes(params)
    q = absorbed_queries(params, h, epsilon, positions, rope, score_factor)  # (B, 1, H, lanes)
    scores = jnp.einsum("bshw,blw->bhsl", q, seen, preferred_element_type=jnp.float32)
    visible = jnp.arange(buf.shape[1])[None, None, None, :] <= idx
    w = jax.nn.softmax(jnp.where(visible, scores, _MASKED), axis=-1)
    ctx = jnp.einsum("bhsl,blr->bshr", w.astype(h.dtype), seen[..., :rank])
    return absorbed_output(params, ctx), new_cache
