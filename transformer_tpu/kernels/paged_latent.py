"""Decode attention over a paged LATENT pool: every query head of a slot
against ONE row a position, read once through the block table.

A latent-attention layer (``ops/mla.py``) caches one row a position, ``[c ;
kp ; 0]``: the normalised latent (``rank`` channels), the key part all heads
share, and zeros up to whole lane rows (512 + 64 -> 640 lanes in bf16). With
the projections absorbed into the query, head ``h``'s score against position
``s`` is ``q~_h . row_s`` and its context ``sum_s p_h(s) c_s``: the row is key
and, by its first ``rank`` channels, value. So where ``paged_flash_attention``
reads a K page and a V page a KV head, this kernel reads one page and serves
all the heads from it: one query row a head against 1,152 bytes a position,
about 60 FLOP a byte at 32 heads and 121 at 64, both under v5e's ridge of
about 240, so the kernel is bound by the bytes it reads. Where the layer
rotates its shared key part the pool holds it turned and the query comes
turned (``ops/mla.py``): the kernel is the same.

The schedule is ``kernels/paged_flash.py``'s streamed route, cut to this
shape. The grid walks the slots; the pool stays in HBM (``memory_space=ANY``);
the kernel copies the live pages of a compute block (``_BLOCK_POSITIONS`` = 512
positions) into one of two VMEM buffers, the next block's copies (this slot's,
or the next slot's first) started before this block's products; a slot runs
``ceil(length / block)`` loop steps and copies only the pages before its
length. A page is (block_tokens, lanes): tokens down the sublanes, so a block
is a plain (positions, lanes) matrix and nothing is transposed or unpacked.
One online softmax for all the heads (running max, normaliser and a float32
accumulator of ``rank`` lanes in scratch). Pages that are not copied keep what
the buffer held: the buffers are zeroed once a call and only ever receive pool
rows, so that is finite, and those positions are masked to zero weight.
Scores stay float32 (they are not rounded to the pool's dtype), as the XLA
form in ``ops/mla.py`` keeps them.

One query position a slot (the decode step; a stateful model is not served
speculatively). Interpret mode off the chip, as the other kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from transformer_tpu.kernels.flash_attention import _MASK_GUARD, _MASKED, _compiler_params

_LANES = 128
_BLOCK_POSITIONS = 512


def _latent_kernel(
    table_ref,    # (N, nblk * pages) int32, SMEM
    lengths_ref,  # (N,) int32, SMEM
    q_ref,        # (1, H, W): absorbed, scaled queries
    pool_hbm,     # (num_blocks, B, W), HBM
    out_ref,      # (1, H, rank)
    buf, sems, slot_ref, m_scr, l_scr, acc_scr,
    *, block_tokens: int, pages: int, rank: int,
):
    tokens = pages * block_tokens
    s, n = pl.program_id(0), pl.num_programs(0)
    length = lengths_ref[s]

    def live_pages(seq_length, j):
        return jnp.clip(pl.cdiv(seq_length, block_tokens) - j * pages, 0, pages)

    def start(seq, j, slot):
        def copy_page(p, carry):
            pltpu.make_async_copy(
                pool_hbm.at[table_ref[seq, j * pages + p]], buf.at[slot, p], sems.at[slot]
            ).start()
            return carry

        jax.lax.fori_loop(0, live_pages(lengths_ref[seq], j), copy_page, 0)

    def wait(slot, count):
        def one(_, carry):
            page = buf.at[slot, 0]
            pltpu.make_async_copy(page, page, sems.at[slot]).wait()
            return carry

        jax.lax.fori_loop(0, count, one, 0)

    @pl.when(s == 0)
    def _prime():
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    m_scr[...] = jnp.full_like(m_scr, _MASKED)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    nblk = jnp.clip(pl.cdiv(length, tokens), 1, table_ref.shape[1] // pages)

    def block_step(j, slot):
        last = j + 1 == nblk
        nxt_seq = jnp.where(last, s + 1, s)

        @pl.when(nxt_seq < n)
        def _prefetch():
            start(nxt_seq, jnp.where(last, 0, j + 1), 1 - slot)

        wait(slot, live_pages(length, j))
        rows = buf[slot].reshape(tokens, buf.shape[-1])
        scores = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (H, tokens)
        pos = j * tokens + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(pos < length, scores, _MASKED)
        m_prev, l_prev = m_scr[...][:, :1], l_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(scores > _MASK_GUARD, jnp.exp(scores - m_new), 0.0)
        l_scr[...] = jnp.broadcast_to(alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(rows.dtype), rows[:, :rank], preferred_element_type=jnp.float32
        )
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, nblk, block_step, slot_ref[0])
    out_ref[0] = (acc_scr[...] / l_scr[...][:, :1]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "interpret"))
def paged_latent_attention(
    q: jax.Array,
    pool: jax.Array,
    table: jax.Array,
    lengths: jax.Array,
    *,
    rank: int,
    interpret: bool | None = None,
) -> jax.Array:
    """``q`` (N, H, W): each slot's absorbed queries, already scaled, zero in
    the lanes the pool pads; ``pool`` (num_blocks, B, W) latent rows, ``W`` in
    whole lane rows; ``table`` (N, nmax) int32 block table (entries past a
    slot's length are never dereferenced); ``lengths`` (N,) positions each
    slot holds, the row just written included (>= 1). Returns the contexts
    (N, H, rank) in q's dtype: ``softmax_s(q . row_s) @ row_s[:rank]``."""
    n, heads, width = q.shape
    _, block_tokens, pool_width = pool.shape
    if pool_width != width or width % _LANES or rank % _LANES or rank > width:
        raise ValueError(f"latent rows of {pool_width} lanes do not fit queries of {width} (rank {rank})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    nmax = table.shape[1]
    pages = max(1, min(_BLOCK_POSITIONS // block_tokens, nmax))
    nblk = -(-nmax // pages)
    table = jnp.pad(table.astype(jnp.int32), ((0, 0), (0, nblk * pages - nmax)))
    return pl.pallas_call(
        functools.partial(_latent_kernel, block_tokens=block_tokens, pages=pages, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((1, heads, width), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, rank), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, block_tokens, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),  # the buffer the next block is in
                pltpu.VMEM((heads, _LANES), jnp.float32),  # running max
                pltpu.VMEM((heads, _LANES), jnp.float32),  # normaliser
                pltpu.VMEM((heads, rank), jnp.float32),    # context accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, heads, rank), q.dtype),
        # Slots run in order: each starts the next one's first copies.
        compiler_params=_compiler_params(("arbitrary",)),
        interpret=bool(interpret),
        name="paged_latent_attention",
    )(table, lengths.astype(jnp.int32), q, pool)
