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
shape and run as a software pipeline. The grid walks the slots; the pool
stays in HBM (``memory_space=ANY``); the kernel copies the live pages of each
compute block (``_BLOCK_POSITIONS`` = 512 positions) into one of THREE VMEM
buffers, in a walk that runs slot after slot, block after block: a slot has
``ceil(length / block)`` blocks and copies only the pages before its length,
so no table entry past a slot's length is dereferenced. A page is
(block_tokens, lanes): tokens down the sublanes, so a block is a plain
(positions, lanes) matrix and nothing is transposed or unpacked.

The loop step that finishes block ``j`` of the walk

1. starts the copies of block ``j + 2`` (unrolled where the block is whole, a
   rolled loop over the live pages of a partial one),
2. waits ONCE for block ``j + 1``'s bytes (a DMA semaphore counts bytes: a
   whole block in one wait; a partial one by sub-chunks of 128 positions,
   then by pages),
3. takes block ``j + 1``'s score product ``q . rowsᵀ`` (float32) into a value
   and, in the same straight-line region, finishes block ``j`` from the
   scores the step before left in scratch: the mask, one online-softmax
   update for all heads (running max, normaliser and a float32 accumulator of
   ``rank`` lanes) and the value product ``p . rows[:, :rank]``,
4. leaves block ``j + 1``'s scores in the scratch for the next step.

So block ``j + 1``'s score product on the MXU runs under block ``j``'s softmax
on the vector unit, and block ``j + 2``'s bytes arrive under both; nothing
of a step waits on the step before it but the scores in scratch (on a TPU
v5e at the latent cells' decode shapes, 4.2 ns a position as a serial chain,
2.5 pipelined, against 1.6 for the bytes the pool stores). The walk
crosses a slot's end without draining: the step that finishes a slot's last
block scores the next slot's first with the next slot's queries (a second
BlockSpec of ``q``, one slot ahead), and the copies two ahead may be the next
slot's (or, after a slot of one block, the one after). Only the call's first
block is scored before the loop, and its last finished alone. Pages that are
not copied keep what the buffer held: the buffers are zeroed once a call and
only ever receive pool rows, so that is finite, and those positions are
masked to zero weight. Scores stay float32 (they are not rounded to the
pool's dtype), as the XLA form in ``ops/mla.py`` keeps them.

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
_CHUNK_POSITIONS = 128


def _latent_kernel(
    table_ref,    # (N, nblk * pages) int32, SMEM
    lengths_ref,  # (N,) int32, SMEM
    q_ref,        # (1, H, W): this slot's absorbed, scaled queries
    qn_ref,       # (1, H, W): the next slot's (the last slot's own for the last)
    pool_hbm,     # (num_blocks, B, W), HBM
    out_ref,      # (1, H, rank)
    buf, sems, walk_ref, scores_scr, m_scr, l_scr, acc_scr,
    *, block_tokens: int, pages: int, rank: int,
):
    tokens = pages * block_tokens
    chunk = max(1, min(pages, _CHUNK_POSITIONS // block_tokens))
    s, n = pl.program_id(0), pl.num_programs(0)
    length = lengths_ref[s]

    def blocks(seq):
        # A slot always has its first block (lengths >= 1 in every caller),
        # and never walks past its table.
        return jnp.clip(pl.cdiv(lengths_ref[seq], tokens), 1, table_ref.shape[1] // pages)

    def live_pages(seq, j):
        return jnp.clip(pl.cdiv(lengths_ref[seq], block_tokens) - j * pages, 0, pages)

    def after(b):
        # The three buffers in turn.
        return jnp.where(b == 2, 0, b + 1)

    def start(seq, j, b):
        def copy_page(p, carry):
            pltpu.make_async_copy(
                pool_hbm.at[table_ref[seq, j * pages + p]], buf.at[b, p], sems.at[b]
            ).start()
            return carry

        # A whole block's copies are issued unrolled, a partial one's in a
        # rolled loop: on a TPU v5e at the latent cells' shapes the rolled
        # issue of 32 pages alone read 2.2 ns a position, and the kernel 3.3
        # with it against 2.5 unrolled (PERF.md, section 6).
        live = live_pages(seq, j)

        @pl.when(live == pages)
        def _whole():
            for p in range(pages):
                copy_page(p, 0)

        @pl.when(live < pages)
        def _partial():
            jax.lax.fori_loop(0, live, copy_page, 0)

    def wait(seq, j, b):
        count = live_pages(seq, j)

        def wait_for(size):
            part = buf.at[b, pl.ds(0, size)]
            pltpu.make_async_copy(part, part, sems.at[b]).wait()

        def wait_each(size):
            def body(_, carry):
                wait_for(size)
                return carry

            return body

        pl.when(count == pages)(lambda: wait_for(pages))

        @pl.when(count < pages)
        def _partial():
            jax.lax.fori_loop(0, count // chunk, wait_each(chunk), 0)
            jax.lax.fori_loop(0, count % chunk, wait_each(1), 0)

    def score(q, b):
        rows = buf[b].reshape(tokens, buf.shape[-1])
        return jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (H, tokens)

    def finish(j, b, scores):
        # Block j of this slot, from its scores: mask, softmax update, values.
        pos = j * tokens + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(pos < length, scores, _MASKED)
        m_prev, l_prev = m_scr[...][:, :1], l_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(scores > _MASK_GUARD, jnp.exp(scores - m_new), 0.0)
        l_scr[...] = jnp.broadcast_to(alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        values = buf[b, :, :, :rank].reshape(tokens, rank)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(values.dtype), values, preferred_element_type=jnp.float32
        )

    def second(seq):
        # The block after a slot's first in the walk: its second, or the next
        # slot's first (slot + 1, block 0).
        one_block = blocks(jnp.minimum(seq, n - 1)) == 1
        return jnp.where(one_block, seq + 1, seq), jnp.where(one_block, 0, 1)

    @pl.when(s == 0)
    def _prime():
        # Pages that are not copied keep what the buffer held: make that
        # finite once (the pool holds finite numbers, and a masked position's
        # zero weight times a finite value is zero).
        buf[...] = jnp.zeros_like(buf)
        walk_ref[0] = 0
        start(0, 0, 0)
        seq, j = second(0)
        pl.when(seq < n)(lambda: start(seq, j, 1))
        wait(0, 0, 0)
        scores_scr[...] = score(q_ref[0], 0)

    m_scr[...] = jnp.full_like(m_scr, _MASKED)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    nblk = blocks(s)

    def two_ahead(j):
        # The block two past this slot's block j in the walk: this slot's
        # j + 2, the next slot's first, or the block after the next slot's
        # first.
        past = j + 2 - nblk
        seq, k = second(s + 1)
        return (jnp.where(past < 0, s, jnp.where(past == 0, s + 1, seq)),
                jnp.where(past < 0, j + 2, jnp.where(past == 0, 0, k)))

    def step(j, b, q, seq1, j1):
        # Finish block j (in buffer b) while block j1 of slot seq1, the next
        # in the walk, is scored with the queries q and the one after it is
        # on its way. Returns the buffer of block (seq1, j1).
        b1 = after(b)
        seq2, j2 = two_ahead(j)
        pl.when(seq2 < n)(lambda: start(seq2, j2, after(b1)))
        wait(seq1, j1, b1)
        scores = score(q, b1)
        finish(j, b, scores_scr[...])
        scores_scr[...] = scores
        return b1

    last = jax.lax.fori_loop(0, nblk - 1, lambda j, b: step(j, b, q_ref[0], s, j + 1), walk_ref[0])

    @pl.when(s + 1 < n)
    def _carry():
        walk_ref[0] = step(nblk - 1, last, qn_ref[0], s + 1, 0)

    pl.when(s + 1 == n)(lambda: finish(nblk - 1, last, scores_scr[...]))
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
                pl.BlockSpec((1, heads, width), lambda s, *_: (jnp.minimum(s + 1, n - 1), 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, rank), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((3, pages, block_tokens, width), pool.dtype),
                pltpu.SemaphoreType.DMA((3,)),
                pltpu.SMEM((1,), jnp.int32),  # the buffer the next slot's first block is in
                pltpu.VMEM((heads, pages * block_tokens), jnp.float32),  # the next block's scores
                pltpu.VMEM((heads, _LANES), jnp.float32),  # running max
                pltpu.VMEM((heads, _LANES), jnp.float32),  # normaliser
                pltpu.VMEM((heads, rank), jnp.float32),    # context accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, heads, rank), q.dtype),
        # Slots run in order: each scores the next one's first block.
        compiler_params=_compiler_params(("arbitrary",)),
        interpret=bool(interpret),
        name="paged_latent_attention",
    )(table, lengths.astype(jnp.int32), q, q, pool)
