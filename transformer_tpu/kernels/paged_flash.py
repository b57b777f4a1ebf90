"""Fused paged-decode attention: a Pallas kernel over the KVPool block table.

The gather-path twins (``serve/scheduler.py`` ``_pool_step_paged`` /
``paged_attention(impl="xla")``) first materialize a dense-ordered view of
every slot's whole KV working set through ``gather_block_views`` — one extra
full HBM pass per decode step on a path that is already KV-bandwidth bound
(decode arithmetic intensity ~0.18 vs prefill's ~0.34, ``analysis costs``).
This kernel removes that pass: pages are read straight from the pool buffers
they live in, found through the scalar-prefetched block table.

The schedule. Work is done in **compute blocks** of many pages
(``_pages_per_block``: from ``block_tokens``, ``H_kv``, ``D``, the pool's
dtype and the table's width, with the double-buffered blocks inside
``_BUFFER_BUDGET`` of VMEM, no option), so the score and value products get a
wider dimension and the online-softmax update runs once a block, not once a
page. A table that is not a whole number of blocks wide is padded with the
sink id. A block is fetched one of two ways, by the pages' shape:

- **streamed** (pages that fill whole tiles: a minor axis in whole lane rows
  and a token's rows filling 32-bit sublanes, e.g. bf16 with 2 or 8 KV heads
  of 128, or 8 KV heads of 64 that the pool keeps two a lane row as 4 rows of
  128: ``heads_per_lane_row``): the grid walks the slots only; the pools stay
  in HBM
  (``memory_space=ANY``) and the kernel copies pages by hand into one of two
  VMEM buffers. A block aims for ``_BLOCK_POSITIONS`` = 512 key positions in
  sub-chunks of ``_CHUNK_POSITIONS`` = 128, because a block costs about
  0.6 us before its first position (the softmax update's chain of latencies,
  paid once for all the KV heads: ``_attend`` batches them);
  what it costs beyond that **follows the slot's live length**, every trip
  count read from ``lengths``: a slot runs ``ceil(length / block)`` loop steps
  and no more; of a block only the pages that hold a visible position are
  copied (those before the slot's length and, on a window layer, from its
  band's first page); the wait counts exactly the bytes that were started
  (a wait a sub-chunk, then a wait a page for the rest: a DMA semaphore
  counts bytes); and only the sub-chunks that hold such a page are unpacked
  and folded, as one softmax update of 128 to 512 positions, the width
  chosen by a branch. So a free slot (length ``S_q``, all-sink table) copies
  one page and folds one sub-chunk, and a window layer's band of 512 costs
  five sub-chunks in two folds wherever it starts: the block is NOT bounded
  by the band (blocks of a quarter of it read 301 us a call against 202 at
  the Laguna cell's shape, with a softmax update a head; PERF.md PR 32). The
  copies of block ``i+1`` (the slot's next, or the next slot's first) are
  started before the products of block ``i``. Head ``h`` of the token-major block (tokens, H_kv, D) is a
  sublane-strided read of rows ``h::H_kv`` (of the packed 32-bit words for
  bf16, the head then shifted out): no transpose. Where a lane row holds
  several heads the kernel's "head" IS the lane row: the wrapper hands it the
  queries of the heads that share the row, each zero outside its own head's
  lanes, and takes each head's lanes of the result, so the body below is the
  same for both and no lane is shuffled (the products carry zeros and the
  call is bound by bytes: 8 KV heads of 64 under 32 query heads, 128 slots
  of mean length 790, read 457 us a call this way against 3,895 on the tiled
  route and 253 for the bytes alone; PERF.md PR 34).
- **tiled** (every other shape: heads under a lane row that fill no whole
  lane rows and sublanes, e.g. an odd count of 64-wide heads or two of them
  in bf16, a head count that leaves part of a packed sublane empty, e.g. one
  bf16 head, and every int8 pool, whose scales' size-1 minor axis is such a
  shape; also a pool of narrow heads handed over BY heads): these pages cannot
  be sliced out of HBM by hand, so the same pool is handed to the call once
  per page of the block, each BlockSpec resolving its own table entry; the
  grid walks (slot, block), blocks past a slot's length skip their compute,
  and the block is transposed token-major -> head-major in VMEM. Its grid
  steps cost the same dead or alive (measured at the serving cells' shape:
  three times the streamed route's time), which is why it is only the
  fallback. Its block stays at ``_TILED_BLOCK_POSITIONS`` = 64 and is folded
  whole: no cell runs it since PR 34 (the LFM2 cell's 8 heads of 64 read
  3,895 us a call on it, fifteen times their bytes' time), 128 -> 512
  positions read 570 -> 520 us a call (PR 27), and at 512 the pool would be
  handed to the call 64 to 128 times.

What is not copied, and why that is harmless: a streamed block's pages at or
past the slot's length, or before its band, keep what the buffer held. The
buffers are zeroed once a call and only ever receive pool pages, so that is
finite, and those positions are masked to exactly zero weight: zero times a
finite number is zero. Their table entries are never dereferenced, so stale
or hostile ids there cannot matter. Whole blocks past the length are not
visited (streamed) or resolve to the sink, whose repeated index the pipeline
does not fetch again (tiled; inside a live tiled block the pages past the
length resolve to the sink too).

Fused into the block read:

- online-softmax accumulation across blocks (running max / normalizer / fp32
  output accumulator in VMEM scratch, exactly like ``flash_attention``'s
  k-axis walk);
- GQA head grouping: queries arrive folded as (N, H_kv, G*S_q, D) so one
  block read serves all ``G = H/H_kv`` query heads of its kv head — kv HBM
  traffic stays at the H_kv rate with no materialized repeat;
- int8 dequantization: quantized pools pass codes AND scales as separate
  inputs and the kernel dequantizes per block in VMEM — no bf16 pool copy is
  ever materialized in HBM;
- stale-row / sink masking from ``lengths``: per-row offset causality
  (query row i of sequence s sits at absolute position
  ``lengths[s] - S_q + i``) masks rejected-speculation leftovers, unwritten
  sink gathers, and lookahead rows in one predicate — which is also what
  lifts the gather-flash path's S_q = 1 restriction (verify rows S_q = k+1
  attend causally inside the row).

Numerics: scores are computed per (q-row, key) pair exactly like the XLA
oracle (dot in the compute dtype, cast to fp32, scaled), so masked positions
contribute exactly 0.0 either way; only the softmax normalizer/PV summation
ORDER differs (online, a block at a time, vs full-row), which perturbs low
fp32 bits — the serving tests pin answer-level byte identity, the kernel
tests pin per-dtype tolerances.

On non-TPU backends the kernel runs in Pallas interpret mode (the CPU suite's
path); ``interpret=None`` auto-detects, same convention as
``flash_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from transformer_tpu.kernels.flash_attention import (
    _MASK_GUARD,
    _MASKED,
    _compiler_params,
    _round_up,
)

# Lane width of the m/l scratch rows (replicate-to-lanes layout, same as the
# flash kernel's (block_q, 128) running-max/normalizer scratch).
_LANES = 128

# Key positions one compute block aims for on each route, the positions of a
# streamed block's sub-chunk, and the VMEM the two K/V block buffers
# (double-buffered, tile padding included) may take between them. Streamed:
# 512 is what the chip prefers at both serving deployments' mixes of lengths
# (PERF.md, PR 27 and PR 32: a call of 193 us at 64 reads 93 at the
# StarCoder2 cell's shape, 947 -> 265 and 461 -> 121 at the Laguna cell's two
# kinds of layer), and since the copies and the folds follow the live length
# in sub-chunks of 128 a free slot costs what it did at 64 (0.73 us; sub-chunks
# of 64 or 256 read the same or worse). Tiled: the fallback keeps 64.
_BLOCK_POSITIONS = 512
_TILED_BLOCK_POSITIONS = 64
_CHUNK_POSITIONS = 128
_BUFFER_BUDGET = 8 * 1024 * 1024


def _page_vmem_bytes(block_tokens: int, h_kv: int, d: int, itemsize: int) -> int:
    """VMEM one (block_tokens, H_kv, D) page occupies: the head axis padded to
    its sublane tile (a power of two of packed rows, 8 at most), D to lanes."""
    packing = max(1, 4 // itemsize)
    tile = packing
    while tile < h_kv and tile < 8 * packing:
        tile *= 2
    return block_tokens * _round_up(h_kv, tile) * _round_up(d, _LANES) * itemsize


def _pages_per_block(
    block_tokens: int, h_kv: int, d: int, itemsize: int, quantized: bool, nmax: int,
    streamed: bool,
) -> tuple[int, int]:
    """(pages one compute block holds, pages of one of its sub-chunks), from
    what the call can see: enough pages for the route's block positions, no
    wider than the table, and with the double-buffered K and V blocks (and an
    int8 pool's scales, whose size-1 minor axis pads to a whole lane row per
    head) inside ``_BUFFER_BUDGET``. Here the two routes' rules part: a
    streamed block aims for ``_BLOCK_POSITIONS`` in whole sub-chunks of
    ``_CHUNK_POSITIONS`` (a block no wider than one sub-chunk is its own);
    a tiled block aims for ``_TILED_BLOCK_POSITIONS`` and has no sub-chunks."""
    page = _page_vmem_bytes(block_tokens, h_kv, d, itemsize)
    if quantized:
        page += _page_vmem_bytes(block_tokens, h_kv, 1, 4)
    positions = _BLOCK_POSITIONS if streamed else _TILED_BLOCK_POSITIONS
    pages = max(1, min(positions // block_tokens, nmax))
    while pages > 1 and 2 * 2 * pages * page > _BUFFER_BUDGET:
        pages //= 2
    chunk = max(1, _CHUNK_POSITIONS // block_tokens)
    if not streamed or pages <= chunk:
        return pages, pages
    return pages // chunk * chunk, chunk


def _streamable(h_kv: int, d: int, dtype) -> bool:
    """Whether (..., H_kv, D) pages of ``dtype`` tile without padding: D in
    whole lane rows and a token's rows in whole 32-bit sublanes, a power of
    two up to 8 or a multiple of 8 of them. Only then can a page be sliced
    out of HBM by hand, and row ``h`` be read as rows ``h::H_kv`` of the
    block's (tokens*H_kv, D) view. The page is the pool's own: for heads kept
    several a lane row (``heads_per_lane_row``) ``h_kv`` counts the lane rows
    and ``d`` is 128. An int8 pool never is: its scales' size-1 minor axis
    cannot be sliced, and they decide for the codes."""
    itemsize = jnp.dtype(dtype).itemsize
    packing = 4 // itemsize
    if itemsize not in (2, 4) or d % _LANES or h_kv % packing:
        return False
    rows = h_kv // packing
    return rows in (1, 2, 4, 8) or rows % 8 == 0


def heads_per_lane_row(h_kv: int, d: int, dtype, quantized: bool = False) -> int:
    """How many of a token's KV heads the pool keeps in one row of 128 lanes
    (``ops.attention.init_block_pool``): ``128 // D`` where a head is narrower
    than a lane row, the heads fill whole rows and a page of those rows
    streams; 1, the (H_kv, D) page, for every other shape. A minor axis under
    128 is padded to a lane row on the chip, which a hand-made copy cannot
    slice and which cost the LFM2 cell a relayout of each pool every step
    (PERF.md PR 33); row-major, ``128 // D`` heads a row are the same bytes
    in the same order with nothing to pad."""
    if quantized or d >= _LANES or _LANES % d or (h_kv * d) % _LANES:
        return 1
    return _LANES // d if _streamable(h_kv * d // _LANES, _LANES, dtype) else 1


def streams(pool_shape, dtype, quantized: bool) -> bool:
    """Whether ``paged_flash_attention`` takes the streamed route over a K or
    V pool of this shape, (num_blocks, B, rows, lanes) as stored: the page it
    holds is what decides."""
    return not quantized and _streamable(*pool_shape[2:], dtype)


def _head_rows(pages_ref, h: int):
    """Head ``h`` of a (pages, B, H_kv, D) VMEM ref as (pages*B, D), by a
    sublane-strided read of its (tokens*H_kv, D) view; no transpose. A bf16
    pool packs two consecutive heads into one 32-bit sublane row: read the
    words and shift the wanted head out, as fp32 holding the same number."""
    pages, block_tokens, h_kv, d = pages_ref.shape
    tokens = pages * block_tokens
    flat = pages_ref.reshape(tokens * h_kv, d)
    if jnp.dtype(pages_ref.dtype).itemsize == 4:
        return flat[pl.ds(h, tokens, stride=h_kv), :]
    words = flat.bitcast(jnp.uint32)[pl.ds(h // 2, tokens, stride=h_kv // 2), :]
    bits = words & jnp.uint32(0xFFFF0000) if h % 2 else words << 16
    return pltpu.bitcast(bits, jnp.float32)


def _heads(groups) -> list[jax.Array]:
    """A block's H_kv heads as (tokens, D) arrays. ``groups`` are the VMEM
    refs, each (pages, B, H_kv, D), that hold the block's pages in order."""
    _, _, h_kv, d = groups[0].shape
    if len(groups) == 1 and _streamable(h_kv, d, groups[0].dtype):
        return [_head_rows(groups[0], h) for h in range(h_kv)]
    # Shapes the strided read cannot serve (D under a lane row, a head count
    # that leaves a packed sublane part empty, an int8 pool's scales):
    # transpose the block token-major -> head-major instead.
    x = jnp.concatenate([g[...].reshape(-1, h_kv, d) for g in groups])
    x = jnp.swapaxes(x, 0, 1)
    return [x[h] for h in range(h_kv)]


def _block_heads(groups, scale_groups, dtype) -> list[jax.Array]:
    """``_heads`` in the compute dtype, an int8 pool dequantised on the way:
    codes * per-(position, head) scale, in the compute dtype — the same round
    trip the dense cache's read path applies, so values match bit-for-bit."""
    heads = _heads(groups)
    if scale_groups is None:
        return [x.astype(dtype) for x in heads]
    return [
        x.astype(jnp.float32).astype(dtype) * sc.astype(dtype)
        for x, sc in zip(heads, _heads(scale_groups))
    ]


def _attend(q_ref, ks, vs, visible, m_scr, l_scr, acc_scr, scale: float):
    """Fold one compute block into the running softmax: ONE update over all
    the KV heads' rows (the products are batched over the heads), so its
    chain of latencies is paid once a block, not once a head and block (an
    update a head read 378 us a call against 265 at the Laguna cell's 8 KV
    heads, 106 against 93 at StarCoder2's 2), and the kernel's body holds a
    third of the operations to trace: with an update a head and four fold
    widths, tracing cost the Laguna cell 7.6 s of warm set-up."""
    dtype = q_ref.dtype
    k, v = jnp.stack(ks), jnp.stack(vs)  # (H_kv, tokens, D)
    # Mosaic accepts only a 32-bit matmul accumulator, so accumulate in fp32
    # and round where the XLA oracle's compute-dtype dot rounds (its result)
    # before the fp32 scale — per (row, key) values stay independent of
    # blocking and match the gather path.
    scores = jax.lax.dot_general(
        q_ref[0], k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ).astype(dtype).astype(jnp.float32) * scale  # (H_kv, GS, tokens)
    scores = jnp.where(visible[None], scores, _MASKED)

    m_prev = m_scr[...][:, :, :1]  # (H_kv, GS, 1)
    l_prev = l_scr[...][:, :, :1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # Exp-guard: fully-masked entries must contribute exactly 0 (not
    # exp(_MASKED - m) underflow noise) so masked-column parity with the
    # XLA softmax holds exactly.
    p = jnp.where(scores > _MASK_GUARD, jnp.exp(scores - m_new), 0.0)
    l_scr[...] = jnp.broadcast_to(
        alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_scr.shape
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )


def _paged_kernel(
    # scalar-prefetch refs
    table_ref,    # (N, nblk * pages) int32 — SMEM
    lengths_ref,  # (N,) int32 — SMEM
    # inputs
    q_ref,        # (1, H_kv, G*S_q, D) — queries folded by kv group
    *rest,
    s_q: int,
    block_tokens: int,
    pages: int,
    chunk: int,
    scale: float,
    quantized: bool,
    streamed: bool,
    window: int,
):
    """Two ways to the same block. ``streamed``: grid (N,), the pools stay in
    HBM and the kernel copies a block's live pages into one of two VMEM
    buffers, as many blocks as the slot's length needs, and folds the
    sub-chunks of ``chunk`` pages that hold them. Otherwise: grid (N, nblk),
    every page of the block its own BlockSpec-fed input."""
    tokens = pages * block_tokens
    s = pl.program_id(0)
    length = lengths_ref[s]
    gs = q_ref.shape[2]

    def band_start(seq_length):
        # The first position any of the slot's query rows may see (row 0's).
        return jnp.maximum(seq_length - s_q - window + 1, 0)

    def first_block(seq_length):
        return band_start(seq_length) // tokens if window else 0

    def init():
        m_scr[...] = jnp.full_like(m_scr, _MASKED)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(first, groups):
        """Fold the key positions ``first`` onwards that ``groups`` hold."""
        k, v = groups[0], groups[1]
        k_sc, v_sc = (groups[2], groups[3]) if quantized else (None, None)
        k_heads = _block_heads(k, k_sc, q_ref.dtype)
        v_heads = _block_heads(v, v_sc, q_ref.dtype)
        # Per-row offset causality: folded row r = g * S_q + i holds query
        # index i = r % S_q at absolute position length - S_q + i; a pool
        # position is visible iff <= that. This one predicate hides stale
        # rows (positions >= length), pages that were not copied, and — for
        # verify rows — each lookahead token's future.
        shape = (gs, k_heads[0].shape[0])
        q_pos = (length - s_q) + jax.lax.broadcasted_iota(jnp.int32, shape, 0) % s_q
        pos = first + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        visible = pos <= q_pos
        if window:
            visible &= pos > q_pos - window
        _attend(q_ref, k_heads, v_heads, visible, m_scr, l_scr, acc_scr, scale)

    def finalize():
        out_ref[0] = (acc_scr[...] / l_scr[...][:, :, :1]).astype(out_ref.dtype)

    if not streamed:
        pools = 4 if quantized else 2  # K, V and an int8 pool's two scales
        page_refs = rest[: pools * pages]
        out_ref, m_scr, l_scr, acc_scr = rest[pools * pages :]
        j, nblk = pl.program_id(1), pl.num_programs(1)
        pl.when(j == 0)(init)

        # Blocks that start at or past the slot's length, or end before its
        # band, hold no visible position: no compute, and their pages all
        # resolve to the sink.
        alive = j * tokens < length
        if window:
            alive &= j >= first_block(length)

        @pl.when(alive)
        def _block():
            fold(j * tokens, [
                page_refs[i * pages : (i + 1) * pages] for i in range(pools)
            ])

        pl.when(j == nblk - 1)(finalize)
        return

    k_hbm, v_hbm, out_ref, k_buf, v_buf, sems, slot_ref, m_scr, l_scr, acc_scr = rest
    streams = ((k_hbm, k_buf), (v_hbm, v_buf))
    n = pl.num_programs(0)

    def live_pages(seq_length, j):
        """The pages of block ``j`` that hold a position the slot can see, as
        ``[lo, hi)`` counted from the block's first page: those before the
        slot's length and, on a window layer, from its band's first page."""
        hi = jnp.clip(pl.cdiv(seq_length, block_tokens) - j * pages, 0, pages)
        if not window:
            return 0, hi
        lo = jnp.clip(band_start(seq_length) // block_tokens - j * pages, 0, pages)
        return lo, hi

    def start(seq, j, slot):
        # One DMA per live page and pool; no table entry past a slot's length
        # (or before its band) is dereferenced, so stale or hostile ids there
        # cannot matter. A rolled loop: unrolled, 32 pages' copies at each of
        # two call sites, 30 layers a program, cost the host 6 s of lowering
        # in every process for 0.02 ms a layer on the chip.
        lo, hi = live_pages(lengths_ref[seq], j)

        def copy_page(p, carry):
            block = table_ref[seq, j * pages + p]
            for i, (hbm, buf) in enumerate(streams):
                pltpu.make_async_copy(
                    hbm.at[block], buf.at[slot, p], sems.at[slot, i]
                ).start()
            return carry

        jax.lax.fori_loop(lo, hi, copy_page, 0)

    def wait(slot, count):
        # A DMA semaphore counts bytes: ``count`` pages were started, so wait
        # for as many bytes, a sub-chunk at a time and the rest a page at a
        # time (a wait a page throughout read 113 us a call against 106 at
        # the serving cell's shape).
        def wait_for(size):
            def body(_, carry):
                for i, (_, buf) in enumerate(streams):
                    part = buf.at[slot, pl.ds(0, size)]
                    pltpu.make_async_copy(part, part, sems.at[slot, i]).wait()
                return carry

            return body

        jax.lax.fori_loop(0, count // chunk, wait_for(chunk), 0)
        jax.lax.fori_loop(0, count % chunk, wait_for(1), 0)

    @pl.when(s == 0)
    def _prime():
        # Pages that are not copied keep what the buffer held: make that
        # finite once (the pool holds finite numbers, and a masked position's
        # zero weight times a finite value is zero).
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        start(0, first_block(lengths_ref[0]), 0)

    init()
    # A slot always runs its first block (lengths >= S_q >= 1 in every
    # caller), which keeps the chain of prefetches free of conditions, and
    # never walks past its table.
    nblk = jnp.clip(pl.cdiv(length, tokens), 1, table_ref.shape[1] // pages)

    def fold_chunks(j, slot, c_lo, width):
        first_page = c_lo * chunk
        fold(
            (j * pages + first_page) * block_tokens,
            [[buf.at[slot, pl.ds(first_page, width * chunk)]] for _, buf in streams],
        )

    def block_step(j, slot):
        # Start the next block's copies — this slot's, or the next slot's
        # first — into the other buffer, then wait for this block's.
        last = j + 1 == nblk
        nxt_seq = jnp.where(last, s + 1, s)

        @pl.when(nxt_seq < n)
        def _prefetch():
            # (The clamp keeps the read of the next slot's length in range
            # where there is no next slot; the copy is then not started.)
            nxt_first = first_block(lengths_ref[jnp.minimum(s + 1, n - 1)])
            start(nxt_seq, jnp.where(last, nxt_first, j + 1), 1 - slot)

        lo, hi = live_pages(length, j)
        c_lo, c_hi = lo // chunk, pl.cdiv(hi, chunk)
        wait(slot, hi - lo)
        # The block's live sub-chunks are folded as ONE softmax update,
        # whatever their number: a branch for each width. (A fold a sub-chunk
        # in a rolled loop pays the update's chain of latencies each time:
        # 144 us a call against 106 at the serving cell's shape, read with an
        # update a head.)
        for width in range(1, pages // chunk + 1):
            pl.when(c_hi - c_lo == width)(
                functools.partial(fold_chunks, j, slot, c_lo, width)
            )
        return 1 - slot

    # On a window layer the walk starts at the block that holds the band's
    # first position: work follows min(length, window), not the length.
    slot_ref[0] = jax.lax.fori_loop(first_block(length), nblk, block_step, slot_ref[0])
    finalize()


# Jitted, so that a program's many call sites of one shape (a layer each) are
# traced and lowered once: the kernel's body is four fold widths long, and
# traced and lowered a layer at a time it cost a 30-layer step 4.6 s in every
# process (1.9 s before the widths; 0.3 s this way), warm or cold.
@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_flash_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    table: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    window: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused attention over a paged KV pool, blocks read in place.

    Args:
      q: (N, S_q, H, D) queries; row ``s`` sits at absolute positions
        ``lengths[s] - S_q .. lengths[s] - 1`` (decode S_q = 1; speculative
        verify S_q = k + 1, causal inside the row).
      k_pool, v_pool: (num_blocks, B, H_kv, D) pool buffers — bf16/fp32
        values, or int8 codes when ``k_scale``/``v_scale`` are given — or
        the same bytes as (num_blocks, B, H_kv * D // 128, 128), heads
        narrower than a lane row kept ``128 // D`` a row (what
        ``init_block_pool`` allocates where ``heads_per_lane_row`` says so):
        told apart by the minor axis against ``q``'s, and streamed.
      table: (N, nmax) int32 block table (``kernels/kv_pool.KVPool``);
        entries past a slot's owned count point at the pinned sink block 0.
      lengths: (N,) int32 valid KV length per sequence (including the S_q
        rows just written for this forward).
      k_scale, v_scale: (num_blocks, B, H_kv, 1) fp32 dequant scales for
        int8 pools (``init_block_pool(quantize=True)`` storage layout); the
        kernel consumes codes + scales directly.
      window: static causal band (0 = none): a query at position p sees
        positions ``p - window + 1 .. p``. Blocks that end before a slot's
        band are neither copied nor computed, on both routes; the positions
        before the band inside its first block are masked (and, streamed, the
        pages and sub-chunks wholly before it neither copied nor folded).
      interpret: Pallas interpret mode; default True off-TPU (same
        convention as ``flash_attention``).

    Returns (N, S_q, H, D) attention outputs in q's dtype.
    """
    n, s_q, h, d = q.shape
    num_blocks, block_tokens, rows, d_k = k_pool.shape
    if (k_scale is None) != (v_scale is None):
        raise ValueError("int8 pools need BOTH k_scale and v_scale")
    quantized = k_scale is not None
    # A pool kept several heads a lane row says so by its minor axis.
    per_row = 1 if d_k == d else _LANES // d
    if d_k != d * per_row or (per_row > 1 and quantized):
        raise ValueError(f"head_dim mismatch: q {d} vs pool {d_k}")
    h_kv = rows * per_row
    if h % h_kv:
        raise ValueError(f"query heads {h} must be a multiple of kv heads {h_kv}")
    # Mosaic packs the pool's token axis into (sublane, lane) vregs whose
    # sublane count depends on the element width: 8 rows for fp32, 16 for
    # bf16, 32 for int8. A block_tokens that neither divides nor is a
    # multiple of that count forces a mid-vreg block boundary the lowering
    # rejects with an opaque shape error — fail loudly at call time instead.
    sublane = {4: 8, 2: 16, 1: 32}.get(jnp.dtype(k_pool.dtype).itemsize, 8)
    if block_tokens % sublane and sublane % block_tokens:
        raise ValueError(
            f"block_tokens {block_tokens} is incompatible with the "
            f"{jnp.dtype(k_pool.dtype).name} pool's native sublane tiling "
            f"({sublane}): it must divide {sublane} or be a multiple of it"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    group = h // h_kv
    gs = group * s_q
    nmax = table.shape[1]
    table = table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    # Fold queries by kv group: (N, S_q, H, D) -> (N, H_kv, G*S_q, D) with
    # folded row r = g*S_q + i (head h = kv_head*G + g, query index i) — one
    # pool block read serves every query head of its kv head.
    qf = (
        q.transpose(0, 2, 1, 3)
        .reshape(n, h_kv, group, s_q, d)
        .reshape(n, h_kv, gs, d)
    )
    if per_row > 1:
        # Heads kept `per_row` to a lane row: the kernel's "head" is a lane row,
        # its queries the rows of the heads that live there, each zero but on
        # its own head's lanes (head j of a row, folded row r, sits at row
        # j*G*S_q + r). The score product then picks that head's keys out of
        # the row, the value product fills all 128 lanes and the head's are
        # taken below: the same sums with zeros added, no lane shuffled, each
        # row of keys and values read once for the heads that share it.
        own = jnp.eye(per_row, dtype=bool)[None, None, :, None, :, None]
        qf = jnp.where(own, qf.reshape(n, rows, per_row, gs, 1, d), 0)
        qf = qf.reshape(n, rows, per_row * gs, d_k)
    q_rows = per_row * gs

    # The compute block: `pages` table entries a step, chosen from the
    # shapes. A table that is not a whole number of blocks wide is padded
    # with the sink id (no entry past a slot's length is ever dereferenced).
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    streamed = streams(k_pool.shape, k_pool.dtype, quantized)
    pages, chunk = _pages_per_block(
        block_tokens, rows, d_k, itemsize, quantized, nmax, streamed
    )
    nblk = -(-nmax // pages)
    if nblk * pages > nmax:
        table = jnp.pad(table, ((0, 0), (0, nblk * pages - nmax)))
    pools = [k_pool, v_pool] + ([k_scale, v_scale] if quantized else [])

    def _at_seq(s, *_):
        return (s, 0, 0, 0)

    def _at_page(p):
        def index(s, j, table_ref, lengths_ref):
            page = j * pages + p
            live = page * block_tokens < lengths_ref[s]
            if window:
                # Pages that end before the band resolve to the sink too.
                live &= (page + 1) * block_tokens > lengths_ref[s] - s_q - window + 1
            return (jnp.where(live, table_ref[s, page], 0), 0, 0, 0)

        return index

    q_spec = pl.BlockSpec((1, rows, q_rows, d_k), _at_seq)
    softmax_state = [
        pltpu.VMEM((rows, q_rows, _LANES), jnp.float32),  # running max
        pltpu.VMEM((rows, q_rows, _LANES), jnp.float32),  # normalizer
        pltpu.VMEM((rows, q_rows, d_k), jnp.float32),     # output accumulator
    ]
    if streamed:
        # The pools stay in HBM as they are; the kernel copies the pages it
        # needs into two buffers a pool: one being filled, one being used.
        grid = (n,)
        in_specs = [q_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
        inputs = [qf, k_pool, v_pool]
        scratch = [
            pltpu.VMEM((2, pages, *k_pool.shape[1:]), k_pool.dtype),
            pltpu.VMEM((2, pages, *v_pool.shape[1:]), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # (buffer, K or V)
            pltpu.SMEM((1,), jnp.int32),      # the buffer the next block is in
        ] + softmax_state
    else:
        # Every page of the block is an input of its own: the same pool handed
        # over `pages` times, each BlockSpec resolving its own table entry.
        grid = (n, nblk)
        in_specs = [q_spec] + [
            pl.BlockSpec((1, *x.shape[1:]), _at_page(p))
            for x in pools for p in range(pages)
        ]
        inputs = [qf] + [x for x in pools for _ in range(pages)]
        scratch = softmax_state

    kernel = functools.partial(
        _paged_kernel,
        s_q=s_q,
        block_tokens=block_tokens,
        pages=pages,
        chunk=chunk,
        scale=d**-0.5,
        quantized=quantized,
        streamed=streamed,
        window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        # Streamed slots run in order: each starts the next one's first copies.
        compiler_params=_compiler_params(
            ("arbitrary",) if streamed else ("parallel", "arbitrary")
        ),
        interpret=bool(interpret),
        name="paged_flash_attention",
    )(table, lengths, *inputs)
    if per_row > 1:
        # Head j of a lane row: its own query rows, its own lanes.
        out = out.reshape(n, rows, per_row, gs, per_row, d)
        out = jnp.stack([out[:, :, j, :, j] for j in range(per_row)], axis=2)
    # Unfold (N, H_kv, G*S_q, D) -> (N, S_q, H, D).
    return (
        out.reshape(n, h_kv, group, s_q, d)
        .reshape(n, h, s_q, d)
        .transpose(0, 2, 1, 3)
    )
