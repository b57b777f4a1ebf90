"""Paged KV memory: one device-resident block pool for slots and prefixes.

The serving tier's dense layout reserves ``max_total`` KV rows per slot
whether or not a token ever lands there — the cost model
(``analysis/costs.py``) prices that as the repo's largest memory waste
(bf16 128 B/token, GQA 64, int8 96, per slot, per layer). This module is
the vLLM-style alternative: ONE pool of fixed-size token-aligned blocks
per layer, shared by every slot, addressed through per-slot block tables.
Resident KV becomes proportional to *used* tokens, a prefix-cache hit
becomes block-table aliasing (no host round trip), and speculative
rollback becomes a table truncation that returns blocks to the free list.

Split of responsibilities:

- :class:`KVPool` — the HOST-side allocator: free-list alloc/free,
  per-block refcounts (a block may be shared by several slot tables plus
  the prefix cache's device tier), copy-on-write splits for shared blocks
  about to be written, per-slot table rows, and the cached device upload
  of the table. Pure numpy + lists under ONE lock (the TPA1xx concurrency
  rules lint this module; ``analysis/schedules.py kv_pool_contention``
  explores two-thread interleavings against exactly this guard, and a
  real-thread hammer test rides tier-1).
- Device-side pure functions (``gather_block_views`` here,
  ``paged_attention`` in ``kernels/flash_attention.py``, the jitted
  ``_pool_*_paged`` programs in ``serve/scheduler.py``) — functional jax
  code that threads the pool buffers through jit like any other cache
  pytree. The allocator never touches device memory; the jitted programs
  never see the free list.

Block 0 is the SINK: permanently pinned, never allocated, never aliased.
Unmapped table entries point at it (gathered sink rows land at positions
the offset causal mask hides) and free slots' steps write into it (their
writes must land somewhere fixed that no live slot can own — the paged
twin of the dense pool's "free slots step too" invariant).

Byte parity with the dense layout is structural: the paged decode step
gathers each slot's blocks into a dense-ordered view, runs the SAME
vmapped model forward the dense pool runs (same shapes, same mask, same
storage-layout round trip), and scatters the newly written rows back —
so greedy AND seeded-sampled answers are bit-identical paged vs dense
(tests/test_kv_pool.py pins this across bf16/int8/GQA, composed with
chunked prefill, speculative decoding, and prefix reuse).
"""

from __future__ import annotations

import threading

import numpy as np


class KVPoolExhausted(RuntimeError):
    """The free list cannot satisfy an allocation. Admission-time callers
    degrade this to a transient (retryable) error after asking the prefix
    cache's device tier to spill; decode-time callers preempt the slot
    with a structured ``resource`` answer."""


class KVPool:
    """Host-side allocator for a ``num_blocks`` x ``block_tokens`` pool.

    Owns the per-slot block tables (``num_slots`` rows of
    ``slot_blocks`` entries each): ``table[s, j]`` is the pool block
    holding slot ``s``'s positions ``[j*B, (j+1)*B)``; entries at or past
    the slot's allocated count point at the sink. Every live table entry
    holds one reference on its block; the prefix cache's device tier takes
    additional references via :meth:`retain`. A block returns to the free
    list exactly when its refcount reaches zero — refcounts never go
    negative and a block is never double-freed (``check_consistency``
    re-derives the whole accounting; the schedule checker and the hammer
    test assert it under contention).

    Threading contract: ONE ``threading.Lock`` guards the free list, the
    refcounts, the tables, and the stats. The device-table upload cache
    (:meth:`table_device`) is refreshed under the same lock.
    """

    SINK = 0

    def __init__(
        self, num_blocks: int, block_tokens: int,
        num_slots: int, slot_blocks: int,
    ):
        if num_blocks < 2:
            raise ValueError(
                f"kv pool needs >= 2 blocks (sink + 1), got {num_blocks}"
            )
        if block_tokens < 1:
            raise ValueError(f"block_tokens must be >= 1, got {block_tokens}")
        self.num_blocks = num_blocks
        self.block_tokens = block_tokens
        self.num_slots = num_slots
        self.slot_blocks = slot_blocks
        self._lock = threading.Lock()
        self._refs = np.zeros((num_blocks,), np.int32)
        self._refs[self.SINK] = 1  # permanently pinned
        # LIFO free list (ids 1..num_blocks-1): recently freed blocks are
        # reused first, keeping the working set hot.
        self._free = list(range(num_blocks - 1, 0, -1))
        self.table = np.zeros((num_slots, slot_blocks), np.int32)
        self._owned = np.zeros((num_slots,), np.int32)
        self._dirty = True
        self._table_dev = None
        self.stats = {
            "allocated_blocks": 0, "freed_blocks": 0, "cow_splits": 0,
            "alias_blocks": 0,
        }

    # ---- accounting --------------------------------------------------------

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_blocks(self) -> int:
        with self._lock:
            return self.num_blocks - 1 - len(self._free)

    def refs(self, bid: int) -> int:
        with self._lock:
            return int(self._refs[bid])

    def slot_tokens(self, slot: int) -> int:
        """Token capacity currently backed by real blocks for ``slot``."""
        with self._lock:
            return int(self._owned[slot]) * self.block_tokens

    # ---- alloc / free ------------------------------------------------------

    def _pop_free(self) -> int:
        # caller holds the lock
        if not self._free:
            raise KVPoolExhausted(
                f"kv pool exhausted: {self.num_blocks - 1} blocks all "
                "referenced (live slots + device-resident prefixes)"
            )
        bid = self._free.pop()
        self._refs[bid] = 1
        self.stats["allocated_blocks"] += 1
        return bid

    def _release(self, bid: int) -> bool:
        # caller holds the lock; returns True when the block was freed
        if bid == self.SINK:
            return False
        self._refs[bid] -= 1
        if self._refs[bid] < 0:  # pragma: no cover - guarded by tests
            raise AssertionError(f"negative refcount on block {bid}")
        if self._refs[bid] == 0:
            self._free.append(bid)
            self.stats["freed_blocks"] += 1
            return True
        return False

    def ensure(self, slot: int, tokens: int) -> bool:
        """Grow ``slot``'s table to cover ``tokens`` positions with OWNED
        (refcount-1) blocks appended past the current end. Returns True
        when the table changed. Raises :class:`KVPoolExhausted` (leaving
        already-appended blocks in place — the caller's free_slot/truncate
        rolls back) when the free list runs dry."""
        need = min(-(-tokens // self.block_tokens), self.slot_blocks)
        changed = False
        with self._lock:
            while self._owned[slot] < need:
                bid = self._pop_free()
                self.table[slot, self._owned[slot]] = bid
                self._owned[slot] += 1
                changed = True
            if changed:
                self._dirty = True
        return changed

    def extend(self, slot: int, bid: int | None = None) -> tuple[int, int]:
        """Append ONE block at the slot's next table position: alias an
        existing block (``bid`` given — takes a reference; the prefix
        cache's device-resident hit path) or allocate a fresh one.
        Returns ``(position, block_id)``."""
        with self._lock:
            j = int(self._owned[slot])
            if j >= self.slot_blocks:
                raise ValueError(
                    f"slot {slot} table full ({self.slot_blocks} blocks)"
                )
            if bid is None:
                bid = self._pop_free()
            else:
                if bid == self.SINK or self._refs[bid] <= 0:
                    raise ValueError(f"cannot alias dead block {bid}")
                self._refs[bid] += 1
                self.stats["alias_blocks"] += 1
            self.table[slot, j] = bid
            self._owned[slot] += 1
            self._dirty = True
            return j, int(bid)

    def truncate(self, slot: int, tokens: int) -> int:
        """Shrink ``slot``'s table to the blocks covering ``tokens``
        positions, releasing the rest (speculative rollback = table
        truncation; freed blocks return to the pool unless the device
        tier still references them). Returns blocks released from the
        table."""
        keep = -(-tokens // self.block_tokens) if tokens > 0 else 0
        released = 0
        with self._lock:
            while self._owned[slot] > keep:
                j = int(self._owned[slot]) - 1
                self._release(int(self.table[slot, j]))
                self.table[slot, j] = self.SINK
                self._owned[slot] = j
                released += 1
            if released:
                self._dirty = True
        return released

    def free_slot(self, slot: int) -> int:
        """Retire ``slot``: drop every table reference (aliased prefix
        blocks survive under the device tier's refs) and reset the row to
        the sink."""
        return self.truncate(slot, 0)

    # ---- sharing -----------------------------------------------------------

    def retain(self, bid: int) -> None:
        """External pin (the prefix cache's device tier adopting a
        retiring slot's block)."""
        with self._lock:
            if bid == self.SINK or self._refs[bid] <= 0:
                raise ValueError(f"cannot retain dead block {bid}")
            self._refs[bid] += 1

    def release(self, bid: int) -> bool:
        """Drop an external pin; True when the block returned to the
        free list."""
        with self._lock:
            return self._release(bid)

    def make_writable(
        self, slot: int, start_token: int, end_token: int
    ) -> list[tuple[int, int]]:
        """Copy-on-write guard for a write into positions ``[start_token,
        end_token)``: any touched block shared with another owner
        (refcount > 1) is split — a fresh block takes its table entry, the
        old block keeps its other owners. Returns ``(src, dst)`` block-id
        pairs the caller must copy ON DEVICE (``_pool_copy_blocks``)
        before dispatching the write. Normal serving flows write only past
        the aliased (block-aligned) prefix, so this usually returns [] —
        it is the guard that makes aliasing safe by construction rather
        than by call-site discipline."""
        if end_token <= start_token:
            return []
        B = self.block_tokens
        pairs: list[tuple[int, int]] = []
        with self._lock:
            j0 = start_token // B
            j1 = -(-end_token // B)
            for j in range(j0, min(j1, int(self._owned[slot]))):
                bid = int(self.table[slot, j])
                if bid == self.SINK or self._refs[bid] <= 1:
                    continue
                new = self._pop_free()
                self._refs[bid] -= 1  # > 1 before, so never frees here
                self.table[slot, j] = new
                self.stats["cow_splits"] += 1
                pairs.append((bid, new))
            if pairs:
                self._dirty = True
        return pairs

    # ---- device table ------------------------------------------------------

    def table_device(self):
        """The (num_slots, slot_blocks) int32 table as a device array,
        re-uploaded only when the host table changed since the last call
        (a few hundred bytes — negligible next to a decode step, and the
        block DATA never moves through the host on the aliased path)."""
        import jax.numpy as jnp

        with self._lock:
            if self._dirty or self._table_dev is None:
                self._table_dev = jnp.asarray(self.table)
                self._dirty = False
            return self._table_dev

    # ---- invariants --------------------------------------------------------

    def check_consistency(self) -> None:
        """Re-derive the whole accounting from first principles: refcounts
        never negative, free list duplicate-free and disjoint from every
        table, every live table entry referenced, freed blocks hold zero
        references, block-count conservation. The schedule checker and the
        hammer test call this after every operation."""
        with self._lock:
            free = list(self._free)
            assert len(set(free)) == len(free), "double-free: dup in free list"
            assert self.SINK not in free, "sink leaked into the free list"
            assert (self._refs >= 0).all(), (
                f"negative refcount: {self._refs.tolist()}"
            )
            for bid in free:
                assert self._refs[bid] == 0, (
                    f"free block {bid} still referenced ({self._refs[bid]})"
                )
            table_refs = np.zeros_like(self._refs)
            for s in range(self.num_slots):
                owned = int(self._owned[s])
                for j in range(self.slot_blocks):
                    bid = int(self.table[s, j])
                    if j < owned:
                        assert bid != self.SINK, (
                            f"slot {s} owned entry {j} points at the sink"
                        )
                        assert bid not in free, (
                            f"slot {s} references freed block {bid}"
                        )
                        table_refs[bid] += 1
                    else:
                        assert bid == self.SINK, (
                            f"slot {s} stale entry {j} -> {bid}"
                        )
            # refs = table occurrences + external pins (>= 0 each)
            extra = self._refs - table_refs
            extra[self.SINK] -= 1  # the permanent sink pin
            assert (extra >= 0).all(), (
                f"refcount below table occupancy: {extra.tolist()}"
            )
            live = self.num_blocks - 1 - len(free)
            assert live == int((self._refs[1:] > 0).sum()), (
                "block-count conservation violated"
            )


# ==========================================================================
# device-side pure helpers (used inside jitted programs)


def heads_view(rows, head_dim: int | None):
    """A token's stored rows (..., R, C) as its heads (..., H_kv, D). A pool
    whose heads are narrower than a lane row may keep ``128 // D`` of them a
    row (``ops.attention.init_block_pool``): row-major that is the same bytes
    in the same order, so the heads are a reshape away. Every other buffer
    (``C == D``, an int8 pool's size-1 scales, ``head_dim`` not given) is
    returned as it is, and so is a buffer with no head axis at all: a latent
    layer's rows (``ops/mla.py``), (blocks or slots, tokens, lanes)."""
    per_row = rows.shape[-1] // head_dim if head_dim and rows.ndim > 3 else 1
    if per_row <= 1:
        return rows
    return rows.reshape(*rows.shape[:-2], rows.shape[-2] * per_row, head_dim)


def gather_block_views(
    buf, table, width: int | None = None, head_dim: int | None = None
):
    """Gather per-sequence dense-ordered KV views through block tables:
    ``buf`` (num_blocks, B, ...) x ``table`` (N, nmax) -> (N, L, ...) where
    ``L = width`` (sliced from nmax*B; ``None`` keeps the full nmax*B).
    Slicing to the dense buffer length keeps the attention reduction the
    SAME shape as the dense layout — a precondition of bitwise parity.
    Unmapped entries gather the sink block; its rows land at positions the
    offset causal mask hides. With ``head_dim`` the view comes out by heads,
    (N, L, H_kv, D), whatever rows the pool keeps them in (``heads_view``)."""
    import jax.numpy as jnp

    n, nmax = table.shape
    view = jnp.take(buf, table, axis=0)  # (N, nmax, B, ...)
    view = view.reshape(n, nmax * buf.shape[1], *buf.shape[2:])
    if width is not None and width < view.shape[1]:
        view = view[:, :width]
    return heads_view(view, head_dim)


def scatter_rows(buf, row_ids, rows):
    """Write flat pool rows: ``buf`` (num_blocks, B, ...), ``row_ids``
    (M,) flat row indices (block*B + offset), ``rows`` (M, ...): a token's
    values by heads, (M, H_kv, D), written in whatever rows the pool keeps
    them (the same bytes in the same order). Row ids
    may repeat ONLY on sink rows (free slots all write there); the sink's
    content is never read unmasked, so the scatter's pick order is
    irrelevant."""
    nb, bt = buf.shape[0], buf.shape[1]
    flat = buf.reshape(nb * bt, *buf.shape[2:])
    rows = rows.reshape(rows.shape[0], *buf.shape[2:])
    return flat.at[row_ids].set(rows).reshape(buf.shape)


def block_row_ids(table, index, s_q: int, block_tokens: int):
    """Flat pool row ids for per-sequence writes at positions
    ``[index[s], index[s] + s_q)``: (N, s_q) int32. Positions past the
    table's mapped range clamp into the slot's last entry — free slots
    (index 0, all-sink rows) land in the sink."""
    import jax.numpy as jnp

    nmax = table.shape[1]
    pos = index[:, None] + jnp.arange(s_q)[None, :]
    blk = jnp.take_along_axis(
        table, jnp.clip(pos // block_tokens, 0, nmax - 1), axis=1
    )
    return blk * block_tokens + pos % block_tokens
