"""Continuous (in-flight) batching for decoder-only LM serving.

The grouped path in ``cli/serve.py`` decodes each drained batch TO
COMPLETION before any newly queued request gets a slot: one straggler with
a long generation holds an entire batch's worth of chip time hostage, and a
request that arrives one tick after a batch launches waits out the whole
batch. This module replaces that with a step-level scheduler over a fixed
pool of KV-cache slots:

- **Slot pool**: ``num_slots`` independent single-request KV caches stacked
  into one device-resident pytree (leading slot axis). One jitted
  ``_pool_step`` advances EVERY slot one token per call (a vmapped
  ``transformer_decode_step`` — each slot carries its own cache index, so
  slots sit at unrelated positions in unrelated requests).
- **Admission by prefill-into-slot**: a newly queued request claims a free
  slot mid-flight; its prompt is ingested in one chunked
  ``transformer_prefill`` pass into that slot's cache (the slot's index is
  reset — stale K/V from the previous occupant is provably invisible, the
  position mask zeroes anything at positions the new request has not
  written). Prefill lengths are bucketed (``prefill_len_for``) so serving
  never recompiles per prompt length.
- **Cross-request prefix reuse** (``prefix_cache=``, ``serve/
  prefix_cache.py``): admission first walks a host-side radix trie of
  stored KV blocks for the longest block-aligned prefix an earlier request
  already computed; matched blocks are copied into the slot's cache with
  one jitted ``_slot_restore`` (no model forward) and only the unmatched
  suffix is chunk-prefilled. Retirement slices the slot's prompt-region KV
  back into the trie. Greedy answers are byte-identical cache on/off;
  per-request ``"cache_prefix": false`` opts out of both directions.
- **Retirement at step boundaries**: a slot that emits EOS (or exhausts its
  ``max_new`` budget) is retired and recycled at the next step boundary; the
  remaining slots never wait for it.
- **One step always in flight** (the plain path, every layout): a call of
  ``step()`` enqueues pool step t+1 BEFORE it fetches step t's picks, so the
  device never waits for the host's build, dispatch and slot walk. What step
  t+1 feeds is known without those picks (positions advance by one, keys
  fold the position in, budgets are counted); the tokens are chosen on the
  device between the picks in flight and what the host supplies
  (``_choose``). An EOS is seen one step late: the slot's extra row writes
  behind its last valid row and its pick is dropped (docs/SERVING.md).
- **Speculative decoding** (``speculate_k > 0``, ``serve/speculative.py``):
  each step becomes a verify step — every occupied slot feeds its pending
  token plus up to ``k`` lookahead tokens (un-ingested prompt tail first,
  then drafter proposals) through ONE static-width ``_pool_verify``
  forward; the accepted prefix is kept and the rejected tail is erased by
  O(1) index rollback (``_pool_rollback``). Greedy answers stay
  byte-identical; mixed speculative/non-speculative slots share the one
  compiled program. Refused for rolling-window caches (eviction defeats
  rollback).

Outputs are bit-identical to ``serve_batch=1`` sequential serving (each
request alone through ``train.decode.generate``): the per-slot decode is the
same cached step at the same positions, picks go through the same
``sample_token`` with the same position-keyed rng folding, and masked cache
slots contribute exactly zero to attention regardless of their stale
content. ``tests/test_scheduler.py`` pins this.

Per-request error isolation (the ``cli/serve.py`` grouped-path guarantee)
holds structurally here: requests fail at admission (encode/validation) —
one poisoned request answers with its error and never enters the pool, so
co-batched requests are untouched.

With a ``telemetry=`` handle (``obs.Telemetry``, docs/OBSERVABILITY.md) the
scheduler records per-request spans (enqueue→admit→prefill→first-token→
finish), slot-occupancy/backlog gauges, and admission/retirement/error
counters — all host-side at step boundaries: answers stay byte-identical
and the hot path compiles the same programs (both pinned in tests).

Fault tolerance (``serve/resilience.py``, docs/ROBUSTNESS.md): requests
may carry ``deadline_ms`` (honored at queue/prefill/decode-step
boundaries; expiry frees the slot and answers a structured ``deadline``
error with the partial continuation), ``cancel(order)`` registers a
cancellation from any thread that the scheduler loop executes at the next
step boundary, ``max_backlog`` bounds admission with immediate
``backpressure`` answers, and transient admission faults retry with
jittered exponential backoff before answering ``transient``. Circuit
breakers fail speculation and prefix reuse OPEN to the plain byte-parity
path after K consecutive faults (half-open re-probe after a cooldown),
with state exported as obs gauges + ``serve.breaker`` events — the chaos
suite (tests/test_resilience.py) pins that fault storms lose no request,
slot, or prefix pin, and that greedy answers return byte-identical once
the breakers close, at zero steady-state recompiles.

Tracing (``telemetry.tracer`` set — the ``--trace`` flag): every request
becomes a span tree (``serve.request`` root; ``serve.queue`` /
``serve.admit`` / ``serve.prefill`` / ``serve.decode`` children, plus
``prefix.match`` / ``prefix.restore`` / ``prefix.insert`` and the
step-level ``scheduler.step`` / ``spec.draft`` / ``spec.verify`` /
``spec.rollback`` spans), emitted as ``trace.span`` events on the same
JSONL log and exportable to Perfetto with ``python -m transformer_tpu.obs
trace``. A request dict may carry a W3C ``"traceparent"`` — the root span
parents under it, so a fronting router's trace context propagates across
the process boundary. Error answers, retry/backoff attempts
(``serve.retry`` events) and breaker transitions carry the victim
request's ``trace`` id, so a chaos episode reconstructs as one tree.
Tracing is host-side bookkeeping at the same boundaries as the metrics:
answers stay byte-identical and the compiled programs are jaxpr-identical
tracing on vs. off (``telemetry_inert`` contract + tests/test_trace.py).

SLOs (``slos=`` — specs or a ``--slo_spec`` string, ``obs/slo.py``):
every answer feeds a streaming burn-rate engine; ``serve_slo_burn_*``
gauges and ``slo.burn`` breach-transition events ride the same telemetry,
and ``python -m transformer_tpu.obs slo`` renders the report offline.

Paged KV memory (``kv_layout="paged"``, docs/SERVING.md): every slot is
backed by ONE device-resident block pool per layer through a per-slot
block table (``kernels/kv_pool.py``) instead of a dense ``max_total``
buffer — resident KV proportional to used tokens, prefix-cache hits
restored by block-table ALIASING (zero host copies, zero forwards),
speculative rollback a table truncation, copy-on-write guarding every
write into a shared block. Answers are byte-identical to the dense
layout (the paged step gathers dense-ordered views through the tables
and runs the SAME vmapped forward); pool exhaustion degrades spill →
``transient`` at admission → a structured ``resource`` preemption
mid-flight, never a corrupted neighbor.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import zlib
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from transformer_tpu.config import PAD_ID, ModelConfig
from transformer_tpu.data.seeding import keyed_rng
from transformer_tpu.models.decoder import init_decoder_caches, init_layer_state
from transformer_tpu.models.encoder import layer_uses_moe
from transformer_tpu.models.paged_decode import (
    MOE_COUNTS,
    check_paged_flash_config,
    paged_decode_forward,
)
from transformer_tpu.models.transformer import (
    transformer_decode_step,
    transformer_prefill,
    transformer_verify,
)
from transformer_tpu.obs.trace import SpanContext
from transformer_tpu.ops.attention import (
    insert_kv_blocks,
    kv_buffer_keys,
    slice_kv_blocks,
)
from transformer_tpu.ops.mla import latent_width
from transformer_tpu.ops.short_conv import state_buffer_keys
from transformer_tpu.serve.resilience import (
    BREAKER_STATE_VALUE,
    CircuitBreaker,
    TransientError,
    backoff_ms,
    classify_error,
    error_answer,
    maybe_fail,
)
from transformer_tpu.serve.speculative import (
    NgramDrafter,
    build_verify_row,
    filtered_probs,
    judge_row,
    sampled_accept,
    verify_row_picks,
)
from transformer_tpu.train.decode import (
    _detokenize_rows,
    prefill_len_for,
    sample_token,
)
from transformer_tpu.utils.profiling import mirrored_tracer


# Decode steps between two fetches of a dropless model's expert counts: a
# fetch costs the host 0.25 ms or more (PERF.md, PR 26).
_MOE_READ_EVERY = 32


def abstract_pool_caches(cfg: ModelConfig, num_slots: int, max_total: int):
    """The slot pool's KV cache pytree as ``ShapeDtypeStruct``s — the ONE
    statement of the pool's device layout (per-slot caches from
    ``init_decoder_caches`` stacked on a leading slot axis), shared by the
    abstract analyses (``analysis/contracts.py`` jaxpr twins,
    ``analysis/costs.py`` memory/FLOP budgets) so they can never drift from
    what the scheduler actually allocates. Nothing is allocated here."""
    per_slot = jax.eval_shape(lambda: init_decoder_caches(cfg, 1, max_total))
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((num_slots, *x.shape), x.dtype), per_slot
    )


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _pool_step(params, pool_caches, toks, cfg: ModelConfig):
    """One decode step for every slot: (N,) tokens -> ((N, V) logits,
    updated pool caches). vmap over the slot axis: each slot runs a batch-1
    ``transformer_decode_step`` at its OWN cache index (free slots step too —
    a fixed-shape program beats per-occupancy recompiles; their writes land
    at masked positions and are overwritten at admission)."""

    def one(tok, caches):
        pos = caches[0]["index"]
        logits, caches = transformer_decode_step(
            params, tok[None, None], None, None, caches, pos, cfg
        )
        return logits[0], caches

    return jax.vmap(one)(toks, pool_caches)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def _pool_verify(params, pool_caches, toks, cfg: ModelConfig):
    """One speculative VERIFY step for every slot: (N, W) candidate rows ->
    ((N, W, V) logits — one distribution per fed position — and updated
    pool caches). The W-wide sibling of ``_pool_step``, vmapping
    ``transformer_verify`` (the chunked-prefill S_q > 1 cache-write path)
    over the slot axis. Every slot feeds a full static-W row — occupied
    slots pad short rows with PAD lookahead, free slots feed all-PAD — so
    mixed speculative/non-speculative pools run ONE fixed-shape program.
    Each slot's index advances by W inside; the host decides per-slot
    acceptance and rolls back via ``_pool_rollback``."""

    def one(tok_row, caches):
        pos = caches[0]["index"]
        logits, caches = transformer_verify(
            params, tok_row[None, :], caches, pos, cfg
        )
        return logits[0], caches

    return jax.vmap(one)(toks, pool_caches)


@partial(jax.jit, donate_argnums=(0,))
def _pool_rollback(pool_caches, delta):
    """O(1) speculative rollback over the whole pool: add ``delta`` (N,)
    — ``accepted_width - W``, zero for free slots — to every layer's cache
    index. Stale K/V beyond the restored index stay in the buffers but the
    offset causal mask already hides positions ``>= index`` from all later
    reads, and the next real write overwrites them in place (the same
    invariant ``ops.attention.rollback_cache`` documents; the pool variant
    is arithmetic on the stacked index vector so it stays ONE jitted
    program)."""
    return [dict(c, index=c["index"] + delta) for c in pool_caches]


@partial(jax.jit, static_argnames=("cfg", "chunk"))
def _slot_prefill(
    params, pool_caches, slot, prompt, start, cfg: ModelConfig, chunk: int
):
    """Prefill a (1, n) prompt suffix into slot ``slot`` at absolute
    positions ``start .. start + n - 1`` (slot AND start traced — no
    recompile per slot or per prefix-cache hit length), resetting the
    slot's cache index to ``start``. ``start`` is 0 for a plain admission;
    a prefix-cache hit restores ``start`` positions first
    (``_slot_restore``) and prefills only the unmatched suffix from there.
    Returns ((1, V) logits for the next position, updated pool caches).

    NOT donated, unlike the paged ``_slot_prefill_paged``, so a failure of
    the call leaves the pool whole and answers that request alone; the price
    is a copy of the whole pool an admission. No benchmark cell serves the
    dense layout, so it keeps that price until one does (ROADMAP S5, D1)."""
    slot_caches = jax.tree.map(lambda x: x[slot], pool_caches)
    slot_caches = [dict(c, index=jnp.asarray(start, jnp.int32)) for c in slot_caches]
    logits, slot_caches = transformer_prefill(
        params, prompt, None, None, slot_caches, start, cfg, chunk=chunk
    )
    pool_caches = jax.tree.map(
        lambda pool, s: pool.at[slot].set(s), pool_caches, slot_caches
    )
    return logits, pool_caches


@jax.jit
def _slot_restore(pool_caches, slot, blocks):
    """Copy prefix-cache blocks (per-layer host buffers, already
    ``device_put`` by jit's argument transfer) into slot ``slot`` at
    positions ``[0, width)`` — the NO-FORWARD half of a cache-hit
    admission. ``blocks`` is padded to a power-of-two block count
    (``PrefixHit.stacked``), so the compile set is O(log(max_total /
    block)), never one per hit length; zero pad rows land at positions the
    offset causal mask hides until the suffix prefill overwrites them.
    Cache ``index`` is untouched here — ``_slot_prefill`` resets it to the
    restored width when it ingests the suffix. NOT donated, like
    ``_slot_prefill`` and for the same reason: the dense layout's programs
    keep their per-call isolation until a cell serves that layout."""
    slot_caches = jax.tree.map(lambda x: x[slot], pool_caches)
    slot_caches = [
        insert_kv_blocks(c, b, 0) for c, b in zip(slot_caches, blocks)
    ]
    return jax.tree.map(
        lambda pool, s: pool.at[slot].set(s), pool_caches, slot_caches
    )


@partial(jax.jit, static_argnames=("n",))
def _slot_read_blocks(pool_caches, slot, start, n: int):
    """Read ``n`` KV rows at ``[start, start + n)`` from slot ``slot`` in
    storage layout (``ops.attention.slice_kv_blocks``) — the retirement-side
    export the prefix cache host-copies into its trie. ``n`` is the static
    block width, so this compiles ONCE; ``start``/``slot`` are traced."""
    slot_caches = jax.tree.map(lambda x: x[slot], pool_caches)
    return [slice_kv_blocks(c, start, n) for c in slot_caches]


# --------------------------------------------------------------------------
# paged KV layout (--kv_layout paged): ONE block pool per layer, per-slot
# block tables (kernels/kv_pool.py). The jitted programs below are the
# paged twins of the dense _pool_step/_pool_verify/_slot_prefill family:
# each gathers the slots' dense-ORDERED views through the table (sliced to
# the dense buffer length, so every attention reduction keeps the dense
# shape), runs the SAME vmapped model forward the dense pool runs, and
# scatters only the newly written rows back into the pool — greedy and
# seeded-sampled answers are bit-identical paged vs dense because the
# compute graph consumes identical values at every unmasked position
# (stale gathered rows sit at positions the offset causal mask already
# hides, the invariant recycled dense slots rely on too). Per-slot cache
# indices are HOST-authoritative in paged mode (rebuilt from st.pos each
# call, like the pick positions), so rollback is pure table truncation.


def init_paged_pools(
    cfg: ModelConfig, num_slots: int, pool_blocks: int, block_tokens: int
) -> list:
    """The paged pool's per-layer device state, two kinds in one list. Rows a
    position, which every slot reaches through the block table: ONE block pool
    of K and V rows for each attention layer, one of latent rows for each
    latent layer (``ops/mla.py``: a row of ``latent_width`` lanes stored once).
    A fixed state a slot, and no blocks at all, for each short-convolution
    layer (``{"conv_state": (num_slots, L - 1, d_model)}``) and each delta-rule
    layer (``ops/kda.py``: ``kda_state`` (num_slots, H, D, D) float32 and the
    three convolutions' ``kda_conv`` rows). The pool's bytes a token are the
    attention and latent layers'."""
    from transformer_tpu.ops.attention import init_block_pool
    from transformer_tpu.ops.mla import init_latent_pool

    def one(i):
        kind = cfg.layer_kind(i)
        if kind.mixer in ("conv", "kda"):
            return init_layer_state(cfg, i, num_slots)
        if kind.mixer == "mla":
            return init_latent_pool(
                pool_blocks, block_tokens, kind.latent_rank,
                kind.latent_shared_dim, cfg.compute_dtype,
            )
        return init_block_pool(
            pool_blocks, block_tokens, cfg.kv_heads, cfg.head_dim,
            cfg.compute_dtype, quantize=cfg.kv_cache_int8,
        )

    return [one(i) for i in range(cfg.num_layers)]


def _paged_views(pool_caches, table, index, buf_len: int, head_dim: int):
    """Per-layer stacked slot views, structurally identical to the dense
    SlotPool pytree: leaves (N, 1, buf_len, H, D), by heads of ``head_dim``
    whatever rows the pool keeps them in (a latent layer's: (N, 1, buf_len,
    lanes)), + per-slot ``index`` (a stateful layer's: its state, (N, 1,
    ...))."""
    from transformer_tpu.kernels.kv_pool import gather_block_views

    views = []
    for layer in pool_caches:
        view = {
            key: gather_block_views(layer[key], table, buf_len, head_dim)[:, None]
            for key in kv_buffer_keys(layer)
        }
        for key in state_buffer_keys(layer):
            view[key] = layer[key][:, None]
        view["index"] = index
        views.append(view)
    return views


def _paged_scatter(pool_caches, new_views, table, index, s_q: int,
                   block_tokens: int):
    """Write the rows the forward just produced — per slot, positions
    ``[index, index + s_q)`` of its view — back into the pool buffers, in
    storage layout (the view's buffers were written by the same _store_kv
    the dense path uses, so the pool rows are bit-identical to a dense
    cache's). Free slots (index 0, all-sink tables) land in the sink, and
    keep the state they held (a short convolution's rows, a delta-rule
    layer's matrix)."""
    from transformer_tpu.kernels.kv_pool import block_row_ids, scatter_rows

    n = table.shape[0]
    rids = block_row_ids(table, index, s_q, block_tokens).reshape(-1)
    out = []
    for layer, view in zip(pool_caches, new_views):
        new = dict(layer)
        for key in kv_buffer_keys(layer):
            rows = jax.vmap(
                lambda v, i: jax.lax.dynamic_slice_in_dim(v[0], i, s_q, axis=0)
            )(view[key], index)  # (N, s_q, ...)
            new[key] = scatter_rows(
                layer[key], rids, rows.reshape(n * s_q, *rows.shape[2:])
            )
        for key in state_buffer_keys(layer):
            live = (index > 0)[(slice(None),) + (None,) * (layer[key].ndim - 1)]
            new[key] = jnp.where(live, view[key][:, 0].astype(layer[key].dtype), layer[key])
        out.append(new)
    return out


@partial(
    jax.jit,
    static_argnames=("cfg", "block_tokens", "buf_len"),
    donate_argnums=(1,),
)
def _pool_step_paged(
    params, pool_caches, table, index, toks, cfg: ModelConfig,
    block_tokens: int, buf_len: int,
):
    """Paged ``_pool_step``: gather views -> the SAME vmapped batch-1
    decode step -> scatter each slot's one new row back into its block."""
    views = _paged_views(pool_caches, table, index, buf_len, cfg.head_dim)

    def one(tok, caches):
        pos = caches[0]["index"]
        logits, caches = transformer_decode_step(
            params, tok[None, None], None, None, caches, pos, cfg
        )
        return logits[0], caches

    logits, new_views = jax.vmap(one)(toks, views)
    return logits, _paged_scatter(
        pool_caches, new_views, table, index, 1, block_tokens
    )


@partial(
    jax.jit,
    static_argnames=("cfg", "block_tokens", "buf_len"),
    donate_argnums=(1,),
)
def _pool_verify_paged(
    params, pool_caches, table, index, toks, cfg: ModelConfig,
    block_tokens: int, buf_len: int,
):
    """Paged ``_pool_verify``: W-wide rows through the same static-shape
    verify forward; rejected tails are erased by HOST table truncation
    (blocks return to the pool), not a device index rollback."""
    views = _paged_views(pool_caches, table, index, buf_len, cfg.head_dim)

    def one(tok_row, caches):
        pos = caches[0]["index"]
        logits, caches = transformer_verify(
            params, tok_row[None, :], caches, pos, cfg
        )
        return logits[0], caches

    logits, new_views = jax.vmap(one)(toks, views)
    return logits, _paged_scatter(
        pool_caches, new_views, table, index, toks.shape[1], block_tokens
    )


@partial(
    jax.jit,
    static_argnames=("cfg", "block_tokens", "interpret"),
    donate_argnums=(1,),
)
def _pool_step_paged_flash(
    params, pool_caches, table, index, toks, cfg: ModelConfig,
    block_tokens: int, interpret: bool,
):
    """``_pool_step_paged`` on the fused kernels (--decode_kernel
    paged_flash): one batched forward whose attention reads pool blocks in
    place through the table — no gathered view, no per-slot vmap — and
    whose dense-FFN sublayers run as single Pallas kernels
    (``models/paged_decode.py``). Same signature family as the gather twin
    minus ``buf_len`` (nothing dense-ordered exists to size)."""
    logits, new_pools = paged_decode_forward(
        params, toks[:, None], pool_caches, table, index, cfg,
        block_tokens=block_tokens, interpret=interpret,
    )
    return logits[:, 0], new_pools


@partial(
    jax.jit,
    static_argnames=("cfg", "block_tokens", "interpret"),
    donate_argnums=(1,),
)
def _pool_verify_paged_flash(
    params, pool_caches, table, index, toks, cfg: ModelConfig,
    block_tokens: int, interpret: bool,
):
    """``_pool_verify_paged`` on the fused kernels: W-wide speculative
    rows scored in one forward — the paged-flash kernel's per-row offset
    causality handles S_q = k + 1 directly (the gather-flash path's S_q=1
    restriction does not apply). Rejected tails still roll back by HOST
    table truncation, exactly like the gather twin."""
    logits, new_pools = paged_decode_forward(
        params, toks, pool_caches, table, index, cfg,
        block_tokens=block_tokens, interpret=interpret,
    )
    return logits, new_pools


@partial(
    jax.jit,
    static_argnames=("cfg", "chunk", "block_tokens", "buf_len"),
    donate_argnums=(1,),
)
def _slot_prefill_paged(
    params, pool_caches, table, slot, prompt, start, cfg: ModelConfig,
    chunk: int, block_tokens: int, buf_len: int,
):
    """Paged ``_slot_prefill``: one slot's gathered view through the same
    chunked prefill, then scatter the written suffix rows ``[start, start
    + n)`` into the slot's blocks. ``slot`` and ``start`` stay traced (no
    recompile per slot or hit length). The pool is DONATED, as the pool
    step's is: the slot's rows and state are written in place, where an
    undonated pool made XLA copy every K/V pool and state buffer whole first
    (PR 38). A failure raised before the program is enqueued leaves the pool
    whole and answers that request alone; one after it has taken the pool is
    the pool's, and stops the scheduler (``admit``). A stateful layer's view
    is the slot's own state (a short convolution's rows; a delta-rule layer's
    matrix and convolution inputs): read as the chunk's left edge where
    ``start > 0`` (zeros at 0, whatever the slot held, so a recycled slot
    never reads its predecessor's), written back as the state to the right of
    the chunk."""
    from transformer_tpu.kernels.kv_pool import gather_block_views, scatter_rows

    row = jax.lax.dynamic_slice_in_dim(table, slot, 1, axis=0)  # (1, nmax)
    views = [
        {
            key: gather_block_views(layer[key], row, buf_len, cfg.head_dim)
            for key in kv_buffer_keys(layer)
        }
        | {
            key: jax.lax.dynamic_slice_in_dim(layer[key], slot, 1, axis=0)
            for key in state_buffer_keys(layer)
        }
        for layer in pool_caches
    ]
    caches = [dict(v, index=jnp.asarray(start, jnp.int32)) for v in views]
    logits, caches = transformer_prefill(
        params, prompt, None, None, caches, start, cfg, chunk=chunk
    )
    n = prompt.shape[1]
    nmax = table.shape[1]
    pos = start + jnp.arange(n)
    blk = jnp.take(row[0], jnp.clip(pos // block_tokens, 0, nmax - 1))
    rids = blk * block_tokens + pos % block_tokens
    new_pool = []
    for layer, c in zip(pool_caches, caches):
        new = dict(layer)
        for key in kv_buffer_keys(layer):
            rows = jax.lax.dynamic_slice_in_dim(c[key], start, n, axis=1)[0]
            new[key] = scatter_rows(layer[key], rids, rows)
        for key in state_buffer_keys(layer):
            new[key] = jax.lax.dynamic_update_slice_in_dim(
                layer[key], c[key].astype(layer[key].dtype), slot, axis=0
            )
        new_pool.append(new)
    return logits, new_pool


@jax.jit
def _pool_write_blocks(pool_caches, bids, blocks):
    """Write host-format prefix blocks into pool blocks ``bids`` — the
    paged restore for HOST-tier hits (and the warm-up/disaggregation
    inject path). ``blocks`` is per-layer dicts of (n_pad, B, H, D)
    buffers in storage layout, written in whatever rows the pool keeps a
    token's heads (the same bytes in the same order); ``bids`` is padded to
    a power-of-two count
    with sink ids + zero rows (compile set O(log pool), never one per hit
    length). Device-tier hits never reach here — they are pure table
    aliasing with zero host<->device copies."""
    out = []
    for layer, b in zip(pool_caches, blocks):
        new = dict(layer)
        for key in kv_buffer_keys(layer):
            rows = b[key].reshape(b[key].shape[0], *layer[key].shape[1:])
            new[key] = layer[key].at[bids].set(rows)
        out.append(new)
    return out


@partial(jax.jit, static_argnames=("head_dim",))
def _pool_read_block(pool_caches, bid, head_dim: int):
    """One pool block in host prefix-cache format: per-layer dicts of
    (1, B, H, D) storage-layout buffers, by heads of ``head_dim`` whatever
    rows the pool keeps them in — byte-compatible with the dense
    ``_slot_read_blocks`` export, so spill-to-host, ``--disaggregate``
    KV handoff, and supervisor cache-warming keep their wire format."""
    from transformer_tpu.kernels.kv_pool import heads_view

    return [
        {
            key: heads_view(
                jax.lax.dynamic_slice_in_dim(layer[key], bid, 1, axis=0), head_dim
            )
            for key in kv_buffer_keys(layer)
        }
        for layer in pool_caches
    ]


@jax.jit
def _pool_copy_blocks(pool_caches, src, dst):
    """Device-side block copies for copy-on-write splits: ``src``/``dst``
    id vectors padded to a power of two with (sink, sink) no-op pairs."""
    out = []
    for layer in pool_caches:
        new = dict(layer)
        for key in kv_buffer_keys(layer):
            new[key] = layer[key].at[dst].set(layer[key][src])
        out.append(new)
    return out


def version_value(tag: "str | None") -> float:
    """Stable numeric rendering of a weight_version tag for the
    ``serve_weight_version`` gauge (gauges are floats; the digest is hex).
    crc32 keeps it exactly representable in a float64 and stable across
    processes. 0.0 = untagged."""
    return float(zlib.crc32(str(tag).encode())) if tag else 0.0


def _pow2_pad(ids: list[int], fill: int = 0) -> list[int]:
    """Pad an id list to the next power-of-two length (bounded compile
    set for the block-granular device ops)."""
    n = max(1, len(ids))
    p = 1
    while p < n:
        p *= 2
    return list(ids) + [fill] * (p - len(ids))


def abstract_paged_pool(
    cfg: ModelConfig, num_slots: int, max_total: int,
    pool_blocks: int, block_tokens: int,
):
    """The paged pool's device layout as ShapeDtypeStructs — per-layer
    block-pool buffers (``init_paged_pools``) plus the (num_slots,
    slot_blocks) table and (N,) index — the ONE statement the abstract
    analyses (contracts, costs) share with what
    ``SlotPool(kv_layout="paged")`` actually allocates."""
    pool = jax.eval_shape(
        lambda: init_paged_pools(cfg, num_slots, pool_blocks, block_tokens)
    )
    slot_blocks = -(-max_total // block_tokens)
    table = jax.ShapeDtypeStruct((num_slots, slot_blocks), np.int32)
    index = jax.ShapeDtypeStruct((num_slots,), np.int32)
    return pool, table, index


@partial(jax.jit, static_argnames=("sample", "top_k", "top_p"))
def _pick_pool(logits, base_keys, positions, temperatures, *, sample, top_k, top_p):
    """Per-slot next-token picks over the whole pool (fixed shape — one
    compile per distinct static sampling signature, not per occupancy).
    Each slot's rng is ``fold_in(base_key, position)`` — the same
    position-keyed folding ``lm_generate`` uses, so picks match sequential
    serving bit for bit."""

    def one(row_logits, base_key, position, temperature):
        key = jax.random.fold_in(base_key, position)
        return sample_token(
            row_logits[None], key, sample=sample, temperature=temperature,
            top_k=top_k, top_p=top_p,
        )[0]

    return jax.vmap(one)(logits, base_keys, positions, temperatures)


@partial(jax.jit, static_argnames=("sample", "top_k", "top_p"))
def _pick_pool_verify(
    logits, base_keys, positions, temperatures, *, sample, top_k, top_p
):
    """Per-slot, per-position picks over a verify step's (N, W, V) logits
    -> (N, W) tokens: ``speculative.verify_row_picks`` (the ONE definition
    of the position-keyed verify-pick math — ``fold_in(base_key, position
    + j)``, same folding as ``_pick_pool``/``lm_generate``) vmapped over
    the slot axis, so a slot whose drafts all miss still draws exactly
    what sequential serving would draw at each absolute position."""

    def one(row_logits, base_key, position, temperature):
        return verify_row_picks(
            row_logits, base_key, position, temperature,
            sample=sample, top_k=top_k, top_p=top_p,
        )

    return jax.vmap(one)(logits, base_keys, positions, temperatures)


@partial(jax.jit, static_argnames=("sample", "top_k", "top_p"))
def _pick_one(logits, base_key, position, temperature, *, sample, top_k, top_p):
    """Single-row pick for the prefill edge (prompt fully ingested — the
    prefill's last logits are the first generation tick's logits)."""
    key = jax.random.fold_in(base_key, position)
    return sample_token(
        logits, key, sample=sample, temperature=temperature,
        top_k=top_k, top_p=top_p,
    )[0]


@jax.jit
def _choose(host, device, take):
    """(N,) tokens, row by row: ``device`` where ``take``, else ``host``. The
    one small program of the step kept in flight: a pool step's input tokens
    out of the picks still on the device and the tokens the host knows (a
    prompt tail's, a new admission's), and two sampling groups' picks into
    one vector."""
    return jnp.where(take, device, host)


def _request_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)``'s words, made on the host: the jitted
    seeding is a device program whose fetch would wait for everything
    enqueued before it, a prefill and the step in flight."""
    if jax.config.jax_default_prng_impl != "threefry2x32":
        return np.asarray(jax.random.PRNGKey(seed))
    high = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([high, seed & 0xFFFFFFFF], np.uint32)


@dataclasses.dataclass
class _Pending:
    """One queued (not-yet-admitted) request."""

    order: int
    req: dict
    t_enqueue: float
    # Absolute perf_counter deadline (submit time + deadline_ms), or None.
    # Parsed leniently at submit — an unconvertible deadline_ms stays None
    # here and raises the validation error at admission, where it answers
    # this request alone.
    deadline: float | None = None
    # Bounded-retry state for transient admission faults: attempts so far,
    # and the jittered-backoff timestamp before which admit() must not
    # re-try this entry.
    attempts: int = 0
    not_before: float = 0.0
    # Tracing: the request's root span (submit -> answer) and the
    # currently-open lifecycle child.
    # span_admit/span_prefill ride here only during an admission attempt,
    # so a transient-fault retry (or an admission error) can close them.
    span_root: object = None
    span_queue: object = None
    span_admit: object = None
    span_prefill: object = None


@dataclasses.dataclass
class _Active:
    """Host-side state of one occupied slot."""

    order: int                 # request arrival index (output ordering)
    ids: list[int]             # BOS-led prompt token ids
    prompt_len: int
    pos: int                   # next position to consume (== cache index)
    cur: int                   # token to feed at the next pool step
    emitted: list[int]
    max_new: int
    key: np.ndarray            # base PRNG key (request seed)
    sample: bool
    temperature: float
    top_k: int
    top_p: float
    seed: int = 0              # raw seed (rejection-sampling acceptance rng)
    # Speculative decoding (scheduler-level k > 0): whether THIS request
    # drafts (per-request "speculate": false opts out — it still rides the
    # W-wide verify step, just with no lookahead candidates), the drafter's
    # per-request state, and the accounting behind acceptance-rate /
    # tokens-per-forward telemetry.
    spec: bool = False
    dstate: object = None
    drafted: int = 0
    accepted: int = 0
    forwards: int = 0          # target-model decode forwards this request rode
    # Plain pool steps enqueued for this slot whose picks are not bookkept
    # yet: the next one feeds position ``pos + sent``.
    sent: int = 0
    # Prefix cache: whether this request participates (per-request
    # "cache_prefix": false opts out of BOTH reading and feeding the trie)
    # and how many prompt positions were restored from stored blocks
    # instead of a model forward (span field; hit-rate in obs summarize).
    use_prefix: bool = False
    prefix_hit: int = 0
    # Admission-time weight_version tag (None on an untagged scheduler):
    # stamped at admission and carried onto the answer/span — a request
    # that straddles an upgrade still reports (and was served by) the
    # weights it was admitted under.
    wv: "str | None" = None
    # Span clock (host perf_counter; None until the edge is reached):
    # enqueue -> admit -> prefill-dispatched -> first token -> finish.
    t_enqueue: float = 0.0
    t_admit: float = 0.0
    t_prefill: float | None = None
    t_first: float | None = None
    # The last output token's stamp and the longest gap between two of them
    # (None until there are two): one stamp per slot, no list.
    t_last: float = 0.0
    itl_max: float | None = None
    # Absolute perf_counter deadline (None = no deadline): checked at the
    # queue, prefill, and decode-step boundaries; expiry frees the slot and
    # answers a structured "deadline" error with the partial continuation.
    deadline: float | None = None
    # Tracing spans: the root rides over from the _Pending; prefill closes
    # when the LAST prompt token is in cache (exactly the t_prefill edge)
    # and decode opens there.
    span_root: object = None
    span_prefill: object = None
    span_decode: object = None

    @property
    def trace_id(self) -> "str | None":
        return None if self.span_root is None else self.span_root.ctx.trace_id

    @property
    def last_feed(self) -> int:
        """The position whose step picks the request's last token by its
        budget (the last prompt token's where it asks for one or none): known
        without any pick, unlike an EOS."""
        return self.prompt_len + max(self.max_new, 1) - 2

    @property
    def spent(self) -> bool:
        """Its budget's last step is already in flight: whatever row a
        further step runs for it is dropped."""
        return self.sent > 0 and self.pos + self.sent > self.last_feed


@dataclasses.dataclass
class _Flight:
    """One pool step that is enqueued and whose picks are still on the
    device. ``drain``, where the step was not ``ahead``: ``idle`` (nothing was
    in flight: the pool had been empty), ``no_block`` (the call before found
    no block to step ahead, ``_paged_prepare``), ``spent`` (the call before
    enqueued nothing: every occupied slot's budget ended in flight) or
    ``first_pick`` (an admission's first pick waited for its prefill and so
    for the step in flight, and the device stood empty while the host
    finished the admission and built this step)."""

    pairs: list                # (slot, _Active) it stepped, as at its dispatch
    positions: np.ndarray      # (N,) the position each row fed
    picks: jax.Array           # (N,) every sampling group's picks
    dropped: int               # rows run for a slot whose budget ended a step before
    ahead: bool                # the device had work queued when it was enqueued
    drain: "str | None"        # where not ahead, why the device had none
    prefills: int              # prefills enqueued between the step before and it
    prefill_tokens: int        # the tokens those fed
    # Every _MOE_READ_EVERY steps: (a copy of the expert counts as this step
    # left them, the rows every step up to this one fed).
    moe: "tuple | None" = None


class SlotPool:
    """A fixed pool of per-slot decoder KV storage: stacked dense caches
    (``kv_layout="dense"``, the historical layout) or ONE block pool per
    attention layer shared by every slot through block tables (``"paged"``,
    kernels/kv_pool.py — resident KV proportional to used tokens; a latent
    layer's pool holds one row a position) beside a fixed state a slot for
    each layer that keeps one (``init_paged_pools``)."""

    def __init__(
        self,
        cfg: ModelConfig,
        num_slots: int,
        max_total: int,
        *,
        kv_layout: str = "dense",
        kv_block: int = 16,
        kv_pool_blocks: int = 0,
    ):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if kv_layout not in ("dense", "paged"):
            raise ValueError(
                f"kv_layout must be 'dense' or 'paged', got {kv_layout!r}"
            )
        self.num_slots = num_slots
        self.max_total = max_total
        self.layout = kv_layout
        self.alloc = None
        if kv_layout == "paged":
            if cfg.attention_window:
                # The windowed-refusal variant: a rolling buffer stores
                # position p at slot p % buf_len and evicts on wrap, so
                # absolute-position block rows are neither stable nor
                # complete — same policy as the prefix cache and
                # speculative rollback.
                raise ValueError(
                    "kv_layout='paged' cannot serve a rolling-window cache "
                    "(attention_window evicts absolute-position rows on "
                    "wrap); serve this config with kv_layout='dense'"
                )
            from transformer_tpu.kernels.kv_pool import KVPool

            if kv_block < 1:
                raise ValueError(f"kv_block must be >= 1, got {kv_block}")
            self.block_tokens = kv_block
            self.slot_blocks = -(-max_total // kv_block)
            # Views gather at nmax*B rows then slice to max_total, so the
            # attention reduction keeps the DENSE buffer shape (a bitwise-
            # parity precondition).
            self.buf_len = max_total
            # 0 = full provisioning (every slot can always reach max_total
            # — zero behavior change vs dense, the safe default); smaller
            # pools bound resident KV by used tokens and lean on the spill
            # /preemption ladder under pressure.
            num_blocks = kv_pool_blocks or (1 + num_slots * self.slot_blocks)
            self.alloc = KVPool(
                num_blocks, kv_block, num_slots, self.slot_blocks
            )
            self.caches = init_paged_pools(cfg, num_slots, num_blocks, kv_block)
            return
        per_slot = [
            init_decoder_caches(cfg, 1, max_total) for _ in range(num_slots)
        ]
        # Stack to a leading slot axis: k/v (N, 1, buf, H, D), index (N,).
        self.caches = jax.tree.map(
            lambda *xs: jnp.stack(xs), per_slot[0], *per_slot[1:]
        )


class ContinuousScheduler:
    """Step-level continuous-batching scheduler for decoder-only exports.

    ``submit`` queues LM requests (dicts with ``prompt`` and the optional
    ``max_new`` / ``temperature`` / ``top_k`` / ``top_p`` / ``seed`` fields
    the grouped path accepts); ``submit_done`` reserves an output position
    for an already-answered response (parse/routing errors) so ordering is
    preserved across both. ``admit``/``step``/``drain_ready`` are the
    streaming API the serve CLI drives; ``run`` is the batch convenience
    the tests (and one-shot callers) use.
    """

    def __init__(
        self,
        params,
        cfg: ModelConfig,
        tokenizer,
        *,
        num_slots: int = 8,
        max_total: int | None = None,
        prefill_chunk: int = 0,
        default_max_new: int = 64,
        telemetry=None,
        speculate_k: int = 0,
        drafter=None,
        prefix_cache=None,
        max_backlog: int = 0,
        admission_retries: int = 2,
        retry_backoff_ms: float = 20.0,
        drafter_slow_ms: float = 0.0,
        breaker_threshold: int = 3,
        breaker_cooldown_s: float = 30.0,
        breaker_clock=time.monotonic,
        slos=None,
        span_tap=None,
        kv_layout: str = "dense",
        kv_block: int = 16,
        kv_pool_blocks: int = 0,
        decode_kernel: str = "xla",
        weight_version: "str | None" = None,
        mesh: "int | str | None" = None,
    ):
        if not cfg.decoder_only:
            raise ValueError(
                "continuous batching serves decoder-only LM exports; seq2seq "
                "and fill-mask requests go through the grouped path"
            )
        if speculate_k < 0:
            raise ValueError(f"speculate_k must be >= 0, got {speculate_k}")
        if speculate_k and cfg.attention_window:
            raise ValueError(
                "speculative decoding cannot roll back a rolling-window "
                "cache (attention_window evicts slots that stay in-window "
                "after rollback); serve this config with speculate_k=0"
            )
        if prefix_cache is not None and cfg.attention_window:
            # Mirrors the speculative refusal above: block restore addresses
            # cache rows by absolute position, which a rolling buffer evicts
            # on wrap (PrefixCache's own constructor refuses too — this
            # guards a cache built against a different config).
            raise ValueError(
                "prefix cache cannot serve a rolling-window cache "
                "(attention_window evicts absolute-position rows on wrap); "
                "serve this config without --prefix_cache_mb"
            )
        if cfg.state_layers:
            # A layer whose state is not rows a position (a short
            # convolution's last gated inputs, a delta-rule layer's matrix)
            # has one state a slot, overwritten by every token: nothing below
            # can bring an earlier one back.
            if speculate_k:
                raise ValueError(
                    "speculative decoding cannot serve a model with a stateful "
                    "layer: a rejected draft's tokens have already moved the "
                    "state a slot keeps in place of rows a position (a short "
                    "convolution's inputs, a delta-rule matrix), and it cannot "
                    "be rolled back; serve this config with speculate_k=0"
                )
            if prefix_cache is not None:
                raise ValueError(
                    "prefix cache cannot serve a model with a stateful layer: a "
                    "cached prefix holds rows a position and no snapshot of "
                    "the state a slot keeps beside them at the prefix's end; "
                    "serve this config without --prefix_cache_mb"
                )
            if mesh is not None:
                raise ValueError(
                    "a sharded replica (--mesh) places the pool by its KV "
                    "layout, and a model with a stateful layer has a state "
                    "a slot beside it; serve this config on one device"
                )
        self.params, self.cfg, self.tok = params, cfg, tokenizer
        # ---- live-weights control plane (serve/upgrade.py) ----------------
        # The TWO-VERSION param slot: `params` serves; a staged
        # (params, version) pair waits for the quiesce drain; after a swap
        # the displaced pair stays resident in `_prev` so rollback is an
        # O(1) re-stage of buffers that never left the device. While a
        # stage is pending, admission pauses (the local quiesce — the
        # router has already stopped dispatching) so every in-flight
        # request finishes on its ADMISSION-TIME weights; the flip happens
        # at the next drained step boundary and compiles nothing: the new
        # params are structure/shape/dtype-verified twins, so every jitted
        # program re-runs its existing executable with new operand values.
        self.weight_version = weight_version
        self._staged: "tuple | None" = None        # (params, version)
        self._prev: "tuple | None" = None          # the resident old pair
        self._swap_events: "deque[dict]" = deque() # worker-loop outbox
        self.prefix_cache = prefix_cache
        self.prefill_chunk = prefill_chunk
        self.default_max_new = default_max_new
        self.max_total = max_total or cfg.max_position + 1
        self.speculate_k = speculate_k
        # k > 0 with no drafter given: the model-free n-gram drafter (zero
        # extra params/forwards — the safe default).
        self.drafter = (
            drafter if drafter is not None or not speculate_k else NgramDrafter()
        )
        # speculate_k rows of buffer slack: a verify step writes W = k + 1
        # positions even when the slot sits at its very last budgeted
        # position — the slack keeps those writes in-bounds (a clamped
        # dynamic_update_slice would silently shift the write over REAL
        # prefix positions). Admission budgets still use max_total.
        if kv_layout == "paged" and prefix_cache is not None:
            # Pool blocks and prefix-cache blocks must be the SAME unit:
            # a device-tier hit aliases trie-held pool blocks straight
            # into the slot's table.
            kv_block = prefix_cache.block_tokens
        # ---- sharded replica (serve/sharded.py, --mesh) -------------------
        # mesh = N makes this scheduler a pjit program over an N-device
        # serving mesh: params replicated by the partition rules, pool KV
        # sharded on its leading storage axis, every canned program
        # re-jitted with explicit in/out shardings (the _fn_* dispatch
        # below). mesh = None is the historical single-device path,
        # byte-for-byte untouched.
        from transformer_tpu.serve.sharded import parse_mesh_spec

        self.mesh_size = parse_mesh_spec(mesh)
        self._sharded = None
        if self.mesh_size is not None:
            if decode_kernel == "paged_flash":
                raise ValueError(
                    "decode_kernel='paged_flash' is a single-device fused-"
                    "kernel program (models/paged_decode.py reads pool "
                    "blocks in place); serve --mesh replicas with the "
                    "gather-view programs (decode_kernel='xla')"
                )
            if num_slots % self.mesh_size:
                raise ValueError(
                    f"num_slots={num_slots} must divide the serving mesh "
                    f"(data={self.mesh_size}): the pool shards on the slot "
                    "axis, and a ragged shard would fail at the first "
                    "dispatch instead of here"
                )
            if kv_layout == "paged":
                # The paged pool shards on the block-row axis: round the
                # pool up to a multiple of the mesh so every shard holds
                # the same number of block rows. The extra rows just sit
                # on the allocator's free list.
                slot_blocks = -(-(self.max_total + speculate_k) // kv_block)
                blocks = kv_pool_blocks or (1 + num_slots * slot_blocks)
                kv_pool_blocks = blocks + (-blocks) % self.mesh_size
        self.pool = SlotPool(
            cfg, num_slots, self.max_total + speculate_k,
            kv_layout=kv_layout, kv_block=kv_block,
            kv_pool_blocks=kv_pool_blocks,
        )
        self.paged = self.pool.layout == "paged"
        # ---- decode kernel selection (--decode_kernel) --------------------
        # "xla": the gather-view programs — the bitwise parity reference and
        # the fallback for every config. "paged_flash": the fused Pallas
        # programs (models/paged_decode.py) that read pool blocks in place;
        # paged layout only, and the config guards are static so a bad combo
        # fails at construction, not at the first step. Off-TPU the kernels
        # run in interpret mode — resolved ONCE here so the flag is a static
        # jit arg (one executable per scheduler, not per backend probe).
        if decode_kernel not in ("xla", "paged_flash"):
            raise ValueError(
                f"decode_kernel must be 'xla' or 'paged_flash', got "
                f"{decode_kernel!r}"
            )
        if decode_kernel == "paged_flash":
            if not self.paged:
                raise ValueError(
                    "decode_kernel='paged_flash' reads the block-pool "
                    "buffers in place and needs kv_layout='paged'"
                )
            check_paged_flash_config(cfg)
        self.decode_kernel = decode_kernel
        # ---- counts a model of several layer kinds adds to a step ---------
        # The positions its full layers attend and, with a windowed kind,
        # those its window layers do (host arithmetic over the step's
        # positions); the layers whose state is no KV rows and the bytes a
        # slot holds for them (constants of a run: two gauges). A dropless
        # expert model on the fused step: picks held here and experts hit,
        # accumulated on the device in the pool pytree (the pool programs
        # return logits and pools, nothing else), copied every
        # _MOE_READ_EVERY steps at a step's dispatch and fetched once that
        # step's picks are.
        self._band = next(
            (k.window for k in cfg.attention_kinds if k.window), 0
        )
        self._state_layers = len(cfg.state_layers)
        self._state_bytes = sum(
            x.size * x.dtype.itemsize
            for i in cfg.state_layers
            for x in jax.eval_shape(lambda i=i: init_layer_state(cfg, i, 1)).values()
        )
        self._moe_layer = None
        if decode_kernel == "paged_flash" and cfg.moe_dispatch == "dropless":
            self._moe_layer = next(
                (i for i in range(cfg.num_layers) if layer_uses_moe(cfg, i)), None
            )
        if self._moe_layer is not None:
            self.pool.caches[self._moe_layer][MOE_COUNTS] = jnp.zeros((4,), jnp.int32)
        self._moe_read = np.zeros((4,), np.int64)  # the device's totals at the last fetch
        # Steps enqueued since the counts were last copied for a fetch; rows
        # those steps fed, ever, and as of the last copy that was fetched.
        self._moe_uncopied = self._moe_rows = self._moe_rows_read = 0
        self._kernel_interpret = jax.default_backend() != "tpu"
        # ---- program dispatch: module-level jits or sharded twins ---------
        # Unsharded schedulers dispatch the module-level programs (shared
        # compile caches across schedulers — the retrace budgets pin them);
        # a sharded scheduler dispatches its own pjit twins with explicit
        # in/out shardings over the serving mesh. Same signatures, same
        # statics, same donation — call sites below never branch.
        if self.mesh_size is not None:
            from transformer_tpu.serve.sharded import (
                ShardedPrograms,
                serving_mesh,
            )

            self._mesh = serving_mesh(self.mesh_size)
            sp = self._sharded = ShardedPrograms(self._mesh, self.params)
            self.params = sp.place_params(self.params)
            self.pool.caches = sp.place_pool(self.pool.caches)
            self._fn_pool_step = sp.pool_step
            self._fn_pool_verify = sp.pool_verify
            self._fn_pool_rollback = sp.pool_rollback
            self._fn_slot_prefill = sp.slot_prefill
            self._fn_slot_restore = sp.slot_restore
            self._fn_slot_read_blocks = sp.slot_read_blocks
            self._fn_pool_step_paged = sp.pool_step_paged
            self._fn_pool_verify_paged = sp.pool_verify_paged
            self._fn_slot_prefill_paged = sp.slot_prefill_paged
            self._fn_pool_write_blocks = sp.pool_write_blocks
            self._fn_pool_read_block = sp.pool_read_block
            self._fn_pool_copy_blocks = sp.pool_copy_blocks
        else:
            self._mesh = None
            self._fn_pool_step = _pool_step
            self._fn_pool_verify = _pool_verify
            self._fn_pool_rollback = _pool_rollback
            self._fn_slot_prefill = _slot_prefill
            self._fn_slot_restore = _slot_restore
            self._fn_slot_read_blocks = _slot_read_blocks
            self._fn_pool_step_paged = _pool_step_paged
            self._fn_pool_verify_paged = _pool_verify_paged
            self._fn_slot_prefill_paged = _slot_prefill_paged
            self._fn_pool_write_blocks = _pool_write_blocks
            self._fn_pool_read_block = _pool_read_block
            self._fn_pool_copy_blocks = _pool_copy_blocks
        if self.paged and prefix_cache is not None:
            # Device-resident prefix tier: retiring slots donate their
            # prompt blocks by aliasing (refcount, zero copies), hits
            # alias back, and pool pressure spills LRU device blocks to
            # the host trie in the existing wire format.
            prefix_cache.attach_device_pool(
                self.pool.alloc, self._read_pool_block
            )
        self.num_slots = num_slots
        self._free = list(range(num_slots))
        self._active: dict[int, _Active] = {}
        # Plain pool steps enqueued and not fetched yet, oldest first: one
        # between two calls of step(), two inside a call that found none;
        # and the newest one's picks, the next step's input where a slot
        # goes on decoding.
        self._flights: deque[_Flight] = deque()
        self._picks = jnp.zeros((num_slots,), jnp.int32)
        # What the device's queue holds behind the newest pool step: the
        # prefills dispatched since that step was enqueued and the tokens
        # they feed (host ints, moved onto the next _Flight); and, where the
        # next step will find the device drained for another cause than an
        # empty pool, that cause (_Flight.drain).
        self._prefills_queued = 0
        self._prefill_tokens_queued = 0
        self._drain: "str | None" = None
        self._queue: deque[_Pending] = deque()
        self._done: dict[int, dict] = {}
        self._next_order = 0
        self._emit_next = 0
        # Intake lock: submit/submit_done allocate output orders and append
        # to the queue from CLIENT threads (the multi-replica router has
        # several); admission/stepping stay single-threaded on the
        # scheduler's own loop.
        self._intake_lock = threading.Lock()
        # shutdown() flips this: late submissions (the router's redispatch
        # window can race a draining replica) answer a structured
        # "routing" error instead of queueing into a loop nobody drives.
        self._closed = False
        # Orders whose cancellation was requested (order -> message):
        # registered from ANY thread under the intake lock, EXECUTED by the
        # scheduler loop at the next step boundary (_expire) — the queue
        # answers, _active dict, slot pool, and stats are owned by the
        # scheduler thread, so a client thread never mutates them.
        self._cancel_pending: dict[int, str] = {}
        # Queued entries carrying a deadline (maintained under the intake
        # lock at every queue add/remove): lets the per-step expiry sweep
        # skip its O(backlog) queue scan entirely in the common
        # no-deadlines case, like the _cancel_pending guard below.
        self._queued_deadlines = 0
        # ---- resilience knobs (docs/ROBUSTNESS.md) ------------------------
        self.max_backlog = max_backlog          # 0 = unbounded (historical)
        self.admission_retries = max(0, admission_retries)
        self.retry_backoff_ms = retry_backoff_ms
        self.drafter_slow_ms = drafter_slow_ms
        # Circuit breakers: fail speculation / prefix reuse OPEN to the
        # plain byte-parity path after `threshold` consecutive faults; one
        # half-open probe per `cooldown_s` decides recovery. Both always
        # exist (record/allow are cheap) so degraded-mode logic has one
        # shape with or without telemetry.
        self._brk_spec = CircuitBreaker(
            "speculative", threshold=breaker_threshold,
            cooldown_s=breaker_cooldown_s, clock=breaker_clock,
            on_transition=self._on_breaker_transition,
        )
        self._brk_prefix = CircuitBreaker(
            "prefix_cache", threshold=breaker_threshold,
            cooldown_s=breaker_cooldown_s, clock=breaker_clock,
            on_transition=self._on_breaker_transition,
        )
        self.breakers = {b.name: b for b in (self._brk_spec, self._brk_prefix)}
        self.stats = {
            "admitted": 0, "steps": 0, "max_active": 0,
            # Prefix-cache accounting (host-side, filled at admission):
            # prompt tokens seen, tokens restored from stored blocks, and
            # the prefill forwards actually dispatched (a forward per chunk
            # of the suffix: what a prefix hit saves shows here).
            "prompt_tokens": 0, "prefix_hit_tokens": 0, "prefill_forwards": 0,
            # Paged-KV accounting (kv_layout="paged"): prompt tokens whose
            # restore was pure device-side block-table ALIASING vs tokens
            # restored through a host block copy, slots preempted on pool
            # exhaustion (answered "resource"), and device blocks spilled
            # to the host trie under pool pressure.
            "prefix_alias_tokens": 0, "host_restored_tokens": 0,
            "kv_preempted": 0, "kv_spilled_blocks": 0,
            # Resilience accounting (telemetry-free introspection for the
            # chaos suite): transient-admission retries, deadline expiries,
            # client cancellations, backpressure refusals.
            "retries": 0, "deadline_expired": 0, "cancelled": 0,
            "backpressure": 0,
        }
        # Telemetry (obs.Telemetry | None) records host-side scalars only, at
        # the step/admission boundaries that already exist — answers stay
        # byte-identical (tests/test_obs.py pins this) and the decode hot
        # path compiles the same programs (retrace budget stays 0).
        self._tel = telemetry
        # Spans go through the telemetry bundle's tracer (or the process
        # default without one): always into the in-memory buffer, into the
        # event log under --trace, and — the context-manager ones — into
        # the profiler's trace while a profiler session runs.
        self._tracer = mirrored_tracer(telemetry)
        # Victim attribution for breaker transitions: the trace id of the
        # request whose fault is being recorded, set around the fallible
        # regions (admission, retirement feed, drafting) on the scheduler
        # thread — _on_breaker_transition stamps it into serve.breaker
        # events so a chaos episode reconstructs as one trace tree.
        self._breaker_trace: str | None = None
        # SLO engine (obs/slo.py): burn-rate evaluation over the answer
        # stream. `slos` is a spec tuple or an --slo_spec string; needs
        # telemetry (gauges + slo.burn events are its whole output).
        # Span tap: an optional host-side callable handed every answer-
        # boundary span dict (the same payload `serve.request` events and
        # the SLO engine see) WITHOUT requiring a telemetry bundle — the
        # replica worker uses it to ship per-answer ttft/prefix numbers to
        # the router's own SLO engine over the wire (serve/replica.py).
        # Host-side only, never traced: jaxpr-inert by construction.
        self._span_tap = span_tap
        self._slo = None
        if telemetry is not None and slos:
            from transformer_tpu.obs.slo import SLOEngine, parse_slo_spec

            specs = parse_slo_spec(slos) if isinstance(slos, str) else tuple(slos)
            if specs:
                self._slo = SLOEngine(
                    specs, registry=telemetry.registry, emit=telemetry.emit
                )
        if telemetry is not None:
            reg = telemetry.registry
            self._m_slots_total = reg.gauge(
                "serve_slots_total", "configured KV-cache slots")
            self._m_slots_total.set(num_slots)
            self._m_weight_version = reg.gauge(
                "serve_weight_version",
                "crc32 of the serving weight_version tag (0 = untagged); "
                "flips exactly at the double-buffered param swap")
            self._m_weight_version.set(version_value(weight_version))
            self._m_active = reg.gauge(
                "serve_slots_active", "slots occupied by in-flight requests")
            self._m_backlog = reg.gauge(
                "serve_backlog", "submitted-but-not-admitted requests")
            self._m_ready = reg.gauge(
                "serve_ready", "completed responses awaiting drain")
            self._m_requests = reg.counter(
                "serve_requests_total", "requests submitted (incl. errors)")
            self._m_admissions = reg.counter(
                "serve_admissions_total", "requests admitted into a slot")
            self._m_retirements = reg.counter(
                "serve_retirements_total", "requests finished and retired")
            self._m_errors = reg.counter(
                "serve_errors_total", "requests answered with an error")
            self._m_steps = reg.counter(
                "serve_steps_total", "pool decode steps executed")
            self._m_steps_ahead = reg.counter(
                "serve_steps_ahead_total",
                "pool decode steps enqueued while the device still had work "
                "queued (an earlier step unfetched and nothing waited for "
                "since: the device never waited for the host there)")
            self._m_oversteps = reg.counter(
                "serve_oversteps_total",
                "rows a pool decode step ran for a slot that had ended in "
                "the step before it (an EOS or a budget seen one step late, "
                "a cancellation, an expiry, a preemption); their picks are "
                "dropped")
            self._m_tokens = reg.counter(
                "serve_generated_tokens_total", "tokens emitted to clients")
            self._m_queue_s = reg.histogram(
                "serve_queue_seconds", "submit -> slot admission")
            self._m_prefill_s = reg.histogram(
                "serve_prefill_seconds", "admission -> prompt ingested")
            self._m_ttft_s = reg.histogram(
                "serve_ttft_seconds", "submit -> first generated token")
            self._m_total_s = reg.histogram(
                "serve_request_seconds", "submit -> response complete")
            self._m_step_s = reg.histogram(
                "serve_step_seconds", "one pool step (all slots, one token)")
            if speculate_k:
                self._m_spec_drafted = reg.counter(
                    "serve_spec_drafted_total",
                    "draft tokens proposed to verify steps")
                self._m_spec_accepted = reg.counter(
                    "serve_spec_accepted_total",
                    "draft tokens the target model accepted")
                self._m_spec_rejected = reg.counter(
                    "serve_spec_rejected_total",
                    "draft tokens rejected or wasted past a mismatch")
            if prefix_cache is not None:
                self._m_prefix_hit = reg.counter(
                    "serve_prefix_hit_tokens_total",
                    "prompt tokens restored from the prefix cache "
                    "(no model forward)")
                self._m_prefix_evicted = reg.counter(
                    "serve_prefix_evicted_blocks_total",
                    "prefix-cache KV blocks evicted under the byte budget")
            if self.paged:
                self._m_pool_used = reg.gauge(
                    "serve_kv_pool_used_blocks",
                    "paged KV pool blocks referenced by live slots or the "
                    "device-resident prefix tier")
                self._m_pool_free = reg.gauge(
                    "serve_kv_pool_free_blocks",
                    "paged KV pool blocks on the free list")
                self._m_pool_used.set(self.pool.alloc.used_blocks)
                self._m_pool_free.set(self.pool.alloc.free_blocks)
                if self._moe_layer is not None:
                    self._m_moe_assign = reg.counter(
                        "serve_moe_assignments_total",
                        "router picks of decode steps that landed on an "
                        "expert this replica holds")
                    self._m_moe_hit = reg.counter(
                        "serve_moe_experts_hit_total",
                        "experts that received a token, summed over expert "
                        "layers and decode steps")
                    self._m_moe_max_load = reg.counter(
                        "serve_moe_max_load_total",
                        "rows the most-loaded held expert received, summed "
                        "over expert layers and decode steps")
                if prefix_cache is not None:
                    self._m_alias_tokens = reg.counter(
                        "serve_prefix_alias_tokens_total",
                        "prompt tokens served by device-side block-table "
                        "aliasing (zero host<->device copies) — a subset "
                        "of serve_prefix_hit_tokens_total; the remainder "
                        "was restored through a host block copy")
            if self.decode_kernel == "paged_flash":
                # Which way each attention layer's decode kernel fetches its
                # pages, by the kernel's own rule over the pool as allocated.
                from transformer_tpu.kernels.paged_flash import streams

                routes = [
                    streams(layer["k"].shape, layer["k"].dtype, "k_scale" in layer)
                    for layer in self.pool.caches if "k" in layer
                ]
                reg.gauge(
                    "serve_attn_layers_streamed",
                    "attention layers whose paged_flash decode attention "
                    "copies pool pages by hand (the streamed route)",
                ).set(sum(routes))
                reg.gauge(
                    "serve_attn_layers_tiled",
                    "attention layers whose paged_flash decode attention "
                    "takes the tiled fallback (int8 pools, heads that fill "
                    "no whole lane rows)",
                ).set(len(routes) - sum(routes))
            if self._state_layers:
                reg.gauge(
                    "serve_state_layers",
                    "layers whose per-slot state is not rows a position "
                    "(short convolutions, delta-rule layers)",
                ).set(self._state_layers)
                reg.gauge(
                    "serve_state_bytes_per_slot",
                    "bytes a slot holds for those layers' state",
                ).set(self._state_bytes)
            latent = [
                k for k in map(cfg.layer_kind, range(cfg.num_layers))
                if k.mixer == "mla"
            ]
            if latent:
                reg.gauge(
                    "serve_latent_layers",
                    "layers whose pool holds one latent row a position",
                ).set(len(latent))
                reg.gauge(
                    "serve_latent_bytes_per_position",
                    "bytes a position holds in those layers' pools, as "
                    "stored (a row padded to whole lane rows)",
                ).set(cfg.compute_dtype.itemsize * sum(
                    latent_width(k.latent_rank, k.latent_shared_dim)
                    for k in latent
                ))
            self._m_deadline = reg.counter(
                "serve_deadline_expired_total",
                "requests answered with a deadline error")
            self._m_cancelled = reg.counter(
                "serve_cancelled_total", "requests cancelled by the client")
            self._m_backpressure = reg.counter(
                "serve_backpressure_total",
                "requests refused at submit (max_backlog)")
            self._m_retries = reg.counter(
                "serve_admission_retries_total",
                "transient admission faults retried with backoff")
            self._m_admit_pool_lost = reg.counter(
                "serve_admit_pool_lost_total",
                "admissions that failed after their prefill took the donated "
                "pool: the scheduler stops (0 while it serves)")

    # ---- request intake ---------------------------------------------------

    def _on_breaker_transition(self, name: str, old: str, new: str) -> None:
        """Breaker state -> obs: a gauge (0 closed / 1 half-open / 2 open)
        plus a ``serve.breaker`` event per transition — `obs summarize`
        derives degraded-time from the event stream, and the event carries
        the trace id of the request whose fault tripped it (when tracing).
        Host-side only; no-op without telemetry."""
        if self._tel is None:
            return
        self._tel.registry.gauge(
            f"serve_breaker_state_{name}",
            "circuit-breaker state: 0 closed, 1 half-open, 2 open",
        ).set(BREAKER_STATE_VALUE[new])
        extra = {}
        if self._breaker_trace is not None:
            extra["trace"] = self._breaker_trace
        self._tel.emit(
            "serve.breaker", name=name, state=new, previous=old, **extra
        )

    # ---- tracing / SLO plumbing -------------------------------------------

    def _traced(self, name: str, parent, **attrs):
        """A ``tracer.span`` context with an explicit parent — request-
        lifecycle spans must tie to THEIR request's tree, never to whatever
        step span happens to be current."""
        return self._tracer.span(name, parent=parent, **attrs)

    def _record_request(self, span: dict, root=None) -> None:
        """The one answer-boundary funnel: every ``serve.request`` span
        event goes through here so the trace id is stamped uniformly and
        the SLO engine sees exactly what the log sees."""
        if root is not None:
            span.setdefault("trace", root.ctx.trace_id)
        if self._slo is not None:
            self._slo.record(dict(span))
        if self._span_tap is not None:
            self._span_tap(dict(span))
        if self._tel is not None:
            self._tel.emit("serve.request", **span)

    @staticmethod
    def _end_spans(obj, attrs: "tuple[str, ...]", **fields) -> None:
        """Close any still-open spans named by ``attrs`` on a _Pending or
        _Active (defensive: every error path funnels through one of the
        answer helpers, and a span left open would fail the completeness
        tests)."""
        for attr in attrs:
            sp = getattr(obj, attr, None)
            if sp is not None:
                sp.end(**fields)
                setattr(obj, attr, None)

    def _trace_prefill_done(self, st: _Active) -> None:
        """The prompt is fully in cache: close the prefill span and open
        the decode span — called exactly where ``t_prefill`` is finalized
        (admission for full-prefill requests, the boundary step for
        chunked/tail-fed ones)."""
        if st.span_prefill is not None:
            st.span_prefill.end(prompt_tokens=st.prompt_len,
                                prefix_hit_tokens=st.prefix_hit)
            st.span_prefill = None
            st.span_decode = self._tracer.start_span(
                "serve.decode", parent=st.span_root, lane=st.span_root.lane
            )

    # ---- paged-KV plumbing (kv_layout="paged") ----------------------------

    def _read_pool_block(self, bid: int):
        """Fetch ONE pool block to host prefix-cache format — the only
        host<->device block copy the paged prefix tier ever pays, and only
        for spill-under-pressure or a wire export (--disaggregate handoff,
        supervisor cache warming). The device-resident HIT path never
        reaches here (pinned by test)."""
        return jax.device_get(
            self._fn_pool_read_block(
                self.pool.caches, jnp.int32(bid), self.cfg.head_dim
            )
        )

    def _paged_alloc(self, fn):
        """Run an allocator mutation with ONE spill-and-retry rung: on
        pool exhaustion, ask the prefix cache's device tier to release
        LRU blocks (their data spills to the host trie in the wire format
        first), then retry. Re-raises ``KVPoolExhausted`` when the pool
        is genuinely full of live slots — admission maps that to a
        retryable transient, the step path to a preemption."""
        from transformer_tpu.kernels.kv_pool import KVPoolExhausted

        try:
            return fn()
        except KVPoolExhausted:
            if self.prefix_cache is None:
                raise
            freed = self.prefix_cache.release_device_blocks(
                max(1, self.pool.slot_blocks)
            )
            self.stats["kv_spilled_blocks"] += freed
            if not freed:
                raise
            return fn()

    def _paged_ensure(self, slot: int, tokens: int) -> None:
        """Grow ``slot``'s block table to cover ``tokens`` positions."""
        self._paged_alloc(lambda: self.pool.alloc.ensure(slot, tokens))

    def _paged_cow(self, slot: int, start: int, end: int) -> None:
        """Copy-on-write guard before writing positions ``[start, end)``:
        any table block shared with the device tier (or another slot) is
        split — fresh block allocated, contents copied ON DEVICE, table
        updated — before the write dispatches. Serving flows only write
        past the block-aligned aliased prefix, so this is normally a
        no-op; it is the guard that makes aliasing safe by construction."""
        pairs = self._paged_alloc(
            lambda: self.pool.alloc.make_writable(slot, start, end)
        )
        if pairs and self._state_layers:
            raise ValueError(
                "a copy-on-write fork cannot serve a model with a stateful "
                "layer: the blocks a fork shares hold rows a position, and "
                "the state a slot keeps beside them at the fork's position "
                "is not kept"
            )
        if pairs:
            src = jnp.asarray(_pow2_pad([s for s, _ in pairs]), jnp.int32)
            dst = jnp.asarray(_pow2_pad([d for _, d in pairs]), jnp.int32)
            self.pool.caches = self._fn_pool_copy_blocks(
                self.pool.caches, src, dst
            )

    def _paged_restore(self, slot: int, hit, m: int) -> int:
        """Paged restore of a matched ``m``-token prefix: device-tier
        nodes ALIAS their pool block into the slot's table (zero model
        forwards, zero host<->device copies); host-tier nodes take a
        fresh block and ride ONE batched scatter write (then the device
        tier adopts the written block, so the NEXT hit aliases). Returns
        the aliased token count."""
        B = self.pool.block_tokens
        alloc = self.pool.alloc
        aliased = 0
        host_bids: list[int] = []
        host_payload: list = []  # per restored block: per-layer dicts
        adopt: list = []
        for node, bid, blocks in hit.paged_plan():
            if bid is not None:
                self._paged_alloc(lambda b=bid: alloc.extend(slot, bid=b))
                aliased += B
            else:
                _, new_bid = self._paged_alloc(lambda: alloc.extend(slot))
                host_bids.append(new_bid)
                host_payload.append(blocks)
                adopt.append((node, new_bid))
        if host_bids:
            bids = _pow2_pad(host_bids)
            pad = len(bids) - len(host_bids)
            stacked = [
                {
                    key: np.concatenate(
                        [np.asarray(blk[li][key]) for blk in host_payload]
                        + [np.zeros_like(host_payload[0][li][key])] * pad,
                        axis=0,
                    )
                    for key in host_payload[0][li]
                }
                for li in range(len(host_payload[0]))
            ]
            self.pool.caches = self._fn_pool_write_blocks(
                self.pool.caches, jnp.asarray(bids, jnp.int32), stacked
            )
            for node, bid in adopt:
                self.prefix_cache.adopt_device(node, bid)
        # Stats are recorded by the caller at admission SUCCESS (next to
        # prefix_hit_tokens): counting here would double-count retried
        # admissions and break the alias <= hit invariant.
        return aliased

    def _paged_prepare(self, width: int) -> bool:
        """Before a paged step is enqueued: every occupied slot needs blocks
        covering its write range ``[at, at + width)``, CoW-split where
        shared, with ``at`` the position that step feeds (``pos`` plus the
        steps in flight). Pool exhaustion (after the spill ladder) preempts
        the REQUESTING slot with a structured ``resource`` answer carrying
        its partial continuation — bounded degradation, never a corrupted
        neighbor. That is decided only with nothing in flight, where every
        finished slot has given its blocks back: a step ahead of one in
        flight is simply not enqueued (False), and the next call asks again
        from there."""
        from transformer_tpu.kernels.kv_pool import KVPoolExhausted

        for slot, st in list(self._active.items()):
            if st.spent:
                continue  # the row writes nothing kept
            at = st.pos + st.sent
            try:
                self._paged_ensure(slot, at + width)
                self._paged_cow(slot, at, at + width)
            except KVPoolExhausted as e:
                if self._flights:
                    return False
                self.stats["kv_preempted"] += 1
                self._abort(
                    slot, st, "resource",
                    f"kv pool exhausted after {len(st.emitted)} of "
                    f"{st.max_new} tokens: {e}",
                )
        return True

    def _paged_gauges(self) -> None:
        if self.paged and self._tel is not None:
            self._m_pool_used.set(self.pool.alloc.used_blocks)
            self._m_pool_free.set(self.pool.alloc.free_blocks)

    # ---- live weights: the two-version param slot (serve/upgrade.py) ------

    def stage_params(self, params, version: str) -> None:
        """Stage a new weight set for the double-buffered swap. The new
        pytree must be a structural twin of the serving one — same
        treedef, same per-leaf shapes AND dtypes — so the flip re-runs
        every compiled program with new operand values and **zero
        recompiles**; any mismatch raises here, before anything is
        scheduled, and serving is untouched. While a stage is pending,
        admission pauses (the local quiesce): every in-flight request
        finishes on its admission-time weights, and the flip happens at
        the next drained step boundary (:meth:`step`)."""
        cur = jax.tree_util.tree_flatten_with_path(self.params)
        new = jax.tree_util.tree_flatten_with_path(params)
        if jax.tree_util.tree_structure(self.params) != (
            jax.tree_util.tree_structure(params)
        ):
            raise ValueError(
                f"staged weights for version {version!r} have a different "
                "pytree structure than the serving params — a swap would "
                "recompile (or crash) every program; refuse it"
            )
        mismatched = []
        for (path, a), (_, b) in zip(cur[0], new[0]):
            a_s, b_s = np.shape(a), np.shape(b)
            a_d = getattr(a, "dtype", np.asarray(a).dtype)
            b_d = getattr(b, "dtype", np.asarray(b).dtype)
            if a_s != b_s or a_d != b_d:
                key = "/".join(str(getattr(p, "key", p)) for p in path)
                mismatched.append(f"{key}: {b_s}/{b_d} != {a_s}/{a_d}")
        if mismatched:
            raise ValueError(
                f"staged weights for version {version!r} mismatch the "
                f"serving spec on {len(mismatched)} leaf/leaves "
                f"({'; '.join(mismatched[:3])}) — refused before any swap "
                "was scheduled"
            )
        if self._sharded is not None:
            # Sharded replica: the twin check grows SHARDING specs. Staged
            # leaves already committed to a device layout must match the
            # serving mesh's partition rules — a pytree living on a
            # different mesh would reshard (or crash) at the flip, so it
            # is refused here with serving untouched; host-loaded arrays
            # (the checkpoint path) pass and are committed below, keeping
            # the swap zero-recompile.
            bad = self._sharded.check_staged_shardings(params)
            if bad:
                raise ValueError(
                    f"staged weights for version {version!r} carry sharding "
                    f"specs incompatible with the serving mesh "
                    f"(data={self.mesh_size}) on {len(bad)} leaf/leaves "
                    f"({'; '.join(bad[:3])}) — refused before any swap was "
                    "scheduled"
                )
            params = self._sharded.place_params(params)
        self._staged = (params, str(version))

    def stage_rollback(self) -> str:
        """Re-stage the resident PREVIOUS weights (the second buffer a
        completed swap left behind): the canary-rollback path. Returns the
        version being rolled back to; raises when no swap ever landed."""
        if self._prev is None:
            raise ValueError(
                "no resident previous weights to roll back to (no swap has "
                "completed on this scheduler)"
            )
        params, version = self._prev
        self._staged = (params, version)
        return version

    @property
    def swap_pending(self) -> bool:
        return self._staged is not None

    def consume_swap_events(self) -> "list[dict]":
        """Drain completed/aborted swap notifications (the replica worker
        forwards them to the router as ``upgraded`` messages)."""
        out = list(self._swap_events)
        self._swap_events.clear()
        return out

    def _maybe_swap(self) -> None:
        """The step-boundary flip: only once the pool is DRAINED (every
        in-flight request answered from its admission-time weights) does
        the staged pair become the serving pair; the displaced pair stays
        resident for O(1) rollback. The ``ckpt.swap`` fault point fires
        here — an injected failure aborts the swap with the old weights
        still serving and zero requests disturbed."""
        if self._staged is None or self._active:
            return
        params, version = self._staged
        self._staged = None
        try:
            maybe_fail("ckpt.swap")
        except OSError as e:
            # InjectedFault (and any real OS-level swap veto) aborts the
            # swap, never the scheduler: old weights keep serving and the
            # worker reports the failure upstream.
            self._swap_events.append({
                "ok": False, "version": version,
                "error": f"{type(e).__name__}: {e}",
            })
            return
        self._prev = (self.params, self.weight_version)
        self.params = params
        self.weight_version = version
        self._swap_events.append({"ok": True, "version": version})
        if self._tel is not None:
            self._m_weight_version.set(version_value(version))

    def submit(self, req: dict) -> int:
        now = time.perf_counter()
        # Root span BEFORE the lock (id generation is not free): parents
        # under an incoming W3C "traceparent" when the request carries one
        # — the cross-process hook the router tier rides. Invalid headers
        # degrade to a fresh trace (W3C semantics), never an error.
        root = self._tracer.start_span(
            "serve.request", lane="intake",
            parent=SpanContext.from_traceparent(req.get("traceparent")),
        )
        queue_span = self._tracer.start_span(
            "serve.queue", parent=root, lane="intake"
        )
        refused = None  # the refusal message, captured INSIDE the lock —
        # reading self._done[order] back after release would race the
        # scheduler thread's drain_ready() popping it.
        refused_code = "backpressure"
        with self._intake_lock:
            order = self._next_order
            self._next_order += 1
            if self._closed:
                # Post-shutdown submission (the router's redispatch path
                # hits this window): answer NOW with a structured routing
                # error — queueing would strand the request in a loop that
                # will never admit again.
                refused = (
                    "scheduler is shut down and accepts no new requests; "
                    "resubmit to a live replica"
                )
                refused_code = "routing"
                self._done[order] = error_answer(refused_code, refused)
            elif self.max_backlog and len(self._queue) >= self.max_backlog:
                # Bounded admission backpressure: refuse NOW with a
                # structured error instead of queueing without bound — the
                # client sees a retryable condition while in-flight
                # requests keep their latency.
                self.stats["backpressure"] += 1
                refused = (
                    f"admission queue is full ({self.max_backlog} "
                    "requests); retry after a backoff"
                )
                self._done[order] = error_answer("backpressure", refused)
            else:
                deadline = None
                try:
                    d = req.get("deadline_ms")
                    if d is not None:
                        deadline = now + float(d) / 1e3
                except (TypeError, ValueError):
                    pass  # _start re-parses and answers the validation error
                self._queue.append(
                    _Pending(order=order, req=req, t_enqueue=now,
                             deadline=deadline, span_root=root,
                             span_queue=queue_span)
                )
                if deadline is not None:
                    self._queued_deadlines += 1
        if refused is not None:
            queue_span.end(error=refused)
            root.end(order=order, error=refused, code=refused_code)
        if self._tel is not None:
            self._m_requests.inc()
            if refused is not None:
                if refused_code == "backpressure":
                    self._m_backpressure.inc()
                self._m_errors.inc()
                self._record_request(
                    {"order": order, "total_s": 0.0, "error": refused,
                     "code": refused_code},
                    root=root,
                )
        return order

    def submit_done(self, resp: dict) -> int:
        # Pre-answered (parse/routing) responses still get a (leaf) span:
        # every output order is accounted for in the trace.
        root = self._tracer.start_span("serve.request", lane="intake")
        with self._intake_lock:
            order = self._next_order
            self._next_order += 1
            self._done[order] = resp
        extra = {}
        if "error" in resp:
            extra["error"] = resp["error"]
            if "code" in resp:  # error code, like every error root
                extra["code"] = resp["code"]
        root.end(order=order, **extra)
        if self._tel is not None:
            self._m_requests.inc()
            if "error" in resp:
                self._m_errors.inc()
            span = {"order": order, "total_s": 0.0}
            if "error" in resp:
                span["error"] = resp["error"]
                if "code" in resp:
                    span["code"] = resp["code"]
            self._record_request(span, root=root)
        return order

    def cancel(self, order: int, message: str = "cancelled by client") -> bool:
        """Request cancellation of a queued or in-flight request. The
        cancellation is REGISTERED here (any thread, intake lock only) and
        EXECUTED by the scheduler loop at the next step boundary: the queue
        entry is dropped or the slot freed, and a structured "cancelled"
        error answers at the request's reserved output position, so
        arrival-order draining is unaffected and no prefix-cache pin can be
        left behind (admission releases its hit synchronously). Returns
        False when ``order`` is unknown, already answered, or already being
        cancelled; True means the cancellation will be honored unless the
        request completes first (it answers exactly once either way — the
        benign race of cancelling a finishing request)."""
        with self._intake_lock:
            if (
                order in self._done            # answered, not yet drained
                or order >= self._next_order   # never submitted
                or order < self._emit_next     # answered and drained
                or order in self._cancel_pending
            ):
                return False
            self._cancel_pending[order] = message
        return True

    def _answer_cancelled(self, p: _Pending, message: str) -> None:
        """Answer a queued (never-admitted) cancellation — scheduler
        thread only, like every other queue answer."""
        self.stats["cancelled"] += 1
        self._done[p.order] = error_answer("cancelled", message)
        root = p.span_root
        self._end_spans(p, ("span_queue", "span_admit", "span_prefill"))
        self._end_spans(
            p, ("span_root",), order=p.order, error=message, code="cancelled"
        )
        if self._tel is not None:
            now = time.perf_counter()
            self._m_cancelled.inc()
            self._m_errors.inc()
            span = {"order": p.order, "error": message, "code": "cancelled"}
            if p.t_enqueue:
                span["queue_s"] = round(now - p.t_enqueue, 6)
                span["total_s"] = round(now - p.t_enqueue, 6)
            self._record_request(span, root=root)

    @property
    def busy(self) -> bool:
        return bool(self._queue or self._active)

    @property
    def has_ready(self) -> bool:
        """True when ``drain_ready`` would release at least one response."""
        return self._emit_next in self._done

    @property
    def ready_count(self) -> int:
        """Completed-but-not-drained responses (includes out-of-order
        completions waiting behind the arrival-order emit head). The serve
        loop counts these toward its ingest cap so a flood of instantly
        answered lines — e.g. all-malformed input — cannot grow the host-side
        buffer without bound."""
        return len(self._done)

    @property
    def backlog(self) -> int:
        """Submitted-but-not-admitted requests (the serve loop bounds this
        so stdin backpressure survives — see ``cli/serve.py``)."""
        return len(self._queue)

    @property
    def active_count(self) -> int:
        return len(self._active)

    # ---- admission --------------------------------------------------------

    def admit(self) -> None:
        """Fill free slots from the queue (prefill-into-slot). A request
        that fails validation/encoding answers with its error alone — it
        never enters the pool, so it cannot poison co-batched requests.
        Transient faults (:class:`TransientError`, e.g. an injected prefill
        fault or a flaky device) get up to ``admission_retries`` re-tries
        with jittered exponential backoff before answering a structured
        "transient" error; entries whose backoff has not elapsed are
        skipped this tick, not dropped."""
        if self._staged is not None:
            # Quiesce: a staged weight swap is waiting for the pool to
            # drain. New admissions would re-fill it with requests pinned
            # to the OLD weights and starve the swap — queued requests
            # wait (deadline/cancel sweeps still run at step boundaries)
            # and admission resumes the moment the flip lands.
            return
        now = time.perf_counter()
        deferred: list[_Pending] = []
        while self._free:
            with self._intake_lock:
                # Pops (and the extendleft below) take the intake lock so
                # cancel()'s queue scan from a client thread never observes
                # a deque mutating under its iteration.
                if not self._queue:
                    break
                p = self._queue.popleft()
                if p.deadline is not None:
                    self._queued_deadlines -= 1
            if p.not_before > now:
                deferred.append(p)
                continue
            if p.deadline is not None and now >= p.deadline:
                self._answer_expired(p, now)
                continue
            with self._intake_lock:
                cancel_msg = self._cancel_pending.pop(p.order, None)
            if cancel_msg is not None:
                # Registered cancel caught before admission: answer without
                # ever paying the prefill (or taking a slot).
                self._answer_cancelled(p, cancel_msg)
                continue
            try:
                self._start(p)
            except TransientError as e:
                if self._pool_lost():
                    raise
                if p.attempts < self.admission_retries:
                    p.attempts += 1
                    wait_ms = backoff_ms(
                        self.retry_backoff_ms, p.attempts - 1, p.order
                    )
                    p.not_before = now + wait_ms / 1e3
                    deferred.append(p)
                    self.stats["retries"] += 1
                    # Spans opened by the failed attempt close with the
                    # fault; the request goes back to queueing, so a fresh
                    # queue span covers the backoff wait.
                    self._end_spans(
                        p, ("span_admit", "span_prefill"),
                        error=f"{type(e).__name__}: {e}", retried=True,
                    )
                    if p.span_queue is None:
                        p.span_queue = self._tracer.start_span(
                            "serve.queue", parent=p.span_root, lane="intake",
                            attempt=p.attempts,
                        )
                    if self._tel is not None:
                        self._m_retries.inc()
                        retry_ev = {
                            "order": p.order, "attempt": p.attempts,
                            "backoff_ms": round(wait_ms, 3),
                            "error": f"{type(e).__name__}: {e}",
                        }
                        retry_ev["trace"] = p.span_root.ctx.trace_id
                        self._tel.emit("serve.retry", **retry_ev)
                    continue
                self._answer_admission_error(p, e, now)
            except Exception as e:  # noqa: BLE001  # tpa: disable=TPA006 — per-request isolation: ANY admission failure must answer this request alone, never kill co-batched ones; a pool lost to the prefill is re-raised, it is no request's
                if self._pool_lost():
                    raise
                self._answer_admission_error(p, e, now)
        # Backoff-deferred entries return to the FRONT in arrival order:
        # output order is fixed by `order` anyway, this just keeps queue
        # scans (deadline expiry, cancel) seeing them.
        if deferred:
            with self._intake_lock:
                self._queue.extendleft(reversed(deferred))
                self._queued_deadlines += sum(
                    1 for p in deferred if p.deadline is not None
                )

    def _pool_lost(self) -> bool:
        """Whether the failed admission took the pool with it: the paged
        prefill is donated, so a leaf deleted means the pool went to a
        program that did not return its successor. No step can run on, and
        the failure is the pool's, not the request's, so ``admit`` raises
        it as a failed pool step would. A failure raised before the program
        was enqueued leaves every leaf alive and answers its request alone.
        A lost pool counts in ``serve_admit_pool_lost_total``."""
        lost = any(leaf.is_deleted() for leaf in jax.tree.leaves(self.pool.caches))
        if lost and self._tel is not None:
            self._m_admit_pool_lost.inc()
        return lost

    def _answer_admission_error(
        self, p: _Pending, e: BaseException, now: float
    ) -> None:
        code = classify_error(e)
        self._done[p.order] = error_answer(code, f"{type(e).__name__}: {e}")
        root = p.span_root
        self._end_spans(
            p, ("span_queue", "span_admit", "span_prefill"),
            error=type(e).__name__,
        )
        self._end_spans(
            p, ("span_root",), order=p.order,
            error=self._done[p.order]["error"], code=code,
        )
        if self._tel is not None:
            t_enq = p.t_enqueue
            self._m_errors.inc()
            self._record_request(
                {
                    "order": p.order,
                    "queue_s": round(now - t_enq, 6) if t_enq else 0.0,
                    "total_s": round(now - t_enq, 6) if t_enq else 0.0,
                    "error": self._done[p.order]["error"],
                    "code": code,
                },
                root=root,
            )

    def _answer_expired(self, p: _Pending, now: float) -> None:
        """A queued request's deadline elapsed before a slot freed."""
        self.stats["deadline_expired"] += 1
        self._done[p.order] = error_answer(
            "deadline",
            f"deadline_ms elapsed after {round((now - p.t_enqueue) * 1e3)}ms "
            "in the admission queue",
        )
        root = p.span_root
        self._end_spans(p, ("span_queue", "span_admit", "span_prefill"))
        self._end_spans(
            p, ("span_root",), order=p.order,
            error=self._done[p.order]["error"], code="deadline",
        )
        if self._tel is not None:
            self._m_deadline.inc()
            self._m_errors.inc()
            self._record_request(
                {
                    "order": p.order,
                    "queue_s": round(now - p.t_enqueue, 6),
                    "total_s": round(now - p.t_enqueue, 6),
                    "error": self._done[p.order]["error"],
                    "code": "deadline",
                },
                root=root,
            )

    def _expire(self, now: float) -> None:
        """Deadline sweep at a step boundary: queued requests whose
        deadline passed answer without ever taking a slot; in-flight ones
        free their slot mid-generation (the emitted prefix rides along as
        ``"partial"``)."""
        expired_q: list[_Pending] = []
        if self._queued_deadlines:
            with self._intake_lock:
                # Scan under the intake lock: client threads append to the
                # deque concurrently, and deque ITERATION (unlike popleft/
                # append) is not atomic. Answers are emitted after release —
                # telemetry takes locks of its own. The _queued_deadlines
                # guard keeps this O(backlog) scan off the per-step path
                # when no queued request carries a deadline.
                expired_q = [
                    p for p in self._queue
                    if p.deadline is not None and now >= p.deadline
                ]
                for p in expired_q:
                    self._queue.remove(p)
                    self._queued_deadlines -= 1
        for p in expired_q:
            self._answer_expired(p, now)
        if self._cancel_pending:
            with self._intake_lock:
                pending = dict(self._cancel_pending)
                cancelled_q = [
                    p for p in self._queue if p.order in pending
                ]
                for p in cancelled_q:
                    self._queue.remove(p)
                    if p.deadline is not None:
                        self._queued_deadlines -= 1
        else:
            pending, cancelled_q = {}, []
        for p in cancelled_q:
            self._answer_cancelled(p, pending[p.order])
        for slot, st in list(self._active.items()):
            if st.order in pending:
                # Cancellation registered by cancel() (any thread),
                # executed here on the scheduler thread that owns the pool.
                self._abort(slot, st, "cancelled", pending[st.order])
            elif st.deadline is not None and now >= st.deadline:
                self._abort(
                    slot, st, "deadline",
                    f"deadline_ms elapsed after {len(st.emitted)} of "
                    f"{st.max_new} tokens",
                )
        if pending:
            # Retire executed/answered registrations; one mid-admission at
            # this instant (popped from the queue, not yet in _active)
            # stays pending and is swept right after its admission lands.
            # An order that completed normally before its sweep was simply
            # answered once, normally — the benign race cancel() documents.
            with self._intake_lock:
                for order in pending:
                    if order in self._done or order < self._emit_next:
                        self._cancel_pending.pop(order, None)

    def _abort(self, slot: int, st: _Active, code: str, message: str) -> None:
        """Free an occupied slot WITHOUT normal retirement (deadline expiry
        or cancellation): the slot returns to the pool (admission resets
        its cache index, so stale K/V is provably invisible to the next
        occupant), nothing is fed to the prefix cache, and the request
        answers a structured error carrying whatever was generated so far.
        No prefix-cache pins can be outstanding here — admission releases
        its hit synchronously before the request ever reaches a step
        boundary."""
        del self._active[slot]
        if self.paged:
            self.pool.alloc.free_slot(slot)
        self._free.append(slot)
        resp = error_answer(code, message)
        if st.emitted:
            resp["partial"] = _detokenize_rows(
                np.asarray([st.emitted], np.int32), 1, self.tok
            )[0]
        if st.wv is not None:
            resp["weight_version"] = st.wv
        self._done[st.order] = resp
        if code == "deadline":
            self.stats["deadline_expired"] += 1
        elif code == "cancelled":
            self.stats["cancelled"] += 1
        root = st.span_root
        self._end_spans(st, ("span_prefill", "span_decode"))
        self._end_spans(
            st, ("span_root",), order=st.order, error=message, code=code,
            new_tokens=len(st.emitted),
        )
        if self._tel is not None:
            now = time.perf_counter()
            if code == "deadline":
                self._m_deadline.inc()
            elif code == "cancelled":
                self._m_cancelled.inc()
            self._m_errors.inc()
            span = {
                "order": st.order,
                "prompt_tokens": st.prompt_len,
                "new_tokens": len(st.emitted),
                "queue_s": round(st.t_admit - st.t_enqueue, 6),
                "total_s": round(now - st.t_enqueue, 6),
                "error": message,
                "code": code,
            }
            if st.wv is not None:
                span["weight_version"] = st.wv
            self._record_request(span, root=root)

    def _start(self, p: _Pending) -> None:
        """Admission wrapper: breaker-fault attribution (set by the inner
        body) must not outlive the admission — a stale trace id would be
        stamped onto the NEXT cooldown-driven breaker transition, blaming
        an unrelated request."""
        try:
            self._start_inner(p)
        finally:
            self._breaker_trace = None

    def _validate(self, req: dict, t_enq: float):
        """Tokenize and validate one request before a slot is popped, so a
        bad request answers alone: ``(ids, max_new, deadline, (sample,
        temperature, top_k, top_p, seed))``."""
        prompt = str(req["prompt"])
        ids = [self.tok.bos_id, *self.tok.encode(prompt)]
        L = len(ids)
        if L >= self.cfg.max_position:
            # Same failure mode (and message shape) as generate().
            raise ValueError(
                f"a prompt encodes to {L} tokens but the model's "
                f"max_position is {self.cfg.max_position}; shorten the prompt"
            )
        max_new = int(req.get("max_new", self.default_max_new))
        max_new = min(max_new, self.cfg.max_position - L)
        if L + 1 >= self.max_total:
            raise ValueError(
                f"a prompt encodes to {L} tokens but the slot budget "
                f"(serve_max_total) is {self.max_total}; shorten the prompt "
                "or raise --serve_max_total"
            )
        # A slot holds ``max_total`` tokens, prompt and answer together. The
        # answer's last token is never fed, so the last position written is
        # ``L + max_new - 2``, and the row a step in flight runs past a
        # budget's end lands on ``L + max_new - 1``: both inside the slot.
        max_new = min(max_new, self.max_total - L)
        deadline = None
        if req.get("deadline_ms") is not None:
            # float() raising (e.g. "soon") answers a validation error for
            # this request alone, like every other unconvertible field.
            deadline = (
                (t_enq or time.perf_counter())
                + float(req["deadline_ms"]) / 1e3
            )
        temperature = float(req.get("temperature", 0.0))
        sample = temperature > 0.0
        # Greedy never touches the rng or the truncation params: normalize
        # them (mirroring _signature's grouped path) so stray values neither
        # change the answer nor split step()'s pick groups into extra
        # byte-identical argmax compiles.
        top_k = int(req.get("top_k", 0)) if sample else 0
        top_p = float(req.get("top_p", 1.0)) if sample else 1.0
        seed = int(req.get("seed", 0)) if sample else 0
        if sample and top_k > self.cfg.target_vocab_size:
            # lax.top_k would raise INSIDE the jitted pick — validate before
            # a slot is popped so the bad request answers alone (the grouped
            # path's per-member retry answers the same line with an error).
            raise ValueError(
                f"top_k={top_k} exceeds the vocab size "
                f"{self.cfg.target_vocab_size}"
            )
        if req.get("cache_prefix") and self.cfg.attention_window:
            # An EXPLICIT cache_prefix=true on a rolling-window server is a
            # contract the server cannot honor (block restore addresses
            # rows by absolute position; the window buffer evicts them on
            # wrap) — answer this request alone with a structured error,
            # before any slot is popped, mirroring the speculative-rollback
            # refusal. Absent/false composes fine: the request just
            # prefills normally.
            raise ValueError(
                "cache_prefix=true cannot be honored: this server runs a "
                "rolling-window cache (attention_window), which the prefix "
                "cache refuses — resend with cache_prefix=false or serve "
                "without attention_window"
            )
        return ids, max_new, deadline, (sample, temperature, top_k, top_p, seed)

    def _start_inner(self, p: _Pending) -> None:
        order, req, t_enq = p.order, p.req, p.t_enqueue
        maybe_fail("serve.prefill")  # chaos point: admission-time fault
        # The queue phase ends here (a retry re-opens it); everything from
        # validation through the first pick is the admit span. Faults from
        # here on feed breakers under this request's name.
        self._end_spans(p, ("span_queue",))
        p.span_admit = self._tracer.start_span(
            "serve.admit", parent=p.span_root, lane="intake"
        )
        self._breaker_trace = p.span_root.ctx.trace_id
        with self._traced("admit.encode", p.span_admit, lane="intake"):
            ids, max_new, deadline, sampling = self._validate(req, t_enq)
        L = len(ids)
        sample, temperature, top_k, top_p, seed = sampling
        use_prefix = (
            self.prefix_cache is not None
            and bool(req.get("cache_prefix", True))
            # Degradation ladder: while the prefix breaker is open, opted-in
            # requests neither read nor feed the cache — they take the plain
            # byte-parity full-prefill path (answers identical either way).
            and self._brk_prefix.allow()
        )
        hit = None
        m = 0
        prefix_ok = True  # no cache fault during THIS admission
        if use_prefix:
            # Match the prompt MINUS its last token: at least one token must
            # go through the model forward — the admission pick needs
            # next-token logits, which a block restore cannot produce.
            try:
                with self._traced(
                    "prefix.match", p.span_admit, lane="intake"
                ) as msp:
                    hit = self.prefix_cache.match(ids[: L - 1])
                    m = hit.tokens
                    msp.set(hit_tokens=m)
            except Exception:  # noqa: BLE001  # tpa: disable=TPA006 — prefix reuse is an optional accelerator: ANY cache failure (corrupt block, injected fault, trie bug) feeds the breaker and degrades THIS admission to full prefill; it must never answer the request with an error
                self._brk_prefix.record_failure()
                prefix_ok = False
                hit, m = None, 0
        n_suffix = prefill_len_for(L - m, self.prefill_chunk)
        n = m + n_suffix
        slot = self._free.pop()
        t_admit = time.perf_counter()
        # The slot is known now: the request's remaining lifecycle renders
        # on this slot's lane (admit/queue stay on intake — they are
        # scheduler work, not slot residency).
        p.span_root.lane = f"slot{slot}"
        p.span_prefill = self._tracer.start_span(
            "serve.prefill", parent=p.span_root, lane=f"slot{slot}",
        )
        aliased = 0
        try:
            if m:
                try:
                    with self._traced(
                        "prefix.restore", p.span_prefill,
                        lane=f"slot{slot}", tokens=m,
                    ):
                        if self.paged:
                            aliased = self._paged_restore(slot, hit, m)
                        else:
                            self.pool.caches = self._fn_slot_restore(
                                self.pool.caches, jnp.int32(slot),
                                hit.stacked(self.max_total + self.speculate_k),
                            )
                except TransientError:
                    # Pool pressure (KVPoolExhausted maps below), retried
                    # faults: not the cache's fault — no breaker feed.
                    raise
                except Exception as e:  # noqa: BLE001  # tpa: disable=TPA006 — same degradation contract as the match above: a failed restore falls back to full prefill (the slot's index reset makes any partial restore invisible), feeding the breaker instead of erroring the request
                    from transformer_tpu.kernels.kv_pool import KVPoolExhausted

                    if isinstance(e, KVPoolExhausted):
                        # Exhaustion mid-restore is pool pressure, not a
                        # cache fault: surface as a retryable transient.
                        raise TransientError(str(e)) from e
                    self._brk_prefix.record_failure()
                    prefix_ok = False
                    hit.release()
                    hit, m, aliased = None, 0, 0
                    if self.paged:
                        # Drop any partially-aliased table entries so the
                        # fallback full prefill starts from a clean row.
                        self.pool.alloc.free_slot(slot)
                    n_suffix = prefill_len_for(L, self.prefill_chunk)
                    n = n_suffix
            if self.paged:
                from transformer_tpu.kernels.kv_pool import KVPoolExhausted

                try:
                    with self._traced(
                        "admit.blocks", p.span_admit, lane="intake"
                    ):
                        self._paged_ensure(slot, n)
                        self._paged_cow(slot, m, n)
                except KVPoolExhausted as e:
                    raise TransientError(str(e)) from e
            # Returns at enqueue: the prefill runs on while the host goes on.
            with self._traced(
                "admit.prefill_dispatch", p.span_admit, lane="intake"
            ):
                if self.paged:
                    logits, self.pool.caches = self._fn_slot_prefill_paged(
                        self.params, self.pool.caches,
                        self.pool.alloc.table_device(), jnp.int32(slot),
                        jnp.asarray([ids[m:n]], jnp.int32), jnp.int32(m),
                        self.cfg, self.prefill_chunk,
                        self.pool.block_tokens, self.pool.buf_len,
                    )
                else:
                    logits, self.pool.caches = self._fn_slot_prefill(
                        self.params, self.pool.caches, jnp.int32(slot),
                        jnp.asarray([ids[m:n]], jnp.int32), jnp.int32(m),
                        self.cfg, self.prefill_chunk,
                    )
        except Exception:
            if self.paged:
                self.pool.alloc.free_slot(slot)
            self._free.append(slot)
            raise
        finally:
            if hit is not None:
                hit.release()
        if use_prefix and prefix_ok:
            # The cache served this admission end-to-end (hit or clean
            # miss): a half-open probe closes the breaker here.
            self._brk_prefix.record_success()
        self.stats["prompt_tokens"] += L
        self.stats["prefix_hit_tokens"] += m
        if self.paged:
            # Restored tokens split: m = aliased (device table op, zero
            # copies) + host-restored (one batched block write).
            self.stats["prefix_alias_tokens"] += aliased
            self.stats["host_restored_tokens"] += m - aliased
        chunk = self.prefill_chunk
        self.stats["prefill_forwards"] += (
            -(-n_suffix // chunk) if chunk > 0 else 1
        )
        # On the device's queue behind the newest pool step (a restore is no
        # prefill); the next step enqueued takes both.
        self._prefills_queued += 1
        self._prefill_tokens_queued += n_suffix
        if m and self._tel is not None and self.prefix_cache is not None:
            self._m_prefix_hit.inc(m)
            if aliased:
                self._m_alias_tokens.inc(aliased)
        spec = bool(self.speculate_k) and bool(req.get("speculate", True))
        st = _Active(
            order=order, ids=ids, prompt_len=L, pos=n, cur=PAD_ID,
            emitted=[], max_new=max_new,
            key=_request_key(seed),
            sample=sample, temperature=temperature, top_k=top_k, top_p=top_p,
            seed=seed, spec=spec,
            use_prefix=use_prefix, prefix_hit=m, wv=self.weight_version,
            dstate=(
                self.drafter.start(ids) if spec and self.drafter is not None
                else None
            ),
            t_enqueue=t_enq or t_admit, t_admit=t_admit,
            # Stamped where the whole prompt is in cache: below, after the
            # first pick's sync, for a full prefill; at the boundary step
            # for a chunked (tail-fed) one.
            deadline=deadline,
            # Span ownership transfers from the _Pending to the slot state:
            # from here on, answer paths close through st, not p.
            span_root=p.span_root, span_prefill=p.span_prefill,
        )
        p.span_root = p.span_prefill = None
        self._active[slot] = st
        self.stats["max_active"] = max(self.stats["max_active"], len(self._active))
        p.span_admit.set(
            slot=slot, prefix_hit_tokens=st.prefix_hit, prompt_tokens=L,
            prefill_tokens=n_suffix,
        )
        if deadline is not None and time.perf_counter() >= deadline:
            # Prefill-boundary deadline check: the prompt ingest alone
            # consumed the budget — answer now instead of decoding tokens
            # the client has already given up on.
            self._end_spans(p, ("span_admit",))
            self.stats["admitted"] += 1
            if self._tel is not None:
                self._m_admissions.inc()
            self._abort(
                slot, st, "deadline", "deadline_ms elapsed during prefill"
            )
            return
        if n < L:
            st.cur = ids[n]  # un-prefilled prompt tail feeds token-by-token
            self._end_spans(p, ("span_admit",))
        else:
            try:
                # int() waits for the prefill and the pick: the one sync of
                # an admission.
                with self._traced(
                    "admit.first_pick", p.span_admit, lane="intake"
                ):
                    tokv = int(
                        _pick_one(
                            logits, jnp.asarray(st.key), jnp.int32(n - 1),
                            jnp.float32(st.temperature),
                            sample=st.sample, top_k=st.top_k, top_p=st.top_p,
                        )
                    )
                if self._flights:
                    # The wait was for the step in flight too: the device is
                    # empty until the next step is built and enqueued.
                    self._drain = "first_pick"
            except Exception:
                # The pick failing must not leak the slot: restore the pool
                # so the error answers this request alone (admit() catches;
                # spans travel back to the _Pending so the answer path can
                # close them).
                del self._active[slot]
                if self.paged:
                    self.pool.alloc.free_slot(slot)
                self._free.append(slot)
                p.span_root, p.span_prefill = st.span_root, st.span_prefill
                raise
            # The pick above synced the prefill: the whole prompt is in
            # cache, decoding starts now.
            st.t_prefill = time.perf_counter()
            self._end_spans(p, ("span_admit",))
            self._trace_prefill_done(st)
            self._consume_pick(slot, st, tokv)
        self.stats["admitted"] += 1
        if self._tel is not None:
            self._m_admissions.inc()

    # ---- stepping ---------------------------------------------------------

    def step(self) -> None:
        """Advance every occupied slot (ONE pooled forward): one token per
        slot on the plain path, up to ``speculate_k + 1`` on the
        speculative verify path. Retires finished slots; no-op when the
        pool is idle."""
        if not self._active:
            # An idle pool leaves no span: a serve loop polls it thousands
            # of times a second. Whatever drained the device last, the next
            # step finds it idle.
            self._drain = None
            self._step_prepare()
            self._step_publish()
            return
        with self._tracer.span(
            "scheduler.step", lane="scheduler",
            active=len(self._active), backlog=len(self._queue),
        ) as step_span:
            with self._tracer.span("step.prepare", lane="scheduler") as sp:
                preempted = self.stats["kv_preempted"]
                roomy = self._step_prepare()
                sp.set(preempted=self.stats["kv_preempted"] - preempted)
            # The slots this step feeds (prepare may have expired some).
            step_span.set(active=len(self._active))
            if not self._active:
                with self._tracer.span(
                    "step.bookkeep", lane="scheduler", retired=0
                ):
                    self._step_publish()
            elif self.speculate_k:
                self._step_verify(step_span)
            else:
                self._step_plain(step_span, roomy)

    def _step_prepare(self) -> bool:
        """What comes before a step is enqueued; False where the paged pool
        has no block for a step ahead of the one in flight."""
        self._expire(time.perf_counter())
        if not self._active:
            # A drained pool drops the picks of a step still in flight: every
            # slot it stepped has answered.
            self._flights.clear()
        # The step-boundary weight flip: no-op unless a verified stage is
        # pending AND the expiry sweep just drained the last slot (so nothing
        # is in flight either).
        self._maybe_swap()
        if self._active and self.paged:
            # Paged capacity pass BEFORE the step arrays are built: a
            # pool-exhausted slot is preempted here (answered "resource")
            # and must not be stepped.
            return self._paged_prepare(
                self.speculate_k + 1 if self.speculate_k else 1
            )
        return True

    def _step_publish(self) -> None:
        """The end of every step: gauges, the periodic sinks, the SLO tick."""
        if self._tel is None:
            return
        self._m_active.set(len(self._active))
        self._m_backlog.set(len(self._queue))
        self._m_ready.set(len(self._done))
        self._paged_gauges()
        self._tel.maybe_flush()
        if self._slo is not None:
            self._slo.maybe_evaluate()

    def _dispatch_pool_step(self, table, positions, toks):
        """Enqueue the one pool-step program this scheduler's layout and
        kernel choice fixed: ``(logits, new pool caches)``."""
        if self.paged and self.decode_kernel == "paged_flash":
            return _pool_step_paged_flash(
                self.params, self.pool.caches,  # tpa: disable=TPA005 — exclusive branches: exactly one runs per step and the caller rebinds self.pool.caches from its result
                table, positions, toks, self.cfg,
                self.pool.block_tokens, self._kernel_interpret,
            )
        if self.paged:
            return self._fn_pool_step_paged(
                self.params, self.pool.caches,  # tpa: disable=TPA005 — exclusive branches: exactly one runs per step and the caller rebinds self.pool.caches from its result
                table, positions, toks, self.cfg,
                self.pool.block_tokens, self.pool.buf_len,
            )
        return self._fn_pool_step(
            self.params, self.pool.caches,  # tpa: disable=TPA005 — exclusive branches: exactly one runs per step and the caller rebinds self.pool.caches from its result
            toks, self.cfg,
        )

    def _step_plain(self, step_span, roomy: bool) -> None:
        """One call of the plain decode loop: enqueue the step AFTER the one
        in flight, then fetch and bookkeep the one in flight, so the device
        has its next step queued while the host does everything else. What
        that step feeds is known without the picks in flight: positions go
        up by one whatever was picked, a slot that goes on decoding takes its
        token from those picks on the device (``_choose``), a prompt tail
        and a new admission bring theirs from the host. What is not known is
        an EOS: its slot runs one row too many, whose pick is dropped and
        whose write lands behind the slot's last valid row. With nothing in
        flight the call enqueues two steps, so every call retires one."""
        t_step = time.perf_counter()
        span = partial(self._tracer.span, lane="scheduler")
        in_flight = bool(self._flights)
        if roomy:
            self._enqueue_step(span)
        if not in_flight:
            with span("step.prepare"):
                roomy = not self.paged or self._paged_prepare(1)
            if roomy:
                self._enqueue_step(span)
        if not roomy:
            self._drain = "no_block"
        flight = self._flights.popleft()
        # The one wait for the device of the call: for the step before the
        # one just enqueued, every sampling group's picks in one vector.
        with span("step.fetch"):
            out = np.asarray(flight.picks)
        with span("step.bookkeep") as sp:
            emitted = continued = walked = 0
            before = len(self._active)
            live = []
            for slot, st in flight.pairs:
                if self._active.get(slot) is not st:
                    # Ended since this step's dispatch (an EOS or a budget in
                    # the step before, a cancellation, an expiry, a
                    # preemption): the pick is nobody's, least of all the
                    # slot's next occupant's.
                    continue
                live.append(slot)
                st.sent -= 1
                st.pos += 1
                st.forwards += 1
                if st.pos < st.prompt_len:
                    st.cur = st.ids[st.pos]  # still consuming the prompt tail
                    walked += 1
                    continue
                if st.pos == st.prompt_len and not st.emitted:
                    # Only reachable for a chunked (tail-fed) prompt: the
                    # step that just ran ingested the FINAL prompt token (and
                    # its logits feed the first pick below) — close the
                    # prefill span here so it covers the whole prompt.
                    # Full-prefill slots pick their first token at admission
                    # and skip this transition.
                    st.t_prefill = time.perf_counter()
                    self._trace_prefill_done(st)
                first = not st.emitted
                if self._consume_pick(slot, st, int(out[slot])):
                    emitted += 1
                    continued += not first
            overstepped = flight.dropped + len(flight.pairs) - len(live)
            if self.cfg.layer_pattern:
                # Positions this step's attention read: all of a slot's on a
                # full layer, the band's on a window layer.
                lengths = flight.positions[live].astype(np.int64) + 1
                attended = {"attn_pos_full": int(lengths.sum())}
                if self._band:
                    attended["attn_pos_band"] = int(
                        np.minimum(lengths, self._band).sum()
                    )
                step_span.set(**attended)
            if flight.moe is not None:
                self._read_moe_counts(step_span, *flight.moe)
            self.stats["steps"] += 1
            if self._tel is not None:
                # The fetch above was a real device sync, so this window is
                # genuine step time, not dispatch time.
                dt_step = time.perf_counter() - t_step
                self._m_step_s.observe(dt_step)
                self._m_steps.inc()
                if flight.ahead:
                    self._m_steps_ahead.inc()
                if overstepped:
                    self._m_oversteps.inc(overstepped)
            sp.set(retired=before - len(self._active))
            if not self._active:
                self._flights.clear()  # as in _step_prepare
            self._step_publish()
        # All of the step just fetched: the slots it stepped that were still
        # theirs; of those, the ones that produced an output token, the ones
        # for which it was not the first, and the ones that only consumed a
        # prompt-tail token; whether the device had work queued when it was
        # enqueued (where not, why); rows run and dropped; the prefills that
        # lie on the device's queue between the step before and it (always
        # set: 0 is none, absent an older program) and the tokens they fed.
        step_span.set(
            active=len(live), emitted=emitted, continued=continued,
            walked=walked, ahead=int(flight.ahead), overstepped=overstepped,
            prefills=flight.prefills, prefill_tokens=flight.prefill_tokens,
        )
        if flight.drain is not None:
            step_span.set(drain=flight.drain)

    def _enqueue_step(self, span) -> None:
        """Build and dispatch one pool step over the occupied slots, each at
        ``pos`` plus its steps in flight; nothing here waits for the device.
        No step where every occupied slot's budget ends in flight."""
        with span("step.build"):
            N = self.num_slots
            toks = np.full((N,), PAD_ID, np.int32)
            take = np.zeros((N,), bool)  # rows fed the pick in flight
            keys = np.zeros((N, *_request_key(0).shape), np.uint32)
            positions = np.zeros((N,), np.int32)
            temps = np.ones((N,), np.float32)
            groups: dict[tuple, list[int]] = {}
            pairs = []
            dropped = 0
            for slot, st in self._active.items():
                at = st.pos + st.sent
                take[slot] = st.sent and at >= st.prompt_len
                if st.spent:
                    # Its budget ends in the step in flight. The row runs all
                    # the same (one fixed shape), where an over-stepped EOS
                    # would: behind the last valid row of its own blocks, or
                    # in the sink where none is mapped. Position 0 would
                    # overwrite a row that _finish is about to publish.
                    positions[slot] = min(at, self.max_total - 1)
                    dropped += 1
                    continue
                if at < st.prompt_len:
                    toks[slot] = st.ids[at]  # the prompt tail
                elif not st.sent:
                    toks[slot] = st.cur  # picked at admission, or bookkept
                keys[slot] = st.key
                positions[slot] = at
                temps[slot] = st.temperature
                groups.setdefault(
                    (st.sample, st.top_k, st.top_p), []
                ).append(slot)
                pairs.append((slot, st))
            if not pairs:
                self._drain = "spent"
                return
            d_positions = jnp.asarray(positions)
            d_toks, d_take = jnp.asarray(toks), jnp.asarray(take)
            table = self.pool.alloc.table_device() if self.paged else None
        # The pool step goes out first and the picks' inputs are copied in
        # AFTER it, while the device is already at work (a small
        # host-to-device copy costs the host a quarter of a millisecond on
        # the chip); then one pick a sampling group, merged into one vector.
        with span("step.dispatch", programs=2 * len(groups) + 1):
            logits, self.pool.caches = self._dispatch_pool_step(
                table, d_positions, _choose(d_toks, self._picks, d_take)
            )
            moe = None
            if self._moe_layer is not None:
                self._moe_uncopied += 1
                self._moe_rows += len(pairs) + dropped
                if self._moe_uncopied >= _MOE_READ_EVERY:
                    # The pool is donated to the next step: what is read
                    # later has to be a copy made before that one goes out.
                    moe = (
                        jnp.copy(self.pool.caches[self._moe_layer][MOE_COUNTS]),
                        self._moe_rows,
                    )
                    self._moe_uncopied = 0
            d_keys, d_temps = jnp.asarray(keys), jnp.asarray(temps)
            picks = None
            for (sample, top_k, top_p), slots in groups.items():
                pick = _pick_pool(
                    logits, d_keys, d_positions, d_temps,
                    sample=sample, top_k=top_k, top_p=top_p,
                )
                if picks is None:
                    picks = pick
                else:
                    mine = np.zeros((N,), bool)
                    mine[slots] = True
                    picks = _choose(picks, pick, jnp.asarray(mine))
            self._picks = picks
        for _, st in pairs:
            st.sent += 1
        # Ahead where a step is unfetched and nothing has waited for it since.
        ahead = bool(self._flights) and self._drain is None
        self._flights.append(
            _Flight(
                pairs, positions, picks, dropped, ahead,
                None if ahead else self._drain or "idle",
                self._prefills_queued, self._prefill_tokens_queued, moe,
            )
        )
        self._drain = None
        self._prefills_queued = self._prefill_tokens_queued = 0

    def _read_moe_counts(self, step_span, counts, rows: int) -> None:
        """Every ``_MOE_READ_EVERY`` plain steps: one small fetch of the
        expert layers' counts as that step left them (a copy made at its
        dispatch, and its picks have just been fetched, so it waits for
        nothing), onto the step's span and into the registry as what was
        added since the last fetch; ``rows`` is what the steps up to that one
        fed (a slot that had ended a step before is a row to the device too)."""
        total = np.asarray(counts, np.int64)
        # The device's int32 totals are never reset and wrap (after hours of
        # serving): what was added since the last fetch is the difference
        # modulo 2**32, far above what 32 steps can add.
        assign, hit, max_load, steps = (
            (total - self._moe_read) & 0xFFFFFFFF
        ).tolist()
        self._moe_read = total
        step_span.set(
            moe_assign=assign, moe_hit=hit, moe_max_load=max_load,
            moe_steps=steps, moe_tokens=rows - self._moe_rows_read,
        )
        self._moe_rows_read = rows
        if self._tel is not None:
            self._m_moe_assign.inc(assign)
            self._m_moe_hit.inc(hit)
            self._m_moe_max_load.inc(max_load)

    def _step_verify(self, step_span) -> None:
        """One speculative verify step: every occupied slot feeds its
        pending token plus up to ``speculate_k`` lookahead tokens — the
        un-ingested prompt tail first (teacher-forced, like chunked
        prefill), then drafter proposals — through ONE ``_pool_verify``
        forward. The longest accepted prefix is kept; the rejected tail is
        erased with an O(1) index rollback (``_pool_rollback``). Rows are
        padded to the static width W = k + 1 and free slots ride along, so
        mixed speculative/non-speculative pools never retrace. Emissions
        go through the same ``_consume_pick`` as the plain path — greedy
        answers are byte-identical to non-speculative serving
        (tests/test_speculative.py pins this)."""
        t_step = time.perf_counter()
        n_rows = len(self._active)  # rows fed at dispatch (pre-retirement)
        draft_span = self._tracer.start_span(
            "spec.draft", parent=step_span, lane="scheduler",
        )
        N, W = self.num_slots, self.speculate_k + 1
        toks = np.full((N, W), PAD_ID, np.int32)
        keys = np.zeros((N, *np.shape(jax.random.PRNGKey(0))), np.uint32)
        positions = np.zeros((N,), np.int32)
        temps = np.ones((N,), np.float32)
        # Degradation ladder: while the speculative breaker is open, no slot
        # drafts — rows carry only the pending token (plus any prompt tail),
        # which rides the SAME static-W verify program (zero recompiles) and
        # is byte-identical to plain stepping for greedy AND sampled
        # requests (no drafts = no rejection-sampling draws). A half-open
        # probe re-consults the drafter after the cooldown.
        spec_allowed = self.drafter is not None and self._brk_spec.allow()
        rows: dict[int, tuple[list[int], int]] = {}
        for slot, st in self._active.items():
            drafter = self.drafter if (st.spec and spec_allowed) else None
            # A drafter fault recorded below is this slot's request's fault.
            self._breaker_trace = st.trace_id
            t_draft = time.perf_counter()
            try:
                row, n_drafted = build_verify_row(
                    st.ids + st.emitted, st.pos, self.speculate_k,
                    drafter, st.dstate,
                )
            except Exception:  # noqa: BLE001  # tpa: disable=TPA006 — drafting is an optional accelerator: ANY drafter failure feeds the speculative breaker and this row degrades to no-lookahead (byte-identical answers); it must never kill the request, let alone the pool
                self._brk_spec.record_failure()
                row, n_drafted = build_verify_row(
                    st.ids + st.emitted, st.pos, self.speculate_k, None, None,
                )
            else:
                if drafter is not None:
                    draft_ms = (time.perf_counter() - t_draft) * 1e3
                    if self.drafter_slow_ms and draft_ms > self.drafter_slow_ms:
                        # A drafter that stalls past its budget is as bad as
                        # one that raises: speculation exists to SAVE time.
                        self._brk_spec.record_failure()
                    else:
                        self._brk_spec.record_success()
            rows[slot] = (row, n_drafted)
            toks[slot, : len(row)] = row
            keys[slot] = st.key
            positions[slot] = st.pos
            temps[slot] = st.temperature
        self._breaker_trace = None
        draft_span.end(drafted=sum(n for _, n in rows.values()))
        verify_span = self._tracer.start_span(
            "spec.verify", parent=step_span, lane="scheduler", width=W,
        )
        if self.paged and self.decode_kernel == "paged_flash":
            logits, self.pool.caches = _pool_verify_paged_flash(
                self.params, self.pool.caches,  # tpa: disable=TPA005 — exclusive if/elif/else triplet: exactly one branch runs per step and all rebind self.pool.caches from their own result
                self.pool.alloc.table_device(), jnp.asarray(positions),
                jnp.asarray(toks), self.cfg,
                self.pool.block_tokens, self._kernel_interpret,
            )
        elif self.paged:
            logits, self.pool.caches = self._fn_pool_verify_paged(
                self.params, self.pool.caches,  # tpa: disable=TPA005 — exclusive if/elif/else triplet: exactly one branch runs per step and all rebind self.pool.caches from their own result
                self.pool.alloc.table_device(), jnp.asarray(positions),
                jnp.asarray(toks), self.cfg,
                self.pool.block_tokens, self.pool.buf_len,
            )
        else:
            logits, self.pool.caches = self._fn_pool_verify(
                self.params, self.pool.caches, jnp.asarray(toks), self.cfg
            )
        groups: dict[tuple, list[int]] = {}
        for slot, st in self._active.items():
            groups.setdefault((st.sample, st.top_k, st.top_p), []).append(slot)
        picks: dict[int, np.ndarray] = {}
        for (sample, top_k, top_p), slots in groups.items():
            out = np.asarray(
                _pick_pool_verify(
                    logits, jnp.asarray(keys), jnp.asarray(positions),
                    jnp.asarray(temps),
                    sample=sample, top_k=top_k, top_p=top_p,
                )
            )
            for slot in slots:
                picks[slot] = out[slot]
        delta = np.zeros((N,), np.int32)
        drafted = accepted = 0
        for slot, st in list(self._active.items()):
            row, n_drafted = rows[slot]
            slot_picks = picks[slot]
            if st.sample and n_drafted:
                # Rejection-sampling acceptance needs the target
                # probabilities at the draft tokens — numbers that never
                # leave the device on the plain path. Slice THIS slot's
                # (W, V) rows on device; fetching the whole (N, W, V) pool
                # tensor would tax every greedy neighbor's step latency.
                slot_logits = np.asarray(logits[slot], np.float32)
                pos0 = st.pos

                def accept(j, draft, _l=slot_logits, _st=st, _p=pos0):
                    probs = filtered_probs(
                        _l[j], _st.temperature, _st.top_k, _st.top_p
                    )
                    return sampled_accept(
                        probs, draft, keyed_rng(_st.seed, _p + j)
                    )

            else:

                def accept(j, draft, _picks=slot_picks):
                    pick = int(_picks[j])
                    return pick == draft, pick

            emitted, keep, n_accepted = judge_row(
                row, st.pos, st.prompt_len, accept,
                lambda j, _picks=slot_picks: int(_picks[j]),
            )
            st.forwards += 1
            # Count as ACCEPTED only drafts whose emissions will actually
            # be consumed — judge_row keeps judging past an EOS it cannot
            # see, and counting those would skew acceptance telemetry on
            # every finishing request. Counters must be final BEFORE the
            # consume loop: retirement emits the request's span in there.
            n_accepted = min(n_accepted, self._consumable(st, emitted))
            drafted += n_drafted
            accepted += n_accepted
            st.drafted += n_drafted
            st.accepted += n_accepted
            delta[slot] = keep - W
            st.pos += keep
            if not emitted:
                # Every fed position was still prompt: the next pending
                # token is the known prompt token at the new position.
                st.cur = st.ids[st.pos]
                continue
            if not st.emitted:
                # First generated pick for a tail-fed prompt: this verify
                # ingested the final prompt token — close the prefill span
                # here, exactly like the plain path's boundary transition.
                st.t_prefill = time.perf_counter()
                self._trace_prefill_done(st)
            for tok in emitted:
                self._consume_pick(slot, st, tok)
                if slot not in self._active:
                    break  # retired (EOS / budget): drop the row's tail
        verify_span.end(drafted=drafted, accepted=accepted)
        rollback_span = self._tracer.start_span(
            "spec.rollback", parent=step_span, lane="scheduler"
        )
        if self.paged:
            # Paged rollback IS table truncation: blocks past each slot's
            # kept width return to the pool's free list (re-ensured next
            # step), stale rows inside the kept block stay masked, and no
            # device index needs resetting — per-slot indices are rebuilt
            # from host state every call. Retired slots already freed
            # their whole row in _finish.
            for slot, st in self._active.items():
                self.pool.alloc.truncate(slot, st.pos)
        else:
            self.pool.caches = self._fn_pool_rollback(
                self.pool.caches, jnp.asarray(delta)  # tpa: disable=TPA005 — the linter's linear scan pairs this dense-branch donation with the paged verify call above; the branches are mutually exclusive and every donating call rebinds immediately
            )
        rollback_span.end()
        self.stats["steps"] += 1
        self.stats["drafted"] = self.stats.get("drafted", 0) + drafted
        self.stats["accepted"] = self.stats.get("accepted", 0) + accepted
        step_span.set(drafted=drafted, accepted=accepted)
        if self._tel is not None:
            dt_step = time.perf_counter() - t_step
            self._m_step_s.observe(dt_step)
            self._m_steps.inc()
            if drafted:
                self._m_spec_drafted.inc(drafted)
                if accepted:
                    self._m_spec_accepted.inc(accepted)
                if drafted - accepted:
                    self._m_spec_rejected.inc(drafted - accepted)
        self._step_publish()

    def _consumable(self, st: _Active, emitted: list[int]) -> int:
        """How many of a verify row's emissions ``_consume_pick`` will
        consume before retiring the slot (the finishing token included) —
        a side-effect-free twin of its EOS/budget rules, used to finalize
        acceptance counters before retirement emits the request span."""
        n, cnt = 0, len(st.emitted)
        for tok in emitted:
            n += 1
            if tok == self.tok.eos_id or cnt >= st.max_new:
                break
            cnt += 1
            if cnt >= st.max_new:
                break
        return n

    def _consume_pick(self, slot: int, st: _Active, tokv: int) -> bool:
        """Apply one generated token: retire on EOS or budget exhaustion,
        else schedule it as the slot's next input. The budget check runs
        BEFORE the append so max_new=0 answers with an empty continuation
        (matching generate(max_new=0)). True when the token was emitted
        to the client (not an EOS, not over budget)."""
        if tokv == self.tok.eos_id or len(st.emitted) >= st.max_new:
            self._finish(slot, st)
            return False
        st.emitted.append(tokv)
        now = time.perf_counter()
        if st.t_first is None:
            st.t_first = now
        else:
            st.itl_max = max(st.itl_max or 0.0, now - st.t_last)
        st.t_last = now
        if self._tel is not None:
            self._m_tokens.inc()
        if len(st.emitted) >= st.max_new:
            self._finish(slot, st)
        else:
            st.cur = tokv
        return True

    def _finish(self, slot: int, st: _Active) -> None:
        # Attribution BEFORE the allow() below: a cooldown-driven
        # open->half_open transition inside it belongs to this request.
        self._breaker_trace = st.trace_id
        if (
            self.prefix_cache is not None and st.use_prefix
            and self._brk_prefix.allow()
        ):
            # Feed the trie BEFORE the slot is recycled: slice the slot's
            # prompt-region KV (block-aligned; the cache's own storage
            # layout) into blocks. Only blocks the trie is missing are
            # fetched off the device — a request that fully hit fetches
            # nothing, and an unfittable budget fetches nothing either
            # (insert prechecks). Fetches are one fixed-shape dispatch per
            # missing block on purpose: slicing a whole missing RUN would
            # mint a compile per run length, trading bounded host syncs at
            # retirement for unbounded recompiles. Opted-out requests
            # neither read nor feed the cache.
            B = self.prefix_cache.block_tokens
            aligned = (st.prompt_len // B) * B
            if aligned:
                try:
                    with self._traced(
                        "prefix.insert", st.span_root,
                        lane=st.span_root.lane,
                        tokens=aligned,
                    ):
                        if self.paged:
                            # Device-tier donation: the trie ADOPTS the
                            # retiring slot's prompt blocks by reference
                            # (pool refcount) — zero device reads, zero
                            # host copies; spill-to-host happens lazily
                            # under pool pressure or a wire export.
                            evicted = self.prefix_cache.insert_device(
                                st.ids, aligned,
                                [
                                    int(b)
                                    for b in self.pool.alloc.table[slot][
                                        : aligned // B
                                    ]
                                ],
                            )
                        else:
                            evicted = self.prefix_cache.insert(
                                st.ids, aligned,
                                lambda start: jax.device_get(
                                    self._fn_slot_read_blocks(
                                        self.pool.caches, jnp.int32(slot),
                                        jnp.int32(start), B,
                                    )
                                ),
                            )
                except Exception:  # noqa: BLE001  # tpa: disable=TPA006 — feeding the trie is best-effort: a retirement-side cache fault (injected or real) feeds the breaker and this request simply does not donate its KV; its ANSWER is already computed and must still flush
                    self._brk_prefix.record_failure()
                else:
                    # Mirrors the admission path: a clean feed closes a
                    # half-open probe (without this, a breaker probed by a
                    # RETIREMENT would stay half-open, where one isolated
                    # fault re-opens it with the threshold bypassed).
                    self._brk_prefix.record_success()
                    if evicted and self._tel is not None:
                        self._m_prefix_evicted.inc(evicted)
        self._breaker_trace = None
        text = _detokenize_rows(
            np.asarray([st.emitted], np.int32) if st.emitted
            else np.zeros((1, 0), np.int32),
            1, self.tok,
        )[0]
        resp = {"continuation": text}
        if st.wv is not None:
            resp["weight_version"] = st.wv
        self._done[st.order] = resp
        del self._active[slot]
        if self.paged:
            # After donation: table references drop, aliased prompt blocks
            # live on under the device tier's refs, everything else
            # returns to the free list.
            self.pool.alloc.free_slot(slot)
        self._free.append(slot)
        root = st.span_root
        self._end_spans(st, ("span_prefill",))
        self._end_spans(st, ("span_decode",), new_tokens=len(st.emitted))
        # The longest gap between two of its output tokens, where it had two.
        itl = {} if st.itl_max is None else {"itl_max_s": round(st.itl_max, 6)}
        self._end_spans(
            st, ("span_root",), order=st.order,
            prompt_tokens=st.prompt_len, new_tokens=len(st.emitted), **itl,
        )
        if self._tel is not None or self._span_tap is not None:
            now = time.perf_counter()
            queue_s = st.t_admit - st.t_enqueue
            total_s = now - st.t_enqueue
            span = {
                "order": st.order,
                "prompt_tokens": st.prompt_len,
                "new_tokens": len(st.emitted),
                "queue_s": round(queue_s, 6),
                "total_s": round(total_s, 6),
            }
            if st.forwards:
                # Decode forwards this request rode (verify or plain steps;
                # prefill excluded) — summarize derives tokens-per-forward.
                span["forwards"] = st.forwards
            if st.spec:
                span["drafted"] = st.drafted
                span["draft_accepted"] = st.accepted
            if self.prefix_cache is not None and st.use_prefix:
                # Recorded on MISSES too (0): summarize's hit rate divides
                # by prompt_tokens over participating requests only.
                span["prefix_hit_tokens"] = st.prefix_hit
            if st.wv is not None:
                span["weight_version"] = st.wv
            if st.t_prefill is not None:
                span["prefill_s"] = round(st.t_prefill - st.t_admit, 6)
            if st.t_first is not None:
                span["ttft_s"] = round(st.t_first - st.t_enqueue, 6)
            span.update(itl)
            if self._tel is not None:
                self._m_queue_s.observe(queue_s)
                self._m_total_s.observe(total_s)
                if st.t_prefill is not None:
                    self._m_prefill_s.observe(st.t_prefill - st.t_admit)
                if st.t_first is not None:
                    self._m_ttft_s.observe(st.t_first - st.t_enqueue)
                self._m_retirements.inc()
            self._record_request(span, root=root)

    # ---- shutdown ---------------------------------------------------------

    def shutdown(self) -> None:
        """Stop accepting NEW work: any later :meth:`submit` answers a
        structured ``routing`` error at its reserved order instead of
        queueing into a loop nobody will drive again (the multi-replica
        router's redispatch path can race a draining replica in exactly
        this window). Everything already queued or in flight keeps its
        contract — the caller drives ``admit``/``step``/``drain_ready``
        until ``busy`` clears, exactly as before."""
        with self._intake_lock:
            self._closed = True

    # ---- output -----------------------------------------------------------

    def drain_ready(self) -> list[dict]:
        """Responses completed in arrival order (the serve loop's stdout
        contract): a response is released once every earlier request has
        answered."""
        out = []
        while self._emit_next in self._done:
            out.append(self._done.pop(self._emit_next))
            self._emit_next += 1
        return out

    def idle_backoff(self) -> None:
        """Sleep out the shortest pending retry backoff when there is
        nothing else to do (no active slots and every queued entry is
        waiting out its jittered backoff) — the drive loops would otherwise
        spin hot until the earliest ``not_before``. Bounded at 50ms so an
        arriving request is never kept waiting long."""
        if self._active or not self._queue:
            return
        now = time.perf_counter()
        with self._intake_lock:  # deque iteration vs concurrent submits
            qlen = len(self._queue)
            waits = [
                p.not_before - now for p in self._queue if p.not_before > now
            ]
        if waits and len(waits) == qlen:
            time.sleep(min(min(waits), 0.05))

    def run(self, reqs: list[dict]) -> list[dict]:
        """Drive a fixed request list to completion; returns responses in
        request order."""
        for req in reqs:
            self.submit(req)
        while self.busy:
            self.admit()
            self.step()
            self.idle_backoff()
        out = self.drain_ready()
        if self._tel is not None:
            if self._slo is not None:
                self._slo.maybe_evaluate(force=True)
            self._tel.maybe_flush(force=True)
        return out
