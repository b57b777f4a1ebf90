"""Fused paged-decode attention: a Pallas kernel over the KVPool block table.

The gather-path twins (``serve/scheduler.py`` ``_pool_step_paged`` /
``paged_attention(impl="xla")``) first materialize a dense-ordered view of
every slot's whole KV working set through ``gather_block_views`` — one extra
full HBM pass per decode step on a path that is already KV-bandwidth bound
(decode arithmetic intensity ~0.18 vs prefill's ~0.34, ``analysis costs``).
This kernel removes that pass: the grid iterates the block TABLE, the
BlockSpec index map of the K/V pool inputs resolves ``table[s, j]`` through a
scalar-prefetched table (the classic paged-attention schedule), and each
(block_tokens, H_kv, D) block is consumed straight from the pool buffer it
lives in. Fused into the block read:

- online-softmax accumulation across table entries (running max / normalizer
  / fp32 output accumulator in VMEM scratch, exactly like
  ``flash_attention``'s k-axis walk);
- GQA head grouping: queries arrive folded as (N, H_kv, G*S_q, D) so one
  block read serves all ``G = H/H_kv`` query heads of its kv head — kv HBM
  traffic stays at the H_kv rate with no materialized repeat;
- int8 dequantization: quantized pools pass codes AND scales as separate
  inputs and the kernel dequantizes per block tile in VMEM — no bf16 pool
  copy is ever materialized in HBM;
- stale-row / sink masking from ``lengths``: per-row offset causality
  (query row i of sequence s sits at absolute position
  ``lengths[s] - S_q + i``) masks rejected-speculation leftovers, unwritten
  sink gathers, and lookahead rows in one predicate — which is also what
  lifts the gather-flash path's S_q = 1 restriction (verify rows S_q = k+1
  attend causally inside the row).

Numerics: scores are computed per (q-row, key) pair exactly like the XLA
oracle (dot in the compute dtype, cast to fp32, scaled), so masked positions
contribute exactly 0.0 either way; only the softmax normalizer/PV summation
ORDER differs (online vs full-row), which perturbs low fp32 bits — the
serving tests pin answer-level byte identity, the kernel tests pin per-dtype
tolerances.

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
)

# Lane width of the m/l scratch rows (replicate-to-lanes layout, same as the
# flash kernel's (block_q, 128) running-max/normalizer scratch).
_LANES = 128


def _paged_kernel(
    # scalar-prefetch refs
    table_ref,    # (N, nmax) int32 — SMEM
    lengths_ref,  # (N,) int32 — SMEM
    # inputs
    q_ref,        # (1, H_kv, G*S_q, D) — queries folded by kv group
    k_ref,        # (1, B, H_kv, D) — pool block, resolved via table[s, j]
    v_ref,        # (1, B, H_kv, D)
    *rest,        # [k_scale_ref, v_scale_ref,] out_ref, m_scr, l_scr, acc_scr
    s_q: int,
    block_tokens: int,
    scale: float,
    quantized: bool,
):
    if quantized:
        k_scale_ref, v_scale_ref, out_ref, m_scr, l_scr, acc_scr = rest
    else:
        out_ref, m_scr, l_scr, acc_scr = rest
        k_scale_ref = v_scale_ref = None
    s, j = pl.program_id(0), pl.program_id(1)
    nmax = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _MASKED)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[s]

    # Blocks that start at or past this sequence's valid length hold no
    # visible position (stale table tails point at the pinned sink block):
    # skip their compute outright. The DMA still lands — table-width HBM
    # traffic is bounded by the allocator keeping tables trimmed.
    @pl.when(j * block_tokens < length)
    def _block():
        dtype = q_ref.dtype
        k = k_ref[0]  # (B, H_kv, D)
        v = v_ref[0]
        if quantized:
            # Dequant fused into the block read: codes * per-(position, head)
            # scale, in the compute dtype — the same round trip the dense
            # cache's read path applies, so values match it bit-for-bit.
            k = k.astype(dtype) * k_scale_ref[0].astype(dtype)
            v = v.astype(dtype) * v_scale_ref[0].astype(dtype)
        kt = jnp.swapaxes(k, 0, 1)  # (H_kv, B, D)
        vt = jnp.swapaxes(v, 0, 1)
        q = q_ref[0]  # (H_kv, GS, D)
        # Mosaic accepts only a 32-bit matmul accumulator, so accumulate in
        # fp32 and round where the XLA oracle's compute-dtype dot rounds (its
        # result) before the fp32 scale — per (row, key) values stay
        # independent of blocking and match the gather path.
        scores = jax.lax.dot_general(
            q, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).astype(dtype).astype(jnp.float32) * scale  # (H_kv, GS, B)

        # Per-row offset causality: folded row r = g * S_q + i holds query
        # index i = r % S_q at absolute position length - S_q + i; pool
        # position j*B + b is visible iff <= that. This one predicate hides
        # stale rows (positions >= length), sink reads, and — for verify
        # rows — each lookahead token's future.
        gs, b = scores.shape[1], scores.shape[2]
        row = jax.lax.broadcasted_iota(jnp.int32, (gs, b), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (gs, b), 1)
        q_pos = (length - s_q) + row % s_q
        visible = (j * block_tokens + col) <= q_pos
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
            p.astype(dtype), vt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nmax - 1)
    def _finalize():
        out_ref[0] = (
            acc_scr[...] / l_scr[...][:, :, :1]
        ).astype(out_ref.dtype)


def paged_flash_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    table: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused attention over a paged KV pool, blocks read in place.

    Args:
      q: (N, S_q, H, D) queries; row ``s`` sits at absolute positions
        ``lengths[s] - S_q .. lengths[s] - 1`` (decode S_q = 1; speculative
        verify S_q = k + 1, causal inside the row).
      k_pool, v_pool: (num_blocks, B, H_kv, D) pool buffers — bf16/fp32
        values, or int8 codes when ``k_scale``/``v_scale`` are given.
      table: (N, nmax) int32 block table (``kernels/kv_pool.KVPool``);
        entries past a slot's owned count point at the pinned sink block 0.
      lengths: (N,) int32 valid KV length per sequence (including the S_q
        rows just written for this forward).
      k_scale, v_scale: (num_blocks, B, H_kv, 1) fp32 dequant scales for
        int8 pools (``init_block_pool(quantize=True)`` storage layout); the
        kernel consumes codes + scales directly.
      interpret: Pallas interpret mode; default True off-TPU (same
        convention as ``flash_attention``).

    Returns (N, S_q, H, D) attention outputs in q's dtype.
    """
    n, s_q, h, d = q.shape
    num_blocks, block_tokens, h_kv, d_k = k_pool.shape
    if d_k != d:
        raise ValueError(f"head_dim mismatch: q {d} vs pool {d_k}")
    if h % h_kv:
        raise ValueError(f"query heads {h} must be a multiple of kv heads {h_kv}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("int8 pools need BOTH k_scale and v_scale")
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
    quantized = k_scale is not None
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

    def _at_table(s, j, table_ref, lengths_ref):
        return (table_ref[s, j], 0, 0, 0)

    def _at_seq(s, j, table_ref, lengths_ref):
        return (s, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, h_kv, gs, d), _at_seq),
        pl.BlockSpec((1, block_tokens, h_kv, d), _at_table),
        pl.BlockSpec((1, block_tokens, h_kv, d), _at_table),
    ]
    inputs = [qf, k_pool, v_pool]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, block_tokens, h_kv, 1), _at_table),
            pl.BlockSpec((1, block_tokens, h_kv, 1), _at_table),
        ]
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, nmax),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h_kv, gs, d), _at_seq),
        scratch_shapes=[
            pltpu.VMEM((h_kv, gs, _LANES), jnp.float32),  # running max
            pltpu.VMEM((h_kv, gs, _LANES), jnp.float32),  # normalizer
            pltpu.VMEM((h_kv, gs, d), jnp.float32),       # output accumulator
        ],
    )
    kernel = functools.partial(
        _paged_kernel,
        s_q=s_q,
        block_tokens=block_tokens,
        scale=d**-0.5,
        quantized=quantized,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h_kv, gs, d), q.dtype),
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=bool(interpret),
        name="paged_flash_attention",
    )(table, lengths, *inputs)
    # Unfold (N, H_kv, G*S_q, D) -> (N, S_q, H, D).
    return (
        out.reshape(n, h_kv, group, s_q, d)
        .reshape(n, h, s_q, d)
        .transpose(0, 2, 1, 3)
    )
