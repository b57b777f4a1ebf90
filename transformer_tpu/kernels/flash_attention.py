"""Blockwise (flash) attention as a Pallas TPU kernel, forward + backward.

This is the TPU-native replacement for the reference's
``scaled_dot_product_attention`` (``Attention.py:3-34``) at long sequence
length: instead of materializing the full (B, H, S, S) score tensor in HBM
(reference ``Attention.py:20``), scores are computed tile-by-tile in VMEM with
an online softmax, so memory is O(S·D) and the two matmuls per tile stay on
the MXU. The (B·H, q-block, k-block) grid walks the k-axis sequentially,
carrying the running max / normalizer / output accumulator in VMEM scratch —
the canonical TPU flash-attention schedule.

Semantics match ``ops.attention.dot_product_attention``:

- softmax in fp32 regardless of input dtype;
- optional key-padding mask (True = "may attend"), same polarity as
  ``ops.masks``;
- optional causal masking, passed *structurally* (a static flag, not a dense
  (S, S) mask) so fully-above-diagonal tiles are skipped outright.

The backward pass is the standard two-kernel split: one accumulates dQ over
k-blocks, the other dK/dV over q-blocks, both recomputing the tile of
attention probabilities from the saved per-row logsumexp rather than storing
the (S, S) probability matrix.

On non-TPU backends the kernels run in Pallas interpret mode, which is how the
CPU test suite exercises them bit-for-bit against the XLA oracle.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

# Finite stand-in for -inf: keeps fully-masked rows NaN-free (same approach as
# the reference's additive -1e9, ``Attention.py:26``) while staying far below
# any reachable logit so the exp-guard below can recognize masked entries.
_MASKED = -1e30
_MASK_GUARD = -1e29


@dataclasses.dataclass(frozen=True)
class _FlashConfig:
    """Static kernel configuration (hashable: used as a nondiff custom-vjp arg)."""

    causal: bool
    has_mask: bool
    block_q: int
    block_k: int
    num_heads: int  # for the kv-mask index map: grid axis 0 runs over B*H
    scale: float
    interpret: bool
    # Grouped-query attention: k/v arrive folded as (B*H_kv, S_k, D) and each
    # kv head serves num_heads/num_kv_heads query heads VIA THE BLOCKSPEC
    # INDEX MAPS — kv is never materialized at the full head count, so HBM kv
    # traffic stays at the H_kv rate (the whole point of GQA).
    num_kv_heads: int = 0  # 0 = same as num_heads (plain MHA)
    # Sliding-window band (Mistral-style local attention): LOCAL row r may
    # attend LOCAL col c only when c > r - band. None = unbounded. For plain
    # flash attention band == window (> 0); for ring hops the band is the
    # window shifted by the hop's static chunk offset (band = W - t·C, any
    # sign — ring_attention). Structural like causality: tiles fully below
    # the band are skipped by _visible, so compute per q-block is O(window),
    # not O(S).
    band: int | None = None

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def group(self) -> int:
        return self.num_heads // self.kv_heads

    def kv_row(self, b):
        """Grid row over B*H -> row of the folded (B*H_kv, S, D) kv array."""
        if self.group == 1:
            return b
        return (b // self.num_heads) * self.kv_heads + (b % self.num_heads) // self.group


def _largest_divisor_block(seq_len: int, requested: int) -> int:
    block = min(requested, seq_len)
    while seq_len % block:
        block -= 1
    return block


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _block_and_padded_len(seq_len: int, requested: int) -> tuple[int, int]:
    """Pick a TPU-legal block size and the (possibly padded) sequence length.

    The Mosaic lowering requires the block's sublane dim to be divisible by 8
    or equal to the full array dim. A divisor block satisfying that is used
    as-is (no padding); otherwise the sequence is padded up to a multiple of
    an 8-aligned block (e.g. S=4095 -> block 128, padded to 4096 — the
    teacher-forcing shift makes off-by-one lengths the common case)."""
    block = _largest_divisor_block(seq_len, requested)
    if block == seq_len or block % 8 == 0:
        return block, seq_len
    block = max(8, min(requested, _round_up(seq_len, 8)) // 8 * 8)
    return block, _round_up(seq_len, block)


def _compiler_params(dimension_semantics: tuple[str, ...]):
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


def _gated(cfg: _FlashConfig) -> bool:
    """Whether any structural tile-skip condition applies."""
    return cfg.causal or cfg.band is not None


def _visible(cfg: _FlashConfig, i, j):
    """Whether k-block j has any position visible to q-block i under
    causality and/or the sliding-window band (call only when ``_gated``)."""
    conds = []
    if cfg.causal:
        conds.append(j * cfg.block_k <= i * cfg.block_q + cfg.block_q - 1)
    if cfg.band is not None:
        # Band lower edge, conservatively from the q-block's FIRST row
        # (i*bq): its band start (row - band + 1) is the leftmost in the
        # tile, so any tile whose last col reaches it may still hold
        # in-band entries for some row. Using the last row here would skip
        # tiles that earlier rows still need when band < block_q.
        conds.append(
            j * cfg.block_k + cfg.block_k - 1 >= i * cfg.block_q - cfg.band + 1
        )
    vis = conds[0]
    for extra in conds[1:]:
        vis = jnp.logical_and(vis, extra)
    return vis


def _tile_bias(cfg: _FlashConfig, s, i, j, mask_ref):
    """Apply key-padding and intra-tile causal masking to a (bq, bk) score tile."""
    if cfg.has_mask:
        # Mask arrives pre-tiled as (B, nk, 1, block_k) so each grid step maps
        # its (1, block_k) tile as a full block — TPU lane tiling forbids a
        # blocked lane dim that is neither 128-aligned nor the whole array.
        valid = mask_ref[0, 0] != 0  # (1, block_k)
        s = jnp.where(valid, s, _MASKED)
    if _gated(cfg):
        rows = i * cfg.block_q + jax.lax.broadcasted_iota(
            jnp.int32, (cfg.block_q, cfg.block_k), 0
        )
        cols = j * cfg.block_k + jax.lax.broadcasted_iota(
            jnp.int32, (cfg.block_q, cfg.block_k), 1
        )
        allowed = None
        if cfg.causal:
            allowed = cols <= rows
        if cfg.band is not None:
            in_band = cols > rows - cfg.band
            allowed = in_band if allowed is None else jnp.logical_and(allowed, in_band)
        s = jnp.where(allowed, s, _MASKED)
    return s


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(cfg: _FlashConfig, *refs):
    if cfg.has_mask:
        mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        mask_ref = None
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _MASKED)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        # Matmul inputs stay in the model dtype (bf16 runs the MXU at full
        # rate; fp32 inputs don't) with fp32 accumulation; scale applies to
        # the fp32 scores. For fp32 models every cast below is a no-op.
        q = q_ref[0]  # (bq, D)
        k = k_ref[0]  # (bk, D)
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * cfg.scale
        )  # (bq, bk) fp32
        s = _tile_bias(cfg, s, i, j, mask_ref)

        m_prev = m_scr[:, 0:1]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # exp(_MASKED - _MASKED) would be 1, silently attending to masked
        # positions in all-masked tiles — zero those entries explicitly.
        p = jnp.where(s > _MASK_GUARD, jnp.exp(s - m_new), 0.0)  # (bq, bk)
        correction = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = correction * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0]  # (bk, D)
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if _gated(cfg):
        pl.when(_visible(cfg, i, j))(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, 0:1] + jnp.log(l_safe)


def _fwd(cfg: _FlashConfig, q, k, v, kv_mask):
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    nq = s_q // cfg.block_q
    nk = s_k // cfg.block_k

    in_specs = []
    inputs = []
    if cfg.has_mask:
        in_specs.append(
            pl.BlockSpec(
                (1, 1, 1, cfg.block_k), lambda b, i, j: (b // cfg.num_heads, j, 0, 0)
            )
        )
        inputs.append(kv_mask)
    in_specs += [
        pl.BlockSpec((1, cfg.block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, cfg.block_k, d), lambda b, i, j: (cfg.kv_row(b), j, 0)),
        pl.BlockSpec((1, cfg.block_k, d), lambda b, i, j: (cfg.kv_row(b), j, 0)),
    ]
    inputs += [q, k, v]

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, cfg.block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, cfg.block_q, 1), lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
            # Per-row logsumexp, stored column-shaped (bq, 1) per tile so the
            # backward pass broadcasts it along lanes with no relayout.
            jax.ShapeDtypeStruct((bh, nq, cfg.block_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.block_q, 128), jnp.float32),
            pltpu.VMEM((cfg.block_q, 128), jnp.float32),
            pltpu.VMEM((cfg.block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=cfg.interpret,
        name="flash_attention_fwd",
    )(*inputs)
    return out, lse


# ---------------------------------------------------------------------------
# Ring-step forward: the same blockwise inner loop, but the online-softmax
# carry (running max m, normalizer l, unnormalized accumulator acc) is an
# HBM-resident input/output instead of kernel-local scratch, so sequence-
# parallel ring attention (parallel/ring_attention.py) can fold one KV chunk
# per ring hop without ever materializing a (C, C) score tensor.
# ---------------------------------------------------------------------------


def _ring_step_kernel(cfg: _FlashConfig, *refs):
    if cfg.has_mask:
        (mask_ref, q_ref, k_ref, v_ref, m_in, l_in, acc_in,
         m_out, l_out, acc_out, m_scr, l_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref, m_in, l_in, acc_in,
         m_out, l_out, acc_out, m_scr, l_scr, acc_scr) = refs
        mask_ref = None
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.broadcast_to(m_in[0, 0], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_in[0, 0], l_scr.shape)
        acc_scr[:] = acc_in[0]

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * cfg.scale
        )
        s = _tile_bias(cfg, s, i, j, mask_ref)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(s > _MASK_GUARD, jnp.exp(s - m_new), 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_new = correction * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0]
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if _gated(cfg):
        pl.when(_visible(cfg, i, j))(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _write():
        m_out[0, 0] = m_scr[:, 0:1]
        l_out[0, 0] = l_scr[:, 0:1]
        acc_out[0] = acc_scr[:]


def flash_ring_step(
    cfg: _FlashConfig,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_mask: jax.Array | None,
    m: jax.Array,
    l: jax.Array,
    acc: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fold one KV chunk into the online-softmax carry.

    Args (all folded to grid layout):
      q:    (BH, S_q, D) local query chunk (model dtype).
      k, v: (BH, C, D) the KV chunk visiting this ring step.
      kv_mask: pre-tiled (B, C // block_k, 1, block_k) int32 or None
        (must match ``cfg.has_mask``).
      m, l: (BH, nq, block_q, 1) fp32 running max / normalizer.
      acc:  (BH, S_q, D) fp32 unnormalized output accumulator.

    Returns the updated ``(m, l, acc)``. ``cfg.causal`` here means "this is
    the diagonal chunk pair" — intra-tile causality applies; fully-below-
    diagonal pairs use a non-causal cfg and fully-above pairs are skipped by
    the caller.
    """
    bh, s_q, d = q.shape
    c = k.shape[1]
    nq = s_q // cfg.block_q
    nk = c // cfg.block_k

    in_specs = []
    inputs = []
    if cfg.has_mask:
        in_specs.append(
            pl.BlockSpec(
                (1, 1, 1, cfg.block_k), lambda b, i, j: (b // cfg.num_heads, j, 0, 0)
            )
        )
        inputs.append(kv_mask)
    carry_specs = [
        pl.BlockSpec((1, 1, cfg.block_q, 1), lambda b, i, j: (b, i, 0, 0)),
        pl.BlockSpec((1, 1, cfg.block_q, 1), lambda b, i, j: (b, i, 0, 0)),
        pl.BlockSpec((1, cfg.block_q, d), lambda b, i, j: (b, i, 0)),
    ]
    in_specs += [
        pl.BlockSpec((1, cfg.block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, cfg.block_k, d), lambda b, i, j: (cfg.kv_row(b), j, 0)),
        pl.BlockSpec((1, cfg.block_k, d), lambda b, i, j: (cfg.kv_row(b), j, 0)),
    ] + carry_specs
    inputs += [q, k, v, m, l, acc]

    n_fixed = (1 if cfg.has_mask else 0) + 3
    return pl.pallas_call(
        functools.partial(_ring_step_kernel, cfg),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=list(carry_specs),
        out_shape=[
            jax.ShapeDtypeStruct(m.shape, jnp.float32),
            jax.ShapeDtypeStruct(l.shape, jnp.float32),
            jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.block_q, 128), jnp.float32),
            pltpu.VMEM((cfg.block_q, 128), jnp.float32),
            pltpu.VMEM((cfg.block_q, d), jnp.float32),
        ],
        # The carries are read once (j == 0) and written once (j == nk - 1):
        # alias them through so XLA updates in place instead of copying.
        input_output_aliases={n_fixed: 0, n_fixed + 1: 1, n_fixed + 2: 2},
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=cfg.interpret,
        name="flash_attention_fwd_ring",
    )(*inputs)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _recompute_p(cfg: _FlashConfig, q_ref, k_ref, lse_ref, mask_ref, i, j):
    """Recompute the (bq, bk) probability tile from the saved logsumexp.
    q/k are returned in their stored (model) dtype; scale is folded into the
    fp32 score tensor, so callers contracting against q must scale ds."""
    q = q_ref[0]
    k = k_ref[0]
    s = (
        jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * cfg.scale
    )
    s = _tile_bias(cfg, s, i, j, mask_ref)
    lse = lse_ref[0, 0]  # (bq, 1) column — broadcasts along lanes
    p = jnp.where(s > _MASK_GUARD, jnp.exp(s - lse), 0.0)
    return q, k, p


def _dq_kernel(cfg: _FlashConfig, *refs):
    if cfg.has_mask:
        (mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
        mask_ref = None
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        _, k, p = _recompute_p(cfg, q_ref, k_ref, lse_ref, mask_ref, i, j)
        do = do_ref[0]  # (bq, D)
        v = v_ref[0]  # (bk, D)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        ds = p * (dp - delta_ref[0, 0])  # delta: (bq, 1) column
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if _gated(cfg):
        pl.when(_visible(cfg, i, j))(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        # s = (q·scale)·kᵀ, so dq picks up one more factor of scale.
        dq_ref[0] = (dq_scr[:] * cfg.scale).astype(dq_ref.dtype)


def _dkdv_kernel(cfg: _FlashConfig, *refs):
    if cfg.has_mask:
        (mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        mask_ref = None
    j = pl.program_id(1)  # k-block: parallel axis
    # Sequential accumulation axis walks (group, q-block) pairs: with grouped
    # kv heads (GQA), grid axis 0 runs over B*H_kv and the q-heads sharing
    # each kv head are folded in here, so dk/dv accumulate across the whole
    # group in VMEM scratch with no cross-grid-row write race.
    t = pl.program_id(2)
    nt = pl.num_programs(2)
    nq = nt // cfg.group
    i = t % nq  # q-block within the current group member

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        q, _, p = _recompute_p(cfg, q_ref, k_ref, lse_ref, mask_ref, i, j)
        do = do_ref[0]  # (bq, D)
        v = v_ref[0]  # (bk, D)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # pᵀ·do -> (bk, D)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, 0])
        # s = scale·(q·kᵀ): the scale that used to ride on q folds into ds.
        dk_scr[:] += jax.lax.dot_general(
            (ds * cfg.scale).astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (ds·scale)ᵀ·q -> (bk, D)

    if _gated(cfg):
        pl.when(_visible(cfg, i, j))(_compute)
    else:
        _compute()

    @pl.when(t == nt - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(cfg: _FlashConfig, q, k, v, kv_mask, out, lse, do):
    bh, s_q, d = q.shape
    nq = s_q // cfg.block_q

    # Per-row rowsum(do * out) — tiny elementwise op, left to XLA to fuse.
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(bh, nq, cfg.block_q, 1)
    return flash_chunk_bwd(cfg, q, k, v, kv_mask, lse, delta, do)


def flash_chunk_bwd(cfg: _FlashConfig, q, k, v, kv_mask, lse, delta, do):
    """dq/dk/dv for one (q, KV-chunk) pair given the GLOBAL per-row softmax
    statistics (lse) and delta = rowsum(do·out). For plain flash attention the
    chunk is the whole sequence; ring attention calls this once per ring hop
    (with its local chunk pair) and accumulates — the decomposition is exact
    because p recomputed from the global lse is the true probability tile."""
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    nq = s_q // cfg.block_q
    nk = s_k // cfg.block_k

    q_spec_i = lambda b, i, j: (b, i, 0)  # noqa: E731
    lse_spec_i = lambda b, i, j: (b, i, 0, 0)  # noqa: E731

    in_specs = []
    inputs = []
    if cfg.has_mask:
        in_specs.append(
            pl.BlockSpec(
                (1, 1, 1, cfg.block_k), lambda b, i, j: (b // cfg.num_heads, j, 0, 0)
            )
        )
        inputs.append(kv_mask)
    in_specs += [
        pl.BlockSpec((1, cfg.block_q, d), q_spec_i),
        pl.BlockSpec((1, cfg.block_k, d), lambda b, i, j: (cfg.kv_row(b), j, 0)),
        pl.BlockSpec((1, cfg.block_k, d), lambda b, i, j: (cfg.kv_row(b), j, 0)),
        pl.BlockSpec((1, cfg.block_q, d), q_spec_i),
        pl.BlockSpec((1, 1, cfg.block_q, 1), lse_spec_i),
        pl.BlockSpec((1, 1, cfg.block_q, 1), lse_spec_i),
    ]
    inputs += [q, k, v, do, lse, delta]

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, cfg.block_q, d), q_spec_i),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((cfg.block_q, d), jnp.float32)],
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=cfg.interpret,
        name="flash_attention_bwd_dq",
    )(*inputs)

    # dk/dv: k-blocks parallel; (group member, q-block) pairs sequential.
    # Grid axis 0 runs over the FOLDED kv rows (B*H_kv): with grouped kv
    # heads every q-head sharing a kv head lands on the same grid row, so
    # its contribution accumulates in the same VMEM scratch.
    bkv = k.shape[0]
    group = cfg.group

    def q_row(b, t):
        # kv grid row b + group member t//nq -> row of the (B*H, ...) arrays.
        if group == 1:
            return b
        return (b // cfg.kv_heads) * cfg.num_heads + (b % cfg.kv_heads) * group + t // nq

    in_specs_kv = []
    inputs_kv = []
    if cfg.has_mask:
        in_specs_kv.append(
            pl.BlockSpec(
                (1, 1, 1, cfg.block_k), lambda b, j, t: (b // cfg.kv_heads, j, 0, 0)
            )
        )
        inputs_kv.append(kv_mask)
    in_specs_kv += [
        pl.BlockSpec((1, cfg.block_q, d), lambda b, j, t: (q_row(b, t), t % nq, 0)),
        pl.BlockSpec((1, cfg.block_k, d), lambda b, j, t: (b, j, 0)),
        pl.BlockSpec((1, cfg.block_k, d), lambda b, j, t: (b, j, 0)),
        pl.BlockSpec((1, cfg.block_q, d), lambda b, j, t: (q_row(b, t), t % nq, 0)),
        pl.BlockSpec((1, 1, cfg.block_q, 1), lambda b, j, t: (q_row(b, t), t % nq, 0, 0)),
        pl.BlockSpec((1, 1, cfg.block_q, 1), lambda b, j, t: (q_row(b, t), t % nq, 0, 0)),
    ]
    inputs_kv += [q, k, v, do, lse, delta]

    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, cfg),
        grid=(bkv, nk, nq * group),
        in_specs=in_specs_kv,
        out_specs=[
            pl.BlockSpec((1, cfg.block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, cfg.block_k, d), lambda b, j, t: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bkv, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((bkv, s_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.block_k, d), jnp.float32),
            pltpu.VMEM((cfg.block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=cfg.interpret,
        name="flash_attention_bwd_dkv",
    )(*inputs_kv)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _FlashConfig, q, k, v, kv_mask):
    out, _ = _fwd(cfg, q, k, v, kv_mask)
    return out


def _flash_fwd_rule(cfg, q, k, v, kv_mask):
    out, lse = _fwd(cfg, q, k, v, kv_mask)
    return out, (q, k, v, kv_mask, out, lse)


def _flash_bwd_rule(cfg, residuals, do):
    q, k, v, kv_mask, out, lse = residuals
    dq, dk, dv = _bwd(cfg, q, k, v, kv_mask, out, lse, do)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    kv_mask: jax.Array | None = None,
    causal: bool = False,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Blockwise attention over (B, S, H, D) activations.

    Args:
      q, k, v: (B, S_q|S_k, H, D). Cross-attention (S_q != S_k) is supported.
        Grouped-query attention: k/v may carry FEWER heads (B, S_k, H_kv, D)
        with H % H_kv == 0 — kv stays folded at H_kv rows and the kernel's
        BlockSpec index maps assign each q-head its kv group, so kv HBM
        traffic stays at the H_kv rate (no materialized repeat).
      kv_mask: optional (B, S_k) bool/int, True where the key is a real token
        (the padding mask of ``ops.masks.make_padding_mask`` squeezed to 2D).
      causal: structural causal masking (requires S_q == S_k positions to be
        aligned, as in self-attention).
      window: causal sliding window (requires ``causal``): row r attends
        cols in [r - window + 1, r]. Structural like causality — tiles
        outside the band are skipped, so per-row compute is O(window).
      block_q, block_k: tile sizes; shrunk to the largest divisor of the
        sequence length at or below the request.
      interpret: run in Pallas interpret mode. Default: True off-TPU, so the
        same code path is testable on CPU.

    Returns the (B, S_q, H, D) attention output in q's dtype.
    """
    if q.ndim != 4:
        raise ValueError(f"expected (B, S, H, D) inputs, got shape {q.shape}")
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    h_kv = k.shape[2]
    if v.shape[2] != h_kv:
        raise ValueError(f"k has {h_kv} heads but v has {v.shape[2]}")
    if h % h_kv:
        raise ValueError(
            f"query heads {h} must be a multiple of kv heads {h_kv}"
        )
    if causal and s_q != s_k:
        raise ValueError("causal flash attention requires S_q == S_k")
    if window and not causal:
        raise ValueError("window requires causal=True (causal sliding window)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    bq, s_q_pad = _block_and_padded_len(s_q, block_q)
    bk, s_k_pad = _block_and_padded_len(s_k, block_k)
    pad_q, pad_k = s_q_pad - s_q, s_k_pad - s_k
    if pad_k and kv_mask is None and not causal:
        # Padded keys must not receive attention; under causality they sit
        # above the diagonal for every real query row, so no mask is needed.
        kv_mask = jnp.ones((b, s_k), dtype=jnp.int32)
    if kv_mask is not None:
        kv_mask = jnp.broadcast_to(kv_mask, (b, s_k))
        if pad_k:
            kv_mask = jnp.pad(kv_mask.astype(jnp.int32), ((0, 0), (0, pad_k)))
    if pad_q or pad_k:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    cfg = _FlashConfig(
        causal=causal,
        has_mask=kv_mask is not None,
        block_q=bq,
        block_k=bk,
        num_heads=h,
        scale=d**-0.5,
        interpret=bool(interpret),
        num_kv_heads=h_kv,
        band=int(window) if window else None,
    )

    # (B, S, H, D) -> (B*H, S, D): heads become independent grid rows (kv
    # folds at its own, possibly smaller, head count).
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], x.shape[1], d)

    # Pre-tile the mask to (B, nk, 1, block_k): each (1, block_k) tile is a
    # full block under the TPU lane-tiling rules.
    mask_i32 = (
        None
        if kv_mask is None
        else kv_mask.astype(jnp.int32).reshape(b, s_k_pad // bk, 1, bk)
    )
    out = _flash(cfg, fold(q), fold(k), fold(v), mask_i32)
    out = out.reshape(b, h, s_q_pad, d).transpose(0, 2, 1, 3)
    return out[:, :s_q] if pad_q else out


# ---------------------------------------------------------------------------
# Paged (block-table) attention: the kernel-facing entry of the paged KV
# pool (kernels/kv_pool.py). K/V live in ONE (num_blocks, B, H_kv, D) pool
# per layer; each sequence addresses its blocks through a table row, so
# resident KV is proportional to used tokens and a shared prefix is the
# same physical blocks in two tables. This function gathers K/V through
# the table and attends the valid prefix — the dense path stays available
# behind the same serving interface (--kv_layout dense), and the fused
# Pallas decode kernel that reads blocks in place (no gathered view) is
# the ROADMAP's next kernel item.
# ---------------------------------------------------------------------------


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    table: jax.Array,
    lengths: jax.Array,
    *,
    impl: str = "xla",
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    width: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    window: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Attention over a paged KV pool through per-sequence block tables.

    Args:
      q: (N, S_q, H, D) queries; row ``s`` sits at absolute positions
        ``lengths[s] - S_q .. lengths[s] - 1`` (decode: S_q = 1 at the
        newest position, already written into the pool).
      k_pool, v_pool: (num_blocks, B, H_kv, D) pool buffers — bf16/fp32
        values, or int8 codes paired with ``k_scale``/``v_scale`` — or the
        same bytes with narrow heads kept ``128 // D`` a lane row
        (``ops.attention.init_block_pool``), on every impl.
      table: (N, nmax) int32 block table (``kernels/kv_pool.KVPool``).
      lengths: (N,) int32 valid KV length per sequence — positions
        ``>= lengths[s]`` (stale rows, sink gathers) are masked out.
      impl: "xla" — bitwise-identical math to the dense cache path
        (gather + fp32-softmax ``dot_product_attention``); "flash" — the
        Pallas blockwise kernel over the gathered view (decode S_q=1
        only: its key-padding mask carries no per-row causality);
        "paged_flash" — the fused Pallas kernel reading pool blocks in
        place through the table, no gathered view (any S_q, per-row
        offset causality, int8 dequant and GQA grouping fused).
      k_scale, v_scale: (num_blocks, B, H_kv, 1) fp32 dequant scales for
        int8 pools. "xla"/"flash" dequantize the gathered view (same
        round trip as the serving path); "paged_flash" consumes
        codes + scales inside the kernel.
      width: gather width in TOKENS (a multiple of the block size,
        typically ``ceil(max lengths / B) * B``). Clamps the gathered
        view so short slots don't pay an nmax-wide gather; positions
        beyond every slot's length carry softmax weight exactly 0.0 in
        fp32, so the clamp is bitwise-invisible. Ignored by
        "paged_flash" (the kernel skips out-of-length blocks instead).
      window: static causal band over absolute positions (0 = none), on
        the "xla" oracle and in the "paged_flash" kernel.

    Returns (N, S_q, H, D) attention outputs in q's dtype.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("int8 pools need BOTH k_scale and v_scale")
    n, s_q = q.shape[:2]
    if impl == "paged_flash":
        from transformer_tpu.kernels.paged_flash import paged_flash_attention

        return paged_flash_attention(
            q, k_pool, v_pool, table, lengths,
            k_scale=k_scale, v_scale=v_scale, window=window, interpret=interpret,
        )
    if window and impl != "xla":
        raise ValueError(f"paged_attention impl={impl!r} has no window band")
    from transformer_tpu.kernels.kv_pool import gather_block_views

    d = q.shape[-1]  # by heads, whatever rows the pool keeps them in
    k = gather_block_views(k_pool, table, width=width, head_dim=d)  # (N, L, H_kv, D)
    v = gather_block_views(v_pool, table, width=width, head_dim=d)
    if k_scale is not None:
        k = k.astype(q.dtype) * gather_block_views(
            k_scale, table, width=width
        ).astype(q.dtype)
        v = v.astype(q.dtype) * gather_block_views(
            v_scale, table, width=width
        ).astype(q.dtype)
    L = k.shape[1]
    if impl == "flash":
        if s_q != 1:
            raise ValueError(
                "paged_attention impl='flash' serves decode (S_q = 1): its "
                "key-padding mask cannot express per-row offset causality"
            )
        kv_mask = jnp.arange(L)[None, :] < lengths[:, None]
        return flash_attention(
            q, k, v, kv_mask=kv_mask, causal=False,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
    if impl != "xla":
        raise ValueError(f"unknown paged_attention impl {impl!r}")
    from transformer_tpu.ops.attention import dot_product_attention

    # The offset causal mask of make_cache_prefix_mask, batched per
    # sequence: query i (absolute position lengths - s_q + i) attends
    # pool position j iff j <= that position.
    positions = jnp.arange(L)[None, None, None, :]
    q_pos = (lengths[:, None, None, None] - s_q) + jnp.arange(s_q)[
        None, None, :, None
    ]
    visible = positions <= q_pos
    if window:
        visible &= positions > q_pos - window
    out, _ = dot_product_attention(q, k, v, visible)
    return out
