"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no long-context story at all — sequence length is capped at
50 and the full (B, H, S, S) score tensor is materialized per step
(``Attention.py:20``, ``utils.py:22``; SURVEY.md §5 "Long-context"). These are
the TPU-native mechanisms that make the 4096-token decoder-only config
(BASELINE.json configs[4]) scale past one chip:

- **Ring attention** (``ring_attention``): activations are sharded along the
  sequence on the ``seq`` mesh axis. Each device scores its local query chunk
  against every key/value chunk as the chunks rotate around the ring via
  ``lax.ppermute`` over ICI, folding each contribution in with the same
  online-softmax update the flash kernel uses. Peak memory is O(S/P · S/P)
  per device and the permute overlaps with the matmuls under XLA's latency
  hiding scheduler.

- **Ulysses** (``ulysses_attention``): two ``lax.all_to_all``s re-shard the
  activation from sequence-sharded to head-sharded and back, so each device
  runs *full-sequence* attention on H/P heads. Cheaper collectives for
  moderate S (2 all-to-alls vs P-1 permutes of the whole KV), but requires
  num_heads % P == 0 and the full S on every chip.

Both are **per-shard** functions: call them inside ``shard_map`` (or any
context where ``axis_name`` is bound). ``make_sequence_parallel_attention``
wraps either in shard_map against a concrete mesh for stack-level use.

Mask/causality semantics mirror ``kernels.flash_attention``: an optional
(B, S_local) key-padding mask (True = attend) plus a structural causal flag;
chunk-level causality is resolved from ring positions, so above-diagonal
chunk pairs contribute nothing.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from transformer_tpu.kernels.flash_attention import (
    _MASKED,
    _FlashConfig,
    _largest_divisor_block,
    flash_chunk_bwd,
    flash_ring_step,
)


@dataclasses.dataclass(frozen=True)
class _RingConfig:
    """Static ring configuration (hashable: the nondiff custom-vjp arg)."""

    axis_name: str
    axis_size: int
    causal: bool
    has_mask: bool
    block_q: int
    block_k: int
    num_heads: int
    scale: float
    interpret: bool
    num_kv_heads: int = 0  # 0 = same as num_heads (plain MHA)
    # Sliding window (causal only). The band is STATIC per hop: at hop t the
    # visiting kv chunk sits exactly t chunks behind the local q chunk
    # (src = (my - t) mod P), so in local tile coordinates the window
    # constraint col_global > row_global - W becomes col > row - (W - t·C) —
    # a static band the kernels skip tiles against. Hops with the whole
    # chunk below the band are dropped from the ring entirely, so ICI
    # traffic is O(window), not O(S).
    window: int = 0
    chunk: int = 0  # local chunk length C (set when window > 0)

    def flash(self, causal: bool, band: int | None = None) -> _FlashConfig:
        """Kernel config for one chunk pair; ``causal`` means 'this is the
        diagonal pair' (intra-chunk causality — local coordinates coincide
        with global ones there); ``band`` is the hop's static window band."""
        return _FlashConfig(
            causal=causal,
            has_mask=self.has_mask,
            block_q=self.block_q,
            block_k=self.block_k,
            num_heads=self.num_heads,
            scale=self.scale,
            interpret=self.interpret,
            num_kv_heads=self.num_kv_heads,
            band=band,
        )

    def kept_hops(self) -> int:
        """How many ring hops can contribute at all under the window: hop t
        is dead once even its newest position (local col c-1 against local
        row 0) falls out of the band (W <= t·C - C + 1). Monotonic in t, so
        the ring simply stops early. Without a window: all P hops."""
        if not self.window:
            return self.axis_size
        t = 0
        while t < self.axis_size and self.window > t * self.chunk - self.chunk + 1:
            t += 1
        return t

    def hop_band(self, t: int) -> int | None:
        return (self.window - t * self.chunk) if self.window else None


def _ring_block(c: int, requested: int) -> int:
    """A TPU-legal tile size that divides the chunk exactly (no padding in
    the ring: carries are chunk-shaped): 8-aligned divisor, else the whole
    chunk (a block equal to the full dim is always legal)."""
    blk = _largest_divisor_block(c, requested)
    return blk if blk % 8 == 0 else c


def _fold(x: jax.Array) -> jax.Array:
    """(B, C, H, D) -> (B*H, C, D): heads become independent grid rows."""
    b, c, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, c, d)


def _unfold(x: jax.Array, b: int, h: int) -> jax.Array:
    bh, c, d = x.shape
    return x.reshape(b, h, c, d).transpose(0, 2, 1, 3)


def _tile_mask(kv_mask: jax.Array | None, block_k: int) -> jax.Array | None:
    """(B, C) -> the kernels' pre-tiled (B, C/block_k, 1, block_k) int32."""
    if kv_mask is None:
        return None
    b, c = kv_mask.shape
    return kv_mask.astype(jnp.int32).reshape(b, c // block_k, 1, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring(cfg: _RingConfig, q, k, v, kv_mask):
    out, _ = _ring_fwd_impl(cfg, q, k, v, kv_mask)
    return out


def _ring_fwd_impl(cfg: _RingConfig, q, k, v, kv_mask):
    """Forward ring: one ``flash_ring_step`` Pallas call per hop folds the
    visiting KV chunk into the online-softmax carry — scores exist only as
    (block_q, block_k) VMEM tiles, never as a (C, C) HBM tensor."""
    b, c, h, d = q.shape
    P_ = cfg.axis_size
    my = jax.lax.axis_index(cfg.axis_name)
    shift = [(i, (i + 1) % P_) for i in range(P_)]
    qf = _fold(q)
    nq = c // cfg.block_q
    m = jnp.full((b * h, nq, cfg.block_q, 1), _MASKED, jnp.float32)
    l = jnp.zeros_like(m)
    acc = jnp.zeros((b * h, c, d), jnp.float32)

    k_cur, v_cur, mask_cur = k, v, kv_mask
    hops = cfg.kept_hops()  # < P_ under a window: the ring stops early
    for t in range(hops):  # unrolled: XLA overlaps each ppermute with compute
        src = (my - t) % P_  # global index of the chunk visiting this step
        kf, vf = _fold(k_cur), _fold(v_cur)
        mt = _tile_mask(mask_cur, cfg.block_k)
        band = cfg.hop_band(t)  # static per hop (relative offset == t)

        def step(fcfg, m, l, acc, kf=kf, vf=vf, mt=mt):
            return flash_ring_step(fcfg, qf, kf, vf, mt, m, l, acc)

        if cfg.causal:
            # The whole chunk pair is below (fold fully), on (fold with
            # intra-chunk causality), or above the diagonal (skip).
            branch = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
            m, l, acc = jax.lax.switch(
                branch,
                [
                    functools.partial(step, cfg.flash(False, band)),
                    functools.partial(step, cfg.flash(True, band)),
                    lambda m, l, acc: (m, l, acc),
                ],
                m, l, acc,
            )
        else:
            m, l, acc = step(cfg.flash(False), m, l, acc)
        if t + 1 < hops:
            k_cur = jax.lax.ppermute(k_cur, cfg.axis_name, shift)
            v_cur = jax.lax.ppermute(v_cur, cfg.axis_name, shift)
            if mask_cur is not None:
                mask_cur = jax.lax.ppermute(mask_cur, cfg.axis_name, shift)

    l_col = l.reshape(b * h, c, 1)
    l_safe = jnp.where(l_col == 0.0, 1.0, l_col)
    out = _unfold((acc / l_safe), b, h).astype(q.dtype)
    lse = m + jnp.log(jnp.where(l == 0.0, 1.0, l))  # (B*H, nq, bq, 1)
    return out, lse


def _ring_fwd_rule(cfg, q, k, v, kv_mask):
    out, lse = _ring_fwd_impl(cfg, q, k, v, kv_mask)
    return out, (q, k, v, kv_mask, out, lse)


def _ring_bwd_rule(cfg, residuals, do):
    """Ring backward: dq accumulates locally; dk/dv ride the ring WITH their
    k/v chunks (P hops total, so every chunk's gradient arrives back home
    with all devices' contributions folded in). Probability tiles are
    recomputed per chunk from the forward's global logsumexp — the exact
    flash decomposition, O(block²) VMEM per tile."""
    q, k, v, kv_mask, out, lse = residuals
    b, c, h, d = q.shape
    P_ = cfg.axis_size
    my = jax.lax.axis_index(cfg.axis_name)
    shift = [(i, (i + 1) % P_) for i in range(P_)]
    qf, dof, outf = _fold(q), _fold(do), _fold(out)
    nq = c // cfg.block_q
    delta = jnp.sum(
        dof.astype(jnp.float32) * outf.astype(jnp.float32), axis=-1
    ).reshape(b * h, nq, cfg.block_q, 1)

    h_kv = k.shape[2]
    dq = jnp.zeros((b * h, c, d), jnp.float32)
    dk_cur = jnp.zeros((b * h_kv, c, d), jnp.float32)
    dv_cur = jnp.zeros((b * h_kv, c, d), jnp.float32)
    k_cur, v_cur, mask_cur = k, v, kv_mask

    hops = cfg.kept_hops()
    for t in range(hops):
        src = (my - t) % P_
        kf, vf = _fold(k_cur), _fold(v_cur)
        mt = _tile_mask(mask_cur, cfg.block_k)
        band = cfg.hop_band(t)

        def step(fcfg, dq, dk_acc, dv_acc, kf=kf, vf=vf, mt=mt):
            dq_s, dk_s, dv_s = flash_chunk_bwd(
                fcfg, qf, kf, vf, mt, lse, delta, dof
            )
            return (
                dq + dq_s.astype(jnp.float32),
                dk_acc + dk_s.astype(jnp.float32),
                dv_acc + dv_s.astype(jnp.float32),
            )

        if cfg.causal:
            branch = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
            dq, dk_cur, dv_cur = jax.lax.switch(
                branch,
                [
                    functools.partial(step, cfg.flash(False, band)),
                    functools.partial(step, cfg.flash(True, band)),
                    lambda dq, dk_acc, dv_acc: (dq, dk_acc, dv_acc),
                ],
                dq, dk_cur, dv_cur,
            )
        else:
            dq, dk_cur, dv_cur = step(cfg.flash(False), dq, dk_cur, dv_cur)
        # Full ring: rotate EVERY hop (unlike the forward's P-1) — after P
        # hops the kv chunks, and the gradients riding with them, are home
        # again. Early-stopped ring (window): skip the last hop's rotation
        # (its k/v would never be used) and fold ALL remaining displacement
        # into the single re-home permute below.
        if t + 1 < hops or hops == P_:
            k_cur = jax.lax.ppermute(k_cur, cfg.axis_name, shift)
            v_cur = jax.lax.ppermute(v_cur, cfg.axis_name, shift)
            dk_cur = jax.lax.ppermute(dk_cur, cfg.axis_name, shift)
            dv_cur = jax.lax.ppermute(dv_cur, cfg.axis_name, shift)
            if mask_cur is not None:
                mask_cur = jax.lax.ppermute(mask_cur, cfg.axis_name, shift)

    if hops < P_:
        # dk/dv sit hops-1 rotations from the loop; one permute covering
        # the remaining P - (hops - 1) steps re-homes them (skip the no-op
        # when that wraps to a full circle).
        offset = (P_ - (hops - 1)) % P_
        if offset:
            rehome = [(i, (i + offset) % P_) for i in range(P_)]
            dk_cur = jax.lax.ppermute(dk_cur, cfg.axis_name, rehome)
            dv_cur = jax.lax.ppermute(dv_cur, cfg.axis_name, rehome)

    return (
        _unfold(dq, b, h).astype(q.dtype),
        _unfold(dk_cur, b, h_kv).astype(k.dtype),
        _unfold(dv_cur, b, h_kv).astype(v.dtype),
        None,
    )


_ring.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    kv_mask: jax.Array | None = None,
    causal: bool = False,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Blockwise ring attention over a sequence-sharded activation.

    The inner loop IS the flash kernel (``kernels.flash_attention``): each
    ring hop folds the visiting KV chunk into the online-softmax carry with
    one ``flash_ring_step`` Pallas call, so per-device memory is O(block_q ×
    block_k) VMEM tiles + the O(C·D) carry — never the (C, C) fp32 score
    block the r2 XLA-einsum version materialized per hop. The backward pass
    recomputes probability tiles from the forward's global logsumexp and
    rotates dk/dv home with their chunks (custom VJP).

    Args:
      q, k, v: (B, C, H, D) local chunks, C = S / axis_size. Chunk i on
        device i covers global positions [i*C, (i+1)*C). Grouped-query
        attention: k/v may carry FEWER heads (B, C, H_kv, D) with
        H % H_kv == 0 — kv stays at H_kv heads through the whole ring, so
        both the Pallas tiles AND the per-hop ppermute payload shrink by
        the group factor (the GQA bandwidth win extends to ICI).
      axis_name: mesh axis the sequence is sharded over (bound in shard_map).
      axis_size: number of devices on that axis (static Python int — the ring
        is unrolled so XLA can overlap each ppermute with the next matmul).
      kv_mask: optional (B, C) bool, True where the local key is real.
      causal: structural causal masking across global positions (chunk pairs
        fully above the diagonal skip their kernel launch entirely).
      window: causal sliding window (requires ``causal``). The hop-t band
        offset is STATIC (the visiting chunk is always exactly t chunks
        behind), so the band is a compile-time kernel constraint AND the
        ring stops after ceil-ish window/C hops — out-of-band chunks are
        never even ppermuted, making ICI traffic O(window), not O(S).
      block_q, block_k: requested tile sizes; shrunk to TPU-legal divisors
        of the chunk length.
      interpret: run the Pallas kernels in interpret mode (default: off-TPU).

    Returns (B, C, H, D) in q's dtype.
    """
    b, c, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(
            f"query heads {h} must be a multiple of kv heads {h_kv}"
        )
    if window and not causal:
        raise ValueError("ring window requires causal=True")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cfg = _RingConfig(
        axis_name=axis_name,
        axis_size=axis_size,
        causal=causal,
        has_mask=kv_mask is not None,
        block_q=_ring_block(c, block_q),
        block_k=_ring_block(c, block_k),
        num_heads=h,
        scale=d**-0.5,
        interpret=bool(interpret),
        num_kv_heads=h_kv,
        window=int(window),
        chunk=c,
    )
    if kv_mask is not None:
        kv_mask = jnp.broadcast_to(kv_mask, (b, c))
    return _ring(cfg, q, k, v, kv_mask)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
    kv_mask: jax.Array | None = None,
    causal: bool = False,
    window: int = 0,
) -> jax.Array:
    """Ulysses-style sequence parallelism: all-to-all from sequence-sharded
    (B, C, H, D) to head-sharded (B, S, H/P, D), full-sequence attention per
    device, and all-to-all back. Requires H % axis_size == 0.

    Grouped-query kv (k/v with H_kv < H heads, H % H_kv == 0) rides the
    all-to-all at its own head count when H_kv % axis_size == 0: each device
    then holds q-head block i and kv-head block i, which pair exactly (local
    group == global group), and the kv all-to-all payload shrinks by the
    group factor. Callers fall back to repeating kv when H_kv doesn't divide
    the axis (``seq_context.seq_parallel_attention``)."""
    b, c, h, d = q.shape
    h_kv = k.shape[2]
    if h % axis_size:
        raise ValueError(
            f"ulysses needs num_heads ({h}) divisible by the seq axis ({axis_size})"
        )
    if h_kv % axis_size:
        raise ValueError(
            f"ulysses with grouped kv needs kv heads ({h_kv}) divisible by "
            f"the seq axis ({axis_size}); repeat kv to full heads first"
        )

    def seq_to_heads(x):  # (B, C, H, D) -> (B, S, H/P, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):  # (B, S, H/P, D) -> (B, C, H, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    q_full, k_full, v_full = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)

    # Per-device full-sequence attention runs the FLASH kernel, not the
    # dense XLA path: at the long-context shapes the seq axis exists for,
    # a dense (S, S) causal mask + score tensor per device would be the
    # exact O(S²) HBM blow-up sequence parallelism is meant to avoid.
    # Causality stays structural (above-diagonal tiles skip their launch)
    # and key padding rides as a (B, S) vector.
    full_kv = (
        jax.lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)  # (B, S)
        if kv_mask is not None
        else None
    )
    from transformer_tpu.kernels.flash_attention import flash_attention

    # Windowed attention passes straight through: each device holds the FULL
    # sequence for its head block, so the flash kernel's structural band
    # applies unchanged.
    out = flash_attention(
        q_full, k_full, v_full, kv_mask=full_kv, causal=causal, window=window
    )
    return heads_to_seq(out)


def make_sequence_parallel_attention(
    mesh: Mesh,
    impl: str = "ring",
    axis: str = "seq",
    batch_axes: tuple[str, ...] = (),
):
    """Wrap ring/ulysses attention in shard_map against a concrete mesh.

    Returns ``fn(q, k, v, kv_mask=None, causal=False)`` over *global*
    (B, S, H, D) arrays with S sharded on ``axis`` (and optionally B on
    ``batch_axes``) — the stack-level entry point used by the long-context
    trunk and the parity tests.
    """
    axis_size = mesh.shape[axis]
    inner = {"ring": ring_attention, "ulysses": ulysses_attention}[impl]
    bdim = tuple(batch_axes) if batch_axes else None
    act = P(bdim, axis, None, None)
    mask_spec = P(bdim, axis)

    def call(q, k, v, kv_mask=None, causal=False, window=0):
        fn = functools.partial(
            inner, axis_name=axis, axis_size=axis_size, causal=causal,
            window=window,
        )
        if kv_mask is None:
            sharded = shard_map(
                lambda q, k, v: fn(q, k, v),
                mesh=mesh,
                in_specs=(act, act, act),
                out_specs=act,
                check_vma=False,
            )
            return sharded(q, k, v)
        sharded = shard_map(
            lambda q, k, v, m: fn(q, k, v, kv_mask=m),
            mesh=mesh,
            in_specs=(act, act, act, mask_spec),
            out_specs=act,
            check_vma=False,
        )
        return sharded(q, k, v, kv_mask)

    return call
