"""Sinusoidal positional encoding.

Counterpart of the reference's ``positionalencoding.py:4-23``, computed with
jnp closed-form (traceable, constant-folded by XLA) instead of eager NumPy at
module-construction time. The table is sized by **max positions**, fixing the
reference's quirk of sizing it by vocab size (~32k rows; ``Encoder.py:40``,
SURVEY.md §2.3.5).

Layout matches the reference: the first d_model/2 channels carry sin of the
even-index angle frequencies and the last d_model/2 carry cos of the odd-index
frequencies, concatenated block-wise (``positionalencoding.py:19``) rather than
interleaved. Any self-consistent layout trains identically; the block layout is
also the friendlier one for rotary-style slicing later.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sinusoidal_positional_encoding(
    max_position: int, d_model: int, dtype=jnp.float32
) -> jax.Array:
    """Return (max_position, d_model) table: pe[p] = [sin(p/10000^(2i/d)) for
    even i] ++ [cos(p/10000^(2i/d)) for odd i] (reference ``get_angles``,
    ``positionalencoding.py:4-6``)."""
    positions = jnp.arange(max_position, dtype=jnp.float32)[:, None]  # (P, 1)
    channels = jnp.arange(d_model, dtype=jnp.float32)[None, :]  # (1, D)
    angle_rates = jnp.power(10000.0, -(2.0 * jnp.floor(channels / 2.0)) / d_model)
    angles = positions * angle_rates  # (P, D)
    evens = angles[:, 0::2]
    odds = angles[:, 1::2]
    table = jnp.concatenate([jnp.sin(evens), jnp.cos(odds)], axis=-1)
    return table.astype(dtype)


def rope_inv_freq(
    rotary_dim: int,
    base: float = 10000.0,
    yarn_factor: float = 0.0,
    yarn_original_max_position: int = 0,
    yarn_beta_fast: float = 32.0,
    yarn_beta_slow: float = 1.0,
) -> np.ndarray:
    """(rotary_dim / 2,) inverse frequencies ``base**(-i / (rotary_dim/2))``.

    ``yarn_factor > 0`` blends them as YaRN does (arXiv:2309.00071, the
    "NTK-by-parts" rule): channel pairs that turn more than ``beta_fast``
    times within the original context keep their frequency, those that turn
    fewer than ``beta_slow`` times have it divided by the factor, and a
    linear ramp over the pair index joins the two. A pair turns ``n`` times
    in ``L`` positions at index ``rotary_dim * ln(L / (2 pi n)) / (2 ln base)``;
    the ramp's ends are that index for ``beta_fast`` rounded down and for
    ``beta_slow`` rounded up, clipped to the pairs there are. Host numpy in
    float64: the table is a compile-time constant."""
    half = rotary_dim // 2
    inv = base ** (-np.arange(half, dtype=np.float64) / half)
    if not yarn_factor:
        return inv.astype(np.float32)

    def pair_index(turns: float) -> float:
        return rotary_dim * np.log(yarn_original_max_position / (turns * 2 * np.pi)) / (2 * np.log(base))

    low = max(np.floor(pair_index(yarn_beta_fast)), 0)
    high = min(np.ceil(pair_index(yarn_beta_slow)), rotary_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (inv / yarn_factor * ramp + inv * (1.0 - ramp)).astype(np.float32)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    base: float = 10000.0,
    *,
    rotary_share: float = 1.0,
    attention_factor: float = 1.0,
    **yarn,
) -> jax.Array:
    """Rotary position embedding (no reference counterpart — the reference is
    additive-sinusoidal only; RoPE is the long-context extension for the
    decoder-only 4096-token config, ``ModelConfig.position_scheme="rope"``).

    Rotates each (even, odd-half) channel pair of ``x`` (B, S, H, D) by an
    angle proportional to its absolute position, which makes q·k depend only
    on the RELATIVE distance between query and key. Half-split layout
    (first D/2 channels pair with the last D/2) — contiguous slices, no
    interleaved gather, TPU-lane friendly. ``positions`` is (S,) absolute
    token positions (pass ``offset + arange(S)`` during KV-cache decode).
    Angles in fp32; output in x.dtype.

    ``rotary_share < 1`` rotates only the first ``D * rotary_share`` channels
    (half-split within them) and passes the rest; ``attention_factor``
    multiplies cos and sin; ``yarn`` are ``rope_inv_freq``'s YaRN arguments.
    """
    rot = int(x.shape[-1] * rotary_share)
    half = rot // 2
    inv_freq = jnp.asarray(rope_inv_freq(rot, base, **yarn))  # (rot/2,)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (S, rot/2)
    cos = jnp.cos(angles)[None, :, None, :]  # (1, S, 1, rot/2)
    sin = jnp.sin(angles)[None, :, None, :]
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:rot]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x32[..., rot:]], axis=-1
    )
    return rotated.astype(x.dtype)


def kind_rope(kind) -> dict:
    """``apply_rope``'s keyword arguments for one ``config.AttentionKind``."""
    kw = {"base": kind.rope_base, "rotary_share": kind.rotary_share,
          "attention_factor": kind.rope_attention_factor}
    if kind.yarn_factor:
        kw.update(
            yarn_factor=kind.yarn_factor,
            yarn_original_max_position=kind.yarn_original_max_position,
            yarn_beta_fast=kind.yarn_beta_fast, yarn_beta_slow=kind.yarn_beta_slow,
        )
    return kw
