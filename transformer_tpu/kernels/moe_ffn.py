"""Grouped feed-forward over the experts that received tokens, gated (three
matrices an expert) or not (two).

The routed half of a dropless expert layer (``ops/moe.py``
``moe_apply_dropless``): rows arrive sorted by expert, each expert's rows
padded to whole row tiles, so that tile ``t`` belongs to ONE expert,
``tile_group[t]``. The grid walks (row tile, slice of the expert width); the
three weight blocks of a step are found through the scalar-prefetched
``tile_group``, so an expert that received no token owns no tile and its
weights are never read: at decode, where a step is bound by the bytes of the
expert weights it streams, the kernel reads the experts hit and no others.

Tiles past the live count (the grid is sized for the worst case) resolve to
the blocks of the last live step, which the pipeline does not fetch again,
and skip their compute; their output lands in a spare tile nobody reads.

Numerics follow ``ffn_apply``: each product accumulates in fp32 and is
rounded to the compute dtype where ``dense_apply`` rounds; the activation
(and the gate product) run in fp32 and are rounded once, and the sum over the
expert width is taken slice by slice in fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from transformer_tpu.ops.ffn import _ACTIVATIONS, _GATED_ACTIVATIONS, _ffn_tile, is_gated

# Double-buffered weight slices of 1,024 columns (a whole expert of that
# width: on the chip 8 % faster at decode than slices of 256 or 512, PERF.md
# PR 28) at a 3,072-wide model take 38 MB; the default scoped limit is 16.
_VMEM_LIMIT = 64 * 1024 * 1024
# What the three double-buffered weight slices of a grid step may take of it
# (the rows, the accumulator and the output tile are small beside them).
_SLICE_BUDGET = 48 * 1024 * 1024


def expert_slice_width(m: int, dff: int, itemsize: int, matrices: int = 3) -> int:
    """Columns of the expert width one grid step reads: the whole expert
    where its ``matrices``, double-buffered, fit ``_SLICE_BUDGET`` (one
    step an expert: no slice's fixed cost paid twice), else the widest lane
    tile that divides the width and does."""
    fits = _SLICE_BUDGET // (2 * matrices * m * itemsize)
    return dff if dff <= fits else _ffn_tile(dff, fits)


def _kernel(
    group_ref,  # (tiles,) int32 SMEM: expert of each row tile
    live_ref,   # (1,) int32 SMEM: row tiles that hold rows
    x_ref,      # (tm, M) rows of this tile
    *refs,      # [wg_ref (1, M, bf) gate slice of the tile's expert,] wi_ref
                # (1, M, bf), wo_ref (1, bf, M), out_ref (tm, M), acc_ref (tm, M) fp32
    activation: str,
):
    del group_ref
    gated = is_gated(activation)
    wg_ref, (wi_ref, wo_ref, out_ref, acc_ref) = (refs[0], refs[1:]) if gated else (None, refs)
    t, f = pl.program_id(0), pl.program_id(1)
    dtype = x_ref.dtype

    @pl.when(t < live_ref[0])
    def _tile():
        @pl.when(f == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def dense(w_ref):
            # Rounded where dense_apply rounds, then back to fp32: Mosaic has
            # no bf16 logistic on this chip, and the gate product is exact.
            return jax.lax.dot_general(
                x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(dtype).astype(jnp.float32)

        if gated:
            h = (_GATED_ACTIVATIONS[activation](dense(wg_ref)) * dense(wi_ref)).astype(dtype)
        else:
            h = _ACTIVATIONS[activation](dense(wi_ref)).astype(dtype)
        acc_ref[...] += jax.lax.dot_general(
            h, wo_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(f == pl.num_programs(1) - 1)
        def _store():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def moe_expert_ffn(
    x: jax.Array,
    w_gate: jax.Array | None,
    w_in: jax.Array,
    w_out: jax.Array,
    tile_group: jax.Array,
    live_tiles: jax.Array,
    *,
    tile_rows: int,
    activation: str = "swiglu",
    block_dff: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``act(x @ w_gate[e]) * (x @ w_in[e]) @ w_out[e]`` (a gated
    ``activation``) or ``act(x @ w_in[e]) @ w_out[e]`` (``w_gate`` None) for
    each row tile's expert ``e = tile_group[t]``.

    Args:
      x: (tiles * tile_rows, M) rows grouped by expert, each group padded to
        whole tiles (padding rows are zero and give zero).
      w_gate, w_in: (E, M, F); w_out: (E, F, M) stacked expert weights
        (``w_gate`` None where ``activation`` is not gated).
      tile_group: (tiles,) int32, the expert of each tile; entries at or past
        ``live_tiles`` repeat the last live tile's.
      live_tiles: () int32, how many leading tiles hold rows.
      block_dff: columns of the expert width a grid step reads (None:
        ``expert_slice_width``).

    Returns ((tiles + 1) * tile_rows, M): the rows of ``x``'s tiles, then a
    spare tile that takes what the dead grid steps write back. Rows of tiles
    at or past ``live_tiles`` are written by no one: do not read them.
    """
    rows, m = x.shape
    tiles = rows // tile_rows
    dff = w_in.shape[2]
    if (w_gate is not None) != is_gated(activation):
        raise ValueError(f"a gated activation takes w_gate and no other does: got {activation!r}")
    weights = [w for w in (w_gate, w_in) if w is not None]
    if block_dff is None:
        block_dff = expert_slice_width(m, dff, jnp.dtype(w_in.dtype).itemsize, len(weights) + 1)
    bf = _ffn_tile(dff, block_dff)
    nf = dff // bf
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def live(t, live_ref):
        return t < live_ref[0]

    def at_rows(t, f, group_ref, live_ref):
        return (jnp.clip(t, 0, jnp.maximum(live_ref[0] - 1, 0)), 0)

    def at_slice(t, f, live_ref):
        return jnp.where(live(t, live_ref), f, nf - 1)

    def at_cols(t, f, group_ref, live_ref):
        return (group_ref[t], 0, at_slice(t, f, live_ref))

    def at_out_rows(t, f, group_ref, live_ref):
        return (group_ref[t], at_slice(t, f, live_ref), 0)

    def at_result(t, f, group_ref, live_ref):
        return (jnp.where(live(t, live_ref), t, tiles), 0)

    return pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles, nf),
            in_specs=[
                pl.BlockSpec((tile_rows, m), at_rows),
                *[pl.BlockSpec((1, m, bf), at_cols) for _ in weights],
                pl.BlockSpec((1, bf, m), at_out_rows),
            ],
            out_specs=pl.BlockSpec((tile_rows, m), at_result),
            scratch_shapes=[pltpu.VMEM((tile_rows, m), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows + tile_rows, m), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=bool(interpret),
        name="moe_expert_ffn",
    )(tile_group.astype(jnp.int32), live_tiles.astype(jnp.int32).reshape(1),
      x, *weights, w_out)
