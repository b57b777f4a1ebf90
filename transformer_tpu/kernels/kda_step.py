"""One position of delta-rule linear attention over every pool slot, the
state updated in place: the decode step of a KDA layer (``ops/kda.py``).

For slot ``n`` and head ``h``, with ``S`` the head's (D, D) float32 state (key
channels down, value channels across):

    S <- Diag(exp(g)) S;   u = beta (v - S^T k);   S <- S + k u^T;   o = S^T q

The state is the layer's whole traffic: 2.10 MB a slot at 32 heads of 128, read
once and written once (the input is aliased to the output), beside a few KB of
q, k, v and gates. So the call is bound by bytes, and the grid walks the slots
with one slot's state a block: BlockSpec pipelining fetches slot ``n + 1`` and
writes slot ``n - 1`` back while slot ``n`` is updated.

The products are on the VPU, none on the MXU: ``S^T k`` and ``S^T q`` are a
multiply by a column and a sum down the sublanes, ``k u^T`` a column times a
row. The columns (a head's ``q``, ``k``, ``beta k`` and ``exp(g)``, key channels
down) cannot be sliced out of a row on the chip, so the wrapper hands them over
already transposed, the four kinds of all heads side by side as one (D, 4 H)
matrix a slot (64 KB beside the 2 MB; for 32 heads exactly 128 lanes), and the
kernel takes head ``h``'s by a static lane slice in a loop unrolled over the
heads. ``beta`` is folded into ``beta v`` and ``beta k`` by the wrapper, so the
kernel sees no scalar a head.

A slot that is not live (a free slot: position 0, fed PAD) is written back as
it was and reads out zeros, as ``short_conv`` keeps a free slot's rows with
``jnp.where(live, ...)``: a freed slot's state is never read by the next
request because the prefill starts it from zeros, not because the step wipes
it. On non-TPU backends the kernel runs in Pallas interpret mode
(``interpret=None`` auto-detects, as the other kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(live_ref, cols_ref, bv_ref, s_ref, o_ref, s_out_ref, *, heads: int):
    n = pl.program_id(0)

    @pl.when(live_ref[n] > 0)
    def _update():
        for h in range(heads):
            def col(kind, h=h):
                return cols_ref[0, :, kind * heads + h : kind * heads + h + 1]  # (D, 1)

            decayed = s_ref[0, h] * col(3)
            u = bv_ref[0, h : h + 1, :] - jnp.sum(decayed * col(2), axis=0, keepdims=True)
            new = decayed + col(1) * u
            o_ref[0, h : h + 1, :] = jnp.sum(new * col(0), axis=0, keepdims=True)
            s_out_ref[0, h] = new

    @pl.when(live_ref[n] <= 0)
    def _keep():
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_step(
    state: jax.Array,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    live: jax.Array,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``state`` (N, H, D, D) float32 (aliased to the result: updated in place
    inside a program that donates it); q, k (normalised, q scaled), v, the
    log-decay g: (N, H, D) float32; beta (N, H); ``live`` (N,) bool or int, the
    slots that hold a sequence. Returns (o (N, H, D) float32, zeros for the
    others; the new state, theirs unchanged)."""
    n, heads, d, _ = state.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    # (N, 4 H, D): q, k, beta k, exp(g) of every head, then key channels down.
    cols = jnp.concatenate([q, k, beta[..., None] * k, jnp.exp(g)], axis=1)
    cols = jnp.swapaxes(cols, 1, 2)  # (N, D, 4 H)
    bv = beta[..., None] * v

    o, new_state = pl.pallas_call(
        functools.partial(_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((1, d, 4 * heads), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, heads, d), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, heads, d, d), lambda i, *_: (i, 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, heads, d), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, heads, d, d), lambda i, *_: (i, 0, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n, heads, d), f32),
            jax.ShapeDtypeStruct(state.shape, f32),
        ],
        # The state goes in as operand 3 (after the prefetched ``live``).
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(32 * 1024 * 1024, 6 * heads * d * d * 4),
        ),
        interpret=bool(interpret),
        name="kda_step",
    )(live.astype(jnp.int32), cols, bv, state.astype(f32))
    return o, new_state
