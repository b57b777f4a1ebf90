"""One position of a Mamba-2 state-space layer over every pool slot, the state
updated in place: the decode step of an SSD mixer (``ops/ssd.py``).

For slot ``n`` and head ``h``, with ``S`` the head's (P, N) float32 state
(the head's channels down, the state's across) and ``g`` the head's group:

    S <- exp(dt A) S + (dt x) B_g^T;   y = S C_g

The state is the layer's whole traffic: 4.19 MB a slot at 128 heads of 64 x
128, read once and written once (the input is aliased to the output), beside
a few KB of x, B, C and the step sizes. So the call is bound by bytes, and the
grid walks the slots with one slot's state a block: BlockSpec pipelining
fetches slot ``n + 1`` and writes slot ``n - 1`` back while slot ``n`` is
updated.

The products are on the VPU, none on the MXU: the decay and ``dt x`` are
columns (a head's channels down) that multiply the state and the row ``B_g``.
The columns cannot be sliced out of a row on the chip, so the wrapper hands
them over already transposed, ``dt x`` and the decay of all heads side by side
as one (P, 2 H) matrix a slot, and the kernel takes head ``h``'s by a static
lane slice in a loop unrolled over the heads, as ``kernels/kda_step.py`` does.

The read-out ``S C_g`` is one reduction a group. The group's heads share
``C_g``, so their new states, read back as one (per_group P, N) matrix (the
leading axes merge for free: P is a whole number of sublane tiles), times the
row ``C_g`` is transposed on the XLU to (N, per_group P) and summed down the
sublanes: adds across vregs, as ``kda_step`` reduces, and one lane-dense row
of per_group P values (``y`` of head ``g per_group + j``, channel ``p`` at
lane ``j P + p``; 1,024 lanes at Nemotron's 16 heads of 64) stored whole into
a (G, per_group P) block. The wrapper reshapes it to (H, P) for free.

A slot that is not live (a free slot: position 0, fed PAD) is written back as
it was and reads out zeros: a freed slot's state is never read by the next
request because the prefill starts it from zeros. On non-TPU backends the
kernel runs in Pallas interpret mode (``interpret=None`` auto-detects).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(live_ref, cols_ref, b_ref, c_ref, s_ref, y_ref, s_out_ref, *, heads: int, groups: int):
    n = pl.program_id(0)
    per_group = heads // groups
    _, _, p, n_state = s_ref.shape

    @pl.when(live_ref[n] > 0)
    def _update():
        for g in range(groups):
            first = g * per_group
            for h in range(first, first + per_group):
                dtx = cols_ref[0, :, h : h + 1]  # (P, 1)
                decay = cols_ref[0, :, heads + h : heads + h + 1]  # (P, 1)
                s_out_ref[0, h] = s_ref[0, h] * decay + dtx * b_ref[0, g : g + 1, :]
            # The read-out a group: summed down the sublanes of the transposed
            # product, so that it comes out as one lane-dense row.
            new = s_out_ref[0, first : first + per_group].reshape(per_group * p, n_state)
            read = (new * c_ref[0, g : g + 1, :]).T  # (N, per_group P)
            y_ref[0, g : g + 1, :] = jnp.sum(read, axis=0, keepdims=True)

    @pl.when(live_ref[n] <= 0)
    def _keep():
        s_out_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_step(
    state: jax.Array,
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    bmat: jax.Array,
    cmat: jax.Array,
    live: jax.Array,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``state`` (N, H, P, N_s) float32 (aliased to the result: updated in
    place inside a program that donates it); x (N, H, P) and dt (N, H)
    float32; ``a`` = -exp(A_log) (H,); B and C (N, G, N_s) float32; ``live``
    (N,) bool or int, the slots that hold a sequence. Returns (``S C`` (N, H,
    P) float32, zeros for the others; the new state, theirs unchanged)."""
    n, heads, p, n_state = state.shape
    groups = bmat.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f32 = jnp.float32
    dt = dt.astype(f32)
    # (N, P, 2 H): dt x of every head, then its decay, the head's channels down.
    dtx = jnp.swapaxes(dt[..., None] * x.astype(f32), 1, 2)
    decay = jnp.broadcast_to(jnp.exp(dt * a.astype(f32))[:, None, :], (n, p, heads))
    cols = jnp.concatenate([dtx, decay], axis=2)

    row = heads // groups * p  # a group's read-out: its heads' channels, head by head
    y, new_state = pl.pallas_call(
        functools.partial(_kernel, heads=heads, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((1, p, 2 * heads), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, groups, n_state), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, groups, n_state), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, heads, p, n_state), lambda i, *_: (i, 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, groups, row), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((1, heads, p, n_state), lambda i, *_: (i, 0, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n, groups, row), f32),
            jax.ShapeDtypeStruct(state.shape, f32),
        ],
        # The state goes in as operand 4 (after the prefetched ``live``).
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(32 * 1024 * 1024, 5 * heads * p * n_state * 4),
        ),
        interpret=bool(interpret),
        name="ssm_step",
    )(live.astype(jnp.int32), cols, bmat.astype(f32), cmat.astype(f32), state.astype(f32))
    return y.reshape(n, heads, p), new_state
