"""Gated short convolution: the mixer of a layer that keeps no KV rows.

No reference counterpart (the reference mixes positions by attention alone).
A layer of this kind (``config.AttentionKind.conv_kernel`` taps, ``L``) is

    (B, C, u) = split3(h W_in);  z = B * u
    c_t = sum_j w_j * z_{t - (L - 1) + j}      (causal, depthwise; z is 0 before position 0)
    y = (C * c) W_out

with no bias anywhere. What a sequence carries from one call to the next is
the last ``L - 1`` rows of ``z``: a fixed (L - 1, d_model) state however long
the sequence, where an attention layer keeps a K and a V row a position.

One function serves every path. The full-sequence forward (training, the
dense-cache forward, a prefill chunk) passes the chunk and the state to its
left (zeros at position 0); the one-token step passes one row and the state,
and gets ``(state[1:], z)`` back. XLA fuses the gate, the taps and the second
gate into the products' epilogues; at decode the whole mixer is two small
matmuls over the slots.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from transformer_tpu.ops.nn import Params, glorot_uniform


def short_conv_init(
    key: jax.Array, d_model: int, kernel: int, param_dtype=jnp.float32
) -> Params:
    """``in``: d_model -> 3 d_model (the gate B, the gate C, the input u, in
    that order); ``conv``: (L, d_model) taps, oldest first, drawn so that the
    convolution keeps its input's variance; ``out``: d_model -> d_model."""
    k_in, k_conv, k_out = jax.random.split(key, 3)
    limit = math.sqrt(3.0 / kernel)
    taps = jax.random.uniform(k_conv, (kernel, d_model), jnp.float32, -limit, limit)
    return {
        "in": {"kernel": glorot_uniform(k_in, (d_model, 3 * d_model), param_dtype, d_model, 3 * d_model)},
        "conv": {"kernel": taps.astype(param_dtype)},
        "out": {"kernel": glorot_uniform(k_out, (d_model, d_model), param_dtype, d_model, d_model)},
    }


def init_conv_state(batch: int, d_model: int, kernel: int, dtype=jnp.bfloat16) -> jax.Array:
    """The state before position 0: ``L - 1`` rows of zeros a sequence."""
    return jnp.zeros((batch, kernel - 1, d_model), dtype)


# Every key that holds a fixed state a sequence, whatever the layer's kind.
_STATE_KEYS = ("conv_state", "kda_state", "kda_conv", "ssm_state", "ssm_conv")


def state_buffer_keys(cache: dict) -> tuple[str, ...]:
    """The keys of one layer's cache or pool entry that hold a fixed state a
    sequence (``ops.attention.kv_buffer_keys`` lists the per-position ones):
    ``("conv_state",)`` for a short-convolution layer, ``("kda_state",
    "kda_conv")`` for a delta-rule layer (``ops/kda.py``: the matrix a head
    and the three convolutions' inputs), nothing otherwise. The ONE listing:
    the prefill's read and write, the views and the scatter iterate it."""
    return tuple(key for key in _STATE_KEYS if key in cache)


def short_conv_apply(
    params: Params, h: jax.Array, state: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """(B, S, M) normalised input, (B, L - 1, M) state to its left (None =
    zeros: the sequence starts here) -> ((B, S, M) output, the state to the
    right of the chunk: its last ``L - 1`` gated inputs, the old state's
    rows where the chunk is shorter than that)."""
    dtype = h.dtype
    taps = params["conv"]["kernel"]
    kernel = taps.shape[0]
    s = h.shape[1]
    gates = jnp.einsum("bsm,mf->bsf", h, params["in"]["kernel"].astype(dtype))
    b, c, u = jnp.split(gates, 3, axis=-1)
    z = b * u
    if state is None:
        state = init_conv_state(h.shape[0], h.shape[2], kernel, dtype)
    padded = jnp.concatenate([state.astype(dtype), z], axis=1)  # (B, L - 1 + S, M)
    conv = sum(
        taps[j].astype(jnp.float32) * padded[:, j : j + s].astype(jnp.float32)
        for j in range(kernel)
    )
    y = jnp.einsum(
        "bsm,mn->bsn", c * conv.astype(dtype), params["out"]["kernel"].astype(dtype)
    )
    return y, padded[:, s:]
