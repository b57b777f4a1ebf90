"""Mamba-2 state-space mixer (SSD): the mixer of a layer whose state is a
matrix a head, however long the sequence.

No reference counterpart (the reference mixes positions by softmax attention
alone). The layer is Mamba-2's ("Transformers are SSMs", arXiv:2405.21060) as
Nemotron-H runs it. ``h`` is the normalised input, ``t`` a position, ``H``
heads of ``P`` channels, ``G`` groups of B and C of ``N`` channels each (head
``i`` reads group ``i // (H / G)``), ``L`` the convolution's taps:

    [z | xBC | dt] = h W_in                                      widths H P | H P + 2 G N | H
    xBC = SiLU(conv_L(xBC) + conv_bias);  [x | B | C] = xBC      causal depthwise; zeros before position 0
    dt  = softplus(dt + dt_bias);  A = -exp(A_log)               a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T                   S: (P, N) a head, float32, S_0 = 0
    y_t = S_t C_t + D x_t
    out = RMSNorm_groups(y * SiLU(z)) W_out                      the norm over G groups of H P / G channels

with no bias but the convolution's and ``dt``'s. What a sequence carries from
one call to the next: ``S`` (H x P x N float32) and the last ``L - 1`` inputs
of the convolution, ``(L - 1, H P + 2 G N)``: a fixed state, where an
attention layer keeps rows a position.

Which form each path takes. A chunk of more than one position (training, the
dense-cache forward, a prefill chunk with the state to its left) takes SSD's
**chunked form** (``ssd_chunked``): chunks of ``chunk`` positions, and inside
a chunk, with ``c_r`` the cumulative log-decay ``sum dt A`` up to position
``r`` of the chunk,

    y_i = sum_{j <= i} (C_i . B_j) exp(c_i - c_j) dt_j x_j + exp(c_i) S_0 C_i
    S_C = exp(c_C) S_0 + sum_j exp(c_C - c_j) dt_j x_j B_j^T

the state handed from chunk to chunk in a ``lax.scan``. Every exponent is a
difference of cumulative log-decays with the later position first, so it is
<= 0. A prompt is padded to whole chunks with positions whose ``dt`` is 0:
they neither decay the state nor write to it. One position (the dense-cache
decode step) takes the recurrence as it stands (``ssm_recurrent_step``); the
paged step runs it through ``kernels/ssm_step.py``, which reads and writes
each live slot's ``S`` once, in place.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from transformer_tpu.ops.nn import Params, glorot_uniform


def ssm_init(
    key: jax.Array, d_model: int, heads: int, head_dim: int, groups: int,
    state: int, taps: int, param_dtype=jnp.float32,
) -> Params:
    """``in``: d_model -> [z | x B C | dt]; ``conv``: (taps, H P + 2 G N) taps,
    oldest first, and their bias, both drawn as a depthwise ``Conv1d``'s
    default (U(+-taps**-0.5)); ``dt``'s bias the inverse softplus of
    exp(U(log 1e-3, log 1e-1)) floored at 1e-4, ``A_log`` log U(1, 16) (both
    float32 a head: Mamba-2's draw); ``D`` ones (float32 a head); the gated
    norm's scale (H P,); ``out``: H P -> d_model."""
    width = heads * head_dim
    conv_dim = width + 2 * groups * state
    ks = jax.random.split(key, 6)
    bound = 1.0 / math.sqrt(taps)
    dt = jnp.exp(jax.random.uniform(ks[3], (heads,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    in_width = 2 * width + 2 * groups * state + heads
    return {
        "in": {"kernel": glorot_uniform(ks[0], (d_model, in_width), param_dtype, d_model, in_width)},
        "conv": {
            "kernel": jax.random.uniform(ks[1], (taps, conv_dim), jnp.float32, -bound, bound).astype(param_dtype),
            "bias": jax.random.uniform(ks[2], (conv_dim,), jnp.float32, -bound, bound).astype(param_dtype),
        },
        "dt": {"bias": dt + jnp.log(-jnp.expm1(-dt))},  # softplus(bias) = dt
        "A_log": jnp.log(jax.random.uniform(ks[4], (heads,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((heads,), jnp.float32),
        "norm": {"scale": jnp.ones((width,), param_dtype)},
        "out": {"kernel": glorot_uniform(ks[5], (width, d_model), param_dtype, width, d_model)},
    }


def init_ssm_state(
    batch: int, heads: int, head_dim: int, groups: int, state: int, taps: int,
    dtype=jnp.bfloat16,
) -> dict[str, jax.Array]:
    """The state before position 0: ``ssm_state`` (B, H, P, N) float32 zeros
    and ``ssm_conv`` (B, L - 1, H P + 2 G N) zeros."""
    return {
        "ssm_state": jnp.zeros((batch, heads, head_dim, state), jnp.float32),
        "ssm_conv": jnp.zeros((batch, taps - 1, heads * head_dim + 2 * groups * state), dtype),
    }


def ssm_inputs(params: Params, h: jax.Array, conv_state: jax.Array, groups: int):
    """Everything of the layer before the recurrence. (B, S, M) normalised
    input and the (B, L - 1, C) convolution inputs to its left -> z (B, S, H
    P) in the compute dtype; x (B, S, H, P), B and C (B, S, G, N), each the
    compute dtype's rounding of the convolution's output, as float32; dt
    (B, S, H) float32; the convolution inputs to the right of the chunk."""
    dtype = h.dtype
    heads = params["A_log"].shape[0]
    width = params["norm"]["scale"].shape[0]
    taps = params["conv"]["kernel"].shape[0]
    b, s, _ = h.shape
    proj = jnp.einsum("bsm,mf->bsf", h, params["in"]["kernel"].astype(dtype))
    z, xbc, dt = jnp.split(proj, [width, proj.shape[-1] - heads], axis=-1)
    padded = jnp.concatenate([conv_state.astype(dtype), xbc], axis=1)  # (B, L - 1 + S, C)
    w = params["conv"]["kernel"].astype(jnp.float32)
    conv = sum(w[j] * padded[:, j : j + s].astype(jnp.float32) for j in range(taps))
    xbc = jax.nn.silu(conv + params["conv"]["bias"].astype(jnp.float32)).astype(dtype).astype(jnp.float32)
    x, bmat, cmat = jnp.split(xbc, [width, width + (xbc.shape[-1] - width) // 2], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt"]["bias"].astype(jnp.float32))
    return (
        z, x.reshape(b, s, heads, -1), bmat.reshape(b, s, groups, -1),
        cmat.reshape(b, s, groups, -1), dt, padded[:, s:],
    )


def ssm_output(params: Params, h: jax.Array, y: jax.Array, x: jax.Array, z: jax.Array,
               groups: int, epsilon: float) -> jax.Array:
    """(B, S, H, P) float32 read-outs ``S C`` and the inputs ``x`` -> the
    layer's (B, S, M) output: ``+ D x``, the gate ``SiLU(z)``, the RMSNorm
    over each of ``groups`` groups of channels, the out projection."""
    dtype = h.dtype
    b, s = y.shape[:2]
    y = y + params["D"].astype(jnp.float32)[:, None] * x
    y = y.reshape(b, s, -1) * jax.nn.silu(z.astype(jnp.float32))
    g = y.reshape(b, s, groups, -1)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + epsilon)
    y = g.reshape(b, s, -1) * params["norm"]["scale"].astype(jnp.float32)
    return jnp.einsum("bsf,fm->bsm", y.astype(dtype), params["out"]["kernel"].astype(dtype))


def _by_head(t: jax.Array, heads: int) -> jax.Array:
    """(..., G, N) a group -> (..., H, N) a head (head i reads group i // (H / G))."""
    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def ssm_recurrent_step(state, x, dt, a, bmat, cmat):
    """One position of the recurrence in plain XLA. ``state`` (B, H, P, N)
    float32; x (B, H, P), dt (B, H), B and C (B, G, N), all float32; ``a``
    = -exp(A_log) (H,). Returns (``S C`` (B, H, P), the new state)."""
    heads = state.shape[1]
    decay = jnp.exp(dt * a)
    new = decay[..., None, None] * state + (dt[..., None] * x)[..., None] * _by_head(bmat, heads)[:, :, None, :]
    return jnp.einsum("bhpn,bhn->bhp", new, _by_head(cmat, heads), precision="highest"), new


def ssd_chunked(state, x, dt, a, bmat, cmat, chunk: int):
    """The chunked form over (B, S, H, P) float32 ``x``, (B, S, H) ``dt``,
    (B, S, G, N) ``B`` and ``C`` from ``state`` (B, H, P, N) float32: (``S C``
    (B, S, H, P), the state after the last position). ``S`` is padded to
    whole chunks with positions whose ``dt`` is 0 (they neither decay nor
    write)."""
    b, s, heads, p = x.shape
    groups, n_state = bmat.shape[2:]
    per = heads // groups
    pad = -s % chunk
    n = (s + pad) // chunk

    def chunks(t):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape(b, n, chunk, *t.shape[2:]), 1, 0)  # (n, B, C, ...)

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(s0, xs):
        xc, dc, bc, cc = xs  # (B, C, H, P), (B, C, H), (B, C, G, N) x 2
        cum = jnp.cumsum(dc * a, axis=1)  # (B, C, H), decreasing in the position
        gap = jnp.where(lower[None, :, :, None], cum[:, :, None] - cum[:, None, :], -jnp.inf)
        decay = jnp.exp(gap).reshape(b, chunk, chunk, groups, per)  # (B, Ci, Cj, G, R)
        cb = jnp.einsum("bign,bjgn->bijg", cc, bc, precision="highest")
        dx = (dc[..., None] * xc).reshape(b, chunk, groups, per, p)
        y = jnp.einsum("bijgr,bjgrp->bigrp", cb[..., None] * decay, dx, precision="highest")
        s0g = s0.reshape(b, groups, per, p, n_state)
        into = jnp.exp(cum).reshape(b, chunk, groups, per)  # exp(c_i) <= 1
        y = y + into[..., None] * jnp.einsum("bign,bgrpn->bigrp", cc, s0g, precision="highest")
        out_of = jnp.exp(cum[:, -1:] - cum).reshape(b, chunk, groups, per)  # exp(c_C - c_j) <= 1
        s1 = jnp.exp(cum[:, -1]).reshape(b, groups, per)[..., None, None] * s0g + jnp.einsum(
            "bjgr,bjgrp,bjgn->bgrpn", out_of, dx, bc, precision="highest"
        )
        return s1.reshape(b, heads, p, n_state), y.reshape(b, chunk, heads, p)

    with jax.named_scope("ssd_chunk_scan"):
        state, y = jax.lax.scan(one, state, tuple(chunks(t) for t in (x, dt, bmat, cmat)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, n * chunk, heads, p)
    return y[:, :s], state


def ssm_apply(
    params: Params, h: jax.Array, state: dict[str, jax.Array] | None, *,
    groups: int, state_size: int, chunk: int, epsilon: float = 1e-5,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """(B, S, M) normalised input and the state to its left (``None`` = the
    sequence starts here) -> ((B, S, M) output, the state to its right:
    ``{"ssm_state", "ssm_conv"}`` as ``init_ssm_state`` lays them out)."""
    heads = params["A_log"].shape[0]
    width = params["norm"]["scale"].shape[0]
    taps = params["conv"]["kernel"].shape[0]
    if state is None:
        state = init_ssm_state(h.shape[0], heads, width // heads, groups, state_size, taps, h.dtype)
    z, x, bmat, cmat, dt, conv_state = ssm_inputs(params, h, state["ssm_conv"], groups)
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    s0 = state["ssm_state"].astype(jnp.float32)
    if h.shape[1] == 1:
        y, s1 = ssm_recurrent_step(s0, x[:, 0], dt[:, 0], a, bmat[:, 0], cmat[:, 0])
        y = y[:, None]
    else:
        y, s1 = ssd_chunked(s0, x, dt, a, bmat, cmat, chunk)
    return ssm_output(params, h, y, x, z, groups, epsilon), {"ssm_state": s1, "ssm_conv": conv_state}
