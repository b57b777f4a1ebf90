"""Position-wise feed-forward network.

Counterpart of the reference's ``point_wise_feed_forward_network``
(``point_ffn.py:3-7``): Dense(dff, act) -> Dense(d_model), relu by default.
Two MXU matmuls with the activation fused between them by XLA. The ``dff``
axis is the tensor-parallel shard axis (column-parallel first matmul,
row-parallel second).

Gated variants (Shazeer 2020, "GLU Variants Improve Transformer"):
``swiglu``/``geglu``/``reglu`` add a third (gate) projection —
``act(x W_gate) * (x W_in) W_out`` — the FFN used by most modern LLMs.
Three matmuls instead of two; all still column/row-parallel on ``dff``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from transformer_tpu.ops.nn import Params, dense_apply, dense_init

_ACTIVATIONS = {
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}

# Gated variants: activation applied to the GATE branch.
_GATED_ACTIVATIONS = {
    "swiglu": jax.nn.silu,
    "geglu": jax.nn.gelu,
    "reglu": jax.nn.relu,
}

# Public name list: config validation derives from this; the CLI keeps a
# jax-import-free literal copy pinned to it by tests/test_flags.py.
FFN_ACTIVATIONS = tuple(sorted({**_ACTIVATIONS, **_GATED_ACTIVATIONS}))


def is_gated(activation: str) -> bool:
    return activation in _GATED_ACTIVATIONS


def ffn_init(
    key: jax.Array,
    d_model: int,
    dff: int,
    param_dtype=jnp.float32,
    activation: str = "relu",
    use_bias: bool = True,
) -> Params:
    # Ungated configs split exactly as before the gated variants existed, so
    # seeded inits stay byte-identical regardless of JAX's split semantics.
    k1, k2 = jax.random.split(key)
    params = {
        "in": dense_init(k1, d_model, dff, param_dtype, use_bias),
        "out": dense_init(k2, dff, d_model, param_dtype, use_bias),
    }
    if is_gated(activation):
        params["gate"] = dense_init(
            jax.random.fold_in(key, 2), d_model, dff, param_dtype, use_bias
        )
    return params


def ffn_apply(params: Params, x: jax.Array, activation: str = "relu") -> jax.Array:
    if is_gated(activation):
        act = _GATED_ACTIVATIONS[activation]
        h = act(dense_apply(params["gate"], x)) * dense_apply(params["in"], x)
        return dense_apply(params["out"], h)
    act = _ACTIVATIONS[activation]
    h = act(dense_apply(params["in"], x))
    return dense_apply(params["out"], h)


# ---------------------------------------------------------------------------
# Fused residual+LN+FFN decode kernel (Flash Multi-Head FFN shape).
#
# The XLA decode path runs the FFN sublayer as LN -> matmul -> activation ->
# matmul -> residual(+LN), each stage writing its result to HBM — including
# the (M, dff) intermediate, the widest tensor in the layer. For decode M is
# tiny (num_slots * S_q rows), so every stage is bandwidth-bound and the
# round trips dominate. This kernel walks the dff axis in tiles: each grid
# step loads one (d, bdff) column slab of W_in (plus the gate slab when the
# activation is gated), produces its (M, bdff) slice of the intermediate IN
# VMEM, multiplies into the (bdff, d) row slab of W_out, and accumulates
# into an (M, d) fp32 scratch. The dff-wide intermediate never exists in
# HBM; weight traffic is the unavoidable one pass over W_in/W_gate/W_out.
#
# Numerics track the XLA stage chain: LN statistics in fp32 exactly as
# ``layernorm_apply``; both matmuls accumulate fp32 and cast to the compute
# dtype like ``dense_apply``'s bf16 matmuls; only the second matmul's
# dff-contraction ORDER differs (tile partial sums vs one reduction), a
# low-bit fp32 effect that the cast to bf16 usually rounds away. MoE layers
# keep the XLA path (dispatch is data-dependent; fusing it is its own
# kernel) — ``models/paged_decode.py`` routes per layer.
# ---------------------------------------------------------------------------


def _ffn_tile(dff: int, requested: int = 512) -> int:
    """Largest divisor of ``dff`` at or below ``requested`` that is a legal
    TPU lane tile (a multiple of 128, or the full axis)."""
    for t in range(min(requested, dff), 0, -1):
        if dff % t == 0 and (t % 128 == 0 or t == dff):
            return t
    return dff


def _fused_kernel(
    x_ref,       # (M, d) sublayer input
    w_in_ref,    # (d, bdff) column slab of W_in
    b_in_ref,    # (1, bdff)
    *rest,       # [w_gate_ref, b_gate_ref,] w_out_ref, b_out_ref,
                 # ln_scale_ref, ln_bias_ref, out_ref, h_scr, acc_scr
    activation: str,
    pre_ln: bool,
    epsilon: float,
):
    if is_gated(activation):
        w_gate_ref, b_gate_ref = rest[0], rest[1]
        rest = rest[2:]
    else:
        w_gate_ref = b_gate_ref = None
    w_out_ref, b_out_ref, ln_scale_ref, ln_bias_ref, out_ref, h_scr, acc_scr = rest
    j = pl.program_id(0)
    dtype = x_ref.dtype

    def _ln(t):
        # layernorm_apply verbatim: fp32 stats, affine in fp32, cast back.
        t32 = t.astype(jnp.float32)
        mean = jnp.mean(t32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(t32 - mean), axis=-1, keepdims=True)
        normed = (t32 - mean) * jax.lax.rsqrt(var + epsilon)
        out = normed * ln_scale_ref[0].astype(jnp.float32) + ln_bias_ref[
            0
        ].astype(jnp.float32)
        return out.astype(dtype)

    @pl.when(j == 0)
    def _init():
        # Pre-LN feeds LN(x) to the FFN; post-LN feeds x itself (the LN in
        # that scheme wraps the residual sum at the end).
        h_scr[...] = _ln(x_ref[...]) if pre_ln else x_ref[...]
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _dense(w_ref, b_ref):
        # dense_apply's bf16 matmul accumulates fp32 on the MXU; mirror it.
        z = jax.lax.dot_general(
            h_scr[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return z.astype(dtype) + b_ref[0]

    if is_gated(activation):
        t = _GATED_ACTIVATIONS[activation](_dense(w_gate_ref, b_gate_ref)) * _dense(
            w_in_ref, b_in_ref
        )
    else:
        t = _ACTIVATIONS[activation](_dense(w_in_ref, b_in_ref))
    acc_scr[...] += jax.lax.dot_general(
        t, w_out_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == pl.num_programs(0) - 1)
    def _finalize():
        y = acc_scr[...].astype(dtype) + b_out_ref[0]
        res = x_ref[...] + y
        out_ref[...] = res if pre_ln else _ln(res)


def fused_ln_ffn(
    ln_params: Params,
    ffn_params: Params,
    x: jax.Array,
    *,
    activation: str = "relu",
    norm_scheme: str = "pre",
    epsilon: float = 1e-6,
    block_dff: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """The whole FFN sublayer — residual, LayerNorm, and both matmuls — as
    one Pallas kernel, dff tiled so the wide intermediate stays in VMEM.

    Computes ``x + ffn(LN(x))`` (pre-LN) or ``LN(x + ffn(x))`` (post-LN)
    for deterministic decode (dropout is identity there). ``x`` is
    (..., d_model); leading axes fold into rows.
    """
    if norm_scheme not in ("pre", "post"):
        raise ValueError(f"unknown norm_scheme {norm_scheme!r}")
    from transformer_tpu.kernels.flash_attention import _compiler_params

    lead, d = x.shape[:-1], x.shape[-1]
    m = 1
    for a in lead:
        m *= a
    xf = x.reshape(m, d)
    dff = ffn_params["in"]["kernel"].shape[1]
    bdff = _ffn_tile(dff, block_dff)
    dtype = x.dtype
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def _cast2(t):
        return t.astype(dtype).reshape(1, -1) if t.ndim == 1 else t.astype(dtype)

    inputs = [
        xf,
        ffn_params["in"]["kernel"].astype(dtype),
        _cast2(ffn_params["in"]["bias"]),
    ]
    in_specs = [
        pl.BlockSpec((m, d), lambda j: (0, 0)),
        pl.BlockSpec((d, bdff), lambda j: (0, j)),
        pl.BlockSpec((1, bdff), lambda j: (0, j)),
    ]
    if is_gated(activation):
        inputs += [
            ffn_params["gate"]["kernel"].astype(dtype),
            _cast2(ffn_params["gate"]["bias"]),
        ]
        in_specs += [
            pl.BlockSpec((d, bdff), lambda j: (0, j)),
            pl.BlockSpec((1, bdff), lambda j: (0, j)),
        ]
    inputs += [
        ffn_params["out"]["kernel"].astype(dtype),
        _cast2(ffn_params["out"]["bias"]),
        _cast2(ln_params["scale"]),
        _cast2(ln_params["bias"]),
    ]
    in_specs += [
        pl.BlockSpec((bdff, d), lambda j: (j, 0)),
        pl.BlockSpec((1, d), lambda j: (0, 0)),
        pl.BlockSpec((1, d), lambda j: (0, 0)),
        pl.BlockSpec((1, d), lambda j: (0, 0)),
    ]

    kernel = functools.partial(
        _fused_kernel,
        activation=activation,
        pre_ln=norm_scheme == "pre",
        epsilon=epsilon,
    )
    out = pl.pallas_call(
        kernel,
        grid=(dff // bdff,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, d), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), dtype),
        scratch_shapes=[
            pltpu.VMEM((m, d), dtype),        # FFN input rows (LN'd or raw)
            pltpu.VMEM((m, d), jnp.float32),  # fp32 output accumulator
        ],
        compiler_params=_compiler_params(("arbitrary",)),
        interpret=bool(interpret),
        name="fused_ln_ffn",
    )(*inputs)
    return out.reshape(*lead, d)
