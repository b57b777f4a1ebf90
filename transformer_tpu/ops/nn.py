"""Primitive neural-net building blocks: dense, embedding, layernorm, dropout.

Functional style: ``*_init(key, ...) -> params`` (a dict pytree of jnp arrays)
and ``*_apply(params, x, ...) -> y``. Parameters live in ``param_dtype``
(fp32 by default); compute casts to the caller's ``dtype`` (bf16 on TPU so the
MXU runs at full rate).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Params = dict


def glorot_uniform(key: jax.Array, shape: tuple[int, ...], dtype, fan_in: int, fan_out: int):
    """Glorot/Xavier uniform — the initializer the reference inherits from
    ``tf.keras.layers.Dense`` defaults (reference ``Attention.py:46-50``,
    ``point_ffn.py:4-6``)."""
    limit = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, shape, dtype=dtype, minval=-limit, maxval=limit)


def dense_init(
    key: jax.Array, d_in: int, d_out: int, dtype=jnp.float32, use_bias: bool = True
) -> Params:
    params = {"kernel": glorot_uniform(key, (d_in, d_out), dtype, d_in, d_out)}
    if use_bias:
        params["bias"] = jnp.zeros((d_out,), dtype=dtype)
    return params


def dense_apply(params: Params, x: jax.Array, dtype=None) -> jax.Array:
    """``x @ kernel`` plus the bias where the layer has one."""
    dtype = dtype or x.dtype
    y = jnp.matmul(x.astype(dtype), params["kernel"].astype(dtype))
    return y + params["bias"].astype(dtype) if "bias" in params else y


def embedding_init(key: jax.Array, vocab_size: int, d_model: int, dtype=jnp.float32) -> Params:
    # Normal(0, 1) scaled down — standard for transformer embeddings that are
    # multiplied by sqrt(d_model) in the stack prologue (reference ``Encoder.py:52``).
    table = jax.random.normal(key, (vocab_size, d_model), dtype=dtype) * (d_model**-0.5)
    return {"table": table}


def embedding_lookup(params: Params, ids: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return jnp.take(params["table"].astype(dtype), ids, axis=0)


def embedding_attend(params: Params, x: jax.Array) -> jax.Array:
    """Tied output projection: logits = x @ table.T (BASELINE.json configs[3])."""
    table = params["table"].astype(x.dtype)
    return jnp.matmul(x, table.T)


def layernorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype), "bias": jnp.zeros((d,), dtype=dtype)}


def layernorm_apply(params: Params, x: jax.Array, epsilon: float = 1e-6) -> jax.Array:
    """LayerNorm with the reference's epsilon=1e-6 (``Encoder.py:13-14``).

    Statistics are computed in fp32 regardless of the compute dtype — bf16
    variance is numerically unsafe — then the result is cast back.
    """
    orig_dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    normed = (x32 - mean) * jax.lax.rsqrt(var + epsilon)
    out = normed * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return out.astype(orig_dtype)


def norm_init(d: int, dtype=jnp.float32, kind: str = "layernorm") -> Params:
    """Parameters of ``norm_apply``: RMSNorm has a ``scale`` and no ``bias``."""
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype=dtype)}
    return layernorm_init(d, dtype)


def norm_apply(
    params: Params, x: jax.Array, epsilon: float = 1e-6, kind: str = "layernorm"
) -> jax.Array:
    """The block's normalisation: LayerNorm, or RMSNorm (Zhang & Sennrich
    2019: ``x / sqrt(mean(x^2) + eps) * scale``, no mean, no bias), with the
    statistics in fp32 either way."""
    if kind == "layernorm":
        return layernorm_apply(params, x, epsilon)
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + epsilon)
    return (x32 * inv * params["scale"].astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.checkpoint, static_argnums=(2,))
def _drop(key: jax.Array, x: jax.Array, keep: float) -> jax.Array:
    """One dropout site: each element kept with probability ``keep`` and
    scaled by ``1 / keep``.

    The mask's bits come from the device's generator (XLA ``RngBitGenerator``),
    whose four-word state is hashed out of ``key``: the hash runs over four
    words, not over the activation. The compare is on integers, at 32 bits of
    resolution. The shape is folded into the state (one word, one hash)
    because the generator counts from its state: one key at two shapes would
    otherwise share the stream's start. The stream is XLA's: the same within
    one compiled program, not across XLA versions, backends or shardings.

    Under ``jax.checkpoint`` the backward pass keeps the key and draws the
    mask again: the generator is an instruction of its own that writes its
    words once for every reader, so a second draw costs two passes over the
    words, where a kept mask costs a byte an element for the whole of the
    backward pass (1 GB over the 32 sites of a 256 x 128 x 1024 step, which
    the Transformer-big cell's widest step has not got: PERF.md section 6,
    PR 29).
    """
    shape_word = functools.reduce(lambda h, n: (h * 1000003 + n) % 2**32, x.shape, x.ndim)
    state = jax.random.bits(jax.random.fold_in(key, shape_word), (4,), jnp.uint32)
    _, bits = jax.lax.rng_bit_generator(state, x.shape, dtype=jnp.uint32)
    mask = bits < jnp.uint32(min(round(keep * 2**32), 2**32 - 1))
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


def dropout(key: jax.Array | None, x: jax.Array, rate: float, deterministic: bool) -> jax.Array:
    """Inverted dropout. ``deterministic=True`` (eval) or rate==0 is identity —
    and both must be decided at trace time (static), never via data-dependent
    control flow inside jit."""
    if deterministic or rate == 0.0:
        return x
    if key is None:
        raise ValueError("dropout in training mode requires an rng key")
    return _drop(key, x, 1.0 - rate)


def remat_layer(fn, cfg):
    """Wrap a per-layer apply in ``jax.checkpoint`` under the configured
    policy (``ModelConfig.remat_policy``): "full" recomputes everything;
    "dots" saves matmul outputs and recomputes only the elementwise/
    bandwidth-bound ops (``dots_with_no_batch_dims_saveable``) — the same
    gradients either way, different memory/recompute point."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)
