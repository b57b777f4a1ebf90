"""Plain reference: a pre-LN decoder-only language model with grouped-query
attention, rotary positions, a GELU (tanh form) feed-forward with biases,
LayerNorm with bias, and an output projection tied to the embedding: the
block StarCoder 2 publishes (arXiv:2402.19173, architecture table; the
``bigcode/starcoder2-3b`` config.json).

Full forward pass over the whole sequence in ``jax.numpy`` float32 at the
highest matmul precision: no cache, no kernels, no batching tricks. It
imports nothing from the program and only reads the program's parameter tree.
Weights stay in their served dtype and are upcast one layer at a time (each
layer is one small jitted call), so the float32 copy never holds more than a
layer.

Departures from the published model, each made to compute what the program
states it computes (none changes an operation count):
- rotary base 10,000 where the model card has 999,999.44: ``apply_rope``'s
  base is not reachable from ``ModelConfig`` (same arithmetic, other angles);
- the embedding is multiplied by sqrt(d_model) as the repo's prologue does
  (its table is initialised d_model**-0.5 smaller, so activations have the
  published scale); StarCoder 2 does not scale;
- no sliding window: at the cells' lengths (at most 2,048 positions) a window
  of 4,096 never cuts anything;
- rotary pairs are (i, i + head_dim/2), the half-split layout, as in the
  Hugging Face implementation of the model.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROPE_BASE = 10000.0


def _f(x):
    return jnp.asarray(x, F32)


def layer_norm(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f(p["scale"]) + _f(p["bias"])


def rope(x):
    """x: (B, S, H, D); rotate pair (i, i + D/2) of position s by s * base**(-i / (D/2))."""
    half = x.shape[-1] // 2
    inv = jnp.power(ROPE_BASE, -jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, x):
    q = jnp.einsum("bsm,mhd->bshd", x, _f(p["query"]["kernel"])) + _f(p["query"]["bias"])
    k = jnp.einsum("bsm,mhd->bshd", x, _f(p["key"]["kernel"])) + _f(p["key"]["bias"])
    v = jnp.einsum("bsm,mhd->bshd", x, _f(p["value"]["kernel"])) + _f(p["value"]["bias"])
    q, k = rope(q), rope(k)
    group = q.shape[2] // k.shape[2]  # each KV head serves `group` query heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(_f(q.shape[-1]))
    n = x.shape[1]
    scores = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None, None], scores, -1e9)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqhd,hdm->bqm", ctx, _f(p["out"]["kernel"])) + _f(p["out"]["bias"])


def ffn(p, x):
    h = jax.nn.gelu(x @ _f(p["in"]["kernel"]) + _f(p["in"]["bias"]), approximate=True)
    return h @ _f(p["out"]["kernel"]) + _f(p["out"]["bias"])


@partial(jax.jit, static_argnames=("eps",))
def _layer(lp, x, eps):
    with jax.default_matmul_precision("highest"):
        x = x + attention(lp["self_mha"], layer_norm(lp["ln1"], x, eps))
        return x + ffn(lp["ffn"], layer_norm(lp["ln_ffn"], x, eps))


@partial(jax.jit, static_argnames=("d",))
def _embed(table, ids, d):
    return _f(table)[ids] * jnp.sqrt(_f(d))


@partial(jax.jit, static_argnames=("eps", "first"))
def _head(final_ln, table, x, eps, first):
    with jax.default_matmul_precision("highest"):
        return layer_norm(final_ln, x[:, first:], eps) @ _f(table).T


def logits(params, ids, cfg: dict, first: int = 0):
    """(B, S) ids -> float32 logits (B, S - first, V) for positions first.. ."""
    dec = params["decoder"]
    x = _embed(dec["embedding"]["table"], ids, cfg["d_model"])
    for lp in dec["layers"]:
        x = _layer(lp, x, cfg["layernorm_epsilon"])
    return _head(dec["final_ln"], dec["embedding"]["table"], x, cfg["layernorm_epsilon"], first)
