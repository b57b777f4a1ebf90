"""Plain reference: LiquidAI's LFM2-MoE block as its ``config.json`` describes
it (``model_type`` ``lfm2_moe``; https://huggingface.co/LiquidAI/LFM2-8B-A1B).

``x`` is the residual stream. No bias anywhere. Layer ``l`` is of kind
``layer_pattern[l % period]``: a kind with ``conv_kernel`` is a gated short
convolution, any other full causal attention.

- **Short convolution** (``conv_kernel`` 3): ``h = RMSNorm(x)``;
  ``(B, C, u) = split3(h W_in)``; ``z = B * u``;
  ``c_t = w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t`` per channel (``z`` is 0 before
  position 0: a causal depthwise convolution, written as three shifted
  products); ``x <- x + (C * c) W_out``.
- **Attention**: ``h = RMSNorm(x)``; ``q = h Wq``, ``k = h Wk``, ``v = h Wv``;
  ``q`` and ``k`` RMS-normalised over each head's channels (their own scales)
  BEFORE the rotation; rotary, "rotate half" pairing (i, i + head/2), base
  ``rope_base`` over the whole head; ``a_i = softmax_j(q_i . k_j / sqrt(head))
  v_j`` over ``j <= i``; query head ``n`` reads KV head ``n // (heads /
  kv_heads)``; ``x <- x + concat(a) Wo``.
- **Feed-forward**, the leading dense layers: ``x <- x + W2(silu(W1 h') * W3 h')``,
  ``h' = RMSNorm(x)``. The others: ``s = sigmoid(h' Wr)`` over all experts in
  float32; the ``top_k`` experts with the largest ``s + b`` (``b``: the
  router's selection bias, in the choice ONLY); ``w_e = s_e / (sum of the
  chosen s + moe_renorm_epsilon)`` times ``moe_routed_scale``; each expert a
  SwiGLU; ``x <- x + sum_e w_e E_e(h')``. No shared expert. Only the experts
  held here are in the parameters and in the sum (here: all of them).
- Final RMSNorm; the head is the embedding's transpose.

Full forward pass over the whole sequence in ``jax.numpy`` float32 at the
highest matmul precision: no cache, no state, no kernels, no grouping. It
imports nothing from the program and only reads the program's parameter tree
and the ``model`` group of the configuration. Weights stay in their served
dtype and are upcast a mixer, a dense FFN or ONE EXPERT at a time (the
experts are a plain loop), so the float32 copy never holds more than one.

Assumed, because the config does not say (the configuration file gives the
reason for each): head size 64; sigmoid scores, the bias in the choice only,
the 1e-6; q/k normalisation before the rotation; the gate order ``B * u``
before the convolution and ``C *`` after it, ``in_proj`` split as B, C, u;
tied embedding and head. Departure, to compute what the program states it
computes: the embedding is multiplied by sqrt(d_model) as the repo's
prologue does (its table is initialised d_model**-0.5 smaller).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f(x):
    return jnp.asarray(x, F32)


def rms_norm(p, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f(p["scale"])


def kind_of(cfg: dict, layer: int) -> dict:
    name = cfg["layer_pattern"][layer % len(cfg["layer_pattern"])]
    return next(k for k in cfg["attention_kinds"] if k["name"] == name)


def rotary(x, base: float):
    """x: (B, S, H, D); rotate pair (i, i + D/2) of position s by s * base**(-2i/D)."""
    half = x.shape[-1] // 2
    f = base ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * f[None, :]
    cos, sin = _f(np.cos(ang))[None, :, None, :], _f(np.sin(ang))[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, h, base: float, eps: float):
    q = jnp.einsum("bsm,mhd->bshd", h, _f(p["query"]["kernel"]))
    k = jnp.einsum("bsm,mhd->bshd", h, _f(p["key"]["kernel"]))
    v = jnp.einsum("bsm,mhd->bshd", h, _f(p["value"]["kernel"]))
    q, k = rms_norm(p["q_norm"], q, eps), rms_norm(p["k_norm"], k, eps)  # over a head's channels
    q, k = rotary(q, base), rotary(k, base)
    group = q.shape[2] // k.shape[2]  # each KV head serves `group` query heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(_f(q.shape[-1]))
    n = h.shape[1]
    seen = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    scores = jnp.where(seen[None, None], scores, -1e9)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqhd,hdm->bqm", a, _f(p["out"]["kernel"]))


def short_conv(p, h):
    """The gated short convolution over a whole sequence (B, S, M) from position 0."""
    b, c, u = jnp.split(h @ _f(p["in"]["kernel"]), 3, axis=-1)
    z = b * u
    w = _f(p["conv"]["kernel"])  # (L, M), oldest tap first
    taps, n = w.shape[0], h.shape[1]
    conv = jnp.zeros_like(z)
    for j in range(taps):  # tap j weighs z as it was `taps - 1 - j` positions ago
        back = taps - 1 - j
        conv = conv + w[j] * jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :n]
    return (c * conv) @ _f(p["out"]["kernel"])


def swiglu(w_gate, w_in, w_out, h):
    return (jax.nn.silu(h @ _f(w_gate)) * (h @ _f(w_in))) @ _f(w_out)


def route(p, h, top_k: int, scale: float, eps: float):
    """(chosen expert ids (T, top_k), their weights (T, top_k))."""
    s = jax.nn.sigmoid(h @ _f(p["router"]["kernel"]))
    _, chosen = jax.lax.top_k(s + _f(p["router"]["bias"]), top_k)  # the bias chooses ...
    picked = jnp.take_along_axis(s, chosen, axis=-1)  # ... and weighs nothing
    return chosen, scale * picked / (picked.sum(-1, keepdims=True) + eps)


def experts(p, h, top_k: int, offset: int, scale: float, eps: float):
    """The routed experts held here, for rows ``h`` (T, M): a plain loop."""
    chosen, weight = route(p, h, top_k, scale, eps)

    def add_expert(e, y):  # one expert at a time: one float32 copy at a time
        w = jnp.where(chosen == e + offset, weight, 0.0).sum(-1, keepdims=True)
        one = [jax.lax.dynamic_index_in_dim(p[n]["kernel"], e, keepdims=False) for n in ("gate", "in", "out")]
        return y + w * swiglu(*one, h)

    return jax.lax.fori_loop(0, p["in"]["kernel"].shape[0], add_expert, jnp.zeros_like(h))


@partial(jax.jit, static_argnames=("eps", "base"))
def _mixer_sublayer(lp, x, eps, base):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(lp["ln1"], x, eps)
        return x + (short_conv(lp["conv"], h) if "conv" in lp else attention(lp["self_mha"], h, base, eps))


@partial(jax.jit, static_argnames=("eps", "top_k", "offset", "scale", "renorm_eps"))
def _ffn_sublayer(lp, x, eps, top_k, offset, scale, renorm_eps):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(lp["ln_ffn"], x, eps)
        if "moe" in lp:
            y = experts(lp["moe"], h.reshape(-1, h.shape[-1]), top_k, offset, scale, renorm_eps).reshape(h.shape)
        else:
            f = lp["ffn"]
            y = swiglu(f["gate"]["kernel"], f["in"]["kernel"], f["out"]["kernel"], h)
        return x + y


@partial(jax.jit, static_argnames=("d",))
def _embed(table, ids, d):
    return _f(table[ids]) * jnp.sqrt(_f(d))


@partial(jax.jit, static_argnames=("eps", "first"))
def _head(final_ln, table, x, eps, first):
    with jax.default_matmul_precision("highest"):
        return rms_norm(final_ln, x[:, first:], eps) @ _f(table).T


def logits(params, ids, cfg: dict, first: int = 0):
    """(B, S) ids -> float32 logits (B, S - first, V) for positions first.. ."""
    dec = params["decoder"]
    eps = cfg["layernorm_epsilon"]
    rows = []
    for row in np.asarray(ids):  # one sequence at a time: its scores alone are (heads, S, S) float32
        x = _embed(dec["embedding"]["table"], row[None], cfg["d_model"])
        for l, lp in enumerate(dec["layers"]):
            x = _mixer_sublayer(lp, x, eps, float(kind_of(cfg, l).get("rope_base", 10000.0)))
            x = _ffn_sublayer(lp, x, eps, cfg["moe_top_k"], cfg.get("moe_expert_offset", 0),
                              cfg.get("moe_routed_scale", 1.0), cfg.get("moe_renorm_epsilon", 1e-6))
        rows.append(_head(dec["final_ln"], dec["embedding"]["table"], x, eps, first))
    return jnp.concatenate(rows)
