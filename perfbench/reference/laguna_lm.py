"""Plain reference: poolside's Laguna block as its ``config.json`` describes it
(``model_type`` ``laguna``; https://huggingface.co/poolside/Laguna-S-2.1), as
one chip's share of an expert-parallel deployment.

``x`` is the residual stream. Layer ``l`` is of kind ``layer_pattern[l % period]``
(full at ``l % 4 == 0``, else sliding), with that kind's query heads over the
model's KV heads; no bias anywhere.

- ``h = RMSNorm(x)``; ``q = h Wq``, ``k = h Wk``, ``v = h Wv``.
- Rotary, "rotate half" pairing (i, i + rot/2) over the first ``rot`` channels
  of a head (``rotary_share``; the rest pass). Sliding: base 10,000 over the
  whole head. Full: base 500,000 over the first half, YaRN: the inverse
  frequencies ``f`` blended between ``f`` and ``f / factor`` by a linear ramp
  over the pair index, from the pair that turns ``beta_fast`` times in the
  original context (rounded down) to the one that turns ``beta_slow`` times
  (rounded up); cosine and sine times ``rope_attention_factor``.
- ``a_i = softmax_j(q_i . k_j / sqrt(head)) v_j`` over ``j <= i`` and, on a
  window layer, ``j > i - window``; query head ``n`` reads KV head
  ``n // (heads / kv_heads)``.
- Per-head gate: ``g = sigmoid(h Wg)``, head ``n``'s output times ``g_n``;
  ``x <- x + concat(a) Wo``.
- The leading dense layers: ``x <- x + W2(silu(W1 h') * W3 h')``, ``h' = RMSNorm(x)``.
- The others: ``p = softmax(h' Wr)`` over ALL experts in float32; the ``top_k``
  largest, renormalised to sum 1, times ``moe_routed_scale``, weigh the
  experts' OUTPUTS; each expert and the shared expert a SwiGLU;
  ``x <- x + sum_e w_e E_e(h') + S(h')``. Only the experts HELD HERE
  (``moe_expert_offset .. + moe_experts_held``) are in the parameters and in
  the sum: a pick that falls on another chip's expert adds nothing.
- Final RMSNorm, untied head.

Full forward pass over the whole sequence in ``jax.numpy`` float32 at the
highest matmul precision: no cache, no kernels, no grouping. It imports
nothing from the program and only reads the program's parameter tree and the
``model`` group of the configuration. Weights stay in their served dtype and
are upcast one layer's attention, one dense FFN or ONE EXPERT at a time (the
experts are a plain loop over the held ones), so the float32 copy never holds
more than 40 MB of an expert layer.

Assumed, because the config does not say (the configuration file gives the
reason for each): softmax router scores; an ungated shared expert; the gate
is the head-wise sigmoid gate on the attention output computed from the
layer's normalised input; no normalisation of q and k; pre-norm residuals.
Departure, to compute what the program states it computes: the embedding is
multiplied by sqrt(d_model) as the repo's prologue does (its table is
initialised d_model**-0.5 smaller).
"""

from __future__ import annotations

import math
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


def inverse_frequencies(kind: dict, head: int) -> np.ndarray:
    """(rot / 2,) rotary inverse frequencies of one kind, YaRN applied."""
    rot = int(head * kind.get("rotary_share", 1.0))
    i = np.arange(rot // 2, dtype=np.float64)
    base = kind.get("rope_base", 10000.0)
    f = base ** (-2.0 * i / rot)
    factor = kind.get("yarn_factor", 0.0)
    if not factor:
        return f
    length = kind["yarn_original_max_position"]

    def pair_that_turns(times):  # ... `times` times within `length` positions
        return rot * math.log(length / (times * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(pair_that_turns(kind.get("yarn_beta_fast", 32.0))), 0)
    hi = min(math.ceil(pair_that_turns(kind.get("yarn_beta_slow", 1.0))), rot - 1)
    keep = 1.0 - np.clip((i - lo) / (hi - lo), 0.0, 1.0)  # 1: f as it is; 0: f / factor
    return f * keep + f / factor * (1.0 - keep)


def rotary(x, kind: dict):
    """x: (B, S, H, D); rotate pair (i, i + rot/2) of position s by s * f_i."""
    f = inverse_frequencies(kind, x.shape[-1])
    half = f.shape[0]
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * f[None, :]
    scale = kind.get("rope_attention_factor", 1.0)
    cos = _f(np.cos(ang) * scale)[None, :, None, :]
    sin = _f(np.sin(ang) * scale)[None, :, None, :]
    a, b, rest = x[..., :half], x[..., half : 2 * half], x[..., 2 * half :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def attention(p, h, kind: dict):
    q = jnp.einsum("bsm,mhd->bshd", h, _f(p["query"]["kernel"]))
    k = jnp.einsum("bsm,mhd->bshd", h, _f(p["key"]["kernel"]))
    v = jnp.einsum("bsm,mhd->bshd", h, _f(p["value"]["kernel"]))
    q, k = rotary(q, kind), rotary(k, kind)
    group = q.shape[2] // k.shape[2]  # each KV head serves `group` query heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(_f(q.shape[-1]))
    n = h.shape[1]
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    seen = j <= i
    if kind.get("window", 0):
        seen = seen & (j > i - kind["window"])
    scores = jnp.where(seen[None, None], scores, -1e9)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    gate = jax.nn.sigmoid(jnp.einsum("bsm,mh->bsh", h, _f(p["gate"]["kernel"])))
    return jnp.einsum("bqhd,hdm->bqm", a * gate[..., None], _f(p["out"]["kernel"]))


def swiglu(w_gate, w_in, w_out, h):
    return (jax.nn.silu(h @ _f(w_gate)) * (h @ _f(w_in))) @ _f(w_out)


def experts(p, h, top_k: int, offset: int, scale: float):
    """The routed experts held here plus the shared one, for rows ``h`` (T, M)."""
    probs = jax.nn.softmax(h @ _f(p["router"]["kernel"]), axis=-1)  # over all experts
    top, chosen = jax.lax.top_k(probs, top_k)
    weight = scale * top / top.sum(-1, keepdims=True)  # (T, top_k)

    def add_expert(e, y):  # one expert at a time: one float32 copy at a time
        w = jnp.where(chosen == e + offset, weight, 0.0).sum(-1, keepdims=True)
        one = [jax.lax.dynamic_index_in_dim(p[n]["kernel"], e, keepdims=False) for n in ("gate", "in", "out")]
        return y + w * swiglu(*one, h)

    held = p["in"]["kernel"].shape[0]
    y = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(h))
    s = p["shared"]
    return y + swiglu(s["gate"]["kernel"], s["in"]["kernel"], s["out"]["kernel"], h)


@partial(jax.jit, static_argnames=("eps", "kind"))
def _attention_sublayer(lp, x, eps, kind):
    with jax.default_matmul_precision("highest"):
        return x + attention(lp["self_mha"], rms_norm(lp["ln1"], x, eps), dict(kind))


@partial(jax.jit, static_argnames=("eps", "top_k", "offset", "scale"))
def _ffn_sublayer(lp, x, eps, top_k, offset, scale):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(lp["ln_ffn"], x, eps)
        if "moe" in lp:
            y = experts(lp["moe"], h.reshape(-1, h.shape[-1]), top_k, offset, scale).reshape(h.shape)
        else:
            f = lp["ffn"]
            y = swiglu(f["gate"]["kernel"], f["in"]["kernel"], f["out"]["kernel"], h)
        return x + y


@partial(jax.jit, static_argnames=("d",))
def _embed(table, ids, d):
    return _f(table[ids]) * jnp.sqrt(_f(d))


@partial(jax.jit, static_argnames=("eps", "first"))
def _head(final_ln, kernel, x, eps, first):
    with jax.default_matmul_precision("highest"):
        return rms_norm(final_ln, x[:, first:], eps) @ _f(kernel)


def logits(params, ids, cfg: dict, first: int = 0):
    """(B, S) ids -> float32 logits (B, S - first, V) for positions first.. ."""
    dec = params["decoder"]
    eps = cfg["layernorm_epsilon"]
    rows = []
    for row in np.asarray(ids):  # one sequence at a time: its scores alone are (heads, S, S) float32
        x = _embed(dec["embedding"]["table"], row[None], cfg["d_model"])
        for l, lp in enumerate(dec["layers"]):
            kind = tuple(sorted(kind_of(cfg, l).items()))  # hashable: a static argument
            x = _attention_sublayer(lp, x, eps, kind)
            x = _ffn_sublayer(lp, x, eps, cfg["moe_top_k"], cfg.get("moe_expert_offset", 0),
                              cfg.get("moe_routed_scale", 1.0))
        rows.append(_head(dec["final_ln"], params["final"]["kernel"], x, eps, first))
    return jnp.concatenate(rows)
