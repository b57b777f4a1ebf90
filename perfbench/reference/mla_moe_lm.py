"""Plain reference: a DeepSeek-V3-style block of latent attention (MLA) with a
compressed query and rotated shared keys, and group-limited sigmoid routing, as
SK Telecom's A.X K1 ``config.json`` describes it (``model_type`` ``axk1``;
https://huggingface.co/skt/A.X-K1; the layer is DeepSeek-V2's, arXiv:2405.04434,
with DeepSeek-V3's router, arXiv:2412.19437).

``x`` is the residual stream; no bias anywhere.

- **MLA**, unabsorbed, in every layer: ``h = RMSNorm(x)``; ``c_q =
  RMSNorm_q(h Wqa)``; ``[qn_h ; qr_h] = c_q Wqb_h``; ``[c ; kr] = h Wkva``,
  ``c = RMSNorm_r(c)``; ``qr_h`` and ``kr`` turned at their positions by rotary
  frequencies that YaRN blends (``yarn_inv_freq``); ``[kn_h ; v_h] = c
  Wkvb_h``; ``score_h(t, s) = (qn_h(t) . kn_h(s) + qr_h(t) . kr(s)) * (n +
  p)**-0.5 * m**2`` over ``s <= t`` (``score_factor``); softmax; ``x <- x +
  concat_h(sum_s p v_h(s)) Wo``. The scores a block of rows at a time.
- **Feed-forward**, the leading dense layer: SwiGLU, a slice of its width at a
  time. The others: ``s = sigmoid(h' Wr)`` over all experts in float32; the
  experts in groups of equal size, a group's score the sum of its two largest
  ``s``, the best ``moe_topk_groups`` groups kept; the top ``moe_top_k`` among
  their experts; weights = the chosen ``s`` over (their sum +
  ``moe_renorm_epsilon``), times ``moe_routed_scale``; each expert a SwiGLU;
  plus one shared expert every token takes. Only the experts held here (ids
  ``moe_expert_offset ..``) are in the parameters and in the sum.
- Final RMSNorm; an untied head over the vocabulary's slice.

Full forward pass over the whole sequence from position 0 in ``jax.numpy``
float32 at the highest matmul precision: no cache, no kernels, no batching (one
sequence at a time). It imports nothing from the program and only reads the
program's parameter tree and the ``model`` group of the configuration.

``alter`` (the controls of PERF.md section 6: the check must see each) changes
one part of the mathematics: ``no_score_factor`` (``m**2`` left out),
``no_key_rotation`` (``kr`` cached unturned), ``no_group_limit`` (the top-k over
all experts), ``group_max`` (a group scored by its largest ``s``, DeepSeek-V2's
rule), ``expert_dtype`` = a dtype name (the routed experts' weights, inputs and
hidden rows rounded to it: a precision below the configuration's),
``weights_dtype`` = a dtype name (every kernel, the embedding and the head
rounded to it: the whole model's weights in a precision below the
configuration's).

Departures, as the configuration file states them: the embedding is multiplied
by sqrt(d_model), as the repo's prologue does; a rotated pair is channel ``i``
with ``i + p/2`` (half-split), where DeepSeek's checkpoints interleave the pairs
(a fixed permutation of the rotated columns of ``Wqb`` and ``Wkva``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_ROWS = 512  # query rows whose scores are held at once
_FFN_SLICES = 4  # the dense layer's width in this many slices, one float32 copy at a time


def _f(x, low=None):
    """float32; a weight first rounded to the dtype ``low`` where given."""
    return jnp.asarray(x if low is None else jnp.asarray(x).astype(low), F32)


def rms_norm(scale, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f(scale)


def yarn_inv_freq(dim: int, base: float, factor: float, original: int, beta_fast: float, beta_slow: float) -> np.ndarray:
    """(dim / 2,) inverse frequencies ``base**(-2i / dim)``; with ``factor`` > 1,
    DeepSeek's ``yarn_find_correction_range`` and ``yarn_linear_ramp_mask``: a
    pair that turns more than ``beta_fast`` times in ``original`` positions keeps
    its frequency, one that turns fewer than ``beta_slow`` times has it divided
    by ``factor``, a linear ramp between."""
    half = dim // 2
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / max(high - low, 0.001), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def score_factor(factor: float, mscale_all_dim: float) -> float:
    """DeepSeek's ``softmax_scale *= yarn_get_mscale(factor, mscale_all_dim)**2``."""
    if factor <= 1 or not mscale_all_dim:
        return 1.0
    return (0.1 * mscale_all_dim * math.log(factor) + 1.0) ** 2


def rotate(x, inv_freq):
    """(B, S, [H,] D) turned at positions 0 .. S - 1, channel i with i + D/2."""
    n, half = x.shape[1], x.shape[-1] // 2
    angle = np.arange(n)[:, None] * inv_freq[None, :]  # float64 on the host
    shape = (1, n) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.asarray(np.cos(angle), F32).reshape(shape), jnp.asarray(np.sin(angle), F32).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mla(p, h, eps: float, kind: dict, alter: dict):
    """Latent attention over a whole sequence (B, S, M), keys and values
    expanded a head, nothing absorbed."""
    rank = p["kv_norm"]["scale"].shape[0]
    low = alter.get("weights_dtype")
    a = h @ _f(p["kv_a"]["kernel"], low)
    c, kr = rms_norm(p["kv_norm"]["scale"], a[..., :rank], eps), a[..., rank:]
    shared = kr.shape[-1]
    c_q = rms_norm(p["q_norm"]["scale"], h @ _f(p["q_a"]["kernel"], low), eps)
    q = jnp.einsum("bsr,rhd->bshd", c_q, _f(p["q_b"]["kernel"], low))
    nope = q.shape[-1] - shared
    inv = yarn_inv_freq(shared, kind.get("rope_base", 10000.0), kind.get("yarn_factor", 0.0),
                        kind.get("yarn_original_max_position", 0), kind.get("yarn_beta_fast", 32.0),
                        kind.get("yarn_beta_slow", 1.0))
    qn, qr = q[..., :nope], rotate(q[..., nope:], inv)
    if not alter.get("no_key_rotation"):
        kr = rotate(kr, inv)
    kv = jnp.einsum("bsr,rhd->bshd", c, _f(p["kv_b"]["kernel"], low))
    kn, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + shared) ** -0.5
    if not alter.get("no_score_factor"):
        scale *= score_factor(kind.get("yarn_factor", 0.0), kind.get("yarn_mscale_all_dim", 0.0))
    n = h.shape[1]
    out = []
    for r0 in range(0, n, _ROWS):
        scores = (jnp.einsum("bqhd,bkhd->bhqk", qn[:, r0 : r0 + _ROWS], kn)
                  + jnp.einsum("bqhd,bkd->bhqk", qr[:, r0 : r0 + _ROWS], kr)) * scale
        rows = r0 + jnp.arange(scores.shape[2])
        seen = jnp.arange(n)[None, :] <= rows[:, None]
        w = jax.nn.softmax(jnp.where(seen[None, None], scores, -1e9), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", w, v))
    return jnp.einsum("bqhd,hdm->bqm", jnp.concatenate(out, axis=1), _f(p["out"]["kernel"], low))


def swiglu(w_gate, w_in, w_out, h, low=None):
    return (jax.nn.silu(h @ _f(w_gate, low)) * (h @ _f(w_in, low))) @ _f(w_out, low)


def dense_swiglu(f, h, low=None):
    """The dense layer's SwiGLU, a slice of its width at a time."""
    width = f["in"]["kernel"].shape[1]
    step = width // _FFN_SLICES if width % _FFN_SLICES == 0 else width

    def add(i, y):
        cols = [jax.lax.dynamic_slice_in_dim(f[n]["kernel"], i * step, step, axis=1) for n in ("gate", "in")]
        rows = jax.lax.dynamic_slice_in_dim(f["out"]["kernel"], i * step, step, axis=0)
        return y + swiglu(*cols, rows, h, low)

    return jax.lax.fori_loop(0, width // step, add, jnp.zeros_like(h))


def route(p, h, top_k: int, scale: float, eps: float, groups: int, topk_groups: int, alter: dict | None = None):
    """(chosen expert ids (T, top_k), their weights (T, top_k))."""
    alter = alter or {}
    s = jax.nn.sigmoid(h @ _f(p["router"]["kernel"], alter.get("weights_dtype")))
    choice = s
    if groups > 1 and not alter.get("no_group_limit"):
        g = s.reshape(s.shape[0], groups, -1)
        group_score = g.max(-1) if alter.get("group_max") else jax.lax.top_k(g, 2)[0].sum(-1)
        _, best = jax.lax.top_k(group_score, topk_groups)
        kept = (best[:, :, None] == jnp.arange(groups)[None, None, :]).any(1)  # (T, groups)
        choice = jnp.where(kept[:, :, None], g, -jnp.inf).reshape(s.shape)
    _, chosen = jax.lax.top_k(choice, top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, scale * picked / (picked.sum(-1, keepdims=True) + eps)


def moe(p, h, top_k: int, offset: int, scale: float, eps: float, groups: int, topk_groups: int,
        alter: dict | None = None, shared: bool = True):
    """The routed experts held here, for rows ``h`` (T, M), as a plain loop,
    plus the shared expert (``shared=False``: the routed part alone)."""
    alter = alter or {}
    chosen, weight = route(p, h, top_k, scale, eps, groups, topk_groups, alter)
    low = alter.get("expert_dtype")
    weights_low = low or alter.get("weights_dtype")

    def rounded(t):
        return t if low is None else _f(jnp.asarray(t).astype(low))

    def add_expert(e, y):  # one expert at a time: one float32 copy at a time
        w = jnp.where(chosen == e + offset, weight, 0.0).sum(-1, keepdims=True)
        wg, wi, wo = (_f(jax.lax.dynamic_index_in_dim(p[n]["kernel"], e, keepdims=False), weights_low)
                      for n in ("gate", "in", "out"))
        x = rounded(h)
        return y + w * (rounded(jax.nn.silu(x @ wg) * (x @ wi)) @ wo)

    y = jax.lax.fori_loop(0, p["in"]["kernel"].shape[0], add_expert, jnp.zeros_like(h))
    if not shared:
        return y
    s = p["shared"]
    return y + swiglu(s["gate"]["kernel"], s["in"]["kernel"], s["out"]["kernel"], h, alter.get("weights_dtype"))


def route_margin(p, h, top_k: int, groups: int, topk_groups: int):
    """(T,) how far each row's choice is from a tie: the least of the gap
    between the last kept group's score and the best dropped group's, and of
    the gap between the ``top_k``-th chosen score and the best score passed
    over in the kept groups. A row whose margin is below the rounding of two
    float32 sums of the same products may be routed either way."""
    s = jax.nn.sigmoid(h @ _f(p["router"]["kernel"]))
    margin = jnp.full(s.shape[:1], jnp.inf, F32)
    choice = s
    if groups > 1:
        g = s.reshape(s.shape[0], groups, -1)
        ranked, best = jax.lax.top_k(jax.lax.top_k(g, 2)[0].sum(-1), topk_groups + 1)
        margin = ranked[:, topk_groups - 1] - ranked[:, topk_groups]
        kept = (best[:, :topk_groups, None] == jnp.arange(groups)[None, None, :]).any(1)
        choice = jnp.where(kept[:, :, None], g, -jnp.inf).reshape(s.shape)
    ranked = jax.lax.top_k(choice, top_k + 1)[0]
    return jnp.minimum(margin, ranked[:, top_k - 1] - ranked[:, top_k])


@partial(jax.jit, static_argnames=("top_k", "offset", "scale", "renorm_eps", "groups", "topk_groups", "alter"))
def _routed(p, h, top_k, offset, scale, renorm_eps, groups, topk_groups, alter):
    with jax.default_matmul_precision("highest"):
        return (moe(p, h, top_k, offset, scale, renorm_eps, groups, topk_groups, dict(alter), shared=False),
                route_margin(p, h, top_k, groups, topk_groups))


def routed(params, layer: int, h, cfg: dict, alter: dict | None = None):
    """The routed experts held here of expert layer ``layer``, without the
    shared expert, for rows ``h`` (T, M) of that layer's normalised input:
    (float32 (T, M), each row's ``route_margin``)."""
    return _routed(params["decoder"]["layers"][layer]["moe"], jnp.asarray(h, F32), cfg["moe_top_k"],
                   cfg.get("moe_expert_offset", 0), cfg.get("moe_routed_scale", 1.0),
                   cfg.get("moe_renorm_epsilon", 1e-6), cfg.get("moe_groups", 0), cfg.get("moe_topk_groups", 0),
                   _frozen({k: v for k, v in (alter or {}).items() if k not in _MIXER_ALTER}))


_MIXER_ALTER = ("no_score_factor", "no_key_rotation")
_EVERY_LAYER = ("weights_dtype",)


def _frozen(d, keep=None):
    return tuple(sorted((k, v) for k, v in (d or {}).items() if keep is None or k in keep))


@partial(jax.jit, static_argnames=("eps", "kind", "alter"))
def _mixer_sublayer(lp, x, eps, kind, alter):
    with jax.default_matmul_precision("highest"):
        return x + mla(lp["mla"], rms_norm(lp["ln1"]["scale"], x, eps), eps, dict(kind), dict(alter))


@partial(jax.jit, static_argnames=("eps", "top_k", "offset", "scale", "renorm_eps", "groups", "topk_groups", "alter"))
def _ffn_sublayer(lp, x, eps, top_k, offset, scale, renorm_eps, groups, topk_groups, alter):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(lp["ln_ffn"]["scale"], x, eps)
        if "moe" in lp:
            flat = h.reshape(-1, h.shape[-1])
            y = moe(lp["moe"], flat, top_k, offset, scale, renorm_eps, groups, topk_groups, dict(alter)).reshape(h.shape)
        else:
            y = dense_swiglu(lp["ffn"], h, dict(alter).get("weights_dtype"))
        return x + y


@partial(jax.jit, static_argnames=("d", "low"))
def _embed(table, ids, d, low):
    return _f(table[ids], low) * jnp.sqrt(_f(d))


@partial(jax.jit, static_argnames=("eps", "first", "low"))
def _head(final_ln, kernel, x, eps, first, low):
    with jax.default_matmul_precision("highest"):
        return rms_norm(final_ln["scale"], x[:, first:], eps) @ _f(kernel, low)


def logits(params, ids, cfg: dict, first: int = 0, alter: dict | None = None):
    """(B, S) ids -> float32 logits (B, S - first, V) for positions first.. ."""
    dec = params["decoder"]
    eps = cfg["layernorm_epsilon"]
    kinds = {k["name"]: k for k in cfg["attention_kinds"]}
    pattern = cfg["layer_pattern"]
    # Each sublayer is given the changes that are its own: one program fewer to compile a change.
    mixer_alter = _frozen(alter, _MIXER_ALTER + _EVERY_LAYER)
    ffn_alter = _frozen({k: v for k, v in (alter or {}).items() if k not in _MIXER_ALTER})
    low = (alter or {}).get("weights_dtype")
    rows = []
    for row in np.asarray(ids):  # one sequence at a time
        x = _embed(dec["embedding"]["table"], row[None], cfg["d_model"], low)
        for i, lp in enumerate(dec["layers"]):
            x = _mixer_sublayer(lp, x, eps, _frozen(kinds[pattern[i % len(pattern)]]), mixer_alter)
            x = _ffn_sublayer(lp, x, eps, cfg["moe_top_k"], cfg.get("moe_expert_offset", 0),
                              cfg.get("moe_routed_scale", 1.0), cfg.get("moe_renorm_epsilon", 1e-6),
                              cfg.get("moe_groups", 0), cfg.get("moe_topk_groups", 0),
                              ffn_alter if "moe" in lp else _frozen(alter, _EVERY_LAYER))
        rows.append(_head(dec["final_ln"], params["final"]["kernel"], x, eps, first, low))
    return jnp.concatenate(rows)
