"""Plain reference: Moonshot's Kimi Linear block as its ``config.json`` describes
it (``model_type`` ``kimi_linear``;
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct; the layer is
that of arXiv:2510.26692).

``x`` is the residual stream. No bias but ``dt``'s. No position is encoded
anywhere (``mla_use_nope``). A layer whose parameters are ``kda`` is delta-rule
linear attention (KDA), one whose parameters are ``mla`` latent attention
(MLA): layers 0, 1, 2, 4 and layer 3 of the five that run.

- **KDA**: ``h = RMSNorm(x)``; ``q, k, v = SiLU(conv(h Wq)), SiLU(conv(h Wk)),
  SiLU(conv(h Wv))``, each a causal depthwise convolution of 4 taps (zeros
  before position 0), 32 heads of 128; ``q`` and ``k`` L2-normalised a head,
  ``q`` times ``128**-0.5``; ``g_t = -exp(A_log) * softplus((h Wfa) Wfb +
  dt_bias)`` a channel; ``beta_t = sigmoid(h Wb)`` a head; then, a head, TOKEN BY
  TOKEN (the recurrence itself, no chunks): ``S <- Diag(exp(g_t)) S; S <- S +
  beta_t k_t (v_t - S^T k_t)^T; o_t = S^T q_t`` from ``S = 0``;
  ``x <- x + (RMSNorm_128(o) * sigmoid((h Wga) Wgb)) Wo``.
- **MLA**, unabsorbed: ``[qn_h ; qp_h] = h Wq``; ``[c ; kp] = h Wkva``, ``c =
  RMSNorm_512(c)``; ``[kn_h ; v_h] = c Wkvb_h``; ``score_h(t, s) = (qn_h(t) .
  kn_h(s) + qp_h(t) . kp(s)) * 192**-0.5`` over ``s <= t``; softmax; ``x <- x +
  concat_h(sum_s p v_h(s)) Wo``. Keys and values are expanded a head; the
  scores are computed a block of rows at a time.
- **Feed-forward**, the leading dense layer: SwiGLU. The others: ``s =
  sigmoid(h' Wr)`` over all 256 experts in float32; the 8 with the largest ``s +
  b`` (the selection bias, in the choice ONLY); weights = the chosen ``s`` over
  (their sum + ``moe_renorm_epsilon``), times ``moe_routed_scale``; each expert
  a SwiGLU; plus one shared expert every token takes. Only the experts held
  here (ids ``moe_expert_offset ..``) are in the parameters and in the sum.
- Final RMSNorm; an untied head over the vocabulary's slice.

Full forward pass over the whole sequence in ``jax.numpy`` float32 at the
highest matmul precision: no cache, no state handed on, no kernels, no chunks.
It imports nothing from the program and only reads the program's parameter
tree and the ``model`` group of the configuration.

``alter`` (the controls of PERF.md section 6: the check must see each) leaves
one part of the mathematics out: ``no_decay`` (g = 0), ``beta_one``,
``no_delta`` (``S += beta k v^T``), ``zero_state_at`` = a position at which
``S`` is zeroed, ``no_conv_silu``, ``no_shared_key`` (``kp`` out of the
scores), ``no_latent_norm``, ``no_select_bias``.

Assumed, because the config does not say (the configuration file gives the
reason for each): the gates' rank, the draws of ``A_log`` and ``dt``, the L2
normalisation's epsilon 1e-6, ``192**-0.5``, the float32 state. Departure: the
embedding is multiplied by sqrt(d_model) as the repo's prologue does (its
table is initialised d_model**-0.5 smaller).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_ROWS = 512  # query rows whose scores are held at once


def _f(x):
    return jnp.asarray(x, F32)


def rms_norm(p, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f(p["scale"])


def causal_conv(z, w):
    """(B, S, C) inputs, (L, C) taps oldest first: tap j weighs z as it was
    ``L - 1 - j`` positions ago; zeros before position 0."""
    taps, n = w.shape[0], z.shape[1]
    out = jnp.zeros_like(z)
    for j in range(taps):
        out = out + w[j] * jnp.pad(z, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :n]
    return out


def kda(p, h, eps: float, alter: dict | None = None):
    """The delta-rule layer over a whole sequence (B, S, M) from position 0,
    one position at a time."""
    alter = alter or {}
    b, n, _ = h.shape
    heads = p["A_log"].shape[0]
    act = (lambda t: t) if alter.get("no_conv_silu") else jax.nn.silu

    def branch(name):
        z = causal_conv(h @ _f(p[name]["kernel"]), _f(p[name + "_conv"]["kernel"]))
        return act(z).reshape(b, n, heads, -1)

    q, k, v = branch("q"), branch("k"), branch("v")
    d = q.shape[-1]
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * d**-0.5
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    f = (h @ _f(p["f_a"]["kernel"])) @ _f(p["f_b"]["kernel"]) + _f(p["dt"]["bias"])
    g = -jnp.exp(_f(p["A_log"]))[:, None] * jax.nn.softplus(f).reshape(b, n, heads, d)
    if alter.get("no_decay"):
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(h @ _f(p["beta"]["kernel"]))  # (B, S, H)
    if alter.get("beta_one"):
        beta = jnp.ones_like(beta)
    wipe = alter.get("zero_state_at", -1)

    def step(s, xs):  # s: (B, H, Dk, Dv)
        t, qt, kt, vt, gt, bt = xs
        s = jnp.where(t == wipe, 0.0, s)
        s = s * jnp.exp(gt)[..., None]
        seen = 0.0 if alter.get("no_delta") else jnp.einsum("bhc,bhcv->bhv", kt, s)
        s = s + kt[..., None] * (bt[..., None] * (vt - seen))[..., None, :]
        return s, jnp.einsum("bhc,bhcv->bhv", qt, s)

    by_time = [jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)]
    _, o = jax.lax.scan(step, jnp.zeros((b, heads, d, d), F32), (jnp.arange(n), *by_time))
    o = jnp.moveaxis(o, 0, 1)  # (B, S, H, D)
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) * _f(p["o_norm"]["scale"])
    gate = jax.nn.sigmoid((h @ _f(p["g_a"]["kernel"])) @ _f(p["g_b"]["kernel"]))
    return (o.reshape(b, n, -1) * gate) @ _f(p["out"]["kernel"])


def mla(p, h, eps: float, alter: dict | None = None):
    """Latent attention over a whole sequence (B, S, M), keys and values
    expanded a head, nothing absorbed."""
    alter = alter or {}
    rank = p["kv_norm"]["scale"].shape[0]
    a = h @ _f(p["kv_a"]["kernel"])
    c, kp = a[..., :rank], a[..., rank:]
    if not alter.get("no_latent_norm"):
        c = rms_norm(p["kv_norm"], c, eps)
    kv = jnp.einsum("bsr,rhd->bshd", c, _f(p["kv_b"]["kernel"]))
    shared = kp.shape[-1]
    nope = p["query"]["kernel"].shape[2] - shared
    kn, v = kv[..., :nope], kv[..., nope:]
    q = jnp.einsum("bsm,mhd->bshd", h, _f(p["query"]["kernel"]))
    n = h.shape[1]
    out = []
    for r0 in range(0, n, _ROWS):
        qb = q[:, r0 : r0 + _ROWS]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb[..., :nope], kn)
        if not alter.get("no_shared_key"):
            scores = scores + jnp.einsum("bqhd,bkd->bhqk", qb[..., nope:], kp)
        scores = scores * (nope + shared) ** -0.5
        seen = jnp.arange(n)[None, :] <= (r0 + jnp.arange(qb.shape[1]))[:, None]
        w = jax.nn.softmax(jnp.where(seen[None, None], scores, -1e9), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", w, v))
    return jnp.einsum("bqhd,hdm->bqm", jnp.concatenate(out, axis=1), _f(p["out"]["kernel"]))


def swiglu(w_gate, w_in, w_out, h):
    return (jax.nn.silu(h @ _f(w_gate)) * (h @ _f(w_in))) @ _f(w_out)


def route(p, h, top_k: int, scale: float, eps: float, alter: dict | None = None):
    """(chosen expert ids (T, top_k), their weights (T, top_k))."""
    s = jax.nn.sigmoid(h @ _f(p["router"]["kernel"]))
    bias = 0.0 if (alter or {}).get("no_select_bias") else _f(p["router"]["bias"])
    _, chosen = jax.lax.top_k(s + bias, top_k)  # the bias chooses ...
    picked = jnp.take_along_axis(s, chosen, axis=-1)  # ... and weighs nothing
    return chosen, scale * picked / (picked.sum(-1, keepdims=True) + eps)


def moe(p, h, top_k: int, offset: int, scale: float, eps: float, alter: dict | None = None, shared: bool = True):
    """The routed experts held here, for rows ``h`` (T, M), as a plain loop,
    plus the shared expert (``shared=False``: the routed part alone)."""
    chosen, weight = route(p, h, top_k, scale, eps, alter)

    def add_expert(e, y):  # one expert at a time: one float32 copy at a time
        w = jnp.where(chosen == e + offset, weight, 0.0).sum(-1, keepdims=True)
        one = [jax.lax.dynamic_index_in_dim(p[n]["kernel"], e, keepdims=False) for n in ("gate", "in", "out")]
        return y + w * swiglu(*one, h)

    y = jax.lax.fori_loop(0, p["in"]["kernel"].shape[0], add_expert, jnp.zeros_like(h))
    if not shared:
        return y
    s = p["shared"]
    return y + swiglu(s["gate"]["kernel"], s["in"]["kernel"], s["out"]["kernel"], h)


def _frozen(alter):
    return tuple(sorted((alter or {}).items()))


@partial(jax.jit, static_argnames=("eps", "alter"))
def _mixer_sublayer(lp, x, eps, alter):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(lp["ln1"], x, eps)
        return x + (kda(lp["kda"], h, eps, dict(alter)) if "kda" in lp else mla(lp["mla"], h, eps, dict(alter)))


@partial(jax.jit, static_argnames=("eps", "top_k", "offset", "scale", "renorm_eps", "alter"))
def _ffn_sublayer(lp, x, eps, top_k, offset, scale, renorm_eps, alter):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(lp["ln_ffn"], x, eps)
        if "moe" in lp:
            y = moe(lp["moe"], h.reshape(-1, h.shape[-1]), top_k, offset, scale, renorm_eps, dict(alter)).reshape(h.shape)
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


def logits(params, ids, cfg: dict, first: int = 0, alter: dict | None = None):
    """(B, S) ids -> float32 logits (B, S - first, V) for positions first.. ."""
    dec = params["decoder"]
    eps = cfg["layernorm_epsilon"]
    alter = _frozen(alter)
    rows = []
    for row in np.asarray(ids):  # one sequence at a time
        x = _embed(dec["embedding"]["table"], row[None], cfg["d_model"])
        for lp in dec["layers"]:
            x = _mixer_sublayer(lp, x, eps, alter)
            x = _ffn_sublayer(lp, x, eps, cfg["moe_top_k"], cfg.get("moe_expert_offset", 0),
                              cfg.get("moe_routed_scale", 1.0), cfg.get("moe_renorm_epsilon", 1e-6), alter)
        rows.append(_head(dec["final_ln"], params["final"]["kernel"], x, eps, first))
    return jnp.concatenate(rows)
