"""Plain reference: the encoder-decoder Transformer of Vaswani et al. (2017).

Forward pass and label-smoothed loss in straightforward ``jax.numpy`` and
float32, written from the paper's equations (sections 3.1-3.5, 5.4); gradients
by ``jax.grad`` of that loss. No kernels, no cache, no dropout (the comparison
runs with dropout off). It imports nothing from the program and only reads
the program's parameter tree.

Departures from the paper, each made to compute what the program states it
computes (they change no operation count):
- the sinusoid table holds all sines in its first half and all cosines in its
  second (the paper interleaves them): a fixed permutation of channels;
- the label-smoothed loss spreads ``eps`` over the V - 1 other classes and
  omits the constant entropy term, as most NMT stacks do;
- the source and target embedding tables are separate leaves that start equal
  (the program ties them at initialisation only, PERF.md section 7); the
  output projection is the target table transposed, as published.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e9


def _f(x):
    return jnp.asarray(x, F32)


def layer_norm(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f(p["scale"]) + _f(p["bias"])


def attention(p, x_q, x_kv, allowed):
    """Multi-head attention, eq. (1) and section 3.2.2. Kernels are stored
    (d_model, heads, head_dim); ``allowed`` is (B, 1, S_q|1, S_k) boolean."""
    q = jnp.einsum("bsm,mhd->bshd", x_q, _f(p["query"]["kernel"])) + _f(p["query"]["bias"])
    k = jnp.einsum("bsm,mhd->bshd", x_kv, _f(p["key"]["kernel"])) + _f(p["key"]["bias"])
    v = jnp.einsum("bsm,mhd->bshd", x_kv, _f(p["value"]["kernel"])) + _f(p["value"]["bias"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(_f(q.shape[-1]))
    scores = jnp.where(allowed, scores, NEG)
    w = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", w, v)
    return jnp.einsum("bqhd,hdm->bqm", ctx, _f(p["out"]["kernel"])) + _f(p["out"]["bias"])


def ffn(p, x):
    h = jax.nn.relu(x @ _f(p["in"]["kernel"]) + _f(p["in"]["bias"]))
    return h @ _f(p["out"]["kernel"]) + _f(p["out"]["bias"])


def sinusoids(n, d):
    pos = jnp.arange(n, dtype=F32)[:, None]
    ch = jnp.arange(d, dtype=F32)[None, :]
    angles = pos * jnp.power(10000.0, -(2.0 * jnp.floor(ch / 2.0)) / d)
    return jnp.concatenate([jnp.sin(angles[:, 0::2]), jnp.cos(angles[:, 1::2])], axis=-1)


def embed(table, ids, d):
    return _f(table)[ids] * jnp.sqrt(_f(d)) + sinusoids(ids.shape[1], d)[None]


def logits(params, src, tar_inp, cfg: dict):
    """(B, S_src) and (B, S_tgt) ids -> (B, S_tgt, V) logits; post-LN residual
    blocks (section 3.1), pad id 0 masked as a key everywhere."""
    d, eps = cfg["d_model"], cfg["layernorm_epsilon"]
    src_ok = (src != 0)[:, None, None, :]
    tgt_ok = (tar_inp != 0)[:, None, None, :]
    n = tar_inp.shape[1]
    causal = jnp.tril(jnp.ones((n, n), bool))[None, None]
    x = embed(params["encoder"]["embedding"]["table"], src, d)
    for lp in params["encoder"]["layers"]:
        x = layer_norm(lp["ln1"], x + attention(lp["mha"], x, x, src_ok), eps)
        x = layer_norm(lp["ln2"], x + ffn(lp["ffn"], x), eps)
    enc = x
    y = embed(params["decoder"]["embedding"]["table"], tar_inp, d)
    for lp in params["decoder"]["layers"]:
        y = layer_norm(lp["ln1"], y + attention(lp["self_mha"], y, y, tgt_ok & causal), eps)
        y = layer_norm(lp["ln2"], y + attention(lp["cross_mha"], y, enc, src_ok), eps)
        y = layer_norm(lp["ln_ffn"], y + ffn(lp["ffn"], y), eps)
    return y @ _f(params["decoder"]["embedding"]["table"]).T


def loss(params, src, tgt, cfg: dict, label_smoothing: float):
    """Mean label-smoothed cross entropy over non-pad target tokens, teacher
    forcing: feed tgt[:, :-1], predict tgt[:, 1:]."""
    tar_inp, tar_out = tgt[:, :-1], tgt[:, 1:]
    logp = jax.nn.log_softmax(logits(params, src, tar_inp, cfg), axis=-1)
    v = logp.shape[-1]
    hit = jnp.take_along_axis(logp, tar_out[..., None], axis=-1)[..., 0]
    other = logp.sum(-1) - hit
    per_token = -((1.0 - label_smoothing) * hit + label_smoothing / (v - 1) * other)
    mask = (tar_out != 0).astype(F32)
    return (per_token * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def loss_and_grads(params, src, tgt, cfg: dict, label_smoothing: float):
    """Float32 at the highest matmul precision: on a TPU a float32 matmul runs
    in lower precision unless told otherwise."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params, src, tgt, cfg, label_smoothing)
