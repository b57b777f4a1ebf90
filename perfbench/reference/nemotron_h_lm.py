"""Plain reference: NVIDIA's Nemotron-H block as the ``config.json`` of
Nemotron-3-Super-120B-A12B describes it (``model_type`` ``nemotron_h``;
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16; the mixer
is Mamba-2's, arXiv:2405.21060).

``x`` is the residual stream; every layer is ``x <- x + f(RMSNorm(x))`` with
ONE of three ``f``, as ``hybrid_override_pattern`` places them (the model's
``layer_pattern``): a state-space mixer (parameters ``ssm``), GQA attention
(``self_mha``) or the latent expert layer (``moe``). No bias but the
convolution's and ``dt``'s.

- **Mamba-2**: ``[z | xBC | dt] = h W_in``; ``xBC = SiLU(conv_4(xBC) + b)``, a
  causal depthwise convolution (zeros before position 0); ``[x | B | C] =
  xBC``, 128 heads of 64, 8 groups of B and C of 128 (head ``i`` reads group
  ``i // 16``); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; then, a
  head, POSITION BY POSITION (the recurrence itself, no chunks): ``S <-
  exp(dt A) S + dt x B^T``, ``y = S C + D x`` from ``S = 0`` in float32;
  ``y = RMSNorm_groups(y * SiLU(z)) w`` over 8 groups of 1,024 channels;
  ``x <- x + y W_out``.
- **Attention**: 32 query heads over 2 KV heads of 128, no rotation, scores
  ``q . k / sqrt(128)`` over ``s <= t``, a block of rows at a time.
- **Latent experts**: ``s = sigmoid(h W_r)`` over all 512 experts in float32
  (``h`` the full 4,096-wide input); the 22 with the largest ``s + b`` (the
  selection bias, in the choice ONLY); weights = the chosen ``s`` over (their
  sum + 1e-20), times 5; ``u = h W_down`` (4,096 -> 1,024); each expert
  ``relu(u W1)^2 W2`` in the latent; ``y = (sum_e w_e E_e(u)) W_up + relu(h
  S1)^2 S2`` (the shared expert reads ``h``). Only the experts held here (ids
  ``moe_expert_offset ..``) are in the parameters and in the sum.
- Final RMSNorm; an untied head over the vocabulary's slice.

Full forward pass over the whole sequence from position 0 in ``jax.numpy``
float32 at the highest matmul precision: no cache, no state handed on, no
kernels, no chunks. It imports nothing from the program and only reads the
program's parameter tree and the ``model`` group of the configuration.

``alter`` (the controls of PERF.md section 6: the check must see each) changes
one part of the mathematics: ``state_bfloat16`` (``S`` rounded to bfloat16
after every position), ``state_kept_bfloat16`` = ``k`` (``S`` rounded after
position ``k - 1`` and each later one: a state that a program computes in
float32 but keeps in bfloat16 between its prompt and each decoded token),
``no_D``, ``norm_whole`` (the gated norm over all the
channels at once, not a group at a time), ``no_conv_bias``,
``rope_attention`` (q and k rotated at their positions, base 10,000, the whole
head), ``router_latent`` (the router reads ``u`` in place of ``h``: the first
``latent`` rows of its kernel), ``expert_dtype`` = a dtype name (the routed
experts' weights, inputs and hidden rows rounded to it), ``weights_dtype`` =
a dtype name (every kernel, the embedding and the head rounded to it: the
whole model's weights in a precision below the configuration's).

Departure, as the configuration file states it: the embedding is multiplied
by sqrt(d_model), as the repo's prologue does (its table is initialised
d_model**-0.5 smaller).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_ROWS = 512  # query rows whose scores are held at once


def _f(x, low=None):
    """float32; a weight first rounded to the dtype ``low`` where given."""
    return jnp.asarray(x if low is None else jnp.asarray(x).astype(low), F32)


def _lowered(tree, low):
    """Every kernel of ``tree`` cast to the dtype ``low``, a leaf at a time and
    outside any jitted forward: inside one, a compiler allowed excess
    precision may drop a cast down and back up (the TPU's did)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x.astype(low) if getattr(path[-1], "key", None) == "kernel" else x, tree)


def rms_norm(scale, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f(scale)


def mamba(p, h, eps: float, groups: int, alter: dict):
    """The state-space mixer over a whole sequence (1, S, M), a position at a
    time: (its output (1, S, M), ``S`` after the last position (H, P, N))."""
    low = alter.get("weights_dtype")
    heads = p["A_log"].shape[0]
    width = p["norm"]["scale"].shape[0]
    n = h.shape[1]
    proj = h @ _f(p["in"]["kernel"], low)
    z, xbc, dt = proj[..., :width], proj[..., width:-heads], proj[..., -heads:]
    w = _f(p["conv"]["kernel"], low)
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((1, taps - 1, xbc.shape[-1]), F32), xbc], axis=1)
    conv = sum(w[j] * padded[:, j : j + n] for j in range(taps))
    if not alter.get("no_conv_bias"):
        conv = conv + _f(p["conv"]["bias"])
    xbc = jax.nn.silu(conv)[0]
    state = (xbc.shape[-1] - width) // (2 * groups)
    x = xbc[:, :width].reshape(n, heads, -1)
    bmat = jnp.repeat(xbc[:, width : width + groups * state].reshape(n, groups, state), heads // groups, axis=1)
    cmat = jnp.repeat(xbc[:, width + groups * state :].reshape(n, groups, state), heads // groups, axis=1)
    dt = jax.nn.softplus(dt[0] + _f(p["dt"]["bias"]))  # (S, H)
    a = -jnp.exp(_f(p["A_log"]))

    # S rounded to bfloat16 after position ``rounded_from - 1`` and each later one.
    rounded_from = 0 if alter.get("state_bfloat16") else alter.get("state_kept_bfloat16")

    def step(s, inputs):
        t, xt, bt, ct, dtt = inputs
        s = jnp.exp(dtt * a)[:, None, None] * s + (dtt[:, None] * xt)[..., None] * bt[:, None, :]
        if rounded_from is not None:
            # reduce_precision, not a round trip through astype: a compiler
            # allowed excess precision may drop the round trip.
            s = jnp.where(t + 1 >= rounded_from, jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7), s)
        return s, jnp.einsum("hpn,hn->hp", s, ct)

    s0 = jnp.zeros((heads, x.shape[-1], state), F32)
    last, y = jax.lax.scan(step, s0, (jnp.arange(n), x, bmat, cmat, dt))
    if not alter.get("no_D"):
        y = y + _f(p["D"])[:, None] * x
    y = y.reshape(n, width) * jax.nn.silu(z[0])
    g = y.reshape(n, 1 if alter.get("norm_whole") else groups, -1)
    y = (g / jnp.sqrt((g * g).mean(-1, keepdims=True) + eps)).reshape(n, width) * _f(p["norm"]["scale"])
    return (y @ _f(p["out"]["kernel"], low))[None], last


def rotate(x):
    """(B, S, H, D) turned at positions 0 .. S - 1, channel i with i + D/2, base 10,000."""
    n, d = x.shape[1], x.shape[-1]
    inv = 1.0 / 10000.0 ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(n)[:, None] * inv[None, :]
    cos, sin = jnp.asarray(np.cos(angle), F32)[None, :, None], jnp.asarray(np.sin(angle), F32)[None, :, None]
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, h, alter: dict):
    low = alter.get("weights_dtype")
    q, k, v = (jnp.einsum("bsm,mhd->bshd", h, _f(p[n]["kernel"], low)) for n in ("query", "key", "value"))
    if alter.get("rope_attention"):
        q, k = rotate(q), rotate(k)
    group = q.shape[2] // k.shape[2]  # each KV head serves `group` query heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    n = h.shape[1]
    out = []
    for r0 in range(0, n, _ROWS):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, r0 : r0 + _ROWS], k) * q.shape[-1] ** -0.5
        rows = r0 + jnp.arange(scores.shape[2])
        seen = jnp.arange(n)[None, :] <= rows[:, None]
        w = jax.nn.softmax(jnp.where(seen[None, None], scores, -1e30), axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", w, v))
    return jnp.einsum("bqhd,hdm->bqm", jnp.concatenate(out, axis=1), _f(p["out"]["kernel"], low))


def relu2_mlp(w_in, w_out, h, low=None):
    return jnp.square(jax.nn.relu(h @ _f(w_in, low))) @ _f(w_out, low)


def route(p, h, u, top_k: int, scale: float, eps: float, alter: dict | None = None):
    """(chosen expert ids (T, top_k), their weights (T, top_k))."""
    alter = alter or {}
    kernel = _f(p["router"]["kernel"], alter.get("weights_dtype"))
    s = jax.nn.sigmoid(u @ kernel[: u.shape[-1]] if alter.get("router_latent") else h @ kernel)
    _, chosen = jax.lax.top_k(s + _f(p["router"]["bias"]), top_k)  # the bias chooses ...
    picked = jnp.take_along_axis(s, chosen, axis=-1)  # ... and weighs nothing
    return chosen, scale * picked / (picked.sum(-1, keepdims=True) + eps)


def moe(p, h, top_k: int, offset: int, scale: float, eps: float, alter: dict | None = None, shared: bool = True):
    """The latent expert layer for rows ``h`` (T, M): the routed experts held
    here as a plain loop, plus the shared expert (``shared=False``: the
    routed part alone)."""
    alter = alter or {}
    low = alter.get("expert_dtype")
    weights_low = low or alter.get("weights_dtype")
    u = h @ _f(p["latent_down"]["kernel"], alter.get("weights_dtype"))
    chosen, weight = route(p, h, u, top_k, scale, eps, alter)

    def rounded(t):
        return t if low is None else _f(jnp.asarray(t).astype(low))

    def add_expert(e, y):  # one expert at a time: one float32 copy at a time
        w = jnp.where(chosen == e + offset, weight, 0.0).sum(-1, keepdims=True)
        wi, wo = (_f(jax.lax.dynamic_index_in_dim(p[n]["kernel"], e, keepdims=False), weights_low) for n in ("in", "out"))
        return y + w * (rounded(jnp.square(jax.nn.relu(rounded(u) @ wi))) @ wo)

    y = jax.lax.fori_loop(0, p["in"]["kernel"].shape[0], add_expert, jnp.zeros_like(u))
    y = y @ _f(p["latent_up"]["kernel"], alter.get("weights_dtype"))
    if not shared:
        return y
    s = p["shared"]
    return y + relu2_mlp(s["in"]["kernel"], s["out"]["kernel"], h, alter.get("weights_dtype"))


def route_margin(p, h, top_k: int):
    """(T,) how far each row's choice is from a tie: the gap between the
    ``top_k``-th largest ``s + b`` and the next. A row whose margin is below
    the rounding of two float32 sums of the same products may be routed
    either way."""
    s = jax.nn.sigmoid(h @ _f(p["router"]["kernel"])) + _f(p["router"]["bias"])
    ranked = jax.lax.top_k(s, top_k + 1)[0]
    return ranked[:, top_k - 1] - ranked[:, top_k]


def _frozen(d, keep=None):
    return tuple(sorted((k, v) for k, v in (d or {}).items() if keep is None or k in keep))


_MIXER_ALTER = ("state_bfloat16", "state_kept_bfloat16", "no_D", "norm_whole", "no_conv_bias", "rope_attention")
_EXPERT_ALTER = ("router_latent", "expert_dtype")
_EVERY_LAYER = ("weights_dtype",)


@partial(jax.jit, static_argnames=("top_k", "offset", "scale", "renorm_eps", "alter"))
def _routed(p, h, top_k, offset, scale, renorm_eps, alter):
    with jax.default_matmul_precision("highest"):
        return (moe(p, h, top_k, offset, scale, renorm_eps, dict(alter), shared=False),
                route_margin(p, h, top_k))


def routed(params, layer: int, h, cfg: dict, alter: dict | None = None):
    """The routed experts held here of expert layer ``layer``, with the latent
    projections and without the shared expert, for rows ``h`` (T, M) of that
    layer's normalised input: (float32 (T, M), each row's ``route_margin``)."""
    return _routed(params["decoder"]["layers"][layer]["moe"], jnp.asarray(h, F32), cfg["moe_top_k"],
                   cfg.get("moe_expert_offset", 0), cfg.get("moe_routed_scale", 1.0),
                   cfg.get("moe_renorm_epsilon", 1e-6), _frozen(alter, _EXPERT_ALTER + _EVERY_LAYER))


@partial(jax.jit, static_argnames=("eps", "groups", "alter"))
def _mamba_alone(p, h, eps, groups, alter):
    with jax.default_matmul_precision("highest"):
        return mamba(p, h, eps, groups, dict(alter))


def mixer(params, layer: int, h, cfg: dict, alter: dict | None = None):
    """State-space layer ``layer`` alone for sequences ``h`` (B, S, M) of its
    normalised input, each from zero state: (float32 outputs (B, S, M), each
    sequence's ``S`` after its last position (B, H, P, N))."""
    alter = dict(alter or {})
    p, low = params["decoder"]["layers"][layer]["ssm"], alter.pop("weights_dtype", None)
    if low is not None:
        p = _lowered(p, low)
    groups = next(k["ssm_groups"] for k in cfg["attention_kinds"] if k.get("ssm_heads"))
    alter = _frozen(alter, _MIXER_ALTER)
    outs, states = zip(*(_mamba_alone(p, jnp.asarray(row, F32)[None], cfg["layernorm_epsilon"], groups, alter)
                         for row in np.asarray(h)))
    return jnp.concatenate(outs), jnp.stack(states)


@partial(jax.jit, static_argnames=("eps", "groups", "alter"))
def _mixer_sublayer(lp, x, eps, groups, alter):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(lp["ln1"]["scale"], x, eps)
        if "ssm" in lp:
            return x + mamba(lp["ssm"], h, eps, groups, dict(alter))[0]
        return x + attention(lp["self_mha"], h, dict(alter))


@partial(jax.jit, static_argnames=("eps", "top_k", "offset", "scale", "renorm_eps", "alter"))
def _ffn_sublayer(lp, x, eps, top_k, offset, scale, renorm_eps, alter):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(lp["ln_ffn"]["scale"], x, eps)
        flat = h.reshape(-1, h.shape[-1])
        return x + moe(lp["moe"], flat, top_k, offset, scale, renorm_eps, dict(alter)).reshape(h.shape)


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
    groups = next(k["ssm_groups"] for k in cfg["attention_kinds"] if k.get("ssm_heads"))
    # Each sublayer is given the changes that are its own: one program fewer to compile a change.
    mixer_alter = _frozen(alter, _MIXER_ALTER + _EVERY_LAYER)
    ffn_alter = _frozen(alter, _EXPERT_ALTER + _EVERY_LAYER)
    low = (alter or {}).get("weights_dtype")
    rows = []
    for row in np.asarray(ids):  # one sequence at a time
        x = _embed(dec["embedding"]["table"], row[None], cfg["d_model"], low)
        for lp in dec["layers"]:
            if "ln1" in lp:
                x = _mixer_sublayer(lp, x, eps, groups, mixer_alter)
            if "ln_ffn" in lp:
                x = _ffn_sublayer(lp, x, eps, cfg["moe_top_k"], cfg.get("moe_expert_offset", 0),
                                  cfg.get("moe_routed_scale", 1.0), cfg.get("moe_renorm_epsilon", 1e-6), ffn_alter)
        rows.append(_head(dec["final_ln"], params["final"]["kernel"], x, eps, first, low))
    return jnp.concatenate(rows)
