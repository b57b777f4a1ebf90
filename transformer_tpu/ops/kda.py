"""Delta-rule linear attention with a decay a channel (KDA): the mixer of a
layer whose state is a matrix a head, however long the sequence.

No reference counterpart (the reference mixes positions by softmax attention
alone). The layer is the one of "Kimi Linear: An Expressive, Efficient
Attention Architecture" (arXiv:2510.26692). ``x`` is the normalised input,
``t`` a position, ``h`` one of ``H`` heads of ``D`` key and ``D`` value
channels, ``L`` the taps of three causal depthwise convolutions:

    q, k, v = SiLU(conv_L(x Wq)), SiLU(conv_L(x Wk)), SiLU(conv_L(x Wv))   (H, D) each; zeros before position 0
    q, k    = q / ||q||_2, k / ||k||_2 a head;  q = q * D**-0.5
    g_t     = -exp(A_log[h]) * softplus((x Wfa) Wfb + dt_bias)             (H, D): a log-decay a CHANNEL, <= 0
    beta_t  = sigmoid(x Wb)                                                (H,)
    S_t     = Diag(exp(g_t)) S_{t-1};  S_t += beta_t k_t (v_t - S_t^T k_t)^T   S: (D, D) a head, float32, S_0 = 0
    o_t     = S_t^T q_t
    out     = (RMSNorm_D(o_t) * sigmoid((x Wga) Wgb)) Wo                   the norm's scale (D,) is shared by the heads

which is ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t
v_t^T`` with ``alpha_t = exp(g_t)``. No bias but ``dt_bias``. What a sequence
carries from one call to the next: ``S`` (H x D x D float32) and the last
``L - 1`` inputs of the three convolutions, kept side by side as one
(L - 1, 3 H D) buffer: a fixed state, where an attention layer keeps rows a
position.

Which form each path takes. A chunk of more than one position (training, the
dense-cache forward, a prefill chunk with the state to its left) takes the
**chunked form**: chunks of ``CHUNK`` = 64 positions; inside a chunk, with
``G_r`` the cumulative log-decay up to position ``r`` of the chunk,

    A_ij = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])     (j < i)
    (I + A) U = beta * (V - (K * exp(G)) S_0)                   a unit triangular system a chunk
    o_i = (q_i * exp(G_i)) S_0 + sum_{j <= i} (sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])) u_j
    S_C = Diag(exp(G_C)) S_0 + sum_i (k_i * exp(G_C - G_i)) u_i^T

and the state goes from chunk to chunk in a ``lax.scan``. Every exponent is a
difference of cumulative log-decays with the later position first, so it is
<= 0 whatever the decay: ``exp(-G)`` never appears, and the form holds at the
strongest decay the initialisation gives (about -1.6 a position, e**-100 over
a chunk). Plain XLA and differentiable; the price is the (C, C, D) tensor of
exponentials a chunk and head. One position (the dense-cache decode step, and
the paged step through ``kernels/kda_step.py``, which reads and writes each
live slot's ``S`` once, in place) takes the recurrence above as it stands.
All of them return the state to the right of what they were given.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from transformer_tpu.ops.nn import Params, glorot_uniform

CHUNK = 64
_L2_EPSILON = 1e-6


def kda_init(
    key: jax.Array, d_model: int, heads: int, head_dim: int, taps: int,
    gate_rank: int = 0, param_dtype=jnp.float32,
) -> Params:
    """``q``/``k``/``v``: d_model -> heads * head_dim, each with (taps, heads *
    head_dim) depthwise taps, oldest first; the decay gate ``f_a`` (d_model ->
    rank) and ``f_b`` (rank -> heads * head_dim, a tenth of Glorot size so a
    fresh model's decay sits where ``dt`` puts it), ``A_log`` a head drawn as
    log U(1, 16) and ``dt``'s bias as the inverse softplus of
    exp(U(log 1e-3, log 1e-1)) a channel (both float32: Mamba-2's draw);
    ``beta``: d_model -> heads; the output gate ``g_a``, ``g_b``; the output
    norm's scale (head_dim,); ``out``: heads * head_dim -> d_model."""
    rank = gate_rank or head_dim
    width = heads * head_dim
    ks = jax.random.split(key, 12)
    limit = math.sqrt(3.0 / taps)

    def dense(k, d_in, d_out, scale=1.0):
        w = glorot_uniform(k, (d_in, d_out), param_dtype, d_in, d_out)
        return {"kernel": w if scale == 1.0 else (w.astype(jnp.float32) * scale).astype(param_dtype)}

    def conv(k):
        return {"kernel": jax.random.uniform(k, (taps, width), jnp.float32, -limit, limit).astype(param_dtype)}

    dt = jnp.exp(jax.random.uniform(ks[10], (width,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "q": dense(ks[0], d_model, width), "q_conv": conv(ks[1]),
        "k": dense(ks[2], d_model, width), "k_conv": conv(ks[3]),
        "v": dense(ks[4], d_model, width), "v_conv": conv(ks[5]),
        "f_a": dense(ks[6], d_model, rank), "f_b": dense(ks[7], rank, width, 0.1),
        "A_log": jnp.log(jax.random.uniform(ks[11], (heads,), jnp.float32, 1.0, 16.0)),
        "dt": {"bias": dt + jnp.log(-jnp.expm1(-dt))},  # softplus(bias) = dt
        "beta": dense(ks[8], d_model, heads),
        "g_a": dense(ks[9], d_model, rank),
        "g_b": dense(jax.random.fold_in(key, 12), rank, width),
        "o_norm": {"scale": jnp.ones((head_dim,), param_dtype)},
        "out": dense(jax.random.fold_in(key, 13), width, d_model),
    }


def init_kda_state(
    batch: int, heads: int, head_dim: int, taps: int, dtype=jnp.bfloat16
) -> dict[str, jax.Array]:
    """The state before position 0: ``kda_state`` (B, H, D, D) float32 zeros
    and ``kda_conv`` (B, L - 1, 3 H D) zeros, the q, k and v convolutions'
    inputs side by side."""
    return {
        "kda_state": jnp.zeros((batch, heads, head_dim, head_dim), jnp.float32),
        "kda_conv": jnp.zeros((batch, taps - 1, 3 * heads * head_dim), dtype),
    }


def kda_inputs(params: Params, h: jax.Array, conv_state: jax.Array):
    """Everything of the layer before the recurrence. (B, S, M) normalised
    input and the (B, L - 1, 3 H D) convolution inputs to its left -> q, k
    (normalised; q scaled), v, each (B, S, H, D) float32; the log-decay g
    (B, S, H, D) float32; beta (B, S, H) float32; the convolution inputs to
    the right of the chunk."""
    dtype = h.dtype
    heads = params["A_log"].shape[0]
    taps = params["q_conv"]["kernel"].shape[0]
    b, s, _ = h.shape

    def proj(name, x=h):
        return jnp.einsum("bsm,mf->bsf", x, params[name]["kernel"].astype(dtype))

    qkv = jnp.concatenate([proj("q"), proj("k"), proj("v")], axis=-1)
    padded = jnp.concatenate([conv_state.astype(dtype), qkv], axis=1)  # (B, L - 1 + S, 3HD)
    w = jnp.concatenate(
        [params[n]["kernel"] for n in ("q_conv", "k_conv", "v_conv")], axis=-1
    ).astype(jnp.float32)
    conv = sum(w[j] * padded[:, j : j + s].astype(jnp.float32) for j in range(taps))
    q, k, v = (
        t.reshape(b, s, heads, -1) for t in jnp.split(jax.nn.silu(conv), 3, axis=-1)
    )
    d = q.shape[-1]

    def unit(t):
        return t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + _L2_EPSILON)

    q, k = unit(q) * d**-0.5, unit(k)
    f = proj("f_b", proj("f_a")).astype(jnp.float32) + params["dt"]["bias"].astype(jnp.float32)
    g = -jnp.exp(params["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(f).reshape(b, s, heads, d)
    beta = jax.nn.sigmoid(proj("beta").astype(jnp.float32))
    return q, k, v, g, beta, padded[:, s:]


def kda_output(params: Params, h: jax.Array, o: jax.Array, epsilon: float) -> jax.Array:
    """(B, S, H, D) float32 read-outs -> the layer's (B, S, M) output: the
    head-wise RMSNorm, the low-rank sigmoid gate, the out projection."""
    dtype = h.dtype
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + epsilon)
    o = o * params["o_norm"]["scale"].astype(jnp.float32)
    gate = jnp.einsum(
        "bsr,rf->bsf",
        jnp.einsum("bsm,mr->bsr", h, params["g_a"]["kernel"].astype(dtype)),
        params["g_b"]["kernel"].astype(dtype),
    )
    y = o.reshape(*gate.shape) * jax.nn.sigmoid(gate.astype(jnp.float32))
    return jnp.einsum("bsf,fm->bsm", y.astype(dtype), params["out"]["kernel"].astype(dtype))


def kda_recurrent_step(state, q, k, v, g, beta):
    """One position of the recurrence in plain XLA. ``state`` (B, H, D, D)
    float32; q, k, v, g (B, H, D) float32; beta (B, H). Returns (o (B, H, D),
    the new state)."""
    decayed = state * jnp.exp(g)[..., None]
    seen = jnp.einsum("bhc,bhcv->bhv", k, decayed, precision="highest")
    u = beta[..., None] * (v - seen)
    new = decayed + k[..., None] * u[..., None, :]
    return jnp.einsum("bhc,bhcv->bhv", q, new, precision="highest"), new


def kda_chunked(state, q, k, v, g, beta):
    """The chunked form over (B, S, H, D) float32 inputs (beta (B, S, H)) from
    ``state`` (B, H, D, D) float32: (o (B, S, H, D), the state after the last
    position). ``S`` is padded to whole chunks with positions that neither
    decay nor write (g = 0, beta = 0)."""
    b, s, heads, d = q.shape
    pad = -s % CHUNK
    n = (s + pad) // CHUNK

    def chunks(t):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape(b, n, CHUNK, *t.shape[2:])
        return jnp.moveaxis(t, 1, 0)  # (n, B, C, ...)

    lower = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    strict = jnp.tril(jnp.ones((CHUNK, CHUNK), bool), -1)

    def one(s0, xs):
        qc, kc, vc, gc, bc = xs  # (B, C, H, D) and (B, C, H)
        cum = jnp.cumsum(gc, axis=1)  # G_r, decreasing in r
        # exp(G_i - G_j) for j <= i, 0 above the diagonal: (B, H, C, C, D)
        gap = cum[:, :, None] - cum[:, None, :]  # (B, Ci, Cj, H, D)
        gap = jnp.where(lower[None, :, :, None, None], gap, -jnp.inf)
        decay = jnp.exp(gap)
        kk = jnp.einsum("bihc,bjhc,bijhc->bhij", kc, kc, decay, precision="highest")
        qk = jnp.einsum("bihc,bjhc,bijhc->bhij", qc, kc, decay, precision="highest")
        beta_h = jnp.moveaxis(bc, 2, 1)  # (B, H, C)
        a = jnp.where(strict, kk * beta_h[..., None], 0.0)
        into = jnp.exp(cum)  # exp(G_i) <= 1
        rhs = beta_h[..., None] * (
            jnp.moveaxis(vc, 2, 1)
            - jnp.einsum("bihc,bhcv->bhiv", kc * into, s0, precision="highest")
        )
        u = jax.scipy.linalg.solve_triangular(
            a + jnp.eye(CHUNK, dtype=a.dtype), rhs, lower=True, unit_diagonal=True
        )  # (B, H, C, Dv)
        o = jnp.einsum("bihc,bhcv->bihv", qc * into, s0, precision="highest") + jnp.einsum(
            "bhij,bhjv->bihv", qk, u, precision="highest"
        )
        out_of = jnp.exp(cum[:, -1:] - cum)  # exp(G_C - G_i) <= 1
        s1 = s0 * jnp.exp(cum[:, -1])[..., None] + jnp.einsum(
            "bihc,bhiv->bhcv", kc * out_of, u, precision="highest"
        )
        return s1, o

    state, o = jax.lax.scan(one, state, tuple(chunks(t) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, n * CHUNK, heads, d)
    return o[:, :s], state


def kda_apply(
    params: Params, h: jax.Array, state: dict[str, jax.Array] | None = None,
    epsilon: float = 1e-5,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """(B, S, M) normalised input and the state to its left (``None`` = the
    sequence starts here) -> ((B, S, M) output, the state to its right:
    ``{"kda_state", "kda_conv"}`` as ``init_kda_state`` lays them out)."""
    heads = params["A_log"].shape[0]
    width = params["q"]["kernel"].shape[1]
    taps = params["q_conv"]["kernel"].shape[0]
    if state is None:
        state = init_kda_state(h.shape[0], heads, width // heads, taps, h.dtype)
    q, k, v, g, beta, conv_state = kda_inputs(params, h, state["kda_conv"])
    s0 = state["kda_state"].astype(jnp.float32)
    if h.shape[1] == 1:
        o, s1 = kda_recurrent_step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        o = o[:, None]
    else:
        o, s1 = kda_chunked(s0, q, k, v, g, beta)
    return kda_output(params, h, o, epsilon), {"kda_state": s1, "kda_conv": conv_state}
