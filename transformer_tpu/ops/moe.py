"""Mixture-of-Experts feed-forward layer with expert parallelism.

No reference counterpart: the reference's FFN is a dense two-matmul block
(``point_ffn.py:3-7``) — this is a capability extension (SURVEY.md §2.4 lists
expert parallelism as out of reference scope), built TPU-first:

- **Static shapes.** Routing uses the classic capacity-factor dispatch
  (Shazeer-style top-k gating): every (batch-row, expert) pair gets a fixed
  number of token slots ``C``, and dispatch/combine are dense one-hot
  tensors contracted with einsums. No sort, no gather/scatter with
  data-dependent shapes — everything XLA sees is a fixed-shape matmul, so
  the MXU stays fed and nothing recompiles.
- **Expert parallelism as sharding.** Expert weights are stacked on a leading
  ``E`` axis — ``in/kernel (E, M, F)`` — and sharded over the ``expert`` mesh
  axis (``parallel/sharding.py``). The all-to-all that moves token slots to
  their experts is inserted by GSPMD from the sharding annotations, riding
  ICI; there is no hand-written collective. EP composes with tp ('model'
  shards F) and fsdp exactly like the dense FFN.
- **Remat-safe aux loss.** The load-balance loss is a real function output
  threaded through the layer stack (``models/encoder.py``), not a side
  channel, so it survives ``jax.checkpoint``.

Routing math (fp32 throughout; expert matmuls in the compute dtype):
top-k gates renormalized over the selected experts, earlier choices get
capacity priority, tokens overflowing an expert's capacity are dropped (the
residual connection around the FFN sublayer carries them through unchanged).
The auxiliary load-balancing loss is the standard Switch/GShard form
``E * sum_e f_e * p_e`` (f_e: fraction of tokens whose first choice is e;
p_e: mean router probability), which is 1.0 at perfect balance.
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

from transformer_tpu.ops.ffn import _ACTIVATIONS, ffn_apply, ffn_init
from transformer_tpu.ops.nn import Params, glorot_uniform

# Active mesh for expert-sharding constraints (see ``expert_mesh`` below).
_EXPERT_MESH: list = []


@contextlib.contextmanager
def expert_mesh(mesh):
    """Activate sharding hints inside ``moe_apply``: the distributed engine
    wraps its forward in this context (``parallel/distributed.py``) so the
    dispatch/combine einsums are annotated with the exact resharding points —
    tokens move from batch-sharded (data×fsdp×expert) to expert-sharded via
    ONE GSPMD all-to-all instead of the partitioner's replicate-then-slice
    fallback. Without the context (single chip, plain jit) the hints vanish."""
    _EXPERT_MESH.append(mesh)
    try:
        yield
    finally:
        _EXPERT_MESH.pop()


def _constrain(x: jax.Array, *spec) -> jax.Array:
    if not _EXPERT_MESH:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _EXPERT_MESH[-1]

    def present(a):
        axes = a if isinstance(a, tuple) else (a,)
        return all(ax in mesh.shape for ax in axes)

    cleaned = P(*[(a if a is None or present(a) else None) for a in spec])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, cleaned))


def moe_init(
    key: jax.Array,
    d_model: int,
    dff: int,
    num_experts: int,
    param_dtype=jnp.float32,
    *,
    experts_held: int = 0,
    activation: str = "relu",
    shared_dff: int = 0,
    router_scale: float = 1.0,
    out_scale: float = 1.0,
    select_bias: bool = False,
    dropless: bool = False,
    latent_dim: int = 0,
) -> Params:
    """Router plus the experts' FFNs stacked on a leading E axis. Per-expert
    fan-in/fan-out matches ``ffn_init`` so a 1-expert MoE is
    parameter-for-parameter the dense FFN. Each stacked leaf is one random
    draw (a Python loop over 128 experts, three leaves and four layers is
    1,536 draws to compile).

    A gated ``activation`` (the dropless layer's experts) gives a third,
    ``gate`` stack and no biases; ``dropless`` experts of an ungated one have
    two stacks and no biases, where the capacity layer's have biases;
    ``latent_dim`` (dropless) gives the experts that width in place of
    ``d_model`` and the projections ``latent_down`` (d_model -> latent) and
    ``latent_up`` (latent -> d_model) around them; ``experts_held`` (0 = all) stacks only this
    chip's share while the router keeps its full width; ``shared_dff`` adds
    the shared expert every token takes, a plain gated FFN without biases.
    ``router_scale`` and ``out_scale`` multiply the Glorot draw of the router's
    kernel and of the routed experts' out kernels (``ModelConfig``'s
    ``moe_*_init_scale``: seeded weights that behave like a checkpoint's).
    ``select_bias`` gives the router the float32 bias of ``_route``'s sigmoid
    form (zeros: a checkpoint's is what balancing left there)."""
    from transformer_tpu.ops.ffn import is_gated

    k_router, k_in, k_out = jax.random.split(key, 3)
    E = experts_held or num_experts
    d_expert = latent_dim or d_model

    def scaled(kernel, scale):
        return kernel if scale == 1.0 else (kernel.astype(jnp.float32) * scale).astype(param_dtype)

    def stacked(k, d_in, d_out, scale=1.0):
        return scaled(glorot_uniform(k, (E, d_in, d_out), param_dtype, d_in, d_out), scale)

    router = glorot_uniform(k_router, (d_model, num_experts), param_dtype, d_model, num_experts)
    params = {
        "router": {"kernel": scaled(router, router_scale)},
        "in": {"kernel": stacked(k_in, d_expert, dff)},
        "out": {"kernel": stacked(k_out, dff, d_expert, out_scale)},
    }
    if select_bias:
        params["router"]["bias"] = jnp.zeros((num_experts,), jnp.float32)
    if not is_gated(activation) and not dropless:
        params["in"]["bias"] = jnp.zeros((E, dff), param_dtype)
        params["out"]["bias"] = jnp.zeros((E, d_model), param_dtype)
        return params
    if is_gated(activation):
        params["gate"] = {"kernel": stacked(jax.random.fold_in(key, 3), d_expert, dff)}
    if latent_dim:
        params["latent_down"] = {"kernel": glorot_uniform(
            jax.random.fold_in(key, 5), (d_model, latent_dim), param_dtype, d_model, latent_dim)}
        params["latent_up"] = {"kernel": glorot_uniform(
            jax.random.fold_in(key, 6), (latent_dim, d_model), param_dtype, latent_dim, d_model)}
    if shared_dff:
        params["shared"] = ffn_init(
            jax.random.fold_in(key, 4), d_model, shared_dff, param_dtype,
            activation=activation, use_bias=False,
        )
    return params


def expert_capacity(
    seq_len: int, num_experts: int, top_k: int, capacity_factor: float
) -> int:
    """Token slots per (batch-row, expert): the even-split share
    ``S * k / E`` scaled by the capacity factor, at least 1, at most S."""
    even = seq_len * top_k / num_experts
    return max(1, min(seq_len, math.ceil(even * capacity_factor)))


def _route(
    params: Params, x: jax.Array, k: int, score: str = "softmax", renorm_epsilon: float = 1e-6,
    groups: int = 0, topk_groups: int = 0,
):
    """The router both dispatches share, in fp32 from the start: softmax over
    all experts, the ``k`` largest, renormalised to sum 1 (GShard's top-2
    convention; ``norm_topk_prob``). Returns (probs (..., E), gates (..., k),
    expert ids (..., k)).

    ``score="sigmoid"`` (the dropless layer alone): each expert's score is
    the sigmoid of its logit; the ``k`` experts with the largest ``score +
    bias`` are chosen (the router's ``bias`` leaf, where it has one, takes
    part in the choice and in nothing else), and the gates are the chosen
    SCORES over their sum plus ``renorm_epsilon``. ``groups`` > 1 limits the
    choice (DeepSeek-V3's group-limited routing): the experts fall into
    ``groups`` equal groups, a group scores the sum of its two largest
    ``score + bias``, and the ``k`` are chosen among the experts of the
    ``topk_groups`` best groups alone."""
    logits = jnp.einsum(
        "...m,me->...e", x.astype(jnp.float32), params["router"]["kernel"].astype(jnp.float32)
    )
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        bias = params["router"].get("bias")
        choice = scores if bias is None else scores + bias.astype(jnp.float32)
        if groups > 1:
            by_group = choice.reshape(*choice.shape[:-1], groups, -1)
            _, best = jax.lax.top_k(jax.lax.top_k(by_group, 2)[0].sum(-1), topk_groups)
            kept = jax.nn.one_hot(best, groups, dtype=jnp.bool_).any(-2)  # (..., groups)
            choice = jnp.where(kept[..., None], by_group, -jnp.inf).reshape(choice.shape)
        _, indices = jax.lax.top_k(choice, k)
        gates = jnp.take_along_axis(scores, indices, axis=-1)
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + renorm_epsilon)
        return scores, gates, indices
    probs = jax.nn.softmax(logits, axis=-1)
    gates, indices = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return probs, gates, indices


def moe_apply(
    params: Params,
    x: jax.Array,
    *,
    num_experts: int,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    activation: str = "relu",
    token_mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(B, S, M) -> ((B, S, M), aux_loss).

    Each batch row is a routing group: capacity is budgeted per row, so the
    dispatch tensors stay (B, S, E, C) and the whole layer is four einsums.
    Dropped tokens (capacity overflow) produce zero output here; the caller's
    residual connection passes their activations through unchanged.

    ``token_mask`` (B, S) bool, True = real token: PAD positions are neither
    dispatched (they'd steal capacity slots from real tokens' choices) nor
    counted in the load-balance statistics (a mostly-PAD batch would
    otherwise train the router to balance padding).
    """
    B, S, M = x.shape
    E, k = num_experts, min(top_k, num_experts)
    C = expert_capacity(S, E, k, capacity_factor)
    act = _ACTIVATIONS[activation]
    dtype = x.dtype

    # --- routing (fp32: softmax over experts + cumsum bookkeeping) ---------
    probs, gates, indices = _route(params, x, k)
    live = (
        None
        if token_mask is None
        else jnp.broadcast_to(token_mask.astype(jnp.float32), (B, S))
    )

    combine = jnp.zeros((B, S, E, C), jnp.float32)
    counts = jnp.zeros((B, E), jnp.float32)  # slots used so far, per expert
    for j in range(k):
        oh = jax.nn.one_hot(indices[..., j], E, dtype=jnp.float32)  # (B, S, E)
        if live is not None:
            oh = oh * live[..., None]  # PADs claim no slot
        # Position of each token within its chosen expert's capacity buffer:
        # tokens earlier in the sequence (and earlier choice ranks j) first.
        pos = jnp.cumsum(oh, axis=1) - oh + counts[:, None, :]  # (B, S, E)
        pos_j = jnp.sum(pos * oh, axis=-1)  # (B, S)
        fits = (pos_j < C).astype(jnp.float32) * jnp.sum(oh, axis=-1)
        counts = counts + jnp.sum(oh * fits[..., None], axis=1)
        slot = jax.nn.one_hot(pos_j.astype(jnp.int32), C, dtype=jnp.float32)  # (B, S, C)
        dispatch_j = oh[..., None] * slot[..., None, :] * fits[..., None, None]
        combine = combine + gates[..., j, None, None] * dispatch_j

    dispatch = (combine > 0).astype(dtype)  # (B, S, E, C)

    # --- expert computation (MXU matmuls in the compute dtype) -------------
    # The B dim of the slot tensors drops the 'expert' axis (tokens now live
    # on it via the E dim): that boundary is the token->expert all-to-all.
    xe = jnp.einsum("bsec,bsm->becm", dispatch, x)  # (B, E, C, M)
    xe = _constrain(xe, ("data", "fsdp"), "expert", None, None)
    h = act(
        jnp.einsum("becm,emf->becf", xe, params["in"]["kernel"].astype(dtype))
        + params["in"]["bias"].astype(dtype)[None, :, None, :]
    )
    h = _constrain(h, ("data", "fsdp"), "expert", None, "model")
    ye = (
        jnp.einsum("becf,efm->becm", h, params["out"]["kernel"].astype(dtype))
        + params["out"]["bias"].astype(dtype)[None, :, None, :]
    )
    ye = _constrain(ye, ("data", "fsdp"), "expert", None, None)
    y = jnp.einsum("bsec,becm->bsm", combine.astype(dtype), ye)
    y = _constrain(y, ("data", "fsdp", "expert"), None, None)

    # --- load-balance auxiliary loss (Switch: E * sum_e f_e * p_e) ---------
    # Statistics over REAL tokens only when a token_mask is given.
    first_choice = jax.nn.one_hot(indices[..., 0], E, dtype=jnp.float32)
    if live is None:
        f = jnp.mean(first_choice, axis=(0, 1))  # fraction routed to e
        p = jnp.mean(probs, axis=(0, 1))  # mean router prob for e
    else:
        n = jnp.maximum(jnp.sum(live), 1.0)
        f = jnp.sum(first_choice * live[..., None], axis=(0, 1)) / n
        p = jnp.sum(probs * live[..., None], axis=(0, 1)) / n
    aux = jnp.float32(E) * jnp.sum(f * p)
    return y, aux


def dropless_tile_rows(tokens: int, top_k: int, num_experts: int) -> int:
    """Rows of one tile of the grouped product: the power of two at or above
    the rows an expert expects (``tokens * top_k / num_experts``), between 16
    (a packed bf16 sublane tile) and 128 (the MXU's rows)."""
    expect = tokens * top_k / num_experts
    rows = 16
    while rows < expect and rows < 128:
        rows *= 2
    return rows


def moe_apply_dropless(
    params: Params,
    x: jax.Array,
    *,
    num_experts: int,
    top_k: int,
    expert_offset: int = 0,
    routed_scale: float = 1.0,
    score: str = "softmax",
    renorm_epsilon: float = 1e-6,
    groups: int = 0,
    topk_groups: int = 0,
    activation: str = "swiglu",
    token_mask: jax.Array | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(..., M) -> ((..., M), int32 [picks that landed here, experts hit,
    rows of the most-loaded expert, tokens with a pick here]).

    A routed layer of bias-free experts, gated or not (``activation``), that
    drops no token, told which experts it holds: the stacks in ``params`` are experts ``expert_offset ..
    expert_offset + E_held - 1`` of the router's ``num_experts``. Every token
    is routed over ALL experts (``_route``, in the form ``score`` names, in
    ``groups`` where given); its picks that fall on an expert
    held here are grouped by expert (a stable sort by expert id, group sizes,
    each group padded to whole row tiles) and go through one grouped FFN
    (``kernels/moe_ffn.py``) that reads only the experts hit; picks that
    fall elsewhere add nothing (their chip adds them, in a deployment). The
    renormalised gate times ``routed_scale`` weighs each expert's OUTPUT; the
    shared expert, where the layer has one, is added unweighted. Where the
    layer has ``latent_down`` and ``latent_up``, the experts see the input
    projected down and their weighted sum is projected back up; the router
    and the shared expert read the input itself.

    ``token_mask`` (...,) bool, False = not a token (a free slot): routed
    nowhere, counted nowhere.
    """
    from transformer_tpu.kernels.moe_ffn import moe_expert_ffn

    lead, m = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, m)
    T = xt.shape[0]
    k = min(top_k, num_experts)
    held = params["in"]["kernel"].shape[0]
    _, gates, indices = _route(params, xt, k, score, renorm_epsilon, groups, topk_groups)  # (T, k)
    local = indices - expert_offset
    here = (local >= 0) & (local < held)
    if token_mask is not None:
        here &= token_mask.reshape(-1, 1)
    weights = jnp.where(here, gates * routed_scale, 0.0)

    # --- group the picks held here by expert; ``held`` marks the others -----
    eid = jnp.where(here, local, held).reshape(-1).astype(jnp.int32)  # (T*k,)
    picks = eid.shape[0]
    tm = dropless_tile_rows(T, k, num_experts)
    tiles = -(-picks // tm) + min(held, picks)  # sum_e ceil(n_e / tm) at most
    order = jnp.argsort(eid, stable=True)
    sorted_eid = eid[order]
    sizes = jnp.bincount(eid, length=held + 1)[:held]
    group_tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(group_tiles)
    live_tiles = tile_end[-1]
    g = jnp.minimum(sorted_eid, held - 1)
    rank = jnp.arange(picks) - (jnp.cumsum(sizes) - sizes)[g]
    dest_sorted = jnp.where(
        sorted_eid < held, (tile_end - group_tiles)[g] * tm + rank, tiles * tm
    )  # the padded row of each sorted pick; past the end = not held
    t = jnp.minimum(jnp.arange(tiles), jnp.maximum(live_tiles - 1, 0))
    tile_group = jnp.minimum(jnp.searchsorted(tile_end, t, side="right"), held - 1)

    source = jnp.full((tiles * tm,), T, jnp.int32).at[dest_sorted].set(
        (order // k).astype(jnp.int32), mode="drop"
    )
    xe = xt
    if "latent_down" in params:
        xe = jnp.einsum("tm,ml->tl", xt, params["latent_down"]["kernel"].astype(xt.dtype))
    x_pad = jnp.concatenate([xe, jnp.zeros((1, xe.shape[-1]), xt.dtype)])[source]
    out = moe_expert_ffn(
        x_pad, params["gate"]["kernel"].astype(xt.dtype) if "gate" in params else None,
        params["in"]["kernel"].astype(xt.dtype),
        params["out"]["kernel"].astype(xt.dtype), tile_group, live_tiles,
        tile_rows=tm, activation=activation, interpret=interpret,
    )
    # Back to (token, pick) order: each pick reads its expert's row; a pick
    # not held here reads row 0, which nobody may have written, and drops it.
    dest = jnp.zeros((picks,), jnp.int32).at[order].set(dest_sorted.astype(jnp.int32))
    rows = out[jnp.where(here.reshape(-1), dest, 0)].reshape(T, k, xe.shape[-1])
    rows = jnp.where(here[..., None], rows.astype(jnp.float32), 0.0)
    y = jnp.einsum("tk,tkm->tm", weights, rows).astype(xt.dtype)
    if "latent_up" in params:
        y = jnp.einsum("tl,lm->tm", y, params["latent_up"]["kernel"].astype(xt.dtype))
    if "shared" in params:
        y = y + ffn_apply(params["shared"], xt, activation)
    # A grouped product's time follows its longest group: the third count.
    # The fourth: the tokens a dispatch that sends a token once a chip sends here.
    counts = jnp.stack(
        [jnp.sum(here), jnp.sum(sizes > 0), jnp.max(sizes), jnp.sum(jnp.any(here, axis=-1))]
    ).astype(jnp.int32)
    return y.reshape(*lead, m), counts
