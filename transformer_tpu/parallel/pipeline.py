"""Pipeline parallelism: GPipe microbatch schedule over a ``pipe`` mesh axis.

No reference counterpart exists (SURVEY.md §2.4 — the reference's only
strategy is mirrored data parallelism, ``distributed_train.py:137-139``); this
is net-new TPU-native machinery. Design:

- Layer parameters for the N homogeneous layers of a stack are *stacked* on a
  leading axis and sharded over ``pipe``: each device (stage) holds
  ``N / pipe`` contiguous layers and scans over them locally.
- The batch is split into M microbatches. A ``lax.scan`` over
  ``T = M + P - 1`` ticks runs the classic GPipe schedule: at tick ``t``
  stage ``s`` processes microbatch ``t - s``; activations hop to the next
  stage via ``lax.ppermute`` over ICI (a nearest-neighbour link on a ring
  mesh axis, the same transport ring attention uses).
- Stage 0 feeds from the microbatch buffer; the last stage's outputs are
  collected and ``psum``-broadcast over ``pipe`` so every device returns the
  full output (activations are microbatch-sized, so the broadcast is cheap
  relative to the FLOPs it closes over).

The schedule runs under ``shard_map``, so it composes with the ``data`` axis
(batch-dim sharding splits the microbatches per data-parallel group and the
schedule runs identically in each group) and, via ``param_specs``, with
``fsdp``: stage-interior layer parameters stay sharded over the fsdp axis at
rest and are all-gathered **one layer at a time** inside the stage's layer
scan (ZeRO-3 style), so no device ever holds more than one layer's full
weights transiently — the pipe axis finally buys parameter-memory scaling
when stacked with fsdp. Tensor-sharding interiors over ``model`` is not
wired through this path.

Everything is differentiable: ``ppermute``/``psum`` have transposes, so
``jax.grad`` through ``pipeline_apply`` yields exactly the backward schedule
(activations are rematerialized per microbatch by XLA as usual).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

Params = Any


def stack_layer_params(layers: Sequence[Params]) -> Params:
    """Stack a list of per-layer parameter trees into one tree whose leaves
    have a leading layer axis (shardable over ``pipe``)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def unstack_layer_params(stacked: Params, num_layers: int) -> list[Params]:
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(num_layers)]


def _gather_layer(lp: Params, specs: Params | None, fsdp_axis: str) -> Params:
    """All-gather one layer's fsdp-sharded leaves to full arrays (ZeRO-3:
    done per layer inside the stage scan, so only one layer's full weights
    are ever live). ``specs`` carries each leaf's *unstacked* PartitionSpec;
    None means everything is already replicated."""
    if specs is None:
        return lp

    def gather(leaf, spec):
        for d, ax in enumerate(spec):
            if ax == fsdp_axis:
                leaf = jax.lax.all_gather(leaf, fsdp_axis, axis=d, tiled=True)
        return leaf

    return jax.tree.map(gather, lp, specs, is_leaf=lambda x: x is None)


def _stacked_params_spec(
    stacked_params: Params, param_specs: Params | None, axis: str
) -> Params:
    """shard_map specs for stage-stacked layer params: leading layer dim on
    ``axis``, plus any interior fsdp dims from ``param_specs`` (shared by the
    GPipe and 1F1B paths so their at-rest layouts cannot diverge)."""
    if param_specs is None:
        return jax.tree.map(lambda _: P(axis), stacked_params)
    return jax.tree.map(
        lambda spec: P(axis) if spec is None else P(axis, *spec),
        param_specs,
        is_leaf=lambda s: isinstance(s, P) or s is None,
    )


def pipeline_apply(
    stacked_params: Params,
    layer_fn: Callable[..., jax.Array],
    x: jax.Array,
    mb_consts: tuple[jax.Array, ...] = (),
    *,
    mesh: Mesh,
    num_microbatches: int,
    base_rng: jax.Array | None = None,
    axis: str = "pipe",
    batch_axes: tuple[str, ...] = ("data", "fsdp"),
    param_specs: Params | None = None,
    fsdp_axis: str = "fsdp",
    with_aux: bool = False,
    auto_axes: tuple[str, ...] = (),
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Run a homogeneous layer stack over ``x`` with the GPipe schedule.

    Args:
      stacked_params: layer params stacked on a leading axis of size
        ``num_layers`` (the ``pipe`` mesh axis size must divide it).
      layer_fn: ``layer_fn(layer_params, x, rng, *consts) -> x`` applying ONE
        layer; ``rng`` is None when ``base_rng`` is None (deterministic).
        With ``with_aux=True`` the contract is ``-> (x, aux_scalar)`` instead
        (e.g. a MoE layer's load-balance loss).
      x: ``(B, ...)`` activations (e.g. post-embedding ``(B, S, D)``).
      mb_consts: per-example side inputs streamed with the schedule (masks,
        cross-attention memory) — each ``(B, ...)``, microbatched like ``x``.
      num_microbatches: M; must divide the per-data-shard batch.
      base_rng: optional dropout seed; folded per (layer, microbatch) so the
        pipelined run matches a sequential run that folds the same way.
      batch_axes: mesh axes the batch dimension is sharded over.
      param_specs: optional tree of *per-layer* PartitionSpecs (no leading
        layer axis) whose ``fsdp_axis`` entries mark dims sharded over fsdp;
        those leaves stay sharded at rest and are gathered per layer inside
        the stage scan. None = stages hold their layers whole.
      auto_axes: mesh axes left OUT of the manual shard_map region (GSPMD
        keeps handling them): pass ``("model",)`` to compose the GPipe
        schedule with tensor parallelism — stage-interior layer math stays
        model-axis-sharded and XLA inserts the head/dff collectives, while
        the schedule's ppermute/psum ride the manual ``pipe`` axis.

    Returns ``(B, ...)`` outputs, replicated over ``pipe`` — plus, with
    ``with_aux``, a replicated fp32 scalar: the per-layer aux losses summed
    over layers, averaged over microbatches and batch shards (aux is a batch
    statistic, so the pipelined value is the mean of per-microbatch values —
    the same approximation gradient accumulation makes).
    """
    num_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    n_stages = mesh.shape[axis]
    if num_layers % n_stages:
        raise ValueError(
            f"pipe axis size {n_stages} must divide num_layers {num_layers}"
        )
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)

    params_spec = _stacked_params_spec(stacked_params, param_specs, axis)
    bspec = P(batch_axes)  # batch dim sharded, rest replicated
    consts_spec = tuple(P(batch_axes) for _ in mb_consts)
    rng_spec = P()

    M = num_microbatches
    T = M + n_stages - 1

    manual = tuple(a for a in mesh.axis_names if a not in auto_axes)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(params_spec, bspec, consts_spec, rng_spec),
        out_specs=(bspec, P()) if with_aux else bspec,
        check_vma=False,
        axis_names=set(manual),
    )
    def _pipelined(local_params, x_local, consts_local, rng):
        batch = x_local.shape[0]
        if batch % M:
            raise ValueError(
                f"num_microbatches {M} must divide the per-shard batch {batch}"
            )
        mb = batch // M
        x_mbs = x_local.reshape(M, mb, *x_local.shape[1:])
        consts_mbs = tuple(
            c.reshape(M, mb, *c.shape[1:]) for c in consts_local
        )
        stage = jax.lax.axis_index(axis)
        layers_per_stage = num_layers // n_stages

        def apply_stage(h, mb_idx):
            consts_mb = tuple(c[mb_idx] for c in consts_mbs)

            def one_layer(h, xs):
                local_i, lp = xs
                lp = _gather_layer(lp, param_specs, fsdp_axis)
                if base_rng is None:
                    r = None
                else:
                    global_layer = stage * layers_per_stage + local_i
                    r = jax.random.fold_in(
                        jax.random.fold_in(rng, global_layer), mb_idx
                    )
                out = layer_fn(lp, h, r, *consts_mb)
                if with_aux:
                    h, aux = out
                    return h, jnp.asarray(aux, jnp.float32)
                return out, jnp.float32(0.0)

            h, layer_aux = jax.lax.scan(
                one_layer, h, (jnp.arange(layers_per_stage), local_params)
            )
            return h, jnp.sum(layer_aux)

        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, aux_acc = carry
            mb_idx = jnp.clip(t - stage, 0, M - 1)
            inp = jnp.where(stage == 0, x_mbs[jnp.clip(t, 0, M - 1)], buf)
            out, aux = apply_stage(inp, mb_idx)
            # Only ticks where this stage holds a REAL microbatch contribute
            # aux (warm-up/drain ticks process in-flight garbage).
            valid = jnp.logical_and(t >= stage, t - stage < M)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
            if n_stages > 1:
                nxt = jax.lax.ppermute(out, axis, fwd_perm)
            else:
                nxt = out
            return (nxt, aux_acc), out

        (_, aux_acc), outs = jax.lax.scan(
            tick, (jnp.zeros_like(x_mbs[0]), jnp.float32(0.0)), jnp.arange(T)
        )
        # outs[t] on the last stage holds microbatch t-(P-1); earlier stages
        # hold in-flight garbage. Select + broadcast.
        result = outs[n_stages - 1 :]
        is_last = (stage == n_stages - 1).astype(result.dtype)
        result = jax.lax.psum(result * is_last, axis)
        result = result.reshape(batch, *x_local.shape[1:])
        if not with_aux:
            return result
        # Sum over stages (each stage saw its own layers), mean over
        # microbatches, mean over batch shards -> one replicated scalar.
        aux = jax.lax.psum(aux_acc, axis) / M
        aux = jax.lax.pmean(aux, batch_axes)
        return result, aux

    return _pipelined(stacked_params, x, mb_consts, base_rng if base_rng is not None else jax.random.PRNGKey(0))


# --------------------------------------------------------------------------
# Model-level integration: pipelined encoder/decoder stacks + full forward.
# --------------------------------------------------------------------------


def _layer_fsdp_specs(layer_params: Params, mesh: Mesh) -> Params | None:
    """Per-leaf PartitionSpecs for ONE layer's params, restricted to the fsdp
    axis (the only interior sharding the GPipe path composes with): the same
    path-suffix rules the rest layout uses (``parallel/sharding.py``), with
    model/other axes dropped. None when the mesh has no fsdp axis."""
    if mesh.shape.get("fsdp", 1) == 1:
        return None
    from transformer_tpu.parallel.sharding import param_partition_spec

    def spec_for(path, leaf):
        spec = param_partition_spec(path, leaf, mesh)
        return P(*(ax if ax == "fsdp" else None for ax in spec))

    return jax.tree_util.tree_map_with_path(spec_for, layer_params)


def pipelined_transformer_apply(
    params: Params,
    inp: jax.Array | None,
    tar: jax.Array,
    cfg,
    *,
    mesh: Mesh,
    num_microbatches: int,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    pad_id: int = 0,
    return_hidden: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Pipeline-parallel counterpart of ``models.transformer.transformer_apply``
    (same logits, no attention-weight plumbing): embedding prologue and final
    projection run replicated on every stage (they are tiny next to the layer
    stacks); the encoder and decoder layer stacks run under the GPipe schedule.

    Layer params are stacked on entry — callers that jit this (they should)
    pay that restructuring once at trace time.

    A mesh with a ``model`` axis composes: the GPipe region goes manual over
    {data, fsdp, pipe} only and the ``model`` axis stays GSPMD-auto, so
    stage-interior layer math keeps its tensor-parallel sharding (heads/dff
    on ``model``) with XLA-inserted collectives.

    MoE models (``cfg.moe_experts > 0``, homogeneous stacks only —
    ``moe_every == 1``) return ``(logits, moe_aux)`` instead of bare logits:
    the layers' load-balance losses ride the schedule as a second scan
    output (``pipeline_apply(with_aux=True)``).

    ``return_hidden=True`` stops before the vocab projection and returns the
    (B, S, d_model) decoder hiddens (post final-LN for pre-LN stacks) — the
    pipelined counterpart of ``transformer_hidden_apply``, for the chunked
    vocab-projection/CE path (``TrainConfig.loss_chunks``).
    """
    from transformer_tpu.models.decoder import decoder_layer_apply
    from transformer_tpu.models.encoder import embed_prologue, encoder_layer_apply
    from transformer_tpu.models.transformer import _logits
    from transformer_tpu.ops.masks import make_padding_mask
    from transformer_tpu.ops.nn import layernorm_apply

    if rng is None:
        r_embed_e = r_embed_d = r_enc = r_dec = None
    else:
        r_embed_e, r_embed_d, r_enc, r_dec = jax.random.split(rng, 4)

    moe = bool(cfg.moe_experts)
    # Tensor parallelism composes by exclusion: the 'model' axis stays out
    # of the manual region (GSPMD-auto), so stage interiors keep their
    # heads/dff sharding with XLA-inserted collectives.
    auto = ("model",) if mesh.shape.get("model", 1) > 1 else ()

    if cfg.decoder_only:
        self_mask = make_padding_mask(tar, pad_id)
        x = embed_prologue(
            params["decoder"]["embedding"], tar, cfg, r_embed_d, deterministic
        )
        stacked = stack_layer_params(params["decoder"]["layers"])

        def dec_layer(lp, h, r, smask):
            out = decoder_layer_apply(
                lp, h, None, smask, None, cfg, r, deterministic
            )
            return (out[0], out[4]) if moe else out[0]

        if cfg.remat:
            dec_layer = jax.checkpoint(dec_layer)
        x = pipeline_apply(
            stacked, dec_layer, x, (self_mask,),
            mesh=mesh, num_microbatches=num_microbatches, base_rng=r_dec,
            param_specs=_layer_fsdp_specs(params["decoder"]["layers"][0], mesh),
            with_aux=moe, auto_axes=auto,
        )
        if moe:
            x, aux = x
        if cfg.norm_scheme == "pre":
            x = layernorm_apply(
                params["decoder"]["final_ln"], x, cfg.layernorm_epsilon
            )
        if return_hidden:
            return (x, aux) if moe else x
        logits = _logits(params, x, cfg)
        return (logits, aux) if moe else logits

    enc_mask = make_padding_mask(inp, pad_id)
    self_mask = make_padding_mask(tar, pad_id)

    x = embed_prologue(
        params["encoder"]["embedding"], inp, cfg, r_embed_e, deterministic
    )
    enc_stacked = stack_layer_params(params["encoder"]["layers"])

    def enc_layer(lp, h, r, mask):
        out = encoder_layer_apply(lp, h, mask, cfg, r, deterministic)
        return (out[0], out[2]) if moe else out[0]

    if cfg.remat:
        # Same activation-memory lever as the sequential path (encoder_apply /
        # decoder_apply wrap their layer calls); without this the flag would
        # silently do nothing under pipeline parallelism.
        enc_layer = jax.checkpoint(enc_layer)
    enc_out = pipeline_apply(
        enc_stacked, enc_layer, x, (enc_mask,),
        mesh=mesh, num_microbatches=num_microbatches, base_rng=r_enc,
        param_specs=_layer_fsdp_specs(params["encoder"]["layers"][0], mesh),
        with_aux=moe, auto_axes=auto,
    )
    enc_aux = None
    if moe:
        enc_out, enc_aux = enc_out
    if cfg.norm_scheme == "pre":
        enc_out = layernorm_apply(
            params["encoder"]["final_ln"], enc_out, cfg.layernorm_epsilon
        )

    y = embed_prologue(
        params["decoder"]["embedding"], tar, cfg, r_embed_d, deterministic
    )
    dec_stacked = stack_layer_params(params["decoder"]["layers"])

    def dec_layer(lp, h, r, enc_mb, smask, cmask):
        out = decoder_layer_apply(
            lp, h, enc_mb, smask, cmask, cfg, r, deterministic
        )
        return (out[0], out[4]) if moe else out[0]

    if cfg.remat:
        dec_layer = jax.checkpoint(dec_layer)
    y = pipeline_apply(
        dec_stacked, dec_layer, y, (enc_out, self_mask, enc_mask),
        mesh=mesh, num_microbatches=num_microbatches, base_rng=r_dec,
        param_specs=_layer_fsdp_specs(params["decoder"]["layers"][0], mesh),
        with_aux=moe, auto_axes=auto,
    )
    if moe:
        y, dec_aux = y
    if cfg.norm_scheme == "pre":
        y = layernorm_apply(
            params["decoder"]["final_ln"], y, cfg.layernorm_epsilon
        )
    if return_hidden:
        return (y, enc_aux + dec_aux) if moe else y
    logits = _logits(params, y, cfg)
    return (logits, enc_aux + dec_aux) if moe else logits


# --------------------------------------------------------------------------
# 1F1B: interleaved forward/backward schedule with an O(stages) activation
# stash (manual autodiff — jax.grad cannot interleave backward ticks with
# forward ticks, so the engine owns its own vjp chaining).
# --------------------------------------------------------------------------


def gpipe_ticks(num_microbatches: int, num_stages: int) -> int:
    """Wall ticks of the GPipe forward schedule: M + P - 1 (its backward is
    the autodiff transpose, another M + P - 1). Bubble fraction per
    direction: (P-1)/(M+P-1)."""
    return num_microbatches + num_stages - 1

def one_f1b_ticks(num_microbatches: int, num_stages: int) -> int:
    """Wall ticks of the combined 1F1B schedule: M + 2(P-1). Each tick runs
    ONE stage-forward and ONE stage-backward on every stage (SPMD cannot
    skip work per-stage), so total compute ticks are M + 2P - 2 of (F+B)
    versus GPipe's (M + P - 1) F plus (M + P - 1) B — a slightly LONGER
    wall schedule. What 1F1B buys is memory, not ticks: microbatch i's
    stage input is stashed at tick s+i and consumed by its backward at tick
    2(P-1)+i-s, so at most ``one_f1b_stash_slots(P)`` microbatch
    activations are ever live per stage, independent of M. GPipe's
    autodiff backward stashes all M (well, M+P-1 scan residuals). At pod
    scale the bubble is shrunk by raising M, which is exactly the regime
    where GPipe's O(M) stash stops fitting and this schedule keeps working.
    """
    return num_microbatches + 2 * (num_stages - 1)

def one_f1b_stash_slots(num_stages: int) -> int:
    """Ring-buffer slots for stage-input stashes under 1F1B: 2P - 1.

    Stage s's input for microbatch i is written at tick s+i and read back
    at tick 2(P-1)+i-s; the longest lifetime (stage 0) spans 2(P-1) ticks,
    during which 2P-1 distinct microbatches get written — so a ring of
    2P-1 slots never overwrites a live entry (the same-tick write/read at
    the last stage aliases deliberately: it reads the input it just
    wrote)."""
    return 2 * num_stages - 1


def pipeline_train_1f1b(
    stacked_params: Params,
    nonlayer_params: Params,
    h0: jax.Array,
    mb_streams: tuple[jax.Array, ...],
    layer_fn: Callable,
    head_fn: Callable,
    inv_denom: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    base_rng: jax.Array | None = None,
    axis: str = "pipe",
    batch_axes: tuple[str, ...] = ("data", "fsdp"),
    param_specs: Params | None = None,
    fsdp_axis: str = "fsdp",
    auto_axes: tuple[str, ...] = (),
    grad_streams: tuple[int, ...] = (),
    with_aux: bool = False,
    aux_weight: float = 0.0,
) -> tuple[dict, jax.Array, Params, Params] | tuple[
    dict, jax.Array, Params, Params, tuple[jax.Array, ...]
]:
    """One fused forward+backward pass of a homogeneous layer stack under the
    non-interleaved 1F1B schedule, returning loss sums and gradients.

    ``auto_axes`` composes tensor parallelism exactly like ``pipeline_apply``:
    pass ``("model",)`` to keep that axis OUT of the manual region — stage
    interiors (and the loss head's vocab projection) stay model-axis-sharded
    with XLA-inserted collectives, including through the engine's internal
    ``jax.vjp``s, while the schedule's ppermute/psum ride the manual axes.

    ``with_aux`` carries a per-layer auxiliary loss (MoE load balancing)
    through the manual backward: the ``layer_fn`` contract becomes
    ``-> (h, aux_scalar)`` (matching ``pipeline_apply(with_aux=True)``),
    the objective gains ``aux_weight * aux_model`` where ``aux_model`` is
    the per-layer auxes summed over layers, averaged over microbatches and
    batch shards (exactly ``pipeline_apply``'s aux — the gradient seed for
    each layer call is therefore ``aux_weight / (M * n_batch_shards)``,
    applied through each stage vjp's second cotangent), and ``sums`` gains
    ``"moe_aux"``: ``aux_model`` itself, normalized by the engine so the
    reported metric and the gradient seed share one divisor.

    ``grad_streams`` names indices into ``mb_streams`` whose cotangents the
    engine must also return (appended as a fifth tuple element, each shaped
    and batch-sharded like its stream). This is the seq2seq hook: the
    decoder stack streams the encoder output into every layer's
    cross-attention, and its cotangent — accumulated across all decoder
    stages and microbatches — seeds the encoder backward outside.

    The engine is its own autodiff: ``jax.grad`` over the GPipe scan must
    finish ALL forwards before its transposed backward starts (that is what
    reverse-mode means), which forces the O(M)-microbatch activation stash.
    Here each scan tick runs one stage-forward AND one stage-backward
    (``jax.vjp`` of the stage, rematerialized from a stashed stage input),
    cotangents hop backward over the same ``ppermute`` ring the activations
    hop forward on, and the stash is a ``one_f1b_stash_slots(P)``-deep ring —
    activation memory is O(P), independent of M. See ``one_f1b_ticks`` for
    the tick/bubble accounting.

    Args:
      stacked_params: layer params stacked on a leading axis (sharded over
        ``axis`` by the shard_map in_spec, exactly as ``pipeline_apply``).
      nonlayer_params: the FULL parameter tree with the pipelined stack's
        layer list replaced by an empty container — embedding/final-LN/output
        leaves replicated into every stage (the loss head needs them; grads
        for them are psum'd over ``axis`` + ``batch_axes``).
      h0: (B_local, S, D) post-prologue activations (prologue runs OUTSIDE,
        under plain GSPMD, so its params may keep any sharding; its backward
        chains through the returned ``d_h0``).
      mb_streams: per-example side inputs, each (B_local, ...) — microbatched
        like ``h0`` and handed to ``layer_fn``/``head_fn`` per microbatch
        (token ids for mask building, shifted targets for the loss).
      layer_fn: ``layer_fn(lp, h, rng|None, *streams_mb) -> h`` for ONE layer.
      head_fn: ``head_fn(nonlayer_params, h_out_mb, *streams_mb, inv_denom)
        -> (objective_scalar, sums_dict)`` — the loss head applied to the
        last stage's output microbatch. ``objective`` must already be scaled
        so cotangent seed 1.0 yields final-normalization gradients
        (i.e. objective = loss_sum * inv_denom); ``sums_dict`` carries fp32
        scalars {"loss_sum", "weight", "correct"}.
      inv_denom: fp32 scalar, 1/denominator of the loss normalization
        (computed OUTSIDE over the full batch: per-microbatch normalizers
        would weight microbatches wrongly under "tokens" normalization).

    Returns ``(sums, d_h0, d_stacked, d_nonlayer)``:
      sums: global fp32 scalars {"loss_sum", "weight", "correct"}, plus
        "moe_aux" (the normalized model-level aux) when ``with_aux``.
      d_h0: cotangent of ``h0`` (batch-sharded like ``h0``) — feed it to the
        prologue's ``jax.vjp`` to finish the chain.
      d_stacked: gradient tree like ``stacked_params`` (stage-sharded).
      d_nonlayer: gradient tree like ``nonlayer_params`` (replicated).

    Numerics match the GPipe + autodiff path up to summation order: the same
    per-(layer, microbatch) rng folding, the same stage math, gradients
    accumulated per microbatch instead of transposed en bloc.
    """
    num_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    n_stages = mesh.shape[axis]
    if num_layers % n_stages:
        raise ValueError(
            f"pipe axis size {n_stages} must divide num_layers {num_layers}"
        )
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)

    # fsdp composition (ZeRO-3): layer leaves stay fsdp-sharded at rest and
    # are all-gathered one layer at a time inside stage_fwd; the gather's
    # vjp is a reduce_scatter, which both SUMS gradient contributions
    # across the fsdp shards (each holds different microbatch rows — fsdp
    # is a batch axis too) and re-shards them to the at-rest layout. Same
    # machinery as the GPipe path.
    params_spec = _stacked_params_spec(stacked_params, param_specs, axis)
    nonlayer_spec = jax.tree.map(lambda _: P(), nonlayer_params)
    bspec = P(batch_axes)
    streams_spec = tuple(P(batch_axes) for _ in mb_streams)

    M = num_microbatches
    T = one_f1b_ticks(M, n_stages)
    S_buf = one_f1b_stash_slots(n_stages)
    layers_per_stage = num_layers // n_stages
    # The scan carry accumulates the RAW aux sum ("moe_aux_sum"); the
    # returned dict carries the normalized "moe_aux" (the engine owns the
    # divisor so the metric can never drift from the gradient seed below).
    sum_keys = ("loss_sum", "weight", "correct") + (
        ("moe_aux_sum",) if with_aux else ()
    )
    out_sum_keys = ("loss_sum", "weight", "correct") + (
        ("moe_aux",) if with_aux else ()
    )
    sums_spec = {k: P() for k in out_sum_keys}
    # d(objective)/d(one layer call's aux): the model-level aux is the mean
    # over microbatches AND batch shards of per-call sums (pipeline_apply's
    # definition), entering the objective with coefficient aux_weight.
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]
    aux_seed = jnp.float32(aux_weight / (M * n_batch_shards))
    manual = tuple(a for a in mesh.axis_names if a not in auto_axes)
    out_specs = (sums_spec, bspec, params_spec, nonlayer_spec)
    if grad_streams:
        out_specs = out_specs + (tuple(bspec for _ in grad_streams),)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(params_spec, nonlayer_spec, bspec, streams_spec, P(), P()),
        out_specs=out_specs,
        check_vma=False,
        axis_names=set(manual),
    )
    def _engine(local_params, nonlayer, h0_local, streams_local, rng, inv_d):
        batch = h0_local.shape[0]
        if batch % M:
            raise ValueError(
                f"num_microbatches {M} must divide the per-shard batch {batch}"
            )
        mb = batch // M
        h_mbs = h0_local.reshape(M, mb, *h0_local.shape[1:])
        streams_mbs = tuple(
            s.reshape(M, mb, *s.shape[1:]) for s in streams_local
        )
        stage = jax.lax.axis_index(axis)
        is_last = stage == n_stages - 1
        is_first = stage == 0

        def stage_fwd(lp, h, mb_idx, streams_mb):
            """-> (h, aux_sum): aux is this stage's layer auxes summed (a
            constant 0.0 the compiler drops when with_aux is off)."""

            def one_layer(h, xs):
                local_i, layer_p = xs
                # ZeRO-3: gather this one layer's fsdp-sharded leaves to
                # full arrays just-in-time (no-op when param_specs is None).
                layer_p = _gather_layer(layer_p, param_specs, fsdp_axis)
                if base_rng is None:
                    r = None
                else:
                    global_layer = stage * layers_per_stage + local_i
                    r = jax.random.fold_in(
                        jax.random.fold_in(rng, global_layer), mb_idx
                    )
                out = layer_fn(layer_p, h, r, *streams_mb)
                if with_aux:
                    h_out, aux = out
                    return h_out, jnp.asarray(aux, jnp.float32)
                return out, jnp.float32(0.0)

            h, layer_aux = jax.lax.scan(
                one_layer, h, (jnp.arange(layers_per_stage), lp)
            )
            return h, jnp.sum(layer_aux)

        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]
        bwd_perm = [(i + 1, i) for i in range(n_stages - 1)]

        def masked_add(acc, g, valid):
            return jax.tree.map(
                lambda a, x: a + jnp.where(valid, x, 0).astype(a.dtype), acc, g
            )

        def tick(carry, t):
            fwd_buf, bwd_buf, stash, d_stk, d_non, sums = carry

            # ---- forward half: stage s runs F of microbatch t - s ----
            f_mb = t - stage
            f_c = jnp.clip(f_mb, 0, M - 1)
            streams_f = tuple(s[f_c] for s in streams_mbs)
            inp = jnp.where(is_first, h_mbs[f_c], fwd_buf)
            # Ring-stash the stage INPUT (backward rematerializes from it).
            # Unconditional write: slot f_c % S_buf is free by construction
            # (one_f1b_stash_slots) and garbage ticks write garbage that is
            # overwritten before any valid backward reads it.
            stash = jax.lax.dynamic_update_index_in_dim(
                stash, inp, f_c % S_buf, 0
            )
            # Forward-half aux is discarded: the backward half recomputes it
            # (rematerialization) where the valid-tick masking lives.
            out, _ = stage_fwd(local_params, inp, f_c, streams_f)
            fwd_nxt = (
                jax.lax.ppermute(out, axis, fwd_perm) if n_stages > 1 else out
            )

            # ---- backward half: stage s runs B of microbatch
            #      t - 2(P-1) + s, rematerializing its forward ----
            b_mb = t - 2 * (n_stages - 1) + stage
            b_valid = jnp.logical_and(b_mb >= 0, b_mb < M)
            b_c = jnp.clip(b_mb, 0, M - 1)
            streams_b = tuple(s[b_c] for s in streams_mbs)
            x_in = stash[b_c % S_buf]
            # The vjp also covers the grad_streams operands (e.g. the
            # encoder output a decoder stack cross-attends): their per-tick
            # cotangents ride the scan output and are re-indexed per stage
            # after it.
            gs_b = tuple(streams_b[i] for i in grad_streams)

            def fwd_for_vjp(lp, h, gs):
                merged = list(streams_b)
                for idx, val in zip(grad_streams, gs):
                    merged[idx] = val
                return stage_fwd(lp, h, b_c, tuple(merged))

            (h_out_rec, aux_rec), stage_vjp = jax.vjp(
                fwd_for_vjp, local_params, x_in, gs_b
            )
            # Loss head on the (recomputed) last-stage output: its vjp both
            # seeds the backward chain and yields the head-param grads.
            _, head_vjp, head_sums = jax.vjp(
                lambda nl, h: head_fn(nl, h, *streams_b, inv_d),
                nonlayer, h_out_rec, has_aux=True,
            )
            d_non_mb, d_head_h = head_vjp(jnp.float32(1.0))
            d_out = jnp.where(is_last, d_head_h.astype(bwd_buf.dtype), bwd_buf)
            # Second cotangent: the aux objective term seeds EVERY stage's
            # backward (garbage-tick contributions die in the masked adds).
            d_lp, d_in, d_gs = stage_vjp((d_out, aux_seed))
            d_stk = masked_add(d_stk, d_lp, b_valid)
            d_non = masked_add(d_non, d_non_mb, jnp.logical_and(b_valid, is_last))
            head_mask = jnp.logical_and(b_valid, is_last)
            new_sums = {
                k: sums[k] + jnp.where(head_mask, head_sums[k], 0.0)
                for k in head_sums
            }
            if with_aux:
                # Aux accumulates at every stage (each owns its layers'
                # auxes), not just the loss-head stage.
                new_sums["moe_aux_sum"] = sums["moe_aux_sum"] + jnp.where(
                    b_valid, aux_rec, 0.0
                )
            sums = new_sums
            bwd_nxt = (
                jax.lax.ppermute(d_in, axis, bwd_perm) if n_stages > 1 else d_in
            )
            d_gs = tuple(
                jnp.where(b_valid, g, 0).astype(g.dtype) for g in d_gs
            )
            return (fwd_nxt, bwd_nxt, stash, d_stk, d_non, sums), (d_in, d_gs)

        zero_act = jnp.zeros_like(h_mbs[0])
        init = (
            zero_act,
            zero_act,
            jnp.zeros((S_buf, *zero_act.shape), zero_act.dtype),
            jax.tree.map(jnp.zeros_like, local_params),
            jax.tree.map(jnp.zeros_like, nonlayer),
            {k: jnp.float32(0.0) for k in sum_keys},
        )
        (_, _, _, d_stk, d_non, sums), (d_in_ticks, d_gs_ticks) = jax.lax.scan(
            tick, init, jnp.arange(T)
        )

        # Stage 0's backward for microbatch i lands at tick 2(P-1)+i: the
        # tail slice of the per-tick d_in outputs, masked to stage 0 and
        # broadcast over pipe, is d(h0) in microbatch order.
        d_h0_mbs = d_in_ticks[2 * (n_stages - 1) :]
        d_h0_mbs = jax.lax.psum(
            d_h0_mbs * is_first.astype(d_h0_mbs.dtype), axis
        )
        d_h0 = d_h0_mbs.reshape(batch, *h0_local.shape[1:])

        # grad_streams cotangents: stage s's contribution for microbatch i
        # sits at tick 2(P-1)+i-s, so a per-stage dynamic slice of length M
        # (start 2(P-1)-s, traced) re-indexes ticks -> microbatches; psum
        # over pipe then sums every stage's contribution. Batch-sharded like
        # the stream itself (no psum over batch axes).
        d_streams_out = tuple(
            jax.lax.psum(
                jax.lax.dynamic_slice_in_dim(
                    parts, 2 * (n_stages - 1) - stage, M, axis=0
                ),
                axis,
            ).reshape(batch, *parts.shape[2:])
            for parts in d_gs_ticks
        )

        reduce_axes = (axis,) + batch_axes
        sums = {k: jax.lax.psum(v, reduce_axes) for k, v in sums.items()}
        if with_aux:
            # Raw (stage, layer, microbatch, shard) sum -> pipeline_apply's
            # model-level definition: mean over microbatches + batch shards.
            sums["moe_aux"] = sums.pop("moe_aux_sum") / (M * n_batch_shards)
        d_non = jax.tree.map(lambda g: jax.lax.psum(g, reduce_axes), d_non)
        if batch_axes:
            if param_specs is None:
                d_stk = jax.tree.map(
                    lambda g: jax.lax.psum(g, batch_axes), d_stk
                )
            else:
                # Per-leaf reduction: a leaf sharded over fsdp already had
                # its fsdp-sum done by the gather's reduce_scatter transpose
                # (each shard now holds ITS slice of the summed grads) —
                # psum'ing it over fsdp again would add different slices.
                # Replicated leaves still need the full batch-axes sum.
                def reduce_leaf(g, spec):
                    sharded = spec is not None and fsdp_axis in tuple(spec)
                    axes = tuple(
                        a for a in batch_axes
                        if not (sharded and a == fsdp_axis)
                    )
                    return jax.lax.psum(g, axes) if axes else g

                d_stk = jax.tree.map(
                    reduce_leaf, d_stk, param_specs,
                    is_leaf=lambda x: x is None,
                )
        if grad_streams:
            return sums, d_h0, d_stk, d_non, d_streams_out
        return sums, d_h0, d_stk, d_non

    rng_in = base_rng if base_rng is not None else jax.random.PRNGKey(0)
    return _engine(
        stacked_params, nonlayer_params, h0, mb_streams, rng_in,
        jnp.asarray(inv_denom, jnp.float32),
    )
