"""Sequence-parallel execution context.

Routing problem: ``attention_impl="ring"`` is a *stack-level* transform — the
attention core must run under ``shard_map`` against the concrete device mesh,
but the model code (``ops.attention.mha_apply``) is mesh-agnostic on purpose.
Rather than threading a mesh through every ``*_apply`` signature, the
distributed engine enters this context around the jitted forward
(``parallel.distributed.make_sharded_steps``), and ``mha_apply`` reads it at
trace time. The context is only consulted while tracing, so the usual
contextvar/jit caveats don't apply: the traced program bakes in the mesh.

The reference has no counterpart (its attention materializes the full (S, S)
score tensor on one device, ``Attention.py:20`` — SURVEY §5 long-context).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class SeqParallelContext:
    mesh: Mesh
    axis: str = "seq"
    batch_axes: tuple[str, ...] = ("data", "fsdp")
    model_axis: str | None = "model"  # heads axis sharding, if the mesh has it

    @property
    def axis_size(self) -> int:
        return self.mesh.shape[self.axis]


_ctx: contextvars.ContextVar[SeqParallelContext | None] = contextvars.ContextVar(
    "sequence_parallel_context", default=None
)


@contextlib.contextmanager
def sequence_parallel(ctx: SeqParallelContext):
    """Activate sequence parallelism for every ``mha_apply`` traced inside."""
    token = _ctx.set(ctx)
    try:
        yield ctx
    finally:
        _ctx.reset(token)


def current_seq_context() -> SeqParallelContext | None:
    return _ctx.get()


def seq_parallel_attention(
    ctx: SeqParallelContext,
    impl: str,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_mask: jax.Array | None,
    causal: bool,
    window: int = 0,
) -> jax.Array:
    """Run ring/Ulysses attention over global (B, S, H, D) activations inside
    ``shard_map`` on ``ctx.mesh``: S split on the seq axis, B on the batch
    axes, heads on the model axis (transparent — attention is head-local)."""
    from transformer_tpu.parallel.ring_attention import (
        ring_attention,
        ulysses_attention,
    )

    inner = {"ring": ring_attention, "ulysses": ulysses_attention}[impl]
    sp = ctx.axis_size
    s_q, s_k = q.shape[1], k.shape[1]
    if s_q % sp or s_k % sp:
        raise ValueError(
            f"sequence lengths (q={s_q}, kv={s_k}) must be divisible by the "
            f"'{ctx.axis}' mesh axis size {sp} for sequence parallelism"
        )
    mesh = ctx.mesh
    bdim = tuple(a for a in ctx.batch_axes if mesh.shape.get(a, 1) > 1) or None
    hdim = (
        ctx.model_axis
        if ctx.model_axis and mesh.shape.get(ctx.model_axis, 1) > 1
        else None
    )
    act = P(bdim, ctx.axis, hdim, None)
    # Grouped-query kv normally rides at H_kv heads (the GQA bandwidth win
    # extends to the ring's ppermute / ulysses' all-to-all payloads): kv
    # heads block-shard over the model axis exactly like q heads, keeping
    # the per-shard group mapping aligned (q-head block i pairs with
    # kv-head block i). Two corners where that alignment is impossible fall
    # back to repeating kv to full heads (replicating kv heads under
    # sharded q heads would MISALIGN the groups, so repeat is the only
    # correct fallback): H_kv not divisible by the model axis, or — for
    # ulysses, whose all-to-all splits the head dim — by the seq axis.
    if k.shape[2] != q.shape[2]:
        model_misaligned = hdim is not None and k.shape[2] % mesh.shape[hdim]
        # Ulysses runs PER MODEL-SHARD, so its head all-to-all must divide
        # the LOCAL kv head count (global // model axis when block-sharded).
        local_kv = (
            k.shape[2]
            if model_misaligned or hdim is None
            else k.shape[2] // mesh.shape[hdim]
        )
        if model_misaligned or (impl == "ulysses" and local_kv % sp):
            reps = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, reps, axis=2)
            v = jnp.repeat(v, reps, axis=2)
    fn = functools.partial(
        inner, axis_name=ctx.axis, axis_size=sp, causal=causal, window=window
    )
    if kv_mask is None:
        sharded = shard_map(
            lambda q, k, v: fn(q, k, v),
            mesh=mesh,
            in_specs=(act, act, act),
            out_specs=act,
            check_vma=False,
        )
        return sharded(q, k, v)
    sharded = shard_map(
        lambda q, k, v, m: fn(q, k, v, kv_mask=m),
        mesh=mesh,
        in_specs=(act, act, act, P(bdim, ctx.axis)),
        out_specs=act,
        check_vma=False,
    )
    return sharded(q, k, v, kv_mask)
