"""Jaxpr-level resource cost model: bytes, FLOPs, and collective budgets.

The third analysis family (alongside rules/contracts/retrace): where the
retrace sentinel pins "the hot path compiles zero new programs" and the
contracts pin layouts, this module pins *resources* — statically, on CPU,
with zero device execution. Every canned program (the scheduler's
``_pool_step``/``_slot_prefill``/``_pool_verify``/``_slot_restore``, the
train step, and the explicit-collective sharded programs from
``analysis/sharding.py``) is traced with ``jax.make_jaxpr`` over abstract
inputs and measured:

- **peak_bytes** — peak live-buffer bytes via liveness over the equation
  list: non-donated inputs and constants are caller-held for the whole
  program, donated inputs and intermediates die at their last use, and a
  call-like equation (pjit/scan/while/cond/custom_vjp) contributes the max
  of its output bytes and its sub-jaxpr's own transient peak. This is a
  deterministic, hand-computable model of XLA's allocator, not a promise of
  its exact watermark — the point is that a +1-buffer regression moves the
  number by that buffer's size, every time, before any TPU sees the code.
- **flops** — 2·M·N·K per ``dot_general`` (batch dims multiplied through),
  2·|out|·(C_in/groups · prod(kernel)) per convolution, |operand| per
  ``reduce_*`` — the dot/conv/reduce accounting the arithmetic-intensity
  argument needs (Fast Transformer Decoding, PAPERS.md: decode is
  memory-bound precisely because this number is small per byte moved).
- **bytes_moved** — Σ over equations of operand + result bytes: an upper
  bound proxy for HBM traffic (XLA fuses; real traffic is lower — the
  model is for *regression deltas*, not absolute bandwidth claims).
- **arithmetic intensity** — flops / bytes_moved.
- **collectives** — the per-program collective inventory
  (``sharding.collective_inventory``): kind, mesh axis, scan-weighted
  count, estimated comm bytes. Single-chip serving programs pin the EMPTY
  set — a stray ``all_gather`` in the decode loop is a baseline failure,
  the static cousin of lint TPA204.

**KV budgets** — ``kv_cache_bytes`` prices the serve pool's dense
``max_len × slots`` KV layout per cache variant (plain/int8/rolling/GQA):
bytes per slot, bytes per token, and the MQA/GQA ratio the one-write-head
paper (PAPERS.md) argues from. This is the number the paged-KV refactor
(ROADMAP) will be measured against — today's waste, pinned in the repo.

**Baseline workflow** — ``analysis/costs_baseline.json`` stores every
program's gated numbers; ``python -m transformer_tpu.analysis costs``
fails when peak bytes or KV bytes-per-slot INCREASE or the collective set
grows (decreases are reported as improvements and only rewritten by
``--update-baseline``, same grandfather loop as the lint baselines).
FLOPs/bytes_moved are reported and diffed but not gated — they drift with
jax lowering versions; memory and collectives are the budgets that page
operators at 3am.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Iterable

from transformer_tpu.analysis.sharding import (
    _aval_bytes,
    _sub_jaxprs,
    canned_sharded_programs,
    collective_inventory,
)

# Primitives whose cost the FLOP model prices (the ISSUE's dot/conv/reduce
# scope — elementwise ops are bandwidth, not FLOP, stories).
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin",
})

# Call-like primitives: their params carry sub-jaxprs whose transient peak
# exceeds their output bytes (scan carries, pjit bodies).
_CALL_PRIMS = frozenset({
    "pjit", "closed_call", "core_call", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "remat", "remat2", "checkpoint", "scan", "while",
    "cond", "shard_map", "custom_partitioning",
})


@dataclasses.dataclass
class CostReport:
    """Resource profile of one traced program."""

    name: str
    peak_bytes: int
    flops: int
    bytes_moved: int
    collectives: dict[str, dict[str, int]]
    arg_bytes: int
    out_bytes: int
    extras: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def intensity(self) -> float:
        return round(self.flops / self.bytes_moved, 4) if self.bytes_moved else 0.0

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "peak_bytes": self.peak_bytes,
            "flops": self.flops,
            "bytes_moved": self.bytes_moved,
            "arithmetic_intensity": self.intensity,
            "collectives": self.collectives,
            "arg_bytes": self.arg_bytes,
            "out_bytes": self.out_bytes,
            **self.extras,
        }


# ==========================================================================
# per-equation FLOPs


def _dot_flops(eqn) -> int:
    ((lhs_c, rhs_c), (lhs_b, rhs_b)) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = 1
    for d in lhs_b:
        batch *= int(lhs[d])
    k = 1
    for d in lhs_c:
        k *= int(lhs[d])
    m = 1
    for i, d in enumerate(lhs):
        if i not in lhs_c and i not in lhs_b:
            m *= int(d)
    n = 1
    for i, d in enumerate(rhs):
        if i not in rhs_c and i not in rhs_b:
            n *= int(d)
    return 2 * batch * m * n * k


def _conv_flops(eqn) -> int:
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape  # kernel
    groups = int(eqn.params.get("feature_group_count", 1))
    out_size = 1
    for d in out:
        out_size *= int(d)
    # kernel = (spatial..., C_in/groups, C_out) in whatever dim order; the
    # product over all non-C_out dims is C_in/groups * prod(spatial).
    dn = eqn.params.get("dimension_numbers")
    rhs_spec = getattr(dn, "rhs_spec", None)
    if rhs_spec is not None:
        k_per_out = 1
        for i, d in enumerate(rhs):
            if i != rhs_spec[0]:  # rhs_spec[0] is the out-feature dim
                k_per_out *= int(d)
    else:
        k_per_out = 1
        for d in rhs:
            k_per_out *= int(d)
    del groups  # C_in/groups is already rhs's in-feature dim
    return 2 * out_size * k_per_out


def _eqn_flops(eqn) -> int:
    name = eqn.primitive.name
    if name == "dot_general":
        return _dot_flops(eqn)
    if name.startswith("conv_general"):
        return _conv_flops(eqn)
    if name in _REDUCE_PRIMS:
        return sum(
            _aval_bytes(v.aval) // max(1, _itemsize(v.aval))
            for v in eqn.invars
            if hasattr(v, "aval")
        )
    return 0


def _itemsize(aval) -> int:
    import numpy as np

    dtype = getattr(aval, "dtype", None)
    return np.dtype(dtype).itemsize if dtype is not None else 1


# ==========================================================================
# liveness / peak bytes


def _is_var(v) -> bool:
    from jax.extend.core import Literal

    return not isinstance(v, Literal)


def _peak_extra(jaxpr) -> int:
    """Transient peak of a sub-jaxpr counting ONLY its constants,
    intermediates, and outputs — the inputs are the caller's buffers and are
    already counted live at the call site."""
    persistent = sum(_aval_bytes(v.aval) for v in jaxpr.constvars)
    return persistent + _liveness_peak(jaxpr, initial_alive={})


def _liveness_peak(jaxpr, initial_alive: dict[Any, int]) -> int:
    """Max over equations of (alive-before + equation transient). ``alive``
    tracks buffers that die at their last use (donated inputs and
    intermediates); vars never entered into ``alive`` (non-donated inputs,
    a sub-jaxpr's inputs) are someone else's accounting."""
    out_set = {v for v in jaxpr.outvars if _is_var(v)}
    last_use: dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if _is_var(v):
                last_use[v] = i
    alive = dict(initial_alive)
    peak = sum(alive.values())
    for i, eqn in enumerate(jaxpr.eqns):
        out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
        transient = out_bytes
        if eqn.primitive.name in _CALL_PRIMS:
            # max, not sum: _peak_extra already holds the sub-jaxpr's
            # outputs live at its end, and those ARE this call's outvars.
            for value in eqn.params.values():
                for sub in _sub_jaxprs(value):
                    transient = max(transient, _peak_extra(sub))
        peak = max(peak, sum(alive.values()) + transient)
        # outputs become live if anything later (or the caller) reads them
        for v in eqn.outvars:
            if v in out_set or last_use.get(v, -1) > i:
                alive[v] = _aval_bytes(v.aval)
        # buffers whose last use was this equation die (outputs survive)
        for v in list(alive):
            if v not in out_set and last_use.get(v, -1) <= i:
                del alive[v]
    return max(peak, sum(alive.values()))


def _pallas_grid_size(eqn) -> int:
    """Total grid steps of a ``pallas_call`` equation (1 if unknown)."""
    grid = getattr(eqn.params.get("grid_mapping"), "grid", None) or ()
    n = 1
    for d in grid:
        try:
            n *= int(d)
        except TypeError:  # symbolic / dynamic dims: leave unweighted
            return 1
    return max(1, n)


def _walk_eqns_hbm(jaxpr, weight: int = 1, in_kernel: bool = False):
    """``walk_eqns_weighted`` with Pallas awareness: yields ``(eqn, weight,
    in_kernel)``. A kernel BODY's equations run once per grid step (weight
    multiplied by the grid size — that is what their FLOPs cost), but their
    ref reads/writes move VMEM, not HBM: the ``pallas_call`` equation
    itself, priced once over its operands and outputs, is the program's HBM
    statement — exactly the proxy the gather path gets from its ``take``
    equations. (``pl.when``-guarded steps still count: the weighting is a
    static upper bound, same spirit as the scan trip-count multiply.)"""
    for eqn in jaxpr.eqns:
        yield eqn, weight, in_kernel
        mult = weight
        kernel = in_kernel
        if eqn.primitive.name == "scan":
            mult = weight * int(eqn.params.get("length", 1))
        elif eqn.primitive.name == "pallas_call":
            kernel = True
            mult = weight * _pallas_grid_size(eqn)
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from _walk_eqns_hbm(sub, mult, kernel)


def pallas_call_flops(eqn, outer_weight: int = 1) -> int:
    """Grid-weighted FLOPs of ONE ``pallas_call`` equation, priced with the
    SAME walk/pricing helpers ``jaxpr_costs`` uses — the kernel verifier
    (analysis/kernels.py) reports this number, so the two families cannot
    drift (tests assert the totals agree eqn-for-eqn)."""
    total = 0
    mult = outer_weight * _pallas_grid_size(eqn)
    for value in eqn.params.values():
        for sub in _sub_jaxprs(value):
            for e, w, _ in _walk_eqns_hbm(sub, mult, True):
                total += w * _eqn_flops(e)
    return total


def jaxpr_costs(
    name: str,
    closed,
    donated_invars: set | None = None,
    axis_sizes: dict[str, int] | None = None,
) -> CostReport:
    """Cost report for a ClosedJaxpr. ``donated_invars`` is the set of
    top-level input Vars whose buffers the caller donates (they die at last
    use instead of living the whole program)."""
    jaxpr = closed.jaxpr
    donated = donated_invars or set()

    const_bytes = sum(_aval_bytes(v.aval) for v in jaxpr.constvars)
    arg_bytes = sum(_aval_bytes(v.aval) for v in jaxpr.invars)
    out_bytes = sum(
        _aval_bytes(v.aval) for v in jaxpr.outvars if hasattr(v, "aval")
    )
    held = sum(
        _aval_bytes(v.aval) for v in jaxpr.invars if v not in donated
    ) + const_bytes
    alive0 = {v: _aval_bytes(v.aval) for v in jaxpr.invars if v in donated}
    peak = held + _liveness_peak(jaxpr, initial_alive=alive0)

    flops = 0
    moved = 0
    for eqn, weight, in_kernel in _walk_eqns_hbm(jaxpr):
        flops += weight * _eqn_flops(eqn)
        if in_kernel or eqn.primitive.name in _CALL_PRIMS:
            # Call bodies are walked (don't double-count the call); Pallas
            # kernel bodies move VMEM, not HBM (the pallas_call equation
            # already priced the HBM side).
            continue
        moved += weight * (
            sum(_aval_bytes(v.aval) for v in eqn.invars if hasattr(v, "aval"))
            + sum(_aval_bytes(v.aval) for v in eqn.outvars)
        )
    return CostReport(
        name=name,
        peak_bytes=int(peak),
        flops=int(flops),
        bytes_moved=int(moved),
        collectives=collective_inventory(closed, axis_sizes),
        arg_bytes=int(arg_bytes),
        out_bytes=int(out_bytes),
    )


def program_costs(
    name: str,
    fn: Callable,
    *args,
    donate_argnums: Iterable[int] = (),
    axis_sizes: dict[str, int] | None = None,
) -> CostReport:
    """Trace ``fn`` over abstract ``args`` (ShapeDtypeStructs — zero device
    execution) and price the jaxpr. ``donate_argnums`` mirrors ``jax.jit``
    donation: those arguments' flattened leaves die at last use."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    donated: set = set()
    donate = set(donate_argnums)
    if donate:
        flat_counts = [len(jax.tree_util.tree_leaves(a)) for a in args]
        offset = 0
        invars = closed.jaxpr.invars
        for i, count in enumerate(flat_counts):
            if i in donate:
                donated.update(invars[offset : offset + count])
            offset += count
    return jaxpr_costs(name, closed, donated, axis_sizes)


# ==========================================================================
# KV budgets


def kv_cache_bytes(cfg, max_total: int) -> dict[str, Any]:
    """Device bytes of ONE slot's dense KV cache (every per-position buffer
    in the cache's own storage layout — int8 codes + fp32 scales, GQA head
    counts, rolling-window buffer lengths), plus the derived per-token
    cost. This is the ``max_len × slots`` waste the paged-KV refactor will
    be measured against."""
    import jax

    from transformer_tpu.models.decoder import init_decoder_caches
    from transformer_tpu.ops.attention import kv_buffer_keys

    caches = jax.eval_shape(lambda: init_decoder_caches(cfg, 1, max_total))
    per_slot = 0
    buf_len = max_total
    for layer in caches:
        for key in kv_buffer_keys(layer):
            aval = layer[key]
            per_slot += _aval_bytes(aval)
            buf_len = int(aval.shape[1])
    return {
        "bytes_per_slot": int(per_slot),
        "bytes_per_token": int(per_slot // max(1, buf_len)),
        "buffer_tokens": buf_len,
        "max_total": max_total,
        "layers": len(caches),
    }


def kv_pool_bytes(
    cfg, max_total: int, num_slots: int, pool_blocks: int, block_tokens: int
) -> dict[str, Any]:
    """Device bytes of the PAGED pool amortized per slot: the pool is
    shared, so bytes/slot = pool bytes / slots — the number that must be
    SMALLER than the dense ``kv_cache_bytes`` figure whenever the pool is
    provisioned below ``slots x max_total`` (the refactor's banked win;
    gated per paged program via ``kv_bytes_per_slot``)."""
    import jax

    from transformer_tpu.ops.attention import init_block_pool, kv_buffer_keys

    pool = jax.eval_shape(
        lambda: [
            init_block_pool(
                pool_blocks, block_tokens, cfg.kv_heads, cfg.head_dim,
                cfg.compute_dtype, quantize=cfg.kv_cache_int8,
            )
            for _ in range(cfg.num_layers)
        ]
    )
    total = sum(
        _aval_bytes(layer[key]) for layer in pool for key in kv_buffer_keys(layer)
    )
    return {
        "bytes_per_slot": int(total // max(1, num_slots)),
        "bytes_per_token": int(
            total // max(1, pool_blocks * block_tokens)
        ),
        "pool_bytes": int(total),
        "pool_blocks": pool_blocks,
        "block_tokens": block_tokens,
        "max_total": max_total,
        "layers": len(pool),
    }


# ==========================================================================
# canned programs


_SERVE_SLOTS = 2
_SERVE_TOTAL = 32
_VERIFY_W = 4
_PREFILL_LEN = 8
_RESTORE_BLOCK = 4
# Paged-pool canned sizing (the banked WIN): blocks of _PAGED_BLOCK tokens,
# pool provisioned for HALF the dense worst case — slot cost proportional
# to used tokens is the whole point, and the budget gate fails if a
# regression re-densifies it (kv_bytes_per_slot increase).
_PAGED_BLOCK = 8
_PAGED_POOL_BLOCKS = 1 + _SERVE_SLOTS * (_SERVE_TOTAL // 2 // _PAGED_BLOCK)

# The serving cache variants (analysis/configs.py FAST_MATRIX): plain bf16,
# int8+scales, rolling window, grouped-query.
SERVE_VARIANTS = ("lm_bf16", "lm_int8_cache", "lm_window", "lm_gqa")
# Paged layout refuses rolling windows (absolute-position rows are evicted
# on wrap) — the other three variants store their layouts inside blocks.
PAGED_VARIANTS = ("lm_bf16", "lm_int8_cache", "lm_gqa")


def _abstract_model(cfg):
    import jax
    import numpy as np

    from transformer_tpu.models.transformer import transformer_init

    key = jax.ShapeDtypeStruct((2,), np.uint32)
    return jax.eval_shape(lambda k: transformer_init(k, cfg), key)


def canned_cost_reports() -> tuple[list[CostReport], list[str]]:
    """Cost reports for every canned program, plus the names skipped on
    this host (sharded programs need >= 2 devices)."""
    import jax
    import numpy as np

    from transformer_tpu.analysis.configs import FAST_MATRIX, TINY_TRAIN
    from transformer_tpu.models.decoder import init_decoder_caches
    from transformer_tpu.ops.attention import slice_kv_blocks
    from transformer_tpu.serve import scheduler as sched
    from transformer_tpu.serve.scheduler import abstract_pool_caches

    reports: list[CostReport] = []
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731

    # -- the decode hot loop, per cache variant -----------------------------
    for variant in SERVE_VARIANTS:
        cfg = FAST_MATRIX[variant]
        params = _abstract_model(cfg)
        pool = abstract_pool_caches(cfg, _SERVE_SLOTS, _SERVE_TOTAL)
        step_raw = sched._pool_step.__wrapped__
        r = program_costs(
            f"serve.pool_step[{variant}]",
            lambda p, c, t: step_raw(p, c, t, cfg),
            params, pool, i32(_SERVE_SLOTS),
            donate_argnums=(1,),  # mirrors _pool_step's donate_argnums=(1,)
        )
        kv = kv_cache_bytes(cfg, _SERVE_TOTAL)
        r.extras["kv_bytes_per_slot"] = kv["bytes_per_slot"]
        reports.append(r)

    # -- the PAGED decode hot loop, per non-rolling variant -----------------
    # kv_bytes_per_slot here is the banked paged-KV win: the pool is
    # provisioned for half the dense worst case, so a regression that
    # re-densifies the layout (or silently re-inflates the pool) fails the
    # budget gate the moment it lands.
    from transformer_tpu.serve.scheduler import abstract_paged_pool

    for variant in PAGED_VARIANTS:
        cfg = FAST_MATRIX[variant]
        params = _abstract_model(cfg)
        pool, table, index = abstract_paged_pool(
            cfg, _SERVE_SLOTS, _SERVE_TOTAL, _PAGED_POOL_BLOCKS, _PAGED_BLOCK
        )
        step_raw = sched._pool_step_paged.__wrapped__
        r = program_costs(
            f"serve.pool_step_paged[{variant}]",
            lambda p, c, tb, ix, t: step_raw(
                p, c, tb, ix, t, cfg, _PAGED_BLOCK, _SERVE_TOTAL
            ),
            params, pool, table, index, i32(_SERVE_SLOTS),
            donate_argnums=(1,),
        )
        r.extras["kv_bytes_per_slot"] = kv_pool_bytes(
            cfg, _SERVE_TOTAL, _SERVE_SLOTS, _PAGED_POOL_BLOCKS, _PAGED_BLOCK
        )["bytes_per_slot"]
        reports.append(r)

    # -- the FUSED paged decode hot loop (--decode_kernel paged_flash) ------
    # Same shapes and donation as the gather twins, but attention reads the
    # pool buffers in place through the block table and the dense-FFN
    # sublayer is one Pallas kernel: the dense-ordered gathered view (one
    # full pool pass written then re-read per step) and the per-sublayer HBM
    # round trips are gone from the program, so bytes_moved DROPS vs
    # serve.pool_step_paged[...]. compare_to_baseline enforces the drop
    # STRUCTURALLY (fused < gather, per variant) on the live reports — not
    # just against the banked numbers — so un-fusing the path can never land
    # silently. interpret=False prices the real TPU program; tracing never
    # lowers, so no TPU is needed here.
    for variant in PAGED_VARIANTS:
        cfg = FAST_MATRIX[variant]
        params = _abstract_model(cfg)
        pool, table, index = abstract_paged_pool(
            cfg, _SERVE_SLOTS, _SERVE_TOTAL, _PAGED_POOL_BLOCKS, _PAGED_BLOCK
        )
        flash_raw = sched._pool_step_paged_flash.__wrapped__
        r = program_costs(
            f"serve.pool_step_paged_flash[{variant}]",
            lambda p, c, tb, ix, t: flash_raw(
                p, c, tb, ix, t, cfg, _PAGED_BLOCK, False
            ),
            params, pool, table, index, i32(_SERVE_SLOTS),
            donate_argnums=(1,),
        )
        r.extras["kv_bytes_per_slot"] = kv_pool_bytes(
            cfg, _SERVE_TOTAL, _SERVE_SLOTS, _PAGED_POOL_BLOCKS, _PAGED_BLOCK
        )["bytes_per_slot"]
        reports.append(r)

    cfg = FAST_MATRIX["lm_bf16"]
    params = _abstract_model(cfg)
    pool, table, index = abstract_paged_pool(
        cfg, _SERVE_SLOTS, _SERVE_TOTAL, _PAGED_POOL_BLOCKS, _PAGED_BLOCK
    )
    prefill_paged_raw = sched._slot_prefill_paged.__wrapped__
    reports.append(
        program_costs(
            f"serve.slot_prefill_paged[lm_bf16,n={_PREFILL_LEN}]",
            lambda p, c, tb, s, pr, st: prefill_paged_raw(
                p, c, tb, s, pr, st, cfg, 0, _PAGED_BLOCK, _SERVE_TOTAL
            ),
            params, pool, table, i32(), i32(1, _PREFILL_LEN), i32(),
        )
    )

    # -- admission, verify, restore (plain variant: the structural shapes
    # are identical across variants; the per-variant BYTES are covered by
    # the pool_step + kv_cache sections above) ------------------------------
    cfg = FAST_MATRIX["lm_bf16"]
    params = _abstract_model(cfg)
    pool = abstract_pool_caches(cfg, _SERVE_SLOTS, _SERVE_TOTAL)

    prefill_raw = sched._slot_prefill.__wrapped__
    reports.append(
        program_costs(
            f"serve.slot_prefill[lm_bf16,n={_PREFILL_LEN}]",
            lambda p, c, s, pr, st: prefill_raw(p, c, s, pr, st, cfg, 0),
            params, pool, i32(), i32(1, _PREFILL_LEN), i32(),
        )
    )

    verify_raw = sched._pool_verify.__wrapped__
    reports.append(
        program_costs(
            f"serve.pool_verify[lm_bf16,W={_VERIFY_W}]",
            lambda p, c, t: verify_raw(p, c, t, cfg),
            params, pool, i32(_SERVE_SLOTS, _VERIFY_W),
            donate_argnums=(1,),
        )
    )

    restore_raw = sched._slot_restore.__wrapped__
    blocks = jax.eval_shape(
        lambda: [
            slice_kv_blocks(c, 0, _RESTORE_BLOCK)
            for c in init_decoder_caches(cfg, 1, _SERVE_TOTAL)
        ]
    )
    reports.append(
        program_costs(
            f"serve.slot_restore[lm_bf16,blocks={_RESTORE_BLOCK}]",
            lambda c, s, b: restore_raw(c, s, b),
            pool, i32(), blocks,
        )
    )

    # -- the train step -----------------------------------------------------
    reports.append(train_step_costs(cfg, TINY_TRAIN, name="train.step[lm_bf16]"))

    # -- sharded programs (explicit collectives) ----------------------------
    programs, skipped = canned_sharded_programs()
    for name, (fn, args, axis_sizes) in programs.items():
        reports.append(program_costs(name, fn, *args, axis_sizes=axis_sizes))
    return reports, skipped


def train_step_costs(cfg, train_cfg, name: str = "train.step") -> CostReport:
    """Abstract one-optimizer-step cost (the prediction ``obs summarize``
    cross-checks against recorded ``device.memory_stats()`` samples)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from transformer_tpu.train.state import TrainState, make_optimizer
    from transformer_tpu.train.trainer import make_train_step

    step_fn = make_train_step(cfg, train_cfg)
    params = _abstract_model(cfg)
    tx = make_optimizer(cfg, train_cfg)
    state = jax.eval_shape(
        lambda p: TrainState(step=jnp.int32(0), params=p, opt_state=tx.init(p)),
        params,
    )
    B, L = train_cfg.batch_size, train_cfg.sequence_length
    ids = jax.ShapeDtypeStruct((B, L), np.int32)
    key = jax.ShapeDtypeStruct((2,), np.uint32)
    # donate_argnums=(0,) mirrors the Trainer's jit (trainer.py,
    # donate_state=True default): the incoming state's buffers are updated
    # in place, so they must not be double-counted against the new state.
    r = program_costs(name, step_fn, state, ids, ids, key, donate_argnums=(0,))
    r.extras["tokens_per_step"] = B * L
    return r


# ==========================================================================
# baseline workflow


def default_costs_baseline_path() -> str:
    from transformer_tpu.analysis.baselines import _package_root

    return os.path.join(_package_root(), "analysis", "costs_baseline.json")


def load_costs_baseline(path: str | None) -> dict:
    if path is None or not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def write_costs_baseline(
    reports: list[CostReport],
    kv: dict[str, dict],
    path: str,
    keep: dict[str, dict] | None = None,
) -> None:
    """Write the budget baseline. ``keep`` carries forward existing program
    entries that this host could not reproduce (skipped for insufficient
    devices) — an update on a small host must not silently drop the
    sharded programs' collective budgets from CI."""
    payload = {
        "programs": {
            **(keep or {}),
            **{r.name: {
                "peak_bytes": r.peak_bytes,
                "flops": r.flops,
                "bytes_moved": r.bytes_moved,
                "collectives": {
                    k: v["count"] for k, v in sorted(r.collectives.items())
                },
                **(
                    {"kv_bytes_per_slot": r.extras["kv_bytes_per_slot"]}
                    if "kv_bytes_per_slot" in r.extras
                    else {}
                ),
            }
            for r in reports
            },
        },
        "kv_cache": {
            variant: {
                "bytes_per_slot": entry["bytes_per_slot"],
                "bytes_per_token": entry["bytes_per_token"],
            }
            for variant, entry in sorted(kv.items())
        },
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


@dataclasses.dataclass
class CostsResult:
    reports: list[CostReport]
    kv: dict[str, dict]
    skipped: list[str]
    regressions: list[str]
    notes: list[str]

    @property
    def ok(self) -> bool:
        return not self.regressions

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "programs": [r.to_dict() for r in self.reports],
            "kv_cache": self.kv,
            "skipped": self.skipped,
            "regressions": self.regressions,
            "notes": self.notes,
        }


def compare_to_baseline(
    reports: list[CostReport],
    kv: dict[str, dict],
    baseline: dict,
    skipped: Iterable[str] = (),
) -> tuple[list[str], list[str]]:
    """(regressions, notes). Gated: program peak_bytes increases, KV
    bytes-per-slot/-token increases, collective-set growth (new kind/axis or
    count increase), lost or unbaselined coverage. Advisory: decreases and
    FLOP / bytes_moved drift in either direction."""
    regressions: list[str] = []
    notes: list[str] = []
    base_programs = baseline.get("programs", {})
    seen = set()
    for r in reports:
        seen.add(r.name)
        base = base_programs.get(r.name)
        if base is None:
            regressions.append(
                f"{r.name}: not in the baseline — new programs must be "
                "budgeted (run --update-baseline and commit the diff)"
            )
            continue
        if r.peak_bytes > base["peak_bytes"]:
            regressions.append(
                f"{r.name}: peak_bytes {r.peak_bytes} > budget "
                f"{base['peak_bytes']} (+{r.peak_bytes - base['peak_bytes']})"
            )
        elif r.peak_bytes < base["peak_bytes"]:
            notes.append(
                f"{r.name}: peak_bytes improved {base['peak_bytes']} -> "
                f"{r.peak_bytes} (--update-baseline to bank it)"
            )
        kv_budget = base.get("kv_bytes_per_slot")
        kv_now = r.extras.get("kv_bytes_per_slot")
        if kv_budget is not None and kv_now is not None and kv_now > kv_budget:
            regressions.append(
                f"{r.name}: kv_bytes_per_slot {kv_now} > budget {kv_budget}"
            )
        base_coll = base.get("collectives", {})
        now_coll = {k: v["count"] for k, v in r.collectives.items()}
        for key, count in sorted(now_coll.items()):
            if key not in base_coll:
                regressions.append(
                    f"{r.name}: stray collective {key} (x{count}) — not in "
                    "the budgeted set"
                )
            elif count > base_coll[key]:
                regressions.append(
                    f"{r.name}: collective {key} count {count} > budget "
                    f"{base_coll[key]}"
                )
        for key in sorted(set(base_coll) - set(now_coll)):
            notes.append(f"{r.name}: collective {key} no longer issued")
        for field in ("flops", "bytes_moved"):
            now, was = getattr(r, field), base.get(field)
            if was is not None and now != was:
                notes.append(f"{r.name}: {field} {was} -> {now} (advisory)")
    # Structural fusion gate: every fused paged step must move strictly
    # fewer bytes than its gather twin — the eliminated dense-view HBM pass
    # is THE banked win of the paged_flash kernels, and unlike the advisory
    # per-program bytes_moved drift, the fused-vs-gather ORDERING is a
    # property of the program structure, not of jax lowering versions.
    by_name = {r.name: r for r in reports}
    for name in sorted(by_name):
        if not name.startswith("serve.pool_step_paged_flash["):
            continue
        twin = by_name.get(
            name.replace("pool_step_paged_flash", "pool_step_paged")
        )
        if twin is not None and by_name[name].bytes_moved >= twin.bytes_moved:
            regressions.append(
                f"{name}: bytes_moved {by_name[name].bytes_moved} >= gather "
                f"twin's {twin.bytes_moved} ({twin.name}) — the fused kernel "
                "no longer eliminates the gathered-view HBM pass"
            )
    # Structural sharded-serving gate: the --mesh serving programs are
    # collective-free BY CONSTRUCTION (params replicate, the pool shards a
    # batch-like storage axis — serve/sharded.py) and their byte-parity
    # guarantee depends on it. Like the fused-vs-gather ordering, this is a
    # property of the program structure: even a baselined count would be
    # wrong, so any explicit collective here fails regardless of what the
    # baseline says. (GSPMD-inserted collectives are gated on the compiled
    # HLO in run_costs — tracing cannot see them.)
    for name in sorted(by_name):
        if not (name.startswith("serve.") and "mesh=" in name):
            continue
        if by_name[name].collectives:
            kinds = ", ".join(sorted(by_name[name].collectives))
            regressions.append(
                f"{name}: explicit collective(s) in the sharded serving hot "
                f"loop ({kinds}) — the --mesh byte-parity layout forbids "
                "them (serve/sharded.py)"
            )
    skipped = set(skipped)
    for name in sorted(set(base_programs) - seen):
        if name in skipped:
            notes.append(f"{name}: skipped on this host (insufficient devices)")
        else:
            regressions.append(
                f"{name}: in the baseline but no longer produced — budget "
                "coverage lost"
            )
    base_kv = baseline.get("kv_cache", {})
    for variant, entry in sorted(kv.items()):
        base_entry = base_kv.get(variant)
        if base_entry is None:
            regressions.append(
                f"kv_cache[{variant}]: not in the baseline — run "
                "--update-baseline"
            )
            continue
        for field in ("bytes_per_slot", "bytes_per_token"):
            if entry[field] > base_entry[field]:
                regressions.append(
                    f"kv_cache[{variant}]: {field} {entry[field]} > budget "
                    f"{base_entry[field]}"
                )
            elif entry[field] < base_entry[field]:
                notes.append(
                    f"kv_cache[{variant}]: {field} improved "
                    f"{base_entry[field]} -> {entry[field]}"
                )
    return regressions, notes


def run_costs(
    baseline_path: str | None = None, compare: bool = True
) -> CostsResult:
    """Compute every canned cost report + KV budget and (optionally) diff
    against the checked-in baseline."""
    from transformer_tpu.analysis.configs import FAST_MATRIX

    reports, skipped = canned_cost_reports()
    kv = {
        variant: kv_cache_bytes(FAST_MATRIX[variant], _SERVE_TOTAL)
        for variant in SERVE_VARIANTS
    }
    kv.update({
        f"{variant}_paged": kv_pool_bytes(
            FAST_MATRIX[variant], _SERVE_TOTAL, _SERVE_SLOTS,
            _PAGED_POOL_BLOCKS, _PAGED_BLOCK,
        )
        for variant in PAGED_VARIANTS
    })
    regressions: list[str] = []
    notes: list[str] = []
    if compare:
        if baseline_path is None:
            baseline_path = default_costs_baseline_path()
        baseline = load_costs_baseline(baseline_path)
        if baseline:
            regressions, notes = compare_to_baseline(
                reports, kv, baseline, skipped
            )
        else:
            notes.append(
                f"no baseline at {baseline_path} — run --update-baseline "
                "to pin budgets"
            )
        # Compiled-HLO collective gate (analysis/sharding.py): GSPMD
        # partitions AFTER tracing, so a collective it inserts into the
        # sharded decode step is invisible to every jaxpr-level number
        # above. Compile the dense mesh-2 decode twins for real and fail
        # hard on any collective op in the HLO text.
        from transformer_tpu.analysis.sharding import serving_hlo_collectives

        hlo_inventory, hlo_skipped = serving_hlo_collectives()
        for name, found in sorted(hlo_inventory.items()):
            if found:
                regressions.append(
                    f"{name}: GSPMD-inserted collective(s) in the COMPILED "
                    "decode step: "
                    + ", ".join(
                        f"{k} x{v}" for k, v in sorted(found.items())
                    )
                    + " — the sharded serving hot loop must stay "
                    "collective-free (serve/sharded.py)"
                )
            else:
                notes.append(f"{name}: compiled HLO collective-free")
        for name in hlo_skipped:
            notes.append(
                f"{name}: compiled-HLO collective gate skipped "
                "(insufficient devices)"
            )
    return CostsResult(
        reports=reports, kv=kv, skipped=skipped,
        regressions=regressions, notes=notes,
    )


def summarize(result: CostsResult) -> str:
    lines = []
    for r in result.reports:
        coll = (
            ", ".join(f"{k} x{v['count']}" for k, v in sorted(r.collectives.items()))
            or "none"
        )
        lines.append(
            f"{r.name}: peak {_fmt_bytes(r.peak_bytes)}, "
            f"{_fmt_count(r.flops)} FLOPs, {_fmt_bytes(r.bytes_moved)} moved "
            f"(intensity {r.intensity}), collectives: {coll}"
        )
    for variant, entry in sorted(result.kv.items()):
        if "pool_blocks" in entry:
            geom = (
                f"pool {entry['pool_blocks']} x {entry['block_tokens']}-token "
                f"blocks, max_total {entry['max_total']}"
            )
        else:
            geom = (
                f"buffer {entry['buffer_tokens']} of max_total "
                f"{entry['max_total']}"
            )
        lines.append(
            f"kv_cache[{variant}]: {_fmt_bytes(entry['bytes_per_slot'])}/slot, "
            f"{_fmt_bytes(entry['bytes_per_token'])}/token ({geom})"
        )
    for s in result.skipped:
        lines.append(f"SKIP {s} (needs >= 2 devices)")
    for n in result.notes:
        lines.append(f"note: {n}")
    for reg in result.regressions:
        lines.append(f"REGRESSION: {reg}")
    lines.append(
        f"{len(result.reports)} program(s), {len(result.regressions)} "
        f"regression(s)"
    )
    return "\n".join(lines)


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n}B"


def _fmt_count(n: int) -> str:
    for unit in ("", "K", "M", "G", "T"):
        if abs(n) < 1000 or unit == "T":
            return f"{n:.1f}{unit}" if unit else str(n)
        n /= 1000
    return str(n)
