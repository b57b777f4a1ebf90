"""TPA300 — abstract Pallas kernel verifier (zero device execution).

Every ``pl.pallas_call`` site in the package is discovered two ways at
once and cross-checked:

* **trace capture** — the canned programs from :mod:`.costs` (plus a few
  kernel-direct entries) are traced under a monkeypatched
  ``pallas.pallas_call`` that records grids, BlockSpecs, scratch shapes,
  operand avals and concrete scalar-prefetch values, then matched
  against the ``pallas_call`` equations in the resulting jaxprs;
* **AST discovery** — ``kernels/`` and ``ops/`` are scanned for
  ``pallas_call`` call expressions so a kernel that silently fell out of
  the canned coverage is a finding (TPA300), not a blind spot.

Three analyses run on each captured site, all on the host with no
device work:

1. **grid/BlockSpec conformance** — each index-map lambda is enumerated
   over its full grid (they are pure host Python); every block index
   must land in-bounds, block shapes must tile the array (implicit
   padding is noted), and an out-spec revisited by several grid steps
   must use ``arbitrary`` dimension semantics and guard its writes.
2. **VMEM footprint** — per grid step the in/out/scratch block bytes
   are summed (double-buffered for grid-varying specs) against a
   per-generation budget, banked per kernel in
   ``kernels_baseline.json`` with the costs-style fail-on-growth /
   ``--update-baseline`` workflow.
3. **kernel-safety lints** TPA301-305 (see docs/ANALYSIS.md) riding the
   shared :mod:`.baselines` fingerprint/suppression machinery.

The per-kernel FLOPs reported here are priced by
:func:`.costs.pallas_call_flops` — the same walk ``jaxpr_costs`` uses —
so the two families cannot drift (tests assert equality).
"""

from __future__ import annotations

import ast
import contextlib
import dataclasses
import functools
import itertools
import json
import math
import os
import sys
from typing import Any, Callable, Iterable, Sequence

import numpy as np

from .baselines import Finding, _package_root, line_suppressed

# ---------------------------------------------------------------------------
# Budgets
# ---------------------------------------------------------------------------

_MIB = 1024 * 1024

#: Usable VMEM per TensorCore by TPU generation (conservative: the
#: compiler reserves a slice of the architectural 16/32 MiB for spills).
VMEM_BUDGETS: dict[str, int] = {
    "v4": 16 * _MIB,
    "v5e": 16 * _MIB,
    "v5p": 16 * _MIB,
    "v6e": 32 * _MIB,
}

#: ROADMAP bench target is "TPU v5 lite".
DEFAULT_GENERATION = "v5e"

#: Native (sublane, lane) tile by element byte-width: fp32 (8,128),
#: bf16 (16,128), int8/fp8 (32,128).
_SUBLANE_BY_ITEMSIZE = {8: 8, 4: 8, 2: 16, 1: 32}
_LANE = 128

#: Full-grid index-map enumeration cap; larger grids are corner-sampled.
_MAX_ENUM = 4096

#: Primitives whose interpret-mode semantics diverge from compiled Mosaic
#: (TPA305).
_DIVERGENT_PRIMS = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "callback",
        "threefry2x32",
        "random_seed",
        "random_bits",
        "random_wrap",
        "random_unwrap",
        "random_fold_in",
        "rng_bit_generator",
    }
)

#: Ops that carry a masked-exp taint through (element-wise reshapes of the
#: same values); anything else drops the ("mexp", k) tag.
_MEXP_CARRIERS = frozenset(
    {
        "convert_element_type",
        "broadcast_in_dim",
        "reshape",
        "transpose",
        "squeeze",
        "copy",
    }
)

#: Reductions / contractions kill the "masked" taint: their output is a
#: statistic, not the masked lanes themselves (e.g. a running max of
#: ``_MASKED``-filled scores is a plain finite value afterwards).
_MASK_BARRIERS = frozenset(
    {
        "reduce_max",
        "reduce_min",
        "reduce_sum",
        "reduce_prod",
        "reduce_and",
        "reduce_or",
        "argmax",
        "argmin",
        "dot_general",
        "conv_general_dilated",
    }
)

_NEG_CONST_THRESHOLD = -1e20


# ---------------------------------------------------------------------------
# Capture
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _SpecView:
    """Normalized view of one BlockSpec against its operand aval."""

    role: str  # "in" | "out"
    index: int
    array_shape: tuple[int, ...]
    dtype: Any
    block_shape: tuple[int, ...]
    index_map: Callable | None
    grid_varying: bool = False  # filled by conformance
    # False for ``memory_space=ANY/HBM`` operands: the array stays where it
    # is and the kernel copies what it needs into scratch by hand, so it has
    # no block, no index map and no VMEM of its own.
    in_vmem: bool = True


@dataclasses.dataclass
class _Capture:
    """One pallas_call site captured at trace time."""

    kernel_name: str
    kernel_file: str
    kernel_line: int
    call_path: str
    call_line: int
    grid: tuple[int, ...]
    in_specs: list[Any]
    out_specs: list[Any]
    out_shapes: list[Any]  # ShapeDtypeStruct-likes
    scratch: list[dict]  # {"shape","dtype","space"}
    num_scalar_prefetch: int
    dimension_semantics: tuple[str, ...] | None
    input_output_aliases: dict[int, int]
    interpret: Any
    in_avals: list[tuple[tuple[int, ...], Any]] = dataclasses.field(default_factory=list)
    scalar_values: list[Any] = dataclasses.field(default_factory=list)
    calls: int = 1
    # What the traced pallas_call eqn is called: the call's ``name=`` (the
    # kernel's name in the compiled program and the profiler's trace) where
    # it has one, else the kernel function's. Reports and the baseline stay
    # keyed by the function's name.
    eqn_name: str = ""

    def site_key(self):
        return (
            self.kernel_name,
            self.grid,
            tuple(tuple(s["shape"]) for s in self.scratch),
            tuple(self.in_avals),
        )


def _unwrap_fn(fn):
    while isinstance(fn, functools.partial):
        fn = fn.func
    return getattr(fn, "__wrapped__", fn)


def _normalize_specs(specs) -> list[Any]:
    if specs is None:
        return []
    if isinstance(specs, (list, tuple)):
        out = []
        for s in specs:
            if isinstance(s, (list, tuple)):
                out.extend(_normalize_specs(s))
            else:
                out.append(s)
        return out
    return [specs]


def _scratch_views(scratch_shapes) -> list[dict]:
    out = []
    for s in _normalize_specs(scratch_shapes):
        shape = tuple(getattr(s, "shape", ()))
        try:
            dt = np.dtype(getattr(s, "dtype", np.float32))
        except TypeError:
            dt = np.dtype(np.float32)
        space = str(getattr(s, "memory_space", "vmem")).lower()
        out.append({"shape": shape, "dtype": dt, "space": space})
    return out


@contextlib.contextmanager
def _capture_pallas(records: list[_Capture]):
    """Monkeypatch ``pallas.pallas_call`` on the shared module object.

    Every kernel module in the package imports ``pallas as pl`` from the
    same module, so one patch point sees all call sites at trace time.
    """
    import jax
    from jax.experimental import pallas as _pallas

    # A previous trace of the same program (e.g. the costs family, or a
    # bench's own program_costs call) leaves cached sub-traces that skip
    # re-executing the Python that calls pallas_call — flush them so the
    # capture always sees every site.
    jax.clear_caches()

    real = _pallas.pallas_call

    def patched(kernel, *pargs, **kw):
        caller = sys._getframe(1)
        fn = _unwrap_fn(kernel)
        code = getattr(fn, "__code__", None)
        grid_spec = kw.get("grid_spec")
        if grid_spec is not None:
            grid = tuple(getattr(grid_spec, "grid", ()) or ())
            in_specs = _normalize_specs(getattr(grid_spec, "in_specs", None))
            out_specs = _normalize_specs(getattr(grid_spec, "out_specs", None))
            scratch = _scratch_views(getattr(grid_spec, "scratch_shapes", None))
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
        else:
            g = kw.get("grid", ())
            grid = tuple(g) if isinstance(g, (tuple, list)) else ((g,) if g else ())
            in_specs = _normalize_specs(kw.get("in_specs"))
            out_specs = _normalize_specs(kw.get("out_specs"))
            scratch = _scratch_views(kw.get("scratch_shapes"))
            nsp = 0
        cp = kw.get("compiler_params")
        sem = getattr(cp, "dimension_semantics", None)
        if sem is None and isinstance(cp, dict):
            sem = (cp.get("mosaic") or {}).get("dimension_semantics")
        sem = tuple(sem) if sem else None
        aliases = dict(kw.get("input_output_aliases") or {})
        base = _Capture(
            kernel_name=getattr(fn, "__name__", str(fn)),
            kernel_file=getattr(code, "co_filename", "<unknown>"),
            kernel_line=getattr(code, "co_firstlineno", 0),
            call_path=caller.f_code.co_filename,
            call_line=caller.f_lineno,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shapes=_normalize_specs(kw.get("out_shape")),
            scratch=scratch,
            num_scalar_prefetch=nsp,
            dimension_semantics=sem,
            input_output_aliases=aliases,
            interpret=kw.get("interpret"),
            eqn_name=kw.get("name") or getattr(fn, "__name__", str(fn)),
        )
        inner = real(kernel, *pargs, **kw)

        def wrapped(*operands):
            rec = dataclasses.replace(base)
            flat = []
            for op in operands:
                if isinstance(op, (list, tuple)):
                    flat.extend(op)
                else:
                    flat.append(op)
            rec.in_avals = [
                (tuple(np.shape(o)), np.dtype(getattr(o, "dtype", type(o))))
                for o in flat
            ]
            svals = []
            for o in flat[: rec.num_scalar_prefetch]:
                try:
                    svals.append(np.asarray(o))
                except Exception:  # tpa: disable=TPA006
                    svals.append(None)
            rec.scalar_values = svals
            records.append(rec)
            return inner(*operands)

        return wrapped

    _pallas.pallas_call = patched
    try:
        yield
    finally:
        _pallas.pallas_call = real


# ---------------------------------------------------------------------------
# Spec views + index-map enumeration
# ---------------------------------------------------------------------------


def _spec_views(cap: _Capture) -> list[_SpecView]:
    """Pair each in/out BlockSpec with its operand aval."""
    views: list[_SpecView] = []
    data_avals = cap.in_avals[cap.num_scalar_prefetch :]
    for i, spec in enumerate(cap.in_specs):
        if i < len(data_avals):
            shape, dt = data_avals[i]
        else:
            shape, dt = (), np.dtype(np.float32)
        views.append(_make_view("in", i, shape, dt, spec))
    for i, spec in enumerate(cap.out_specs):
        if i < len(cap.out_shapes):
            o = cap.out_shapes[i]
            shape = tuple(getattr(o, "shape", ()))
            dt = np.dtype(getattr(o, "dtype", np.float32))
        else:
            shape, dt = (), np.dtype(np.float32)
        views.append(_make_view("out", i, shape, dt, spec))
    return views


def _make_view(role, index, array_shape, dtype, spec) -> _SpecView:
    block = getattr(spec, "block_shape", None)
    imap = getattr(spec, "index_map", None)
    if block is None:
        block = array_shape
    else:
        block = tuple(
            array_shape[d] if b is None else int(b) for d, b in enumerate(block)
        )
    return _SpecView(
        role=role,
        index=index,
        array_shape=tuple(int(d) for d in array_shape),
        dtype=np.dtype(dtype),
        block_shape=block,
        index_map=imap,
        in_vmem=str(getattr(spec, "memory_space", None)).lower() not in ("any", "hbm"),
    )


def _grid_points(grid: tuple[int, ...]):
    """Full grid if small, else the corner/midpoint sample lattice."""
    size = int(np.prod(grid)) if grid else 1
    if not grid:
        return [()], False
    if size <= _MAX_ENUM:
        return list(itertools.product(*(range(d) for d in grid))), False
    axes = [sorted({0, d // 2, d - 1}) for d in grid]
    return list(itertools.product(*axes)), True


def _synth_scalar_args(cap: _Capture) -> list[np.ndarray]:
    """Stand-in scalar-prefetch operands when tracing gave us tracers.

    Values are kept in ``[0, lead)`` where ``lead`` is the largest
    leading dim over the data operands — for a paged block table that is
    ``num_blocks``, so synthesized ids are always legal block ids.
    """
    data_avals = cap.in_avals[cap.num_scalar_prefetch :]
    lead = max((s[0] for s, _ in data_avals if s), default=1)
    out = []
    for k in range(cap.num_scalar_prefetch):
        if k < len(cap.scalar_values) and cap.scalar_values[k] is not None:
            out.append(np.asarray(cap.scalar_values[k]))
            continue
        shape, dt = cap.in_avals[k]
        n = int(np.prod(shape)) if shape else 1
        flat = (np.arange(n) % max(lead, 1)).astype(np.dtype(dt))
        if n:
            flat[0] = max(lead - 1, 0)
        out.append(flat.reshape(shape))
    return out


@dataclasses.dataclass
class _Conformance:
    checked_points: int = 0
    sampled: bool = False
    violations: list[str] = dataclasses.field(default_factory=list)
    padding: list[str] = dataclasses.field(default_factory=list)
    revisited_out: dict[int, tuple[int, ...]] = dataclasses.field(default_factory=dict)
    # per (role, index): map from grid point -> block index (for aliases)
    maps: dict[tuple[str, int], dict] = dataclasses.field(default_factory=dict)


def _check_conformance(cap: _Capture, views: list[_SpecView]) -> _Conformance:
    res = _Conformance()
    points, sampled = _grid_points(cap.grid)
    res.sampled = sampled
    res.checked_points = len(points)
    scalars = _synth_scalar_args(cap)
    for v in views:
        if not v.in_vmem:
            continue
        tag = f"{v.role}_specs[{v.index}]"
        res.maps[(v.role, v.index)] = {}
        nblocks = [
            -(-a // b) if b else 1 for a, b in zip(v.array_shape, v.block_shape)
        ]
        for a, b in zip(v.array_shape, v.block_shape):
            if b and a % b:
                res.padding.append(
                    f"{tag}: block {v.block_shape} pads array {v.array_shape}"
                )
                break
        seen_axes_vary = [False] * max(len(cap.grid), 1)
        baseline_idx = None
        oob = 0
        for pt in points:
            try:
                idx = v.index_map(*pt, *scalars) if v.index_map else tuple(
                    0 for _ in v.array_shape
                )
            except Exception as e:  # noqa: BLE001 — report, don't crash the pass  # tpa: disable=TPA006
                res.violations.append(f"{tag}: index map raised {type(e).__name__}: {e}")
                break
            try:
                idx = tuple(int(np.asarray(d)) for d in (
                    idx if isinstance(idx, (tuple, list)) else (idx,)
                ))
            except Exception:  # tpa: disable=TPA006
                res.violations.append(f"{tag}: index map not host-evaluable at {pt}")
                break
            if len(idx) != len(v.array_shape):
                res.violations.append(
                    f"{tag}: index map rank {len(idx)} != operand rank "
                    f"{len(v.array_shape)}"
                )
                break
            for d, (i_d, n_d) in enumerate(zip(idx, nblocks)):
                if not 0 <= i_d < n_d:
                    oob += 1
                    if oob <= 3:
                        res.violations.append(
                            f"{tag}: grid point {pt} -> block index {idx} "
                            f"out of bounds in dim {d} "
                            f"(array {v.array_shape}, block {v.block_shape})"
                        )
            res.maps[(v.role, v.index)][pt] = idx
            if baseline_idx is None:
                baseline_idx = idx
            elif idx != baseline_idx:
                for a in range(len(cap.grid)):
                    ref = tuple(0 if ax == a else p for ax, p in enumerate(pt))
                    prev = res.maps[(v.role, v.index)].get(ref)
                    if prev is not None and prev != idx:
                        seen_axes_vary[a] = True
        if oob > 3:
            res.violations.append(f"{tag}: ... {oob - 3} more out-of-bounds points")
        # grid-varying = double-buffered pipelining; also drives revisit check
        varies = any(seen_axes_vary[: len(cap.grid)])
        v.grid_varying = varies and bool(cap.grid)
        if v.role == "out" and cap.grid:
            const_axes = tuple(
                a
                for a in range(len(cap.grid))
                if cap.grid[a] > 1 and not _axis_varies(res.maps[(v.role, v.index)], a)
            )
            if const_axes:
                res.revisited_out[v.index] = const_axes
    return res


def _axis_varies(mapping: dict, axis: int) -> bool:
    """True if the block index depends on grid axis ``axis``."""
    groups: dict[tuple, set] = {}
    for pt, idx in mapping.items():
        key = tuple(p for a, p in enumerate(pt) if a != axis)
        groups.setdefault(key, set()).add(idx)
    return any(len(v) > 1 for v in groups.values())


def _check_aliases(cap: _Capture, views: list[_SpecView], res: _Conformance):
    ins = {v.index: v for v in views if v.role == "in"}
    outs = {v.index: v for v in views if v.role == "out"}
    for k, val in cap.input_output_aliases.items():
        i = int(k) - cap.num_scalar_prefetch
        vi, vo = ins.get(i), outs.get(int(val))
        if vi is None or vo is None:
            continue
        if vi.block_shape != vo.block_shape:
            res.violations.append(
                f"alias in[{i}]->out[{val}]: block shapes differ "
                f"({vi.block_shape} vs {vo.block_shape})"
            )
            continue
        mi = res.maps.get(("in", i), {})
        mo = res.maps.get(("out", int(val)), {})
        for pt, idx in mi.items():
            if pt in mo and mo[pt] != idx:
                res.violations.append(
                    f"alias in[{i}]->out[{val}]: index maps diverge at {pt} "
                    f"({idx} vs {mo[pt]})"
                )
                break


# ---------------------------------------------------------------------------
# VMEM model
# ---------------------------------------------------------------------------


def _vmem_footprint(cap: _Capture, views: list[_SpecView]) -> tuple[int, dict]:
    """Per-grid-step VMEM bytes: blocks (x2 when pipelined) + scratch."""
    grid_size = int(np.prod(cap.grid)) if cap.grid else 1
    breakdown: dict[str, int] = {}
    total = 0
    for v in views:
        if not v.in_vmem:
            continue
        bytes_ = int(np.prod(v.block_shape)) * v.dtype.itemsize if v.block_shape else (
            v.dtype.itemsize
        )
        mult = 2 if (v.grid_varying and grid_size > 1) else 1
        breakdown[f"{v.role}[{v.index}]"] = bytes_ * mult
        total += bytes_ * mult
    for i, s in enumerate(cap.scratch):
        space = s["space"]
        if "smem" in space or "sem" in space:
            continue
        bytes_ = int(np.prod(s["shape"])) * s["dtype"].itemsize if s["shape"] else s[
            "dtype"
        ].itemsize
        breakdown[f"scratch[{i}]"] = bytes_
        total += bytes_
    return total, breakdown


# ---------------------------------------------------------------------------
# Body provenance engine (jaxpr walk of the kernel body)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Write:
    ref: int
    rmw: bool
    pid_guard: bool
    conditional: bool
    line: int


@dataclasses.dataclass
class _BodyFacts:
    writes: list[_Write] = dataclasses.field(default_factory=list)
    reads: set[int] = dataclasses.field(default_factory=set)
    masked_exps: dict[int, dict] = dataclasses.field(default_factory=dict)
    divergent: list[tuple[str, int]] = dataclasses.field(default_factory=list)
    _mexp_counter: int = 0


def _eqn_line(eqn) -> int:
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        return int(frame.start_line) if frame else 0
    except Exception:  # noqa: BLE001  # tpa: disable=TPA006
        return 0


def _literal_taint(var) -> frozenset:
    val = getattr(var, "val", None)
    if val is None:
        return frozenset()
    try:
        arr = np.asarray(val)
        if arr.dtype.kind == "f" and arr.size and float(arr.min()) <= (
            _NEG_CONST_THRESHOLD
        ):
            return frozenset({"negconst"})
    except Exception:  # noqa: BLE001  # tpa: disable=TPA006
        pass
    return frozenset()


def _taint_of(env, var) -> frozenset:
    if hasattr(var, "val"):  # Literal
        return _literal_taint(var)
    return env.get(var, frozenset())


def _propagate(prim_name: str, taint: frozenset) -> frozenset:
    out = set()
    for t in taint:
        if isinstance(t, tuple) and t and t[0] == "ref":
            continue  # ref identity never flows through values
        if isinstance(t, tuple) and t and t[0] == "mexp":
            if prim_name in _MEXP_CARRIERS:
                out.add(t)
            continue
        if t in ("masked", "negconst") and prim_name in _MASK_BARRIERS:
            continue
        out.add(t)
    return frozenset(out)


def _ref_ids(taint: frozenset) -> set[int]:
    return {t[1] for t in taint if isinstance(t, tuple) and t and t[0] == "ref"}


def _read_ids(taint: frozenset) -> set[int]:
    return {t[1] for t in taint if isinstance(t, tuple) and t and t[0] == "read"}


def _sub_call_jaxprs(eqn):
    """Sub-jaxprs of call-like primitives, via the shared costs helper."""
    from .costs import _sub_jaxprs

    subs = []
    for value in eqn.params.values():
        subs.extend(_sub_jaxprs(value))
    return subs


def _walk_body(jaxpr, env: dict, preds: list, facts: _BodyFacts, depth: int = 0):
    if depth > 16:
        return
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        in_taints = [_taint_of(env, v) for v in eqn.invars]
        union = frozenset().union(*in_taints) if in_taints else frozenset()
        if name == "program_id":
            for ov in eqn.outvars:
                env[ov] = frozenset({"pid"})
        elif name == "get":
            ref_ids = _ref_ids(in_taints[0])
            facts.reads |= ref_ids
            out = frozenset(("read", r) for r in ref_ids) | _propagate(name, union)
            for ov in eqn.outvars:
                env[ov] = out
        elif name in ("swap", "addupdate"):
            ref_ids = _ref_ids(in_taints[0])
            val_taint = in_taints[1] if len(in_taints) > 1 else frozenset()
            pid_guard = any("pid" in p for p in preds)
            for r in ref_ids:
                facts.writes.append(
                    _Write(
                        ref=r,
                        rmw=(name == "addupdate") or (("read", r) in val_taint),
                        pid_guard=pid_guard,
                        conditional=bool(preds),
                        line=_eqn_line(eqn),
                    )
                )
            out = frozenset(("read", r) for r in ref_ids) | _propagate(name, union)
            for ov in eqn.outvars:
                env[ov] = out
        elif name == "cond":
            pred_taint = in_taints[0]
            branches = eqn.params.get("branches", ())
            out_taints = None
            for br in branches:
                bj = getattr(br, "jaxpr", br)
                env2 = dict(env)
                for bv, ov in zip(bj.invars, eqn.invars[1:]):
                    env2[bv] = _taint_of(env, ov)
                _walk_body(bj, env2, preds + [pred_taint], facts, depth + 1)
                branch_outs = [_taint_of(env2, v) for v in bj.outvars]
                if out_taints is None:
                    out_taints = branch_outs
                else:
                    out_taints = [
                        a | b for a, b in zip(out_taints, branch_outs)
                    ]
            for ov, t in zip(eqn.outvars, out_taints or []):
                env[ov] = _propagate(name, t)
        elif name == "select_n":
            data = in_taints[1:]
            data_union = frozenset().union(*data) if data else frozenset()
            out = _propagate(name, data_union)
            if any("negconst" in d for d in data):
                out = out | frozenset({"masked"})
            for d in data:
                for t in d:
                    if isinstance(t, tuple) and t and t[0] == "mexp":
                        k = t[1]
                        if k in facts.masked_exps:
                            facts.masked_exps[k]["guarded"] = True
            for ov in eqn.outvars:
                env[ov] = out
        elif name == "exp":
            out = _propagate(name, union)
            if "masked" in union:
                k = facts._mexp_counter
                facts._mexp_counter += 1
                facts.masked_exps[k] = {"guarded": False, "line": _eqn_line(eqn)}
                out = out | frozenset({("mexp", k)})
            for ov in eqn.outvars:
                env[ov] = out
        else:
            if name in _DIVERGENT_PRIMS:
                facts.divergent.append((name, _eqn_line(eqn)))
            subs = _sub_call_jaxprs(eqn)
            walked = False
            for sub in subs:
                sj = getattr(sub, "jaxpr", sub)
                if len(sj.invars) == len(eqn.invars):
                    env2 = dict(env)
                    for bv, ov in zip(sj.invars, eqn.invars):
                        env2[bv] = _taint_of(env, ov)
                    _walk_body(sj, env2, preds, facts, depth + 1)
                    outs = [_taint_of(env2, v) for v in sj.outvars]
                    for ov, t in zip(eqn.outvars, outs):
                        env[ov] = _propagate(name, t)
                    walked = True
                    break
            if not walked:
                if subs:
                    for sub in subs:
                        sj = getattr(sub, "jaxpr", sub)
                        _walk_body(sj, {}, preds, facts, depth + 1)
                out = _propagate(name, union)
                for ov in eqn.outvars:
                    env[ov] = out


def _body_facts(body_jaxpr, gm) -> tuple[_BodyFacts, dict[int, str], dict[int, Any]]:
    """Walk a kernel body; return facts + ref-slot roles and dtypes.

    ``gm`` is the eqn's GridMapping: invars after the scalar operands are
    ordered [inputs, outputs, scratch].
    """
    n_scalar = int(getattr(gm, "num_index_operands", 0) or 0)
    n_in = int(getattr(gm, "num_inputs", 0) or 0)
    n_out = int(getattr(gm, "num_outputs", 0) or 0)
    roles: dict[int, str] = {}
    dtypes: dict[int, Any] = {}
    env: dict = {}
    for slot, var in enumerate(body_jaxpr.invars):
        env[var] = frozenset({("ref", slot)})
        if slot < n_scalar:
            roles[slot] = "scalar"
        elif slot < n_scalar + n_in:
            roles[slot] = "in"
        elif slot < n_scalar + n_in + n_out:
            roles[slot] = "out"
        else:
            roles[slot] = "scratch"
        aval = getattr(var, "aval", None)
        inner = getattr(aval, "inner_aval", aval)
        dtypes[slot] = getattr(inner, "dtype", None)
    facts = _BodyFacts()
    _walk_body(body_jaxpr, env, [], facts)
    return facts, roles, dtypes


# ---------------------------------------------------------------------------
# Lints (TPA301-305)
# ---------------------------------------------------------------------------


def _is_float(dt) -> bool:
    """Float check that also recognizes ml_dtypes (bf16 has numpy kind 'V')."""
    d = np.dtype(dt)
    if d.kind == "f":
        return True
    return "float" in d.name or d.name in ("bfloat16", "e4m3", "e5m2")


def _display_path(abs_path: str) -> str:
    base = os.path.dirname(_package_root())
    try:
        rel = os.path.relpath(abs_path, base)
    except ValueError:
        return os.path.basename(abs_path)
    if rel.startswith(".."):
        return os.path.basename(abs_path)
    return rel


def _lint_site(cap: _Capture, facts: _BodyFacts | None, roles, dtypes) -> list[Finding]:
    findings: list[Finding] = []
    path = _display_path(cap.kernel_file)
    sym = cap.kernel_name

    def add(code, line, snippet, message):
        findings.append(
            Finding(
                code=code,
                path=path,
                line=line or cap.kernel_line,
                symbol=sym,
                message=message,
                snippet=snippet,
            )
        )

    if facts is not None:
        rmw_refs = {w.ref for w in facts.writes if w.rmw}
        n_data = len(roles)
        # TPA301: read-modify-write accumulator in a sub-fp32 float scratch.
        for r in sorted(rmw_refs):
            if roles.get(r) != "scratch":
                continue
            dt = dtypes.get(r)
            if dt is not None and _is_float(dt) and np.dtype(dt).itemsize < 4:
                add(
                    "TPA301",
                    cap.kernel_line,
                    f"{sym}:scratch{r}",
                    f"accumulator scratch slot {r} is {np.dtype(dt).name}; "
                    "running softmax stats / accumulators must be float32 "
                    "to avoid catastrophic cancellation across grid steps",
                )
        # TPA302: RMW accumulator with no guarded (or unconditional) init.
        for r in sorted(rmw_refs):
            if roles.get(r) not in ("scratch", "out"):
                continue
            inits = [
                w
                for w in facts.writes
                if w.ref == r and not w.rmw and (w.pid_guard or not w.conditional)
            ]
            if not inits:
                add(
                    "TPA302",
                    cap.kernel_line,
                    f"{sym}:init{r}",
                    f"ref slot {r} is accumulated (read-modify-write) but no "
                    "initializing write is guarded by a first-grid-step "
                    "`@pl.when` (or unconditional) — carries garbage from "
                    "the previous grid iteration",
                )
        # TPA303: exp() of mask-selected scores without a guard clamp.
        for k, info in sorted(facts.masked_exps.items()):
            if not info["guarded"]:
                add(
                    "TPA303",
                    info["line"],
                    f"{sym}:exp@{k}",
                    "exp() of masked scores flows to output unguarded — "
                    "clamp with a `_MASK_GUARD` select (jnp.where(s > "
                    "_MASK_GUARD, exp(...), 0)) so -1e30 lanes cannot "
                    "produce spurious non-zero weight",
                )
        # TPA305: interpret-divergent primitives in the body.
        seen_prims = set()
        for prim, line in facts.divergent:
            if prim in seen_prims:
                continue
            seen_prims.add(prim)
            add(
                "TPA305",
                line,
                f"{sym}:{prim}",
                f"primitive `{prim}` behaves differently under "
                "`interpret=True` (CPU CI) than compiled Mosaic — parity "
                "tests cannot vouch for the TPU build",
            )
    # TPA304: last-two-dims block misaligned with the dtype's native tile.
    for v in _spec_views(cap):
        if len(v.block_shape) < 2:
            continue
        sub = _SUBLANE_BY_ITEMSIZE.get(v.dtype.itemsize, 8)
        b2, b1 = v.block_shape[-2], v.block_shape[-1]
        a2, a1 = v.array_shape[-2], v.array_shape[-1]
        bad2 = (b2 % sub != 0) and (b2 != a2)
        bad1 = (b1 % _LANE != 0) and (b1 != a1)
        if bad2 or bad1:
            add(
                "TPA304",
                cap.kernel_line,
                f"{sym}:{v.role}{v.index}",
                f"{v.role}_specs[{v.index}] block {v.block_shape} misaligned "
                f"with native ({sub},{_LANE}) tile for {v.dtype.name} "
                f"(array {v.array_shape}) — forces a Mosaic relayout",
            )
    return findings


def _check_out_race(
    cap: _Capture, conf: _Conformance, facts: _BodyFacts | None, roles
) -> list[str]:
    """Out-spec revisited across grid steps needs arbitrary semantics and
    guarded/accumulated writes."""
    violations = []
    if not conf.revisited_out:
        return violations
    for out_idx, axes in conf.revisited_out.items():
        for a in axes:
            sem = None
            if cap.dimension_semantics and a < len(cap.dimension_semantics):
                sem = str(cap.dimension_semantics[a])
            if sem is not None and "arbitrary" not in sem:
                violations.append(
                    f"out_specs[{out_idx}]: revisited across grid axis {a} "
                    f"(extent {cap.grid[a]}) but dimension_semantics[{a}] is "
                    f"{sem!r} — write race under parallel execution"
                )
        if facts is not None:
            out_slots = [s for s, role in roles.items() if role == "out"]
            out_slots.sort()
            if out_idx < len(out_slots):
                slot = out_slots[out_idx]
                writes = [w for w in facts.writes if w.ref == slot]
                unguarded = [
                    w for w in writes if not w.rmw and not w.pid_guard
                ]
                if writes and unguarded:
                    violations.append(
                        f"out_specs[{out_idx}]: revisited block is written "
                        "unconditionally (no first/last-step `@pl.when` "
                        "guard, not an accumulation) — earlier grid steps' "
                        "results are overwritten"
                    )
    return violations


# ---------------------------------------------------------------------------
# Eqn discovery + matching
# ---------------------------------------------------------------------------


def _iter_pallas_eqns(jaxpr, depth: int = 0):
    from .costs import _sub_jaxprs

    if depth > 24:
        return
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from _iter_pallas_eqns(getattr(sub, "jaxpr", sub), depth + 1)


def _has_data_dependent_loop(eqn) -> bool:
    """Whether a ``pallas_call`` body holds a ``while`` (a ``fori_loop`` whose
    bounds are traced lowers to one): its trips cannot be counted statically."""

    def walk(jaxpr) -> bool:
        for e in jaxpr.eqns:
            if e.primitive.name == "while":
                return True
            if any(walk(sub) for sub in _sub_call_jaxprs(e)):
                return True
        return False

    body = eqn.params.get("jaxpr")
    return body is not None and walk(getattr(body, "jaxpr", body))


def _eqn_kernel_name(eqn) -> str:
    return eqn.params["jaxpr"].debug_info.func_name


def _eqn_key(eqn):
    gm = eqn.params.get("grid_mapping")
    grid = tuple(getattr(gm, "grid", ()) or ())
    return (_eqn_kernel_name(eqn), grid)


def _match_sites(caps: list[_Capture], eqns: list):
    """Dedupe captures, pair each with an unclaimed eqn of the same key."""
    deduped: dict = {}
    for cap in caps:
        key = cap.site_key()
        if key in deduped:
            deduped[key].calls += 1
        else:
            deduped[key] = cap
    pool: dict = {}
    for eqn in eqns:
        pool.setdefault(_eqn_key(eqn), []).append(eqn)
    pairs = []
    for cap in deduped.values():
        key = (cap.eqn_name, cap.grid)
        bucket = pool.get(key)
        pairs.append((cap, bucket.pop(0) if bucket else None))
    return pairs


# ---------------------------------------------------------------------------
# AST discovery (TPA300)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _AstSite:
    path: str
    display: str
    line: int
    end_line: int
    symbol: str


def _ast_pallas_sites(py_path: str, display: str) -> list[_AstSite]:
    try:
        with open(py_path, "r", encoding="utf-8") as fh:
            src = fh.read()
        tree = ast.parse(src)
    except (OSError, SyntaxError):
        return []
    sites = []
    func_stack: list[tuple[str, int, int]] = []

    def visit(node, enclosing):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            enclosing = node.name
        if isinstance(node, ast.Call):
            fn = node.func
            name = None
            if isinstance(fn, ast.Attribute):
                name = fn.attr
            elif isinstance(fn, ast.Name):
                name = fn.id
            if name == "pallas_call":
                sites.append(
                    _AstSite(
                        path=py_path,
                        display=display,
                        line=node.lineno,
                        end_line=getattr(node, "end_lineno", node.lineno),
                        symbol=enclosing or "<module>",
                    )
                )
        for child in ast.iter_child_nodes(node):
            visit(child, enclosing)

    visit(tree, None)
    return sites


def _default_ast_targets() -> list[tuple[str, str]]:
    root = _package_root()
    out = []
    for sub in ("kernels", "ops"):
        d = os.path.join(root, sub)
        if not os.path.isdir(d):
            continue
        for fname in sorted(os.listdir(d)):
            if fname.endswith(".py"):
                p = os.path.join(d, fname)
                out.append((p, _display_path(p)))
    return out


# ---------------------------------------------------------------------------
# Canned entries (the package's shipped kernels, smallest honest shapes)
# ---------------------------------------------------------------------------


def _canned_entries() -> dict[str, Callable[[], tuple[Callable, tuple]]]:
    """name -> zero-arg factory returning ``(fn, args)`` to trace.

    Shapes are the smallest that exercise multi-step grids in every axis
    (so index maps and revisit/guard discipline are actually checked) and
    respect the dtype's native sublane tiling (block 8 for fp32, 16 for
    bf16) so the shipped package stays at zero TPA304 findings.
    """
    import jax
    import jax.numpy as jnp

    from transformer_tpu.analysis.configs import FAST_MATRIX
    from transformer_tpu.kernels.flash_attention import (
        _FlashConfig,
        flash_attention,
        flash_ring_step,
    )
    from transformer_tpu.kernels.paged_flash import paged_flash_attention
    from transformer_tpu.ops.ffn import fused_ln_ffn

    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    bf16 = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731

    def flash_fwd_causal():
        fn = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=True, block_q=8, block_k=8, interpret=True
        )
        return fn, (f32(1, 16, 2, 8), f32(1, 16, 2, 8), f32(1, 16, 2, 8))

    def flash_fwd_mask_bf16():
        fn = lambda q, k, v, m: flash_attention(  # noqa: E731
            q, k, v, kv_mask=m, block_q=16, block_k=16, interpret=True
        )
        return fn, (
            bf16(1, 32, 2, 8),
            bf16(1, 32, 2, 8),
            bf16(1, 32, 2, 8),
            jax.ShapeDtypeStruct((1, 32), jnp.bool_),
        )

    def flash_grad_causal():
        def loss(q, k, v):
            return jnp.sum(
                flash_attention(
                    q, k, v, causal=True, block_q=8, block_k=8, interpret=True
                ).astype(jnp.float32)
            )

        fn = jax.grad(loss, argnums=(0, 1, 2))
        return fn, (f32(1, 16, 2, 8), f32(1, 16, 2, 8), f32(1, 16, 2, 8))

    def flash_grad_gqa():
        def loss(q, k, v):
            return jnp.sum(
                flash_attention(
                    q, k, v, causal=True, block_q=8, block_k=8, interpret=True
                ).astype(jnp.float32)
            )

        fn = jax.grad(loss, argnums=(0, 1, 2))
        return fn, (f32(1, 16, 4, 8), f32(1, 16, 2, 8), f32(1, 16, 2, 8))

    def flash_ring():
        cfg = _FlashConfig(
            causal=False,
            has_mask=False,
            block_q=8,
            block_k=8,
            num_heads=2,
            scale=8**-0.5,
            interpret=True,
        )
        fn = lambda q, k, v, m, l, acc: flash_ring_step(  # noqa: E731
            cfg, q, k, v, None, m, l, acc
        )
        return fn, (
            f32(2, 8, 8),
            f32(2, 8, 8),
            f32(2, 8, 8),
            f32(2, 1, 8, 1),
            f32(2, 1, 8, 1),
            f32(2, 8, 8),
        )

    def _paged_table():
        # Concrete block table/lengths (closure constants): ops on them
        # stay concrete through tracing, so the capture records real block
        # ids and the index-map enumeration runs over genuine table rows —
        # including the last pool block and repeated sink-0 entries.
        table = np.array([[0, 1, 8, 0], [2, 0, 3, 4]], dtype=np.int32)
        lengths = np.array([18, 27], dtype=np.int32)
        return jnp.asarray(table), jnp.asarray(lengths)

    def paged_bf16():
        table, lengths = _paged_table()
        fn = lambda q, kp, vp: paged_flash_attention(  # noqa: E731
            q, kp, vp, table, lengths, interpret=True
        )
        return fn, (bf16(2, 1, 2, 8), bf16(9, 8, 2, 8), bf16(9, 8, 2, 8))

    def paged_int8():
        table, lengths = _paged_table()
        fn = lambda q, kp, vp, ks, vs: paged_flash_attention(  # noqa: E731
            q, kp, vp, table, lengths, k_scale=ks, v_scale=vs, interpret=True
        )
        return fn, (
            bf16(2, 1, 2, 8),
            jax.ShapeDtypeStruct((9, 8, 2, 8), jnp.int8),
            jax.ShapeDtypeStruct((9, 8, 2, 8), jnp.int8),
            f32(9, 8, 2, 1),
            f32(9, 8, 2, 1),
        )

    def paged_gqa_verify():
        table, lengths = _paged_table()
        fn = lambda q, kp, vp: paged_flash_attention(  # noqa: E731
            q, kp, vp, table, lengths, interpret=True
        )
        return fn, (bf16(2, 3, 4, 8), bf16(9, 8, 2, 8), bf16(9, 8, 2, 8))

    def paged_streamed():
        # Pages that fill whole tiles (D a lane row, both heads one packed
        # sublane) take the kernel's streamed route: pools left in HBM, hand-
        # made copies of the live pages, a loop by length, a fold a width of
        # live sub-chunks. The table is wider than one compute block (32
        # pages of 16 in sub-chunks of 8); slot 1 ends in its second block.
        rng = np.random.default_rng(0)
        table = jnp.asarray(rng.integers(1, 24, (2, 40)), jnp.int32)
        lengths = jnp.asarray([18, 600], jnp.int32)
        fn = lambda q, kp, vp: paged_flash_attention(  # noqa: E731
            q, kp, vp, table, lengths, interpret=True
        )
        return fn, (bf16(2, 1, 24, 128), bf16(24, 16, 2, 128), bf16(24, 16, 2, 128))

    def _ffn_params(d, dff, dtype, gated):
        ffn = {
            "in": {"kernel": jax.ShapeDtypeStruct((d, dff), dtype),
                   "bias": jax.ShapeDtypeStruct((dff,), dtype)},
            "out": {"kernel": jax.ShapeDtypeStruct((dff, d), dtype),
                    "bias": jax.ShapeDtypeStruct((d,), dtype)},
        }
        if gated:
            ffn["gate"] = {"kernel": jax.ShapeDtypeStruct((d, dff), dtype),
                           "bias": jax.ShapeDtypeStruct((dff,), dtype)}
        ln = {"scale": jax.ShapeDtypeStruct((d,), dtype),
              "bias": jax.ShapeDtypeStruct((d,), dtype)}
        return ln, ffn

    def ffn_relu():
        ln, ffn = _ffn_params(8, 256, jnp.bfloat16, gated=False)
        fn = lambda lp, fp, x: fused_ln_ffn(  # noqa: E731
            lp, fp, x, activation="relu", block_dff=128, interpret=True
        )
        return fn, (ln, ffn, bf16(2, 8))

    def ffn_swiglu():
        ln, ffn = _ffn_params(8, 256, jnp.bfloat16, gated=True)
        fn = lambda lp, fp, x: fused_ln_ffn(  # noqa: E731
            lp, fp, x, activation="swiglu", block_dff=128, interpret=True
        )
        return fn, (ln, ffn, bf16(2, 8))

    def moe_ffn():
        # The dropless expert layer's grouped product: 6 row tiles of 16 over
        # 4 experts, one expert without a token, two dead tiles at the end.
        from transformer_tpu.kernels.moe_ffn import moe_expert_ffn

        group = jnp.asarray([0, 0, 1, 3, 3, 3], jnp.int32)
        live = jnp.asarray(4, jnp.int32)
        fn = lambda x, wg, wi, wo: moe_expert_ffn(  # noqa: E731
            x, wg, wi, wo, group, live, tile_rows=16, block_dff=128, interpret=True
        )
        return fn, (bf16(96, 128), bf16(4, 128, 256), bf16(4, 128, 256), bf16(4, 256, 128))

    def kda_state_step():
        # The delta-rule layer's decode step: 3 slots (one free) of 4 heads
        # of 128, the state aliased input to output.
        from transformer_tpu.kernels.kda_step import kda_step

        f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
        fn = lambda s, q, k, v, g, b, live: kda_step(  # noqa: E731
            s, q, k, v, g, b, live, interpret=True
        )
        vec = f32(3, 4, 128)
        return fn, (f32(3, 4, 128, 128), vec, vec, vec, vec, f32(3, 4), i32(3))

    def moe_ffn_ungated():
        # The same grouped product with two matrices an expert (relu2).
        from transformer_tpu.kernels.moe_ffn import moe_expert_ffn

        group = jnp.asarray([0, 0, 1, 3, 3, 3], jnp.int32)
        live = jnp.asarray(4, jnp.int32)
        fn = lambda x, wi, wo: moe_expert_ffn(  # noqa: E731
            x, None, wi, wo, group, live, tile_rows=16, block_dff=128, activation="relu2", interpret=True
        )
        return fn, (bf16(96, 128), bf16(4, 128, 256), bf16(4, 256, 128))

    def ssm_state_step():
        # The state-space layer's decode step: 3 slots (one free) of 8 heads
        # of 16 x 128 in 2 groups, the state aliased input to output.
        from transformer_tpu.kernels.ssm_step import ssm_step

        f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
        fn = lambda s, x, dt, a, b, c, live: ssm_step(  # noqa: E731
            s, x, dt, a, b, c, live, interpret=True
        )
        return fn, (f32(3, 8, 16, 128), f32(3, 8, 16), f32(3, 8), f32(8), f32(3, 2, 128), f32(3, 2, 128), i32(3))

    def paged_latent():
        # The latent layer's decode attention: 2 slots, 8 heads against rows
        # of 256 lanes (rank 128), a table of 8 pages of 16 in one block.
        from transformer_tpu.kernels.paged_latent import paged_latent_attention

        fn = lambda q, pool, table, lengths: paged_latent_attention(  # noqa: E731
            q, pool, table, lengths, rank=128, interpret=True
        )
        return fn, (bf16(2, 8, 256), bf16(24, 16, 256), i32(2, 8), i32(2))

    def _serve_entry(variant):
        # Mirror costs.canned_cost_reports()'s fused paged serve program
        # exactly — the kernels verified here are the ones costs prices.
        from transformer_tpu.analysis.costs import (
            _PAGED_BLOCK,
            _PAGED_POOL_BLOCKS,
            _SERVE_SLOTS,
            _SERVE_TOTAL,
            _abstract_model,
        )
        from transformer_tpu.serve import scheduler as sched
        from transformer_tpu.serve.scheduler import abstract_paged_pool

        cfg = FAST_MATRIX[variant]
        params = _abstract_model(cfg)
        pool, table, index = abstract_paged_pool(
            cfg, _SERVE_SLOTS, _SERVE_TOTAL, _PAGED_POOL_BLOCKS, _PAGED_BLOCK
        )
        flash_raw = sched._pool_step_paged_flash.__wrapped__
        fn = lambda p, c, tb, ix, t: flash_raw(  # noqa: E731
            p, c, tb, ix, t, cfg, _PAGED_BLOCK, False
        )
        return fn, (params, pool, table, index, i32(_SERVE_SLOTS))

    entries = {
        "flash.fwd[causal,fp32]": flash_fwd_causal,
        "flash.fwd[mask,bf16]": flash_fwd_mask_bf16,
        "flash.grad[causal,fp32]": flash_grad_causal,
        "flash.grad[gqa,fp32]": flash_grad_gqa,
        "flash.ring_step[fp32]": flash_ring,
        "paged_flash[bf16]": paged_bf16,
        "paged_flash[int8]": paged_int8,
        "paged_flash[gqa,verify]": paged_gqa_verify,
        "paged_flash[bf16,streamed]": paged_streamed,
        "ffn.fused[relu,bf16]": ffn_relu,
        "ffn.fused[swiglu,bf16]": ffn_swiglu,
        "moe.expert_ffn[swiglu,bf16]": moe_ffn,
        "moe.expert_ffn[relu2,bf16]": moe_ffn_ungated,
        "kda.step[fp32]": kda_state_step,
        "ssm.step[fp32]": ssm_state_step,
        "paged_latent[bf16]": paged_latent,
    }
    for variant in ("lm_bf16", "lm_int8_cache", "lm_gqa"):
        entries[f"serve.pool_step_paged_flash[{variant}]"] = functools.partial(
            _serve_entry, variant
        )
    return entries


# ---------------------------------------------------------------------------
# Reports + analysis driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class KernelReport:
    """One verified pallas_call site."""

    name: str  # "<entry>/<kernel fn>"
    entry: str
    kernel: str
    src: str  # "path:line" of the kernel fn
    grid: tuple[int, ...]
    grid_size: int
    calls: int
    predicted_vmem_bytes: int
    vmem_breakdown: dict[str, int]
    budget_bytes: int
    fits_budget: bool
    flops_per_call: int
    checked_points: int
    sampled: bool
    padding: list[str]
    notes: list[str]

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["grid"] = list(self.grid)
        return d


@dataclasses.dataclass
class KernelsResult:
    generation: str
    reports: list[KernelReport]
    findings: list[Finding]  # unbaselined, unsuppressed lints
    baselined: int
    violations: list[str]  # conformance/race/budget — never baselineable
    regressions: list[str]  # vmem growth / coverage loss vs baseline
    notes: list[str]
    files_checked: int
    ast_sites: int

    @property
    def ok(self) -> bool:
        return not (self.findings or self.violations or self.regressions)

    def to_dict(self) -> dict:
        return {
            "generation": self.generation,
            "budget_bytes": VMEM_BUDGETS[self.generation],
            "kernels": [r.to_dict() for r in self.reports],
            "findings": [dataclasses.asdict(f) for f in self.findings],
            "baselined": self.baselined,
            "violations": list(self.violations),
            "regressions": list(self.regressions),
            "notes": list(self.notes),
            "files_checked": self.files_checked,
            "ast_sites": self.ast_sites,
            "ok": self.ok,
        }


def _trace_entry(name: str, factory) -> tuple[list[_Capture], Any]:
    import jax

    records: list[_Capture] = []
    fn, args = factory()
    with _capture_pallas(records):
        closed = jax.make_jaxpr(fn)(*args)
    return records, closed


def _module_lines(path: str) -> list[str]:
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return fh.read().splitlines()
    except OSError:
        return []


def analyze_entries(
    entries: dict[str, Callable],
    generation: str | None = None,
    ast_targets: list[tuple[str, str]] | None = None,
) -> KernelsResult:
    """Trace every entry under capture, verify each pallas_call site, and
    cross-check coverage against AST-discovered sites."""
    generation = generation or DEFAULT_GENERATION
    budget = VMEM_BUDGETS[generation]
    reports: list[KernelReport] = []
    findings: list[Finding] = []
    violations: list[str] = []
    notes: list[str] = []
    covered: list[tuple[str, int]] = []  # (abs call path, call line)
    src_cache: dict[str, list[str]] = {}

    for ename, factory in entries.items():
        try:
            caps, closed = _trace_entry(ename, factory)
        except Exception as e:  # noqa: BLE001  # tpa: disable=TPA006
            violations.append(f"{ename}: entry failed to trace: {e!r}")
            continue
        if not caps:
            notes.append(f"{ename}: no pallas_call captured")
            continue
        eqns = list(_iter_pallas_eqns(closed.jaxpr))
        for cap, eqn in _match_sites(caps, eqns):
            covered.append((os.path.abspath(cap.call_path), cap.call_line))
            views = _spec_views(cap)
            conf = _check_conformance(cap, views)
            _check_aliases(cap, views, conf)
            facts = roles = dtypes = None
            flops = 0
            if eqn is not None:
                gm = eqn.params.get("grid_mapping")
                body = eqn.params.get("jaxpr")
                if body is not None and gm is not None:
                    facts, roles, dtypes = _body_facts(body, gm)
                from .costs import pallas_call_flops

                flops = pallas_call_flops(eqn)
            else:
                notes.append(
                    f"{ename}/{cap.kernel_name}: no matching pallas_call eqn "
                    "(body lints and FLOPs skipped)"
                )
            vmem, breakdown = _vmem_footprint(cap, views)
            race = _check_out_race(cap, conf, facts, roles or {})
            site = f"{ename}/{cap.kernel_name}"
            site_notes = [
                f"{v.role}[{v.index}] {v.array_shape} stays in HBM: the kernel "
                "copies from it by hand into its scratch"
                for v in views
                if not v.in_vmem
            ]
            if eqn is not None and _has_data_dependent_loop(eqn):
                site_notes.append(
                    "a loop's trip count is read at run time: FLOPs count one "
                    "trip a grid step, and every branch of that trip"
                )
            for msg in conf.violations + race:
                violations.append(f"{site}: {msg}")
            if vmem > budget:
                violations.append(
                    f"{site}: predicted_vmem_bytes {vmem} exceeds {generation} "
                    f"budget {budget}"
                )
            lints = _lint_site(cap, facts, roles or {}, dtypes or {})
            kpath = os.path.abspath(cap.kernel_file)
            if kpath not in src_cache:
                src_cache[kpath] = _module_lines(kpath)
            for f in lints:
                if not line_suppressed(src_cache[kpath], f):
                    findings.append(f)
            reports.append(
                KernelReport(
                    name=site,
                    entry=ename,
                    kernel=cap.kernel_name,
                    src=f"{_display_path(cap.kernel_file)}:{cap.kernel_line}",
                    grid=cap.grid,
                    grid_size=int(np.prod(cap.grid)) if cap.grid else 1,
                    calls=cap.calls,
                    predicted_vmem_bytes=vmem,
                    vmem_breakdown=breakdown,
                    budget_bytes=budget,
                    fits_budget=vmem <= budget,
                    flops_per_call=flops,
                    checked_points=conf.checked_points,
                    sampled=conf.sampled,
                    padding=conf.padding,
                    notes=site_notes,
                )
            )

    # TPA300: AST sites with no captured call covering them.
    ast_targets = ast_targets if ast_targets is not None else _default_ast_targets()
    ast_sites: list[_AstSite] = []
    for p, display in ast_targets:
        ast_sites.extend(_ast_pallas_sites(p, display))
    for site in ast_sites:
        hit = any(
            os.path.abspath(site.path) == cp and site.line <= cl <= site.end_line
            for cp, cl in covered
        )
        if not hit:
            f = Finding(
                code="TPA300",
                path=site.display,
                line=site.line,
                symbol=site.symbol,
                message=(
                    f"pallas_call in `{site.symbol}` is not exercised by any "
                    "canned verifier entry — grid/BlockSpec conformance, VMEM "
                    "footprint and safety lints are all blind to it; add an "
                    "entry (see docs/ANALYSIS.md)"
                ),
                snippet=f"{site.symbol}:pallas_call",
            )
            if not line_suppressed(
                src_cache.setdefault(site.path, _module_lines(site.path)), f
            ):
                findings.append(f)

    return KernelsResult(
        generation=generation,
        reports=sorted(reports, key=lambda r: r.name),
        findings=findings,
        baselined=0,
        violations=violations,
        regressions=[],
        notes=notes,
        files_checked=len(ast_targets),
        ast_sites=len(ast_sites),
    )


# ---------------------------------------------------------------------------
# Baseline workflow (costs-style fail-on-growth)
# ---------------------------------------------------------------------------


def default_kernels_baseline_path() -> str:
    return os.path.join(_package_root(), "analysis", "kernels_baseline.json")


def load_kernels_baseline(path: str) -> dict:
    try:
        with open(path, "r", encoding="utf-8") as fh:
            data = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return {"findings": {}, "kernels": {}}
    grand = {
        f["fingerprint"]: f.get("reason", "baselined")
        for f in data.get("findings", [])
    }
    return {"findings": grand, "kernels": data.get("kernels", {})}


def write_kernels_baseline(result: KernelsResult, path: str) -> None:
    payload = {
        "findings": [
            {
                "fingerprint": f.fingerprint,
                "reason": "grandfathered by --update-baseline",
                "line": f.line,
            }
            for f in sorted(result.findings, key=lambda f: f.fingerprint)
        ],
        "kernels": {
            r.name: {
                "predicted_vmem_bytes": r.predicted_vmem_bytes,
                "flops_per_call": r.flops_per_call,
                "grid_size": r.grid_size,
            }
            for r in result.reports
        },
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")


def compare_kernels_to_baseline(result: KernelsResult, path: str) -> KernelsResult:
    """Split findings into baselined/new and gate VMEM against the bank.

    Growth in any kernel's ``predicted_vmem_bytes`` is a regression (run
    ``--update-baseline`` to accept deliberate changes); a banked kernel
    disappearing from the report is lost coverage and also fails.
    FLOPs drift is advisory (a note): it usually means shapes changed.
    """
    bank = load_kernels_baseline(path)
    keep: list[Finding] = []
    baselined = 0
    for f in result.findings:
        if f.fingerprint in bank["findings"]:
            baselined += 1
        else:
            keep.append(f)
    result.findings = keep
    result.baselined = baselined
    banked = bank["kernels"]
    if not banked:
        result.notes.append(f"no kernel baseline at {path} (run --update-baseline)")
        return result
    current = {r.name: r for r in result.reports}
    for name, r in current.items():
        b = banked.get(name)
        if b is None:
            result.regressions.append(
                f"{name}: not in baseline (new kernel or renamed entry — "
                "run --update-baseline to bank it)"
            )
            continue
        if r.predicted_vmem_bytes > int(b.get("predicted_vmem_bytes", 0)):
            result.regressions.append(
                f"{name}: predicted_vmem_bytes grew "
                f"{int(b['predicted_vmem_bytes'])} -> {r.predicted_vmem_bytes}"
            )
        elif r.predicted_vmem_bytes < int(b.get("predicted_vmem_bytes", 0)):
            result.notes.append(
                f"{name}: predicted_vmem_bytes improved "
                f"{int(b['predicted_vmem_bytes'])} -> {r.predicted_vmem_bytes} "
                "(run --update-baseline to bank the win)"
            )
        if r.flops_per_call != int(b.get("flops_per_call", r.flops_per_call)):
            result.notes.append(
                f"{name}: flops_per_call drifted "
                f"{int(b['flops_per_call'])} -> {r.flops_per_call}"
            )
    for name in banked:
        if name not in current:
            result.regressions.append(
                f"{name}: banked kernel missing from report (coverage lost)"
            )
    return result


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def _load_path_entries(paths: Sequence[str]) -> tuple[dict, list[tuple[str, str]]]:
    """User-supplied modules: each may export ``ANALYSIS_KERNEL_ENTRIES``
    (name -> zero-arg factory); all are AST-scanned."""
    import importlib.util

    entries: dict[str, Callable] = {}
    targets: list[tuple[str, str]] = []
    for i, p in enumerate(paths):
        absp = os.path.abspath(p)
        display = os.path.basename(absp)
        targets.append((absp, display))
        spec = importlib.util.spec_from_file_location(f"_tpa_kernel_mod{i}", absp)
        if spec is None or spec.loader is None:
            continue
        mod = importlib.util.module_from_spec(spec)
        try:
            spec.loader.exec_module(mod)
        except Exception:  # noqa: BLE001 — AST scan still applies  # tpa: disable=TPA006
            continue
        for name, factory in (getattr(mod, "ANALYSIS_KERNEL_ENTRIES", {}) or {}).items():
            entries[f"{display}:{name}"] = factory
    return entries, targets


def run_kernels(
    paths: Sequence[str] | None = None,
    baseline_path: str | None = None,
    compare: bool = True,
    generation: str | None = None,
) -> KernelsResult:
    """Package mode (no paths): canned entries + kernels//ops AST scan +
    the checked-in baseline. Paths mode: the given modules' declared
    ``ANALYSIS_KERNEL_ENTRIES`` with those files as the AST universe."""
    if paths:
        entries, targets = _load_path_entries(paths)
        result = analyze_entries(entries, generation, ast_targets=targets)
    else:
        result = analyze_entries(_canned_entries(), generation)
        if baseline_path is None:
            baseline_path = default_kernels_baseline_path()
    if compare and baseline_path is not None:
        result = compare_kernels_to_baseline(result, baseline_path)
    return result


def summarize_kernels(result: KernelsResult) -> str:
    from .costs import _fmt_bytes

    lines = [
        f"kernels: {len(result.reports)} site(s) verified "
        f"[{result.generation}, budget {_fmt_bytes(VMEM_BUDGETS[result.generation])}], "
        f"{result.ast_sites} AST site(s) in {result.files_checked} file(s)"
    ]
    for r in result.reports:
        mark = "ok" if r.fits_budget else "OVER"
        extra = " (sampled)" if r.sampled else ""
        lines.append(
            f"  {r.name}: grid {r.grid} x{r.calls} call(s), "
            f"vmem {_fmt_bytes(r.predicted_vmem_bytes)} [{mark}], "
            f"{r.checked_points} index points{extra}"
        )
    for v in result.violations:
        lines.append(f"  VIOLATION: {v}")
    for g in result.regressions:
        lines.append(f"  REGRESSION: {g}")
    for f in result.findings:
        lines.append(f"  {f.code} {f.path}:{f.line} {f.symbol}: {f.message}")
    if result.baselined:
        lines.append(f"  ({result.baselined} baselined finding(s) suppressed)")
    for n in result.notes:
        lines.append(f"  note: {n}")
    lines.append("kernels: OK" if result.ok else "kernels: FAIL")
    return "\n".join(lines)
