"""Sharding static analysis: collective inventory + TPA201–205 lints.

Mesh-TensorFlow's framing (PAPERS.md) is that a sharded program IS its
per-axis layouts plus the collectives those layouts force — and that both
are checkable at compile time. This module gives the repo that check, on
CPU, with zero device execution:

**Collective inventory** — walk a traced jaxpr (``jax.make_jaxpr``) for the
explicit collective equations ``shard_map`` bodies carry (``psum`` /
``all_gather`` / ``all_to_all`` / ``ppermute`` / ``pmin`` / ``pmax`` /
``reduce_scatter``), attribute each to its mesh axis, weight static counts
by enclosing ``scan`` trip counts (a ring's per-hop permute counts P-1
times, not once), and estimate per-step communication bytes from operand
sizes and the axis size (ring-algorithm factors: an all-reduce moves
``2·(n-1)/n`` of the buffer, a gather ``(n-1)/n`` of its output, a permute
one full shard per hop). GSPMD-inserted collectives (plain ``pjit`` with
``NamedSharding``) are invisible at jaxpr level by construction — the
inventory covers the manual (``shard_map``) programs, which is where this
repo's seq/pipe/expert traffic lives, and the *absence* of collectives in
single-device serving programs, which is what the decode-hot-loop budget
pins (``analysis/costs_baseline.json``).

**Sharding lints (TPA201–205)** — AST rules over the package with the same
fingerprint / ``# tpa: disable`` / baseline workflow as TPA001–007
(``analysis/baselines.py``; separate ``analysis/sharding_baseline.json``,
shipped empty):

- **TPA201** — a jit/pjit call passing ``in_shardings`` without
  ``out_shardings``: the program's boundary activations are left to GSPMD
  propagation, so the layout handed to the NEXT program (or donated back
  into the same buffer) can silently change per compile.
- **TPA202** — a mesh-axis name (in a ``PartitionSpec``/``P`` literal or an
  ``axis_name=`` argument) that is not in the declared mesh vocabulary
  collected from the analyzed files (``Mesh(..., names)``, ``axis_names``
  declarations). A typo'd axis silently means "replicated" in a spec — the
  array is simply not sharded, and nothing fails until HBM fills.
- **TPA203** — a donated argument whose literal ``in_shardings`` and
  ``out_shardings`` entries disagree: XLA cannot alias a buffer across a
  layout change, so the donation silently degrades to a copy (plus a
  resharding collective).
- **TPA204** — a collective call inside a serving-hot-loop jitted function
  (modules under ``serve/`` or the ``_pool_*``/``_slot_*``/``_pick_*``
  naming idiom): the decode loop is one-token latency-bound work; a
  collective there serializes every step on the slowest chip. The runtime
  complement is the empty per-program collective set pinned in
  ``costs_baseline.json``.
- **TPA205** — a partition-rule entry that fully replicates a
  large-parameter path (``embedding``/``table``/``kernel`` patterns mapped
  to an axis-free spec): every chip then holds the whole matrix — the
  "accidental full replication" memory cliff. Deliberately replicated
  small tensors (biases, norms, routers) are out of scope or suppressed
  inline where the decision lives.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Any, Callable, Iterable

from transformer_tpu.analysis.baselines import (
    Finding,
    RulesReport,
    _iter_py_files,
    _package_root,
    line_suppressed,
    load_baseline,
)
from transformer_tpu.analysis.rules import (
    _JIT_NAMES,
    _decorator_jit_spec,
    _dotted,
    _literal_ints,
)

SHARDING_RULES: dict[str, str] = {
    "TPA201": "in_shardings without out_shardings leaves boundary "
              "activations unconstrained",
    "TPA202": "mesh-axis name not in the declared mesh vocabulary",
    "TPA203": "donated argument's in/out shardings disagree (donation "
              "degrades to a copy)",
    "TPA204": "collective op inside a serving-hot-loop jitted function",
    "TPA205": "partition rule fully replicates a large parameter",
}

# Collective jaxpr primitives (and the user-facing call names TPA204 scans
# for). pmean lowers to psum+div; axis_index is not a transfer.
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pgather",
})
_COLLECTIVE_CALLS = COLLECTIVE_PRIMITIVES | frozenset({"pmean", "pshuffle"})

# Spec constructors whose string arguments are mesh-axis uses.
_SPEC_CTORS = frozenset({"P", "PartitionSpec"})


# ==========================================================================
# collective inventory (jaxpr side)


def _sub_jaxprs(value: Any) -> Iterable[Any]:
    """Yield raw Jaxprs nested in an eqn param value (ClosedJaxpr, Jaxpr,
    or lists/tuples of either)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for item in value:
            yield from _sub_jaxprs(item)


def walk_eqns_weighted(jaxpr, weight: int = 1):
    """Yield ``(eqn, weight)`` over every equation, recursing through
    pjit/shard_map/scan/while/cond sub-jaxprs. ``scan`` multiplies the
    weight by its trip count (a collective inside a ring scan runs per
    hop); ``while`` trip counts are unknowable statically and keep weight
    ×1 (documented undercount — budgets pin the *set*, counts are advisory
    there)."""
    for eqn in jaxpr.eqns:
        yield eqn, weight
        mult = weight
        if eqn.primitive.name == "scan":
            mult = weight * int(eqn.params.get("length", 1))
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from walk_eqns_weighted(sub, mult)


def _aval_bytes(aval) -> int:
    import numpy as np

    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        # Extended dtypes (PRNG key arrays) aren't numpy dtypes but do
        # carry their own itemsize (key<fry> = 2 x uint32 = 8 bytes).
        itemsize = int(getattr(dtype, "itemsize", 4))
    return n * itemsize


def _eqn_axes(eqn) -> tuple[str, ...]:
    """The named mesh axes a collective equation runs over."""
    for key in ("axis_name", "axes"):
        v = eqn.params.get(key)
        if v is None:
            continue
        if isinstance(v, str):
            return (v,)
        return tuple(str(a) for a in v if isinstance(a, (str,)))
    return ()


def _comm_bytes(kind: str, in_bytes: int, out_bytes: int, n: int) -> int:
    """Ring-algorithm per-step byte estimate for one call of a collective
    over an axis of size ``n``. n=1 (or unknown axes) transfers nothing."""
    if n <= 1:
        return 0
    if kind == "all_gather":
        return out_bytes * (n - 1) // n
    if kind in ("psum", "pmax", "pmin", "pbroadcast"):
        return 2 * in_bytes * (n - 1) // n
    if kind in ("reduce_scatter", "psum_scatter", "all_to_all", "pgather"):
        return in_bytes * (n - 1) // n
    if kind == "ppermute":
        return in_bytes
    return in_bytes


def collective_inventory(
    closed_jaxpr, axis_sizes: dict[str, int] | None = None
) -> dict[str, dict[str, int]]:
    """Aggregate the collective equations of a traced program.

    Returns ``{"kind[axis,...]": {"count": N, "bytes": B}}`` where ``count``
    is the scan-weighted static occurrence count and ``bytes`` the estimated
    per-step communication volume (see :func:`_comm_bytes`)."""
    axis_sizes = axis_sizes or {}
    out: dict[str, dict[str, int]] = {}
    for eqn, weight in walk_eqns_weighted(closed_jaxpr.jaxpr):
        kind = eqn.primitive.name
        if kind not in COLLECTIVE_PRIMITIVES:
            continue
        axes = _eqn_axes(eqn)
        n = 1
        for a in axes:
            n *= int(axis_sizes.get(a, 1))
        in_bytes = sum(
            _aval_bytes(v.aval) for v in eqn.invars if hasattr(v, "aval")
        )
        out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
        key = f"{kind}[{','.join(axes) or '?'}]"
        slot = out.setdefault(key, {"count": 0, "bytes": 0})
        slot["count"] += weight
        slot["bytes"] += weight * _comm_bytes(kind, in_bytes, out_bytes, n)
    return out


# ==========================================================================
# canned sharded programs (the collective sets costs_baseline.json pins)


def _mesh_1d(axis: str, size: int):
    import numpy as np

    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < size:
        return None
    return Mesh(np.asarray(devices[:size]).reshape(size), (axis,))


def canned_sharded_programs() -> tuple[dict[str, tuple], list[str]]:
    """name -> (traceable_fn, abstract_args, axis_sizes), plus the list of
    programs skipped on this host. Mesh shapes are FIXED (seq=2, model=2,
    fsdp=2) so the traced shapes — and therefore the baselined numbers —
    are identical on every host with >= 2 devices (tests force 8 virtual
    CPU devices via conftest; the CLI forces the same before importing
    jax)."""
    import functools

    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from transformer_tpu.parallel.ring_attention import (
        ring_attention,
        ulysses_attention,
    )

    programs: dict[str, tuple] = {}
    skipped: list[str] = []
    B, S, H, D = 1, 16, 2, 8
    act = jax.ShapeDtypeStruct((B, S, H, D), np.float32)

    # -- sequence parallelism: the repo's real per-shard attention bodies --
    mesh = _mesh_1d("seq", 2)
    if mesh is None:
        skipped.extend(
            ["parallel.ring_attention[seq=2]", "parallel.ulysses_attention[seq=2]"]
        )
    else:
        spec = P(None, "seq", None, None)
        for name, impl in (
            ("parallel.ring_attention[seq=2]", ring_attention),
            ("parallel.ulysses_attention[seq=2]", ulysses_attention),
        ):
            body = functools.partial(
                impl, axis_name="seq", axis_size=2, causal=True
            )
            fn = shard_map(
                lambda q, k, v, body=body: body(q, k, v),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False,
            )
            programs[name] = (fn, (act, act, act), {"seq": 2})

    # -- tensor parallelism: the parallel/sharding.py FFN layout (column-
    # then row-sharded matmul pair, one psum — the Mesh-TF claim made
    # checkable) --
    mesh = _mesh_1d("model", 2)
    M, F = 32, 64
    if mesh is None:
        skipped.append("parallel.tp_ffn[model=2]")
    else:
        def tp_ffn(h, w_in, w_out):
            mid = jax.nn.relu(h @ w_in)        # (B, F/model) per shard
            part = mid @ w_out                 # partial (B, M) per shard
            return jax.lax.psum(part, "model")

        fn = shard_map(
            tp_ffn, mesh=mesh,
            in_specs=(P(), P(None, "model"), P("model", None)),
            out_specs=P(),
            check_vma=False,
        )
        programs["parallel.tp_ffn[model=2]"] = (
            fn,
            (
                jax.ShapeDtypeStruct((4, M), np.float32),
                jax.ShapeDtypeStruct((M, F), np.float32),
                jax.ShapeDtypeStruct((F, M), np.float32),
            ),
            {"model": 2},
        )

    # -- fsdp: the ZeRO-3 per-layer gather (pipeline._gather_layer shape:
    # all_gather the shard, use it, drop it) --
    mesh = _mesh_1d("fsdp", 2)
    if mesh is None:
        skipped.append("parallel.fsdp_gather[fsdp=2]")
    else:
        def fsdp_layer(h, w_shard):
            w = jax.lax.all_gather(w_shard, "fsdp", axis=0, tiled=True)
            return h @ w

        fn = shard_map(
            fsdp_layer, mesh=mesh,
            in_specs=(P(), P("fsdp", None)),
            out_specs=P(),
            check_vma=False,
        )
        programs["parallel.fsdp_gather[fsdp=2]"] = (
            fn,
            (
                jax.ShapeDtypeStruct((4, M), np.float32),
                jax.ShapeDtypeStruct((M, M), np.float32),
            ),
            {"fsdp": 2},
        )

    # -- the SHARDED serving hot loop (serve/sharded.py, --mesh): the
    # programs a --mesh 2 replica jits as pjit twins. At trace level they
    # carry ZERO explicit collectives (params replicate; the pool shards on
    # a batch-like storage axis; cross-shard traffic is GSPMD data
    # movement) — banking them at mesh 2 makes ANY explicit collective that
    # sneaks into the decode/verify/prefill path a hard "stray collective"
    # failure against costs_baseline.json. GSPMD-INSERTED collectives are
    # invisible to a trace; serving_hlo_collectives() below gates those on
    # the compiled HLO.
    mesh = _mesh_1d("data", 2)
    _serve_names = [
        "serve.pool_step[lm_bf16,mesh=2]",
        "serve.pool_verify[lm_bf16,W=4,mesh=2]",
        "serve.slot_prefill[lm_bf16,n=8,mesh=2]",
    ]
    if mesh is None:
        skipped.extend(_serve_names)
    else:
        from transformer_tpu.analysis.configs import FAST_MATRIX
        from transformer_tpu.models.transformer import transformer_init
        from transformer_tpu.serve import scheduler as sched
        from transformer_tpu.serve.scheduler import abstract_pool_caches

        cfg = FAST_MATRIX["lm_bf16"]
        key = jax.ShapeDtypeStruct((2,), np.uint32)
        params = jax.eval_shape(lambda k: transformer_init(k, cfg), key)
        pool = abstract_pool_caches(cfg, 2, 32)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
        step_raw = sched._pool_step.__wrapped__
        verify_raw = sched._pool_verify.__wrapped__
        prefill_raw = sched._slot_prefill.__wrapped__
        programs[_serve_names[0]] = (
            lambda p, c, t: step_raw(p, c, t, cfg),
            (params, pool, i32(2)),
            {"data": 2},
        )
        programs[_serve_names[1]] = (
            lambda p, c, t: verify_raw(p, c, t, cfg),
            (params, pool, i32(2, 4)),
            {"data": 2},
        )
        programs[_serve_names[2]] = (
            lambda p, c, s, pr, st: prefill_raw(p, c, s, pr, st, cfg, 0),
            (params, pool, i32(), i32(1, 8), i32()),
            {"data": 2},
        )
    del jnp
    return programs, skipped


# ==========================================================================
# compiled-HLO collective gate for the sharded serving decode step

# HLO op spellings of the cross-device collectives (sync + async start
# forms share these prefixes).
_HLO_COLLECTIVE_RE = (
    r"\b(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast)"
)


def serving_hlo_collectives() -> tuple[dict[str, dict[str, int]], list[str]]:
    """Compile the DENSE sharded decode-step twins at mesh 2 and inventory
    collectives in the compiled HLO — the layer a jaxpr trace cannot see
    (GSPMD inserts collectives at partitioning time, after tracing).

    The serving layout (serve/sharded.py) makes the dense decode step
    embarrassingly parallel: params fully replicated, pool KV + step
    tokens + logits all sharded on the slot axis — so its compiled HLO
    must contain ZERO collectives, and ``analysis costs`` fails hard on
    any. Prefill and the paged programs legitimately move data across
    shards (replicated prompt rows into a sharded slot, block-row gathers
    through the table) — that traffic is deterministic data movement, not
    a reduction, so it is not gated here.

    Returns ``(inventory, skipped)`` where inventory maps program name ->
    {hlo_op: count} (empty dict = clean)."""
    import re

    import jax
    import numpy as np

    from transformer_tpu.analysis.configs import FAST_MATRIX
    from transformer_tpu.models.transformer import transformer_init
    from transformer_tpu.serve.scheduler import abstract_pool_caches
    from transformer_tpu.serve.sharded import ShardedPrograms, serving_mesh

    names = [
        "serve.pool_step[lm_bf16,mesh=2]",
        "serve.pool_verify[lm_bf16,W=4,mesh=2]",
    ]
    if len(jax.devices()) < 2:
        return {}, names
    cfg = FAST_MATRIX["lm_bf16"]
    key = jax.ShapeDtypeStruct((2,), np.uint32)
    params = jax.eval_shape(lambda k: transformer_init(k, cfg), key)
    pool = abstract_pool_caches(cfg, 2, 32)
    sp = ShardedPrograms(serving_mesh(2), params)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
    out: dict[str, dict[str, int]] = {}
    for name, fn, args in (
        (names[0], sp.pool_step, (params, pool, i32(2), cfg)),
        (names[1], sp.pool_verify, (params, pool, i32(2, 4), cfg)),
    ):
        text = fn.lower(*args).compile().as_text()
        found: dict[str, int] = {}
        for m in re.finditer(_HLO_COLLECTIVE_RE, text):
            found[m.group(1)] = found.get(m.group(1), 0) + 1
        out[name] = found
    return out, []


# ==========================================================================
# TPA201–205 (AST side)


class _ShardModule:
    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)

    # -- shared helpers ----------------------------------------------------

    def finding(self, code: str, node: ast.AST, symbol: str, message: str) -> Finding:
        line = getattr(node, "lineno", 1)
        snippet = (
            self.lines[line - 1].strip() if 0 < line <= len(self.lines) else ""
        )
        return Finding(
            code=code, path=self.rel, line=line, symbol=symbol,
            message=message, snippet=snippet,
        )

    def suppressed(self, f: Finding) -> bool:
        return line_suppressed(self.lines, f)

    def _enclosing(self) -> dict[int, str]:
        out: dict[int, str] = {}

        def visit(node: ast.AST, symbol: str) -> None:
            for child in ast.iter_child_nodes(node):
                child_symbol = symbol
                if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
                ):
                    child_symbol = (
                        child.name
                        if symbol == "<module>"
                        else f"{symbol}.{child.name}"
                    )
                out[id(child)] = child_symbol
                visit(child, child_symbol)

        visit(self.tree, "<module>")
        return out

    # -- axis vocabulary ---------------------------------------------------

    def declared_axes(self) -> set[str]:
        """Mesh-axis names this module DECLARES: ``Mesh(..., (names))``
        literals, ``axis_names`` assignments, and tuples returned from
        ``axis_names`` functions/properties."""
        axes: set[str] = set()

        def strs(node: ast.AST | None) -> list[str]:
            if isinstance(node, (ast.Tuple, ast.List)):
                out = []
                for e in node.elts:
                    if isinstance(e, ast.Constant) and isinstance(e.value, str):
                        out.append(e.value)
                return out
            return []

        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and _dotted(node.func) in (
                "Mesh", "jax.sharding.Mesh",
            ):
                if len(node.args) >= 2:
                    axes.update(strs(node.args[1]))
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        axes.update(strs(kw.value))
            elif isinstance(node, ast.Assign):
                names = []
                for t in node.targets:
                    d = _dotted(t)
                    if d:
                        names.append(d.rsplit(".", 1)[-1])
                if any("axis_names" in n for n in names):
                    axes.update(strs(node.value))
            elif isinstance(node, ast.FunctionDef) and "axis_names" in node.name:
                for inner in ast.walk(node):
                    if isinstance(inner, ast.Return):
                        axes.update(strs(inner.value))
        return axes

    def axis_uses(self) -> list[tuple[str, ast.AST, str]]:
        """(axis_name, node, symbol) for every literal mesh-axis reference:
        strings inside ``P(...)``/``PartitionSpec(...)`` (including tuple
        elements) and ``axis_name=``/collective-call axis arguments."""
        uses: list[tuple[str, ast.AST, str]] = []
        enclosing = self._enclosing()

        def spec_strs(node: ast.AST) -> list[str]:
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                return [node.value]
            if isinstance(node, (ast.Tuple, ast.List)):
                out: list[str] = []
                for e in node.elts:
                    out.extend(spec_strs(e))
                return out
            return []

        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = _dotted(node.func)
            if not fname:
                continue
            base = fname.rsplit(".", 1)[-1]
            symbol = enclosing.get(id(node), "<module>")
            if base in _SPEC_CTORS:
                for a in node.args:
                    for s in spec_strs(a):
                        uses.append((s, node, symbol))
            if base in _COLLECTIVE_CALLS:
                # jax.lax.psum(x, 'axis') / ppermute(x, 'axis', perm)
                if len(node.args) >= 2:
                    for s in spec_strs(node.args[1]):
                        uses.append((s, node, symbol))
            for kw in node.keywords:
                if kw.arg in ("axis_name", "axis_names"):
                    for s in spec_strs(kw.value):
                        uses.append((s, node, symbol))
        return uses

    # -- rules -------------------------------------------------------------

    def _jit_calls(self) -> list[tuple[ast.Call, str]]:
        out = []
        enclosing = self._enclosing()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and _dotted(node.func) in _JIT_NAMES:
                out.append((node, enclosing.get(id(node), "<module>")))
        return out

    def rule_tpa201(self) -> list[Finding]:
        out = []
        for call, symbol in self._jit_calls():
            kwargs = {kw.arg for kw in call.keywords}
            if "in_shardings" in kwargs and "out_shardings" not in kwargs:
                out.append(
                    self.finding(
                        "TPA201", call, symbol,
                        "jit with in_shardings but no out_shardings — the "
                        "output layout is left to GSPMD propagation and can "
                        "change per compile; pin the boundary activations",
                    )
                )
        return out

    def rule_tpa202(self, universe: set[str]) -> list[Finding]:
        if not universe:
            return []  # nothing declared anywhere in the analyzed set
        out = []
        for axis, node, symbol in self.axis_uses():
            if axis not in universe:
                out.append(
                    self.finding(
                        "TPA202", node, symbol,
                        f"mesh axis {axis!r} is not in the declared mesh "
                        f"vocabulary {sorted(universe)} — a typo'd axis "
                        "silently means 'replicated'",
                    )
                )
        return out

    def rule_tpa203(self) -> list[Finding]:
        out = []
        for call, symbol in self._jit_calls():
            kws = {kw.arg: kw.value for kw in call.keywords}
            donate = _literal_ints(kws.get("donate_argnums"))
            ins, outs = kws.get("in_shardings"), kws.get("out_shardings")
            if not donate or ins is None or outs is None:
                continue
            if not isinstance(ins, (ast.Tuple, ast.List)) or not isinstance(
                outs, (ast.Tuple, ast.List)
            ):
                continue  # non-literal: not judgeable from the AST
            for i in donate:
                if 0 <= i < len(ins.elts) and i < len(outs.elts):
                    if ast.dump(ins.elts[i]) != ast.dump(outs.elts[i]):
                        out.append(
                            self.finding(
                                "TPA203", call, symbol,
                                f"donated argument {i} has in_sharding "
                                f"{ast.unparse(ins.elts[i])} but out_sharding "
                                f"{ast.unparse(outs.elts[i])} — XLA cannot "
                                "alias across layouts, so donation degrades "
                                "to a copy plus a reshard",
                            )
                        )
        return out

    def _is_serving_hot(self, fn: ast.FunctionDef) -> bool:
        parts = self.rel.replace(os.sep, "/").split("/")
        in_serve = "serve" in parts[:-1] or parts[-1].startswith("serve")
        hot_name = fn.name.startswith(("_pool_", "_slot_", "_pick_"))
        return in_serve or hot_name

    def rule_tpa204(self) -> list[Finding]:
        out = []
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            if not any(
                _decorator_jit_spec(d) is not None for d in node.decorator_list
            ):
                continue
            if not self._is_serving_hot(node):
                continue
            for inner in ast.walk(node):
                if isinstance(inner, ast.Call):
                    fname = _dotted(inner.func)
                    if fname and fname.rsplit(".", 1)[-1] in _COLLECTIVE_CALLS:
                        out.append(
                            self.finding(
                                "TPA204", inner, node.name,
                                f"collective `{fname}` inside the serving "
                                "hot loop — every decode step now "
                                "serializes on the slowest chip; keep "
                                "decode single-chip (or move the collective "
                                "out of the per-token path)",
                            )
                        )
        return out

    _LARGE_PARAM = ("embedding", "table", "kernel")
    _SMALL_PARAM = ("bias", "scale", "ln", "norm")

    def rule_tpa205(self) -> list[Finding]:
        out = []
        enclosing = self._enclosing()
        for node in ast.walk(self.tree):
            if not isinstance(node, (ast.Tuple, ast.List)) or len(node.elts) != 2:
                continue
            pat, spec = node.elts
            if not (isinstance(pat, ast.Constant) and isinstance(pat.value, str)):
                continue
            text = pat.value.lower()
            if not any(m in text for m in self._LARGE_PARAM):
                continue
            if any(m in text for m in self._SMALL_PARAM):
                continue
            if not (
                isinstance(spec, ast.Call)
                and _dotted(spec.func)
                and _dotted(spec.func).rsplit(".", 1)[-1] in _SPEC_CTORS
            ):
                continue
            axes = [
                a for a in spec.args
                if not (isinstance(a, ast.Constant) and a.value is None)
            ]
            if axes:
                continue  # something is sharded
            out.append(
                self.finding(
                    "TPA205", node, enclosing.get(id(node), "<module>"),
                    f"partition rule {pat.value!r} maps a large-parameter "
                    "path to a fully replicated spec — every chip holds the "
                    "whole matrix; shard it (or justify inline if the "
                    "tensor is genuinely small)",
                )
            )
        return out


# ==========================================================================
# driver


def default_sharding_baseline_path() -> str:
    return os.path.join(_package_root(), "analysis", "sharding_baseline.json")


def run_sharding(
    paths: list[str] | None = None,
    baseline_path: str | None = None,
) -> RulesReport:
    """Run TPA201–205 over ``paths`` (default: the installed
    ``transformer_tpu`` package + its sharding baseline). The TPA202 axis
    vocabulary is collected across the WHOLE analyzed file set first, so a
    mesh declared in ``config.py`` covers specs written in ``parallel/``."""
    if paths is None:
        paths = [_package_root()]
        if baseline_path is None:
            baseline_path = default_sharding_baseline_path()
    baseline = load_baseline(baseline_path)

    modules: list[_ShardModule] = []
    for full, rel in _iter_py_files(paths):
        with open(full, encoding="utf-8") as f:
            source = f.read()
        try:
            modules.append(_ShardModule(full, rel, source))
        except SyntaxError as e:
            raise SyntaxError(f"cannot analyze {full}: {e}") from e

    universe: set[str] = set()
    for m in modules:
        universe |= m.declared_axes()

    findings: list[Finding] = []
    baselined: list[Finding] = []
    for m in modules:
        raw = (
            m.rule_tpa201()
            + m.rule_tpa202(universe)
            + m.rule_tpa203()
            + m.rule_tpa204()
            + m.rule_tpa205()
        )
        for f in raw:
            if m.suppressed(f):
                continue
            if f.fingerprint in baseline:
                baselined.append(f)
            else:
                findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.code))
    return RulesReport(
        findings=findings, baselined=baselined, files_checked=len(modules)
    )
