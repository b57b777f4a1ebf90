"""Retrace sentinel: catch recompilation regressions before a TPU does.

A jitted hot path that silently retraces — a config knob that stopped being
hashable, a shape that stopped bucketing, a weak-typed scalar flipping per
call — costs seconds of XLA compile per occurrence and shows up only as
mysterious step-time jitter. This module turns "the steady-state decode
path compiles exactly N programs" into an assertable budget, so a retrace
regression is caught by the CPU tests instead of by a chip run.

Mechanics: every ``jax.jit`` callable exposes ``_cache_size()`` — the number
of compiled executables its cache holds. :class:`RetraceSentinel` snapshots
the watched functions' cache sizes, the caller drives the hot path, and
``check()`` fails if any function compiled more NEW programs than its
declared budget (0 for a steady-state path). This is jit-cache accounting,
not wall-clock sampling, so it is exact and CPU-safe.

``leak_checking()`` wires ``jax.checking_leaks`` around a block: tracer
leaks (the cousin failure mode — a traced value smuggled out through module
state) raise at the source instead of exploding later.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Iterator

import jax


def _cache_size(fn: Any) -> int:
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        raise ValueError(
            f"{fn!r} exposes no _cache_size — pass the jax.jit-wrapped "
            "callable itself (not the underlying Python function)"
        )
    return int(probe())


@dataclasses.dataclass
class WatchDelta:
    name: str
    budget: int
    before: int
    after: int

    @property
    def compiles(self) -> int:
        return self.after - self.before

    @property
    def within_budget(self) -> bool:
        return self.compiles <= self.budget

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "budget": self.budget,
            "compiles": self.compiles,
            "cache_before": self.before,
            "cache_after": self.after,
            "ok": self.within_budget,
        }


class RetraceSentinel:
    """Budgeted compile-count accounting over a set of jitted functions.

    >>> sentinel = RetraceSentinel()
    >>> sentinel.watch("decode_step", _pool_step, budget=0)
    >>> sentinel.snapshot()          # after warmup
    >>> ...drive the steady-state hot path...
    >>> sentinel.assert_within_budget()
    """

    def __init__(self) -> None:
        self._fns: dict[str, tuple[Any, int]] = {}
        self._before: dict[str, int] = {}

    def watch(self, name: str, fn: Any, budget: int = 0) -> None:
        _cache_size(fn)  # validate now, not at snapshot time
        self._fns[name] = (fn, budget)

    def snapshot(self) -> dict[str, int]:
        self._before = {
            name: _cache_size(fn) for name, (fn, _) in self._fns.items()
        }
        return dict(self._before)

    def deltas(self) -> list[WatchDelta]:
        if not self._fns:
            return []
        if not self._before:
            raise RuntimeError("snapshot() was never taken — nothing to diff")
        return [
            WatchDelta(
                name=name,
                budget=budget,
                before=self._before[name],
                after=_cache_size(fn),
            )
            for name, (fn, budget) in self._fns.items()
        ]

    def violations(self) -> list[WatchDelta]:
        return [d for d in self.deltas() if not d.within_budget]

    def assert_within_budget(self) -> None:
        bad = self.violations()
        if bad:
            raise AssertionError(
                "retrace budget exceeded: "
                + "; ".join(
                    f"{d.name} compiled {d.compiles} new program(s), "
                    f"budget {d.budget}"
                    for d in bad
                )
            )


@contextlib.contextmanager
def leak_checking() -> Iterator[None]:
    """``jax.checking_leaks`` as a composable context: tracer leaks raise
    where they escape. Trace-heavy (re-traces watched functions), so this is
    a debugging/CI tool, not a production wrapper."""
    with jax.checking_leaks():
        yield


# --------------------------------------------------------------------------
# canned steady-state scenarios (CLI `retrace` + tests)


def _tiny_lm_setup():
    from transformer_tpu.analysis.configs import FAST_MATRIX
    from transformer_tpu.data.tokenizer import SubwordTokenizer
    from transformer_tpu.models.transformer import transformer_init

    cfg = FAST_MATRIX["lm_bf16"]
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    tok = SubwordTokenizer.build_from_corpus(
        ["the quick brown fox jumps over the lazy dog"] * 4,
        target_vocab_size=cfg.input_vocab_size - 2,
    )
    return cfg, params, tok


def decode_retrace_report(steps: int = 3) -> list[WatchDelta]:
    """Steady-state serving: warm the slot-pool scheduler up on one request,
    snapshot, then serve ``steps`` more same-shaped requests. The hot paths
    (``_pool_step`` = decode step, ``_slot_prefill``, ``_pick_pool``) must
    compile ZERO new programs — admission bucketing (``prefill_len_for``)
    and the fixed-shape pool exist precisely to guarantee this."""
    from transformer_tpu.serve import scheduler as sched
    from transformer_tpu.serve.scheduler import ContinuousScheduler

    cfg, params, tok = _tiny_lm_setup()

    def serve(reqs):
        s = ContinuousScheduler(
            params, cfg, tok, num_slots=2, max_total=32, default_max_new=4
        )
        return s.run(reqs)

    serve([{"prompt": "the quick brown fox"}])  # warmup compile
    sentinel = RetraceSentinel()
    sentinel.watch("decode_step(_pool_step)", sched._pool_step, budget=0)
    sentinel.watch("_slot_prefill", sched._slot_prefill, budget=0)
    sentinel.watch("pick(_pick_pool)", sched._pick_pool, budget=0)
    sentinel.snapshot()
    for _ in range(steps):
        out = serve([{"prompt": "the quick brown fox"}])
        assert "continuation" in out[0], out
    return sentinel.deltas()


def speculative_retrace_report(steps: int = 3) -> list[WatchDelta]:
    """Steady-state SPECULATIVE serving: accept lengths vary per request
    (a self-repeating prompt lands long n-gram accepts; an irregular one
    mostly misses), yet the hot paths — ``_pool_verify`` (the W-wide
    verify forward), ``_pick_pool_verify``, ``_slot_prefill``, and
    ``_pool_rollback`` — must compile ZERO new programs after warmup:
    rows are padded to the static width k + 1 and rollback is index
    arithmetic, so no accept length may mint a fresh shape."""
    from transformer_tpu.serve import scheduler as sched
    from transformer_tpu.serve.scheduler import ContinuousScheduler

    cfg, params, tok = _tiny_lm_setup()

    # Mixed acceptance shapes on purpose: repetitive text drafts well,
    # irregular text rejects early, short prompts exercise the boundary.
    waves = [
        [{"prompt": "the quick brown fox"}, {"prompt": "dog dog dog dog"}],
        [{"prompt": "the the the the the"}, {"prompt": "lazy fox"}],
        [{"prompt": "quick quick brown"}, {"prompt": "the lazy dog"}],
    ]

    def serve(reqs):
        s = ContinuousScheduler(
            params, cfg, tok, num_slots=2, max_total=32, default_max_new=6,
            speculate_k=3,
        )
        return s.run(reqs)

    for wave in waves:
        # Warmup covers every prefill bucket the waves touch: bucketed
        # prefill widths (prefill_len_for) are a bounded compile set, not
        # steady-state retraces — the budget guards the per-STEP paths.
        serve([dict(r) for r in wave])
    sentinel = RetraceSentinel()
    sentinel.watch("verify(_pool_verify)", sched._pool_verify, budget=0)
    sentinel.watch("pick(_pick_pool_verify)", sched._pick_pool_verify, budget=0)
    sentinel.watch("_slot_prefill", sched._slot_prefill, budget=0)
    sentinel.watch("rollback(_pool_rollback)", sched._pool_rollback, budget=0)
    sentinel.snapshot()
    for i in range(steps):
        out = serve([dict(r) for r in waves[i % len(waves)]])
        assert all("continuation" in r for r in out), out
    return sentinel.deltas()


def prefix_cache_retrace_report(steps: int = 3) -> list[WatchDelta]:
    """Steady-state serving WITH the cross-request prefix cache: hits,
    misses, and partial hits all flow through admission, yet the hot paths
    — ``_pool_step``, ``_slot_prefill`` (suffix prefill at a traced start),
    ``_slot_restore`` (block restore at power-of-two padded widths),
    ``_slot_read_blocks`` (retirement export, one static block width), and
    ``_pick_pool`` — must compile ZERO new programs after warmup: hit
    lengths bucket by block count exactly as prompt lengths bucket by
    ``prefill_len_for``, so no admission outcome may mint a fresh shape."""
    from transformer_tpu.serve import PrefixCache
    from transformer_tpu.serve import scheduler as sched
    from transformer_tpu.serve.scheduler import ContinuousScheduler

    cfg, params, tok = _tiny_lm_setup()
    cache = PrefixCache(cfg, block_tokens=4, budget_mb=8)

    # One shared long prefix plus divergent tails: replays are full hits,
    # tail variants are partial hits, and the short prompt is a clean miss
    # — every admission outcome the trie can produce, every round.
    waves = [
        [{"prompt": "the quick brown fox jumps"}],
        [{"prompt": "the quick brown fox jumps"},        # full hit
         {"prompt": "the quick brown dog"}],             # partial hit
        [{"prompt": "lazy"},                             # miss
         {"prompt": "the quick brown fox jumps"}],
    ]

    def serve(reqs):
        s = ContinuousScheduler(
            params, cfg, tok, num_slots=2, max_total=48, default_max_new=4,
            prefix_cache=cache,
        )
        return s.run(reqs)

    for wave in waves + waves:
        # TWO warmup passes: the first populates the trie (every wave-0
        # admission is a miss), the second re-serves the same prompts as
        # hits/partial hits — covering every restore-pad bucket and
        # suffix-prefill bucket steady state will see (bounded compile
        # sets, not steady-state retraces — the budget guards the
        # per-admission/per-step paths).
        serve([dict(r) for r in wave])
    sentinel = RetraceSentinel()
    sentinel.watch("decode_step(_pool_step)", sched._pool_step, budget=0)
    sentinel.watch("_slot_prefill", sched._slot_prefill, budget=0)
    sentinel.watch("restore(_slot_restore)", sched._slot_restore, budget=0)
    sentinel.watch("export(_slot_read_blocks)", sched._slot_read_blocks, budget=0)
    sentinel.watch("pick(_pick_pool)", sched._pick_pool, budget=0)
    sentinel.snapshot()
    for i in range(steps):
        out = serve([dict(r) for r in waves[i % len(waves)]])
        assert all("continuation" in r for r in out), out
    return sentinel.deltas()


def paged_retrace_report(steps: int = 3) -> list[WatchDelta]:
    """Steady-state serving on the PAGED KV layout (``--kv_layout paged``)
    across every admission outcome the block pool can produce — fresh
    allocations, frees at retirement, device-tier ALIAS hits, spill-to-host
    followed by host-restore (re-adopted back into the device tier), and a
    copy-on-write block split — while the hot paths
    (``_pool_step_paged``, ``_slot_prefill_paged``, ``_pool_write_blocks``,
    ``_pool_read_block``, ``_pool_copy_blocks``, ``_pick_pool``) compile
    ZERO new programs after warmup: table/index shapes are static, host
    restores pad to power-of-two block counts, and per-slot indices are
    host-derived, so no pool state may mint a fresh shape. Greedy answers
    are asserted byte-identical round over round."""
    from transformer_tpu.serve import PrefixCache
    from transformer_tpu.serve import scheduler as sched
    from transformer_tpu.serve.scheduler import ContinuousScheduler

    cfg, params, tok = _tiny_lm_setup()
    cache = PrefixCache(cfg, block_tokens=4, budget_mb=8)
    s = ContinuousScheduler(
        params, cfg, tok, num_slots=2, max_total=48, default_max_new=4,
        prefix_cache=cache, kv_layout="paged",
    )
    wave = [
        {"prompt": "the quick brown fox jumps"},
        {"prompt": "the quick brown dog"},
    ]

    def one_round():
        out = s.run([dict(r) for r in wave])       # miss / alias / partial
        # Spill rung: push every device-tier block to the host trie (the
        # wire format), then re-serve — hits now restore through the
        # batched host write and are re-adopted, so the NEXT round
        # aliases again. Exercises _pool_read_block + _pool_write_blocks.
        s.stats["kv_spilled_blocks"] += cache.release_device_blocks(1 << 30)
        out2 = s.run([dict(r) for r in wave])
        # CoW rung: alias a device-tier block into a free slot's table
        # (refcount 2) and write-guard it — the pool splits the block and
        # copies it on device (_pool_copy_blocks), the fork a
        # parallel-sampling tier drives per step. The row is returned
        # before any admission can see it.
        bid = None
        with cache._lock:
            stack = [cache._root]
            while stack:
                n = stack.pop()
                stack.extend(n.children.values())
                if n.device_block is not None:
                    bid = n.device_block
                    break
        if bid is not None:
            slot = s._free[-1]
            s.pool.alloc.extend(slot, bid=bid)
            s._paged_cow(slot, 0, cache.block_tokens)
            s.pool.alloc.free_slot(slot)
        s.pool.alloc.check_consistency()
        return [r.get("continuation") for r in out + out2]

    # ONE warmup round compiles every shape steady state sees: the round
    # itself covers miss -> spill -> host-restore -> re-adopt -> CoW, and
    # the first steady round's alias hits reuse the restore-round's
    # suffix buckets (aliasing is a host-side table op).
    want = one_round()
    sentinel = RetraceSentinel()
    sentinel.watch("decode(_pool_step_paged)", sched._pool_step_paged, budget=0)
    sentinel.watch("_slot_prefill_paged", sched._slot_prefill_paged, budget=0)
    sentinel.watch("restore(_pool_write_blocks)", sched._pool_write_blocks, budget=0)
    sentinel.watch("spill(_pool_read_block)", sched._pool_read_block, budget=0)
    sentinel.watch("cow(_pool_copy_blocks)", sched._pool_copy_blocks, budget=0)
    sentinel.watch("pick(_pick_pool)", sched._pick_pool, budget=0)
    sentinel.snapshot()
    for i in range(steps):
        got = one_round()
        assert got == want, f"paged round {i} changed greedy answers"
    return sentinel.deltas()


def resilience_retrace_report(steps: int = 3) -> list[WatchDelta]:
    """Steady-state serving WHILE circuit breakers flip: injected drafter
    and prefix-cache faults open the breakers mid-run, requests keep
    answering through the degraded path, the fault plane disarms, and
    half-open probes close the breakers — all on ONE scheduler whose hot
    paths (``_pool_verify``, ``_pick_pool_verify``, ``_slot_prefill``,
    ``_slot_restore``, ``_slot_read_blocks``, ``_pool_rollback``) must
    compile ZERO new programs after warmup. Degradation is a row-content /
    admission-path change, never a shape change: breaker-open rows still
    ride the static W-wide verify program and breaker-open admissions use
    the same bucketed full-prefill widths a cache miss uses. Greedy
    answers are asserted byte-identical before, during, and after the
    breaker transitions (docs/ROBUSTNESS.md)."""
    from transformer_tpu.serve import PrefixCache, resilience
    from transformer_tpu.serve import scheduler as sched
    from transformer_tpu.serve.resilience import FaultPlane
    from transformer_tpu.serve.scheduler import ContinuousScheduler

    cfg, params, tok = _tiny_lm_setup()
    cache = PrefixCache(cfg, block_tokens=4, budget_mb=8)
    s = ContinuousScheduler(
        params, cfg, tok, num_slots=2, max_total=48, default_max_new=4,
        speculate_k=2, prefix_cache=cache,
        breaker_threshold=2, breaker_cooldown_s=0.0, retry_backoff_ms=1.0,
    )
    wave = [
        {"prompt": "the quick brown fox jumps"},
        {"prompt": "the quick brown dog"},
        {"prompt": "lazy"},
    ]
    # Warmup: two passes cover misses (full prefill buckets) AND
    # hits/partial hits (restore pads + suffix buckets) — breaker-open
    # admissions reuse the miss path's programs, so warmup covers the
    # degraded mode too.
    want = s.run([dict(r) for r in wave])
    want2 = s.run([dict(r) for r in wave])
    assert [r.get("continuation") for r in want] == [
        r.get("continuation") for r in want2
    ], "prefix-cache replay changed greedy answers"
    sentinel = RetraceSentinel()
    sentinel.watch("verify(_pool_verify)", sched._pool_verify, budget=0)
    sentinel.watch("pick(_pick_pool_verify)", sched._pick_pool_verify, budget=0)
    sentinel.watch("_slot_prefill", sched._slot_prefill, budget=0)
    sentinel.watch("restore(_slot_restore)", sched._slot_restore, budget=0)
    sentinel.watch("export(_slot_read_blocks)", sched._slot_read_blocks, budget=0)
    sentinel.watch("rollback(_pool_rollback)", sched._pool_rollback, budget=0)
    sentinel.snapshot()
    for i in range(steps):
        with resilience.active(
            FaultPlane.parse("draft.propose:p=1,times=4;prefix.match:p=1,times=4")
        ):
            out = s.run([dict(r) for r in wave])  # breakers open mid-run
        assert [r.get("continuation") for r in out] == [
            r.get("continuation") for r in want
        ], f"degraded round {i} changed greedy answers"
        out = s.run([dict(r) for r in wave])      # probes close the breakers
        assert [r.get("continuation") for r in out] == [
            r.get("continuation") for r in want
        ], f"recovered round {i} changed greedy answers"
        assert s.breakers["speculative"].state == "closed"
        assert s.breakers["prefix_cache"].state == "closed"
    return sentinel.deltas()


def upgrade_retrace_report(steps: int = 3) -> list[WatchDelta]:
    """Steady-state serving ACROSS live-weight swaps: requests are
    admitted, a structural-twin weight set is staged mid-flight (the
    quiesce), the pool drains on the admission-time weights, the flip
    lands at a drained step boundary, new traffic serves the new weights,
    and a rollback re-stages the resident old pair — and through the
    whole quiesce/swap/rollback ladder the hot paths (``_pool_step``,
    ``_slot_prefill``, ``_pick_pool``) must compile ZERO new programs:
    params are traced operands of the same executables, so a verified
    twin only changes VALUES (docs/SERVING.md "Live-weights rollout").
    Answers are asserted byte-stable per weight_version tag."""
    from transformer_tpu.models.transformer import transformer_init
    from transformer_tpu.serve import scheduler as sched
    from transformer_tpu.serve.scheduler import ContinuousScheduler

    cfg, params, tok = _tiny_lm_setup()
    params_new = transformer_init(jax.random.PRNGKey(1), cfg)
    s = ContinuousScheduler(
        params, cfg, tok, num_slots=2, max_total=32, default_max_new=4,
        weight_version="v0",
    )
    wave = [
        {"prompt": "the quick brown fox"}, {"prompt": "the lazy dog"},
    ]
    want_old = s.run([dict(r) for r in wave])  # warmup compile on v0
    sentinel = RetraceSentinel()
    sentinel.watch("decode_step(_pool_step)", sched._pool_step, budget=0)
    sentinel.watch("_slot_prefill", sched._slot_prefill, budget=0)
    sentinel.watch("pick(_pick_pool)", sched._pick_pool, budget=0)
    sentinel.snapshot()
    want_new = None
    for i in range(steps):
        # Straddle the boundary: admit the wave on v0, THEN stage v1 —
        # the in-flight requests must finish on their admission-time
        # weights while admission quiesces.
        for r in wave:
            s.submit(dict(r))
        s.admit()
        assert s.active_count == len(wave), "wave not admitted pre-stage"
        s.stage_params(params_new, "v1")
        while s.busy:
            s.admit()
            s.step()
        out = s.drain_ready()
        assert [r["continuation"] for r in out] == [
            r["continuation"] for r in want_old
        ], f"round {i}: straddling requests left their admission weights"
        assert all(r["weight_version"] == "v0" for r in out)
        s.step()  # the drained boundary: the flip lands here
        assert s.weight_version == "v1", "swap did not land"
        out = s.run([dict(r) for r in wave])
        assert all(r["weight_version"] == "v1" for r in out)
        if want_new is None:
            want_new = out
        else:
            assert [r["continuation"] for r in out] == [
                r["continuation"] for r in want_new
            ], f"round {i}: v1 answers drifted"
        s.stage_rollback()
        s.step()
        assert s.weight_version == "v0", "rollback did not land"
        out = s.run([dict(r) for r in wave])
        assert [r["continuation"] for r in out] == [
            r["continuation"] for r in want_old
        ], f"round {i}: rollback changed v0 answers"
    return sentinel.deltas()


def train_retrace_report(steps: int = 3) -> list[WatchDelta]:
    """Steady-state training: one warmup step compiles; ``steps`` more
    same-shaped steps must not."""
    import numpy as np

    from transformer_tpu.analysis.configs import TINY_TRAIN
    from transformer_tpu.train.state import TrainState, make_optimizer
    from transformer_tpu.train.trainer import make_train_step

    cfg, params, _ = _tiny_lm_setup()
    train_cfg = TINY_TRAIN
    tx = make_optimizer(cfg, train_cfg)
    state = TrainState(
        step=jax.numpy.int32(0), params=params, opt_state=tx.init(params)
    )
    step = jax.jit(make_train_step(cfg, train_cfg, tx=tx))
    B, L = train_cfg.batch_size, train_cfg.sequence_length
    rng = np.random.default_rng(0)

    def batch():
        ids = rng.integers(1, cfg.input_vocab_size, size=(B, L)).astype(np.int32)
        return ids, ids

    src, tgt = batch()
    state, _ = step(state, src, tgt, jax.random.PRNGKey(0))  # warmup
    sentinel = RetraceSentinel()
    sentinel.watch("train_step", step, budget=0)
    sentinel.snapshot()
    for i in range(steps):
        src, tgt = batch()
        state, _ = step(state, src, tgt, jax.random.PRNGKey(i))
    return sentinel.deltas()


def sharded_retrace_report(steps: int = 3) -> list[WatchDelta]:
    """Steady-state SHARDED serving (``--mesh``, serve/sharded.py): one
    LONG-LIVED scheduler whose canned programs are per-instance pjit twins
    over a 2-device mesh — the twins live on the instance, so the watched
    jit objects must be the scheduler's own, not the module-level ones.
    Same bucketing contract as the unsharded scenarios: after warmup, the
    sharded decode step, verify, prefill, and the shared pick programs
    must compile ZERO new programs. A resharding leak — an operand whose
    committed sharding drifts between calls, re-keying the pjit cache —
    shows up here as a steady-state retrace."""
    from transformer_tpu.serve import scheduler as sched
    from transformer_tpu.serve.scheduler import ContinuousScheduler

    if len(jax.devices()) < 2:
        # The CLI forces 8 virtual CPU devices before importing jax
        # (_ensure_cpu_devices); a bare interpreter without them cannot
        # build the mesh, so the scenario reports nothing rather than
        # failing for a reason that is not a retrace.
        return []
    cfg, params, tok = _tiny_lm_setup()
    s = ContinuousScheduler(
        params, cfg, tok, num_slots=2, max_total=32, default_max_new=4,
        mesh=2, speculate_k=2,
    )
    # Greedy only: the tiny bf16 analysis model NaNs under sampled
    # residual draws regardless of mesh (a numeric quirk of the canned
    # config, not a serving property); sampled-request parity is
    # tests/test_sharded.py's statement, over float32 models.
    waves = [
        [{"prompt": "the quick brown fox"}, {"prompt": "dog dog dog dog"}],
        [{"prompt": "the the the the the"}, {"prompt": "the lazy dog"}],
    ]
    for wave in waves:  # warmup covers every prefill bucket the waves touch
        out = s.run([dict(r) for r in wave])
        assert all("continuation" in r for r in out), out
    sentinel = RetraceSentinel()
    sentinel.watch("sharded decode(pool_step)", s._sharded.pool_step, budget=0)
    sentinel.watch("sharded verify(pool_verify)", s._sharded.pool_verify,
                   budget=0)
    sentinel.watch("sharded rollback(pool_rollback)", s._sharded.pool_rollback,
                   budget=0)
    sentinel.watch("sharded prefill(slot_prefill)", s._sharded.slot_prefill,
                   budget=0)
    sentinel.watch("pick(_pick_pool_verify) on sharded logits",
                   sched._pick_pool_verify, budget=0)
    sentinel.snapshot()
    for i in range(steps):
        out = s.run([dict(r) for r in waves[i % len(waves)]])
        assert all("continuation" in r for r in out), out
    return sentinel.deltas()
