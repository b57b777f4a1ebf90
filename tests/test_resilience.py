"""Fault-tolerant serving (``transformer_tpu/serve/resilience.py``,
docs/ROBUSTNESS.md): the deterministic fault plane, request deadlines /
cancellation / backpressure, the circuit-breaker degradation ladder, and
the seeded chaos drills.

The chaos contract every drill asserts: EVERY request is answered (success
or structured error), zero slots leak, zero prefix-cache pins stay
outstanding, the hot paths compile zero new programs while breakers flip,
and greedy answers return byte-identical once the plane disarms and the
breakers close. The fast subset (fixed seeds, >= 4 fault points) rides
tier-1; the full >= 200-episode sweep across >= 6 points runs under
``-m slow`` (both carry the ``chaos`` marker).
"""

import json
import queue
import threading
import time

import jax
import numpy as np
import pytest

from transformer_tpu.analysis.retrace import RetraceSentinel
from transformer_tpu.config import ModelConfig
from transformer_tpu.data.tokenizer import SubwordTokenizer
from transformer_tpu.models import transformer_init
from transformer_tpu.obs.events import EventLog, read_events
from transformer_tpu.serve import (
    ContinuousScheduler,
    FaultPlane,
    InjectedFault,
    PrefixCache,
    resilience,
)
from transformer_tpu.serve.resilience import (
    CircuitBreaker,
    TransientError,
    backoff_ms,
    classify_error,
)
from transformer_tpu.serve.scheduler import (
    _pick_pool_verify,
    _pool_rollback,
    _pool_verify,
    _slot_prefill,
    _slot_read_blocks,
    _slot_restore,
)


@pytest.fixture(scope="module")
def lm():
    # Deliberately IDENTICAL to tests/test_scheduler.py's fixture: the
    # slot-pool programs cache by shape, so the chaos drills reuse the
    # compiles the parity tests pay for (and vice versa).
    tok = SubwordTokenizer.build_from_corpus(
        ["ab cd ef gh ij kl mn"] * 3, target_vocab_size=300
    )
    cfg = ModelConfig(
        num_layers=1, d_model=16, num_heads=2, dff=32,
        input_vocab_size=tok.model_vocab_size,
        target_vocab_size=tok.model_vocab_size,
        max_position=32, decoder_only=True, tie_output=True,
        dtype="float32", dropout_rate=0.0,
    )
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    return params, cfg, tok


# --------------------------------------------------------------------------
# fault plane: grammar, determinism, installation


def test_fault_spec_grammar():
    plane = FaultPlane.parse(
        "serve.prefill:p=0.25,seed=7;obs.emit:at=2+5;draft.slow:every=3,ms=40;"
        "prefix.corrupt:times=1"
    )
    rules = plane._rules
    assert rules["serve.prefill"].p == 0.25
    assert rules["serve.prefill"].seed == 7
    assert rules["obs.emit"].at == frozenset({2, 5})
    assert rules["draft.slow"].every == 3
    assert rules["draft.slow"].delay_ms == 40.0
    assert rules["prefix.corrupt"].times == 1
    with pytest.raises(ValueError, match="unknown fault point"):
        FaultPlane.parse("serve.prefil:p=1")
    with pytest.raises(ValueError, match="unknown fault_spec key"):
        FaultPlane.parse("serve.prefill:prob=1")
    with pytest.raises(ValueError, match="twice"):
        # Silently keeping only the last clause would run half the drill.
        FaultPlane.parse("obs.emit:at=2;obs.emit:at=5")


def test_fault_schedules_deterministic():
    def fires(spec, calls=50):
        plane = FaultPlane.parse(spec)
        return [
            bool(plane.fire("serve.prefill")) for _ in range(calls)
        ]

    a = fires("serve.prefill:p=0.3,seed=11")
    b = fires("serve.prefill:p=0.3,seed=11")
    c = fires("serve.prefill:p=0.3,seed=12")
    assert a == b, "same seed must replay the same fault episode"
    assert a != c, "a different seed must explore a different schedule"
    assert 0 < sum(a) < 50
    # at / every / times semantics
    at = fires("serve.prefill:at=3+5", calls=6)
    assert at == [False, False, True, False, True, False]
    every = fires("serve.prefill:every=2,times=2", calls=8)
    assert every == [False, True, False, True, False, False, False, False]


def test_disarmed_plane_is_free_and_scoped():
    assert resilience.installed() is None
    resilience.maybe_fail("serve.prefill")  # no plane: pure no-op
    with resilience.active(FaultPlane.parse("serve.prefill:p=1")) as plane:
        assert resilience.installed() is plane
        with pytest.raises(InjectedFault) as e:
            resilience.maybe_fail("serve.prefill")
        assert isinstance(e.value, OSError)       # leaf-site handler shape
        assert isinstance(e.value, TransientError)  # retry-policy shape
    assert resilience.installed() is None
    # leaf-module hooks were cleared with the plane
    from transformer_tpu.data import pipeline
    from transformer_tpu.obs import events
    from transformer_tpu.train import checkpoint

    assert events.fault_hook is None
    assert checkpoint.fault_hook is None
    assert pipeline.fault_hook is None


def test_backoff_deterministic_and_jittered():
    a = backoff_ms(20.0, 0, order=7)
    assert a == backoff_ms(20.0, 0, order=7)
    assert 10.0 <= a < 30.0                      # [0.5, 1.5) x base
    assert 20.0 <= backoff_ms(20.0, 1, order=7) < 60.0  # exponential
    assert backoff_ms(20.0, 0, order=8) != a     # spread across orders


def test_error_code_classification():
    assert classify_error(InjectedFault("serve.prefill", 1)) == "transient"
    assert classify_error(ValueError("bad")) == "validation"
    assert classify_error(RuntimeError("boom")) == "internal"


# --------------------------------------------------------------------------
# circuit breaker lifecycle (fake clock: deterministic cooldowns)


def test_breaker_ladder():
    clock = [0.0]
    seen = []
    b = CircuitBreaker(
        "x", threshold=2, cooldown_s=10.0, clock=lambda: clock[0],
        on_transition=lambda name, old, new: seen.append((old, new)),
    )
    assert b.allow() and b.state == "closed"
    b.record_failure()
    assert b.state == "closed" and b.allow()     # below threshold
    assert b.record_failure() is True            # K-th consecutive: opens
    assert b.state == "open" and not b.allow()
    clock[0] = 5.0
    assert not b.allow()                         # cooldown not elapsed
    clock[0] = 10.0
    assert b.allow() and b.state == "half_open"  # the probe
    assert b.record_failure() is True            # probe failed: re-open
    assert b.state == "open" and not b.allow()
    clock[0] = 25.0
    assert b.allow()
    b.record_success()                           # probe succeeded
    assert b.state == "closed" and b.allow()
    b.record_failure()
    b.record_success()                           # success resets the streak
    b.record_failure()
    assert b.state == "closed"
    assert seen == [
        ("closed", "open"), ("open", "half_open"), ("half_open", "open"),
        ("open", "half_open"), ("half_open", "closed"),
    ]
    assert b.stats["opens"] == 2 and b.stats["closes"] == 1


def test_breaker_open_ignores_stray_success():
    """A success recorded while OPEN (e.g. another slot's drafter in the
    same scheduler step, admitted before the trip) must NOT close the
    breaker — recovery goes through the half-open probe only, or an
    intermittent fault flaps the breaker every step."""
    clock = [0.0]
    b = CircuitBreaker("x", threshold=1, cooldown_s=10.0, clock=lambda: clock[0])
    assert b.record_failure() is True    # opens
    b.record_success()                   # stray pre-trip success: ignored
    assert b.state == "open" and not b.allow()
    clock[0] = 10.0
    assert b.allow() and b.state == "half_open"
    b.record_success()                   # the PROBE's success closes
    assert b.state == "closed"


class _FlakyFile:
    """A text sink whose next ``fail_next`` writes raise OSError."""

    def __init__(self, fail_next=0):
        self.fail_next = fail_next
        self.lines = []

    def write(self, s):
        if self.fail_next > 0:
            self.fail_next -= 1
            raise OSError("disk full")
        self.lines.append(s)

    def flush(self):
        pass


def test_eventlog_breaker_recovers(capsys):
    clock = [0.0]
    f = _FlakyFile(fail_next=3)
    log = EventLog(
        f,
        breaker=CircuitBreaker(
            "event_sink", threshold=2, cooldown_s=5.0, clock=lambda: clock[0]
        ),
    )
    log.emit("a")          # fail 1
    log.emit("b")          # fail 2: opens, ONE warning
    log.emit("c")          # open: dropped without touching the file
    assert f.fail_next == 1 and not f.lines
    clock[0] = 5.0
    log.emit("d")          # half-open probe: fails, re-opens (no 2nd warn yet)
    clock[0] = 10.0
    log.emit("e")          # probe succeeds: closed, event lands
    log.emit("f")
    assert [json.loads(s)["kind"] for s in f.lines] == ["e", "f"]
    err = capsys.readouterr().err
    assert err.count("sink open") == 2  # one warning per outage, not per fault


def test_eventlog_without_breaker_keeps_historic_contract(capsys):
    f = _FlakyFile(fail_next=1)
    log = EventLog(f)
    log.emit("a")
    log.emit("b")          # sink permanently disabled after first failure
    assert not f.lines
    assert capsys.readouterr().err.count("telemetry disabled") == 1


# --------------------------------------------------------------------------
# request lifecycle: deadlines, cancellation, backpressure, bounded retry


def test_deadline_expires_in_queue(lm):
    params, cfg, tok = lm
    s = ContinuousScheduler(params, cfg, tok, num_slots=2)
    out = s.run([
        {"prompt": "ab cd", "max_new": 3, "deadline_ms": 0},   # pre-expired
        {"prompt": "ab cd", "max_new": 3},                     # untouched
    ])
    assert out[0]["code"] == "deadline" and "error" in out[0]
    assert "continuation" in out[1]
    assert s.stats["deadline_expired"] == 1
    assert len(s._free) == 2


def test_deadline_expires_mid_generation(lm):
    params, cfg, tok = lm
    s = ContinuousScheduler(params, cfg, tok, num_slots=2)
    order = s.submit({"prompt": "ab cd", "max_new": 20, "deadline_ms": 60_000})
    s.admit()
    s.step()
    s.step()
    (slot, st), = s._active.items()
    st.deadline = time.perf_counter() - 1.0  # force expiry at the boundary
    s.step()
    out = s.drain_ready()
    assert out and out[0]["code"] == "deadline"
    assert "partial" in out[0]  # the tokens generated before expiry
    assert order not in s._done and len(s._free) == 2 and not s._active


def test_unparseable_deadline_is_validation_error(lm):
    params, cfg, tok = lm
    s = ContinuousScheduler(params, cfg, tok, num_slots=2)
    out = s.run([{"prompt": "ab cd", "max_new": 2, "deadline_ms": "soon"}])
    assert out[0]["code"] == "validation"


def test_cancel_queued_and_active(lm):
    params, cfg, tok = lm
    s = ContinuousScheduler(params, cfg, tok, num_slots=1)
    o1 = s.submit({"prompt": "ab cd", "max_new": 20})
    o2 = s.submit({"prompt": "ef gh", "max_new": 2})
    s.admit()   # o1 takes the only slot; o2 queued
    s.step()
    assert s.cancel(o2)                  # queued: registered
    assert s.cancel(o1)                  # in-flight: registered
    assert not s.cancel(o1)              # already pending
    assert not s.cancel(999)             # unknown order
    s.step()                             # the loop executes both
    assert not s.cancel(o1)              # already answered
    out = s.drain_ready()
    assert [r["code"] for r in out] == ["cancelled", "cancelled"]
    assert "partial" in out[0]           # in-flight cancel keeps its tokens
    assert len(s._free) == 1 and not s._active and not s.busy
    assert s.stats["cancelled"] == 2
    assert not s.cancel(o2)              # answered AND drained


def test_backpressure_bound(lm):
    params, cfg, tok = lm
    s = ContinuousScheduler(params, cfg, tok, num_slots=1, max_backlog=2)
    for _ in range(5):
        s.submit({"prompt": "ab", "max_new": 1})
    while s.busy:
        s.admit()
        s.step()
    out = s.drain_ready()
    codes = [r.get("code", "ok") for r in out]
    assert codes.count("backpressure") == 3 and codes.count("ok") == 2
    assert s.stats["backpressure"] == 3
    # refused requests still answer at their arrival-order position
    assert len(out) == 5


@pytest.mark.chaos
def test_transient_fault_retries_to_byte_identical_answer(lm):
    params, cfg, tok = lm
    reqs = [{"prompt": "ab cd ef", "max_new": 4}, {"prompt": "kl", "max_new": 2}]
    want = ContinuousScheduler(params, cfg, tok, num_slots=2).run(
        [dict(r) for r in reqs]
    )
    s = ContinuousScheduler(
        params, cfg, tok, num_slots=2, retry_backoff_ms=1.0
    )
    with resilience.active(FaultPlane.parse("serve.prefill:at=1")) as plane:
        out = s.run([dict(r) for r in reqs])
    assert out == want, "a retried admission must not change the answer"
    assert s.stats["retries"] == 1 and plane.episodes == 1
    assert len(s._free) == 2


@pytest.mark.chaos
def test_persistent_fault_answers_structured_transient(lm):
    params, cfg, tok = lm
    s = ContinuousScheduler(
        params, cfg, tok, num_slots=2, admission_retries=1,
        retry_backoff_ms=1.0,
    )
    with resilience.active(FaultPlane.parse("serve.prefill:p=1")):
        out = s.run([{"prompt": "ab cd", "max_new": 2}])
    assert out[0]["code"] == "transient" and "InjectedFault" in out[0]["error"]
    assert len(s._free) == 2 and not s.busy


# --------------------------------------------------------------------------
# leaf fault points: prefetch worker, checkpoint commit


@pytest.mark.chaos
def test_prefetch_fault_reraises_at_consumer():
    from transformer_tpu.data.pipeline import _threaded_device_prefetch

    batches = [
        (np.full((2, 2), i, np.int32), np.full((2, 2), i, np.int32))
        for i in range(4)
    ]
    got = []
    with resilience.active(FaultPlane.parse("data.prefetch:at=3")):
        with pytest.raises(InjectedFault):
            for b in _threaded_device_prefetch(iter(batches)):
                got.append(b)
    # the two pre-fault batches arrived, in order, before the re-raise
    assert [int(b[0][0, 0]) for b in got] == [0, 1]


@pytest.mark.chaos
def test_ckpt_write_fault_preserves_previous_checkpoint(tmp_path):
    from transformer_tpu.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), max_to_keep=3, is_primary=True)
    state = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    mgr.save(state, step=1)
    with resilience.active(FaultPlane.parse("ckpt.write:p=1")):
        with pytest.raises(OSError):
            mgr.save({"w": state["w"] + 1}, step=2)
    # the failed commit left no ckpt_2 and did not disturb ckpt_1
    assert mgr.all_steps() == [1]
    restored = mgr.restore_latest({"w": np.zeros((2, 3), np.float32)})
    np.testing.assert_array_equal(restored["w"], state["w"])


def test_restore_latest_falls_back_past_corrupt_checkpoint(tmp_path, capsys):
    from transformer_tpu.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), max_to_keep=5, is_primary=True)
    template = {"w": np.zeros((2, 3), np.float32)}
    for step in (1, 2, 3):
        mgr.save({"w": np.full((2, 3), step, np.float32)}, step=step)
    # Tear the LATEST checkpoint mid-npz (the crash shape atomic rename
    # prevents for OUR writes, but bit rot / partial copies still produce).
    npz = tmp_path / "ckpt_00000003" / "arrays.npz"
    npz.write_bytes(npz.read_bytes()[: npz.stat().st_size // 2])
    restored = mgr.restore_latest(dict(template))
    np.testing.assert_array_equal(restored["w"], np.full((2, 3), 2.0))
    assert "falling back" in capsys.readouterr().err
    # ...and a garbled meta.json on top: falls back once more
    (tmp_path / "ckpt_00000002" / "meta.json").write_text("{torn")
    (tmp_path / "ckpt_00000002" / "arrays.npz").write_bytes(b"not a zip")
    fallbacks = []
    restored = mgr.restore_latest(
        dict(template), on_fallback=lambda step, exc: fallbacks.append(step)
    )
    np.testing.assert_array_equal(restored["w"], np.full((2, 3), 1.0))
    assert fallbacks == [3, 2]
    # explicit-step restore still fails loudly
    with pytest.raises(Exception):
        mgr.restore(dict(template), 3)
    # ...and when EVERY checkpoint fails (the all-steps-unreadable shape of
    # a target/config mismatch), restore_latest re-raises instead of
    # silently restarting from scratch
    (tmp_path / "ckpt_00000001" / "arrays.npz").write_bytes(b"also not a zip")
    with pytest.raises(Exception):
        mgr.restore_latest(dict(template))
    # an EMPTY directory is still the quiet first-run case
    from transformer_tpu.train.checkpoint import CheckpointManager as CM

    empty = CM(str(tmp_path / "fresh"), is_primary=True)
    assert empty.restore_latest(dict(template)) is None


# --------------------------------------------------------------------------
# chaos drills: the fast tier-1 subset and the full sweep


def _chaos_answers_ok(out, n):
    assert len(out) == n, f"only {len(out)}/{n} requests answered"
    for r in out:
        assert ("continuation" in r) or ("error" in r and "code" in r), r


def _pool_invariants(s, cache=None):
    assert sorted(s._free) == list(range(s.num_slots)), "slot leak"
    assert not s._active and not s.busy
    assert s._queued_deadlines == 0, "queued-deadline counter drifted"
    if cache is not None:
        assert cache.outstanding_refs() == 0, "leaked prefix-cache pin"


_CHAOS_REQS = [
    {"prompt": "ab cd ef gh ij kl", "max_new": 4},
    {"prompt": "ab cd ef gh mn", "max_new": 3},
    {"prompt": "kl mn", "max_new": 2},
    {"prompt": "ab cd ef gh ij kl", "max_new": 4},
]


def _chaos_scheduler(params, cfg, tok, cache, telemetry=None):
    return ContinuousScheduler(
        params, cfg, tok, num_slots=2, speculate_k=2, prefix_cache=cache,
        breaker_threshold=2, breaker_cooldown_s=0.0, retry_backoff_ms=1.0,
        telemetry=telemetry,
    )


def _chaos_watch():
    sentinel = RetraceSentinel()
    sentinel.watch("verify", _pool_verify, budget=0)
    sentinel.watch("pick", _pick_pool_verify, budget=0)
    sentinel.watch("prefill", _slot_prefill, budget=0)
    sentinel.watch("restore", _slot_restore, budget=0)
    sentinel.watch("export", _slot_read_blocks, budget=0)
    sentinel.watch("rollback", _pool_rollback, budget=0)
    return sentinel


@pytest.mark.chaos
def test_chaos_fast_subset(lm):
    """Tier-1 chaos drill: fixed seeds, four fault points, one breaker
    round-trip — every request answered, nothing leaks, zero recompiles,
    byte-identical greedy answers once the plane disarms."""
    params, cfg, tok = lm
    cache = PrefixCache(cfg, block_tokens=4, budget_mb=8)
    s = _chaos_scheduler(params, cfg, tok, cache)
    want = s.run([dict(r) for r in _CHAOS_REQS])   # also populates the trie
    assert all("continuation" in r for r in want)
    s.run([dict(r) for r in _CHAOS_REQS])          # warm the hit paths
    sentinel = _chaos_watch()
    sentinel.snapshot()
    spec = (
        "serve.prefill:p=0.4,seed=3;prefix.match:p=0.4,seed=4;"
        "prefix.corrupt:p=0.5,seed=5;draft.propose:p=0.5,seed=6"
    )
    with resilience.active(FaultPlane.parse(spec)) as plane:
        for _ in range(3):
            out = s.run([dict(r) for r in _CHAOS_REQS])
            _chaos_answers_ok(out, len(_CHAOS_REQS))
    assert plane.episodes >= 8, f"only {plane.episodes} episodes injected"
    assert len({p for p, _ in plane.fired_log}) >= 3
    _pool_invariants(s, cache)
    # recovery: breakers close, greedy answers return byte-identical
    out = s.run([dict(r) for r in _CHAOS_REQS])
    assert out == want, "answers changed after the chaos round"
    assert s.breakers["speculative"].state == "closed"
    assert s.breakers["prefix_cache"].state == "closed"
    sentinel.assert_within_budget()  # 0 recompiles across breaker flips
    _pool_invariants(s, cache)


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_full_sweep(lm, tmp_path):
    """The acceptance sweep: >= 200 injected-fault episodes across >= 6
    distinct injection points, every request answered, zero leaked slots,
    zero outstanding prefix pins, 0 steady-state recompiles, byte-identical
    greedy answers after all breakers close — and the event log survives
    its own injected sink faults as parseable JSONL."""
    from transformer_tpu.obs import Telemetry

    params, cfg, tok = lm
    jsonl = str(tmp_path / "chaos.jsonl")
    telemetry = Telemetry(
        events=EventLog(
            jsonl,
            breaker=CircuitBreaker("event_sink", threshold=2, cooldown_s=0.0),
        ),
        interval=0.0,
    )
    cache = PrefixCache(cfg, block_tokens=4, budget_mb=8)
    s = _chaos_scheduler(params, cfg, tok, cache, telemetry=telemetry)
    want = s.run([dict(r) for r in _CHAOS_REQS])
    s.run([dict(r) for r in _CHAOS_REQS])
    sentinel = _chaos_watch()
    sentinel.snapshot()
    spec = (
        "serve.prefill:p=0.3,seed=1;prefix.match:p=0.3,seed=2;"
        "prefix.corrupt:p=0.3,seed=3;prefix.insert:p=0.3,seed=4;"
        "draft.propose:p=0.4,seed=5;draft.slow:every=5,ms=1;"
        "obs.emit:p=0.3,seed=6"
    )
    total = 0
    with resilience.active(FaultPlane.parse(spec)) as plane:
        for round_i in range(40):
            reqs = [dict(r) for r in _CHAOS_REQS]
            if round_i % 3 == 0:
                reqs.append({"prompt": "kl", "max_new": 2, "deadline_ms": 0})
            out = s.run(reqs)
            _chaos_answers_ok(out, len(reqs))
            total += len(reqs)
            if plane.episodes >= 220:
                break
        episodes = plane.episodes
        points = {p for p, _ in plane.fired_log}
    assert episodes >= 200, f"only {episodes} episodes over {total} requests"
    assert len(points) >= 6, f"only {sorted(points)} fired"
    _pool_invariants(s, cache)
    # recovery: all breakers close, answers return byte-identical
    out = s.run([dict(r) for r in _CHAOS_REQS])
    assert out == want
    assert s.breakers["speculative"].state == "closed"
    assert s.breakers["prefix_cache"].state == "closed"
    sentinel.assert_within_budget()
    _pool_invariants(s, cache)
    telemetry.close()
    # the log survived its own sink faults: every surviving line parses,
    # and the breaker transitions the sweep caused were recorded
    events = read_events(jsonl)
    assert events, "event log is empty"
    kinds = {e["kind"] for e in events}
    assert "serve.request" in kinds and "serve.breaker" in kinds


@pytest.mark.chaos
def test_hammer_thread_storm(lm):
    """Real-thread fault storm (the ISSUE's hammer): four client threads
    submit mixed deadline/plain requests while the scheduler loop runs
    under injected prefill + prefix faults. No slot leaks, no negative or
    leaked prefix refcounts, every request answered exactly once."""
    params, cfg, tok = lm
    cache = PrefixCache(cfg, block_tokens=4, budget_mb=8)
    s = ContinuousScheduler(
        params, cfg, tok, num_slots=2, prefix_cache=cache,
        breaker_threshold=2, breaker_cooldown_s=0.0, retry_backoff_ms=1.0,
    )
    n_threads, per = 4, 10

    def client(t):
        for i in range(per):
            req = {"prompt": "ab cd ef gh", "max_new": 2}
            if (t + i) % 4 == 0:
                req["deadline_ms"] = 0     # guaranteed queue expiry
            s.submit(req)

    spec = "serve.prefill:p=0.3,seed=8;prefix.match:p=0.3,seed=9"
    threads = [
        threading.Thread(target=client, args=(t,)) for t in range(n_threads)
    ]
    give_up = time.monotonic() + 120
    with resilience.active(FaultPlane.parse(spec)) as plane:
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads) or s.busy:
            s.admit()
            s.step()
            s.idle_backoff()
            assert time.monotonic() < give_up, "storm did not drain"
        for t in threads:
            t.join()
        # one last sweep: submissions racing the final busy check
        while s.busy:
            s.admit()
            s.step()
    out = s.drain_ready()
    _chaos_answers_ok(out, n_threads * per)
    _pool_invariants(s, cache)
    # refcounts never went negative: every node's pin balance is exactly 0
    assert cache.outstanding_refs() == 0
    assert plane.episodes > 0


# --------------------------------------------------------------------------
# serve loop integration: structured errors ride the JSONL surface


def test_serve_continuous_carries_error_codes(lm, capsys):
    from transformer_tpu.cli.serve import serve_continuous

    params, cfg, tok = lm
    s = ContinuousScheduler(params, cfg, tok, num_slots=2)
    q: queue.Queue = queue.Queue()
    q.put('{"prompt": "ab cd", "max_new": 2, "deadline_ms": 0}\n')
    q.put('{"prompt": "ab cd", "max_new": 2}\n')
    q.put(None)
    serve_continuous(q, s, cfg)
    lines = [
        json.loads(l) for l in capsys.readouterr().out.strip().splitlines()
    ]
    assert lines[0]["code"] == "deadline"
    assert "continuation" in lines[1]
