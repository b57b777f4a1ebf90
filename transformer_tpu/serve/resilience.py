"""Fault-tolerant serving: deterministic fault injection + circuit breakers.

At the scale the ROADMAP targets (multi-replica serving, millions of
users), partial failure is the steady state — the Mesh-TensorFlow premise
(PAPERS.md) applied to the serving tier: a drafter that hangs, a disk that
fills under the event log, a flipped bit in a cached KV block. PR 6 gave
the tier a machine-checked answer to "what happens when threads interleave
badly"; this module gives it one for "what happens when X breaks
mid-request", in three parts (docs/ROBUSTNESS.md is the long-form
catalogue):

- **Fault plane** (:class:`FaultPlane`): named, seeded injection points
  threaded through the scheduler (``serve.prefill``), the prefix cache
  (``prefix.match`` / ``prefix.corrupt`` / ``prefix.insert``), the
  speculative drafters (``draft.propose`` / ``draft.slow``), the telemetry
  sink (``obs.emit``), checkpoint commits (``ckpt.write``) and the data
  prefetch thread (``data.prefetch``). Enabled via ``--fault_spec`` or the
  test API (:func:`active`); a disabled plane costs ONE module-global
  ``None`` check per site and adds nothing to any trace (the
  ``fault_plane_inert`` contract pins jaxpr byte-identity, like
  telemetry).
- **Deterministic schedules**: every rule fires as a pure function of
  ``(seed, point, call_index)`` — the same spec replays the same fault
  episode, so a chaos failure is a reproducible test case, not a flake.
- **Circuit breakers** (:class:`CircuitBreaker`): K consecutive faults
  fail a subsystem OPEN to the plain byte-parity path (speculation stops
  drafting, the prefix cache stops matching/feeding, the event sink goes
  quiet), a cooldown later one HALF-OPEN probe decides recovery. Breaker
  state exports as obs gauges + ``serve.breaker`` events; ``obs
  summarize`` reports degraded time.

Import contract: stdlib-only (no jax, no numpy). Serve-side modules import
this directly; jax-free leaves (``obs/events.py``) and heavyweight-import
leaves (``train/checkpoint.py``, ``data/pipeline.py``) instead expose a
module-level ``fault_hook`` attribute that :func:`install` fills in — the
dependency points INTO this module only from code that already lives in
``serve/``.

Injected faults subclass ``OSError`` on purpose: at leaf sites (event-log
writes, checkpoint renames, prefetch ``device_put``) the injection flows
through exactly the ``except (OSError, ...)`` handler a real environmental
failure would take — the chaos suite exercises the production handlers,
not parallel test-only ones. They also subclass :class:`TransientError`,
the marker the scheduler's bounded admission retry keys on.
"""

from __future__ import annotations

import contextlib
import dataclasses
import random
import threading
import time
from typing import Iterator

# The breaker primitive lives in obs/ (stdlib-only, importable by the CLI
# flag layer without the serve stack — the event-log sink is itself a
# protected subsystem); re-exported here as part of the resilience surface.
from transformer_tpu.obs.breaker import (
    BREAKER_STATE_VALUE,
    CircuitBreaker,
)

#: Every injection point the plane recognizes — a typo'd ``--fault_spec``
#: fails at parse time, not silently never-fires. docs/ROBUSTNESS.md holds
#: the per-point semantics table.
FAULT_POINTS = frozenset({
    "serve.prefill",    # raise inside slot admission, before the prefill pick
    "prefix.match",     # raise inside PrefixCache.match (trie walk)
    "prefix.corrupt",   # flip a byte of a matched KV block (checksum catches)
    "prefix.insert",    # raise inside PrefixCache.insert (retirement feed)
    "draft.propose",    # raise inside the drafter's propose
    "draft.slow",       # sleep inside the drafter's propose (ms=N)
    "obs.emit",         # raise inside EventLog.emit's write
    "ckpt.write",       # raise inside CheckpointManager._commit (pre-rename)
    "data.prefetch",    # raise inside the prefetch worker, before device_put
    # Router-tier points (the supervision/HA drill surface, PR 11):
    "route.spawn",      # raise inside the supervisor's replica (re)spawn —
    #                     a crash-looping bootstrap, deterministically
    "route.hb",         # swallow a replica heartbeat at the router —
    #                     heartbeat-loss/failover storms without real stalls
    "route.takeover",   # raise inside the standby's per-replica takeover
    #                     handshake — partial adoptions + split-brain drills
    # Live-weights control plane (serve/upgrade.py, PR 15):
    "ckpt.swap",        # raise inside the scheduler's step-boundary param
    #                     flip — the swap aborts with old weights serving
    "route.upgrade",    # raise inside the coordinator's per-replica swap
    #                     dispatch — mid-rollout aborts + fleet rollback
    "route.canary",     # mark a canary answer bad in the per-version SLO
    #                     split — deterministic burn -> auto-rollback drills
})


class TransientError(RuntimeError):
    """Marker for failures worth a bounded, jitter-backed admission retry
    (as opposed to validation errors, which retrying can never fix)."""


class InjectedFault(OSError, TransientError):
    """A fault the plane fired. Subclasses ``OSError`` so leaf sites catch
    it exactly where they catch the real environmental failure it stands
    in for, and :class:`TransientError` so the scheduler's retry sees it."""

    def __init__(self, point: str, index: int):
        super().__init__(f"injected fault at {point} (call #{index})")
        self.point = point
        self.index = index


@dataclasses.dataclass
class FaultRule:
    """When one injection point fires. Exactly one trigger shape applies:
    ``at`` (explicit 1-based call indices) > ``every`` (every n-th call) >
    ``p`` (seeded Bernoulli per call; the default, p=1.0). ``times`` caps
    total fires; ``delay_ms`` turns the fault into a stall (sleep) instead
    of an exception — the slow-drafter / slow-sink shape."""

    point: str
    p: float = 1.0
    seed: int = 0
    at: frozenset[int] = frozenset()
    every: int = 0
    times: int = 0
    delay_ms: float = 0.0

    def should_fire(self, index: int, fired_so_far: int) -> bool:
        if self.times and fired_so_far >= self.times:
            return False
        if self.at:
            return index in self.at
        if self.every:
            return index % self.every == 0
        if self.p >= 1.0:
            return True
        # str-seeded Random is sha512-based — deterministic across runs and
        # platforms (unlike hash()-seeded tuples under PYTHONHASHSEED).
        return random.Random(
            f"{self.seed}|{self.point}|{index}"
        ).random() < self.p


class FaultPlane:
    """A set of :class:`FaultRule` plus per-point call counters and a fired
    log (the test introspection surface: ``plane.episodes`` counts injected
    faults, ``plane.fired_log`` says exactly which call of which point).

    Thread-safe: fault points are consulted from the scheduler thread, the
    prefetch worker, checkpoint writers and concurrent event-log emitters.
    """

    def __init__(self, rules: Iterator[FaultRule] | list[FaultRule] = ()):
        self._rules: dict[str, FaultRule] = {}
        for rule in rules:
            if rule.point not in FAULT_POINTS:
                raise ValueError(
                    f"unknown fault point {rule.point!r}; valid points: "
                    f"{', '.join(sorted(FAULT_POINTS))}"
                )
            if rule.point in self._rules:
                # Same hard-fail policy as unknown points: silently keeping
                # only the last clause would run half the intended drill.
                raise ValueError(
                    f"fault point {rule.point!r} appears twice in the spec"
                )
            self._rules[rule.point] = rule
        self._lock = threading.Lock()
        self.calls: dict[str, int] = {}
        self.fired: dict[str, int] = {}
        self.fired_log: list[tuple[str, int]] = []

    # ---- spec grammar ------------------------------------------------------

    @classmethod
    def parse(cls, spec: str) -> "FaultPlane":
        """``--fault_spec`` grammar (docs/ROBUSTNESS.md):

            spec   := clause (';' clause)*
            clause := point ':' param (',' param)*   |   point
            param  := 'p=' float | 'seed=' int | 'at=' int('+' int)*
                    | 'every=' int | 'times=' int | 'ms=' float

        Example: ``prefill.error by probability, a dead sink at call 5,
        a 40ms-slow drafter every 3rd propose``::

            serve.prefill:p=0.25,seed=7;obs.emit:at=5;draft.slow:every=3,ms=40
        """
        rules = []
        for clause in spec.split(";"):
            clause = clause.strip()
            if not clause:
                continue
            point, _, params = clause.partition(":")
            kw: dict = {"point": point.strip()}
            for param in params.split(",") if params else []:
                key, sep, value = param.partition("=")
                key, value = key.strip(), value.strip()
                if not sep:
                    raise ValueError(
                        f"fault_spec param {param!r} is not key=value"
                    )
                if key == "p":
                    kw["p"] = float(value)
                elif key == "seed":
                    kw["seed"] = int(value)
                elif key == "at":
                    kw["at"] = frozenset(int(v) for v in value.split("+"))
                elif key == "every":
                    kw["every"] = int(value)
                elif key == "times":
                    kw["times"] = int(value)
                elif key == "ms":
                    kw["delay_ms"] = float(value)
                else:
                    raise ValueError(
                        f"unknown fault_spec key {key!r} (valid: p, seed, "
                        "at, every, times, ms)"
                    )
            rules.append(FaultRule(**kw))
        return cls(rules)

    # ---- firing ------------------------------------------------------------

    @property
    def episodes(self) -> int:
        with self._lock:
            return len(self.fired_log)

    def fire(self, point: str) -> FaultRule | None:
        """Count one call at ``point``; return its rule iff it fires."""
        with self._lock:
            rule = self._rules.get(point)
            n = self.calls.get(point, 0) + 1
            self.calls[point] = n
            if rule is None or not rule.should_fire(n, self.fired.get(point, 0)):
                return None
            self.fired[point] = self.fired.get(point, 0) + 1
            self.fired_log.append((point, n))
            return rule

    def hook(self, point: str) -> None:
        """The callable :func:`install` plants into leaf modules'
        ``fault_hook`` slots: raise (or stall) iff ``point`` fires."""
        rule = self.fire(point)
        if rule is None:
            return
        if rule.delay_ms:
            time.sleep(rule.delay_ms / 1e3)
            return
        raise InjectedFault(point, self.calls[point])


# --------------------------------------------------------------------------
# global installation (the --fault_spec / test surface)

_PLANE: FaultPlane | None = None


def installed() -> FaultPlane | None:
    return _PLANE


def install(plane: FaultPlane | None) -> None:
    """Make ``plane`` the process-wide fault plane (None = disarm). Leaf
    modules that cannot import this one (obs stays jax-free and
    serve-free; checkpoint/pipeline must not drag the serve stack into
    every train import) expose a ``fault_hook`` module attribute instead —
    installation fills those slots, uninstallation clears them. Install
    BEFORE serving/training threads start (the CLIs install at startup;
    tests use the :func:`active` context manager)."""
    global _PLANE
    _PLANE = plane
    hook = None if plane is None else plane.hook
    from transformer_tpu.data import pipeline
    from transformer_tpu.obs import events
    from transformer_tpu.train import checkpoint

    events.fault_hook = hook
    checkpoint.fault_hook = hook
    pipeline.fault_hook = hook


@contextlib.contextmanager
def active(plane: FaultPlane):
    """Scoped installation — the chaos-test idiom::

        with resilience.active(FaultPlane.parse("serve.prefill:p=0.3")):
            scheduler.run(reqs)
    """
    install(plane)
    try:
        yield plane
    finally:
        install(None)


def maybe_fail(point: str) -> None:
    """The serve-side injection site: no-op without a plane (one global
    load + ``is None`` — the zero-overhead-when-disabled contract), else
    raise/stall per the point's rule. Host-side only, never traced."""
    plane = _PLANE
    if plane is None:
        return
    plane.hook(point)


def fired(point: str) -> bool:
    """Non-raising consultation for data-corruption-shaped points (the
    site mutates its own state when True — e.g. ``prefix.corrupt`` flips a
    stored block byte so the checksum path proves detection end-to-end)."""
    plane = _PLANE
    if plane is None:
        return False
    return plane.fire(point) is not None


# --------------------------------------------------------------------------
# structured error codes (the continuous scheduler's answer contract)

#: code -> meaning; docs/ROBUSTNESS.md carries the full table. Every error
#: the continuous scheduler answers carries one of these under ``"code"``
#: (the grouped path keeps its historical string-only shape).
ERROR_CODES = {
    "validation": "the request itself is unservable (bad field, over-length)",
    "routing": "request kind does not match what this export serves",
    "deadline": "the request's deadline_ms elapsed before completion",
    "cancelled": "the client (or operator) cancelled the request",
    "backpressure": "the admission queue is full (max_backlog)",
    "transient": "a transient fault persisted through the bounded retries",
    "resource": "a device resource budget (paged KV pool) was exhausted "
                "mid-flight; the partial continuation rides along",
    "upgrade": "a live-weights rollout command was refused (torn/mismatched "
               "checkpoint, no coordinator, or a rollout already in flight) "
               "— serving is untouched",
    "internal": "an unexpected failure; the request was isolated",
}


def classify_error(exc: BaseException) -> str:
    """Exception -> error code for admission-time failures."""
    if isinstance(exc, TransientError):
        return "transient"
    if isinstance(exc, (ValueError, TypeError, KeyError)):
        return "validation"
    return "internal"


def error_answer(code: str, message: str, **extra) -> dict:
    assert code in ERROR_CODES, code
    return {"error": message, "code": code, **extra}


def backoff_ms(base_ms: float, attempt: int, order: int) -> float:
    """Jittered exponential backoff for admission retries: deterministic
    per (order, attempt) — chaos runs replay bit-identically — but spread
    over [0.5, 1.5)x so a herd of same-tick failures does not retry in
    lockstep."""
    jitter = 0.5 + random.Random(f"backoff|{order}|{attempt}").random()
    return base_ms * (2 ** attempt) * jitter
