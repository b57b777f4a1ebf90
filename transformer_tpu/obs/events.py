"""Structured JSONL event log.

One event per line: ``{"ts": <unix seconds>, "kind": "<dotted.kind>", ...}``.
The kinds this repo emits (schema in docs/OBSERVABILITY.md):

- ``serve.request`` — one per finished/errored request: the full span
  breakdown (queue/prefill/first-token/total seconds, token counts).
- ``serve.batch`` — one per grouped-path decode batch.
- ``train.window`` — one per closed StepTimer window (log/eval/epoch
  boundary): steps, tokens, throughput, loss/accuracy/grad-norm.
- ``train.memory`` / ``train.compile`` — device memory stats and jit
  compile-cache accounting at epoch boundaries.
- ``trace.span`` — one per CLOSED tracing span (``obs/trace.py``):
  ``trace``/``span``/``parent`` lineage, ``name``, ``lane``, start ``t0``
  and ``dur_s``. Export with ``python -m transformer_tpu.obs trace``.
- ``slo.burn`` — one per SLO breach-state transition (``obs/slo.py``):
  ``name``, ``breached``, per-window burn rates.
- ``serve.retry`` — one per transient-admission retry: ``order``,
  ``attempt``, ``backoff_ms``, the fault, and the victim's ``trace`` id
  when tracing is on.
- ``route.dispatch`` / ``route.failover`` / ``route.revive`` — the
  multi-replica router's events (``serve/router.py``): per-request
  dispatch decisions (replica, policy, redispatch count, ``trace``),
  replica failures with the victim orders + trace ids, and half-open
  breaker revivals of heartbeat-timeout victims; ``obs summarize
  --merge`` reports per-replica request share and redispatches from
  these.
- ``route.spawn`` / ``route.retire`` / ``route.scale`` — the supervision
  tier (``serve/supervisor.py``): replica (re)spawn admissions
  (``heal_s`` death-to-admitted, ``warmed_tokens`` prefix-cache warm-up;
  ``gave_up=true`` when a crash loop exhausts its restart budget),
  drain-and-retire completions, and every autoscaling decision with the
  SLO burn-rate evidence window that justified it (``direction``,
  ``signal``, ``burn_rate``, ``evidence``). ``obs summarize --merge``
  renders the fleet section from these.
- ``route.intake`` / ``route.answered`` / ``route.hb`` — the primary
  router's HA journal (``--ha``; ``serve/standby.py`` tails these): one
  replayable intake record per accepted order (request, traceparent,
  remaining deadline budget), delivery marks from ``drain_ready``, and
  the periodic liveness beacon (authority ``epoch``, replica control
  ``ports``). An adopting router re-journals the orders it adopted, so
  chained takeovers replay from its log alone.
- ``route.takeover`` — emitted once by an adopting standby: the new
  ``epoch``, adopted/failed replicas, and how every undelivered order
  was resolved (recovered / re-owned / re-dispatched).
- ``route.mesh_mismatch`` — the Supervisor refused a spawned replica
  whose ``ready`` line reported a mesh shape different from the fleet's
  ``expected_mesh`` (``expected``, ``got``): the link is killed, the
  attempt counts as a spawn failure, and respawn backoff applies — a
  heal can never silently downgrade a sharded replica
  (docs/SERVING.md "Sharded replicas").
- ``route.upgrade`` / ``route.canary`` — the live-weights control plane
  (``serve/upgrade.py``): rollout lifecycle events tagged by ``phase``
  (``started``/``swapped``/``completed``/``rejected``/``failed``/
  ``rolled_back``) carrying the target ``version`` (checkpoint manifest
  digest), per-replica quiesce/swap seconds, ``time_to_upgrade_s``, and —
  on a rollback — ``rolled_back=true`` with the per-window burn
  ``evidence`` that triggered it; canary lifecycle (``started``/
  ``promoted``) with the pinned slice (``every``), window, and request
  count. ``route.dispatch`` additionally carries each dispatch's
  ``weight_version``, so ``obs summarize --merge`` renders the upgrade
  section (per-version request share, canary window, rollbacks,
  time-to-upgrade) from the same stream.
- ``route.postmortem`` — emitted by the Supervisor when it captures a
  dead or respawning replica's final flight record (``obs/flight.py``)
  before recycling the slot: ``replica``, ``origin`` (``wire`` for a
  live ``dump`` reply, ``file`` for an on-disk autodump salvaged after a
  SIGKILL), and the full ``record`` (events/spans/snapshots rings).
  ``python -m transformer_tpu.obs postmortem`` reconstructs the fleet's
  last seconds from these.
- ``flight.dump`` — one per non-automatic flight-recorder dump
  (signal / explicit request / clean close; periodic autodumps stay
  silent): ``reason``, ``path``, and ring sizes.
- ``metrics.snapshot`` — periodic full registry dump (histograms as
  count/sum/min/max/p50/p95/p99).

The machine-readable mirror of this list is :data:`EVENT_CATALOGUE`
below; a tier-1 AST sweep (tests/test_perf_observatory.py) fails if any
``emit`` call site in the package uses a kind missing from the catalogue
or from docs/OBSERVABILITY.md — the catalogue cannot silently rot.

Threading contract (machine-checked: the TPA1xx concurrency rules lint
this module, ``analysis/schedules.py eventlog_writers`` explores
concurrent-emit interleavings, and tests/test_obs.py hammers it with real
threads): ``emit`` is MULTI-WRITER SAFE. One lock serializes every write —
the serve CLI's scrape/flush threads, scheduler spans, and bench
attribution can share one log and two events can never interleave bytes
within a line (each line parses back as one JSON object). The
``_broken``-sink state transitions under the same lock, so concurrent
writers hitting a dead disk produce exactly one stderr warning. A full
disk must never kill the process being observed: OSError on write
downgrades to that warning and the log goes quiet — telemetry is an
instrument, not a dependency.
"""

from __future__ import annotations

import io
import json
import os
import sys
import threading
import time

# Fault-injection slot: ``serve.resilience.install`` plants the plane's
# hook here (and clears it on uninstall) so the ``obs.emit`` chaos point
# works WITHOUT this module importing resilience — obs stays jax-free and
# serve-free by import structure (test-pinned). The injected exception
# subclasses OSError on purpose: it flows through the same handler a full
# disk would.
fault_hook = None

#: Every event kind this package emits, with a one-line meaning. The
#: catalogue drift gate (tests/test_perf_observatory.py) AST-sweeps all
#: literal ``emit(kind, ...)`` call sites and asserts each kind appears
#: here AND in docs/OBSERVABILITY.md — add the entry (and the doc schema)
#: in the same change that adds an emit site.
EVENT_CATALOGUE = {
    "ckpt.fallback": "trainer restored an older checkpoint after a bad one",
    "flight.dump": "non-automatic flight-recorder dump (signal/request/close)",
    "metrics.snapshot": "periodic full metrics-registry dump",
    "route.answered": "HA journal: delivery mark for an accepted order",
    "route.canary": "canary slice lifecycle (started/promoted)",
    "route.dispatch": "router picked a replica for one request",
    "route.failover": "replica failure with victim orders re-dispatched",
    "route.hb": "HA journal: periodic primary liveness beacon",
    "route.intake": "HA journal: one replayable accepted-order record",
    "route.mesh_mismatch": "respawned replica reported the wrong mesh shape",
    "route.postmortem": "supervisor captured a dead replica's flight record",
    "route.retire": "supervised drain-and-retire completed",
    "route.revive": "half-open breaker revived a heartbeat-timeout victim",
    "route.scale": "autoscaling decision with its burn-rate evidence",
    "route.spawn": "replica (re)spawn admitted (or crash loop gave up)",
    "route.takeover": "standby adopted the fleet under a new epoch",
    "route.upgrade": "live-weights rollout lifecycle (by phase)",
    "schedules.test": "interleaving explorer's synthetic event (self-test)",
    "serve.batch": "one grouped-path decode batch",
    "serve.breaker": "admission circuit-breaker state transition",
    "serve.request": "one finished/errored request with span breakdown",
    "serve.retry": "one transient-admission retry",
    "slo.burn": "SLO breach-state transition with window burn rates",
    "trace.span": "one closed tracing span",
    "train.compile": "jit compile-cache accounting at an epoch boundary",
    "train.eval": "one eval pass result",
    "train.memory": "device memory stats at an epoch boundary",
    "train.predicted": "cost-model prediction snapshot for the train step",
    "train.preempt": "preemption checkpoint written on signal",
    "train.window": "one closed StepTimer throughput window",
}


class EventLog:
    """Append-only JSONL event writer.

    ``breaker`` (optional, duck-typed ``serve.resilience.CircuitBreaker``)
    upgrades the permanent ``_broken`` downgrade to the graceful-degradation
    ladder: K consecutive write failures OPEN the sink (events dropped,
    one stderr warning per outage), a cooldown later one half-open emit
    re-probes the disk, and success closes the breaker — a transiently
    full disk costs an outage window, not the rest of the process's
    telemetry. Without a breaker the historical contract holds: first
    failure disables the sink for good, with exactly one warning.
    """

    def __init__(
        self, path_or_file: "str | io.TextIOBase", breaker=None
    ) -> None:
        self._lock = threading.Lock()
        self._broken = False
        self._breaker = breaker
        if isinstance(path_or_file, str):
            d = os.path.dirname(os.path.abspath(path_or_file))
            os.makedirs(d, exist_ok=True)
            self._file = open(path_or_file, "a", buffering=1)
            self.path: str | None = path_or_file
            self._owns = True
        else:
            self._file = path_or_file
            self.path = getattr(path_or_file, "name", None)
            self._owns = False

    def emit(self, kind: str, **fields) -> None:
        """Append one event. ``fields`` must be JSON-serializable; a ``ts``
        stamp is added unless the caller supplies one.
        Safe to call from any thread: the line is serialized outside the
        lock, the single ``write`` happens inside it."""
        if self._broken:
            # Racy fast path — a dead sink must not keep paying json.dumps
            # per emit; the authoritative re-check happens under the lock.
            return
        if self._breaker is not None and not self._breaker.allow():
            return  # sink open: drop quietly until the cooldown re-probe
        event = {"ts": fields.pop("ts", None) or round(time.time(), 6),
                 "kind": kind, **fields}
        line = json.dumps(event, sort_keys=False)
        try:
            with self._lock:
                if self._broken:
                    return
                if fault_hook is not None:
                    fault_hook("obs.emit")  # raises an OSError-shaped fault
                self._file.write(line + "\n")
        except (OSError, ValueError):  # ValueError: write to a closed file
            if self._breaker is not None:
                self._record_sink_failure()
                return
            if self._mark_broken():
                print(
                    f"obs: event log {self.path or '<stream>'} unwritable; "
                    "telemetry disabled for this process",
                    file=sys.stderr,
                )
        else:
            if self._breaker is not None:
                self._breaker.record_success()

    def _record_sink_failure(self) -> None:
        """Feed the breaker; warn exactly when this failure OPENS it (one
        warning per outage, whichever of emit/flush trips it)."""
        if self._breaker.record_failure():
            print(
                f"obs: event log {self.path or '<stream>'} unwritable; "
                "sink open (will re-probe after cooldown)",
                file=sys.stderr,
            )

    def _mark_broken(self) -> bool:
        """Flip the sink dead under the lock; True for exactly one caller
        (so N concurrent writers racing a dead disk warn once, not N
        times)."""
        with self._lock:
            was = self._broken
            self._broken = True
            return not was

    def flush(self) -> None:
        try:
            with self._lock:
                if self._broken:
                    return
                self._file.flush()
        except (OSError, ValueError):
            if self._breaker is not None:
                # A flush can be the fault that OPENS the sink; without the
                # shared warn-on-trip the outage would start silently
                # (emit()'s allow() short-circuits before any write).
                self._record_sink_failure()
            else:
                self._mark_broken()

    def close(self) -> None:
        self.flush()
        if self._owns:
            try:
                self._file.close()
            except OSError:
                pass


def read_events(path: str, kind: str | None = None) -> list[dict]:
    """Load a JSONL event log; malformed lines (a crash mid-write) are
    skipped, never fatal — the summarize CLI must work on truncated logs."""
    out: list[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            if isinstance(ev, dict) and (kind is None or ev.get("kind") == kind):
                out.append(ev)
    return out
