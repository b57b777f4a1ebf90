"""Multi-replica serving tier: the front-end router.

Everything serve-side up to PR 9 was one process wrapping one
``ContinuousScheduler``. This module is the scale-out step the ROADMAP
gates on: a front-end :class:`Router` that owns client intake (the same
``submit``/``submit_done``/``drain_ready`` surface the scheduler exposes,
plus the line-oriented loop in ``cli/router.py``) and dispatches to N
replica workers (``serve/replica.py``), each running the existing
scheduler over its own model copy — plain CPU processes in CI, per-replica
sharded processes (``parallel/mesh.py``) on real pods. Mesh-TensorFlow
(PAPERS.md) grounds the sharded-replica story; the one-write-head paper's
cheap-KV argument is why N per-replica slot pools stay affordable.

Dispatch policy — **prefix affinity first, least-loaded fallback**:

- The prompt's leading ``affinity_block``-aligned token blocks are hashed
  (the same block alignment the prefix cache keys on), and the request is
  routed by rendezvous hashing over the healthy replicas — repeated system
  prompts land on the replica whose ``PrefixCache`` is already warm, and a
  replica death only remaps the keys it owned.
- When the affine replica is unhealthy, or its load (router-assigned
  in-flight + heartbeat backlog) exceeds the least-loaded replica's by
  more than ``affinity_slack``, the request falls back to least-loaded.
  Load is fed by replica heartbeats (backlog/free-slot gauges over the
  control channel) topped up with the router's own assignment counts
  between beats.

**Zero-loss failover**: every dispatched-but-unanswered request is tracked
in an order-keyed in-flight table. A replica death (pipe EOF, send
failure, process exit, missed heartbeats — all feeding a per-replica
:class:`~transformer_tpu.serve.resilience.CircuitBreaker`) re-enqueues its
victims at the FRONT of the pending queue in their original order, with
their original trace id and deadline intact; redispatch is bounded
(``max_redispatch``) and exhaustion answers a structured ``transient``
error. A failed-over worker whose PROCESS still runs (a heartbeat-timeout
victim: GC pause, slow step) earns its way back: when a fresh heartbeat
arrives after the death mark and the breaker's cooldown has elapsed, the
half-open probe re-admits the link (``route.revive``) and its first
answered request closes the breaker — exited/SIGKILLed workers stay dead. **At-most-once answers** are enforced by the router's order-keyed
answer funnel: an answer for an order that is already answered (or already
drained) is counted and dropped, so the benign race of a replica answering
just before its death can never double-answer a client.

**Tracing**: every request gets a router-minted trace identity
(:class:`~transformer_tpu.obs.trace.SpanContext`, parented under an
incoming client ``traceparent`` when one is present) and every forwarded
request carries it as the W3C ``traceparent`` header — the replica's
``serve.request`` root parents under the router's ``route.request`` span,
so ``python -m transformer_tpu.obs summarize/trace/slo --merge`` re-joins
one request's spans across the router's and every replica's JSONL log
(docs/OBSERVABILITY.md "Multi-source merge"). ``route.dispatch`` /
``route.failover`` events carry the victim trace ids.

**Disaggregated prefill/decode** (``disaggregate=True``): replicas are
marked prefill-only or decode-only. A request is first sent to a prefill
replica, which ingests the prompt (``max_new=0``) and hands back the
prompt's KV as host-side token-aligned blocks in the prefix-cache block
format (``serve/replica.py`` ``export_blocks``); the router forwards the
request plus blocks to a decode replica, which injects them into its own
``PrefixCache`` so admission restores them without a model forward.
Greedy answers stay byte-identical (the prefix-cache parity contract);
losing either side mid-handoff degrades to a full prefill on a decode
replica, never to a lost request.

Threading contract (linted by TPA101–105 and explored by
``analysis/schedules.py router_dispatch_tables``): client threads call
``submit``/``submit_done``/``drain_ready`` under the intake lock
(exactly the scheduler's intake split); per-replica READER threads
only parse pipe lines into the router's inbox ``queue.Queue`` and touch
no other router state; all dispatch/answer/liveness tables are owned by
the single router thread driving :meth:`pump`. Nothing in this module
touches jax — the router process stays model-free (the tokenizer is the
only vocabulary it needs, for affinity hashing): no weights loaded, no
programs compiled, so it restarts cheaply and survives replica OOMs
untouched.
"""

from __future__ import annotations

import dataclasses
import glob
import hashlib
import json
import os
import queue
import subprocess
import sys
import threading
import time
from collections import deque

from transformer_tpu.obs.trace import SpanContext
from transformer_tpu.serve.resilience import (
    CircuitBreaker,
    InjectedFault,
    error_answer,
    maybe_fail,
)


def affinity_key(ids, block: int) -> "int | None":
    """Hash of the prompt's leading ``block``-aligned token blocks — the
    prefix the replica-side ``PrefixCache`` would match (the prompt minus
    its last token, rounded down to whole blocks, mirroring
    ``PrefixCache.match``'s ``ids[:L-1]`` contract). None when the prompt
    is shorter than one block: there is no shared prefix worth pinning, so
    the request routes least-loaded."""
    if block < 1:
        return None
    aligned = ((len(ids) - 1) // block) * block
    if aligned < block:
        return None
    digest = hashlib.blake2b(
        ("/".join(str(i) for i in ids[:aligned])).encode(), digest_size=8
    ).digest()
    return int.from_bytes(digest, "big")


def _rendezvous(key: int, name: str) -> int:
    """Highest-random-weight score of (affinity key, replica name): each
    key independently ranks every replica, so removing a dead replica
    remaps ONLY the keys it owned — the warm prefix caches on survivors
    keep their traffic."""
    digest = hashlib.blake2b(
        key.to_bytes(8, "big") + name.encode(), digest_size=8
    ).digest()
    return int.from_bytes(digest, "big")


class _RouterLineError(ValueError):
    """Line-intake routing/parse failure, answered with the bare message
    (byte-identical to ``cli/serve.py``'s grouped-path kind-mismatch
    answers — the router must not change what a bad line reads back)."""


def parse_router_line(line: str) -> dict:
    """One stdin line -> LM request dict for the router (raises
    :class:`_RouterLineError` with the exact message shapes
    ``cli/serve.py`` answers with — the router serves LM exports only, so
    the kind-mismatch wording matches ``_route_lm_request``)."""
    if line.startswith("{"):
        req = json.loads(line)
        if not isinstance(req, dict):
            raise ValueError("request must be a JSON object")
    else:
        req = {"prompt": line}
    if "src" in req:
        raise _RouterLineError("LM export serves 'prompt', not 'src'")
    if "prompt" not in req:
        if "fill" in req:
            raise _RouterLineError("LM export serves 'prompt', not 'fill'")
        raise _RouterLineError(
            "request needs 'src' (seq2seq), 'prompt' (LM) or "
            "'fill' (masked-LM)"
        )
    return req


@dataclasses.dataclass
class _Tracked:
    """One accepted request, from submit to its exactly-once answer."""

    order: int
    req: dict
    ctx: SpanContext            # router-minted trace identity (stable
    #                             across redispatches — the failover
    #                             contract: original order, trace id and
    #                             deadline ride every re-submission)
    t_submit: float
    deadline: float | None      # absolute perf_counter, or None
    affinity: int | None
    attempts: int = 0           # total dispatch count (incl. the disagg
    #                             prefill->decode stage progression)
    redispatches: int = 0       # failover-driven re-dispatches only —
    #                             what max_redispatch bounds and the
    #                             route.dispatch event reports
    refailed: bool = False      # the NEXT dispatch is a failover
    #                             redispatch (set by _fail_replica)
    replica: int | None = None  # current assignment (None = pending)
    t_dispatch: float | None = None   # first dispatch (queue-latency edge)
    stage: str = "decode"       # disaggregation: "prefill" -> "decode"
    blocks: object = None       # prefill handoff payload (opaque to us)
    blocks_tokens: int = 0
    span_root: object = None    # tracing only (None without a tracer)


class ReplicaLink:
    """The router's handle on one replica worker: an outbound ``send``
    plus liveness/load bookkeeping. The subprocess transport is
    :class:`ReplicaProcess`; tests and the deterministic-schedule scenario
    substitute in-process fakes with the same three-method surface
    (``send`` / ``alive`` / ``close``)."""

    def __init__(self, index: int, name: str, role: str = "both"):
        self.index = index
        self.name = name
        self.role = role            # "both" | "prefill" | "decode"
        # Router-thread-owned load/liveness bookkeeping (heartbeat-fed,
        # topped up by the router's own assignment counts between beats).
        self.inflight = 0
        self.hb_backlog = 0
        self.hb_free = 0
        self.hb_active = 0
        self.last_hb: float | None = None
        self.dispatched = 0
        self.answered = 0
        self.dead = False
        self.died_at: float | None = None  # monotonic death mark: only a
        #                                    heartbeat NEWER than this can
        #                                    revive the link
        # Supervision states (serve/supervisor.py): a warming replacement
        # is bootstrapping/cache-warming and takes no traffic yet; a
        # draining victim finishes its in-flight work, then retires for
        # good (retired links are never respawned or revived).
        self.warming = False
        self.draining = False
        self.retired = False
        # Live-weights rollout state (serve/upgrade.py): an `upgrading`
        # link is quiescing/swapping and takes no new dispatches (its
        # in-flight work finishes on its admission-time weights); `wv` is
        # the replica's last-confirmed weight_version tag (ready/hb/
        # upgraded messages), None until the fleet is version-tagged.
        self.upgrading = False
        self.wv: str | None = None
        # Sharded-replica shape (serve/sharded.py): the canonical
        # 'data=N' string the replica's ready/hb messages report, None
        # for a single-device worker. The Supervisor's expected_mesh
        # check reads this at admission — the router itself never sees
        # device topology beyond the string.
        self.mesh: str | None = None
        # What the replica's ready line says it runs on: platform,
        # device_kind and the devices it holds (the parent never asks jax).
        self.device: dict | None = None
        self.control_port: int | None = None  # --ha takeover socket
        # Flight-recorder hooks (obs/flight.py): where this worker's
        # on-disk dumps land (parsed from --metrics_jsonl at spawn), and
        # the last record it shipped over the wire (a `dump` reply) — the
        # Supervisor's postmortem capture reads these.
        self.metrics_jsonl: str | None = None
        self.flight_record: dict | None = None

    # -- transport surface (overridden by real links) -----------------------

    def send(self, msg: dict) -> None:
        raise NotImplementedError

    def alive(self) -> bool:
        """TRANSPORT liveness only (is the worker process still running?);
        the router's failover policy lives in ``dead``, which the revival
        path can clear again — so this must not consult it."""
        return True

    def close(self) -> None:
        pass

    def kill(self) -> None:
        """Force the worker down (supervisor slot reclaim); transports
        without a process are a no-op."""

    def serves(self, stage: str) -> bool:
        return self.role == "both" or self.role == stage


# One process per chip: a TPU chip belongs to one process at a time, so the
# router parent stays off JAX and hands replica i its own chip (or, under
# --mesh N, its own group of N chips) through the variables libtpu reads at
# start-up. The chips are counted by their device nodes, so the parent never
# loads the TPU runtime. (PCI is no guide: a machine that is handed one chip
# of a four-chip host shows four PCI devices and one node — measured on the
# v5e, PR 21.)
# TPU_CHIPS_PER_PROCESS_BOUNDS by chips per replica, on a 2x2 or 2x4 host.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def count_tpu_chips() -> int:
    """TPU chips this host can open: ``/dev/accel<N>`` nodes (v4 and
    earlier drivers) or numbered ``/dev/vfio/<N>`` groups (v5e and later;
    ``/dev/vfio/vfio`` is the control node). 0 off a TPU host."""
    nodes = glob.glob("/dev/accel[0-9]*") + glob.glob("/dev/vfio/[0-9]*")
    return len(nodes)


def replica_chip_env(index: int, chips_per_replica: int, host_chips: int) -> dict:
    """The environment that gives replica ``index`` chips
    ``[index * m, (index + 1) * m)`` of this host as a one-process slice.
    Empty off a TPU host (CPU workers share the host platform). Raises when
    the fleet outgrows the host — a second process on a taken chip would
    hang at start-up instead of failing."""
    if not host_chips:
        return {}
    m = chips_per_replica
    if m not in _CHIP_BOUNDS:
        raise ValueError(
            f"a replica can span {sorted(_CHIP_BOUNDS)} chips, not {m}"
        )
    if (index + 1) * m > host_chips:
        raise ValueError(
            f"replica {index} needs chips {index * m}..{(index + 1) * m - 1}: "
            f"{index + 1} replica(s) x {m} chip(s) each = {(index + 1) * m} "
            f"chips, but this host has {host_chips}"
        )
    return {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in range(index * m, (index + 1) * m)),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BOUNDS[m],
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


class ReplicaProcess(ReplicaLink):
    """A replica worker as a subprocess speaking JSONL over its pipes.

    The reader thread's ONLY job is parsing stdout lines into the router's
    inbox (and an ``exit`` sentinel on EOF) — every other piece of state
    on this object is owned by the router thread, so the TPA101 shared-
    state surface between the two is exactly the synchronized queue."""

    def __init__(self, index: int, name: str, argv: list[str],
                 role: str = "both", env: "dict | None" = None):
        super().__init__(index, name, role=role)
        self._proc = subprocess.Popen(
            argv, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=sys.stderr, text=True, bufsize=1, env=env,
        )

    @classmethod
    def spawn(cls, index: int, worker_args: list[str], role: str = "both",
              name: str | None = None) -> "ReplicaProcess":
        """Launch ``python -m transformer_tpu.serve.replica`` with
        ``worker_args`` plus the replica's identity flags, on the chips
        its index assigns it (a respawn at the same index lands on the
        same chips)."""
        from transformer_tpu.serve.sharded import parse_mesh_spec

        name = name or f"replica{index}"
        argv = [
            sys.executable, "-m", "transformer_tpu.serve.replica",
            "--replica_name", name, "--role", role, *worker_args,
        ]

        def flag(key: str) -> "str | None":
            # Both `--key VALUE` and `--key=VALUE` spellings.
            for i, arg in enumerate(worker_args):
                if arg == key and i + 1 < len(worker_args):
                    return worker_args[i + 1]
                if arg.startswith(key + "="):
                    return arg.split("=", 1)[1]
            return None

        chips = replica_chip_env(
            index, parse_mesh_spec(flag("--mesh")) or 1, count_tpu_chips()
        )
        link = cls(
            index, name, argv, role=role,
            env={**os.environ, **chips} if chips else None,
        )
        # Remember where the worker's flight dumps will land: the
        # Supervisor salvages <path>.flight.json after a hard kill.
        link.metrics_jsonl = flag("--metrics_jsonl") or None
        return link

    def start_reader(self, inbox: "queue.Queue") -> None:
        threading.Thread(
            target=self._read_loop, args=(inbox, self._proc.stdout),
            name=f"router-read-{self.name}", daemon=True,
        ).start()

    def _read_loop(self, inbox: "queue.Queue", stdout) -> None:
        for line in stdout:
            line = line.strip()
            if not line:
                continue
            try:
                msg = json.loads(line)
            except ValueError:
                continue  # torn final line of a dying replica
            if isinstance(msg, dict):
                inbox.put((self.index, msg))
        # The pid stamps the sentinel so a supervisor-respawned REPLACEMENT
        # at this index is never failed over by its predecessor's EOF (the
        # old reader thread can outlive the link swap).
        inbox.put((self.index, {"type": "exit", "pid": self._proc.pid}))

    def send(self, msg: dict) -> None:
        stdin = self._proc.stdin
        if stdin is None or self._proc.poll() is not None:
            raise BrokenPipeError(f"replica {self.name} is gone")
        stdin.write(json.dumps(msg) + "\n")
        stdin.flush()

    def alive(self) -> bool:
        return self._proc.poll() is None

    def pid(self) -> int:
        return self._proc.pid

    def kill(self) -> None:
        if self._proc.poll() is None:
            self._proc.kill()

    def close(self, timeout: float = 10.0) -> None:
        try:
            self.send({"type": "shutdown"})
        except (OSError, ValueError):
            pass
        try:
            self._proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self._proc.kill()
            self._proc.wait()


class Router:
    """Front-end dispatcher over N replica links.

    Client surface (any thread, intake-locked): :meth:`submit` /
    :meth:`submit_done` / :meth:`drain_ready` / the ``busy`` /
    ``has_ready`` / ``backlog`` properties — deliberately the scheduler's
    own programmatic shape, so a caller written against one drives the
    other. Control surface (the ONE router thread): :meth:`pump`, which
    drains the inbox (answers, heartbeats, prefill handoffs, exits),
    sweeps liveness, and dispatches pending requests. :meth:`run` is the
    batch convenience tests and benches use."""

    def __init__(
        self,
        links: "list[ReplicaLink]",
        *,
        encode=None,
        bos_id: int = 1,
        affinity_block: int = 16,
        affinity_slack: int = 4,
        max_redispatch: int = 2,
        heartbeat_timeout_s: float = 0.0,
        breaker_threshold: int = 1,
        breaker_cooldown_s: float = 30.0,
        disaggregate: bool = False,
        telemetry=None,
        supervisor=None,
        scaler=None,
        upgrader=None,
        slos=None,
        ha: bool = False,
        epoch: int = 1,
        ha_heartbeat_s: float = 0.5,
    ):
        if not links:
            raise ValueError("router needs at least one replica link")
        self.links = list(links)
        self.encode = encode          # str -> token ids (affinity hashing
        #                               only; None = least-loaded always)
        self.bos_id = bos_id
        self.affinity_block = affinity_block
        self.affinity_slack = affinity_slack
        self.max_redispatch = max(0, max_redispatch)
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.disaggregate = disaggregate
        if disaggregate:
            if not any(l.serves("prefill") for l in links) or not any(
                l.serves("decode") for l in links
            ):
                raise ValueError(
                    "disaggregate mode needs at least one prefill-capable "
                    "and one decode-capable replica"
                )
        # Inbox: the ONE channel from replica reader threads (and fakes)
        # into the router thread — (replica_index, msg) tuples.
        self.inbox: queue.Queue = queue.Queue()
        # Intake state (client threads + router thread, under this lock —
        # the same split the scheduler's submit/drain contract uses).
        self._intake_lock = threading.Lock()
        self._next_order = 0
        self._done: dict[int, dict] = {}
        self._emit_next = 0
        self._pending: deque[_Tracked] = deque()
        # Router-thread-owned tables.
        self._inflight: dict[int, _Tracked] = {}
        # Per-replica breakers: a death/timeout opens the breaker so the
        # dispatcher stops offering traffic; a half-open probe after the
        # cooldown lets a recovered link earn its way back.
        self._breaker_threshold = breaker_threshold
        self._breaker_cooldown_s = breaker_cooldown_s
        self.breakers = [
            CircuitBreaker(
                f"replica_{l.name}", threshold=breaker_threshold,
                cooldown_s=breaker_cooldown_s,
            )
            for l in links
        ]
        self.stats = {
            "submitted": 0, "dispatched": 0, "redispatched": 0,
            "answered": 0, "failovers": 0, "revivals": 0,
            "duplicate_answers": 0, "expired": 0, "exhausted": 0,
            "no_replica": 0, "prefill_handoffs": 0, "dropped_heartbeats": 0,
        }
        # ---- supervision / autoscaling / HA (serve/supervisor.py,
        # serve/standby.py; docs/SERVING.md "Self-healing fleet") ----------
        self._sup = supervisor
        self._scaler = scaler
        # Live-weights control plane (serve/upgrade.py): the rollout
        # coordinator, and the fleet's TARGET weights — (ckpt_dir,
        # weight_version) once a rollout starts/completes, None before
        # (and after a rollback). The supervisor's spawn recipe reads it
        # so a respawned replacement bootstraps at the version the fleet
        # is converging to, never the stale original argv weights.
        self._upgrader = upgrader
        self.weight_target: "tuple[str, str] | None" = None
        self.ha = ha
        self.epoch = epoch
        self.ha_heartbeat_s = ha_heartbeat_s
        self._last_ha_hb = 0.0
        # The router's OWN SLO engine over the answer funnel: replicas ship
        # per-answer latency in the "slo" side channel, the funnel records
        # it here, and the FleetScaler consumes the burn gauges — the PR 9
        # engine driving fleet size, as the ROADMAP elasticity item asks.
        self._slo_engine = None
        if slos is not None and hasattr(slos, "maybe_evaluate"):
            # A prebuilt SLOEngine (tests pin the clock/interval; the
            # standby hands over its own engine across the cutover).
            self._slo_engine = slos
        elif slos:
            from transformer_tpu.obs.slo import SLOEngine, parse_slo_spec

            specs = (
                parse_slo_spec(slos) if isinstance(slos, str) else tuple(slos)
            )
            if specs:
                self._slo_engine = SLOEngine(
                    specs,
                    registry=(
                        telemetry.registry if telemetry is not None else None
                    ),
                    emit=telemetry.emit if telemetry is not None else None,
                )
        # submit -> first dispatch; bounded (the bench reads it — the
        # serve-forever process must not grow a list per request when the
        # same data lives in the router_queue_seconds histogram).
        self.queue_latencies: "deque[float]" = deque(maxlen=65536)
        self._tel = telemetry
        self._tracer = getattr(telemetry, "tracer", None)
        if telemetry is not None:
            reg = telemetry.registry
            self._m_dispatch = reg.counter(
                "router_dispatch_total", "requests dispatched to replicas")
            self._m_redispatch = reg.counter(
                "router_redispatch_total",
                "failover re-dispatches of in-flight requests")
            self._m_failover = reg.counter(
                "router_failover_total", "replica failures handled")
            self._m_answers = reg.counter(
                "router_answers_total", "replica answers accepted")
            self._m_dup = reg.counter(
                "router_duplicate_answers_total",
                "late/duplicate replica answers dropped by the funnel")
            self._m_queue_s = reg.histogram(
                "router_queue_seconds", "submit -> first dispatch")
            self._m_replicas = reg.gauge(
                "router_replicas_live", "replica links currently usable")
            self._m_replicas.set(len(links))
            self._m_fleet = reg.gauge(
                "route_fleet_size",
                "healthy serving replicas (live, admitted, not draining)")
            self._m_fleet.set(len(links))
        if supervisor is not None:
            supervisor.attach(self)
        if scaler is not None:
            if supervisor is None:
                raise ValueError("a FleetScaler needs a Supervisor to act")
            scaler.bind(self, supervisor)
        if upgrader is not None:
            upgrader.attach(self)

    # ---- client intake (any thread) ---------------------------------------

    def submit(self, req: dict) -> int:
        """Accept one LM request; returns its output order. Affinity and
        trace identity are minted here so failover can re-dispatch with
        both intact."""
        now = time.perf_counter()
        span_root = None
        parent = SpanContext.from_traceparent(req.get("traceparent"))
        if self._tracer is not None:
            span_root = self._tracer.start_span(
                "route.request", parent=parent, lane="router"
            )
            ctx = span_root.ctx
        else:
            ctx = parent.child() if parent is not None else SpanContext.new()
        affinity = None
        if self.encode is not None:
            try:
                ids = [self.bos_id, *self.encode(str(req.get("prompt", "")))]
                affinity = affinity_key(ids, self.affinity_block)
            except Exception:  # tpa: disable=TPA006 — affinity is a routing hint: an unencodable prompt routes least-loaded and the REPLICA answers the validation error (one answer path for bad requests)
                affinity = None
        deadline = None
        try:
            d = req.get("deadline_ms")
            if d is not None:
                deadline = now + float(d) / 1e3
        except (TypeError, ValueError):
            pass  # the replica's admission answers the validation error
        with self._intake_lock:
            order = self._next_order
            self._next_order += 1
            self.stats["submitted"] += 1
            self._pending.append(
                _Tracked(
                    order=order, req=req, ctx=ctx, t_submit=now,
                    deadline=deadline, affinity=affinity,
                    stage="prefill" if self.disaggregate else "decode",
                    span_root=span_root,
                )
            )
        if self.ha:
            # The standby's replayable intake record: enough to re-own (or
            # re-dispatch) this order after adopting the fleet — the
            # request itself, its trace identity, and its remaining
            # deadline budget (serve/standby.py).
            self.emit_event(
                "route.intake", order=order, req=req,
                traceparent=ctx.to_traceparent(),
                deadline_ms=(
                    None if deadline is None
                    else round((deadline - now) * 1e3, 3)
                ),
            )
        return order

    def submit_done(self, resp: dict) -> int:
        """Reserve an output position for an already-answered response
        (parse/routing errors) — ordering is preserved across both."""
        with self._intake_lock:
            order = self._next_order
            self._next_order += 1
            self.stats["submitted"] += 1
            self._done[order] = resp
        if self.ha:
            # Pre-answered orders carry their response in the intake
            # record: the standby re-answers them from the log alone.
            self.emit_event("route.intake", order=order, resp=resp)
        if self._tracer is not None:
            span = self._tracer.start_span("route.request", lane="router")
            extra = {}
            if "error" in resp:
                extra = {"error": resp["error"]}
                if "code" in resp:
                    extra["code"] = resp["code"]
            span.end(order=order, **extra)
        return order

    def drain_ready(self) -> list[dict]:
        """Responses completed in arrival order (the stdout contract)."""
        out = []
        with self._intake_lock:
            first = self._emit_next
            while self._emit_next in self._done:
                out.append(self._done.pop(self._emit_next))
                self._emit_next += 1
            last = self._emit_next
        if self.ha and out:
            # Delivery marks, not completion marks: an answer sitting
            # out-of-order in _done died with this process — the standby
            # recovers it from the replicas' re-delivery caches, while
            # DELIVERED orders must never reach the client twice.
            self.emit_event(
                "route.answered", first=first, upto=last - 1, n=len(out)
            )
        return out

    @property
    def busy(self) -> bool:
        with self._intake_lock:
            return self._emit_next < self._next_order

    @property
    def has_ready(self) -> bool:
        with self._intake_lock:
            return self._emit_next in self._done

    @property
    def backlog(self) -> int:
        """Accepted-but-unanswered requests (pending + in flight)."""
        with self._intake_lock:
            return (self._next_order - self._emit_next) - len(self._done)

    # ---- the router thread -------------------------------------------------

    def pump(self, timeout: float = 0.05) -> bool:
        """One control-loop turn: drain the inbox (blocking up to
        ``timeout`` only when there is nothing to dispatch), sweep replica
        liveness, dispatch pending requests. Returns whether any message
        or dispatch happened (the idle signal for callers)."""
        progressed = self._drain_inbox(timeout)
        self._sweep_liveness()
        progressed |= self._dispatch_pending()
        # Supervision tier (serve/supervisor.py): advance respawn/warm
        # state machines, ship shutdowns to drained retirees, then let the
        # scaling policy consume the freshest SLO burn evaluation.
        if self._sup is not None:
            progressed |= self._sup.poll()
            progressed |= self._sup.reap_draining()
        if self._upgrader is not None:
            progressed |= self._upgrader.poll()
        slo_result = None
        if self._slo_engine is not None:
            slo_result = self._slo_engine.maybe_evaluate()
        if self._scaler is not None:
            progressed |= self._scaler.poll(slo_result)
        if self.ha:
            self._ha_heartbeat()
        return progressed

    def run(self, reqs: "list[dict]") -> "list[dict]":
        """Submit ``reqs`` and pump until every one is answered; responses
        in request order (the scheduler-``run`` convenience)."""
        for req in reqs:
            self.submit(req)
        out: list[dict] = []
        while self.busy:
            self.pump()
            out.extend(self.drain_ready())
        out.extend(self.drain_ready())
        if self._tel is not None:
            self._tel.maybe_flush(force=True)
        return out

    def shutdown(self) -> None:
        """Close every replica link (graceful drain where the transport
        supports it) and flush telemetry."""
        for link in self.links:
            link.close()
        if self._tel is not None:
            self._tel.maybe_flush(force=True)

    # -- fleet management (serve/supervisor.py, router thread) ---------------

    def emit_event(self, kind: str, **fields) -> None:
        """Telemetry-gated event emission — the supervision tier's one
        outlet (``route.spawn`` / ``route.retire`` / ``route.scale`` / ...),
        shared so fakes in tests can observe through a real EventLog."""
        if self._tel is not None:
            self._tel.emit(kind, **fields)

    def replace_link(self, index: int, link: ReplicaLink) -> None:
        """Swap a respawned replacement in UNDER ITS PREDECESSOR'S index
        and name — rendezvous hashing therefore re-offers it exactly the
        affinity keys the dead replica owned. The replacement arrives
        ``warming`` (the supervisor admits it after cache warm-up)."""
        self.links[index] = link
        link.last_hb = None
        if hasattr(link, "start_reader"):
            link.start_reader(self.inbox)
        self.on_fleet_change()

    def append_link(self, link: ReplicaLink) -> None:
        """Grow the fleet by one (FleetScaler scale-up): a fresh breaker,
        a fresh rendezvous name — existing keys keep their owners."""
        self.links.append(link)
        self.breakers.append(
            CircuitBreaker(
                f"replica_{link.name}", threshold=self._breaker_threshold,
                cooldown_s=self._breaker_cooldown_s,
            )
        )
        if hasattr(link, "start_reader"):
            link.start_reader(self.inbox)
        self.on_fleet_change()

    def start_upgrade(self, ckpt: str) -> dict:
        """Begin a rolling weight swap to ``ckpt`` (the ``--upgrade`` flag
        and the control-line command both land here). Returns the
        coordinator's status dict; a router without an UpgradeCoordinator
        answers a structured refusal instead of raising."""
        if self._upgrader is None:
            return {
                "ok": False, "code": "upgrade",
                "error": "this router has no UpgradeCoordinator attached",
            }
        return self._upgrader.start(ckpt)

    def reset_breaker(self, index: int) -> None:
        """A freshly admitted REPLACEMENT process deserves a fresh breaker:
        the old one's open state belongs to the dead process (an OPEN
        breaker deliberately ignores stray successes, so re-arming must be
        explicit, not a side effect of the first answer)."""
        link = self.links[index]
        self.breakers[index] = CircuitBreaker(
            f"replica_{link.name}", threshold=self._breaker_threshold,
            cooldown_s=self._breaker_cooldown_s,
        )

    @property
    def healthy_links(self) -> "list[ReplicaLink]":
        """Links currently SERVING: live, admitted, not draining — the one
        definition of fleet size the gauge, the autoscaler, and the warm-
        source picker all share."""
        return [
            l for l in self.links
            if not l.dead and not l.warming and not l.draining
            and not l.upgrading
        ]

    def on_fleet_change(self) -> None:
        """Refresh the fleet-size gauges after any membership change."""
        if self._tel is not None:
            self._m_replicas.set(sum(1 for l in self.links if not l.dead))
            self._m_fleet.set(len(self.healthy_links))

    def seed_takeover(
        self,
        *,
        next_order: int,
        emit_next: int,
        done: "dict[int, dict]",
        inflight: "list[tuple[int, _Tracked]]",
        pending: "list[_Tracked]",
    ) -> None:
        """Install adopted state from a warm standby's takeover
        (``serve/standby.py``): the order clock resumes past every order
        the primary minted, delivery resumes at the client's floor
        (``emit_next``), recovered answers land in the funnel, replica-
        claimed orders are re-owned in the in-flight table exactly once,
        and unknowns queue for dispatch. Call BEFORE the pump thread
        starts — this is takeover bootstrap, not a concurrent surface."""
        with self._intake_lock:
            self._next_order = max(self._next_order, next_order)
            self._emit_next = emit_next
            self._done.update(done)
            self._pending.extend(pending)
        for index, rr in inflight:
            rr.replica = index
            self._inflight[rr.order] = rr
            self.links[index].inflight += 1
        if self.ha:
            # Re-journal the adopted state: THIS router's journal starts
            # empty, and route.intake is otherwise only written by
            # submit()/submit_done() — without these records a SECOND
            # (chained) standby tailing us would neither re-own nor
            # re-dispatch the adopted orders and its funnel would wedge
            # at the delivery floor forever.
            if emit_next > 0:
                # Floor mark (n=0): nothing newly delivered, but orders
                # below emit_next reached the client via a predecessor.
                self.emit_event(
                    "route.answered", first=emit_next, upto=emit_next - 1,
                    n=0,
                )
            now = time.perf_counter()
            for order in sorted(done):
                self.emit_event("route.intake", order=order,
                                resp=done[order])
            for rr in sorted(
                [rr for _, rr in inflight] + list(pending),
                key=lambda r: r.order,
            ):
                self.emit_event(
                    "route.intake", order=rr.order, req=rr.req,
                    traceparent=rr.ctx.to_traceparent(),
                    deadline_ms=(
                        None if rr.deadline is None
                        else round((rr.deadline - now) * 1e3, 3)
                    ),
                )

    def _ha_heartbeat(self) -> None:
        """The primary's liveness beacon for a warm standby
        (``serve/standby.py``): a periodic ``route.hb`` event on the
        answer-funnel event log carrying the authority epoch and the
        replica control ports. The order-keyed inflight table itself is
        NOT in the beacon — the standby reconstructs it from the
        ``route.intake``/``route.answered`` records, so the beacon stays
        O(fleet) on the pump hot path instead of O(inflight) twice a
        second."""
        now = time.monotonic()
        if now - self._last_ha_hb < self.ha_heartbeat_s:
            return
        self._last_ha_hb = now
        self.emit_event(
            "route.hb",
            epoch=self.epoch,
            ports={
                l.name: l.control_port
                for l in self.links
                if l.control_port is not None and not l.retired
            },
        )

    # -- inbox --------------------------------------------------------------

    def _drain_inbox(self, timeout: float) -> bool:
        with self._intake_lock:
            idle = not self._pending
        try:
            if idle and timeout > 0:
                item = self.inbox.get(timeout=timeout)
            else:
                item = self.inbox.get_nowait()
        except queue.Empty:
            return False
        handled = 0
        while True:
            self._handle_msg(*item)
            handled += 1
            try:
                item = self.inbox.get_nowait()
            except queue.Empty:
                break
        return handled > 0

    def _handle_msg(self, index: int, msg: dict) -> None:
        link = self.links[index]
        kind = msg.get("type")
        if kind == "answer":
            self._on_answer(link, msg)
        elif kind == "hb":
            try:
                # route.hb fault point: deterministically SWALLOW replica
                # heartbeats so --fault_spec episodes drill heartbeat-loss
                # failover storms without real stalls (docs/ROBUSTNESS.md).
                maybe_fail("route.hb")
            except InjectedFault:
                self.stats["dropped_heartbeats"] += 1
                return
            link.last_hb = time.monotonic()
            link.hb_backlog = int(msg.get("backlog", 0))
            link.hb_free = int(msg.get("free", 0))
            link.hb_active = int(msg.get("active", 0))
            if msg.get("wv") is not None:
                link.wv = msg["wv"]
            if msg.get("mesh") is not None:
                link.mesh = msg["mesh"]
        elif kind == "prefilled":
            self._on_prefilled(link, msg)
        elif kind == "exit":
            # A supervisor-respawned REPLACEMENT at this index must never
            # be failed over by its predecessor's EOF sentinel: the old
            # reader thread can outlive the link swap, so the sentinel's
            # pid must match the CURRENT process behind the link.
            pid = msg.get("pid")
            cur = getattr(link, "pid", None)
            cur = cur() if callable(cur) else None
            if pid is not None and cur is not None and pid != cur:
                return
            if not link.dead:
                self._fail_replica(index, "pipe closed")
        elif kind == "ready":
            link.last_hb = time.monotonic()
            port = msg.get("control_port")
            if isinstance(port, int):
                link.control_port = port
            if msg.get("weight_version") is not None:
                # A replica bootstrapped from --init_ckpt announces the
                # verified version it serves — a respawn mid-rollout comes
                # up already converged to the fleet's target.
                link.wv = msg["weight_version"]
            if msg.get("mesh") is not None:
                # Captured BEFORE on_ready: the supervisor's wrong-shape
                # refusal judges the replica's announced mesh.
                link.mesh = msg["mesh"]
            link.device = msg.get("device")
            if self._sup is not None and link.warming:
                self._sup.on_ready(link)
        elif kind in ("upgrade_staged", "upgraded"):
            if kind == "upgraded" and msg.get("ok", True):
                link.wv = msg.get("version")
            if self._upgrader is not None:
                self._upgrader.on_msg(link, msg)
        elif kind == "prefix_state":
            if self._sup is not None:
                self._sup.on_prefix_state(link, msg)
        elif kind == "state_injected":
            if self._sup is not None:
                self._sup.on_state_injected(link, msg)
        elif kind == "flight":
            # A `dump` reply: hold the freshest wire-shipped flight record
            # for the Supervisor's postmortem capture.
            link.flight_record = msg.get("record")

    def _on_answer(self, link: ReplicaLink, msg: dict) -> None:
        order = msg.get("rid")
        rr = self._inflight.pop(order, None)
        if rr is None:
            # The order-keyed answer funnel's at-most-once arm: already
            # answered (a failover raced a completing replica), already
            # drained, or never ours — count and drop.
            self.stats["duplicate_answers"] += 1
            if self._tel is not None:
                self._m_dup.inc()
            return
        # Unload the replica the order is CURRENTLY assigned to, not the
        # answering one: a failed-over victim's late answer must release
        # the survivor's slot (the survivor's own answer for this order
        # takes the duplicate early-return above and never decrements).
        assigned = self.links[rr.replica] if rr.replica is not None else link
        assigned.inflight = max(0, assigned.inflight - 1)
        link.answered += 1
        resp = msg.get("resp")
        if not isinstance(resp, dict):
            resp = error_answer(
                "internal", f"replica {link.name} returned a malformed answer"
            )
        self._answer(rr, resp, replica=link.name, slo=msg.get("slo"))
        self.breakers[link.index].record_success()

    def _on_prefilled(self, link: ReplicaLink, msg: dict) -> None:
        """Disaggregation stage 1 complete: the prefill replica handed the
        prompt's KV blocks back; forward the request (plus blocks) to a
        decode replica."""
        order = msg.get("rid")
        rr = self._inflight.pop(order, None)
        if rr is None:
            self.stats["duplicate_answers"] += 1
            return
        assigned = self.links[rr.replica] if rr.replica is not None else link
        assigned.inflight = max(0, assigned.inflight - 1)
        self.breakers[link.index].record_success()
        rr.stage = "decode"
        rr.replica = None
        rr.blocks = msg.get("blocks")
        rr.blocks_tokens = int(msg.get("tokens", 0))
        self.stats["prefill_handoffs"] += 1
        with self._intake_lock:
            self._pending.appendleft(rr)

    # -- liveness + failover -------------------------------------------------

    def _sweep_liveness(self) -> None:
        now = time.monotonic()
        for link in self.links:
            if link.dead:
                self._maybe_revive(link)
                continue
            if not link.alive():
                self._fail_replica(link.index, "process exited")
            elif (
                self.heartbeat_timeout_s > 0
                and link.last_hb is not None
                and now - link.last_hb > self.heartbeat_timeout_s
            ):
                self._fail_replica(link.index, "heartbeat timeout")

    def _maybe_revive(self, link: ReplicaLink) -> None:
        """The breaker's half-open arm: a failed-over link whose worker
        PROCESS still runs (heartbeat-timeout victims — exited workers
        fail ``alive()`` forever) is re-admitted once a heartbeat NEWER
        than the death mark arrives and the breaker cooldown has elapsed;
        its first answered request then closes the breaker, and a fresh
        failure (half-open -> open) restarts the cooldown."""
        if link.retired or not link.alive():
            return
        if (
            link.last_hb is None
            or link.died_at is None
            or link.last_hb <= link.died_at
        ):
            return
        if not self.breakers[link.index].allow():
            return
        link.dead = False
        link.died_at = None
        self.stats["revivals"] += 1
        self.on_fleet_change()
        self.emit_event("route.revive", replica=link.name)
        # A revival also wins the race against a scheduled respawn: the
        # supervisor's slot returns to "up" on its next poll (link.dead is
        # False again before the backoff elapses).

    def _fail_replica(self, index: int, reason: str) -> None:
        """Zero-loss failover: every in-flight request assigned to the
        dead replica is re-enqueued at the FRONT of the pending queue in
        its original order, with its original trace id and deadline
        intact. The answer funnel keeps at-most-once: if the victim
        replica's answer for one of these orders still arrives (it was
        written before the death), whichever of answer/redispatch lands
        first wins and the other is dropped/cancelled by the funnel."""
        link = self.links[index]
        if link.retired:
            return  # a drained retiree's EOF is not a failure
        link.dead = True
        link.died_at = time.monotonic()
        self.breakers[index].record_failure()
        victims = sorted(
            (rr for rr in self._inflight.values() if rr.replica == index),
            key=lambda rr: rr.order,
        )
        for rr in victims:
            del self._inflight[rr.order]
            rr.replica = None
            rr.refailed = True  # the next dispatch is a bounded redispatch
            if self.disaggregate and rr.stage == "prefill":
                rr.blocks = None  # the handoff payload died with the worker
        link.inflight = 0
        with self._intake_lock:
            self._pending.extendleft(reversed(victims))
        self.stats["failovers"] += 1
        if self._tel is not None:
            self._m_failover.inc()
        self.on_fleet_change()
        self.emit_event(
            "route.failover",
            replica=link.name,
            reason=reason,
            orders=[rr.order for rr in victims],
            traces=[rr.ctx.trace_id for rr in victims],
        )
        if self._sup is not None:
            self._sup.on_death(link)
        if self._upgrader is not None:
            self._upgrader.on_death(link)

    # -- dispatch ------------------------------------------------------------

    def _usable(self, stage: str) -> "list[ReplicaLink]":
        out = []
        for link in self.links:
            if link.dead or not link.serves(stage):
                continue
            if link.warming or link.draining or link.upgrading:
                # Supervision states: a warming replacement is still
                # bootstrapping/cache-warming; a draining retiree finishes
                # its in-flight work but takes nothing new; an upgrading
                # replica is quiescing for (or mid-) a weight swap.
                continue
            if not self.breakers[link.index].allow():
                continue
            out.append(link)
        return out

    def _load(self, link: ReplicaLink) -> int:
        return link.inflight + link.hb_backlog

    def _pick(self, rr: _Tracked) -> "tuple[ReplicaLink, str] | None":
        stage = rr.stage if self.disaggregate else "decode"
        usable = self._usable(stage)
        if not usable and self.disaggregate and stage == "prefill":
            # Degradation: no prefill worker left — decode replicas can
            # serve the whole request (full prefill), losing only the
            # handoff win, never the request.
            rr.stage = "decode"
            rr.blocks = None
            usable = self._usable("decode")
        elif not usable and self.disaggregate and stage == "decode":
            # Mirror degradation: no decode-capable replica left — a live
            # prefill-only worker runs the same scheduler and serves the
            # whole request (rr.stage stays "decode", so the forwarded
            # message is a full "req"); role segregation yields before
            # zero-loss does.
            usable = self._usable("prefill")
        if not usable:
            return None
        if self._upgrader is not None:
            # Canary pinning: during a rollout's canary window, a
            # deterministic slice of accepted orders routes to the first
            # upgraded replica so the per-version SLO split has traffic
            # to judge (serve/upgrade.py).
            forced = self._upgrader.route(rr, usable)
            if forced is not None:
                return forced, "canary"
        least = min(usable, key=lambda l: (self._load(l), l.index))
        if rr.affinity is None:
            return least, "least_loaded"
        affine = max(usable, key=lambda l: _rendezvous(rr.affinity, l.name))
        if self._load(affine) - self._load(least) > self.affinity_slack:
            return least, "least_loaded"
        return affine, "affinity"

    def _dispatch_pending(self) -> bool:
        progressed = False
        while True:
            with self._intake_lock:
                if not self._pending:
                    return progressed
                rr = self._pending.popleft()
            now = time.perf_counter()
            if rr.deadline is not None and now >= rr.deadline:
                self.stats["expired"] += 1
                self._answer(
                    rr,
                    error_answer(
                        "deadline",
                        "deadline_ms elapsed in the router queue after "
                        f"{round((now - rr.t_submit) * 1e3)}ms",
                    ),
                )
                progressed = True
                continue
            if rr.refailed and rr.redispatches >= self.max_redispatch:
                self.stats["exhausted"] += 1
                self._answer(
                    rr,
                    error_answer(
                        "transient",
                        f"request redispatched {self.max_redispatch} time(s) "
                        "after replica failures and still unanswered",
                    ),
                )
                progressed = True
                continue
            picked = self._pick(rr)
            if picked is None:
                if any(not l.dead for l in self.links):
                    # Breakers half-open/cooling: park the request at the
                    # front and let the next pump retry.
                    with self._intake_lock:
                        self._pending.appendleft(rr)
                    return progressed
                self.stats["no_replica"] += 1
                self._answer(
                    rr,
                    error_answer(
                        "transient",
                        "no live replica to serve the request (all "
                        f"{len(self.links)} failed)",
                    ),
                )
                progressed = True
                continue
            link, policy = picked
            fwd = dict(rr.req)
            fwd["traceparent"] = rr.ctx.to_traceparent()
            if rr.deadline is not None:
                fwd["deadline_ms"] = max(
                    0.0, round((rr.deadline - now) * 1e3, 3)
                )
            msg = {"type": "req", "rid": rr.order, "req": fwd}
            if self.disaggregate and rr.stage == "prefill":
                msg["type"] = "prefill"
            elif rr.blocks is not None:
                msg["blocks"] = rr.blocks
                msg["tokens"] = rr.blocks_tokens
            try:
                link.send(msg)
            except (OSError, ValueError):  # tpa: disable=TPA007 — bounded: _fail_replica permanently removes the dead link (at most N send failures total) and rr.attempts is capped by max_redispatch above
                with self._intake_lock:
                    self._pending.appendleft(rr)
                self._fail_replica(link.index, "send failed")
                progressed = True
                continue
            # Only failover-driven re-dispatches count against the
            # max_redispatch budget and the redispatch metrics — the
            # disaggregated prefill->decode stage progression is normal
            # request flow, not a failure.
            redispatch = rr.refailed
            rr.refailed = False
            rr.attempts += 1
            if redispatch:
                rr.redispatches += 1
            rr.replica = link.index
            if rr.t_dispatch is None:
                rr.t_dispatch = now
                self.queue_latencies.append(now - rr.t_submit)
            self._inflight[rr.order] = rr
            link.inflight += 1
            link.dispatched += 1
            self.stats["dispatched"] += 1
            if redispatch:
                self.stats["redispatched"] += 1
            progressed = True
            if self._tel is not None:
                self._m_dispatch.inc()
                if redispatch:
                    self._m_redispatch.inc()
                self._m_queue_s.observe(now - rr.t_submit)
                self._tel.emit(
                    "route.dispatch",
                    order=rr.order, replica=link.name, policy=policy,
                    stage=rr.stage if self.disaggregate else None,
                    redispatch=rr.redispatches,
                    weight_version=link.wv,
                    trace=rr.ctx.trace_id,
                )

    # -- the answer funnel ---------------------------------------------------

    def _answer(
        self, rr: _Tracked, resp: dict, replica: str = "", slo=None
    ) -> None:
        with self._intake_lock:
            self._done[rr.order] = resp
        self.stats["answered"] += 1
        if self._upgrader is not None:
            # The per-weight_version SLO split the canary verdict reads —
            # fed from the SAME funnel as the fleet engine below.
            self._upgrader.observe(rr, resp, slo)
        if self._slo_engine is not None:
            # The router's own SLO engine over the answer funnel: the
            # replica's per-answer side channel carries ttft/prefix numbers
            # (serve/replica.py "slo"); router-local answers (queue
            # deadline, redispatch exhaustion, no-replica) contribute their
            # availability/deadline weight with no latency sample. This is
            # the FleetScaler's autoscaling signal.
            sample = dict(slo) if isinstance(slo, dict) else {}
            sample["order"] = rr.order
            sample.setdefault(
                "total_s", round(time.perf_counter() - rr.t_submit, 6)
            )
            if "error" in resp:
                sample["error"] = resp["error"]
                if "code" in resp:
                    sample["code"] = resp["code"]
            self._slo_engine.record(sample)
        if rr.span_root is not None:
            extra = {}
            if "error" in resp:
                extra["error"] = resp["error"]
                if "code" in resp:
                    extra["code"] = resp["code"]
            rr.span_root.end(
                order=rr.order, replica=replica,
                redispatches=rr.redispatches, **extra,
            )
            rr.span_root = None
        if self._tel is not None:
            self._m_answers.inc()
