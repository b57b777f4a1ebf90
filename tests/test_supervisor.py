"""Self-healing fleet (``serve/supervisor.py`` + ``serve/standby.py``):
supervised replica respawn with survivor cache warm-up, crash-loop budget
exhaustion, SLO-burn-driven autoscaling, the router-tier fault points, and
warm-standby router takeover with exactly-once answers across the cutover."""

import io
import json
import os
import signal
import socket
import time

import pytest

from transformer_tpu.obs import EventLog, Telemetry
from transformer_tpu.serve.router import ReplicaLink, ReplicaProcess, Router
from transformer_tpu.serve.supervisor import FleetScaler, Supervisor

# The deterministic test-model bootstrap (tests/test_router.py): every
# process building this spec gets bit-identical params and vocab, so
# byte-parity assertions hold across process boundaries AND respawns.
SPEC = {
    "config": {
        "num_layers": 1, "d_model": 16, "num_heads": 2, "dff": 32,
        "max_position": 32, "decoder_only": True, "tie_output": True,
        "dtype": "float32", "dropout_rate": 0.0,
    },
    "seed": 0,
    "corpus": ["ab cd ef gh ij kl mn"] * 3,
    "target_vocab_size": 300,
}
PROMPT_A = "ab cd ef gh ij"


@pytest.fixture(scope="module")
def lm():
    from transformer_tpu.serve.replica import build_model_from_spec

    return build_model_from_spec(SPEC)


@pytest.fixture(scope="module")
def spec_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("supervisor") / "spec.json"
    path.write_text(json.dumps(SPEC))
    return str(path)


def _reference(lm, reqs):
    from transformer_tpu.serve import ContinuousScheduler

    params, cfg, tok = lm
    return ContinuousScheduler(params, cfg, tok, num_slots=2).run(
        [dict(r) for r in reqs]
    )


def _events(buf: io.StringIO) -> list:
    return [json.loads(line) for line in buf.getvalue().splitlines()]


# --------------------------------------------------------------------------
# the acceptance drill: SIGKILL a replica, the fleet heals back to N


def test_sigkill_heal_soak(lm, spec_file, tmp_path):
    """SIGKILL one of two replicas under a Supervisor: the fleet heals
    back to N — the replacement re-bootstraps from the same --model_spec
    under its old rendezvous name, warms its PrefixCache from the
    survivor, and serves affine traffic again — with zero accepted
    requests lost and answers byte-identical to a single scheduler."""
    params, cfg, tok = lm
    worker = [
        "--model_spec", spec_file, "--serve_slots", "2",
        "--heartbeat_ms", "50", "--prefix_cache_mb", "8",
        "--prefix_block", "4",
    ]
    links = [ReplicaProcess.spawn(i, list(worker)) for i in range(2)]

    def spawn(index, name, role):
        return ReplicaProcess.spawn(index, list(worker), role=role, name=name)

    sup = Supervisor(spawn, backoff_ms=50.0)
    buf = io.StringIO()
    telemetry = Telemetry(events=EventLog(buf))
    router = Router(
        links, encode=tok.encode, bos_id=tok.bos_id, affinity_block=4,
        heartbeat_timeout_s=10.0, telemetry=telemetry, supervisor=sup,
    )
    for link in links:
        link.start_reader(router.inbox)
    reqs = [{"prompt": PROMPT_A, "max_new": 6}] * 6
    want = _reference(lm, reqs)
    deadline = time.time() + 110
    try:
        out = router.run([dict(r) for r in reqs])
        assert [o.get("continuation") for o in out] == [
            w["continuation"] for w in want
        ]
        # PROMPT_A's affine replica owns the warm cache — kill it.
        victim = max(router.links, key=lambda l: l.answered)
        os.kill(victim.pid(), signal.SIGKILL)
        while time.time() < deadline:
            router.pump()
            healthy = [
                l for l in router.links
                if not l.dead and not l.warming and not l.draining
            ]
            if len(healthy) == 2 and sup.stats["respawns"] == 1:
                break
        assert sup.stats["respawns"] == 1, sup.stats
        assert sup.stats["gave_up"] == 0
        # The replacement's PrefixCache was warmed from the survivor over
        # the export/inject wire format before it took traffic.
        assert sup.stats["warmed_tokens"] > 0, sup.stats
        assert sup.heal_times and sup.heal_times[0] > 0
        # Same traffic again: byte parity holds through the respawn, and
        # the replacement (old name, old rendezvous keys) serves it.
        out2 = router.run([dict(r) for r in reqs])
        assert [o.get("continuation") for o in out2] == [
            w["continuation"] for w in want
        ]
        replacement = router.links[victim.index]
        assert replacement is not victim
        assert replacement.name == victim.name
        assert replacement.answered > 0, "replacement took no traffic"
    finally:
        router.shutdown()
        telemetry.maybe_flush(force=True)
    events = _events(buf)
    spawns = [e for e in events if e.get("kind") == "route.spawn"]
    assert len(spawns) == 1
    assert spawns[0]["replica"] == victim.name
    assert spawns[0]["heal_s"] > 0
    assert spawns[0]["warmed_tokens"] == sup.stats["warmed_tokens"]
    # The fleet gauge recovered to N.
    assert telemetry.registry.gauge(
        "route_fleet_size", ""
    ).value == 2
    # The merged report's fleet section renders the heal.
    from transformer_tpu.obs.__main__ import render_text, summarize_events

    fleet = summarize_events(events)["fleet"]
    assert fleet["respawns"] == 1
    assert fleet["time_to_heal_s"]["count"] == 1
    assert fleet["warmed_tokens"] > 0
    assert "fleet:" in render_text(summarize_events(events))


# --------------------------------------------------------------------------
# the acceptance drill: kill the primary router, the standby adopts


def test_router_ha_takeover_exactly_once(lm, spec_file, tmp_path):
    """Kill the primary router mid-stream: the warm standby tails its
    journal, detects heartbeat silence, adopts the inflight table, and
    every in-flight request is answered exactly once — recovered answers
    replayed from replica re-delivery caches, the rest re-owned or
    re-dispatched. A second takeover attempt at the same epoch is
    rejected (the split-brain guard)."""
    from transformer_tpu.serve.standby import Standby

    params, cfg, tok = lm
    worker = [
        "--model_spec", spec_file, "--serve_slots", "2",
        "--heartbeat_ms", "50", "--ha",
    ]
    links = [ReplicaProcess.spawn(i, list(worker)) for i in range(2)]
    primary_log = str(tmp_path / "primary.jsonl")
    telemetry = Telemetry(events=EventLog(primary_log))
    router = Router(
        links, encode=tok.encode, bos_id=tok.bos_id, affinity_block=4,
        heartbeat_timeout_s=10.0, telemetry=telemetry, ha=True,
        ha_heartbeat_s=0.1,
    )
    for link in links:
        link.start_reader(router.inbox)
    reqs = [{"prompt": PROMPT_A, "max_new": 20} for _ in range(8)]
    want = _reference(lm, reqs)
    new_router = None
    try:
        # Both control ports must be in the journal's beacon before the
        # cutover: with a warm compile cache the first replica can answer
        # two requests before the second has said "ready", and the standby
        # then adopts a fleet of one.
        deadline = time.time() + 110
        while any(l.control_port is None for l in links) and time.time() < deadline:
            router.pump()
        for r in reqs:
            router.submit(dict(r))
        delivered = []
        while len(delivered) < 2 and time.time() < deadline:
            router.pump()
            delivered.extend(router.drain_ready())
        assert len(router._inflight) + len(router._pending) > 0, (
            "nothing in flight at the cutover — the drill is vacuous"
        )
        telemetry.maybe_flush(force=True)
        # The primary "dies" here: it stops pumping forever. Its pipes
        # stay open — the replicas' epoch guard handles any stragglers.
        standby = Standby(
            primary_log, takeover_after_s=0.5,
            encode=tok.encode, bos_id=tok.bos_id,
            telemetry=Telemetry(
                events=EventLog(str(tmp_path / "standby.jsonl"))
            ),
        )
        new_router = standby.run_until_takeover(poll_s=0.05, timeout=60)
        assert new_router.epoch == 2
        assert len(new_router.links) == 2
        assert (
            standby.stats["recovered_answers"]
            + standby.stats["reowned_inflight"]
            + standby.stats["redispatched"]
        ) > 0, standby.stats
        while new_router.busy and time.time() < deadline:
            new_router.pump()
            delivered.extend(new_router.drain_ready())
        delivered.extend(new_router.drain_ready())
        # Exactly once across the cutover: all 8, no duplicates, byte-
        # identical to the single-scheduler reference.
        assert len(delivered) == len(reqs)
        assert [d.get("continuation") for d in delivered] == [
            w["continuation"] for w in want
        ]
        # Split-brain guard: a takeover with a non-higher epoch is
        # rejected by the replica's control socket.
        port = next(
            l.control_port for l in new_router.links
            if l.control_port is not None
        )
        with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
            wf = s.makefile("w", encoding="utf-8", buffering=1)
            rf = s.makefile("r", encoding="utf-8")
            wf.write(json.dumps(
                {"type": "takeover", "epoch": 2, "inflight": []}
            ) + "\n")
            wf.flush()
            reply = json.loads(rf.readline())
        assert reply["type"] == "rejected" and reply["epoch"] == 2
    finally:
        if new_router is not None:
            new_router.shutdown()
        else:
            router.shutdown()
    # The merged logs reconstruct the cutover: both routers as sources,
    # one route.takeover event, and the fleet summary section reports it.
    from transformer_tpu.obs.__main__ import summarize_events
    from transformer_tpu.obs.merge import merge_events

    events, info = merge_events(
        [primary_log, str(tmp_path / "standby.jsonl")]
    )
    assert set(info["sources"]) == {"primary.jsonl", "standby.jsonl"}
    takeovers = [e for e in events if e.get("kind") == "route.takeover"]
    assert len(takeovers) == 1
    assert takeovers[0]["epoch"] == 2
    assert takeovers[0]["source"] == "standby.jsonl"
    fleet = summarize_events(events)["fleet"]
    assert fleet["takeovers"] == 1
    assert fleet["takeover"]["epoch"] == 2


# --------------------------------------------------------------------------
# crash-loop handling (fake links: fast and deterministic)


class _FakeLink(ReplicaLink):
    def __init__(self, index, name, answer=True):
        super().__init__(index, name)
        self.sent = []
        self.answer_back = answer
        self.ok = True
        self.router = None

    def alive(self):
        return self.ok

    def kill(self):
        self.ok = False

    def send(self, msg):
        if not self.ok:
            raise BrokenPipeError("dead")
        self.sent.append(msg)
        if msg.get("type") == "req" and self.answer_back:
            self.router.inbox.put((self.index, {
                "type": "answer", "rid": msg["rid"],
                "resp": {"continuation": self.name},
            }))
        elif msg.get("type") == "export_state":
            # Survivor warm-up export: nothing cached — the supervisor
            # admits the replacement cold.
            self.router.inbox.put(
                (self.index, {"type": "prefix_state", "entries": []})
            )


def _fake_fleet(n=2, *, supervisor=None, scaler=None, slos=None,
                telemetry=None, **kw):
    links = [_FakeLink(i, f"f{i}") for i in range(n)]
    router = Router(
        links, encode=None, supervisor=supervisor, scaler=scaler,
        slos=slos, telemetry=telemetry, **kw,
    )
    for link in links:
        link.router = router
    return router, links


def test_crash_loop_exhausts_budget_and_serves_n_minus_1():
    """A replica whose bootstrap always fails must exhaust its restart
    budget, trip the breaker, and leave the fleet serving at N-1 with
    zero lost requests — not spin."""
    clk = [0.0]
    spawn_calls = []

    def spawn(index, name, role):
        spawn_calls.append(index)
        raise RuntimeError("bootstrap faults every time")

    sup = Supervisor(
        spawn, max_restarts=3, restart_window_s=1000.0, backoff_ms=0.0,
        clock=lambda: clk[0],
    )
    buf = io.StringIO()
    telemetry = Telemetry(events=EventLog(buf))
    router, links = _fake_fleet(2, supervisor=sup, telemetry=telemetry)
    links[0].ok = False
    router.inbox.put((0, {"type": "exit"}))
    router.pump(timeout=0)
    assert links[0].dead
    for _ in range(20):  # far more polls than the budget allows attempts
        clk[0] += 1.0
        router.pump(timeout=0)
    assert len(spawn_calls) == 3, f"budget not honored: {spawn_calls}"
    assert sup.stats["gave_up"] == 1
    assert sup._slots[0].phase == "gave_up"
    assert router.breakers[0].state == "open"
    # The fleet serves at N-1, losing nothing.
    out = router.run([{"prompt": "p"} for _ in range(4)])
    assert [o["continuation"] for o in out] == ["f1"] * 4
    events = _events(buf)
    gave_up = [e for e in events
               if e.get("kind") == "route.spawn" and e.get("gave_up")]
    assert len(gave_up) == 1 and gave_up[0]["attempts"] == 3


def test_respawn_storm_via_fault_plane():
    """--fault_spec route.spawn episodes drill crash loops
    deterministically: the first two attempts fault, the third succeeds,
    and the replacement is admitted (warm-up skipped: no survivor
    entries) — the same episode replays identically from the spec."""
    from transformer_tpu.serve.resilience import FaultPlane, install

    clk = [0.0]
    spawned = []

    def spawn(index, name, role):
        link = _FakeLink(index, name)
        link.router = router
        spawned.append(link)
        router.inbox.put((index, {"type": "ready", "replica": name}))
        return link

    sup = Supervisor(
        spawn, max_restarts=5, backoff_ms=0.0, clock=lambda: clk[0],
    )
    router, links = _fake_fleet(2, supervisor=sup)
    install(FaultPlane.parse("route.spawn:p=1,times=2,seed=7"))
    try:
        links[0].ok = False
        router.inbox.put((0, {"type": "exit"}))
        router.pump(timeout=0)
        for _ in range(10):
            clk[0] += 1.0
            router.pump(timeout=0)
            if sup.stats["respawns"] == 1:
                break
        assert sup.stats["spawn_failures"] == 2
        assert sup.stats["spawn_attempts"] == 3
        assert sup.stats["respawns"] == 1
        assert sup._slots[0].phase == "up"
        assert router.links[0] is spawned[0]
        assert not router.links[0].dead
    finally:
        install(None)


def test_respawn_refuses_wrong_mesh_shape():
    """A respawned replica that bootstraps at the WRONG mesh shape (stale
    binary, hand-edited argv) is refused loudly — route.mesh_mismatch
    event, killed before warm-up or traffic, one budgeted failure — and
    the next (correct-shape) respawn is admitted. Rides the SIGKILL-heal
    machinery with fake links so the drill is deterministic."""
    clk = [0.0]
    spawned = []

    def spawn(index, name, role):
        link = _FakeLink(index, name)
        link.router = router
        spawned.append(link)
        # First replacement announces data=4 (wrong), the second data=2.
        mesh = "data=4" if len(spawned) == 1 else "data=2"
        router.inbox.put(
            (index, {"type": "ready", "replica": name, "mesh": mesh})
        )
        return link

    sup = Supervisor(
        spawn, max_restarts=5, backoff_ms=0.0, clock=lambda: clk[0],
        expected_mesh="data=2",
    )
    buf = io.StringIO()
    telemetry = Telemetry(events=EventLog(buf))
    router, links = _fake_fleet(2, supervisor=sup, telemetry=telemetry)
    links[0].ok = False
    router.inbox.put((0, {"type": "exit"}))
    router.pump(timeout=0)
    for _ in range(10):
        clk[0] += 1.0
        router.pump(timeout=0)
        if sup.stats["respawns"] == 1:
            break
    # The wrong-shape link was killed without admission; the failure was
    # budgeted (not free) and the correct-shape retry healed the fleet.
    assert not spawned[0].ok and spawned[0].sent == []
    assert spawned[1].ok and router.links[0] is spawned[1]
    assert router.links[0].mesh == "data=2"
    assert sup.stats["spawn_failures"] == 1
    assert sup.stats["respawns"] == 1
    assert sup._slots[0].phase == "up"
    mm = [e for e in _events(buf) if e.get("kind") == "route.mesh_mismatch"]
    assert len(mm) == 1
    assert mm[0]["expected"] == "data=2" and mm[0]["got"] == "data=4"


def test_route_hb_fault_swallows_heartbeats():
    """The route.hb fault point drops replica heartbeats at the router —
    heartbeat-loss storms without real stalls."""
    from transformer_tpu.serve.resilience import FaultPlane, install

    router, links = _fake_fleet(1)
    install(FaultPlane.parse("route.hb:p=1,times=2,seed=3"))
    try:
        for _ in range(3):
            router.inbox.put(
                (0, {"type": "hb", "backlog": 0, "free": 2, "active": 0})
            )
        router.pump(timeout=0)
        assert router.stats["dropped_heartbeats"] == 2
        assert links[0].last_hb is not None  # the third one landed
    finally:
        install(None)


# --------------------------------------------------------------------------
# SLO-driven autoscaling (fake links + scripted burn rates)


class _ScriptedSLO:
    """Duck-typed SLOEngine: maybe_evaluate returns whatever burn the
    test scripts next (None = no evaluation this pump)."""

    def __init__(self):
        self.next_burn = None

    def maybe_evaluate(self):
        if self.next_burn is None:
            return None
        return {
            "ttft_p95": {
                "burn_rate": self.next_burn,
                "breached": self.next_burn > 1.0,
                "windows": {"60s": {"burn_rate": self.next_burn}},
            }
        }

    def record(self, span):
        pass


def test_autoscale_burn_spawns_idle_drains():
    """Sustained ttft_p95 burn > 1 spawns a replica (route.scale up with
    the evidence window); sustained idleness drains the youngest back
    down (drain -> retire), bounded by min_replicas."""
    clk = [0.0]

    def spawn(index, name, role):
        link = _FakeLink(index, name)
        link.router = router
        spawned.append(link)
        router.inbox.put((index, {"type": "ready", "replica": name}))
        return link

    spawned = []
    sup = Supervisor(spawn, backoff_ms=0.0, clock=lambda: clk[0])
    scaler = FleetScaler(
        sustain_s=2.0, idle_s=3.0, max_replicas=2, min_replicas=1,
        cooldown_s=0.0, clock=lambda: clk[0],
    )
    slo = _ScriptedSLO()
    buf = io.StringIO()
    telemetry = Telemetry(events=EventLog(buf))
    router, links = _fake_fleet(
        1, supervisor=sup, scaler=scaler, slos=slo, telemetry=telemetry,
    )
    # ---- burn > 1, sustained: one scale-up (and only one — cap = 2) ----
    slo.next_burn = 2.5
    router.pump(timeout=0)          # starts the sustain clock
    clk[0] += 2.5
    router.pump(timeout=0)          # sustained past sustain_s: spawn
    assert len(spawned) == 1
    assert scaler.stats["scale_up"] == 1
    router.pump(timeout=0)          # "ready" admits the newcomer (cold)
    assert sup._slots[1].phase == "up"
    clk[0] += 5.0
    router.pump(timeout=0)
    assert scaler.stats["scale_up"] == 1, "double-spawned at max_replicas"
    healthy = [l for l in router.links if not l.dead and not l.warming]
    assert len(healthy) == 2
    # ---- burn at 0, fleet idle: drain the youngest back down ----------
    slo.next_burn = 0.0
    router.pump(timeout=0)          # starts the idle clock
    clk[0] += 3.5
    router.pump(timeout=0)          # sustained idle: retire youngest
    router.pump(timeout=0)          # reap: no in-flight work -> shutdown
    assert scaler.stats["scale_down"] == 1
    assert router.links[1].retired
    assert sup.stats["retired"] == 1
    clk[0] += 10.0
    router.pump(timeout=0)
    assert scaler.stats["scale_down"] == 1, "drained below min_replicas"
    # A retired link's EOF is not a failure — and it is never respawned.
    router.inbox.put((1, {"type": "exit"}))
    router.pump(timeout=0)
    assert router.stats["failovers"] == 0
    clk[0] += 10.0
    router.pump(timeout=0)
    assert len(spawned) == 1
    # Traffic still answers on the remaining replica.
    out = router.run([{"prompt": "p"}] * 3)
    assert [o["continuation"] for o in out] == ["f0"] * 3
    events = _events(buf)
    scales = [e for e in events if e.get("kind") == "route.scale"]
    assert [e["direction"] for e in scales] == ["up", "down"]
    assert scales[0]["signal"] == "ttft_p95"
    assert scales[0]["burn_rate"] == 2.5
    assert scales[0]["evidence"], "scale decision carried no evidence"
    assert [e["kind"] for e in events].count("route.retire") == 1


def test_router_answer_funnel_feeds_slo_engine():
    """The replica's per-answer "slo" side channel lands in the router's
    own SLO engine through the answer funnel — the autoscaling signal."""
    recorded = []

    class _Capture(_ScriptedSLO):
        def record(self, span):
            recorded.append(span)

    router, links = _fake_fleet(1, slos=_Capture())
    links[0].answer_back = False
    order = router.submit({"prompt": "p"})
    router.pump(timeout=0)
    router.inbox.put((0, {
        "type": "answer", "rid": order,
        "resp": {"continuation": "x"},
        "slo": {"ttft_s": 0.25, "total_s": 0.5},
    }))
    router.pump(timeout=0)
    assert router.drain_ready() == [{"continuation": "x"}]
    assert len(recorded) == 1
    assert recorded[0]["ttft_s"] == 0.25
    assert recorded[0]["order"] == order


def test_scheduler_span_tap_carries_latency(lm):
    """ContinuousScheduler's span_tap (the replica worker's side channel)
    hands the answer-boundary span — ttft/total/order — to host code
    without needing a telemetry bundle."""
    from transformer_tpu.serve import ContinuousScheduler

    params, cfg, tok = lm
    taps = []
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=1, span_tap=taps.append,
    )
    out = sched.run([{"prompt": PROMPT_A, "max_new": 3}])
    assert "continuation" in out[0]
    assert len(taps) == 1
    assert taps[0]["order"] == 0
    assert taps[0]["total_s"] > 0
    assert taps[0]["ttft_s"] > 0


# --------------------------------------------------------------------------
# standby internals (pure units: the tail, the floor, the stand-down)


def test_standby_tail_reconstruction(tmp_path):
    from transformer_tpu.serve.standby import Standby

    log = tmp_path / "primary.jsonl"
    clk = [100.0]
    standby = Standby(
        str(log), takeover_after_s=2.0, clock=lambda: clk[0],
    )
    lines = [
        {"kind": "route.intake", "order": 0, "req": {"prompt": "a"},
         "traceparent": None, "ts": 1.0},
        {"kind": "route.intake", "order": 1, "resp": {"error": "x",
                                                      "code": "routing"},
         "ts": 1.0},
        {"kind": "route.hb", "epoch": 3, "ports": {"replica0": 1234},
         "ts": 1.1},
        {"kind": "route.answered", "first": 0, "upto": 0, "n": 1,
         "ts": 1.2},
    ]
    log.write_text("".join(json.dumps(e) + "\n" for e in lines))
    assert standby.poll() == 0.0
    assert standby.epoch == 3
    assert standby.ports == {"replica0": 1234}
    assert standby.delivered_upto == 1  # order 0 reached the client
    # Delivered orders are pruned (bounded standby memory); the order
    # clock still resumes past everything ever seen.
    assert set(standby.intake) == {1}
    assert standby.max_order == 1
    # Torn tail line: buffered, not parsed — until its newline arrives.
    with open(log, "a") as f:
        f.write(json.dumps({"kind": "route.intake", "order": 2,
                            "req": {"prompt": "c"}})[:25])
    clk[0] += 1.0
    assert standby.poll() > 0  # heartbeat silence is accruing
    assert 2 not in standby.intake
    assert not standby.primary_dead
    clk[0] += 5.0
    assert standby.primary_dead


def test_standby_merge_prefers_owner_claim(tmp_path, monkeypatch):
    """Every replica reports every asked rid, so an early peer's
    "unknown" must never block the real owner's later "inflight" claim
    (and "done" beats both): the order is re-owned by its owner exactly
    once, not redispatched."""
    from transformer_tpu.serve.standby import Standby

    log = tmp_path / "primary.jsonl"
    events = [
        {"kind": "route.intake", "order": o, "req": {"prompt": "p"},
         "ts": 1.0}
        for o in (5, 6)
    ] + [{
        "kind": "route.hb", "epoch": 1, "ports": {"a": 1, "b": 2},
        "ts": 1.1,
    }]
    log.write_text("".join(json.dumps(e) + "\n" for e in events))
    standby = Standby(str(log))
    standby.poll()

    class _NoopLink(ReplicaLink):
        def start_reader(self, inbox):
            pass

    def _handshake(index, name, port, ask):
        link = _NoopLink(index, name)
        if name == "a":  # handshaked first (sorted), owns nothing
            return link, {"5": "unknown", "6": "unknown"}, {}
        return link, {
            "5": "inflight",
            "6": "done",
        }, {"6": {"type": "answer", "rid": 6, "resp": {"continuation": "x"}}}

    monkeypatch.setattr(standby, "_handshake",
                        lambda *a: _handshake(*a))
    router = standby.adopt()
    assert standby.stats["reowned_inflight"] == 1
    assert standby.stats["recovered_answers"] == 1
    assert standby.stats["redispatched"] == 0
    assert router._inflight[5].replica == 1  # re-owned by its OWNER
    assert router._done[6] == {"continuation": "x"}
    # The order clock resumes past everything ever seen even though the
    # delivered prefix was pruned from the intake table.
    assert router._next_order == 7


def test_adopted_router_rejournals_for_chained_takeover(
    tmp_path, monkeypatch
):
    """Orders adopted via seed_takeover are re-journaled by the new
    primary (intake records + the delivery floor): a SECOND standby
    tailing the adopted router's journal reconstructs the same
    undelivered set — chained takeovers replay from each log alone."""
    from transformer_tpu.serve.standby import Standby

    log = tmp_path / "primary.jsonl"
    events = [
        {"kind": "route.intake", "order": 0, "req": {"prompt": "a"},
         "ts": 1.0},
        {"kind": "route.intake", "order": 1, "req": {"prompt": "b"},
         "ts": 1.0},
        {"kind": "route.intake", "order": 2,
         "resp": {"error": "bad line", "code": "validation"}, "ts": 1.0},
        {"kind": "route.answered", "first": 0, "upto": 0, "n": 1,
         "ts": 1.1},
        {"kind": "route.hb", "epoch": 1, "ports": {"r0": 7}, "ts": 1.2},
    ]
    log.write_text("".join(json.dumps(e) + "\n" for e in events))
    new_log = str(tmp_path / "adopted.jsonl")
    standby = Standby(
        str(log), telemetry=Telemetry(events=EventLog(new_log)),
    )
    standby.poll()

    class _NoopLink(ReplicaLink):
        def start_reader(self, inbox):
            pass

    monkeypatch.setattr(
        standby, "_handshake",
        lambda index, name, port, ask: (
            _NoopLink(index, name), {"1": "inflight"}, {},
        ),
    )
    router = standby.adopt()
    standby._tel.maybe_flush(force=True)
    chained = Standby(new_log)
    chained.poll()
    assert chained.delivered_upto == 1           # the floor survived
    assert set(chained.intake) == {1, 2}         # adopted orders replay
    assert chained.intake[1]["req"] == {"prompt": "b"}
    assert chained.intake[2]["resp"]["code"] == "validation"
    assert chained.max_order == 2
    assert router._inflight[1].replica == 0      # and the adoption held


def test_failed_scale_up_respects_cooldown():
    """A failed spawn_new re-arms the scale-up cooldown: burn is highest
    exactly when fork is most likely to fail, and an unthrottled retry
    would fork a failing subprocess at pump frequency."""
    clk = [100.0]  # past the fresh scaler's initial cooldown window
    calls = []

    def spawn(index, name, role):
        calls.append(clk[0])
        raise RuntimeError("fork fails under pressure")

    sup = Supervisor(spawn, backoff_ms=0.0, clock=lambda: clk[0])
    scaler = FleetScaler(
        sustain_s=1.0, max_replicas=2, cooldown_s=10.0,
        clock=lambda: clk[0],
    )
    slo = _ScriptedSLO()
    router, links = _fake_fleet(
        1, supervisor=sup, scaler=scaler, slos=slo,
    )
    slo.next_burn = 3.0
    router.pump(timeout=0)              # sustain clock starts
    clk[0] += 1.5
    router.pump(timeout=0)              # sustained: one FAILED attempt
    assert len(calls) == 1
    for _ in range(5):                  # pump frequency >> cooldown
        clk[0] += 0.5
        router.pump(timeout=0)
    assert len(calls) == 1, "failed spawn retried inside the cooldown"
    clk[0] += 10.0
    router.pump(timeout=0)              # cooldown over: one more attempt
    assert len(calls) == 2
    assert sup.stats["spawn_failures"] == 2


def test_standby_stands_down_on_higher_epoch(tmp_path, monkeypatch):
    """TakeoverRejected propagates out of adopt(): another standby won
    the fleet and this one must not serve."""
    from transformer_tpu.serve.standby import Standby, TakeoverRejected

    log = tmp_path / "primary.jsonl"
    log.write_text(json.dumps({
        "kind": "route.hb", "epoch": 1, "ports": {"replica0": 9},
        "ts": 1.0,
    }) + "\n")
    standby = Standby(str(log))
    standby.poll()

    def _reject(index, name, port, ask):
        raise TakeoverRejected("epoch 5 owns the fleet")

    monkeypatch.setattr(standby, "_handshake", _reject)
    with pytest.raises(TakeoverRejected):
        standby.adopt()


def test_summarize_fleet_section_shapes():
    from transformer_tpu.obs.__main__ import render_text, summarize_events

    events = [
        {"kind": "route.spawn", "replica": "r0", "heal_s": 1.5,
         "warmed_tokens": 12, "scale_up": False, "ts": 1.0},
        {"kind": "route.spawn", "replica": "r2", "scale_up": True,
         "warmed_tokens": 0, "heal_s": None, "ts": 2.0},
        {"kind": "route.spawn", "replica": "r1", "gave_up": True,
         "attempts": 3, "ts": 3.0},
        {"kind": "route.scale", "direction": "up", "signal": "ttft_p95",
         "burn_rate": 2.0, "fleet_size": 3,
         "evidence": {"60s": {"burn_rate": 2.0}}, "ts": 2.0},
        {"kind": "route.scale", "direction": "down", "signal": "ttft_p95",
         "burn_rate": 0.0, "replica": "r2", "fleet_size": 2, "ts": 4.0},
        {"kind": "route.retire", "replica": "r2", "ts": 4.1},
        {"kind": "route.takeover", "epoch": 2, "adopted": ["r0", "r1"],
         "failed": [], "recovered_answers": 1, "reowned_inflight": 2,
         "redispatched": 0, "delivered_upto": 3, "ts": 5.0},
    ]
    fleet = summarize_events(events)["fleet"]
    assert fleet["respawns"] == 1
    assert fleet["gave_up"] == 1
    assert fleet["warmed_tokens"] == 12
    assert fleet["scale_ups"] == 1 and fleet["scale_downs"] == 1
    assert fleet["retired"] == 1
    assert fleet["takeovers"] == 1
    assert fleet["time_to_heal_s"]["mean"] == 1.5
    assert fleet["final_fleet_size"] == 2
    assert fleet["takeover"]["reowned_inflight"] == 2
    text = render_text(summarize_events(events))
    assert "fleet:" in text and "respawn" in text and "takeover" in text
