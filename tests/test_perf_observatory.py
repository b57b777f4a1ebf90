"""The flight recorder (``obs/flight.py``) with supervisor-captured
postmortems, the ``/healthz`` endpoint, and the event-catalogue AST gate
that keeps docs/OBSERVABILITY.md honest."""

import ast
import io
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

from transformer_tpu.obs import EventLog, Telemetry
from transformer_tpu.obs.flight import (
    FlightRecorder,
    flight_path_for,
    load_flight_record,
)

REPO = Path(__file__).resolve().parents[1]

# The deterministic test-model bootstrap (tests/test_supervisor.py): every
# process building this spec gets bit-identical params and vocab.
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
    path = tmp_path_factory.mktemp("observatory") / "spec.json"
    path.write_text(json.dumps(SPEC))
    return str(path)


def _scheduler(lm, telemetry, **kw):
    from transformer_tpu.serve import ContinuousScheduler

    params, cfg, tok = lm
    return ContinuousScheduler(
        params, cfg, tok, num_slots=2, max_total=32, default_max_new=4,
        telemetry=telemetry, **kw,
    )


# --------------------------------------------------------------------------
# the flight recorder (no jax)


def test_flight_ring_bounded_and_routed():
    fr = FlightRecorder(None, capacity=8, snapshots=2)
    for i in range(50):
        fr.record("serve.request", {"order": i})
    fr.record("trace.span", {"name": "x"})
    fr.record("metrics.snapshot", {"metrics": {}})
    rec = fr.snapshot_record()
    assert [e["order"] for e in rec["events"]] == list(range(42, 50))
    assert len(rec["spans"]) == 1 and len(rec["snapshots"]) == 1
    assert rec["recorded"] == 52  # everything seen, ring or not
    assert fr.depth() == 10


def test_flight_dump_file_event_and_salvage(tmp_path):
    emitted = []
    path = flight_path_for(str(tmp_path / "rep.jsonl"))
    assert path.endswith(".jsonl.flight.json")
    fr = FlightRecorder(
        path, emit=lambda kind, **f: emitted.append({"kind": kind, **f}),
    )
    fr.record("serve.request", {"order": 0})
    fr.dump("request")
    loaded = load_flight_record(path)
    assert loaded["reason"] == "request" and loaded["pid"] == os.getpid()
    assert [e["kind"] for e in loaded["events"]] == ["serve.request"]
    assert [e["kind"] for e in emitted] == ["flight.dump"]
    assert emitted[0]["reason"] == "request"
    # Auto dumps persist but stay SILENT (2 Hz must not flood the log).
    emitted.clear()
    fr.autodump_s = 1e-4
    time.sleep(2e-4)
    assert fr.maybe_dump() is True
    assert emitted == []
    assert load_flight_record(path)["reason"] == "auto"
    # Salvage is best-effort by contract: missing / torn / non-flight
    # files load as None, never raise.
    assert load_flight_record(str(tmp_path / "missing.json")) is None
    (tmp_path / "torn.json").write_text('{"events": [')
    assert load_flight_record(str(tmp_path / "torn.json")) is None
    (tmp_path / "other.json").write_text('{"kind": "x"}')
    assert load_flight_record(str(tmp_path / "other.json")) is None


def test_flight_tap_records_then_forwards():
    seen = []
    fr = FlightRecorder(None)
    tapped = fr.tap(lambda kind, **f: seen.append((kind, f)))
    tapped("serve.request", order=1)
    assert seen == [("serve.request", {"order": 1})]
    assert fr.depth() == 1
    assert callable(tapped.__wrapped__)


def test_flight_autodump_outruns_snapshot_interval(tmp_path):
    """The autodump cadence is the flight recorder's own (autodump_s), NOT
    the telemetry snapshot interval: a SIGKILL can't trigger a dump, so
    the on-disk record's staleness bound must not inherit the (much
    longer) sink interval."""
    path = flight_path_for(str(tmp_path / "m.jsonl"))
    tel = Telemetry(interval=1e9)
    tel.arm_flight(path, autodump_s=1e-4)
    tel.emit("serve.request", order=7)
    assert tel.maybe_flush() is True  # the first flush always runs
    os.remove(path)
    tel.emit("serve.request", order=8)
    time.sleep(2e-4)
    assert tel.maybe_flush() is False  # inside the snapshot interval...
    rec = load_flight_record(path)  # ...but the autodump still fired
    assert rec is not None and rec["reason"] == "auto"
    assert any(e["kind"] == "serve.request" for e in rec["events"])


def test_flight_signal_dump_in_subprocess(tmp_path):
    """SIGTERM dumps the ring THEN chains to SIG_DFL (default termination
    survives) — in a subprocess, because the re-raise kills the process."""
    path = flight_path_for(str(tmp_path / "sig.jsonl"))
    code = (
        "import os, signal, sys\n"
        "from transformer_tpu.obs.flight import FlightRecorder\n"
        "fr = FlightRecorder(sys.argv[1])\n"
        "fr.record('serve.request', {'order': 1})\n"
        "fr.install_signal_handlers()\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
        "raise SystemExit('unreachable: SIG_DFL did not terminate')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, path],
        cwd=str(REPO), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == -signal.SIGTERM, (proc.returncode, proc.stderr)
    rec = load_flight_record(path)
    assert rec is not None and rec["reason"] == "signal"
    assert [e["kind"] for e in rec["events"]] == ["serve.request"]


# --------------------------------------------------------------------------
# /healthz beside /metrics


def test_healthz_endpoint(tmp_path):
    buf = io.StringIO()
    tel = Telemetry(events=EventLog(buf))
    tel.arm_flight(None)
    tel.registry.counter("serve_steps_total").inc()
    tel.emit("serve.request", order=0)
    port = tel.start_prometheus_server(0)
    base = f"http://127.0.0.1:{port}"
    try:
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            assert r.status == 200
            assert "serve_steps_total 1" in r.read().decode()
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            assert r.status == 200
            doc = json.loads(r.read())
        assert doc["ok"] is True and doc["pid"] == os.getpid()
        assert doc["uptime_s"] >= 0
        assert doc["sinks"]["event_log"]["broken"] is False
        assert doc["flight"]["depth"] >= 1
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/bogus", timeout=10)
        assert ei.value.code == 404
        # A hard-downgraded event sink flips liveness to 503.
        tel.events._broken = True
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/healthz", timeout=10)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["ok"] is False
    finally:
        tel.close()


# --------------------------------------------------------------------------
# the event-catalogue AST gate


def _emitted_kinds() -> set:
    """Every literal event kind at an emit call site in the package."""
    kinds = set()
    for py in sorted((REPO / "transformer_tpu").rglob("*.py")):
        tree = ast.parse(py.read_text(encoding="utf-8"), filename=str(py))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            func = node.func
            name = (
                func.attr if isinstance(func, ast.Attribute)
                else getattr(func, "id", None)
            )
            if name not in ("emit", "emit_event", "_emit"):
                continue
            a0 = node.args[0]
            if isinstance(a0, ast.Constant) and isinstance(a0.value, str):
                kinds.add(a0.value)
    return kinds


def test_event_catalogue_covers_every_emit_site():
    from transformer_tpu.obs.events import EVENT_CATALOGUE

    emitted = _emitted_kinds()
    assert emitted, "the AST sweep found no emit sites — the gate is broken"
    unknown = emitted - set(EVENT_CATALOGUE)
    assert not unknown, (
        f"emit sites use kinds missing from EVENT_CATALOGUE: "
        f"{sorted(unknown)} — add them to obs/events.py AND "
        "docs/OBSERVABILITY.md"
    )
    # This PR's kinds are both emitted somewhere and catalogued.
    for kind in ("flight.dump", "route.postmortem", "metrics.snapshot"):
        assert kind in emitted, kind
        assert kind in EVENT_CATALOGUE, kind


def test_event_catalogue_documented():
    from transformer_tpu.obs.events import EVENT_CATALOGUE

    docs = (REPO / "docs" / "OBSERVABILITY.md").read_text(encoding="utf-8")
    missing = [k for k in EVENT_CATALOGUE if k not in docs]
    assert not missing, (
        f"catalogued kinds undocumented in docs/OBSERVABILITY.md: {missing}"
    )


# --------------------------------------------------------------------------
# armed flight recorder vs the scheduler: inertness, retraces


def _armed_telemetry(buf=None):
    tel = Telemetry(
        events=EventLog(buf) if buf is not None else None, interval=0.0,
    )
    tel.arm_flight(None)
    return tel


def test_scheduler_byte_identity_with_observatory_armed(lm):
    """The flight recorder on the serving path changes no answer byte,
    dense or paged."""
    reqs = [
        {"prompt": PROMPT_A, "max_new": 6},
        {"prompt": "kl", "max_new": 2},
        {"prompt": "ab cd", "max_new": 4},
    ]
    plain = _scheduler(lm, None).run([dict(r) for r in reqs])
    buf = io.StringIO()
    tel = _armed_telemetry(buf)
    armed = _scheduler(lm, tel).run([dict(r) for r in reqs])
    assert plain == armed
    paged_plain = _scheduler(lm, None, kv_layout="paged").run(
        [dict(r) for r in reqs]
    )
    paged = _scheduler(lm, tel, kv_layout="paged").run(
        [dict(r) for r in reqs]
    )
    assert paged_plain == paged
    assert tel.flight.depth() > 0


def test_scheduler_zero_recompiles_with_observatory_armed(lm):
    """Arming the flight recorder must not cost a single recompile
    on the steady-state decode path (retrace-sentinel criterion)."""
    from transformer_tpu.analysis.retrace import RetraceSentinel
    from transformer_tpu.serve import scheduler as sched_mod

    tel = _armed_telemetry()
    warm = _scheduler(lm, tel)
    warm.run([{"prompt": "ab cd", "max_new": 3}])
    sentinel = RetraceSentinel()
    sentinel.watch("_pool_step", sched_mod._pool_step, budget=0)
    sentinel.watch("_slot_prefill", sched_mod._slot_prefill, budget=0)
    sentinel.watch("_pick_pool", sched_mod._pick_pool, budget=0)
    sentinel.snapshot()
    for _ in range(3):
        s = _scheduler(lm, tel)
        out = s.run([{"prompt": "ab cd", "max_new": 3}])
        assert "continuation" in out[0]
    sentinel.assert_within_budget()
    assert tel.flight.depth() > 0


# --------------------------------------------------------------------------
# the chaos drill: SIGKILL a replica, the supervisor lands its postmortem


@pytest.mark.chaos
def test_sigkill_postmortem_capture(lm, spec_file, tmp_path):
    """SIGKILL the busy replica of a supervised pair: the fleet heals AND
    the victim's flight record — final serve.request spans included —
    lands in a route.postmortem event; ``obs postmortem`` reconstructs
    the incident from the logs + dumps."""
    import contextlib

    from transformer_tpu.obs.__main__ import main as obs_main
    from transformer_tpu.serve.router import ReplicaProcess, Router
    from transformer_tpu.serve.supervisor import Supervisor

    params, cfg, tok = lm

    def worker_args(i):
        return [
            "--model_spec", spec_file, "--serve_slots", "2",
            "--heartbeat_ms", "50", "--prefix_cache_mb", "8",
            "--prefix_block", "4",
            "--metrics_jsonl", str(tmp_path / f"replica{i}.jsonl"),
        ]

    links = [ReplicaProcess.spawn(i, worker_args(i)) for i in range(2)]

    def spawn(index, name, role):
        return ReplicaProcess.spawn(
            index, worker_args(index), role=role, name=name
        )

    sup = Supervisor(spawn, backoff_ms=50.0)
    router_log = str(tmp_path / "router.jsonl")
    telemetry = Telemetry(events=EventLog(router_log))
    router = Router(
        links, encode=tok.encode, bos_id=tok.bos_id, affinity_block=4,
        heartbeat_timeout_s=10.0, telemetry=telemetry, supervisor=sup,
    )
    for link in links:
        link.start_reader(router.inbox)
    deadline = time.time() + 110
    try:
        out = router.run([{"prompt": PROMPT_A, "max_new": 6}] * 6)
        assert all("continuation" in o for o in out)
        victim = max(router.links, key=lambda l: l.answered)
        victim_name, victim_jsonl = victim.name, victim.metrics_jsonl
        assert victim_jsonl, "spawn did not parse --metrics_jsonl"
        # Ask the victim to dump: the wire reply is the deterministic
        # capture origin (the 0.5 s autodump file backstops a race).
        victim.send({"type": "dump"})
        while victim.flight_record is None and time.time() < deadline:
            router.pump()
        assert victim.flight_record, "victim never shipped its record"
        kinds = [e.get("kind") for e in victim.flight_record["events"]]
        assert "serve.request" in kinds, kinds
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
        assert sup.stats["postmortems"] >= 1, sup.stats
    finally:
        router.shutdown()
        telemetry.close()
    events = [json.loads(l) for l in open(router_log, encoding="utf-8")]
    pms = [e for e in events if e.get("kind") == "route.postmortem"]
    assert pms, "no route.postmortem in the router log"
    assert pms[0]["replica"] == victim_name
    assert pms[0]["origin"] in ("wire", "file")
    record = pms[0]["record"]
    finals = [
        e for e in record["events"] if e.get("kind") == "serve.request"
    ]
    assert finals, "captured record carries no serve.request spans"
    assert all(f.get("new_tokens") == 6 for f in finals), finals
    # The CLI reconstructs the incident from the same artifacts.
    inputs = [router_log]
    flight_file = flight_path_for(victim_jsonl)
    if os.path.exists(flight_file):
        inputs.append(flight_file)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert obs_main(["postmortem", *inputs, "--format=json"]) == 0
    report = json.loads(buf.getvalue())
    assert report["postmortems"], report
    row = report["postmortems"][0]
    assert row["replica"] == victim_name
    assert row["final_requests"], row
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert obs_main(["postmortem", *inputs]) == 0
    text = buf.getvalue()
    assert "postmortem(s)" in text and victim_name in text
