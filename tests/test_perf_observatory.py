"""Performance observatory (``obs/profile.py`` + ``obs/flight.py``): the
per-program dispatch profiler with its measured-vs-predicted roofline join
and banked drift bands, the always-on flight recorder with supervisor-
captured postmortems, the ``/healthz`` endpoint, and the event-catalogue
AST gate that keeps docs/OBSERVABILITY.md honest."""

import ast
import io
import json
import os
import shutil
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
from transformer_tpu.obs.profile import (
    BASELINE_PATH,
    CANNED_PROGRAMS,
    ProgramProfiler,
    band_breaches,
    load_baseline,
    measured_from_events,
    profile_call,
    roofline_ratio,
    roofline_report,
    write_baseline,
)
from transformer_tpu.obs.registry import MetricsRegistry

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
# the profiler: gauges, drift transitions, the wrapper (no jax)


def test_profiler_gauges_export():
    """Every perf_* family — histogram, token counter, derived measured
    gauges, roofline ratio, and the drift gauge — lands in the registry's
    Prometheus exposition (the acceptance criterion)."""
    reg = MetricsRegistry()
    baseline = {
        "programs": {"serve.pool_step": {
            "p50_s": 0.001, "band": [0.2, 5.0], "bytes_moved": 1000,
        }},
    }
    prof = ProgramProfiler(
        registry=reg, baseline=baseline, device_kind="TPU v5 lite"
    )
    for _ in range(16):
        prof.record("serve.pool_step", 0.001, tokens=2)
    text = reg.to_prometheus_text()
    for metric in (
        "perf_seconds_serve_pool_step_count 16",
        "perf_tokens_total_serve_pool_step 32",
        "perf_measured_tokens_per_s_serve_pool_step",
        "perf_measured_p50_ms_serve_pool_step",
        "perf_measured_bytes_per_s_serve_pool_step",
        "perf_roofline_ratio_serve_pool_step",
        "perf_drift_serve_pool_step",
    ):
        assert metric in text, f"{metric} missing from exposition"
    # The drift gauge carries measured-p50 / banked-p50 — all samples AT
    # the banked p50, so the ratio sits inside the band (histogram-bucket
    # approximation allowed).
    drift = reg.gauge("perf_drift_serve_pool_step").value
    assert 0.2 <= drift <= 5.0
    row = prof.summary()["serve.pool_step"]
    assert row["dispatches"] == 16 and row["tokens"] == 32.0
    assert row["drift"] == pytest.approx(drift, rel=1e-6)
    assert row["roofline_ratio"] == roofline_ratio(
        1000, row["p50_s"], "TPU v5 lite"
    ) > 0
    assert row["tokens_per_s"] > 0
    # No peak is assumed for a device outside the table: the same samples
    # on an unknown device export every family EXCEPT the ratio.
    reg2 = MetricsRegistry()
    prof2 = ProgramProfiler(registry=reg2, baseline=baseline, device_kind="cpu")
    for _ in range(16):
        prof2.record("serve.pool_step", 0.001, tokens=2)
    assert "perf_roofline_ratio" not in reg2.to_prometheus_text()
    assert "perf_measured_p50_ms_serve_pool_step" in reg2.to_prometheus_text()
    assert "roofline_ratio" not in prof2.summary()["serve.pool_step"]
    assert roofline_ratio(1000, 0.001, "cpu") is None
    assert roofline_ratio(1000, 0.001, None) is None


def test_drift_event_fires_on_transition_only():
    """A drifting program emits ONE perf.drift per breach-state
    transition, never per sample (slo.burn's discipline)."""
    events = []
    baseline = {"programs": {"train.step": {
        "p50_s": 0.001, "band": [0.5, 2.0],
    }}}
    prof = ProgramProfiler(
        emit=lambda kind, **f: events.append({"kind": kind, **f}),
        baseline=baseline,
    )
    for _ in range(8):
        prof.record("train.step", 0.001)
    assert events == []  # first judgment lands in band: silence
    for _ in range(64):  # p50 walks 100x out of band — many judged samples
        prof.record("train.step", 0.1)
    drifts = [e for e in events if e["kind"] == "perf.drift"]
    assert len(drifts) == 1, "breach must emit exactly one transition event"
    assert drifts[0]["program"] == "train.step"
    assert drifts[0]["breached"] is True
    assert drifts[0]["ratio"] > 2.0
    assert drifts[0]["band"] == [0.5, 2.0]
    assert prof.stats["drift_events"] == 1
    # A program whose FIRST judgment is already out of band also alerts.
    events2 = []
    prof2 = ProgramProfiler(
        emit=lambda kind, **f: events2.append({"kind": kind, **f}),
        baseline=baseline,
    )
    for _ in range(8):
        prof2.record("train.step", 0.1)
    assert [e["kind"] for e in events2] == ["perf.drift"]
    assert events2[0]["breached"] is True


def test_profile_call_wraps_and_records():
    prof = ProgramProfiler(baseline={})

    def fn(x, y=1):
        return x + y

    wrapped = profile_call(fn, prof, "serve.pool_step", tokens=3)
    assert wrapped.__wrapped__ is fn  # the inertness-contract handle
    assert wrapped(2, y=3) == 5
    assert prof.stats["records"] == 1
    row = prof.summary()["serve.pool_step"]
    assert row["dispatches"] == 1 and row["tokens"] == 3.0


def test_baseline_bank_roundtrip(tmp_path):
    path = str(tmp_path / "bank.json")
    measured = {
        "serve.pool_step": {"p50_s": 0.002},
        "serve.pool_verify": {"p50_s": 0},  # never banked: no honest p50
    }
    preds = {"serve.pool_step": {
        "bytes_moved": 12345, "extras": {"tokens_per_step": 2},
    }}
    doc = write_baseline(path, measured, predictions=preds)
    assert load_baseline(path) == doc
    entry = doc["programs"]["serve.pool_step"]
    assert entry["p50_s"] == 0.002
    assert entry["bytes_moved"] == 12345
    assert entry["tokens_per_step"] == 2
    assert entry["band"] == [0.2, 5.0]
    assert "serve.pool_verify" not in doc["programs"]
    assert "peak_bytes_per_s" not in doc
    assert load_baseline(str(tmp_path / "missing.json")) == {}


def test_checked_in_baseline_hygiene():
    """The shipped bank freezes predictions (bytes_moved) and bands but
    NEVER absolute p50 seconds — those are per-host, banked only by a
    local ``obs roofline --update`` run."""
    doc = load_baseline()
    assert "peak_bytes_per_s" not in doc  # the peak is keyed by device_kind
    assert doc["programs"], "shipped bank has no programs"
    for name, entry in doc["programs"].items():
        assert name in CANNED_PROGRAMS, name
        assert entry.get("bytes_moved", 0) > 0, name
        lo, hi = entry["band"]
        assert 0 < lo < 1 < hi, name
        assert "p50_s" not in entry, (
            f"{name}: absolute p50 seconds must not ship in the repo bank"
        )


# --------------------------------------------------------------------------
# the offline join + the banked-band CLI workflow


def _episode_events(
    p50=0.002, count=16, program="serve.pool_step", roofline=None,
):
    from transformer_tpu.obs.quantiles import StreamingHistogram

    suffix = program.replace(".", "_")
    h = StreamingHistogram()
    for _ in range(count):
        h.observe(p50)
    metrics = {
        f"perf_seconds_{suffix}": h.snapshot(),
        f"perf_tokens_total_{suffix}": float(count * 2),
    }
    if roofline is not None:
        # What a profiler running on a device with a known peak exports.
        metrics[f"perf_roofline_ratio_{suffix}"] = roofline
    return [{"kind": "metrics.snapshot", "ts": 1.0, "metrics": metrics}]


def test_roofline_report_tolerant_join():
    events = _episode_events()
    # Measured-only: rows appear with timing columns, nothing else.
    rows = roofline_report(events, baseline={})["programs"]
    assert [r["program"] for r in rows] == ["serve.pool_step"]
    assert rows[0]["dispatches"] == 16 and rows[0]["p50_ms"] > 0
    assert "roofline_ratio" not in rows[0] and "drift" not in rows[0]
    # + a costs document: bytes and predicted-tokens columns join in (the
    # lm_bf16 variant wins when several share a base name).
    costs = {"programs": [
        {"name": "serve.pool_step[lm_f32]", "bytes_moved": 7},
        {"name": "serve.pool_step[lm_bf16]", "bytes_moved": 1000,
         "extras": {"tokens_per_step": 2}},
    ]}
    row = roofline_report(events, costs=costs, baseline={})["programs"][0]
    assert row["predicted_bytes_moved"] == 1000
    assert row["effective_bytes_per_s"] == 1000 / row["p50_s"]
    # The offline join assumes no peak: the ratio appears only when the
    # live profiler exported one (it ran on a device in the peak table).
    assert "roofline_ratio" not in row
    live = roofline_report(
        _episode_events(roofline=0.25), costs=costs, baseline={},
    )["programs"][0]
    assert live["roofline_ratio"] == 0.25
    assert row["predicted_tokens_per_s"] == pytest.approx(
        2 / row["p50_s"], rel=1e-3
    )
    assert row["measured_over_predicted_tokens"] > 0
    # + a bank: drift columns judge the band; breaches surface.
    bank = {"programs": {
        "serve.pool_step": {"p50_s": row["p50_s"], "band": [0.5, 2.0]},
    }}
    report = roofline_report(events, baseline=bank)
    judged = report["programs"][0]
    assert judged["drift"] == 1.0 and judged["in_band"] is True
    assert band_breaches(report) == []
    bank["programs"]["serve.pool_step"]["p50_s"] = row["p50_s"] / 100
    report = roofline_report(events, baseline=bank)
    assert report["programs"][0]["in_band"] is False
    assert [b["program"] for b in band_breaches(report)] == [
        "serve.pool_step"
    ]


def test_measured_from_events_last_snapshot_wins():
    events = _episode_events(count=16) + _episode_events(count=32)
    measured = measured_from_events(events)
    assert measured["serve.pool_step"]["dispatches"] == 32
    assert measured["serve.pool_step"]["tokens"] == 64.0
    assert measured_from_events([{"kind": "serve.request", "ts": 1.0}]) == {}


def test_roofline_cli_banked_band_workflow(tmp_path, capsys):
    """The acceptance workflow, pinned end to end on a COPY of the
    checked-in bank: pass -> perturb -> --check fails -> --update ->
    pass. (The shipped obs/roofline_baseline.json is never rewritten.)"""
    from transformer_tpu.obs.__main__ import main

    ep = tmp_path / "episode.jsonl"
    ep.write_text("".join(
        json.dumps(e) + "\n" for e in _episode_events()
    ))
    bank = str(tmp_path / "bank.json")
    shutil.copy(BASELINE_PATH, bank)
    # --update banks the measured p50 and freezes the prior bank's
    # predictions next to it (no --costs given).
    assert main(["roofline", str(ep), "--baseline", bank, "--update"]) == 0
    assert "banked 1 program(s)" in capsys.readouterr().out
    banked = load_baseline(bank)["programs"]["serve.pool_step"]
    assert banked["p50_s"] > 0
    assert banked["bytes_moved"] == load_baseline()["programs"][
        "serve.pool_step"]["bytes_moved"]
    # Same episode against its own bank: in band, --check passes.
    assert main(["roofline", str(ep), "--baseline", bank, "--check"]) == 0
    capsys.readouterr()
    # Perturb: the bank remembers a 100x faster program -> breach.
    doc = json.load(open(bank))
    doc["programs"]["serve.pool_step"]["p50_s"] /= 100.0
    with open(bank, "w") as f:
        json.dump(doc, f)
    assert main(["roofline", str(ep), "--baseline", bank, "--check"]) == 1
    err = capsys.readouterr().err
    assert "BAND BREACH serve.pool_step" in err
    # Re-bank on this host: the band heals.
    assert main(["roofline", str(ep), "--baseline", bank, "--update"]) == 0
    assert main(["roofline", str(ep), "--baseline", bank, "--check"]) == 0
    capsys.readouterr()
    # The JSON report carries the judged row.
    assert main(
        ["roofline", str(ep), "--baseline", bank, "--format=json"]
    ) == 0
    report = json.loads(capsys.readouterr().out)
    rows = {r["program"]: r for r in report["programs"]}
    assert rows["serve.pool_step"]["in_band"] is True
    assert rows["serve.pool_step"]["effective_bytes_per_s"] > 0
    # An episode with no profiler stream banks nothing (exit 2).
    empty = tmp_path / "empty.jsonl"
    empty.write_text(json.dumps({"kind": "serve.request", "ts": 1.0}) + "\n")
    assert main(
        ["roofline", str(empty), "--baseline", bank, "--update"]
    ) == 2
    capsys.readouterr()


def test_summarize_reports_perf_section(capsys):
    from transformer_tpu.obs.__main__ import render_text, summarize_events

    report = summarize_events(_episode_events())
    assert report["perf"]["programs"], "summarize dropped the perf section"
    text = render_text(report)
    assert "perf:" in text and "serve.pool_step" in text
    # No profiler stream -> no perf section (the section never lies).
    assert "perf" not in summarize_events(
        [{"kind": "serve.request", "ts": 1.0}]
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
    tel.arm_profiler(baseline={})
    tel.arm_flight(None)
    tel.profiler.record("serve.pool_step", 0.001, tokens=1)
    tel.emit("serve.request", order=0)
    port = tel.start_prometheus_server(0)
    base = f"http://127.0.0.1:{port}"
    try:
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            assert r.status == 200
            assert "perf_seconds_serve_pool_step" in r.read().decode()
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as r:
            assert r.status == 200
            doc = json.loads(r.read())
        assert doc["ok"] is True and doc["pid"] == os.getpid()
        assert doc["uptime_s"] >= 0
        assert doc["sinks"]["event_log"]["broken"] is False
        assert doc["flight"]["depth"] >= 1
        assert doc["profiler"]["records"] == 1
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
    for kind in ("perf.drift", "flight.dump", "route.postmortem",
                 "metrics.snapshot"):
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
# armed observatory vs the scheduler: inertness, retraces, the join


def _armed_telemetry(buf=None):
    tel = Telemetry(
        events=EventLog(buf) if buf is not None else None, interval=0.0,
    )
    tel.arm_profiler()
    tel.arm_flight(None)
    return tel


def test_scheduler_byte_identity_with_observatory_armed(lm):
    """Profiler + flight recorder on the serving path change no answer
    byte — and the dense + paged episodes together give ``obs roofline``
    its >= 4 canned programs (the acceptance floor) from one CPU run."""
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
    assert tel.profiler.stats["records"] > 0
    assert tel.flight.depth() > 0
    summary = tel.profiler.summary()
    for program in ("serve.pool_step", "serve.slot_prefill",
                    "serve.pool_step_paged", "serve.slot_prefill_paged"):
        assert program in summary, sorted(summary)
        assert summary[program]["dispatches"] > 0
    assert summary["serve.pool_step"]["tokens"] > 0
    # The episode's snapshots reconstruct the same programs offline, and
    # the checked-in bank's frozen predictions price their measured p50s
    # (no roofline ratio: this host's device has no entry in the peak table).
    tel.maybe_flush(force=True)
    events = [json.loads(l) for l in buf.getvalue().splitlines()]
    report = roofline_report(events)
    rows = {r["program"]: r for r in report["programs"]}
    assert len(rows) >= 4
    for program in ("serve.pool_step", "serve.pool_step_paged",
                    "serve.slot_prefill", "serve.slot_prefill_paged"):
        assert rows[program]["p50_ms"] > 0, program
        assert rows[program]["effective_bytes_per_s"] > 0, program
        assert "roofline_ratio" not in rows[program], program


def test_scheduler_zero_recompiles_with_observatory_armed(lm):
    """Arming profiler + flight recorder must not cost a single recompile
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
    assert tel.profiler.stats["records"] > 0


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


# --------------------------------------------------------------------------
# the bench acceptance: a real CPU sweep measures what the model predicts


@pytest.mark.slow  # subprocess + two jit sweeps: slow tier
def test_decode_bench_emits_measured_roofline_columns(tmp_path):
    """benchmarks/decode_bench.py on CPU: every sweep row carries
    measured_step_p50_ms (and no roofline ratio — the CPU has no entry in
    the peak table), and ``obs roofline`` over the episode reports >= 4
    canned programs (the acceptance bar)."""
    from transformer_tpu.obs.__main__ import main as obs_main

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    jsonl = str(tmp_path / "bench.jsonl")
    out = subprocess.run(
        [sys.executable, str(REPO / "benchmarks" / "decode_bench.py"),
         "--layers", "1", "--d_model", "32", "--heads", "2", "--dff", "64",
         "--vocab", "128", "--prompt_len", "16", "--decode_steps", "8",
         "--reps", "1", "--prefix_requests", "4",
         "--kv_layout", "dense,paged", "--metrics_jsonl", jsonl],
        capture_output=True, text=True, timeout=420, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    for layout_row in row["kv_layouts"]:
        assert layout_row["measured_step_p50_ms"] > 0, layout_row
        assert layout_row["roofline_ratio"] is None, layout_row
    import contextlib

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert obs_main(["roofline", jsonl, "--format=json"]) == 0
    report = json.loads(buf.getvalue())
    canned = [
        r["program"] for r in report["programs"]
        if r["program"] in CANNED_PROGRAMS
    ]
    assert len(canned) >= 4, canned
