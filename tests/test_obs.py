"""Unified telemetry (``transformer_tpu/obs``): quantile engine, registry +
Prometheus exposition, JSONL event log, tfevents sink round-trip (framing +
proto decoded back in-test), scheduler span lifecycle (admit mid-flight,
error isolation, monotone timings, byte-identical answers), trainer
instrumentation, CLI flag plumbing, and the summarize report."""

import io
import json
import math
import os
import struct
import subprocess
import sys

import pytest

from transformer_tpu.obs import (
    EventLog,
    MetricsRegistry,
    StreamingHistogram,
    Telemetry,
    read_events,
    timed_call,
)

# --------------------------------------------------------------------------
# quantile engine


def test_streaming_histogram_quantiles_within_bucket_error():
    h = StreamingHistogram()
    for i in range(1, 1001):
        h.observe(i / 1000.0)  # 1ms .. 1s uniform
    # Relative error bound: sqrt(growth) - 1 (geometric bucket midpoint).
    bound = math.sqrt(h.growth) - 1 + 1e-9
    for q, exact in ((0.5, 0.5), (0.95, 0.95), (0.99, 0.99)):
        got = h.quantile(q)
        assert abs(got - exact) / exact <= bound, (q, got)
    assert h.count == 1000
    assert h.min == 0.001 and h.max == 1.0
    assert abs(h.mean - 0.5005) < 1e-9


def test_streaming_histogram_weighted_observe_and_edge_cases():
    h = StreamingHistogram()
    h.observe(0.01, n=99)
    h.observe(10.0)
    assert h.count == 100
    assert h.quantile(0.5) == pytest.approx(0.01, rel=0.05)
    assert h.quantile(1.0) == 10.0  # clamped to observed max
    h.observe(float("nan"))  # ignored, never poisons
    assert h.count == 100
    h.observe(1e-12)  # below lo: clamps into first bucket
    h.observe(1e12)   # above hi: clamps into last bucket
    assert h.count == 102 and h.max == 1e12
    assert StreamingHistogram().snapshot() == {"count": 0}
    assert StreamingHistogram().quantile(0.5) == 0.0


def test_streaming_histogram_buckets_are_ascending_nonempty():
    h = StreamingHistogram()
    for v in (0.001, 0.001, 0.5, 2.0):
        h.observe(v)
    buckets = h.buckets()
    bounds = [b for b, _ in buckets]
    assert bounds == sorted(bounds)
    assert sum(c for _, c in buckets) == h.count


def test_streaming_histogram_counts_out_of_range_samples():
    """Satellite audit: samples outside [lo, hi) clamp into the edge
    buckets (historical behavior) but are now COUNTED and surfaced by
    snapshot() — a mis-ranged histogram announces itself instead of
    silently reporting clamp artifacts as tail quantiles."""
    h = StreamingHistogram()
    h.observe(0.5)
    assert h.underflow == 0 and h.overflow == 0
    assert "underflow" not in h.snapshot()  # in-range: schema unchanged
    h.observe(1e-9, n=3)   # below lo=1e-6
    h.observe(5e4)         # at/above hi=1e4
    h.observe(2e5)
    snap = h.snapshot()
    assert snap["underflow"] == 3 and h.underflow == 3
    assert snap["overflow"] == 2 and h.overflow == 2
    assert snap["count"] == 6
    # Exact side-stats still honest at the tails.
    assert snap["min"] == 1e-9 and snap["max"] == 2e5
    # Boundary semantics: lo is IN range, hi is not.
    h2 = StreamingHistogram(lo=1e-3, hi=1.0)
    h2.observe(1e-3)
    h2.observe(1.0)
    assert h2.underflow == 0 and h2.overflow == 1


def test_streaming_histogram_error_bound_vs_sorted_reference():
    """Satellite: pin the documented quantile error bound (sqrt(growth)-1
    relative) against an exact sorted-reference quantile over a seeded
    non-uniform stream — the bound must hold at every reported
    percentile, not just on uniform data."""
    import random

    rng = random.Random(1234)
    h = StreamingHistogram()
    samples = []
    for _ in range(5000):
        # Log-uniform over ~7 decades of the in-range span: exercises many
        # buckets, including sparse tails.
        v = 10 ** rng.uniform(-5.5, 3.5)
        samples.append(v)
        h.observe(v)
    samples.sort()
    bound = math.sqrt(h.growth) - 1 + 1e-9
    for q in (0.01, 0.25, 0.5, 0.9, 0.95, 0.99, 0.999):
        exact = samples[min(len(samples) - 1, max(0, math.ceil(q * len(samples)) - 1))]
        got = h.quantile(q)
        assert abs(got - exact) / exact <= bound, (q, got, exact)
    assert h.underflow == 0 and h.overflow == 0


# --------------------------------------------------------------------------
# StepTimer reuse (satellite: one quantile implementation, shared stream)


def test_step_timer_histogram_and_summary_percentiles():
    from transformer_tpu.utils.profiling import StepTimer

    t = StepTimer(tokens_per_step=10)
    for _ in range(4):
        t.tick()
    t.sync()
    assert t.histogram.count == 4  # window time attributed per step
    s = t.summary()
    assert "p50" in s and "p95" in s and "p99" in s
    # The registry binds the SAME StreamingHistogram instance — no duplicate
    # quantile accounting between StepTimer and the obs export.
    reg = MetricsRegistry()
    m = reg.histogram("train_step_seconds", hist=t.histogram)
    assert m.hist is t.histogram
    with pytest.raises(ValueError, match="different sample stream"):
        reg.histogram("train_step_seconds", hist=StreamingHistogram())


# --------------------------------------------------------------------------
# registry + Prometheus exposition


def test_registry_kinds_and_validation():
    reg = MetricsRegistry()
    c = reg.counter("req_total")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(4)
    g.dec()
    assert g.value == 3
    assert reg.counter("req_total") is c  # get-or-create
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("req_total")
    with pytest.raises(ValueError, match="not Prometheus-exposable"):
        reg.counter("bad name!")


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests").inc(5)
    reg.gauge("occupancy").set(0.5)
    h = reg.histogram("lat_seconds", "latency")
    for v in (0.01, 0.02, 0.02, 0.5):
        h.observe(v)
    text = reg.to_prometheus_text()
    assert "# HELP req_total requests" in text
    assert "# TYPE req_total counter" in text
    assert "req_total 5" in text
    assert "occupancy 0.5" in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="+Inf"} 4' in text
    assert "lat_seconds_count 4" in text
    # Bucket counts are CUMULATIVE and end at the total.
    counts = [
        int(line.rsplit(" ", 1)[1])
        for line in text.splitlines()
        if line.startswith("lat_seconds_bucket")
    ]
    assert counts == sorted(counts) and counts[-1] == 4


# --------------------------------------------------------------------------
# event log


def test_event_log_concurrent_writers_parse_back(tmp_path):
    """The EventLog threading contract: N real threads hammering emit()
    produce a log where EVERY line parses back as one JSON event — no torn
    lines, no lost events. (The deterministic-schedule twin of this test
    lives in analysis/schedules.py eventlog_writers; the revert-the-lock
    canary in test_analysis.py shows the explorer catching the torn case.)"""
    import threading

    from transformer_tpu.obs.events import EventLog, read_events

    path = str(tmp_path / "concurrent.jsonl")
    log = EventLog(path)
    writers, per = 8, 100
    start = threading.Barrier(writers)

    def hammer(wid):
        start.wait()
        for i in range(per):
            log.emit("obs.test", writer=wid, seq=i)

    threads = [
        threading.Thread(target=hammer, args=(w,)) for w in range(writers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    log.close()
    raw = [ln for ln in open(path).read().splitlines() if ln]
    assert len(raw) == writers * per
    events = []
    for line in raw:
        events.append(json.loads(line))  # a torn line dies right here
    assert len(read_events(path, "obs.test")) == writers * per
    # every (writer, seq) pair exactly once, in per-writer order
    by_writer = {}
    for ev in events:
        by_writer.setdefault(ev["writer"], []).append(ev["seq"])
    assert set(by_writer) == set(range(writers))
    for seqs in by_writer.values():
        assert seqs == list(range(per))


def test_event_log_round_trip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path)
    log.emit("serve.request", order=1, total_s=0.5)
    log.emit("train.window", steps=10)
    log.close()
    with open(path, "a") as f:
        f.write("{truncated mid-crash\n")  # must not break readers
    events = read_events(path)
    assert [e["kind"] for e in events] == ["serve.request", "train.window"]
    assert all("ts" in e for e in events)
    assert read_events(path, kind="train.window")[0]["steps"] == 10


def test_event_log_survives_unwritable_sink(capsys):
    buf = io.StringIO()
    log = EventLog(buf)
    log.emit("a", x=1)
    buf.close()
    log.emit("b", x=2)  # write to closed file: degrade, never raise
    log.emit("c", x=3)
    log.flush()
    assert "telemetry disabled" in capsys.readouterr().err


# --------------------------------------------------------------------------
# telemetry bundle


def test_telemetry_flush_interval_and_prom_file(tmp_path):
    jsonl = str(tmp_path / "m.jsonl")
    tel = Telemetry(
        events=EventLog(jsonl), prom_path=jsonl + ".prom", interval=3600.0
    )
    tel.registry.counter("x_total").inc()
    assert tel.maybe_flush() is True   # first flush always runs
    assert tel.maybe_flush() is False  # interval gates the second
    assert tel.maybe_flush(force=True) is True
    tel.close()
    snaps = read_events(jsonl, kind="metrics.snapshot")
    assert len(snaps) == 3  # two explicit + close()
    assert snaps[-1]["metrics"]["x_total"] == 1
    assert "x_total 1" in open(jsonl + ".prom").read()
    assert not os.path.exists(jsonl + ".prom.tmp")  # atomic replace


def test_prometheus_http_endpoint():
    import urllib.request

    tel = Telemetry()
    tel.registry.gauge("up").set(1)
    port = tel.start_prometheus_server(0)  # OS-assigned port
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        assert "# TYPE up gauge" in body and "up 1" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope", timeout=10)
    finally:
        tel.close()


def test_timed_call_records_and_forwards():
    reg = MetricsRegistry()
    h, c = reg.histogram("h"), reg.counter("c_total")
    fn = timed_call(lambda x: x + 1, h, c)
    assert fn(41) == 42
    assert h.hist.count == 1 and c.value == 1
    assert fn.__wrapped__(41) == 42  # underlying fn stays reachable


# --------------------------------------------------------------------------
# tfevents sink: decode the wire format back (masked-crc + varint framing)


def _tfrecords(path):
    from transformer_tpu.utils.tensorboard import _masked_crc

    data = open(path, "rb").read()
    records, off = [], 0
    while off < len(data):
        (length,) = struct.unpack("<Q", data[off:off + 8])
        (hcrc,) = struct.unpack("<I", data[off + 8:off + 12])
        assert hcrc == _masked_crc(data[off:off + 8]), "header crc mismatch"
        payload = data[off + 12:off + 12 + length]
        (pcrc,) = struct.unpack("<I", data[off + 12 + length:off + 16 + length])
        assert pcrc == _masked_crc(payload), "payload crc mismatch"
        records.append(payload)
        off += 16 + length
    return records


def _parse_proto(buf):
    """Minimal wire-format parser: field -> list of raw values (varint int,
    fixed32/64 bytes, or length-delimited bytes)."""
    fields, off = {}, 0
    while off < len(buf):
        tag, off = _read_varint(buf, off)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            val, off = _read_varint(buf, off)
        elif wire == 1:
            val, off = buf[off:off + 8], off + 8
        elif wire == 5:
            val, off = buf[off:off + 4], off + 4
        elif wire == 2:
            n, off = _read_varint(buf, off)
            val, off = buf[off:off + n], off + n
        else:  # pragma: no cover - writer never emits groups
            raise AssertionError(f"unexpected wire type {wire}")
        fields.setdefault(field, []).append(val)
    return fields


def _read_varint(buf, off):
    shift = val = 0
    while True:
        b = buf[off]
        off += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, off
        shift += 7


def _packed_doubles(raw: bytes) -> list:
    return [v for (v,) in struct.iter_unpack("<d", raw)]


def test_tfevents_scalar_and_histogram_round_trip(tmp_path):
    from transformer_tpu.utils.tensorboard import SummaryWriter

    w = SummaryWriter(str(tmp_path))
    w.scalar("loss", 1.25, step=7)
    h = StreamingHistogram()
    for v in (0.001, 0.002, 0.002, 0.4):
        h.observe(v)
    w.histogram("step_time_s", h, step=7)
    w.histogram("empty", StreamingHistogram(), step=7)  # skipped, not written
    w.close()

    records = _tfrecords(w.path)
    assert len(records) == 3  # file_version + scalar + histogram

    version = _parse_proto(records[0])
    assert version[3] == [b"brain.Event:2"]

    scalar_event = _parse_proto(records[1])
    assert scalar_event[2] == [7]  # Event.step varint
    value = _parse_proto(_parse_proto(scalar_event[5][0])[1][0])
    assert value[1] == [b"loss"]
    (loss,) = struct.unpack("<f", value[2][0])
    assert loss == 1.25

    hist_event = _parse_proto(records[2])
    assert hist_event[2] == [7]
    value = _parse_proto(_parse_proto(hist_event[5][0])[1][0])
    assert value[1] == [b"step_time_s"]
    assert 4 not in value  # field 4 is Image — histo MUST be field 5
    histo = _parse_proto(value[5][0])
    (hmin,) = struct.unpack("<d", histo[1][0])
    (hmax,) = struct.unpack("<d", histo[2][0])
    (num,) = struct.unpack("<d", histo[3][0])
    (total,) = struct.unpack("<d", histo[4][0])
    (sum_sq,) = struct.unpack("<d", histo[5][0])
    assert (hmin, hmax, num) == (0.001, 0.4, 4.0)
    assert total == pytest.approx(0.405)
    assert sum_sq == pytest.approx(h.sum_squares)
    limits = _packed_doubles(histo[6][0])
    counts = _packed_doubles(histo[7][0])
    assert len(limits) == len(counts)
    assert sum(counts) == 4.0
    assert limits == sorted(limits)


# --------------------------------------------------------------------------
# scheduler span lifecycle (CPU tiny model)


@pytest.fixture(scope="module")
def lm():
    import jax

    from transformer_tpu.config import ModelConfig
    from transformer_tpu.data.tokenizer import SubwordTokenizer
    from transformer_tpu.models import transformer_init

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


def _scheduler(lm, telemetry, num_slots=2, prefill_chunk=0):
    from transformer_tpu.serve import ContinuousScheduler

    params, cfg, tok = lm
    return ContinuousScheduler(
        params, cfg, tok, num_slots=num_slots, max_total=32,
        default_max_new=4, prefill_chunk=prefill_chunk, telemetry=telemetry,
    )


def test_scheduler_spans_and_byte_identity(lm):
    reqs = [
        {"prompt": "ab cd ef gh ij", "max_new": 6},
        {"prompt": "kl", "max_new": 2},
        {"prompt": "ab cd", "max_new": 8, "temperature": 0.9, "seed": 3},
        {"prompt": "mn ef", "max_new": 3},
        {"prompt": "gh", "max_new": 1},
    ]
    plain = _scheduler(lm, None).run(reqs)
    buf = io.StringIO()
    tel = Telemetry(events=EventLog(buf), interval=0.0)
    instrumented = _scheduler(lm, tel).run(reqs)
    # Metrics on/off must be invisible in the answers (acceptance criterion).
    assert plain == instrumented

    events = [json.loads(line) for line in buf.getvalue().splitlines()]
    spans = [e for e in events if e["kind"] == "serve.request"]
    assert len(spans) == len(reqs)
    for s in spans:
        # Per-request timings are monotone along the request lifecycle.
        assert 0 <= s["queue_s"] <= s["total_s"]
        assert 0 <= s["prefill_s"] <= s["total_s"]
        assert s["queue_s"] + s["prefill_s"] <= s["total_s"] + 1e-9
        assert s["queue_s"] <= s["ttft_s"] <= s["total_s"]
        assert s["new_tokens"] >= 0 and s["prompt_tokens"] > 0
    by_order = {s["order"]: s for s in spans}
    assert by_order[0]["new_tokens"] == 6
    assert by_order[4]["new_tokens"] == 1

    snap = [e for e in events if e["kind"] == "metrics.snapshot"][-1]["metrics"]
    # Admit-mid-flight actually happened: 5 requests through 2 slots.
    assert snap["serve_admissions_total"] == 5
    assert snap["serve_retirements_total"] == 5
    assert snap["serve_slots_total"] == 2
    assert snap["serve_generated_tokens_total"] == sum(
        s["new_tokens"] for s in spans
    )
    assert snap["serve_queue_seconds"]["count"] == 5
    assert snap["serve_request_seconds"]["p95"] > 0


def test_scheduler_spans_cover_chunked_prefill_tail(lm):
    """With --prefill_chunk the un-prefilled prompt tail walks token-by-token
    through the decode loop; the prefill span must close only once the LAST
    prompt token is in cache (incl. the 1-token-tail edge), and timings stay
    monotone. Answers remain byte-identical to the unchunked scheduler."""
    from transformer_tpu.train.decode import prefill_len_for

    _, cfg, tok = lm
    # Prompt lengths around the chunk boundary, so tails of 0 and >=1 tokens
    # (incl. the L == prefill_len + 1 edge) all occur.
    reqs = [
        {"prompt": "ab", "max_new": 2},
        {"prompt": "ab cd", "max_new": 2},
        {"prompt": "ab cd ef", "max_new": 2},
        {"prompt": "ab cd ef gh ij", "max_new": 2},
    ]
    plain = _scheduler(lm, None).run(reqs)
    buf = io.StringIO()
    tel = Telemetry(events=EventLog(buf), interval=0.0)
    chunked = _scheduler(lm, tel, prefill_chunk=2).run(reqs)
    assert plain == chunked
    spans = [
        json.loads(line) for line in buf.getvalue().splitlines()
        if json.loads(line)["kind"] == "serve.request"
    ]
    assert len(spans) == len(reqs)
    tail_fed = 0
    for s in spans:
        assert 0 <= s["prefill_s"] <= s["total_s"]
        assert s["queue_s"] + s["prefill_s"] <= s["total_s"] + 1e-9
        assert s["queue_s"] <= s["ttft_s"] <= s["total_s"]
        L = s["prompt_tokens"]
        if prefill_len_for(L, 2) < L:
            tail_fed += 1
            # Tail steps are real pool steps; a span that closed at dispatch
            # time could not cover them. Weak-but-real floor: the tail-fed
            # prefill span is strictly positive wall time.
            assert s["prefill_s"] > 0
    assert tail_fed >= 1, "no request exercised the chunked tail path"


def test_scheduler_error_isolation_records_error_span(lm):
    _, cfg, _ = lm
    reqs = [
        {"prompt": "ab cd", "max_new": 2},
        {"prompt": "ab " * cfg.max_position, "max_new": 2},  # over-length
        {"prompt": "ef", "max_new": 1},
    ]
    buf = io.StringIO()
    tel = Telemetry(events=EventLog(buf), interval=0.0)
    sched = _scheduler(lm, tel)
    out = sched.run(reqs)
    assert "continuation" in out[0] and "continuation" in out[2]
    assert "error" in out[1] and "max_position" in out[1]["error"]
    events = [json.loads(line) for line in buf.getvalue().splitlines()]
    errs = [e for e in events if e["kind"] == "serve.request" and "error" in e]
    assert len(errs) == 1 and errs[0]["order"] == 1
    assert errs[0]["queue_s"] >= 0
    snap = [e for e in events if e["kind"] == "metrics.snapshot"][-1]["metrics"]
    assert snap["serve_errors_total"] == 1
    assert snap["serve_admissions_total"] == 2  # the poisoned one never admits
    # Pre-answered (routing) errors also count and record a span.
    sched.submit_done({"error": "LM export serves 'prompt', not 'src'"})
    sched.drain_ready()
    tel.maybe_flush(force=True)
    events = [json.loads(line) for line in buf.getvalue().splitlines()]
    snap = [e for e in events if e["kind"] == "metrics.snapshot"][-1]["metrics"]
    assert snap["serve_errors_total"] == 2
    assert snap["serve_requests_total"] == 4


def test_scheduler_zero_recompiles_with_telemetry(lm):
    """Telemetry on the steady-state decode path must not cost a single
    recompile (the retrace-sentinel acceptance criterion, asserted directly
    on the instrumented scheduler)."""
    from transformer_tpu.analysis.retrace import RetraceSentinel
    from transformer_tpu.serve import scheduler as sched_mod

    tel = Telemetry(interval=0.0)
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


# --------------------------------------------------------------------------
# trainer instrumentation (tiny CPU run) + summarize report


def _tiny_train(tmp_path, jsonl):
    import jax
    import numpy as np

    from transformer_tpu.config import ModelConfig, TrainConfig
    from transformer_tpu.train import Trainer, create_train_state

    cfg = ModelConfig(
        num_layers=1, d_model=16, num_heads=2, dff=32,
        input_vocab_size=64, target_vocab_size=64, max_position=64,
        dropout_rate=0.0, dtype="float32", decoder_only=True,
    )
    tcfg = TrainConfig(
        batch_size=2, sequence_length=8, epochs=2, warmup_steps=10,
        log_every_steps=2, eval_every_steps=0,
    )

    class DS:
        def __len__(self):
            return 4

        def batches(self, epoch):
            r = np.random.default_rng(epoch)
            for _ in range(4):
                ids = r.integers(1, 64, size=(2, 8)).astype(np.int32)
                yield ids, ids

    tel = Telemetry(
        events=EventLog(jsonl), prom_path=jsonl + ".prom", interval=0.0
    )
    state = create_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    tr = Trainer(cfg, tcfg, state, telemetry=tel, log_fn=lambda s: None)
    tr.fit(DS(), DS())
    tel.close()
    return tr


def test_trainer_telemetry_and_grad_norm(tmp_path):
    jsonl = str(tmp_path / "train.jsonl")
    tr = _tiny_train(tmp_path, jsonl)
    windows = read_events(jsonl, kind="train.window")
    assert windows, "no train.window events recorded"
    assert sum(w["steps"] for w in windows) == 8  # 2 epochs x 4 steps
    for w in windows:
        assert w["tokens"] > 0 and w["window_s"] >= 0
        assert w["loss"] > 0 and 0 <= w["accuracy"] <= 1
        assert w["grad_norm"] > 0  # the new train-step metric, synced reads
    evals = read_events(jsonl, kind="train.eval")
    assert evals and evals[-1]["loss"] > 0
    compiles = read_events(jsonl, kind="train.compile")
    assert compiles and compiles[-1]["cache_sizes"]["train_step"] >= 1
    prom = open(jsonl + ".prom").read()
    assert "train_grad_norm" in prom and "train_tokens_total" in prom
    assert "train_step_seconds_count" in prom  # StepTimer-backed histogram
    assert tr.step_timer.histogram.count == 8
    # The telemetry-enabled trainer routes dispatches through timed_call —
    # the production path the telemetry_inert contract pins.
    assert tr.train_step.__wrapped__ is not None
    assert tr._m_dispatch.hist.count == 8
    assert "train_dispatch_seconds_count 8" in prom


def test_summarize_cli_on_real_run(tmp_path, capsys, lm):
    """Acceptance: summarize over a short CPU train run AND a serve session
    reports tokens/s, step p50/p95, slot utilization, latency breakdown."""
    from transformer_tpu.obs.__main__ import main as obs_main

    jsonl = str(tmp_path / "run.jsonl")
    _tiny_train(tmp_path, jsonl)
    tel = Telemetry(events=EventLog(jsonl), interval=0.0)
    _scheduler(lm, tel).run(
        [{"prompt": "ab cd", "max_new": 4}, {"prompt": "ef", "max_new": 2}]
    )
    tel.close()

    assert obs_main(["summarize", jsonl]) == 0
    text = capsys.readouterr().out
    assert "tokens/s" in text
    assert "step time: p50" in text and "p95" in text
    assert "slot utilization" in text
    assert "first token" in text and "queue" in text and "total" in text

    assert obs_main(["summarize", jsonl, "--format", "json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["train"]["tokens_per_sec"] is not None
    assert report["train"]["step_seconds"]["p95"] > 0
    assert report["serve"]["requests"] == 2
    assert report["serve"]["spans"]["total_s"]["count"] == 2
    assert "slot_utilization" in report["serve"]

    assert obs_main(["summarize", str(tmp_path / "missing.jsonl")]) == 2


def test_summarize_snapshot_only_serve_log():
    """A serve session killed before any request finished leaves only
    metrics.snapshot events — the report must render, not KeyError."""
    from transformer_tpu.obs.__main__ import render_text, summarize_events

    events = [{
        "ts": 1.0, "kind": "metrics.snapshot",
        "metrics": {"serve_slots_active": 1, "serve_slots_total": 2},
    }]
    report = summarize_events(events)
    text = render_text(report)
    assert "slot utilization" in text and "50.0%" in text


def test_summarize_tolerates_truncated_final_line(tmp_path, capsys):
    """A crash mid-write leaves the log's FINAL line torn — exactly the
    shape a fault-injected sink or an OOM-killed server produces. The
    summarize CLI must report the intact prefix, exit 0, and never raise;
    a snapshot whose metrics payload is not a dict is skipped the same
    way."""
    from transformer_tpu.obs.__main__ import main as obs_main

    jsonl = tmp_path / "crash.jsonl"
    jsonl.write_text(
        json.dumps({"ts": 1.0, "kind": "serve.request", "order": 0,
                    "new_tokens": 3, "total_s": 0.5}) + "\n"
        + json.dumps({"ts": 2.0, "kind": "metrics.snapshot",
                      "metrics": "not-a-dict"}) + "\n"
        + '{"ts": 3.0, "kind": "serve.request", "order": 1, "new_tok'
    )
    assert obs_main(["summarize", str(jsonl)]) == 0
    out = capsys.readouterr().out
    assert "2 events" in out          # torn line skipped, intact ones kept
    assert "1 requests" in out

    # byte-level truncation of a real log tail behaves the same
    real = tmp_path / "real.jsonl"
    real.write_text(
        json.dumps({"ts": 1.0, "kind": "serve.request", "order": 0,
                    "new_tokens": 2, "total_s": 0.25}) + "\n"
        + json.dumps({"ts": 2.0, "kind": "serve.request", "order": 1,
                      "new_tokens": 4, "total_s": 0.5}) + "\n"
    )
    real.write_bytes(real.read_bytes()[:-17])  # tear the final line
    assert obs_main(["summarize", str(real), "--format", "json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["events"] == 1 and report["serve"]["requests"] == 1


def test_summarize_breaker_degraded_time():
    """serve.breaker transitions -> per-breaker opens + degraded seconds
    (open/half-open time between open and the closing transition)."""
    from transformer_tpu.obs.__main__ import render_text, summarize_events

    events = [
        {"ts": 10.0, "kind": "serve.breaker", "name": "speculative",
         "state": "open", "previous": "closed"},
        {"ts": 12.5, "kind": "serve.breaker", "name": "speculative",
         "state": "half_open", "previous": "open"},
        {"ts": 13.0, "kind": "serve.breaker", "name": "speculative",
         "state": "closed", "previous": "half_open"},
        {"ts": 20.0, "kind": "serve.breaker", "name": "prefix_cache",
         "state": "open", "previous": "closed"},
        # never closes: degraded through end-of-log
        {"ts": 26.0, "kind": "metrics.snapshot", "metrics": {}},
    ]
    report = summarize_events(events)
    brk = report["serve"]["breakers"]
    assert brk["speculative"]["opens"] == 1
    assert brk["speculative"]["degraded_s"] == pytest.approx(3.0)
    assert brk["speculative"]["final_state"] == "closed"
    assert brk["prefix_cache"]["degraded_s"] == pytest.approx(6.0)
    assert brk["prefix_cache"]["final_state"] == "open"
    text = render_text(report)
    assert "breakers:" in text and "degraded" in text
    assert "[open]" in text  # still-degraded breakers are called out


def test_summarize_grouped_serve_batches():
    from transformer_tpu.obs.__main__ import render_text, summarize_events

    events = [
        {"ts": 1.0, "kind": "serve.batch", "size": 3, "errors": 1,
         "batch_s": 0.5},
        {"ts": 2.0, "kind": "serve.batch", "size": 2, "errors": 0,
         "batch_s": 0.25},
    ]
    report = summarize_events(events)
    g = report["serve_grouped"]
    assert g["batches"] == 2 and g["requests"] == 5 and g["errors"] == 1
    assert g["batch_s"]["count"] == 2
    text = render_text(report)
    assert "serve (grouped): 5 requests (1 errored) in 2 batches" in text


# --------------------------------------------------------------------------
# CLI flag plumbing smoke (absl flags are process-global -> subprocess)

_FLAGS_SNIPPET = """
import sys, os
from absl import flags
from transformer_tpu.cli.flags import define_flags, flags_to_telemetry
define_flags()
flags.FLAGS(sys.argv)
tel = flags_to_telemetry()
if tel is None:
    print("none")
else:
    tel.registry.counter("smoke_total").inc()
    tel.emit("smoke", ok=True)
    tel.close()
    print("jsonl" if tel.events else "nojsonl", tel.prom_path or "noprom",
          tel.interval)
"""


def _run_flags(*argv):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-c", _FLAGS_SNIPPET, *argv],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip()


def test_metrics_flags_default_off():
    assert _run_flags() == "none"


def test_metrics_flags_build_telemetry(tmp_path):
    jsonl = str(tmp_path / "m.jsonl")
    out = _run_flags(f"--metrics_jsonl={jsonl}", "--metrics_interval=2.5")
    assert out == f"jsonl {jsonl}.prom 2.5"
    events = read_events(jsonl)
    kinds = {e["kind"] for e in events}
    assert "smoke" in kinds and "metrics.snapshot" in kinds
    assert "smoke_total 1" in open(jsonl + ".prom").read()


def test_serve_cli_defines_metrics_flags():
    """cli.serve's separate flag surface carries the shared metrics flags
    (the serve CLI is where --metrics_port matters)."""
    snippet = """
import sys
from absl import flags
from transformer_tpu.cli.serve import define_serve_flags
define_serve_flags()
flags.FLAGS(sys.argv)
print(repr(flags.FLAGS.metrics_jsonl), flags.FLAGS.metrics_port,
      flags.FLAGS.metrics_interval)
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-c", snippet, "--metrics_port=9099"],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["''", "9099", "10.0"]


# --------------------------------------------------------------------------
# lint + contract coverage for the new package


def test_obs_package_lints_clean():
    """Satellite: all three analysis lint families over obs/ are clean
    WITHOUT baseline help (no new grandfathered findings; the package-wide
    tier-1 lint in test_analysis.py covers it against the checked-in
    baseline too). The trace/slo/merge modules ride the same bar."""
    from transformer_tpu.analysis import run_rules
    from transformer_tpu.analysis.concurrency import run_concurrency
    from transformer_tpu.analysis.sharding import run_sharding

    obs_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "transformer_tpu", "obs",
    )
    for run in (run_rules, run_concurrency, run_sharding):
        report = run(paths=[obs_dir])
        assert report.findings == [], (
            run.__name__ + ":\n"
            + "\n".join(str(f) for f in report.findings)
        )
        assert report.files_checked >= 9
    assert {"trace.py", "slo.py", "merge.py"} <= set(os.listdir(obs_dir))


def test_obs_package_is_jax_free():
    """The telemetry-inert guarantee starts at import structure: nothing
    under obs/ may import jax or numpy (quantiles/registry/events run in
    bench wrapper processes and the summarize CLI without a jax tax)."""
    import ast

    obs_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "transformer_tpu", "obs",
    )
    for fname in os.listdir(obs_dir):
        if not fname.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(obs_dir, fname)).read())
        for node in ast.walk(tree):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mods = [node.module or ""]
            for mod in mods:
                root = mod.split(".")[0]
                assert root not in ("jax", "jaxlib", "numpy"), (
                    f"{fname} imports {mod}"
                )


def test_telemetry_inert_contract_catches_a_leak():
    """The contract must FAIL (not vacuously pass) when a wrapper adds an
    operation to the traced computation."""
    import re

    import jax
    import jax.numpy as jnp

    def canon(j):
        return re.sub(r"0x[0-9a-f]+", "0x", str(j))

    def f(x):
        return x * 2

    leaky = lambda x: f(x) + 0.0  # noqa: E731 — the 'improved' wrapper
    good = timed_call(f, None, None)
    x = jax.ShapeDtypeStruct((2,), jnp.float32)
    assert canon(jax.make_jaxpr(f)(x)) == canon(jax.make_jaxpr(good)(x))
    assert canon(jax.make_jaxpr(f)(x)) != canon(jax.make_jaxpr(leaky)(x))
