"""Request-scoped distributed tracing (obs/trace.py), multi-source merge
(obs/merge.py), and the Perfetto exporter — the PR 9 tentpole.

Covers the acceptance criteria: byte-identical answers and zero
steady-state recompiles with tracing ON, span-tree completeness (every
opened span closes exactly once, parentage acyclic) including under the
fast chaos subset, Chrome trace-event schema round-trip, multi-source
merge with deliberately skewed clocks, and trace attribution on
retry/breaker/fault events.
"""

from __future__ import annotations

import io
import json
import os
import subprocess
import sys
import threading

import pytest

from transformer_tpu.obs import EventLog, Telemetry
from transformer_tpu.obs.merge import (
    estimate_skews,
    filter_events,
    merge_events,
    parse_duration,
)
from transformer_tpu.obs.trace import (
    SpanBuffer,
    SpanContext,
    Tracer,
    buffer,
    chrome_trace,
    default_tracer,
    span_tree,
    traced_call,
)

# --------------------------------------------------------------------------
# SpanContext / traceparent


def test_traceparent_round_trip():
    ctx = SpanContext.new()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    parsed = SpanContext.from_traceparent(ctx.to_traceparent())
    assert parsed.trace_id == ctx.trace_id
    assert parsed.span_id == ctx.span_id
    child = ctx.child()
    assert child.trace_id == ctx.trace_id and child.span_id != ctx.span_id


@pytest.mark.parametrize("bad", [
    None, 17, "", "not-a-header",
    "00-short-beef-01",
    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",      # all-zero trace id
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",      # all-zero span id
    "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",      # reserved version
    "00-" + "g" * 32 + "-" + "b" * 16 + "-01",      # non-hex
])
def test_traceparent_invalid_degrades_to_none(bad):
    assert SpanContext.from_traceparent(bad) is None


# --------------------------------------------------------------------------
# Tracer mechanics


def _buf_tracer():
    buf = io.StringIO()
    return Tracer(EventLog(buf).emit), buf


def _spans(buf) -> list:
    return [json.loads(line) for line in buf.getvalue().splitlines()]


def test_span_stack_parenting_and_emission():
    tracer, buf = _buf_tracer()
    with tracer.span("outer", lane="train") as outer:
        with tracer.span("inner") as inner:
            assert inner.ctx.trace_id == outer.ctx.trace_id
            assert inner.parent_id == outer.ctx.span_id
    assert tracer.open_count == 0
    events = _spans(buf)
    # inner closes first (emit-on-close), both land with lineage intact.
    assert [e["name"] for e in events] == ["inner", "outer"]
    assert events[0]["parent"] == events[1]["span"]
    assert events[1].get("parent") is None
    assert events[0]["dur_s"] >= 0 and events[0]["t0"] <= events[0]["ts"]
    assert events[1]["lane"] == "train"


def test_span_explicit_parent_beats_stack_and_threads_are_isolated():
    tracer, buf = _buf_tracer()
    root = tracer.start_span("request")
    seen = {}

    def worker():
        # A fresh thread has no current span: a new root starts there.
        with tracer.span("other-thread") as sp:
            seen["ctx"] = sp.ctx
    with tracer.span("step"):
        child = tracer.start_span("explicit", parent=root)
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        child.end()
    root.end()
    assert child.parent_id == root.ctx.span_id        # not the step span
    assert seen["ctx"].trace_id != root.ctx.trace_id  # thread-local stack
    assert tracer.open_count == 0


def test_span_double_end_is_counted_not_fatal():
    tracer, buf = _buf_tracer()
    sp = tracer.start_span("once")
    sp.end()
    sp.end()
    assert tracer.stats["ended"] == 1
    assert tracer.stats["double_end"] == 1
    assert len(_spans(buf)) == 1


def test_span_reserved_attrs_dropped_and_exception_recorded():
    tracer, buf = _buf_tracer()
    with pytest.raises(RuntimeError):
        with tracer.span("boom", trace="shadow!"):
            raise RuntimeError("x")
    ev = _spans(buf)[0]
    assert ev["error"] == "RuntimeError"
    assert len(ev["trace"]) == 32          # the real id, not "shadow!"
    assert tracer.stats["dropped_attrs"] == 1
    assert tracer.open_count == 0


def test_traced_call_wraps_and_records():
    tracer, buf = _buf_tracer()
    calls = []

    def fn(x):
        calls.append(x)
        return x + 1

    wrapped = traced_call(fn, tracer, "unit.call", lane="train")
    assert wrapped.__wrapped__ is fn
    with tracer.span("parent") as parent:
        assert wrapped(41) == 42
    events = _spans(buf)
    assert events[0]["name"] == "unit.call"
    assert events[0]["parent"] == parent.ctx.span_id  # stack parenting
    assert events[0]["lane"] == "train"


# --------------------------------------------------------------------------
# Chrome trace-event export


def test_buffer_is_bounded_and_counts_drops():
    buf = SpanBuffer(capacity=4)
    for i in range(7):
        buf.append({"name": f"s{i}"})
    assert len(buf) == 4 and buf.capacity == 4 and buf.dropped == 3
    assert [s["name"] for s in buf.snapshot()] == ["s3", "s4", "s5", "s6"]
    buf.clear()
    assert len(buf) == 0 and buf.dropped == 0
    # The process-wide one is the same class at its documented size.
    assert isinstance(buffer(), SpanBuffer) and buffer().capacity == 65536


def test_tracer_without_emit_buffers_and_emits_nothing():
    buffer().clear()
    tracer = Tracer()  # emit=None: buffer only
    with tracer.span("outer", lane="scheduler", n=1) as outer:
        tracer.start_span("inner").end(k=2)
    assert tracer.open_count == 0 and tracer.stats["ended"] == 2
    inner, got_outer = buffer().snapshot()
    assert (inner["name"], got_outer["name"]) == ("inner", "outer")
    # Every field of the trace.span event, plus t0_mono.
    assert got_outer["kind"] == "trace.span" and got_outer["lane"] == "scheduler"
    assert got_outer["n"] == 1 and inner["k"] == 2
    assert inner["parent"] == got_outer["span"] == outer.ctx.span_id
    assert {"ts", "t0", "dur_s", "trace", "t0_mono"} <= set(got_outer)
    assert default_tracer() is default_tracer()
    assert default_tracer()._emit is None


def test_t0_mono_is_perf_counter_at_start():
    import time

    buffer().clear()
    tracer = Tracer()
    before = time.perf_counter()
    with tracer.span("a"):
        mid = time.perf_counter()
        with tracer.span("b"):
            pass
    after = time.perf_counter()
    b, a = buffer().snapshot()
    assert before <= a["t0_mono"] <= mid <= b["t0_mono"] <= after
    assert a["t0_mono"] + a["dur_s"] <= after + 1e-6
    # An attribute may not shadow it, like every reserved field.
    tracer.start_span("c").end(t0_mono=0.0)
    assert buffer().snapshot()[-1]["t0_mono"] >= after
    assert tracer.stats["dropped_attrs"] == 1


def test_annotate_mirrors_span_contexts_only():
    """The profiler mirror: the factory is entered and left once per
    ``tracer.span`` (also when the body raises), never for ``start_span``."""
    log = []

    class Mirror:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    tracer = Tracer(annotate=Mirror)
    with tracer.span("outer"):
        with tracer.span("inner"):
            pass
        tracer.start_span("long.lived").end()
    assert log == [("enter", "outer"), ("enter", "inner"), ("exit", "inner"),
                   ("exit", "outer")]
    del log[:]
    with pytest.raises(KeyError):
        with tracer.span("boom"):
            raise KeyError("x")
    assert log == [("enter", "boom"), ("exit", "boom")]
    assert tracer.open_count == 0


def test_telemetry_always_traces_but_logs_spans_only_on_request():
    buffer().clear()
    sink = io.StringIO()
    tel = Telemetry(events=EventLog(sink), interval=0.0)  # trace=False
    with tel.tracer.span("quiet"):
        pass
    tel.emit("other.event", x=1)
    tel.events.flush()
    kinds = [json.loads(line)["kind"] for line in sink.getvalue().splitlines()]
    assert "other.event" in kinds and "trace.span" not in kinds
    assert [s["name"] for s in buffer().snapshot()] == ["quiet"]
    assert tel.health()["spans"]["buffered"] == 1
    loud = io.StringIO()
    tel2 = Telemetry(events=EventLog(loud), interval=0.0, trace=True)
    with tel2.tracer.span("loud"):
        pass
    tel2.events.flush()
    events = [json.loads(line) for line in loud.getvalue().splitlines()]
    (span,) = [e for e in events if e["kind"] == "trace.span"]
    # The JSONL schema is what it was: the buffer's extra field stays out.
    assert span["name"] == "loud" and "t0_mono" not in span
    assert [s["name"] for s in buffer().snapshot()] == ["quiet", "loud"]


def test_chrome_trace_schema_and_lanes():
    tracer, buf = _buf_tracer()
    with tracer.span("scheduler.step", lane="scheduler"):
        pass
    with tracer.span("serve.decode", lane="slot3"):
        pass
    doc = chrome_trace(_spans(buf))
    # Round-trips through JSON untouched (the on-disk format).
    doc = json.loads(json.dumps(doc))
    assert doc["displayTimeUnit"] == "ms"
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 2
    for e in xs:
        assert set(e) >= {"name", "cat", "pid", "tid", "ts", "dur", "args"}
        assert e["ts"] >= 0 and e["dur"] >= 0
    lanes = {
        e["args"]["name"] for e in metas if e["name"] == "thread_name"
    }
    assert lanes == {"scheduler", "slot3"}
    by_lane = {e["args"]["name"]: e["tid"] for e in metas
               if e["name"] == "thread_name"}
    assert by_lane["slot3"] == 13  # slotN -> tid 10+N, stable across runs
    assert doc["otherData"]["spans"] == 2


def test_chrome_trace_ignores_non_span_events():
    doc = chrome_trace([
        {"kind": "serve.request", "order": 1},
        {"kind": "trace.span"},  # malformed: no t0/dur
    ])
    assert doc["traceEvents"] == [] and doc["otherData"]["spans"] == 0


# --------------------------------------------------------------------------
# multi-source merge + clock alignment


def _mk_log(path, events):
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def test_merge_estimates_deliberate_skew(tmp_path):
    """File B's clock runs 123.4s ahead; its spans are children of file A's
    spans via propagated trace context — the merge must recover the skew
    and produce one coherent timeline."""
    skew = 123.4
    t = 1_700_000_000.0
    a_events, b_events = [], []
    for i in range(5):
        trace = f"{i:032x}"
        parent = f"a{i:015x}"
        child = f"b{i:015x}"
        t0 = t + 10 * i
        a_events.append({
            "ts": t0 + 2.0, "kind": "trace.span", "trace": trace,
            "span": parent, "name": "router.request", "lane": "intake",
            "t0": t0, "dur_s": 2.0,
        })
        # True child interval [t0+0.5, t0+1.5], recorded on B's fast clock.
        b_events.append({
            "ts": t0 + 1.5 + skew, "kind": "trace.span", "trace": trace,
            "span": child, "parent": parent, "name": "serve.request",
            "lane": "slot0", "t0": t0 + 0.5 + skew, "dur_s": 1.0,
        })
    b_events.append({"ts": t + 100 + skew, "kind": "serve.request",
                     "order": 0, "total_s": 1.0})
    _mk_log(tmp_path / "router.jsonl", a_events)
    _mk_log(tmp_path / "replica.jsonl", b_events)
    merged, info = merge_events(
        [str(tmp_path / "router.jsonl"), str(tmp_path / "replica.jsonl")]
    )
    assert info["sources"]["router.jsonl"]["skew_s"] == 0.0
    assert abs(info["sources"]["replica.jsonl"]["skew_s"] - skew) < 1e-6
    # After alignment every child nests inside its parent on ONE timeline.
    trees = span_tree(merged)
    checked = 0
    for byid in trees.values():
        for e in byid.values():
            p = e.get("parent")
            if p and p in byid:
                par = byid[p]
                assert par["t0"] <= e["t0"]
                assert e["t0"] + e["dur_s"] <= par["t0"] + par["dur_s"] + 1e-6
                checked += 1
    assert checked == 5
    # Non-span events from the skewed file shifted too, and stay tagged.
    req = [e for e in merged if e["kind"] == "serve.request"][0]
    assert req["source"] == "replica.jsonl"
    assert abs(req["ts"] - (t + 100)) < 1e-6
    # Merged stream is time-sorted.
    ts = [e["ts"] for e in merged]
    assert ts == sorted(ts)


def test_merge_without_cross_links_keeps_clocks(tmp_path):
    _mk_log(tmp_path / "a.jsonl", [{"ts": 10.0, "kind": "x"}])
    _mk_log(tmp_path / "b.jsonl", [{"ts": 99.0, "kind": "y"}])
    merged, info = merge_events(
        [str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")]
    )
    assert [s["skew_s"] for s in info["sources"].values()] == [0.0, 0.0]
    assert [e["ts"] for e in merged] == [10.0, 99.0]


def test_merge_disambiguates_duplicate_basenames(tmp_path):
    (tmp_path / "r0").mkdir()
    (tmp_path / "r1").mkdir()
    _mk_log(tmp_path / "r0" / "m.jsonl", [{"ts": 1.0, "kind": "x"}])
    _mk_log(tmp_path / "r1" / "m.jsonl", [{"ts": 2.0, "kind": "x"}])
    _, info = merge_events(
        [str(tmp_path / "r0" / "m.jsonl"), str(tmp_path / "r1" / "m.jsonl")]
    )
    assert set(info["sources"]) == {"r0/m.jsonl", "r1/m.jsonl"}


def test_estimate_skews_chains_through_islands():
    # file1 linked to file0, file2 linked to file1 only: offsets chain.
    def span(sid, parent, t0, dur):
        return {"kind": "trace.span", "trace": "t" * 32, "span": sid,
                "parent": parent, "t0": t0, "dur_s": dur, "ts": t0 + dur}

    f0 = [span("a" * 16, None, 100.0, 4.0)]
    f1 = [span("b" * 16, "a" * 16, 111.0, 2.0),   # +10 skew vs f0
          span("c" * 16, None, 120.0, 4.0)]
    f2 = [span("d" * 16, "c" * 16, 126.0, 2.0)]   # +5 skew vs f1
    skews = estimate_skews([f0, f1, f2])
    assert skews[0] == 0.0
    assert abs(skews[1] - 10.0) < 1e-6
    assert abs(skews[2] - 15.0) < 1e-6


# --------------------------------------------------------------------------
# time-window filtering


def test_parse_duration_units_and_errors():
    assert parse_duration("90s") == 90.0
    assert parse_duration("5m") == 300.0
    assert parse_duration("2h") == 7200.0
    assert parse_duration("45") == 45.0
    for bad in ("", "abc", "-5s"):
        with pytest.raises(ValueError):
            parse_duration(bad)


def test_filter_events_since_and_last():
    events = [{"ts": float(t), "kind": "x"} for t in (10, 20, 30, 40)]
    events.append({"kind": "no-ts"})
    assert [e["ts"] for e in filter_events(events, since=25)] == [30.0, 40.0]
    # --last measures back from the NEWEST event, not the wall clock.
    assert [e["ts"] for e in filter_events(events, last=15)] == [30.0, 40.0]
    assert [e["ts"] for e in filter_events(events, since=35, last=30)] == [40.0]
    assert filter_events(events) == events  # no filters: untouched, ts-less kept


# --------------------------------------------------------------------------
# the traced scheduler (CPU tiny model)


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


def _scheduler(lm, telemetry, **kw):
    from transformer_tpu.serve import ContinuousScheduler

    params, cfg, tok = lm
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_total", 32)
    kw.setdefault("default_max_new", 4)
    return ContinuousScheduler(params, cfg, tok, telemetry=telemetry, **kw)


def _traced_run(lm, reqs, **kw):
    buf = io.StringIO()
    tel = Telemetry(events=EventLog(buf), interval=0.0, trace=True)
    out = _scheduler(lm, tel, **kw).run(reqs)
    events = [json.loads(line) for line in buf.getvalue().splitlines()]
    return out, events, tel.tracer


def _assert_tree_complete(events, tracer):
    """The acceptance bar: every opened span closed exactly once, every
    parent reference resolves inside its trace, parentage is acyclic."""
    assert tracer.open_count == 0, tracer.open_spans()
    assert tracer.stats["double_end"] == 0
    assert tracer.stats["started"] == tracer.stats["ended"]
    trees = span_tree(events)
    for trace, byid in trees.items():
        for sid, e in byid.items():
            seen = {sid}
            cur = e.get("parent")
            while cur is not None:
                assert cur in byid, (
                    f"span {e['name']} in trace {trace} has dangling "
                    f"parent {cur}"
                )
                assert cur not in seen, f"parent cycle in trace {trace}"
                seen.add(cur)
                cur = byid[cur].get("parent")
    return trees


def test_traced_scheduler_byte_identity_and_complete_trees(lm):
    reqs = [
        {"prompt": "ab cd ef gh ij", "max_new": 6},
        {"prompt": "kl", "max_new": 2},
        {"prompt": "ab cd", "max_new": 8, "temperature": 0.9, "seed": 3},
        {"prompt": "mn ef", "max_new": 3},
        {"prompt": "gh", "max_new": 1},
    ]
    buffer().clear()
    plain = _scheduler(lm, None).run(reqs)
    # Spans are recorded whatever the telemetry: without a bundle...
    by_default = buffer().snapshot()
    assert {"scheduler.step", "serve.request", "step.fetch"} <= {
        s["name"] for s in by_default
    }
    # ...and with one that does not log them, answers byte-identical.
    quiet = io.StringIO()
    tel = Telemetry(events=EventLog(quiet), interval=0.0)
    assert _scheduler(lm, tel).run(reqs) == plain
    assert '"trace.span"' not in quiet.getvalue()
    assert len(buffer()) == 2 * len(by_default)
    traced, events, tracer = _traced_run(lm, reqs)
    assert plain == traced  # tracing must be invisible in the answers
    trees = _assert_tree_complete(events, tracer)
    # One complete request tree per request: root + queue/admit/prefill/
    # decode children.
    roots = [
        e for e in events
        if e.get("kind") == "trace.span" and e["name"] == "serve.request"
    ]
    assert len(roots) == len(reqs)
    for root in roots:
        byid = trees[root["trace"]]
        names = {e["name"] for e in byid.values()}
        assert names >= {
            "serve.request", "serve.queue", "serve.admit",
            "serve.prefill", "serve.decode",
        }, names
        assert root["lane"].startswith("slot")
        # Lifecycle children all hang off this request's tree (acyclic is
        # already checked; here: single root).
        parentless = [e for e in byid.values() if "parent" not in e]
        assert len(parentless) == 1
    # serve.request span events carry the same trace ids the span tree has.
    req_events = [e for e in events if e.get("kind") == "serve.request"]
    assert len(req_events) == len(reqs)
    assert {e["trace"] for e in req_events} == {r["trace"] for r in roots}
    # Step spans render on the scheduler lane.
    steps = [
        e for e in events
        if e.get("kind") == "trace.span" and e["name"] == "scheduler.step"
    ]
    assert steps and all(e["lane"] == "scheduler" for e in steps)


def test_traceparent_propagates_from_request(lm):
    incoming = SpanContext.new()
    reqs = [
        {"prompt": "ab cd", "max_new": 2,
         "traceparent": incoming.to_traceparent()},
        {"prompt": "ef", "max_new": 2, "traceparent": "garbage-header"},
    ]
    out, events, tracer = _traced_run(lm, reqs)
    assert all("continuation" in r for r in out)
    roots = [
        e for e in events
        if e.get("kind") == "trace.span" and e["name"] == "serve.request"
    ]
    adopted = [r for r in roots if r["trace"] == incoming.trace_id]
    assert len(adopted) == 1
    # The router's span is the root's parent (it lives in the ROUTER's log;
    # here it dangles locally — exactly what the multi-source merge joins).
    assert adopted[0]["parent"] == incoming.span_id
    # The malformed header degrades to a fresh trace, not an error.
    fresh = [r for r in roots if r["trace"] != incoming.trace_id]
    assert len(fresh) == 1 and "parent" not in fresh[0]


def test_traced_speculative_and_prefix_paths(lm):
    from transformer_tpu.serve import PrefixCache

    params, cfg, tok = lm
    reqs = [
        {"prompt": "ab cd ef gh", "max_new": 6},
        {"prompt": "ab cd ef gh", "max_new": 6},   # prefix re-use
        {"prompt": "kl mn", "max_new": 4},
    ]
    # One slot: the repeated prompt admits only after its twin RETIRED (and
    # fed the trie), so the prefix-restore path actually runs.
    kw = dict(speculate_k=2, prefill_chunk=2, num_slots=1)
    plain = _scheduler(
        lm, None, prefix_cache=PrefixCache(cfg, block_tokens=4), **kw
    ).run(reqs)
    traced, events, tracer = _traced_run(
        lm, reqs, prefix_cache=PrefixCache(cfg, block_tokens=4), **kw
    )
    assert plain == traced
    _assert_tree_complete(events, tracer)
    names = {e["name"] for e in events if e.get("kind") == "trace.span"}
    assert names >= {
        "spec.draft", "spec.verify", "spec.rollback",
        "prefix.match", "prefix.insert",
    }, names
    # The repeated prompt restored blocks: its tree carries the restore.
    assert "prefix.restore" in names


def test_chaos_subset_trees_complete_and_attributed(lm, tmp_path):
    """The fast chaos bar (the ISSUE's acceptance episode): injected
    admission+prefix faults over a speculative + prefix-cache scheduler,
    a queued deadline expiry, and a client cancel — every span still
    closes, every request answers exactly once, retry/breaker/fault
    events carry the victim's trace id, and the log exports to a Perfetto
    trace whose admitted requests are complete span trees."""
    from transformer_tpu.serve import PrefixCache, resilience

    params, cfg, tok = lm
    reqs = [
        {"prompt": "ab cd ef", "max_new": 3},
        {"prompt": "ab cd ef", "max_new": 3},
        {"prompt": "kl", "max_new": 2},
        {"prompt": "mn ef", "max_new": 2},
        {"prompt": "gh ij", "max_new": 2},
        {"prompt": "ab kl", "max_new": 0, "deadline_ms": 0},  # expires queued
    ]
    buf = io.StringIO()
    tel = Telemetry(events=EventLog(buf), interval=0.0, trace=True)
    sched = _scheduler(
        lm, tel,
        prefix_cache=PrefixCache(cfg, block_tokens=4),
        speculate_k=2,
        admission_retries=1, retry_backoff_ms=0.1,
        breaker_threshold=1, breaker_cooldown_s=1000.0,
    )
    plane = resilience.FaultPlane.parse(
        "serve.prefill:p=0.5,seed=11;prefix.match:at=1"
    )
    with resilience.active(plane):
        for r in reqs:
            sched.submit(r)
        cancel_order = sched.submit({"prompt": "ef gh", "max_new": 2})
        assert sched.cancel(cancel_order)
        out = []
        for _ in range(500):
            sched.admit()
            sched.step()
            sched.idle_backoff()
            out.extend(sched.drain_ready())
            if not sched.busy and len(out) == len(reqs) + 1:
                break
    assert len(out) == len(reqs) + 1       # every request answered once
    assert plane.episodes >= 1             # the drill actually fired
    events = [json.loads(line) for line in buf.getvalue().splitlines()]
    _assert_tree_complete(events, tel.tracer)
    req_events = [e for e in events if e.get("kind") == "serve.request"]
    assert len(req_events) == len(reqs) + 1
    by_code = {}
    for e in req_events:
        assert "trace" in e                # injected-fault answers included
        by_code.setdefault(e.get("code"), []).append(e)
    assert by_code.get("deadline"), "queued deadline expiry missing"
    assert by_code.get("cancelled"), "client cancel missing"
    # Retries carry the victim's trace id and a real backoff.
    retries = [e for e in events if e.get("kind") == "serve.retry"]
    if plane.fired.get("serve.prefill", 0):
        assert retries, "prefill faults fired but no serve.retry recorded"
    root_traces = {
        e["trace"] for e in events
        if e.get("kind") == "trace.span" and e["name"] == "serve.request"
    }
    for e in retries:
        assert e["trace"] in root_traces and e["backoff_ms"] >= 0
    # The prefix.match fault (threshold 1) opened the breaker, attributed.
    breakers = [e for e in events if e.get("kind") == "serve.breaker"]
    opened = [e for e in breakers if e["state"] == "open"]
    assert opened and all(e["trace"] in root_traces for e in opened)
    # The speculative path ran under the storm (verify spans present).
    span_names = {
        e["name"] for e in events if e.get("kind") == "trace.span"
    }
    assert "spec.verify" in span_names
    # No slot/pin leaks under the storm.
    assert len(sched._free) == sched.num_slots
    assert sched.prefix_cache.outstanding_refs() == 0
    # And the whole episode exports as a loadable Perfetto document whose
    # admitted requests are complete trees (root + lifecycle children).
    doc = json.loads(json.dumps(chrome_trace(events)))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    # Admitted requests render their root on a slot lane (tid 10+N);
    # never-admitted ones (queued expiry, backpressure) stay on intake.
    admitted = {
        e["args"]["trace"] for e in xs
        if e["name"] == "serve.request" and e["tid"] >= 10
    }
    by_trace = {}
    for e in xs:
        if "trace" in e["args"]:
            by_trace.setdefault(e["args"]["trace"], set()).add(e["name"])
    for trace in admitted:
        if "serve.prefill" in by_trace[trace]:  # reached a slot
            assert {"serve.request", "serve.queue", "serve.admit"} <= by_trace[trace]


@pytest.mark.parametrize("trace", [True, False], ids=["logged", "buffer_only"])
def test_traced_scheduler_zero_recompiles(lm, trace):
    """Tracing on the steady-state decode path costs zero recompiles —
    the retrace-sentinel acceptance criterion with spans enabled, written
    to the event log or kept in the buffer alone."""
    from transformer_tpu.analysis.retrace import RetraceSentinel
    from transformer_tpu.serve import scheduler as sched_mod

    tel = Telemetry(interval=0.0, trace=trace)
    warm = _scheduler(lm, tel)
    warm.run([{"prompt": "ab cd", "max_new": 3}])
    sentinel = RetraceSentinel()
    sentinel.watch("_pool_step", sched_mod._pool_step, budget=0)
    sentinel.watch("_slot_prefill", sched_mod._slot_prefill, budget=0)
    sentinel.watch("_pick_pool", sched_mod._pick_pool, budget=0)
    sentinel.snapshot()
    for _ in range(3):
        tel2 = Telemetry(interval=0.0, trace=trace)
        s = _scheduler(lm, tel2)
        out = s.run([{"prompt": "ab cd", "max_new": 3}])
        assert "continuation" in out[0]
        assert tel2.tracer.open_count == 0
    sentinel.assert_within_budget()


# --------------------------------------------------------------------------
# the traced trainer (tiny CPU run)


def test_traced_trainer_step_and_checkpoint_spans(tmp_path):
    import jax
    import numpy as np

    from transformer_tpu.config import ModelConfig, TrainConfig
    from transformer_tpu.train import Trainer, create_train_state
    from transformer_tpu.train.checkpoint import CheckpointManager

    cfg = ModelConfig(
        num_layers=1, d_model=16, num_heads=2, dff=32,
        input_vocab_size=64, target_vocab_size=64, max_position=64,
        dropout_rate=0.0, dtype="float32", decoder_only=True,
    )
    tcfg = TrainConfig(
        batch_size=2, sequence_length=8, epochs=2, warmup_steps=10,
        log_every_steps=2, eval_every_steps=0,
        ckpt_path=str(tmp_path / "ckpt"),
    )

    class DS:
        def __len__(self):
            return 4

        def batches(self, epoch):
            r = np.random.default_rng(epoch)
            for _ in range(4):
                ids = r.integers(1, 64, size=(2, 8)).astype(np.int32)
                yield ids, ids

    jsonl = str(tmp_path / "train.jsonl")
    tel = Telemetry(events=EventLog(jsonl), interval=0.0, trace=True)
    state = create_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    tr = Trainer(
        cfg, tcfg, state, telemetry=tel, log_fn=lambda s: None,
        checkpoint=CheckpointManager(tcfg.ckpt_path, max_to_keep=2),
    )
    tr.fit(DS(), DS())
    tel.close()
    assert tel.tracer.open_count == 0, tel.tracer.open_spans()
    with open(jsonl) as f:
        events = [json.loads(line) for line in f]
    spans = [e for e in events if e["kind"] == "trace.span"]
    _assert_tree_complete(events, tel.tracer)
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert len(by_name["train.fit"]) == 1
    fit = by_name["train.fit"][0]
    assert "parent" not in fit and fit["lane"] == "train"
    # One train.step span per dispatch (2 epochs x 4 steps), all under fit.
    assert len(by_name["train.step"]) == 8
    assert {e["parent"] for e in by_name["train.step"]} == {fit["span"]}
    assert {e["trace"] for e in spans} == {fit["trace"]}  # ONE tree
    # One train.data_wait per batch handed over, and one per epoch for the
    # next() that found it exhausted; all under fit, mirrored in the buffer.
    waits = by_name["train.data_wait"]
    assert len([w for w in waits if not w.get("end")]) == 8
    assert len([w for w in waits if w.get("end")]) == 2
    assert {e["parent"] for e in waits} == {fit["span"]}
    buffered = [s for s in buffer().snapshot() if s["trace"] == fit["trace"]]
    assert len(buffered) == len(spans)
    assert all("t0_mono" in s for s in buffered)
    # Eval + checkpoint spans nest under the fit span too.
    assert by_name["train.eval"]
    assert by_name["ckpt.save"] and by_name["ckpt.restore"]
    assert by_name["ckpt.save"][0]["parent"] == fit["span"]
    # chrome export puts the whole run on the train lane.
    doc = chrome_trace(events)
    lanes = {
        e["args"]["name"] for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert lanes == {"train"}


def test_trainer_without_telemetry_buffers_its_spans():
    """``Trainer(telemetry=None)`` (what a benchmark builds) records into
    the process's default tracer: one ``train.data_wait`` a batch."""
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
        batch_size=2, sequence_length=8, epochs=1, warmup_steps=10,
        log_every_steps=0, eval_every_steps=0,
    )

    class DS:
        def batches(self, epoch):
            r = np.random.default_rng(epoch)
            for _ in range(3):
                ids = r.integers(1, 64, size=(2, 8)).astype(np.int32)
                yield ids, ids

    buffer().clear()
    state = create_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    Trainer(cfg, tcfg, state, log_fn=lambda s: None).fit(DS())
    names = [s["name"] for s in buffer().snapshot()]
    assert names.count("train.data_wait") == 4  # 3 batches + the exhausted next()
    assert names.count("train.step") == 3 and names.count("train.fit") == 1
    assert all(s["lane"] == "train" for s in buffer().snapshot())


# --------------------------------------------------------------------------
# CLI round trip on a real traced run


def test_trace_cli_exports_loadable_perfetto_json(lm, tmp_path, capsys):
    from transformer_tpu.obs.__main__ import main

    jsonl = str(tmp_path / "serve.jsonl")
    tel = Telemetry(events=EventLog(jsonl), interval=0.0, trace=True)
    _scheduler(lm, tel).run([
        {"prompt": "ab cd ef", "max_new": 3},
        {"prompt": "kl", "max_new": 2},
    ])
    tel.close()
    out = str(tmp_path / "trace.json")
    assert main(["trace", jsonl, "--out", out]) == 0
    doc = json.load(open(out))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert doc["otherData"]["spans"] == len(xs) and xs
    lanes = {
        e["args"]["name"] for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert "scheduler" in lanes and any(l.startswith("slot") for l in lanes)
    assert "intake" in lanes
    # Request spans nest inside their trace: args keep lineage for the UI.
    roots = [e for e in xs if e["name"] == "serve.request"]
    assert roots and all("trace" in e["args"] for e in roots)
    # summarize over the SAME log still renders (spans don't break it) and
    # reports the span volume.
    assert main(["summarize", jsonl]) == 0
    text = capsys.readouterr().out
    assert "tracing:" in text


def test_summarize_merge_two_live_logs(lm, tmp_path, capsys):
    """Acceptance: `obs summarize --merge` over two concurrently-written
    JSONL files produces one coherent report."""
    from transformer_tpu.obs.__main__ import main

    paths = []
    for i in range(2):
        jsonl = str(tmp_path / f"replica{i}.jsonl")
        tel = Telemetry(events=EventLog(jsonl), interval=0.0, trace=True)
        _scheduler(lm, tel).run([
            {"prompt": "ab cd", "max_new": 2},
            {"prompt": "ef gh", "max_new": 2},
        ])
        tel.close()
        paths.append(jsonl)
    assert main(["summarize", *paths, "--merge", "--format", "json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["serve"]["requests"] == 4        # aggregated across files
    assert set(report["sources"]) == {"replica0.jsonl", "replica1.jsonl"}
    # --last slices the merged timeline without external tooling.
    assert main(["summarize", *paths, "--last", "1h"]) == 0
    assert main(["slo", *paths]) == 0