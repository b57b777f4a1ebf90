"""Request-scoped distributed tracing: hierarchical spans over the event log.

The metrics registry answers "how is the fleet doing"; this module answers
"where did THIS request's latency go". A :class:`Tracer` mints spans with
``trace_id`` / ``span_id`` / ``parent_id`` lineage and emits one
``trace.span`` event per CLOSED span onto the existing JSONL
:class:`~transformer_tpu.obs.events.EventLog` — no second sink, no second
file format, and ``obs summarize`` keeps working on a traced log unchanged.
:func:`chrome_trace` converts any such log into the Chrome trace-event JSON
that chrome://tracing and Perfetto load (``python -m transformer_tpu.obs
trace <jsonl> --out trace.json``), one lane per serve slot plus
scheduler/intake/train lanes.

Design rules (the same ones the rest of obs lives by):

- **Stdlib-only, jax-free.** Spans are host wall-clock bookkeeping; nothing
  here may touch device values. The ``telemetry_inert`` contract
  (``analysis/contracts.py``) pins that a :func:`traced_call`-wrapped jitted
  function traces to a byte-identical jaxpr, and tests pin byte-identical
  serve answers and 0 steady-state recompiles with tracing enabled.
- **Emit on close.** One event per span, written when the span ends (with
  its start time ``t0`` and duration ``dur_s``), so the log stays
  append-only and a crash loses only the spans still open — the exporter
  and the span-tree tests treat an unclosed span as a defect, and
  ``Tracer.open_count`` makes "every opened span closes exactly once"
  directly assertable.
- **Context crosses processes.** :class:`SpanContext` serializes to the
  W3C ``traceparent`` form (``00-<trace>-<span>-01``); a request dict may
  carry ``"traceparent"`` and the scheduler adopts it as the root parent,
  so the future multi-replica router tier propagates trace lineage for
  free and a cross-file merge (``obs/merge.py``) can re-join one request's
  spans across replica logs — and estimate per-file clock skew from them.

Parenting: ``tracer.span(...)`` (the context-manager form) keeps a
per-thread current-span stack, so nested ``with`` blocks — and any
:func:`traced_call`-wrapped function invoked inside them — parent
automatically. Long-lived spans that outlive a call frame (a serve
request's lifecycle across many scheduler steps) use ``start_span`` /
``Span.end`` with an explicit ``parent=`` instead; they never sit on the
stack.

Thread-safety: spans may start on one thread (client ``submit``) and end
on another (the scheduler loop); the tracer's open-span accounting is
locked, and emission goes through the multi-writer-safe EventLog.

Three sinks, no switch. Every closed span goes to (1) the process-wide
:func:`buffer` — bounded, in memory, always — with the fields of its
``trace.span`` event plus ``t0_mono``, the ``time.perf_counter()`` reading
at its start (the clock benchmarks and step timers use); (2) the event log,
when the tracer was given an ``emit`` (``Telemetry(trace=True)``,
``--trace``); (3) the profiler's own trace, for spans opened with the
context-manager form, when the tracer was given an ``annotate`` factory
(``name -> context manager``; the scheduler and the trainer pass
``jax.profiler.TraceAnnotation`` through ``utils/profiling.annotate``, so
this module still imports no jax): the span is then also a host event of the
same name on the device's clock, at the cost of a flag test while no
profiler session runs. Long-lived ``start_span`` spans are not mirrored.
"""

from __future__ import annotations

import collections
import os
import random
import threading
import time

#: Reserved field names in a ``trace.span`` event — span attributes may not
#: shadow them (``Span.end`` silently drops offenders rather than corrupt
#: the schema; the exporter and merge tooling key on these).
RESERVED_SPAN_FIELDS = frozenset(
    {"ts", "kind", "trace", "span", "parent", "name", "lane", "t0", "dur_s",
     "t0_mono"}
)

#: Closed spans the process keeps (oldest dropped first). A serving replica
#: closes some 200 spans a second, so this is the last five minutes.
BUFFER_CAPACITY = 65536


class SpanBuffer:
    """A bounded, thread-safe ring of closed spans (dicts). Process-wide
    (:func:`buffer`) because its readers — a benchmark's metric reader,
    ``/healthz``, a flight dump — do not hold the scheduler or its
    ``Telemetry``; spans keep their ``lane``, so several replicas in one
    process stay apart."""

    def __init__(self, capacity: int = BUFFER_CAPACITY) -> None:
        self._spans: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.dropped = 0

    @property
    def capacity(self) -> int:
        return self._spans.maxlen

    def __len__(self) -> int:
        return len(self._spans)

    def append(self, span: dict) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(span)

    def snapshot(self) -> list:
        """The buffered spans, oldest first (a copy of the list; the span
        dicts are shared and must not be mutated)."""
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0


_BUFFER = SpanBuffer()
_DEFAULT_TRACER: "Tracer | None" = None


def buffer() -> SpanBuffer:
    """The process-wide buffer every :class:`Tracer` appends to."""
    return _BUFFER


def default_tracer() -> "Tracer":
    """The buffer-only tracer code uses where no ``Telemetry`` was passed."""
    global _DEFAULT_TRACER
    if _DEFAULT_TRACER is None:
        _DEFAULT_TRACER = Tracer()
    return _DEFAULT_TRACER


_TRACEPARENT_VERSION = "00"


# Span ids come from a generator seeded once from the OS (and again in a
# forked child), not from a system call per span: a decode step opens eight
# spans, and they identify, they do not protect.
_IDS = random.Random()
os.register_at_fork(after_in_child=_IDS.seed)


def _hex_id(nbytes: int) -> str:
    return "%0*x" % (2 * nbytes, _IDS.getrandbits(8 * nbytes))


class SpanContext:
    """The serializable identity of one span: ``(trace_id, span_id)``.

    ``trace_id`` is 16 bytes (32 hex chars) shared by every span of one
    request's tree; ``span_id`` is 8 bytes (16 hex chars) unique per span.
    The wire form is the W3C traceparent header: ``00-<trace>-<span>-01``.
    """

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    @classmethod
    def new(cls) -> "SpanContext":
        return cls(_hex_id(16), _hex_id(8))

    def child(self) -> "SpanContext":
        """A fresh span id under the same trace."""
        return SpanContext(self.trace_id, _hex_id(8))

    def to_traceparent(self) -> str:
        return f"{_TRACEPARENT_VERSION}-{self.trace_id}-{self.span_id}-01"

    @classmethod
    def from_traceparent(cls, header) -> "SpanContext | None":
        """Parse a traceparent header; None (never an exception) on any
        malformation — an invalid incoming header must degrade to "start a
        new trace", not error the request carrying it (W3C semantics)."""
        if not isinstance(header, str):
            return None
        parts = header.strip().lower().split("-")
        if len(parts) != 4:
            return None
        version, trace_id, span_id, _flags = parts
        if len(version) != 2 or version == "ff":
            return None
        if len(trace_id) != 32 or len(span_id) != 16:
            return None
        try:
            int(trace_id, 16), int(span_id, 16)
        except ValueError:
            return None
        if trace_id == "0" * 32 or span_id == "0" * 16:
            return None
        return cls(trace_id, span_id)


class Span:
    """One timed operation. Created by the tracer, closed exactly once by
    ``end()`` — which is when (and only when) its event is emitted."""

    __slots__ = (
        "name", "ctx", "parent_id", "lane", "attrs",
        "_t0_wall", "_t0_mono", "_tracer", "_ended", "_mirror",
    )

    def __init__(self, tracer: "Tracer", name: str, ctx: SpanContext,
                 parent_id: "str | None", lane: "str | None", attrs: dict):
        self.name = name
        self.ctx = ctx
        self.parent_id = parent_id
        self.lane = lane
        self.attrs = attrs
        self._t0_wall = time.time()
        self._t0_mono = time.perf_counter()
        self._tracer = tracer
        self._ended = False
        self._mirror = None

    def __enter__(self) -> "Span":
        """Become this thread's current span (``tracer.span(...)`` is used
        this way) and, where the tracer has an ``annotate`` factory, a host
        event of the same name in the profiler's trace."""
        self._tracer._stack().append(self)
        annotate = self._tracer.annotate
        if annotate is not None:
            self._mirror = annotate(self.name)
            self._mirror.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._mirror is not None:
            self._mirror.__exit__(None, None, None)
        self._tracer._stack().pop()
        if not self._ended:
            if exc_type is not None:
                self.end(error=exc_type.__name__)
            else:
                self.end()
        return False

    def set(self, **attrs) -> None:
        """Attach/overwrite attributes before the span closes (recorded in
        the close event). Reserved schema fields are refused at end()."""
        self.attrs.update(attrs)

    def end(self, **attrs) -> None:
        """Close the span and emit its ``trace.span`` event. Exactly-once:
        a second end() is counted (``tracer.stats['double_end']``) and
        otherwise ignored — telemetry must never raise into serving code,
        and the span-tree tests read the counter."""
        if attrs:
            self.attrs.update(attrs)
        self._tracer._end_span(self)


class Tracer:
    """Span factory. Closed spans always land in :func:`buffer`; ``emit``
    (``EventLog.emit`` or ``Telemetry.emit`` — anything with the
    ``(kind, **fields)`` shape) additionally writes them to an event log,
    None means buffer only. ``annotate`` (``name -> context manager``,
    settable after construction by the first user that imports jax) mirrors
    every ``span()`` context into the profiler's trace."""

    def __init__(self, emit=None, annotate=None) -> None:
        self._emit = emit
        self.annotate = annotate
        self._lock = threading.Lock()
        self._open: dict[str, str] = {}  # span_id -> name (introspection)
        self._local = threading.local()
        self.stats = {"started": 0, "ended": 0, "double_end": 0,
                      "dropped_attrs": 0}

    # ---- introspection (the span-tree completeness surface) ---------------

    @property
    def open_count(self) -> int:
        with self._lock:
            return len(self._open)

    def open_spans(self) -> dict[str, str]:
        """span_id -> name of every not-yet-closed span (a copy)."""
        with self._lock:
            return dict(self._open)

    # ---- span lifecycle ----------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current(self) -> "Span | None":
        """The innermost ``span()`` context on THIS thread, or None."""
        stack = self._stack()
        return stack[-1] if stack else None

    def start_span(
        self, name: str, parent=None, lane: "str | None" = None, **attrs
    ) -> Span:
        """Open a span. ``parent`` may be a :class:`Span`, a
        :class:`SpanContext` (e.g. parsed from an incoming traceparent), or
        None — None inherits this thread's current ``span()`` context, and
        starts a NEW trace only when there is none."""
        if parent is None:
            parent = self.current()
        if isinstance(parent, Span):
            parent = parent.ctx
        if isinstance(parent, SpanContext):
            ctx, parent_id = parent.child(), parent.span_id
        else:
            ctx, parent_id = SpanContext.new(), None
        span = Span(self, name, ctx, parent_id, lane, attrs)
        with self._lock:
            self.stats["started"] += 1
            self._open[ctx.span_id] = name
        return span

    def _end_span(self, span: Span) -> None:
        if span._ended:
            with self._lock:
                self.stats["double_end"] += 1
            return
        span._ended = True
        dur = time.perf_counter() - span._t0_mono
        with self._lock:
            self.stats["ended"] += 1
            self._open.pop(span.ctx.span_id, None)
        fields = {
            "trace": span.ctx.trace_id,
            "span": span.ctx.span_id,
            "name": span.name,
            "t0": span._t0_wall,
            "dur_s": dur,
        }
        if span.parent_id is not None:
            fields["parent"] = span.parent_id
        if span.lane is not None:
            fields["lane"] = span.lane
        for key, value in span.attrs.items():
            if key in RESERVED_SPAN_FIELDS or key in fields:
                with self._lock:
                    self.stats["dropped_attrs"] += 1
                continue
            fields[key] = value
        # ts = close time, consistent with every other event kind; t0/dur_s
        # carry the interval (the exporter never trusts ts for geometry).
        ts = span._t0_wall + dur
        _BUFFER.append({"ts": ts, "kind": "trace.span", **fields,
                        "t0_mono": span._t0_mono})
        if self._emit is not None:
            # The log's times are rounded (microseconds; nanoseconds for
            # a duration), the buffer's are as the clocks gave them.
            fields.update(t0=round(fields["t0"], 6), dur_s=round(dur, 9))
            self._emit("trace.span", ts=round(ts, 6), **fields)

    def span(self, name: str, parent=None, lane: "str | None" = None, **attrs):
        """Context-manager span (``with tracer.span(...) as sp``): parents to
        the enclosing ``span()`` on this thread (unless ``parent=``
        overrides), is current for the duration, and always closes — even on
        exception (recorded as ``error=<type name>``; the exception
        propagates untouched). With an ``annotate`` factory the same
        interval is a host event of the same name in the profiler's trace."""
        return self.start_span(name, parent=parent, lane=lane, **attrs)


def traced_call(fn, tracer: Tracer, name: str, lane: "str | None" = None,
                **attrs):
    """Wrap ``fn`` so every call runs inside a ``tracer.span(name)`` —
    parenting to whatever span is current on the calling thread. The
    tracing sibling of ``obs.telemetry.timed_call``, with the same
    inertness obligation: when ``fn`` is jitted the span brackets the host
    dispatch, and tracing the wrapper directly must yield a byte-identical
    jaxpr (``telemetry_inert`` contract traces the pool step, slot prefill,
    and verify programs through this exact wrapper)."""

    def wrapped(*args, **kwargs):
        with tracer.span(name, lane=lane, **attrs):
            return fn(*args, **kwargs)

    wrapped.__wrapped__ = fn
    return wrapped


# --------------------------------------------------------------------------
# Perfetto / Chrome trace-event export

#: Fixed lane -> tid mapping: control lanes first, then one lane per serve
#: slot (``slot0``.. at tid 10+), so every export of the same run lays out
#: identically. Unknown lanes allocate past the slots. ``router`` is the
#: front-end dispatcher's own lane (serve/router.py) — in a multi-source
#: merge the router's log is additionally its own PROCESS row, since
#: processes key on the ``source`` tag.
_CONTROL_LANES = {"intake": 1, "scheduler": 2, "train": 3, "router": 4}
_SLOT_TID_BASE = 10


def _lane_tid(lane: str, extra: dict) -> int:
    if lane in _CONTROL_LANES:
        return _CONTROL_LANES[lane]
    if lane.startswith("slot"):
        try:
            return _SLOT_TID_BASE + int(lane[4:])
        except ValueError:
            pass
    if lane not in extra:
        extra[lane] = 1000 + len(extra)
    return extra[lane]


def chrome_trace(events: list) -> dict:
    """``trace.span`` events -> a Chrome trace-event JSON document (the
    ``{"traceEvents": [...]}`` object form), loadable in chrome://tracing
    and ui.perfetto.dev. Each span becomes one complete ("X") event; each
    source file (multi-source merge) becomes one process with its lanes as
    named threads. Non-span events are ignored, so the exporter runs on
    any event log."""
    spans = [
        e for e in events
        if e.get("kind") == "trace.span"
        and isinstance(e.get("t0"), (int, float))
        and isinstance(e.get("dur_s"), (int, float))
    ]
    pids: dict[str, int] = {}
    extra_lanes: dict[tuple, int] = {}
    out: list[dict] = []
    seen_threads: set[tuple] = set()
    base = min((e["t0"] for e in spans), default=0.0)
    for e in spans:
        source = str(e.get("source", "main"))
        if source not in pids:
            pids[source] = len(pids) + 1
            out.append({
                "ph": "M", "name": "process_name", "pid": pids[source],
                "tid": 0, "args": {"name": source},
            })
        pid = pids[source]
        lane = str(e.get("lane", "main"))
        per_source = extra_lanes.setdefault(("extra", source), {})
        tid = _lane_tid(lane, per_source)
        if (pid, tid) not in seen_threads:
            seen_threads.add((pid, tid))
            out.append({
                "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                "args": {"name": lane},
            })
            # Sort index keeps lanes in the fixed tid order in the UI.
            out.append({
                "ph": "M", "name": "thread_sort_index", "pid": pid,
                "tid": tid, "args": {"sort_index": tid},
            })
        args = {
            k: v for k, v in e.items()
            if k not in ("kind", "t0", "dur_s", "lane", "name", "ts", "source")
        }
        out.append({
            "ph": "X",
            "name": str(e.get("name", "span")),
            "cat": str(e.get("name", "span")).split(".", 1)[0],
            "pid": pid,
            "tid": tid,
            "ts": round((e["t0"] - base) * 1e6, 3),   # microseconds
            "dur": round(max(e["dur_s"], 0.0) * 1e6, 3),
            "args": args,
        })
    out.sort(key=lambda ev: (ev["ph"] != "M", ev.get("ts", 0.0)))
    return {
        "traceEvents": out,
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "transformer_tpu.obs trace",
            "sources": sorted(pids),
            "spans": len(spans),
            "base_unix_s": round(base, 6),
        },
    }


def span_tree(events: list) -> dict:
    """Index ``trace.span`` events into ``{trace_id: {span_id: event}}`` —
    the shape the completeness tests and the merge skew estimator walk."""
    trees: dict[str, dict[str, dict]] = {}
    for e in events:
        if e.get("kind") != "trace.span":
            continue
        trace, span = e.get("trace"), e.get("span")
        if isinstance(trace, str) and isinstance(span, str):
            trees.setdefault(trace, {})[span] = e
    return trees
