"""The telemetry bundle the CLIs wire through train/serve.

One :class:`Telemetry` object carries the whole obs surface: a
:class:`~transformer_tpu.obs.registry.MetricsRegistry`, an optional
:class:`~transformer_tpu.obs.events.EventLog`, and the periodic sinks —
a Prometheus text file rewritten atomically every ``interval`` seconds and
a ``metrics.snapshot`` event appended to the log on the same cadence.
``cli/flags.py flags_to_telemetry`` builds it from ``--metrics_jsonl`` /
``--metrics_port`` / ``--metrics_interval``; passing ``telemetry=None``
everywhere keeps metrics and the event log off (spans still reach the
in-memory buffer through ``obs.trace.default_tracer()``).

Design rule (contract-checked by ``analysis/contracts.py telemetry_inert``):
nothing in this module imports jax or touches device values. Recording
happens at existing host sync points; :func:`timed_call` wraps a jitted
callable without adding a single operation to its trace.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable

from transformer_tpu.obs.events import EventLog
from transformer_tpu.obs.registry import Histogram, MetricsRegistry
from transformer_tpu.obs.trace import Tracer, buffer as span_buffer


def timed_call(
    fn: Callable, histogram: Histogram | None = None, counter=None
) -> Callable:
    """Wrap ``fn`` so each call's host wall time lands in ``histogram`` (and
    ``counter`` counts calls). Under async dispatch this measures dispatch
    latency, not device time — the StepTimer's synced windows remain the
    throughput source of truth; this catches host-side stalls.

    Jaxpr-inert by construction: the wrapper runs OUTSIDE any trace when
    ``fn`` is a jitted callable, and when traced directly (the contract
    check) it forwards ``fn``'s outputs untouched — ``make_jaxpr`` of the
    wrapped and unwrapped function must be byte-identical.
    """

    def wrapped(*args, **kwargs):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        if histogram is not None:
            histogram.observe(time.perf_counter() - t0)
        if counter is not None:
            counter.inc()
        return out

    wrapped.__wrapped__ = fn
    return wrapped


class Telemetry:
    """Registry + event log + periodic sinks, as one pass-around handle.

    The bundle always carries a :class:`~transformer_tpu.obs.trace.Tracer`
    (``telemetry.tracer``): the scheduler and trainer record hierarchical
    spans through it, and every closed span lands in the process-wide
    in-memory buffer (``obs.trace.buffer()``). ``trace=True`` (``--trace``)
    decides only whether closed spans are ALSO written to the event log as
    ``trace.span`` events (docs/OBSERVABILITY.md tracing section) — off by
    default, since spans multiply event volume per request. Either way
    tracing stays answer- and jaxpr-inert (contract-checked).
    """

    def __init__(
        self,
        registry: MetricsRegistry | None = None,
        events: EventLog | None = None,
        prom_path: str | None = None,
        interval: float = 10.0,
        trace: bool = False,
    ) -> None:
        self.registry = registry if registry is not None else MetricsRegistry()
        self.events = events
        self.prom_path = prom_path
        self.interval = max(float(interval), 0.0)
        # -inf, not 0.0: perf_counter's epoch is unspecified (host boot on
        # Linux), so "now - 0 < interval" would skip the first flush on any
        # machine whose uptime is shorter than the interval — the first
        # flush must ALWAYS run.
        self._last_flush = float("-inf")
        self._server = None
        self._t0 = time.time()
        self.tracer = Tracer(self.emit if trace else None)
        # Armed on demand (arm_flight): the flight recorder
        # (obs/flight.py). None keeps the surface free.
        self.flight = None

    # ---- optional subsystems ---------------------------------------------

    def arm_flight(
        self, path: str | None, capacity: int = 256, autodump_s: float = 2.0
    ):
        """Attach a :class:`~transformer_tpu.obs.flight.FlightRecorder`
        tapped off :meth:`emit`; ``maybe_flush`` drives its autodumps and
        ``close`` writes the final record."""
        from transformer_tpu.obs.flight import FlightRecorder

        self.flight = FlightRecorder(
            path, capacity=capacity, autodump_s=autodump_s,
            registry=self.registry, emit=self.emit,
        )
        return self.flight

    # ---- events -----------------------------------------------------------

    def emit(self, kind: str, **fields) -> None:
        if self.events is not None:
            self.events.emit(kind, **fields)
        if self.flight is not None:
            self.flight.record(kind, fields)

    # ---- periodic sinks ---------------------------------------------------

    def maybe_flush(self, force: bool = False) -> bool:
        """Run the periodic sinks if ``interval`` has elapsed (or ``force``).
        Cheap to call every scheduler step / train dispatch: the common case
        is one ``perf_counter`` read and a compare."""
        now = time.perf_counter()
        # The flight recorder's autodump runs at ITS cadence (autodump_s),
        # not the sink interval — a SIGKILL can't trigger a dump, so the
        # on-disk record's staleness bound must not inherit the (much
        # longer) snapshot interval.
        if self.flight is not None:
            self.flight.maybe_dump()
        if not force and now - self._last_flush < self.interval:
            return False
        self._last_flush = now
        self.emit("metrics.snapshot", metrics=self.registry.snapshot())
        if self.prom_path:
            self._write_prom()
        if self.events is not None:
            self.events.flush()
        return True

    def _write_prom(self) -> None:
        """Atomic rewrite (tmp + rename): a scraper tailing the file never
        sees a torn exposition."""
        import sys

        tmp = f"{self.prom_path}.tmp"
        try:
            with open(tmp, "w") as f:
                f.write(self.registry.to_prometheus_text())
            os.replace(tmp, self.prom_path)
        except OSError as e:
            # Same downgrade contract as EventLog: one stderr warning, then
            # the sink goes quiet — the observed process never dies (and a
            # scraper sees a stale-but-valid file, not a torn one).
            print(
                f"obs: prometheus file {self.prom_path} unwritable ({e}); "
                "sink disabled for this process",
                file=sys.stderr,
            )
            self.prom_path = None

    def close(self) -> None:
        self.maybe_flush(force=True)
        if self.flight is not None:
            self.flight.dump("close")
        if self._server is not None:
            self._server.shutdown()
            self._server = None
        if self.events is not None:
            self.events.close()

    # ---- health -----------------------------------------------------------

    def health(self) -> dict:
        """Liveness + sink states, the ``/healthz`` document. ``ok`` is
        False only when a sink has hard-downgraded (broken event log) —
        breaker-open is a transient, reported but not fatal."""
        doc: dict = {
            "ok": True,
            "pid": os.getpid(),
            "ts": round(time.time(), 6),
            "uptime_s": round(time.time() - self._t0, 3),
            "sinks": {
                "prom_file": {"enabled": bool(self.prom_path)},
            },
        }
        if self.events is not None:
            ev = {"broken": bool(getattr(self.events, "_broken", False))}
            breaker = getattr(self.events, "_breaker", None)
            if breaker is not None:
                ev["breaker"] = getattr(breaker, "state", "unknown")
            doc["sinks"]["event_log"] = ev
            if ev["broken"]:
                doc["ok"] = False
        if self.flight is not None:
            doc["flight"] = {
                "depth": self.flight.depth(),
                "dumps": self.flight.dumps,
                "broken": self.flight._broken,
            }
        spans = span_buffer()
        doc["spans"] = {
            "buffered": len(spans), "capacity": spans.capacity,
            "dropped": spans.dropped, "open": self.tracer.open_count,
        }
        return doc

    # ---- scrape endpoint --------------------------------------------------

    def start_prometheus_server(self, port: int) -> int:
        """Serve ``GET /metrics`` (text exposition) and ``GET /healthz``
        (liveness JSON) on ``port`` from a daemon thread; returns the bound
        port (pass 0 to let the OS pick — tests). stdlib ``http.server``
        only: the obs package takes no dependencies."""
        import http.server
        import json as _json

        registry = self.registry
        telemetry = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler API
                if self.path == "/healthz":
                    doc = telemetry.health()
                    body = _json.dumps(doc).encode()
                    self.send_response(200 if doc["ok"] else 503)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if self.path not in ("/metrics", "/"):
                    self.send_error(404)
                    return
                body = registry.to_prometheus_text().encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # scrapes must not spam stderr
                pass

        server = http.server.ThreadingHTTPServer(("0.0.0.0", port), Handler)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        self._server = server
        return server.server_address[1]


def device_memory_stats(device: Any) -> dict | None:
    """Best-effort ``device.memory_stats()`` (PJRT exposes it on TPU/GPU;
    CPU returns None or omits the method). Returns the small stable subset
    worth recording, or None when the backend has nothing."""
    probe = getattr(device, "memory_stats", None)
    if probe is None:
        return None
    try:
        stats = probe()
    except (RuntimeError, NotImplementedError):
        return None
    if not stats:
        return None
    out = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "largest_alloc_size"):
        if key in stats:
            out[key] = int(stats[key])
    return out or None
