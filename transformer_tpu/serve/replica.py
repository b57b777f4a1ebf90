"""Replica worker: one model copy + one ``ContinuousScheduler`` behind a pipe.

The unit the router (``serve/router.py``) multiplies. Launched as

    python -m transformer_tpu.serve.replica --export_path=model \\
        --tgt_vocab_file=vocab.subwords [scheduler flags...]

it loads its own model copy (or builds a deterministic test model from a
``--model_spec`` JSON — the CI/bench bootstrap), wraps the EXISTING
continuous-batching scheduler around it, and speaks a line-oriented JSON
protocol on stdin/stdout:

router -> replica:
    {"type": "req",      "rid": N, "req": {...}}          serve a request
    {"type": "req",      "rid": N, "req": {...},
     "blocks": ..., "tokens": T}                          ...after a prefill
                                                          handoff (inject T
                                                          prompt tokens' KV
                                                          into the local
                                                          PrefixCache first)
    {"type": "prefill",  "rid": N, "req": {...}}          disaggregation
                                                          stage 1: ingest the
                                                          prompt, export its
                                                          KV blocks, answer
                                                          "prefilled"
    {"type": "export_state", "limit": K}                  supervisor warm-up:
                                                          export the K hottest
                                                          PrefixCache prefixes
    {"type": "inject_state", "entries": [...]}            ...inject them into
                                                          a fresh replica
    {"type": "upgrade", "ckpt": D, "version": V}          live-weights swap:
                                                          verify the manifest
                                                          + structure, stage
                                                          into the two-version
                                                          param slot (the flip
                                                          lands at a drained
                                                          step boundary)
    {"type": "rollback"}                                  re-stage the resident
                                                          previous weights
    {"type": "dump"}                                      flight-recorder dump:
                                                          persist the ring and
                                                          reply "flight"
    {"type": "shutdown"}                                  drain + exit

replica -> router:
    {"type": "ready", "replica": name, "slots": N,
     "device": {"platform", "kind", "chips", "devices"}
     [, "control_port": P]}                               P with --ha only
    {"type": "hb", "backlog": B, "free": F, "active": A}  heartbeat (the
                                                          least-loaded gauges)
    {"type": "answer", "rid": N, "resp": {...}
     [, "slo": {...}]}                                    one per request;
                                                          "slo" is the span
                                                          side channel (ttft
                                                          etc., stripped
                                                          before the client)
    {"type": "prefilled", "rid": N, "tokens": T, "blocks": ...}
    {"type": "prefix_state", "entries": [...]}            export_state reply
    {"type": "state_injected", "tokens": T}               inject_state reply
    {"type": "upgrade_staged", "ok": B, "version": V
     [, "error": E]}                                      upgrade/rollback
                                                          verdict (ok=false =
                                                          refused, old weights
                                                          untouched)
    {"type": "upgraded", "ok": B, "version": V}           the step-boundary
                                                          flip landed (or its
                                                          ckpt.swap abort)
    {"type": "flight", "record": {...}|null}              dump reply: the
                                                          flight-recorder ring
                                                          (obs/flight.py)
    {"type": "stats", "stats": {...}}                     final, at shutdown

**Router HA** (``--ha``): the worker additionally listens on a localhost
TCP control socket (ephemeral port, announced in ``ready``). A warm-standby
router (``serve/standby.py``) that declares the primary dead connects and
sends a takeover handshake::

    {"type": "takeover", "epoch": E, "inflight": [rid, ...]}

An epoch HIGHER than the channel currently holding authority (stdin starts
at epoch 1) wins: the reply reports, for every rid the standby believes
in-flight here, whether it is ``done`` (with the original answer message
replayed from a bounded recent-answer cache — an answer lost in the dead
primary's pipe is re-delivered, and the standby's order-keyed funnel keeps
at-most-once), still ``inflight`` (it will answer on the NEW channel), or
``unknown`` (the standby re-dispatches it)::

    {"type": "adopted", "replica": name, "epoch": E,
     "statuses": {rid: "done"|"inflight"|"unknown"},
     "messages": {rid: <original answer/prefilled message>}}

and every subsequent worker message flows to the adopting channel. A
takeover with a stale epoch answers ``{"type": "rejected", "epoch": cur}``
and changes nothing — the split-brain guard: after an adoption, requests
still arriving from the OLD channel (a falsely-declared-dead primary) are
dropped and counted, never served twice. In HA mode stdin EOF does NOT
drain the worker (the primary dying must not kill the fleet); shutdown
comes from the authoritative channel (or the supervising process group).

``rid`` is the ROUTER's order for the request — the replica never invents
identity, so the router's order-keyed answer funnel stays authoritative.
Every forwarded request carries the router-minted ``traceparent``; with
``--metrics_jsonl`` + ``--trace`` this replica's spans parent under the
router's ``route.request`` span and ``obs summarize/trace/slo --merge``
re-joins the fleet trace (docs/OBSERVABILITY.md).

**KV handoff format** (disaggregation): the prompt's KV crosses the
process boundary as the prefix cache's OWN host-side token-aligned blocks
(``serve/prefix_cache.py``) — per layer, per ``block_tokens`` positions,
in the cache's storage layout — serialized as base64 ``tobytes`` with
dtype/shape. :func:`export_blocks` reads them out of this replica's
``PrefixCache`` after a ``max_new=0`` admission fed them; the decode side
:func:`inject_blocks` inserts them into ITS cache so admission restores
them with zero model forwards (the prefix-cache byte-parity contract makes
the handoff answer-invariant).

Sharding: on a multi-device host, ``parallel/mesh.py`` machinery shards
each replica's params exactly as ``cli/serve.py`` would — this worker
adds process isolation on top, not a new parallelism scheme. CI runs it
on plain CPU processes.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import queue
import socket
import sys
import threading
import time
from collections import deque


class _Channel:
    """One duplex control link: stdin/stdout, or an accepted takeover
    socket. The MAIN loop is the only writer (lines never tear); reader
    threads only parse the inbound side into the main queue. ``epoch`` is
    the authority the channel last proved (stdin starts at 1; takeover
    sockets earn theirs through the handshake); a write failure marks the
    channel broken — answers are NOT lost with it, the bounded recent-
    answer cache re-delivers them to whoever adopts next."""

    def __init__(self, write_file, name: str, epoch: int = 0):
        self._write = write_file
        self.name = name
        self.epoch = epoch
        self.broken = False

    def send(self, msg: dict) -> bool:
        if self.broken or self._write is None:
            return False
        try:
            self._write.write(json.dumps(msg) + "\n")
            self._write.flush()
            return True
        except (OSError, ValueError):
            self.broken = True
            return False


# --------------------------------------------------------------------------
# deterministic test-model bootstrap (CI, benches)


def build_model_from_spec(spec: dict):
    """(params, cfg, tok) from a model-spec dict — the deterministic
    bootstrap the router tests and benches use: every process (replicas
    AND the in-process single-scheduler reference) that builds the same
    spec gets bit-identical params and vocab, so byte-parity assertions
    are meaningful across process boundaries.

    Spec shape::

        {"config": {...ModelConfig overrides (vocab sizes filled from the
                    corpus tokenizer)...},
         "seed": 0,
         "corpus": ["line", ...],
         "target_vocab_size": 300}
    """
    import jax

    from transformer_tpu.config import ModelConfig
    from transformer_tpu.data.tokenizer import SubwordTokenizer
    from transformer_tpu.models import transformer_init

    tok = SubwordTokenizer.build_from_corpus(
        list(spec["corpus"]),
        target_vocab_size=int(spec.get("target_vocab_size", 300)),
    )
    cfg = ModelConfig(
        **{
            **dict(spec.get("config", {})),
            "input_vocab_size": tok.model_vocab_size,
            "target_vocab_size": tok.model_vocab_size,
        }
    )
    params = transformer_init(jax.random.PRNGKey(int(spec.get("seed", 0))), cfg)
    return params, cfg, tok


# --------------------------------------------------------------------------
# KV-block handoff (disaggregated prefill/decode)


def _encode_array(a) -> dict:
    import numpy as np

    a = np.ascontiguousarray(a)
    return {
        "dtype": a.dtype.str,
        "shape": list(a.shape),
        "b64": base64.b64encode(a.tobytes()).decode("ascii"),
    }


def _decode_array(d: dict):
    import numpy as np

    return np.frombuffer(
        base64.b64decode(d["b64"]), dtype=np.dtype(d["dtype"])
    ).reshape(d["shape"])


def export_blocks(cache, ids: "list[int]") -> "tuple[int, list]":
    """Read the longest block-aligned prefix of ``ids`` out of ``cache``
    (a ``PrefixCache`` a ``max_new=0`` admission just fed) as the wire
    payload: ``payload[j]`` is block j — per-layer dicts of serialized
    arrays in the cache's own storage layout. Returns ``(tokens,
    payload)``; (0, []) when nothing aligned is stored (budget pressure) —
    the decode side then simply full-prefills."""
    B = cache.block_tokens
    aligned = (len(ids) // B) * B
    if not aligned:
        return 0, []
    hit = cache.match(ids[:aligned])
    try:
        payload = []
        for node in hit._nodes:
            try:
                # host_blocks_for serves both tiers: stored host blocks
                # directly, device-resident blocks via ONE ephemeral pool
                # read (paged serving) — the wire format is identical.
                blocks = cache.host_blocks_for(node)
            except Exception:  # noqa: BLE001  # tpa: disable=TPA006 — wire export is best-effort: an unreadable block truncates the payload to the readable prefix (the decode side full-prefills the rest), it must never kill the handoff
                break
            payload.append(
                [
                    {key: _encode_array(layer[key]) for key in sorted(layer)}
                    for layer in blocks
                ]
            )
        return len(payload) * B, payload
    finally:
        hit.release()


def inject_blocks(cache, ids: "list[int]", tokens: int, payload: list) -> int:
    """Insert a handoff payload into the local ``PrefixCache`` so the next
    admission of ``ids`` restores it without a model forward. Returns the
    tokens actually inserted (the cache's budget may admit fewer)."""
    B = cache.block_tokens
    tokens = min(int(tokens), (len(ids) // B) * B, len(payload) * B)
    if tokens <= 0:
        return 0
    blocks = [
        [
            {key: _decode_array(d) for key, d in layer.items()}
            for layer in blk
        ]
        for blk in payload
    ]
    cache.insert(ids[:tokens], tokens, lambda start: blocks[start // B])
    return tokens


# --------------------------------------------------------------------------
# the worker


def _parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description="router replica worker")
    p.add_argument("--replica_name", default="replica0")
    p.add_argument("--role", choices=("both", "prefill", "decode"),
                   default="both")
    p.add_argument("--export_path", default="")
    p.add_argument("--tgt_vocab_file", default="")
    p.add_argument("--model_spec", default="",
                   help="JSON file with a deterministic test-model spec "
                        "(build_model_from_spec) — CI/bench bootstrap")
    p.add_argument("--kv_cache_int8", action="store_true")
    p.add_argument("--serve_slots", type=int, default=4)
    p.add_argument("--serve_max_total", type=int, default=0)
    p.add_argument("--prefill_chunk", type=int, default=0)
    p.add_argument("--max_len", type=int, default=64,
                   help="default max_new per request")
    p.add_argument("--speculate_k", type=int, default=0)
    p.add_argument("--prefix_cache_mb", type=int, default=0)
    p.add_argument("--prefix_block", type=int, default=16)
    p.add_argument("--kv_layout", choices=("dense", "paged"), default="dense",
                   help="per-slot KV storage: dense max_total buffers, or "
                        "the paged block pool (docs/SERVING.md)")
    p.add_argument("--kv_pool_blocks", type=int, default=0,
                   help="paged pool size in blocks (0 = full provisioning)")
    p.add_argument("--mesh", default="",
                   help="serving mesh size ('N' or 'data=N'): the replica "
                        "becomes ONE pjit program over N devices — params "
                        "replicated by the partition rules, KV pool sharded "
                        "on its storage axis (docs/SERVING.md 'Sharded "
                        "replicas'). On CPU the worker grows its own "
                        "virtual platform before jax initializes. '' = "
                        "single-device (historical)")
    p.add_argument("--max_backlog", type=int, default=0)
    p.add_argument("--heartbeat_ms", type=float, default=200.0)
    p.add_argument("--metrics_jsonl", default="")
    p.add_argument("--trace", action="store_true")
    p.add_argument("--fault_spec", default="")
    p.add_argument("--ha", action="store_true",
                   help="router HA: listen on a localhost control socket "
                        "for a warm standby's takeover handshake, and "
                        "survive stdin EOF (the primary dying must not "
                        "kill the fleet)")
    p.add_argument("--init_ckpt", default="",
                   help="bootstrap the serving weights from this "
                        "manifest-verified checkpoint instead of the "
                        "export/spec weights — the supervisor passes the "
                        "fleet's TARGET version here so a respawn never "
                        "resurrects stale weights (serve/upgrade.py)")
    p.add_argument("--weight_version", default="",
                   help="expected weight_version digest for --init_ckpt "
                        "(mismatch refuses the bootstrap loudly); also "
                        "tags an un-upgraded replica's answers")
    return p.parse_args(argv)


def stdin_reader(q: "queue.Queue") -> None:
    """Feed stdin lines into ``q``, then a ``None`` EOF sentinel — the one
    line-intake reader shared by this worker, ``cli/serve.py``, and
    ``cli/router.py`` (all three speak the same line protocol, so EOF and
    encoding behavior must never diverge between them)."""
    for line in sys.stdin:
        q.put(line)
    q.put(None)


def _control_server(listener: socket.socket, q: "queue.Queue") -> None:
    """Accept takeover connections; per connection, one reader thread
    feeds parsed ``(channel, line)`` pairs into the main queue — exactly
    the stdin_reader contract, so the main loop stays the only owner of
    every piece of serving state (the TPA101 surface between the control
    threads and the loop is the synchronized queue alone)."""
    while True:
        try:
            conn, _ = listener.accept()
        except OSError:
            return  # listener closed at shutdown
        chan = _Channel(
            conn.makefile("w", encoding="utf-8", buffering=1),
            name="takeover",
        )
        rf = conn.makefile("r", encoding="utf-8")

        def reader(chan=chan, rf=rf):
            try:
                for line in rf:
                    q.put((chan, line))
            except (OSError, ValueError):
                pass
            chan.broken = True

        threading.Thread(
            target=reader, daemon=True, name="replica-control-read"
        ).start()


def main(argv=None) -> None:
    args = _parse_args(argv)
    # --mesh bootstrap must precede the FIRST jax-importing line: on the
    # CPU platform the worker grows its own virtual device count (the
    # conftest/analysis trick), which only takes effect before jax
    # initializes. The XLA flag only affects CPU hosts; on a TPU host the
    # router's spawn gives this process exactly its N chips
    # (serve/router.py replica_chip_env). An operator-provided
    # device-count flag always wins.
    from transformer_tpu.serve.sharded import (
        normalize_mesh_spec,
        parse_mesh_spec,
    )

    mesh_n = parse_mesh_spec(args.mesh)
    mesh_shape = normalize_mesh_spec(args.mesh)
    if mesh_n is not None and mesh_n > 1:
        xla_flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in xla_flags:
            os.environ["XLA_FLAGS"] = (
                xla_flags
                + f" --xla_force_host_platform_device_count={mesh_n}"
            ).strip()
    if args.fault_spec:
        from transformer_tpu.serve import resilience

        resilience.install(resilience.FaultPlane.parse(args.fault_spec))

    telemetry = None
    if args.metrics_jsonl:
        from transformer_tpu.obs import EventLog, Telemetry
        from transformer_tpu.obs.flight import flight_path_for

        telemetry = Telemetry(
            events=EventLog(args.metrics_jsonl), trace=args.trace
        )
        # Tight autodump: the on-disk flight record is all a SIGKILL
        # leaves behind, and the Supervisor's postmortem capture reads it
        # — half a second bounds how much of the victim's last telemetry
        # the fleet can lose (docs/OBSERVABILITY.md).
        flight = telemetry.arm_flight(
            flight_path_for(args.metrics_jsonl), autodump_s=0.5
        )
        flight.install_signal_handlers()

    # Before the first compile, so a respawned replica (and every replica
    # after the first) loads its programs instead of compiling them cold.
    from transformer_tpu.utils.profiling import enable_compilation_cache

    enable_compilation_cache()

    if args.model_spec:
        with open(args.model_spec) as f:
            spec = json.load(f)
        params, cfg, tok = build_model_from_spec(spec)
    else:
        from transformer_tpu.cli.translate import load_export
        from transformer_tpu.data.tokenizer import SubwordTokenizer

        params, cfg = load_export(
            args.export_path, kv_cache_int8=args.kv_cache_int8
        )
        tok = SubwordTokenizer.load(args.tgt_vocab_file)

    weight_version = args.weight_version or None
    if args.init_ckpt:
        # Verified-integrity bootstrap at the fleet's target version: the
        # checkpoint's manifest is byte-verified and its arrays matched
        # against the spec-built tree (shape/dtype twins) BEFORE the swap
        # — a bad artifact kills the bootstrap loudly so the supervisor's
        # crash-loop budget (not a silently wrong fleet) absorbs it.
        from transformer_tpu.serve.upgrade import load_checkpoint_params

        params, loaded_version = load_checkpoint_params(
            args.init_ckpt, params
        )
        if args.weight_version and args.weight_version != loaded_version:
            print(
                f"replica: --init_ckpt {args.init_ckpt} verifies to "
                f"{loaded_version} but --weight_version expected "
                f"{args.weight_version}; refusing to serve the wrong "
                "weights", file=sys.stderr,
            )
            raise SystemExit(2)
        weight_version = loaded_version

    from transformer_tpu.serve import ContinuousScheduler, PrefixCache

    prefix_cache = None
    disaggregated = args.role in ("prefill", "decode")
    if args.prefix_cache_mb > 0 or disaggregated:
        # Disaggregation rides the prefix-cache block format on BOTH
        # sides: the prefill worker exports through its cache, the decode
        # worker injects into its own — so both roles get one by default.
        prefix_cache = PrefixCache(
            cfg,
            block_tokens=args.prefix_block,
            budget_mb=max(1, args.prefix_cache_mb or 64),
        )
    # Span side channel: the scheduler hands every answer-boundary span
    # dict to this tap (host-side, jaxpr-inert); flush_answers ships the
    # latency/prefix numbers next to the answer so the ROUTER's SLO engine
    # (the autoscaling signal) sees real per-request ttft without each
    # replica needing its own telemetry sink.
    spans_by_order: "dict[int, dict]" = {}
    sched = ContinuousScheduler(
        params, cfg, tok,
        num_slots=args.serve_slots,
        max_total=args.serve_max_total or None,
        prefill_chunk=args.prefill_chunk,
        default_max_new=args.max_len,
        telemetry=telemetry,
        speculate_k=args.speculate_k,
        prefix_cache=prefix_cache,
        max_backlog=args.max_backlog,
        kv_layout=args.kv_layout,
        kv_block=args.prefix_block,
        kv_pool_blocks=args.kv_pool_blocks,
        mesh=mesh_n,
        weight_version=weight_version,
        span_tap=lambda span: spans_by_order.__setitem__(
            span.get("order"), span
        ),
    )

    q: queue.Queue = queue.Queue()
    threading.Thread(target=stdin_reader, args=(q,), daemon=True).start()
    stdin_chan = _Channel(sys.stdout, "stdin", epoch=1)
    epoch = 1
    out = stdin_chan  # the authoritative outbound channel
    control_port = None
    if args.ha:
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.bind(("127.0.0.1", 0))
        listener.listen(4)
        control_port = listener.getsockname()[1]
        threading.Thread(
            target=_control_server, args=(listener, q), daemon=True,
            name="replica-control-accept",
        ).start()
    import jax

    held = jax.devices()[: mesh_n or 1]  # serving_mesh takes the first N
    ready = {
        "type": "ready", "replica": args.replica_name,
        "slots": args.serve_slots, "role": args.role,
        # What this replica runs on: the chips the router's spawn assigned
        # it (None off a TPU host) and the devices JAX reports for them.
        "device": {
            "platform": held[0].platform,
            "kind": held[0].device_kind,
            "chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "devices": [str(d) for d in held],
        },
    }
    if control_port is not None:
        ready["control_port"] = control_port
    if weight_version is not None:
        ready["weight_version"] = weight_version
    if mesh_shape is not None:
        # Canonical mesh shape ('data=N'): the supervisor compares this
        # against its expected_mesh and refuses a wrong-shape respawn
        # BEFORE the replica takes traffic.
        ready["mesh"] = mesh_shape
    out.send(ready)
    # The same line for the operator: which chip did this replica land on?
    print(f"replica ready: {json.dumps(ready)}", file=sys.stderr, flush=True)

    hb_s = max(args.heartbeat_ms, 1.0) / 1e3
    last_hb = 0.0
    # rid bookkeeping: the scheduler answers in arrival order and this
    # loop is the only submitter, so a FIFO of (rid, scheduler order)
    # (parallel to the submission sequence) maps drained responses back to
    # router orders and their tapped spans.
    rid_fifo: "list[tuple[int, int]]" = []
    prefill_rids: "set[int]" = set()
    prompt_ids: "dict[int, list[int]]" = {}
    # Bounded re-delivery cache: the full outbound message per answered
    # rid, replayed through the takeover handshake when an answer died in
    # the old primary's pipe (the adopting funnel dedupes, so replaying is
    # always safe).
    recent_answers: "dict[int, dict]" = {}
    answer_fifo: deque = deque()
    # At most one in-flight checkpoint verification (upgrade_staged is
    # answered by the main loop once the loader thread finishes — the
    # handoff is the is-alive check, so the loop never blocks on I/O).
    upgrade_load: "list[tuple[threading.Thread, dict]]" = []
    stats_extra = {"stale_dropped": 0, "takeovers": 0, "rejected_takeovers": 0}

    def _reap_upgrade_load() -> None:
        if not upgrade_load or upgrade_load[0][0].is_alive():
            return
        _, holder = upgrade_load.pop(0)
        if holder["error"] is not None:
            out.send({
                "type": "upgrade_staged", "ok": False,
                "version": holder["version"], "error": holder["error"],
            })
            return
        new_params, digest = holder["result"]
        try:
            sched.stage_params(new_params, digest)
        except ValueError as e:
            out.send({
                "type": "upgrade_staged", "ok": False, "version": digest,
                "error": f"{type(e).__name__}: {e}",
            })
            return
        out.send({"type": "upgrade_staged", "ok": True, "version": digest})

    def _remember(rid, msg) -> None:
        recent_answers[rid] = msg
        answer_fifo.append(rid)
        while len(answer_fifo) > 512:
            recent_answers.pop(answer_fifo.popleft(), None)

    def handle_takeover(chan: _Channel, msg: dict) -> None:
        nonlocal epoch, out
        e = int(msg.get("epoch", 0))
        if e <= epoch:
            # Split-brain guard: a stale or duplicate adopter changes
            # nothing — the current authority keeps the worker.
            stats_extra["rejected_takeovers"] += 1
            chan.send({
                "type": "rejected", "replica": args.replica_name,
                "epoch": epoch,
            })
            return
        statuses: dict = {}
        messages: dict = {}
        inflight_here = {rid for rid, _ in rid_fifo}
        for rid in msg.get("inflight", []):
            if rid in recent_answers:
                statuses[str(rid)] = "done"
                messages[str(rid)] = recent_answers[rid]
            elif rid in inflight_here:
                statuses[str(rid)] = "inflight"
            else:
                statuses[str(rid)] = "unknown"
        epoch = e
        chan.epoch = e
        out = chan
        stats_extra["takeovers"] += 1
        out.send({
            "type": "adopted", "replica": args.replica_name, "epoch": e,
            "role": args.role, "slots": args.serve_slots,
            "statuses": statuses, "messages": messages,
            "backlog": sched.backlog, "active": sched.active_count,
        })

    def ingest(chan: _Channel, msg: dict) -> bool:
        """Handle one control message; returns False on shutdown."""
        kind = msg.get("type")
        if kind == "takeover":
            handle_takeover(chan, msg)
            return True
        if chan.epoch < epoch:
            # A channel that lost authority (the falsely-declared-dead
            # primary of a completed takeover): its requests must not be
            # served TWICE — drop and count.
            stats_extra["stale_dropped"] += 1
            return True
        if kind == "shutdown":
            sched.shutdown()
            return False
        if kind == "dump":
            # Explicit flight-recorder dump: persist the ring AND ship the
            # record back over the wire — the Supervisor prefers the wire
            # copy (fresher than the last autodump) when both exist.
            record = None
            if telemetry is not None and telemetry.flight is not None:
                record = telemetry.flight.dump("request")
            out.send({"type": "flight", "record": record})
            return True
        if kind == "export_state":
            entries = []
            if prefix_cache is not None:
                for ids in prefix_cache.hot_prefixes(
                    int(msg.get("limit", 8))
                ):
                    try:
                        tokens, payload = export_blocks(
                            prefix_cache, list(ids)
                        )
                    except Exception:  # tpa: disable=TPA006 — warm-up export is best-effort: a corrupt/evicted prefix is skipped, the newcomer just starts colder
                        continue
                    if tokens:
                        entries.append({
                            "ids": list(ids), "tokens": tokens,
                            "blocks": payload,
                        })
            out.send({"type": "prefix_state", "entries": entries})
            return True
        if kind == "upgrade":
            # Stage a verified weight swap (serve/upgrade.py): byte-verify
            # the checkpoint's manifest, match it against the RUNNING
            # params (structure/shape/dtype), confirm the coordinator's
            # expected digest, then hand it to the scheduler's two-version
            # slot. Verification (full npz read + per-array crc32) runs on
            # a WORKER THREAD — a multi-GB checkpoint must not starve this
            # loop's heartbeats, or the router's liveness sweep would fail
            # the quiesced replica over mid-swap. The main loop collects
            # the result (_reap_upgrade_load) and stages it; the actual
            # flip happens at a drained step boundary — the "upgraded"
            # message reports it. ANY failure answers a structured refusal
            # with the old weights untouched.
            version = msg.get("version")
            if upgrade_load:
                out.send({
                    "type": "upgrade_staged", "ok": False,
                    "version": version,
                    "error": "an upgrade is already being verified",
                })
                return True
            holder = {
                "version": version, "result": None, "error": None,
            }

            def _load(ckpt=str(msg.get("ckpt", "")), holder=holder):
                try:
                    from transformer_tpu.serve.upgrade import (
                        UpgradeError,
                        load_checkpoint_params,
                    )

                    new_params, digest = load_checkpoint_params(
                        ckpt, sched.params
                    )
                    expected = holder["version"]
                    if expected and digest != expected:
                        raise UpgradeError(
                            f"checkpoint verifies to {digest} but the "
                            f"rollout targets {expected} — wrong artifact"
                        )
                    holder["result"] = (new_params, digest)
                except Exception as e:  # noqa: BLE001  # tpa: disable=TPA006 — rejection IS the contract: a torn/mismatched checkpoint must become one structured refusal with serving untouched, never a dead worker
                    holder["error"] = f"{type(e).__name__}: {e}"

            t = threading.Thread(
                target=_load, daemon=True, name="replica-upgrade-load"
            )
            t.start()
            upgrade_load.append((t, holder))
            return True
        if kind == "rollback":
            # Re-stage the resident previous weights (the second buffer a
            # completed swap left behind) — the canary-rollback path.
            try:
                version = sched.stage_rollback()
            except ValueError as e:
                out.send({
                    "type": "upgraded", "ok": False, "version": None,
                    "error": f"{type(e).__name__}: {e}",
                })
                return True
            out.send({
                "type": "upgrade_staged", "ok": True, "version": version,
                "rollback": True,
            })
            return True
        if kind == "inject_state":
            total = 0
            for e in msg.get("entries", []):
                try:
                    total += inject_blocks(
                        prefix_cache, list(e["ids"]), e.get("tokens", 0),
                        e.get("blocks", []),
                    ) if prefix_cache is not None else 0
                except Exception:  # tpa: disable=TPA006 — a corrupt warm-up payload degrades to a cold cache, never a dead worker
                    pass
            out.send({"type": "state_injected", "tokens": total})
            return True
        if kind not in ("req", "prefill"):
            return True
        rid = msg.get("rid")
        req = msg.get("req")
        if not isinstance(req, dict):
            req = {"prompt": ""}
        if kind == "prefill":
            # Disaggregation stage 1: ingest the prompt only (max_new=0
            # feeds the prefix cache at retirement), then export its KV.
            req = {**req, "max_new": 0, "cache_prefix": True}
            prefill_rids.add(rid)
        if prefix_cache is not None:
            try:
                ids = [tok.bos_id, *tok.encode(str(req.get("prompt", "")))]
            except Exception:  # tpa: disable=TPA006 — the scheduler's admission answers the validation error; the handoff bookkeeping just skips it
                ids = []
            prompt_ids[rid] = ids
            if kind == "req" and msg.get("blocks") and ids:
                try:
                    inject_blocks(
                        prefix_cache, ids, msg.get("tokens", 0),
                        msg["blocks"],
                    )
                except Exception:  # tpa: disable=TPA006 — a corrupt handoff payload degrades to full prefill (the cache just misses); it must never kill the worker
                    pass
        order = sched.submit(req)
        rid_fifo.append((rid, order))
        return True

    def flush_answers() -> None:
        for resp in sched.drain_ready():
            rid, order = rid_fifo.pop(0)
            span = spans_by_order.pop(order, None)
            if rid in prefill_rids:
                prefill_rids.discard(rid)
                tokens, payload = 0, []
                ids = prompt_ids.pop(rid, [])
                if "error" not in resp and prefix_cache is not None and ids:
                    try:
                        tokens, payload = export_blocks(prefix_cache, ids)
                    except Exception:  # tpa: disable=TPA006 — export is best-effort: a failed handoff falls back to full prefill on the decode side
                        tokens, payload = 0, []
                msg = {
                    "type": "prefilled", "rid": rid,
                    "tokens": tokens, "blocks": payload,
                }
            else:
                prompt_ids.pop(rid, None)
                msg = {"type": "answer", "rid": rid, "resp": resp}
                if span is not None:
                    # The side channel the router's SLO engine feeds on —
                    # never merged into resp (client answers stay
                    # byte-identical to a single scheduler's).
                    msg["slo"] = {
                        k: span[k]
                        for k in (
                            "ttft_s", "queue_s", "total_s",
                            "prefix_hit_tokens", "new_tokens",
                        )
                        if k in span
                    }
            _remember(rid, msg)
            out.send(msg)

    alive = True
    while alive or sched.busy:
        # Ingest whatever the router already sent; block only when idle.
        while alive:
            try:
                if sched.busy or sched.has_ready:
                    item = q.get_nowait()
                else:
                    # Idle: block, but wake often enough that heartbeats
                    # keep flowing (the router's liveness gauge).
                    item = q.get(timeout=hb_s)
            except queue.Empty:
                break
            if item is None:
                # stdin EOF: in HA mode the worker outlives its primary —
                # a standby adopts through the control socket; without HA
                # the historical drain-and-exit contract holds.
                if not args.ha:
                    alive = False
                    break
                continue
            if isinstance(item, str):
                chan, line = stdin_chan, item
            else:
                chan, line = item
            line = line.strip()
            if not line:
                continue
            try:
                msg = json.loads(line)
            except ValueError:
                continue
            if not isinstance(msg, dict):
                continue
            if not ingest(chan, msg):
                alive = False
                break
        _reap_upgrade_load()
        sched.admit()
        sched.step()
        sched.idle_backoff()
        flush_answers()
        for ev in sched.consume_swap_events():
            # The step-boundary flip (or its ckpt.swap-injected abort)
            # just happened: report it so the coordinator re-admits (or
            # aborts the rollout). ``ok``/``version``/``error`` ride
            # through verbatim.
            out.send({"type": "upgraded", **ev})
        now = time.monotonic()
        if now - last_hb >= hb_s:
            last_hb = now
            hb = {
                "type": "hb",
                "backlog": sched.backlog,
                "free": sched.num_slots - sched.active_count,
                "active": sched.active_count,
            }
            if sched.weight_version is not None:
                hb["wv"] = sched.weight_version
            if mesh_shape is not None:
                hb["mesh"] = mesh_shape
            out.send(hb)
    flush_answers()
    final = {"type": "stats", "stats": {**dict(sched.stats), **stats_extra}}
    out.send(final)
    if telemetry is not None:
        telemetry.close()


if __name__ == "__main__":
    main()
