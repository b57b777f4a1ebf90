"""Multi-replica serving front end: spawn N replica workers and route.

    python -m transformer_tpu.cli.router --replicas 2 --export_path=model \\
        --tgt_vocab_file=vocab.subwords --metrics_jsonl=/tmp/router.jsonl

Same wire contract as ``cli.serve``: one JSONL request (or raw prompt
line) per stdin line, one JSONL response per line, in request order. The
router process itself never loads the model — it owns client intake, the
prefix-affinity/least-loaded dispatch policy, heartbeat-fed liveness, and
zero-loss failover (``serve/router.py``); each replica worker
(``serve/replica.py``) is a subprocess running the continuous-batching
scheduler over its own model copy. Killing a replica mid-stream loses no
accepted request: its in-flight work is re-dispatched to survivors with
original order, trace id, and deadline intact.

With ``--metrics_jsonl=PATH`` the router logs to PATH and each replica to
``PATH.rN``; merge the fleet view with::

    python -m transformer_tpu.obs summarize PATH PATH.r0 PATH.r1
    python -m transformer_tpu.obs trace PATH PATH.r0 PATH.r1 --out t.json

``--disaggregate`` marks replica 0 prefill-only and the rest decode-only:
prompts are ingested on the prefill side and their KV handed to decode
replicas as prefix-cache blocks (docs/SERVING.md "Multi-replica router").

**Self-healing fleet** (PR 11, docs/SERVING.md "Self-healing fleet"):
``--supervise`` (default on) attaches a :class:`serve.supervisor.Supervisor`
— a SIGKILLed replica is re-bootstrapped from the same deterministic
recipe under its old name, its PrefixCache warmed from a survivor, with a
bounded restart budget (``--max_restarts`` per ``--restart_window``).
``--max_replicas N`` > the spawn count enables SLO-driven autoscaling:
sustained ``ttft_p95`` burn > 1 grows the fleet, sustained idleness
drains it back to ``--min_replicas``. ``--ha`` journals intake/delivery/
heartbeat events to ``--metrics_jsonl`` and puts replicas on takeover
control sockets so a warm standby::

    python -m transformer_tpu.cli.router --standby PATH.jsonl ...

can tail the log, detect primary death by heartbeat silence
(``--takeover_after``), adopt the fleet, and answer every in-flight
request exactly once (``serve/standby.py``).
"""

from __future__ import annotations

import json
import queue
import sys
import threading

from absl import app, flags, logging

FLAGS = flags.FLAGS


def define_router_flags() -> None:
    from transformer_tpu.cli.flags import define_metrics_flags

    define_metrics_flags()
    flags.DEFINE_integer("replicas", 2, "replica worker processes to spawn")
    flags.DEFINE_string("export_path", "model", "export directory (per replica)")
    flags.DEFINE_string("tgt_vocab_file", "tgt_vocab.subwords",
                        "target subword vocab (router affinity + replicas)")
    flags.DEFINE_string(
        "model_spec", "",
        "JSON test-model spec file (serve.replica build_model_from_spec) "
        "instead of an export — the CI/bench bootstrap")
    flags.DEFINE_boolean("kv_cache_int8", False, "int8 KV cache in replicas")
    flags.DEFINE_integer("serve_slots", 4, "KV-cache slots per replica")
    flags.DEFINE_integer("serve_max_total", 0, "per-slot KV budget")
    flags.DEFINE_integer("prefill_chunk", 0, "replica prefill chunk")
    flags.DEFINE_integer("max_len", 64, "default max_new per request")
    flags.DEFINE_integer("speculate_k", 0, "replica speculative lookahead")
    flags.DEFINE_integer("prefix_cache_mb", 64,
                         "per-replica prefix KV cache budget (0 = off)")
    flags.DEFINE_integer("prefix_block", 16, "prefix-cache block tokens")
    flags.DEFINE_enum(
        "kv_layout", "dense", ["dense", "paged"],
        "per-slot KV storage in each replica worker: dense buffers or the "
        "paged block pool with device-resident prefix aliasing "
        "(docs/SERVING.md)")
    flags.DEFINE_integer(
        "kv_pool_blocks", 0,
        "paged pool size per replica, in --prefix_block-token blocks "
        "(0 = full provisioning)")
    flags.DEFINE_string(
        "mesh", "",
        "serving mesh per replica ('N' or 'data=N'): each worker becomes "
        "one pjit program over N devices (docs/SERVING.md 'Sharded "
        "replicas'). Rides the deterministic spawn argv, so supervised "
        "respawns and scale-ups inherit the shape; heartbeats report it "
        "and the supervisor refuses a wrong-shape replacement. '' = "
        "single-device workers")
    flags.DEFINE_integer(
        "affinity_block", 0,
        "token-block granularity for prefix-affinity hashing "
        "(0 = --prefix_block); prompts sharing their leading aligned "
        "blocks route to the replica whose PrefixCache is warm")
    flags.DEFINE_integer(
        "affinity_slack", 4,
        "load gap (in-flight + heartbeat backlog) past which an affine "
        "request falls back to the least-loaded replica")
    flags.DEFINE_integer(
        "max_redispatch", 2,
        "bounded failover: redispatches per request before answering a "
        "structured 'transient' error")
    flags.DEFINE_float("heartbeat_ms", 200.0, "replica heartbeat period")
    flags.DEFINE_string(
        "fault_spec", "",
        "deterministic fault injection (docs/ROBUSTNESS.md grammar): "
        "installed in the ROUTER process (route.spawn/route.hb/"
        "route.upgrade/route.canary/route.takeover fire here) AND "
        "forwarded to every replica worker (serve.*/prefix.*/draft.*/"
        "ckpt.swap fire there)")
    flags.DEFINE_float(
        "heartbeat_timeout", 5.0,
        "seconds without a heartbeat before a replica is failed over "
        "(0 = rely on pipe EOF / process exit only)")
    flags.DEFINE_boolean(
        "disaggregate", False,
        "prefill/decode disaggregation: replica 0 ingests prompts only and "
        "hands KV blocks to decode-only peers (docs/SERVING.md)")
    # ---- self-healing fleet (serve/supervisor.py, serve/standby.py) ------
    flags.DEFINE_boolean(
        "supervise", True,
        "supervised respawn: re-bootstrap dead replicas from the same "
        "deterministic recipe under their old rendezvous name, warming "
        "the replacement's PrefixCache from a survivor before admission")
    flags.DEFINE_integer(
        "max_restarts", 3,
        "respawn budget per replica within --restart_window before the "
        "supervisor gives up (breaker stays open, fleet serves at N-1)")
    flags.DEFINE_float("restart_window", 120.0,
                       "seconds over which --max_restarts is counted")
    flags.DEFINE_float("spawn_backoff_ms", 200.0,
                       "base exponential backoff between respawn attempts")
    flags.DEFINE_integer(
        "warm_prefixes", 8,
        "hottest survivor PrefixCache prefixes exported to warm a "
        "respawned replica (0 = admit cold)")
    flags.DEFINE_integer(
        "max_replicas", 0,
        "SLO-driven autoscaling ceiling: > --replicas enables scale-up on "
        "sustained ttft_p95 burn > 1 and idle drain back down "
        "(0 = fixed fleet)")
    flags.DEFINE_integer("min_replicas", 1, "autoscaling floor")
    flags.DEFINE_string(
        "scale_signal", "ttft_p95",
        "the SLO whose burn rate drives scale-up (must name an objective "
        "in --slo_spec / the defaults)")
    flags.DEFINE_float("scale_sustain", 5.0,
                       "seconds of sustained burn > 1 before a scale-up")
    flags.DEFINE_float("scale_idle", 30.0,
                       "seconds of sustained idleness before a drain")
    flags.DEFINE_float("scale_cooldown", 15.0,
                       "seconds between consecutive scaling decisions")
    flags.DEFINE_string(
        "slo_spec", "",
        "SLO objectives for the router's own burn-rate engine (obs/slo.py "
        "grammar; '' = defaults when autoscaling is on; 'none' disables)")
    flags.DEFINE_boolean(
        "ha", False,
        "router HA primary: journal intake/delivery/heartbeat events to "
        "--metrics_jsonl and give replicas takeover control sockets so a "
        "warm standby (--standby) can adopt the fleet")
    # ---- live-weights rollout (serve/upgrade.py) --------------------------
    flags.DEFINE_string(
        "upgrade", "",
        "start a rolling weight swap to this manifest-verified checkpoint "
        "at startup (docs/SERVING.md 'Live-weights rollout'); at runtime "
        "a control line {\"upgrade\": \"<ckpt>\"} on stdin does the same")
    flags.DEFINE_float(
        "canary_window", 5.0,
        "seconds the first upgraded replica serves its pinned traffic "
        "slice before the rollout promotes (clean) or rolls back (burn)")
    flags.DEFINE_integer(
        "canary_every", 0,
        "pin every Nth accepted order to the canary during its window "
        "(0 = the fleet size at rollout start)")
    flags.DEFINE_string(
        "canary_slo", "",
        "SLO objectives for the per-weight-version canary verdict "
        "(obs/slo.py grammar; '' = short-window availability + ttft_p95)")
    flags.DEFINE_string(
        "standby", "",
        "run as the warm STANDBY for the primary whose --metrics_jsonl is "
        "this path: tail its journal, adopt the fleet when its heartbeat "
        "goes silent, then serve from this process's stdin")
    flags.DEFINE_float(
        "takeover_after", 2.0,
        "standby: seconds of primary heartbeat silence before takeover")


def worker_args_from_flags(replica_jsonl: str = "") -> list[str]:
    """The replica-worker argv tail shared by every spawned process."""
    out = [
        "--serve_slots", str(FLAGS.serve_slots),
        "--serve_max_total", str(FLAGS.serve_max_total),
        "--prefill_chunk", str(FLAGS.prefill_chunk),
        "--max_len", str(FLAGS.max_len),
        "--speculate_k", str(FLAGS.speculate_k),
        "--prefix_cache_mb", str(FLAGS.prefix_cache_mb),
        "--prefix_block", str(FLAGS.prefix_block),
        "--kv_layout", FLAGS.kv_layout,
        "--kv_pool_blocks", str(FLAGS.kv_pool_blocks),
        "--heartbeat_ms", str(FLAGS.heartbeat_ms),
    ]
    if FLAGS.model_spec:
        out += ["--model_spec", FLAGS.model_spec]
    else:
        out += ["--export_path", FLAGS.export_path,
                "--tgt_vocab_file", FLAGS.tgt_vocab_file]
        if FLAGS.kv_cache_int8:
            out += ["--kv_cache_int8"]
    if replica_jsonl:
        out += ["--metrics_jsonl", replica_jsonl]
        if FLAGS.trace:
            out += ["--trace"]
    if FLAGS.mesh:
        out += ["--mesh", FLAGS.mesh]
    if FLAGS.fault_spec:
        out += ["--fault_spec", FLAGS.fault_spec]
    if FLAGS.ha or FLAGS.standby:
        out += ["--ha"]
    return out




def route_lines(q: "queue.Queue", router) -> None:
    """Drive the router from the stdin queue: parse lines (malformed/
    wrong-kind ones answer immediately at a reserved order), pump
    dispatch/answers, flush responses in arrival order — the
    ``serve_continuous`` loop shape, one tier up."""
    from transformer_tpu.serve.router import _RouterLineError, parse_router_line

    eof = False
    while not eof or router.busy:
        while not eof:
            try:
                line = q.get_nowait()
            except queue.Empty:
                break
            if line is None:
                eof = True
                break
            line = line.strip()
            if not line:
                continue
            if line.startswith("{") and '"upgrade"' in line:
                # Control line: {"upgrade": "<ckpt_dir>"} starts a rolling
                # weight swap (serve/upgrade.py) and answers the
                # coordinator's status dict at a reserved order — the
                # operator sees the verified version (or the structured
                # refusal) inline with the response stream.
                try:
                    obj = json.loads(line)
                except ValueError:
                    obj = None
                if (
                    isinstance(obj, dict) and "upgrade" in obj
                    and "prompt" not in obj
                ):
                    status = router.start_upgrade(str(obj["upgrade"]))
                    router.submit_done(
                        {"upgrade": str(obj["upgrade"]), **status}
                    )
                    continue
            try:
                req = parse_router_line(line)
            except _RouterLineError as e:
                # Bare message — byte-identical to the grouped path's
                # kind-mismatch answer (cli/serve.py parity).
                router.submit_done({"error": str(e), "code": "routing"})
                continue
            except Exception as e:  # noqa: BLE001 — bad line answers, never kills
                router.submit_done({
                    "error": f"{type(e).__name__}: {e}", "code": "validation",
                })
                continue
            router.submit(req)
        router.pump()
        for resp in router.drain_ready():
            print(json.dumps(resp), flush=True)


def _load_tokenizer():
    # Affinity hashing needs only the tokenizer — the router never loads
    # the model or compiles a program, so it restarts cheaply and
    # survives replica OOMs.
    from transformer_tpu.data.tokenizer import SubwordTokenizer

    if FLAGS.model_spec:
        with open(FLAGS.model_spec) as f:
            spec = json.load(f)
        return SubwordTokenizer.build_from_corpus(
            list(spec["corpus"]),
            target_vocab_size=int(spec.get("target_vocab_size", 300)),
        )
    return SubwordTokenizer.load(FLAGS.tgt_vocab_file)


def _spawn_recipe():
    """The supervisor's deterministic re-bootstrap callable: the SAME
    worker argv the original fleet used, under the replica's old name —
    rendezvous hashing re-offers the replacement its predecessor's keys.
    When a live-weights rollout has set the fleet's target
    (``Router.weight_target``), the replacement bootstraps from that
    checkpoint (``--init_ckpt``, manifest-verified) instead of the argv
    weights — a heal mid- or post-rollout must never resurrect stale
    weights."""
    from transformer_tpu.serve.router import ReplicaProcess

    def spawn(index: int, name: str, role: str, weight_target=None):
        replica_jsonl = (
            f"{FLAGS.metrics_jsonl}.r{index}" if FLAGS.metrics_jsonl else ""
        )
        argv = worker_args_from_flags(replica_jsonl)
        if weight_target is not None:
            ckpt_dir, version = weight_target
            argv += ["--init_ckpt", ckpt_dir, "--weight_version", version]
        return ReplicaProcess.spawn(index, argv, role=role, name=name)

    return spawn


def _supervision_kwargs() -> dict:
    """Supervisor / FleetScaler / SLO kwargs shared by the primary and an
    adopting standby (the standby becomes a first-class primary)."""
    from transformer_tpu.serve.supervisor import FleetScaler, Supervisor

    from transformer_tpu.serve.upgrade import UpgradeCoordinator

    out: dict = {
        # The live-weights rollout coordinator is always attached: the
        # --upgrade flag and the control line both drive it, and an idle
        # coordinator costs one no-op poll per pump.
        "upgrader": UpgradeCoordinator(
            canary_window_s=FLAGS.canary_window,
            canary_every=FLAGS.canary_every,
            canary_slos=FLAGS.canary_slo or None,
        ),
    }
    if FLAGS.supervise:
        from transformer_tpu.serve.sharded import normalize_mesh_spec

        out["supervisor"] = Supervisor(
            _spawn_recipe(),
            max_restarts=FLAGS.max_restarts,
            restart_window_s=FLAGS.restart_window,
            backoff_ms=FLAGS.spawn_backoff_ms,
            warm_prefixes=FLAGS.warm_prefixes,
            # Canonicalized ('data=N') so the flag spelling can never
            # alias into a false wrong-shape refusal.
            expected_mesh=normalize_mesh_spec(FLAGS.mesh),
        )
    slo_spec = FLAGS.slo_spec
    autoscale = FLAGS.supervise and FLAGS.max_replicas > 0
    if slo_spec.lower() in ("none", "off"):
        slo_spec = ""
        autoscale = False
    if autoscale:
        out["scaler"] = FleetScaler(
            signal=FLAGS.scale_signal,
            sustain_s=FLAGS.scale_sustain,
            idle_s=FLAGS.scale_idle,
            max_replicas=FLAGS.max_replicas,
            min_replicas=FLAGS.min_replicas,
            cooldown_s=FLAGS.scale_cooldown,
        )
    if slo_spec:
        out["slos"] = slo_spec
    elif autoscale:
        from transformer_tpu.obs.slo import DEFAULT_SLOS

        out["slos"] = DEFAULT_SLOS
    if autoscale:
        # A watched signal missing from the objective set would pin the
        # scale-up burn to 0 forever while idle drain kept working — a
        # silently one-directional autoscaler. Fail loudly at startup.
        from transformer_tpu.obs.slo import parse_slo_spec

        specs = (
            parse_slo_spec(out["slos"])
            if isinstance(out["slos"], str) else out["slos"]
        )
        names = {s.name for s in specs}
        if FLAGS.scale_signal not in names:
            raise ValueError(
                f"--scale_signal {FLAGS.scale_signal!r} is not among the "
                f"SLO objectives {sorted(names)}; scale-up could never "
                "trigger"
            )
    return out


def _serve_stdin(router, telemetry) -> None:
    from transformer_tpu.serve.replica import stdin_reader

    q: queue.Queue = queue.Queue(
        maxsize=max(1, FLAGS.serve_slots * max(1, len(router.links))) * 8
    )
    threading.Thread(target=stdin_reader, args=(q,), daemon=True).start()
    try:
        route_lines(q, router)
    finally:
        router.shutdown()
        if telemetry is not None:
            telemetry.close()


def main(argv) -> None:
    del argv
    from transformer_tpu.cli.flags import flags_to_telemetry
    from transformer_tpu.serve.router import ReplicaProcess, Router

    if FLAGS.fault_spec:
        from transformer_tpu.serve import resilience

        resilience.install(resilience.FaultPlane.parse(FLAGS.fault_spec))
    telemetry = flags_to_telemetry()
    tok = _load_tokenizer()

    if FLAGS.standby:
        # Warm standby: tail the primary's journal until its heartbeat
        # goes silent, adopt the fleet, then serve from OUR stdin.
        from transformer_tpu.serve.standby import Standby

        if telemetry is None:
            logging.warning(
                "--standby without --metrics_jsonl: after adopting, this "
                "router writes no journal — the NEXT standby will have "
                "nothing to tail"
            )

        standby = Standby(
            FLAGS.standby,
            takeover_after_s=FLAGS.takeover_after,
            encode=tok.encode,
            bos_id=tok.bos_id,
            telemetry=telemetry,
            router_kwargs=dict(
                affinity_block=FLAGS.affinity_block or FLAGS.prefix_block,
                affinity_slack=FLAGS.affinity_slack,
                max_redispatch=FLAGS.max_redispatch,
                heartbeat_timeout_s=FLAGS.heartbeat_timeout,
                **_supervision_kwargs(),
            ),
        )
        logging.info(
            "standby up: tailing %s (takeover after %.1fs of silence)",
            FLAGS.standby, FLAGS.takeover_after,
        )
        router = standby.run_until_takeover()
        logging.info(
            "adopted the fleet as epoch %d: %s", router.epoch,
            standby.stats,
        )
        _serve_stdin(router, telemetry)
        return

    ha = FLAGS.ha
    if ha and telemetry is None:
        # The HA journal IS the event log — a standby cannot adopt what
        # was never written. Warn like --trace does, don't silently no-op.
        # Write the decision back into FLAGS so the worker argv agrees:
        # a worker spawned with --ha would survive this router's death as
        # a permanent orphan no standby could ever find.
        logging.warning(
            "--ha needs --metrics_jsonl for the standby journal; disabling"
        )
        ha = False
        FLAGS.ha = False

    n = max(1, FLAGS.replicas)
    # Refuse a fleet that outgrows the host's chips before any worker
    # starts (each spawn checks its own index again, for scale-ups).
    from transformer_tpu.serve.router import count_tpu_chips, replica_chip_env
    from transformer_tpu.serve.sharded import parse_mesh_spec

    replica_chip_env(n - 1, parse_mesh_spec(FLAGS.mesh) or 1, count_tpu_chips())
    links = []
    for i in range(n):
        role = "both"
        if FLAGS.disaggregate:
            role = "prefill" if i == 0 else "decode"
        replica_jsonl = (
            f"{FLAGS.metrics_jsonl}.r{i}" if FLAGS.metrics_jsonl else ""
        )
        links.append(
            ReplicaProcess.spawn(
                i, worker_args_from_flags(replica_jsonl), role=role,
            )
        )
    router = Router(
        links,
        encode=tok.encode,
        bos_id=tok.bos_id,
        affinity_block=FLAGS.affinity_block or FLAGS.prefix_block,
        affinity_slack=FLAGS.affinity_slack,
        max_redispatch=FLAGS.max_redispatch,
        heartbeat_timeout_s=FLAGS.heartbeat_timeout,
        disaggregate=FLAGS.disaggregate,
        telemetry=telemetry,
        ha=ha,
        **_supervision_kwargs(),
    )
    for link in links:
        link.start_reader(router.inbox)
    logging.info(
        "router up: %d replica(s) x %d slots, affinity block %d%s%s%s",
        n, FLAGS.serve_slots, FLAGS.affinity_block or FLAGS.prefix_block,
        ", disaggregated prefill/decode" if FLAGS.disaggregate else "",
        ", supervised" if FLAGS.supervise else "",
        ", HA journal on" if ha else "",
    )
    if FLAGS.upgrade:
        status = router.start_upgrade(FLAGS.upgrade)
        if status.get("ok"):
            logging.info(
                "rolling upgrade started: %s -> version %s",
                FLAGS.upgrade, status.get("version"),
            )
        else:
            logging.error("upgrade refused: %s", status.get("error"))
    _serve_stdin(router, telemetry)


def run() -> None:
    define_router_flags()
    app.run(main)


if __name__ == "__main__":
    run()
