"""Multi-replica router microbenchmark: the ROADMAP scale-out numbers.

CPU-runnable, like decode_bench.py (its times on a CPU are not device
metrics): a repeated-system-prompt workload — every request carries one of a few
shared system prompts plus a small unique tail — through the real
subprocess serving tier (``cli.router``'s building blocks: one
``serve/router.py`` Router over N ``serve/replica.py`` workers), swept
across 1/2/4 replicas.

    JAX_PLATFORMS=cpu python benchmarks/router_bench.py

Prints ONE summary JSON line per replica count and appends
``bench_rows.jsonl``-compatible rows (``--rows_out``) carrying the
acceptance numbers:

- **router p99 queue latency** (submit -> first dispatch) — the router
  must not become the serialization point as replicas multiply;
- **per-replica prefix hit rate** — prefix-affinity dispatch is what
  keeps the per-replica ``PrefixCache`` warm, so the hit rate should
  survive scale-out instead of diluting 1/N;
- **redispatch count** — with ``--kill`` (default when replicas > 1) one
  replica is SIGKILLed mid-workload: every accepted request must still
  answer (zero loss), and the row pins how many rode the failover path.
- **time-to-heal** — with ``--heal`` (default) an extra soak runs the
  2-replica fleet under a Supervisor, SIGKILLs one replica mid-run, and
  rows the death-to-readmission seconds plus how many requests the
  surviving fleet answered during the gap (the self-healing tier's
  acceptance numbers, docs/SERVING.md "Self-healing fleet").
- **time-to-upgrade** — with ``--upgrade`` (default) another soak rolls a
  manifest-verified checkpoint swap across the fleet MID-RUN (quiesce ->
  double-buffered swap -> canary window -> promote): the row records the
  rollout wall time, requests served during it, the canary's request
  share, and zero lost/errored requests (the live-weights control
  plane's acceptance numbers, docs/SERVING.md "Live-weights rollout").
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SPEC = {
    "config": {
        "num_layers": 2, "d_model": 32, "num_heads": 2, "dff": 64,
        "max_position": 96, "decoder_only": True, "tie_output": True,
        "dtype": "float32", "dropout_rate": 0.0,
    },
    "seed": 0,
    "corpus": ["ab cd ef gh ij kl mn op qr st uv wx"] * 3,
    "target_vocab_size": 300,
}
WORDS = SPEC["corpus"][0].split()


def _workload(n_requests: int, n_systems: int, system_words: int):
    """Repeated-system-prompt requests: request i carries system prompt
    ``i % n_systems`` plus a 2-word unique-ish tail."""
    reqs = []
    for i in range(n_requests):
        s = i % n_systems
        system = " ".join(
            WORDS[(s + j) % len(WORDS)] for j in range(system_words)
        )
        tail = f"{WORDS[i % len(WORDS)]} {WORDS[(i * 5 + 1) % len(WORDS)]}"
        reqs.append({"prompt": f"{system} {tail}", "max_new": 4})
    return reqs


def _p(q: list[float], frac: float) -> float:
    if not q:
        return 0.0
    s = sorted(q)
    return s[min(len(s) - 1, int(frac * len(s)))]


_STEP_COST_CACHE: dict = {}


def _pool_step_bytes(kv_layout: str, slots: int, kv_block: int) -> int:
    """Cost-model bytes_moved for the replicas' batched pool step — the
    program the workers' profilers clock — on the SPEC model at replica
    defaults (max_total = max_position + 1, full paged provisioning), so
    the measured p50 and the prediction describe the same dispatch."""
    key = (kv_layout, slots, kv_block)
    if key in _STEP_COST_CACHE:
        return _STEP_COST_CACHE[key]
    import jax.numpy as jnp

    from transformer_tpu.analysis.costs import program_costs
    from transformer_tpu.serve.replica import build_model_from_spec

    params, cfg, _ = build_model_from_spec(SPEC)
    max_total = cfg.max_position + 1
    if kv_layout == "paged":
        from transformer_tpu.serve.scheduler import (
            _pool_step_paged,
            abstract_paged_pool,
        )

        slot_blocks = -(-max_total // kv_block)
        pool_blocks = 1 + slots * slot_blocks
        raw = program_costs(
            "bench",
            lambda p, c, tb, ix, t: _pool_step_paged.__wrapped__(
                p, c, tb, ix, t, cfg, kv_block, max_total
            ),
            params,
            *abstract_paged_pool(
                cfg, slots, max_total, pool_blocks, kv_block
            ),
            jnp.zeros((slots,), jnp.int32),
            donate_argnums=(1,),
        )
    else:
        from transformer_tpu.serve.scheduler import (
            _pool_step,
            abstract_pool_caches,
        )

        raw = program_costs(
            "bench",
            lambda p, c, t: _pool_step.__wrapped__(p, c, t, cfg),
            params,
            abstract_pool_caches(cfg, slots, max_total),
            jnp.zeros((slots,), jnp.int32),
            donate_argnums=(1,),
        )
    _STEP_COST_CACHE[key] = raw.bytes_moved
    return raw.bytes_moved


def run_sweep(n_replicas: int, args, spec_path: str) -> dict:
    from transformer_tpu.serve.replica import build_model_from_spec
    from transformer_tpu.serve.router import ReplicaProcess, Router

    _, _, tok = build_model_from_spec(SPEC)
    worker = [
        "--model_spec", spec_path,
        "--serve_slots", str(args.slots),
        "--prefix_cache_mb", "32",
        "--prefix_block", str(args.prefix_block),
        "--kv_layout", getattr(args, "kv_layout", "dense"),
        "--heartbeat_ms", "100",
    ]
    # Per-replica metrics JSONL: arms each worker's profiler (+ flight
    # recorder), so the shutdown report carries the measured per-program
    # perf rows the roofline columns join against.
    obs_dir = tempfile.mkdtemp(prefix="router_bench_obs_")
    links = [
        ReplicaProcess.spawn(
            i,
            worker + [
                "--metrics_jsonl", os.path.join(obs_dir, f"replica{i}.jsonl"),
            ],
        )
        for i in range(n_replicas)
    ]
    router = Router(
        links, encode=tok.encode, bos_id=tok.bos_id,
        affinity_block=args.prefix_block, heartbeat_timeout_s=10.0,
    )
    for link in links:
        link.start_reader(router.inbox)

    reqs = _workload(args.requests, max(1, n_replicas), args.system_words)
    kill = args.kill and n_replicas > 1
    t0 = time.perf_counter()
    for r in reqs:
        router.submit(dict(r))
    answered = []
    killed = False
    deadline = time.time() + 300
    while router.busy and time.time() < deadline:
        router.pump()
        answered.extend(router.drain_ready())
        if kill and not killed and len(answered) >= args.requests // 4:
            victim = max(router.links, key=lambda l: l.inflight)
            if victim.inflight > 0:
                os.kill(victim.pid(), signal.SIGKILL)
                killed = True
    answered.extend(router.drain_ready())
    wall = time.perf_counter() - t0
    ok = sum(1 for a in answered if "continuation" in a)

    # Per-replica prefix accounting from the workers' shutdown reports.
    for link in router.links:
        if not link.dead:
            try:
                link.send({"type": "shutdown"})
            except (OSError, ValueError):
                pass
    stats_deadline = time.time() + 15
    while time.time() < stats_deadline and any(
        l.final_stats is None and not l.dead for l in router.links
    ):
        router.pump(timeout=0.05)
    per_replica = {}
    for link in router.links:
        st = link.final_stats or {}
        prompt = int(st.get("prompt_tokens", 0))
        hit = int(st.get("prefix_hit_tokens", 0))
        per_replica[link.name] = {
            "requests": link.answered,
            "prefix_hit_rate": round(hit / prompt, 4) if prompt else None,
            "prefill_forwards": st.get("prefill_forwards"),
            # Paged workers (--kv_layout paged): hit tokens restored by
            # device-side block-table ALIASING (zero host copies) vs
            # through a host block write.
            "prefix_alias_tokens": st.get("prefix_alias_tokens"),
            "host_restored_tokens": st.get("host_restored_tokens"),
            "killed": link.dead,
        }
    # Measured-vs-predicted roofline for the batched pool step, from the
    # workers' final perf reports (median p50 across the surviving
    # replicas) joined against the cost model's bytes_moved.
    from transformer_tpu.obs.profile import roofline_ratio

    step_prog = (
        "serve.pool_step_paged" if args.kv_layout == "paged"
        else "serve.pool_step"
    )
    step_p50s = []
    for link in router.links:
        perf = (link.final_perf or {}).get(step_prog) or {}
        per_replica[link.name]["measured_step_p50_ms"] = perf.get("p50_ms")
        if perf.get("p50_s"):
            step_p50s.append(perf["p50_s"])
    step_bytes = _pool_step_bytes(args.kv_layout, args.slots, args.prefix_block)
    step_p50_s = (
        sorted(step_p50s)[len(step_p50s) // 2] if step_p50s else None
    )
    router.shutdown()
    # Named by the replicas themselves (their ready lines): this parent
    # never asks jax for a device — the chips belong to the workers.
    device = next((l.device for l in router.links if l.device), None) or {}
    return {
        "device": f"{device.get('platform')}:{device.get('kind')}",
        "device_kind": device.get("kind"),
        "replicas": n_replicas,
        "requests": len(reqs),
        "answered": len(answered),
        "answered_ok": ok,
        "wall_s": round(wall, 3),
        "requests_per_sec": round(len(reqs) / wall, 2),
        "queue_p50_s": round(_p(router.queue_latencies, 0.50), 6),
        "queue_p99_s": round(_p(router.queue_latencies, 0.99), 6),
        "redispatch_count": router.stats["redispatched"],
        "failovers": router.stats["failovers"],
        "killed_one": killed,
        "predicted_bytes_moved": step_bytes,
        "measured_step_p50_ms": (
            round(step_p50_s * 1e3, 6) if step_p50_s else None
        ),
        # None on a device with no entry in the peak table (the CPU).
        "roofline_ratio": roofline_ratio(
            step_bytes, step_p50_s or 0.0, device.get("kind")
        ),
        "per_replica": per_replica,
    }


def run_mesh_parity(args, spec_path: str) -> dict:
    """Sharded-replica byte-parity soak (serve/sharded.py, ``--mesh``):
    the SAME workload — greedy AND seeded-sampled requests — through
    single-replica fleets at mesh 1/2/4 must answer byte-identically to
    an UNSHARDED replica. Each worker grows its own virtual CPU platform
    from ``--mesh`` (replica.py appends xla_force_host_platform_device_count
    before importing jax), so the sweep runs on any host."""
    from transformer_tpu.serve.replica import build_model_from_spec
    from transformer_tpu.serve.router import ReplicaProcess, Router

    _, _, tok = build_model_from_spec(SPEC)
    reqs = _workload(16, 2, args.system_words)
    for i, r in enumerate(reqs):
        if i % 3 == 0:  # every third request is seeded-sampled
            r.update(temperature=0.8, top_k=8, seed=i)
    slots = 4  # divides every mesh in the sweep

    def serve(mesh):
        worker = [
            "--model_spec", spec_path,
            "--serve_slots", str(slots),
            "--heartbeat_ms", "100",
        ]
        if mesh:
            worker += ["--mesh", str(mesh)]
        link = ReplicaProcess.spawn(0, worker)
        router = Router(
            [link], encode=tok.encode, bos_id=tok.bos_id,
            heartbeat_timeout_s=30.0,
        )
        link.start_reader(router.inbox)
        t0 = time.perf_counter()
        out = router.run([dict(r) for r in reqs])
        wall = time.perf_counter() - t0
        reported = link.mesh
        router.shutdown()
        return [o.get("continuation") for o in out], wall, reported

    want, _, base_mesh = serve(None)
    assert base_mesh is None and all(c is not None for c in want), want
    meshes = {}
    for mesh in (1, 2, 4):
        got, wall, reported = serve(mesh)
        assert got == want, (
            f"mesh={mesh} answers diverged from the unsharded replica"
        )
        assert reported == f"data={mesh}", (
            f"replica announced mesh {reported!r}, expected data={mesh}"
        )
        meshes[str(mesh)] = {
            "mesh": f"data={mesh}",
            "wall_s": round(wall, 3),
            "requests_per_sec": round(len(reqs) / wall, 2),
            "byte_parity": True,
        }
    return {
        "requests": len(reqs),
        "sampled_requests": sum(1 for r in reqs if "temperature" in r),
        "meshes": meshes,
    }


def run_heal(args, spec_path: str) -> dict:
    """The self-healing soak: 2 supervised replicas, SIGKILL one mid-run,
    measure death -> readmission and what the gap cost."""
    from transformer_tpu.serve.replica import build_model_from_spec
    from transformer_tpu.serve.router import ReplicaProcess, Router
    from transformer_tpu.serve.supervisor import Supervisor

    _, _, tok = build_model_from_spec(SPEC)
    worker = [
        "--model_spec", spec_path,
        "--serve_slots", str(args.slots),
        "--prefix_cache_mb", "32",
        "--prefix_block", str(args.prefix_block),
        "--kv_layout", getattr(args, "kv_layout", "dense"),
        "--heartbeat_ms", "100",
    ]
    n_replicas = 2
    # Per-replica metrics JSONL: the victim's flight recorder autodumps
    # next to it, which is what the supervisor's postmortem capture
    # salvages after the SIGKILL (respawns for the same index reuse the
    # path — the event log appends, the dump is rewritten).
    obs_dir = tempfile.mkdtemp(prefix="router_heal_obs_")

    def _argv(i):
        return list(worker) + [
            "--metrics_jsonl", os.path.join(obs_dir, f"replica{i}.jsonl"),
        ]

    links = [ReplicaProcess.spawn(i, _argv(i)) for i in range(n_replicas)]

    def spawn(index, name, role):
        return ReplicaProcess.spawn(index, _argv(index), role=role, name=name)

    sup = Supervisor(spawn, backoff_ms=50.0)
    router = Router(
        links, encode=tok.encode, bos_id=tok.bos_id,
        affinity_block=args.prefix_block, heartbeat_timeout_s=10.0,
        supervisor=sup,
    )
    for link in links:
        link.start_reader(router.inbox)

    reqs = _workload(args.requests, n_replicas, args.system_words)
    t0 = time.perf_counter()
    for r in reqs:
        router.submit(dict(r))
    answered = []
    killed = False
    gap_served = 0
    deadline = time.time() + 300
    while (
        router.busy or (killed and sup.stats["respawns"] < 1)
    ) and time.time() < deadline:
        router.pump()
        fresh = router.drain_ready()
        answered.extend(fresh)
        if killed and sup.stats["respawns"] < 1:
            # The gap: between the SIGKILL and the replacement's
            # admission, the surviving fleet carries the whole workload.
            gap_served += len(fresh)
        if not killed and len(answered) >= args.requests // 4:
            victim = max(router.links, key=lambda l: l.inflight)
            if victim.inflight > 0:
                os.kill(victim.pid(), signal.SIGKILL)
                killed = True
    answered.extend(router.drain_ready())
    wall = time.perf_counter() - t0
    router.shutdown()
    heal_s = sup.heal_times[0] if sup.heal_times else None
    return {
        "mode": "heal",
        "replicas": n_replicas,
        "requests": len(reqs),
        "answered": len(answered),
        "answered_ok": sum(1 for a in answered if "continuation" in a),
        "wall_s": round(wall, 3),
        "killed_one": killed,
        "time_to_heal_s": None if heal_s is None else round(heal_s, 3),
        "served_during_gap": gap_served,
        "warmed_tokens": sup.stats["warmed_tokens"],
        "respawns": sup.stats["respawns"],
        "postmortems": sup.stats["postmortems"],
        "redispatch_count": router.stats["redispatched"],
    }


def run_upgrade(args, spec_path: str) -> dict:
    """The live-weights soak: roll a verified checkpoint swap across a
    2-replica fleet mid-workload; every request answers, tagged by the
    weight_version that served it, with zero recompiles replica-side."""
    import tempfile as _tempfile

    from transformer_tpu.serve.replica import build_model_from_spec
    from transformer_tpu.serve.router import ReplicaProcess, Router
    from transformer_tpu.serve.supervisor import Supervisor
    from transformer_tpu.serve.upgrade import UpgradeCoordinator
    from transformer_tpu.train.checkpoint import CheckpointManager

    old_params, _, tok = build_model_from_spec(SPEC)
    # The upgrade artifact: the SAME architecture initialized from a
    # different seed, saved with the checksummed manifest — byte-different
    # weights, structurally a twin (the zero-recompile precondition). The
    # fleet also BOOTSTRAPS from a manifest-verified checkpoint of the
    # old weights, so every answer is version-tagged end to end.
    new_params, _, _ = build_model_from_spec({**SPEC, "seed": 1})
    old_root = _tempfile.mkdtemp(prefix="upgrade_old_")
    old_dir = CheckpointManager(old_root, is_primary=True).save(
        old_params, step=1
    )
    ckpt_root = _tempfile.mkdtemp(prefix="upgrade_ckpt_")
    ckpt_dir = CheckpointManager(ckpt_root, is_primary=True).save(
        new_params, step=1
    )

    worker = [
        "--model_spec", spec_path,
        "--init_ckpt", old_dir,
        "--serve_slots", str(args.slots),
        "--prefix_cache_mb", "32",
        "--prefix_block", str(args.prefix_block),
        "--kv_layout", getattr(args, "kv_layout", "dense"),
        "--heartbeat_ms", "100",
    ]
    n_replicas = 2
    links = [ReplicaProcess.spawn(i, list(worker)) for i in range(n_replicas)]

    def spawn(index, name, role, weight_target=None):
        argv = list(worker)
        if weight_target is not None:
            argv += ["--init_ckpt", weight_target[0],
                     "--weight_version", weight_target[1]]
        return ReplicaProcess.spawn(index, argv, role=role, name=name)

    sup = Supervisor(spawn, backoff_ms=50.0)
    up = UpgradeCoordinator(canary_window_s=0.5, canary_min_requests=1)
    router = Router(
        links, encode=tok.encode, bos_id=tok.bos_id,
        affinity_block=args.prefix_block, heartbeat_timeout_s=10.0,
        supervisor=sup, upgrader=up,
    )
    for link in links:
        link.start_reader(router.inbox)

    reqs = _workload(args.requests, n_replicas, args.system_words)
    t0 = time.perf_counter()
    # LIVE traffic, not a pre-loaded batch: keep a bounded window of
    # requests outstanding so the rollout quiesces replicas against a
    # stream (and the canary window has traffic to judge), the shape a
    # production swap actually runs under.
    window = max(2, args.slots)
    next_req = 0
    answered = []
    started = False
    t_up0 = t_up1 = None
    rollout_served = 0
    deadline = time.time() + 300
    while (
        len(answered) < len(reqs) or (started and up.active)
    ) and time.time() < deadline:
        while next_req < len(reqs) and router.backlog < window:
            router.submit(dict(reqs[next_req]))
            next_req += 1
        router.pump()
        fresh = router.drain_ready()
        answered.extend(fresh)
        if started and up.active:
            rollout_served += len(fresh)
        if not started and len(answered) >= args.requests // 4:
            status = router.start_upgrade(ckpt_root)
            assert status.get("ok"), f"upgrade refused: {status}"
            started = True
            t_up0 = time.perf_counter()
        if started and t_up1 is None and not up.active:
            t_up1 = time.perf_counter()
    answered.extend(router.drain_ready())
    wall = time.perf_counter() - t0
    if started and t_up1 is None and not up.active:
        t_up1 = time.perf_counter()
    router.shutdown()
    versions: dict = {}
    for a in answered:
        v = a.get("weight_version")
        if v is not None:
            versions[v] = versions.get(v, 0) + 1
    return {
        "mode": "upgrade",
        "replicas": n_replicas,
        "requests": len(reqs),
        "answered": len(answered),
        "answered_ok": sum(1 for a in answered if "continuation" in a),
        "wall_s": round(wall, 3),
        "upgrade_state": up.state,
        "version": up.target_version,
        "time_to_upgrade_s": (
            None if t_up0 is None or t_up1 is None
            else round(t_up1 - t_up0, 3)
        ),
        "served_during_rollout": rollout_served,
        "canary_requests": up.stats["canary_requests"],
        "canary_share": (
            round(up.stats["canary_requests"] / rollout_served, 4)
            if rollout_served else None
        ),
        "rollbacks": up.stats["rollbacks"],
        "per_version_answers": versions,
        "ckpt": ckpt_dir,
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--replica_counts", type=str, default="1,2,4")
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--system_words", type=int, default=8,
                   help="shared system-prompt length in words")
    p.add_argument("--slots", type=int, default=2)
    p.add_argument("--prefix_block", type=int, default=4)
    p.add_argument("--kv_layout", choices=("dense", "paged"), default="dense",
                   help="replica KV storage; 'paged' makes repeated-system-"
                        "prompt hits device-side block-table aliases "
                        "(prefix_alias_tokens > 0 in the row)")
    p.add_argument("--kill", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="SIGKILL one replica mid-workload (replicas > 1) "
                        "to pin the zero-loss failover numbers")
    p.add_argument("--heal", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="run the supervised-respawn soak: SIGKILL one of "
                        "2 supervised replicas mid-run and row the "
                        "time-to-heal + requests served during the gap")
    p.add_argument("--upgrade", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="run the live-weights soak: roll a verified "
                        "checkpoint swap across 2 replicas mid-run and "
                        "row time-to-upgrade, requests served during the "
                        "rollout, and the canary share")
    p.add_argument("--mesh_parity", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="run the sharded-replica soak: the same greedy + "
                        "seeded-sampled workload through --mesh 1/2/4 "
                        "single-replica fleets, byte-parity asserted "
                        "against an unsharded replica, one row per mesh")
    p.add_argument("--rows_out", type=str, default="",
                   help="append bench_rows.jsonl-compatible rows here "
                        "('' = print them to stderr)")
    args = p.parse_args()

    # This parent uses JAX for host work only (cost-model traces, the
    # upgrade soak's checkpoints): pin ITS platform to the CPU so every chip
    # stays free for the replica processes, one process per chip.
    # jax.config does not reach the children's environment.
    import jax

    jax.config.update("jax_platforms", "cpu")
    device = None  # named by the first sweep's replicas
    fd, spec_path = tempfile.mkstemp(suffix=".json")
    with os.fdopen(fd, "w") as f:
        json.dump(SPEC, f)
    rows = []
    try:
        for n in [int(x) for x in args.replica_counts.split(",") if x.strip()]:
            result = run_sweep(n, args, spec_path)
            print(json.dumps(result))
            assert result["answered"] == result["requests"], (
                "router lost requests"
            )
            assert result["measured_step_p50_ms"], (
                f"no measured pool-step p50 from the fleet: {result}"
            )
            device = device or result["device"]
            hit_rates = [
                r["prefix_hit_rate"]
                for r in result["per_replica"].values()
                if r["prefix_hit_rate"] is not None
            ]
            alias_tokens = sum(
                int(r.get("prefix_alias_tokens") or 0)
                for r in result["per_replica"].values()
            )
            rows.append(json.dumps({
                "metric": "router p99 queue latency",
                "value": result["queue_p99_s"],
                "unit": "s",
                "config": {
                    "replicas": n, "slots": args.slots,
                    "requests": args.requests,
                    "system_words": args.system_words,
                    "prefix_block": args.prefix_block,
                    "kv_layout": args.kv_layout,
                    "killed_one": result["killed_one"],
                },
                "requests_per_sec": result["requests_per_sec"],
                "prefix_hit_rate_per_replica": hit_rates,
                # The aliased hit path: > 0 means repeated system prompts
                # were restored device-side with zero host<->device copies
                # (paged workers only; dense workers report 0).
                "prefix_alias_tokens": alias_tokens,
                "redispatch_count": result["redispatch_count"],
                "failovers": result["failovers"],
                "predicted_bytes_moved": result["predicted_bytes_moved"],
                "measured_step_p50_ms": result["measured_step_p50_ms"],
                "roofline_ratio": result["roofline_ratio"],
                "device": device,
                "vs_baseline": None,
            }))
        if args.mesh_parity:
            result = run_mesh_parity(args, spec_path)
            print(json.dumps(result))
            for r in result["meshes"].values():
                assert r["byte_parity"], f"mesh parity broken: {result}"
                rows.append(json.dumps({
                    "metric": "router mesh requests/s",
                    "value": r["requests_per_sec"],
                    "unit": "req/s",
                    "config": {
                        "replicas": 1, "slots": 4, "mesh": r["mesh"],
                        "requests": result["requests"],
                        "sampled_requests": result["sampled_requests"],
                    },
                    # Asserted, not aspirational: the run aborts above if a
                    # sharded fleet's bytes diverge from the unsharded one.
                    "byte_parity": r["byte_parity"],
                    "wall_s": r["wall_s"],
                    "device": device,
                    "vs_baseline": None,
                }))
        if args.heal:
            result = run_heal(args, spec_path)
            print(json.dumps(result))
            assert result["answered"] == result["requests"], (
                "heal soak lost requests"
            )
            assert result["respawns"] == 1, (
                f"fleet did not heal: {result}"
            )
            rows.append(json.dumps({
                "metric": "router time-to-heal",
                "value": result["time_to_heal_s"],
                "unit": "s",
                "config": {
                    "replicas": result["replicas"], "slots": args.slots,
                    "requests": args.requests,
                    "system_words": args.system_words,
                    "prefix_block": args.prefix_block,
                },
                "served_during_gap": result["served_during_gap"],
                "warmed_tokens": result["warmed_tokens"],
                "redispatch_count": result["redispatch_count"],
                # Supervisor-captured crash forensics: how many dead
                # replicas left a salvageable flight record this soak.
                "postmortems": result["postmortems"],
                "device": device,
                "vs_baseline": None,
            }))
        if args.upgrade:
            result = run_upgrade(args, spec_path)
            print(json.dumps(result))
            assert result["answered"] == result["requests"], (
                "upgrade soak lost requests"
            )
            assert result["answered_ok"] == result["requests"], (
                f"upgrade soak had errored requests: {result}"
            )
            assert result["upgrade_state"] == "done", (
                f"rollout did not complete: {result}"
            )
            rows.append(json.dumps({
                "metric": "router time-to-upgrade",
                "value": result["time_to_upgrade_s"],
                "unit": "s",
                "config": {
                    "replicas": result["replicas"], "slots": args.slots,
                    "requests": args.requests,
                    "system_words": args.system_words,
                    "prefix_block": args.prefix_block,
                },
                "served_during_rollout": result["served_during_rollout"],
                "canary_share": result["canary_share"],
                "rollbacks": result["rollbacks"],
                "per_version_answers": result["per_version_answers"],
                "device": device,
                "vs_baseline": None,
            }))
    finally:
        os.unlink(spec_path)
    if args.rows_out:
        with open(args.rows_out, "a", encoding="utf-8") as f:
            f.write("\n".join(rows) + "\n")
    else:
        for row in rows:
            print(row, file=sys.stderr)


if __name__ == "__main__":
    main()
