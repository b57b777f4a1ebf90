"""Multi-replica serving tier (``transformer_tpu/serve/router.py`` +
``replica.py``): prefix-affinity/least-loaded dispatch, the order-keyed
at-most-once answer funnel, zero-loss SIGKILL failover with byte parity
against a single-scheduler reference, cross-process trace reconstruction
through the merged per-replica logs, and the prefill/decode KV-block
handoff."""

import json
import os
import signal
import time

import pytest

from transformer_tpu.serve.router import (
    ReplicaLink,
    ReplicaProcess,
    Router,
    affinity_key,
    parse_router_line,
)

# The deterministic test-model bootstrap: every process that builds this
# spec (replica subprocesses AND the in-process reference scheduler) gets
# bit-identical params and vocab, so byte-parity assertions hold across
# process boundaries.
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

# Two distinct shared system prompts so BOTH replicas draw affinity
# traffic (block-aligned leading tokens differ between the groups, match
# within them).
PROMPT_A = "ab cd ef gh ij"
PROMPT_B = "kl mn ef cd"
REQS = (
    [{"prompt": PROMPT_A, "max_new": 5}] * 5
    + [{"prompt": PROMPT_B, "max_new": 4}] * 5
)
# Long-budget burst aimed (by affinity) at one replica — the kill window:
# 12 requests over 2 slots decode in waves, so the first answers drain
# while most of the burst is still queued or mid-decode on the victim.
BURST = [{"prompt": PROMPT_A, "max_new": 24}] * 12


@pytest.fixture(scope="module")
def lm():
    from transformer_tpu.serve.replica import build_model_from_spec

    return build_model_from_spec(SPEC)


@pytest.fixture(scope="module")
def spec_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("router") / "spec.json"
    path.write_text(json.dumps(SPEC))
    return str(path)


def _reference(lm, reqs):
    from transformer_tpu.serve import ContinuousScheduler

    params, cfg, tok = lm
    return ContinuousScheduler(params, cfg, tok, num_slots=2).run(
        [dict(r) for r in reqs]
    )


def _spawn_router(lm, spec_file, n, tmp_path, *, disaggregate=False,
                  trace=False, extra=()):
    params, cfg, tok = lm
    args = [
        "--model_spec", spec_file, "--serve_slots", "2",
        "--heartbeat_ms", "50", "--prefix_cache_mb", "8",
        "--prefix_block", "4", *extra,
    ]
    links = []
    for i in range(n):
        role = "both"
        if disaggregate:
            role = "prefill" if i == 0 else "decode"
        worker = list(args)
        if trace:
            worker += ["--metrics_jsonl", str(tmp_path / f"replica{i}.jsonl"),
                       "--trace"]
        links.append(ReplicaProcess.spawn(i, worker, role=role))
    telemetry = None
    if trace:
        from transformer_tpu.obs import EventLog, Telemetry

        telemetry = Telemetry(
            events=EventLog(str(tmp_path / "router.jsonl")), trace=True
        )
    router = Router(
        links, encode=tok.encode, bos_id=tok.bos_id, affinity_block=4,
        heartbeat_timeout_s=10.0, disaggregate=disaggregate,
        telemetry=telemetry,
    )
    for link in links:
        link.start_reader(router.inbox)
    return router, telemetry


# --------------------------------------------------------------------------
# the acceptance demo: SIGKILL one of two replicas mid-stream


def test_failover_zero_loss_byte_identical(lm, spec_file, tmp_path):
    """Two CPU replica processes, one SIGKILLed mid-stream: every accepted
    request answers exactly once, greedy answers are byte-identical to a
    single-scheduler run, and the merged router+replica logs reconstruct
    every failed-over request's trace (root on the router, spans on both
    replicas)."""
    from transformer_tpu.serve.router import _rendezvous

    router, telemetry = _spawn_router(lm, spec_file, 2, tmp_path, trace=True)
    params, cfg, tok = lm
    reqs = [*REQS, *BURST]
    want = _reference(lm, reqs)
    deadline = time.time() + 55  # the <60s acceptance bound
    try:
        # Phase 1: warm both replicas (each prompt group pins to its own
        # affine replica) and wait until both have answered something.
        for r in REQS:
            router.submit(dict(r))
        answered = []
        while (
            len(answered) < len(REQS)
            or not all(l.answered >= 1 for l in router.links)
        ) and time.time() < deadline:
            router.pump()
            answered.extend(router.drain_ready())
        assert all(l.answered >= 1 for l in router.links)
        # Phase 2: aim a long-budget burst at PROMPT_A's affine replica;
        # the moment its first burst answers drain (so it has admitted and
        # is mid-stream), SIGKILL it — the rest of the burst is still in
        # flight there and must fail over losslessly.
        key = affinity_key([tok.bos_id, *tok.encode(PROMPT_A)], 4)
        victim = max(router.links, key=lambda l: _rendezvous(key, l.name))
        for r in BURST:
            router.submit(dict(r))
        router.pump(timeout=0)  # dispatch the burst
        assert victim.inflight >= 1
        while len(answered) < len(REQS) + 2 and time.time() < deadline:
            router.pump()
            answered.extend(router.drain_ready())
        assert victim.inflight >= 1, "burst drained before the kill window"
        # A request writes its first span (``serve.queue``) when the victim
        # takes it up. The victim has just retired a wave; until it takes up
        # the next, nothing in flight there has a span, and the merged trace
        # below would find nothing of the victim's. Wait (milliseconds) for
        # one more request taken up than answered; the rest of the burst
        # keeps the victim mid-stream.
        victim_log = tmp_path / f"{victim.name}.jsonl"
        taken_up = time.time() + 5
        while (
            victim_log.read_text().count('"name": "serve.queue"') <= victim.answered
            and time.time() < taken_up
        ):
            time.sleep(0.001)
        os.kill(victim.pid(), signal.SIGKILL)
        killed_name = victim.name
        while router.busy and time.time() < deadline:
            router.pump()
            answered.extend(router.drain_ready())
        answered.extend(router.drain_ready())
        # Zero loss, exactly once: every accepted order answered, in
        # arrival order, none with an error.
        assert len(answered) == len(reqs)
        assert router.stats["failovers"] == 1
        assert router.stats["redispatched"] >= 1
        assert all("continuation" in a for a in answered), answered
        # Byte parity with the single-scheduler reference.
        assert [a["continuation"] for a in answered] == [
            w["continuation"] for w in want
        ]
    finally:
        router.shutdown()
        if telemetry is not None:
            telemetry.close()

    # ---- merged fleet trace: root on the router, spans on both replicas.
    from transformer_tpu.obs.merge import merge_events
    from transformer_tpu.obs.trace import span_tree

    paths = [str(tmp_path / "router.jsonl"),
             str(tmp_path / "replica0.jsonl"),
             str(tmp_path / "replica1.jsonl")]
    events, info = merge_events(paths)
    assert set(info["sources"]) == {"router.jsonl", "replica0.jsonl",
                                    "replica1.jsonl"}
    failovers = [e for e in events if e.get("kind") == "route.failover"]
    assert len(failovers) == 1 and failovers[0]["replica"] == killed_name
    victim_traces = failovers[0]["traces"]
    assert victim_traces, "failover carried no victim trace ids"
    trees = span_tree(events)
    victim_src = f"{killed_name}.jsonl"
    survivor_src = next(
        s for s in ("replica0.jsonl", "replica1.jsonl") if s != victim_src
    )
    spans_on_victim = 0
    for trace in victim_traces:
        spans = trees.get(trace, {})
        sources = {s.get("source") for s in spans.values()}
        # Root on the router: the route.request span, parentless.
        roots = [s for s in spans.values()
                 if s.get("parent") is None and s["name"] == "route.request"]
        assert roots and roots[0]["source"] == "router.jsonl", spans
        # The redispatched request completed on the survivor.
        assert survivor_src in sources, sources
        spans_on_victim += victim_src in sources
    # At least the slot-resident victims left spans behind (the event log
    # is line-buffered, so SIGKILL loses nothing already emitted): the
    # merge reconstructs one request's lifecycle across BOTH replicas.
    assert spans_on_victim >= 1
    # Every request that was ever dispatched carries a route.dispatch
    # event with its trace id, and redispatches are marked.
    dispatches = [e for e in events if e.get("kind") == "route.dispatch"]
    assert sum(1 for d in dispatches if d.get("redispatch")) == \
        router.stats["redispatched"]
    # The merged fleet report: per-replica request share + redispatches.
    from transformer_tpu.obs.__main__ import summarize_events

    rep = summarize_events(events)["router"]
    assert rep["requests"] == len(reqs)
    assert rep["redispatches"] == router.stats["redispatched"]
    assert rep["failovers"] == 1
    assert set(rep["replicas"]) == {"replica0", "replica1"}
    assert abs(sum(r["share"] for r in rep["replicas"].values()) - 1.0) < 1e-6
    # The Perfetto export gives the router its own lane and each source
    # its own process row.
    from transformer_tpu.obs.trace import chrome_trace

    doc = chrome_trace(events)
    assert sorted(doc["otherData"]["sources"]) == [
        "replica0.jsonl", "replica1.jsonl", "router.jsonl"
    ]
    lanes = {m["args"]["name"] for m in doc["traceEvents"]
             if m.get("name") == "thread_name"}
    assert "router" in lanes


# --------------------------------------------------------------------------
# disaggregated prefill/decode (subprocess path)


@pytest.mark.slow
def test_disaggregated_prefill_decode(lm, spec_file, tmp_path):
    """--disaggregate: prompts ingest on a prefill-only replica and the KV
    crosses to a decode-only replica as prefix-cache blocks; answers stay
    byte-identical and every request rode a handoff."""
    router, _ = _spawn_router(
        lm, spec_file, 2, tmp_path, disaggregate=True
    )
    reqs = REQS[:4]
    try:
        out = router.run([dict(r) for r in reqs])
    finally:
        router.shutdown()
    want = _reference(lm, reqs)
    assert [o.get("continuation") for o in out] == [
        w["continuation"] for w in want
    ]
    assert router.stats["prefill_handoffs"] == len(reqs)
    # The prefill->decode stage progression is normal request flow: it
    # must consume none of the max_redispatch failover budget and never
    # count as a redispatch in the metrics.
    assert router.stats["redispatched"] == 0


# --------------------------------------------------------------------------
# the handoff block format (in-process: the mechanism under the subprocess)


def test_kv_block_handoff_parity(lm):
    """export_blocks -> JSON wire -> inject_blocks restores the prompt's
    KV into a second scheduler's PrefixCache: the decode side answers
    byte-identically while restoring real prefix tokens without a model
    forward."""
    from transformer_tpu.serve import ContinuousScheduler, PrefixCache
    from transformer_tpu.serve.replica import export_blocks, inject_blocks

    params, cfg, tok = lm
    prompt = "ab cd ef gh ij kl"
    ids = [tok.bos_id, *tok.encode(prompt)]

    prefill_cache = PrefixCache(cfg, block_tokens=4, budget_mb=8)
    s1 = ContinuousScheduler(
        params, cfg, tok, num_slots=1, prefix_cache=prefill_cache
    )
    assert s1.run([{"prompt": prompt, "max_new": 0}]) == [{"continuation": ""}]
    tokens, payload = export_blocks(prefill_cache, ids)
    assert tokens > 0 and payload
    wire = json.loads(json.dumps(payload))  # the pipe representation

    decode_cache = PrefixCache(cfg, block_tokens=4, budget_mb=8)
    assert inject_blocks(decode_cache, ids, tokens, wire) == tokens
    s2 = ContinuousScheduler(
        params, cfg, tok, num_slots=1, prefix_cache=decode_cache
    )
    out = s2.run([{"prompt": prompt, "max_new": 6}])
    ref = ContinuousScheduler(params, cfg, tok, num_slots=1).run(
        [{"prompt": prompt, "max_new": 6}]
    )
    assert out[0]["continuation"] == ref[0]["continuation"]
    assert s2.stats["prefix_hit_tokens"] == tokens


# --------------------------------------------------------------------------
# router-core unit tests (in-process fake links)


class _FakeLink(ReplicaLink):
    """In-process replica stand-in: echoes an answer per request unless
    muted; `ok = False` simulates process death."""

    def __init__(self, index, name, answer=True):
        super().__init__(index, name)
        self.sent = []
        self.answer_back = answer
        self.ok = True
        self.router = None

    def alive(self):
        return self.ok  # transport liveness only (the router owns `dead`)

    def send(self, msg):
        if not self.ok:
            raise BrokenPipeError("dead")
        self.sent.append(msg)
        if msg.get("type") == "prefill":
            # Disaggregation stage 1: hand back an (empty) KV payload.
            self.router.inbox.put((self.index, {
                "type": "prefilled", "rid": msg["rid"],
                "tokens": 0, "blocks": [],
            }))
        elif self.answer_back:
            self.router.inbox.put((self.index, {
                "type": "answer", "rid": msg["rid"],
                "resp": {"continuation": self.name},
            }))


def _fake_router(n=2, answer=True, **kw):
    links = [_FakeLink(i, f"f{i}", answer=answer) for i in range(n)]
    router = Router(links, **kw)
    for link in links:
        link.router = router
    return router, links


def test_affinity_pins_shared_prefixes():
    """Same leading blocks -> same replica (warm PrefixCache); the key is
    a pure function of the aligned prefix, so tails never split it."""
    assert affinity_key([1, 2, 3, 4, 5, 6, 7, 8, 9], 4) == \
        affinity_key([1, 2, 3, 4, 5, 6, 7, 8, 200], 4)
    assert affinity_key([1, 2, 3], 4) is None  # shorter than one block
    router, links = _fake_router(
        2, encode=lambda s: [ord(c) % 40 + 3 for c in s], bos_id=1,
        affinity_block=4, affinity_slack=100,
    )
    out = router.run([{"prompt": "shared system prompt, tail %d" % i}
                      for i in range(6)])
    assert len(out) == 6
    # All six rode the same replica: the affinity hash pinned them.
    assert sorted(l.dispatched for l in links) == [0, 6]


def test_least_loaded_fallback_when_affine_overloaded():
    router, links = _fake_router(
        2, answer=False, encode=lambda s: [5] * 10, bos_id=1,
        affinity_block=4, affinity_slack=2,
    )
    for i in range(5):
        router.submit({"prompt": "same prompt"})
    router.pump(timeout=0)
    # Pinned to the affine replica until its unanswered load exceeded the
    # least-loaded peer's by more than the slack (2), then spilled — the
    # gap between the two stays bounded by slack + 1.
    assert all(l.dispatched > 0 for l in links)
    assert abs(links[0].dispatched - links[1].dispatched) <= 3


def test_answer_funnel_at_most_once():
    router, links = _fake_router(1, encode=None)
    order = router.submit({"prompt": "p"})
    router.pump(timeout=0)
    router.pump(timeout=0)
    # A late duplicate (the failover race) is counted and dropped.
    router.inbox.put((0, {"type": "answer", "rid": order,
                          "resp": {"continuation": "dup"}}))
    router.pump(timeout=0)
    out = router.drain_ready()
    assert out == [{"continuation": "f0"}]
    assert router.stats["duplicate_answers"] == 1
    assert router.stats["answered"] == 1


def test_failover_preserves_order_and_bounds_redispatch():
    router, links = _fake_router(
        2, answer=False, encode=None, max_redispatch=1,
    )
    orders = [router.submit({"prompt": "p"}) for _ in range(4)]
    router.pump(timeout=0)
    assert len(router._inflight) == 4
    first = [l for l in links if l.inflight][0]
    survivor = links[1 - first.index]
    victims = sorted(m["rid"] for m in first.sent)
    before = len(survivor.sent)
    first.ok = False  # dies without answering
    router.pump(timeout=0)
    assert router.stats["failovers"] == 1
    # Victims re-dispatched to the survivor in their ORIGINAL order, ahead
    # of nothing (they re-enter at the front of the pending queue).
    assert [m["rid"] for m in survivor.sent[before:]] == victims
    # Survivor dies too: the bounded-redispatch ladder answers a
    # structured transient error instead of looping forever.
    survivor.ok = False
    deadline = time.time() + 10
    while router.busy and time.time() < deadline:
        router.pump(timeout=0)
    out = router.drain_ready()
    assert len(out) == 4
    assert all(o.get("code") == "transient" for o in out), out


def test_late_answer_from_failed_replica_releases_survivor_slot():
    """The failover race's load-accounting arm: a victim's late answer
    must release the slot of the SURVIVOR the order is now assigned to,
    and the survivor's own (duplicate) answer must not double-release."""
    router, links = _fake_router(2, answer=False, encode=None)
    order = router.submit({"prompt": "p"})
    router.pump(timeout=0)
    first = [l for l in links if l.inflight][0]
    survivor = links[1 - first.index]
    first.ok = False
    router.pump(timeout=0)  # failover: redispatched to the survivor
    assert survivor.inflight == 1
    assert router._inflight[order].replica == survivor.index
    # The victim's buffered answer lands AFTER the redispatch and wins.
    router.inbox.put((first.index, {"type": "answer", "rid": order,
                                    "resp": {"continuation": "late"}}))
    router.pump(timeout=0)
    assert router.drain_ready() == [{"continuation": "late"}]
    assert survivor.inflight == 0  # the survivor's load was released
    # The survivor's own answer is the duplicate: dropped, no drift.
    router.inbox.put((survivor.index, {"type": "answer", "rid": order,
                                       "resp": {"continuation": "dup"}}))
    router.pump(timeout=0)
    assert router.stats["duplicate_answers"] == 1
    assert survivor.inflight == 0


def test_heartbeat_timeout_failover_then_revival():
    """A heartbeat-timeout victim whose worker process still runs earns
    its way back through the breaker's half-open probe: a heartbeat newer
    than the death mark revives the link, and its next answered request
    closes the breaker. (Exited/SIGKILLed workers fail ``alive()`` and
    stay dead.)"""
    router, links = _fake_router(
        2, encode=None, heartbeat_timeout_s=0.01, breaker_cooldown_s=0.0,
    )
    lagger = links[0]
    lagger.last_hb = time.monotonic() - 1.0  # a stalled worker
    router.pump(timeout=0)
    assert lagger.dead and router.stats["failovers"] == 1
    assert router.breakers[0].state == "open"
    # The worker wakes up and heartbeats again: half-open revival
    # (cooldown 0 here makes the probe immediate).
    router.inbox.put((0, {"type": "hb", "backlog": 0, "free": 2,
                          "active": 0}))
    router.pump(timeout=0)
    assert not lagger.dead and router.stats["revivals"] == 1
    router.heartbeat_timeout_s = 0.0  # the fakes don't keep heartbeating
    out = router.run([{"prompt": "p"} for _ in range(4)])
    assert len(out) == 4
    assert lagger.dispatched > 0  # the revived link carries traffic again
    assert router.breakers[0].state == "closed"


def test_disaggregate_decode_death_degrades_to_prefill_worker():
    """All decode-capable replicas dead with a prefill-only worker alive:
    the request degrades to a full serve on the prefill worker instead of
    parking forever in the pending queue."""
    links = [_FakeLink(0, "pf"), _FakeLink(1, "dec")]
    links[0].role = "prefill"
    links[1].role = "decode"
    router = Router(links, encode=None, disaggregate=True)
    for link in links:
        link.router = router
    links[1].ok = False  # the decode fleet dies before any dispatch
    router.submit({"prompt": "p"})
    out = []
    deadline = time.time() + 10
    while router.busy and time.time() < deadline:
        router.pump(timeout=0)
        out.extend(router.drain_ready())
    assert out == [{"continuation": "pf"}], \
        "request parked forever with a live prefill worker"
    # Stage 1 rode the prefill protocol; the degraded serve was a full
    # "req" on the same worker.
    assert [m["type"] for m in links[0].sent] == ["prefill", "req"]
    assert router.stats["redispatched"] == 0  # degradation, not failover


def test_submit_done_reserves_order():
    router, _ = _fake_router(1, encode=None)
    a = router.submit({"prompt": "p"})
    b = router.submit_done({"error": "LM export serves 'prompt', not 'src'",
                            "code": "routing"})
    c = router.submit({"prompt": "q"})
    out = router.run([])
    assert (a, b, c) == (0, 1, 2)
    assert len(out) == 3
    assert out[1]["code"] == "routing"
    assert "continuation" in out[0] and "continuation" in out[2]


def test_router_deadline_expires_in_queue():
    router, links = _fake_router(1, answer=False, encode=None)
    router.submit({"prompt": "p", "deadline_ms": 0.0})
    time.sleep(0.002)
    router.pump(timeout=0)
    out = router.drain_ready()
    assert out and out[0].get("code") == "deadline"
    assert router.stats["expired"] == 1


def test_parse_router_line_matches_serve_parity():
    assert parse_router_line("ab cd") == {"prompt": "ab cd"}
    assert parse_router_line('{"prompt": "x", "max_new": 2}') == {
        "prompt": "x", "max_new": 2,
    }
    with pytest.raises(ValueError, match="serves 'prompt', not 'src'"):
        parse_router_line('{"src": "y"}')
    with pytest.raises(ValueError, match="serves 'prompt', not 'fill'"):
        parse_router_line('{"fill": "y"}')
    with pytest.raises(ValueError, match="needs 'src'"):
        parse_router_line('{"beam": 4}')


# --------------------------------------------------------------------------
# one process per chip: the parent stays off JAX, replica i gets chip i


def test_router_parent_imports_leave_jax_out():
    """A parent that has touched JAX holds the chip its replicas need, so
    the router tier must not even import it (the scheduler, the prefix
    cache and the drafters do — they load on first use, in the workers)."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import transformer_tpu.cli.router, transformer_tpu.serve.router\n"
        "import transformer_tpu.serve.supervisor, transformer_tpu.serve.standby\n"
        "print([m for m in ('jax', 'jaxlib') if m in sys.modules])\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]", out.stdout


def test_replica_chip_env_assigns_and_refuses():
    """Replica i takes chips [i*m, (i+1)*m) through libtpu's variables; a
    fleet that outgrows the host is refused at spawn with both counts (a
    second process on a taken chip would hang instead)."""
    from transformer_tpu.serve.router import replica_chip_env

    assert replica_chip_env(3, 1, host_chips=0) == {}  # off a TPU host
    seen = set()
    for i in range(4):
        env = replica_chip_env(i, 1, host_chips=4)
        assert env["TPU_VISIBLE_CHIPS"] == str(i)
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        seen.add(env["TPU_VISIBLE_CHIPS"])
    assert len(seen) == 4
    pair = replica_chip_env(1, 2, host_chips=4)  # --mesh 2: its own group
    assert pair["TPU_VISIBLE_CHIPS"] == "2,3"
    assert pair["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    with pytest.raises(ValueError, match=r"5 replica\(s\) x 1 chip.*has 4"):
        replica_chip_env(4, 1, host_chips=4)
    with pytest.raises(ValueError, match=r"2 replica\(s\) x 4 chip.*has 4"):
        replica_chip_env(1, 4, host_chips=4)
    with pytest.raises(ValueError, match="can span"):
        replica_chip_env(0, 3, host_chips=4)
