"""Continuous-batching scheduler contracts (``transformer_tpu/serve``):
same answers as sequential batch-1 serving under mixed prompt/output lengths,
per-request failure isolation (the ``cli/serve.py`` grouped-path guarantee),
slot recycling, and arrival-order output."""

import jax
import pytest

from transformer_tpu.config import ModelConfig
from transformer_tpu.data.tokenizer import SubwordTokenizer
from transformer_tpu.models import transformer_init
from transformer_tpu.serve import ContinuousScheduler
from transformer_tpu.train.decode import generate, prefill_len_for


@pytest.fixture(scope="module")
def lm():
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


# Mixed prompt lengths, output budgets, and sampling params: the shapes that
# force mid-flight retirement + admission when slots < requests.
REQS = [
    {"prompt": "ab cd ef gh ij", "max_new": 6},
    {"prompt": "kl", "max_new": 2},
    {"prompt": "ef", "max_new": 0},  # empty-budget edge: "" both paths
    {"prompt": "ab cd", "max_new": 8, "temperature": 0.9, "seed": 3},
    {"prompt": "mn ef cd", "max_new": 1},
    {"prompt": "gh ij kl mn", "max_new": 5, "temperature": 0.7, "top_k": 4,
     "seed": 1},
]


def _sequential(params, cfg, tok, reqs):
    """The serve_batch=1 oracle: each request alone through generate()."""
    out = []
    for r in reqs:
        out.append(
            generate(
                params, cfg, tok, [r["prompt"]],
                max_new=r.get("max_new", 64),
                temperature=r.get("temperature", 0.0),
                top_k=r.get("top_k", 0), top_p=r.get("top_p", 1.0),
                seed=r.get("seed", 0),
            )[0]
        )
    return out


def test_matches_sequential_serving(lm):
    """2 slots, 5 requests with mixed prompt/output lengths and sampling
    params: continuous batching returns the same per-request continuations
    as decoding each request alone."""
    params, cfg, tok = lm
    want = _sequential(params, cfg, tok, REQS)
    sched = ContinuousScheduler(params, cfg, tok, num_slots=2)
    got = sched.run([dict(r) for r in REQS])
    assert [g.get("continuation") for g in got] == want
    assert sched.stats["admitted"] == len(REQS)
    assert sched.stats["max_active"] <= 2
    # Slots were actually recycled: 5 admissions through 2 slots.
    assert not sched.busy and len(sched._free) == 2  # pool drained


def test_single_slot_matches_sequential(lm):
    """num_slots=1 degenerates to pure sequential serving — the base case
    the parity claim is anchored to."""
    params, cfg, tok = lm
    reqs = REQS[:3]
    want = _sequential(params, cfg, tok, reqs)
    sched = ContinuousScheduler(params, cfg, tok, num_slots=1)
    got = sched.run([dict(r) for r in reqs])
    assert [g.get("continuation") for g in got] == want


def test_poisoned_request_fails_alone(lm):
    """A poisoned request (over-length prompt / unconvertible field) answers
    with ITS error; co-batched requests still succeed — the isolation
    guarantee the grouped path enforces by per-member retry holds here
    structurally (failures happen at admission, before the pool)."""
    params, cfg, tok = lm
    good = {"prompt": "ab cd", "max_new": 3}
    over = {"prompt": "ab cd ef gh " * 30, "max_new": 3}  # > max_position
    bad_field = {"prompt": "ef gh", "max_new": "four"}
    # Greedy ignores the rng, so even an unconvertible stray seed must not
    # change the answer (grouped-path parity: _signature never coerces it).
    stray_seed = {"prompt": "ab cd", "max_new": 3, "seed": "abc"}
    # An over-vocab top_k would raise inside the jitted pick — it must be
    # rejected at admission, answering alone instead of crashing step()
    # (or leaking the popped slot when the whole prompt prefills).
    big_topk = {"prompt": "ab cd", "max_new": 3, "temperature": 0.8,
                "top_k": 100000}
    sched = ContinuousScheduler(params, cfg, tok, num_slots=2)
    got = sched.run(
        [dict(good), dict(over), dict(bad_field), dict(good),
         dict(stray_seed), dict(big_topk), dict(good)]
    )
    assert got[0]["continuation"] == got[3]["continuation"]
    assert "max_position" in got[1]["error"]
    assert "ValueError" in got[2]["error"] or "int" in got[2]["error"]
    assert "error" not in got[0] and "error" not in got[3]
    assert got[4]["continuation"] == got[0]["continuation"]
    assert "top_k" in got[5]["error"]
    assert got[6]["continuation"] == got[0]["continuation"]
    # The failed admissions never held a slot.
    assert len(sched._free) == 2


def test_straggler_does_not_block_admission(lm):
    """The continuous-batching point: with 2 slots, a long-generation
    straggler and a stream of short requests, short requests are admitted
    and retired while the straggler is still decoding (max_active == 2 and
    total steps < sum of sequential steps)."""
    params, cfg, tok = lm
    reqs = [{"prompt": "ab cd ef gh ij kl", "max_new": 20}] + [
        {"prompt": "mn", "max_new": 1} for _ in range(4)
    ]
    sched = ContinuousScheduler(params, cfg, tok, num_slots=2)
    got = sched.run([dict(r) for r in reqs])
    assert all("continuation" in g for g in got)
    assert sched.stats["max_active"] == 2
    # Step-level interleaving: the pool never ran more total steps than the
    # straggler's own token budget plus a handful of admission edges.
    assert sched.stats["steps"] <= 20 + len(reqs) + 8


def test_arrival_order_output(lm):
    """drain_ready releases responses in ARRIVAL order: a later short
    request that finishes first waits for the earlier straggler (the serve
    loop's stdout contract), and submit_done reserves error positions."""
    params, cfg, tok = lm
    sched = ContinuousScheduler(params, cfg, tok, num_slots=4)
    sched.submit({"prompt": "ab cd ef gh ij", "max_new": 8})
    sched.submit_done({"error": "routing"})
    sched.submit({"prompt": "kl", "max_new": 1})
    early = []
    while sched.busy:
        sched.admit()
        sched.step()
        early.extend(sched.drain_ready())
        if early:
            # Nothing may flush before request 0 (the straggler) answers.
            assert "continuation" in early[0]
    out = early + sched.drain_ready()
    assert len(out) == 3
    assert out[1] == {"error": "routing"}
    assert "continuation" in out[2]


def test_cache_variants_match_sequential(lm):
    """The slot pool composes with the int8-quantized rolling-window cache:
    parity against sequential serving holds for the exotic cache layout
    too (the per-variant prefill math is pinned in test_prefill.py)."""
    import dataclasses

    params_base, cfg, tok = lm
    cfg_v = dataclasses.replace(cfg, kv_cache_int8=True, attention_window=4)
    params = transformer_init(jax.random.PRNGKey(0), cfg_v)
    reqs = [dict(r) for r in REQS[:3]]
    want = _sequential(params, cfg_v, tok, reqs)
    sched = ContinuousScheduler(params, cfg_v, tok, num_slots=2)
    got = sched.run(reqs)
    assert [g.get("continuation") for g in got] == want


def test_malformed_flood_stays_bounded(lm, capsys):
    """Error-answered lines count toward the serve loop's ingest cap: a
    flood of bad lines flushes incrementally instead of accumulating in the
    scheduler's done-buffer (the backpressure contract for invalid input)."""
    import json
    import queue

    from transformer_tpu.cli.serve import serve_continuous

    params, cfg, tok = lm
    sched = ContinuousScheduler(params, cfg, tok, num_slots=2)
    peak = 0
    orig = sched.submit_done

    def spying(resp):
        nonlocal peak
        order = orig(resp)
        peak = max(peak, sched.ready_count)
        return order

    sched.submit_done = spying
    q: queue.Queue = queue.Queue()
    for _ in range(100):
        q.put('{bad\n')
    q.put(None)
    serve_continuous(q, sched, cfg)
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 100
    assert all("error" in json.loads(l) for l in lines)
    assert peak <= 2 * 8  # backlog_cap for num_slots=2


def test_submit_after_shutdown_answers_routing_error(lm):
    """A submission landing after shutdown() answers a structured
    'routing' error at its reserved order instead of queueing into a loop
    nobody drives again — the window the multi-replica router's redispatch
    path can hit on a draining replica. Requests accepted BEFORE the
    shutdown keep their full contract."""
    params, cfg, tok = lm
    sched = ContinuousScheduler(params, cfg, tok, num_slots=2)
    sched.submit({"prompt": "ab cd", "max_new": 3})
    sched.shutdown()
    late = sched.submit({"prompt": "ef gh", "max_new": 3})
    assert late == 1
    while sched.busy:
        sched.admit()
        sched.step()
    out = sched.drain_ready()
    assert len(out) == 2
    assert "continuation" in out[0]  # pre-shutdown request still served
    assert out[1]["code"] == "routing"
    assert "shut down" in out[1]["error"]
    # The refused request never entered the queue or took a slot.
    assert sched.backlog == 0 and len(sched._free) == 2


def test_serve_continuous_loop(lm, capsys):
    """cli.serve's continuous loop end-to-end (in-process): JSONL + raw +
    malformed + wrong-kind lines through the stdin queue; one response per
    line in order, the loop surviving the bad ones."""
    import json
    import queue

    from transformer_tpu.cli.serve import serve_continuous

    params, cfg, tok = lm
    sched = ContinuousScheduler(params, cfg, tok, num_slots=2)
    q: queue.Queue = queue.Queue()
    for line in [
        'ab cd\n',                                  # raw line -> prompt
        '{"prompt": "ef gh", "max_new": 2}\n',
        '{broken json\n',                           # malformed: answered
        '{"src": "wrong kind"}\n',                  # seq2seq key on LM export
        '{"src": "x", "prompt": "y"}\n',  # 'src' wins (grouped-path parity)
        '\n',                                       # blank: skipped
    ]:
        q.put(line)
    q.put(None)
    serve_continuous(q, sched, cfg)
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 5
    assert "continuation" in lines[0]
    assert "continuation" in lines[1]
    assert "error" in lines[2]
    # Bare message, no exception-type prefix — byte-identical to the
    # grouped path's kind-mismatch answer.
    assert lines[3]["error"] == "LM export serves 'prompt', not 'src'"
    assert lines[4]["error"] == "LM export serves 'prompt', not 'src'"


# --------------------------------------------------------------------------
# spans inside step() and admit(), kept in the in-memory buffer (no Telemetry)

STEP_PHASES = (
    "step.prepare", "step.build", "step.dispatch", "step.fetch",
    "step.bookkeep",
)


def _buffered_run(lm, reqs, **kw):
    """Run ``reqs`` on a telemetry-free scheduler; returns (answers, the
    spans the run left in the process-wide buffer, request span dicts)."""
    from transformer_tpu.obs.trace import buffer

    params, cfg, tok = lm
    buffer().clear()
    tapped = []
    sched = ContinuousScheduler(
        params, cfg, tok, span_tap=tapped.append, **kw
    )
    out = sched.run([dict(r) for r in reqs])
    return out, buffer().snapshot(), tapped


def _end(span):
    return span["t0_mono"] + span["dur_s"]


def _children(spans, parent):
    return sorted(
        (s for s in spans if s.get("parent") == parent["span"]),
        key=lambda s: s["t0_mono"],
    )


@pytest.mark.parametrize(
    "kw",
    [
        dict(num_slots=2),
        dict(num_slots=3, prefill_chunk=2),
        dict(num_slots=2, kv_layout="paged", kv_block=4),
    ],
    ids=["dense", "chunked", "paged"],
)
def test_step_spans_phases_and_counts(lm, kw):
    """Every ``scheduler.step`` span holds the five phases inside itself,
    and its counts add up: a stepped slot either emitted a token or walked
    a prompt-tail token, and the run's output tokens are the steps'
    ``emitted`` plus the tokens picked at admission."""
    # Every request asks for a token or more and none can end early (no EOS
    # in a random model's greedy output is not guaranteed, so check below).
    reqs = [
        {"prompt": "ab cd ef gh ij", "max_new": 6},
        {"prompt": "kl", "max_new": 3},
        {"prompt": "ab cd", "max_new": 8, "temperature": 0.9, "seed": 3},
        {"prompt": "mn ef cd", "max_new": 4},
        {"prompt": "gh ij kl mn", "max_new": 5},
    ]
    out, spans, tapped = _buffered_run(lm, reqs, **kw)
    assert all("continuation" in a for a in out)
    steps = [s for s in spans if s["name"] == "scheduler.step"]
    assert steps and all(s["lane"] == "scheduler" for s in steps)
    assert all("parent" not in s for s in steps)  # roots
    ended_early = any(
        t["new_tokens"] < r["max_new"]
        for t, r in zip(sorted(tapped, key=lambda t: t["order"]), reqs)
    )
    for step in steps:
        kids = _children(spans, step)
        names = [k["name"] for k in kids]
        for phase in STEP_PHASES:
            assert phase in names, (phase, names)
        for k in kids:
            assert k["t0_mono"] >= step["t0_mono"]
            assert _end(k) <= _end(step) + 1e-6
            assert k["lane"] == "scheduler" and k["trace"] == step["trace"]
        # The phases come in order; dispatch and fetch alternate, a pair a
        # sampling group.
        df = [n for n in names if n in ("step.dispatch", "step.fetch")]
        assert df == ["step.dispatch", "step.fetch"] * (len(df) // 2)
        assert names[0] == "step.prepare" and names[1] == "step.build"
        assert names[-1] == "step.bookkeep"
        assert step["continued"] <= step["emitted"]
        assert step["emitted"] + step["walked"] <= step["active"]
        if not ended_early:
            assert step["emitted"] + step["walked"] == step["active"]
        dispatched = [k for k in kids if k["name"] == "step.dispatch"]
        assert dispatched[0]["programs"] == 2
        assert all(k["programs"] == 1 for k in dispatched[1:])
    admits = [s for s in spans if s["name"] == "serve.admit"]
    assert len(admits) == len(reqs)
    first_picks = sum(
        1 for s in spans if s["name"] == "admit.first_pick"
    )
    total_out = sum(t["new_tokens"] for t in tapped)
    assert sum(s["emitted"] for s in steps) + first_picks == total_out
    retired = sum(
        k["retired"] for s in steps for k in _children(spans, s)
        if k["name"] == "step.bookkeep"
    )
    # A request that answers at its admission's first pick never steps.
    assert retired <= len(reqs)


@pytest.mark.parametrize("speculate_k", [0, 2], ids=["plain", "speculate_k=2"])
@pytest.mark.parametrize(
    "kw",
    [
        dict(kv_layout="dense", decode_kernel="xla"),
        dict(kv_layout="paged", kv_block=4, decode_kernel="xla"),
        dict(kv_layout="paged", kv_block=4, decode_kernel="paged_flash"),
    ],
    ids=["dense-xla", "paged-xla", "paged-paged_flash"],
)
def test_step_metrics_count_every_step(lm, kw, speculate_k):
    """The histograms and counters an operator reads count what the
    scheduler did: a ``serve_step_seconds`` sample and a ``serve_steps_total``
    tick a step (plain or verify), a ``serve_generated_tokens_total`` tick a
    token answered, a ``serve_prefill_seconds`` sample an admission."""
    from transformer_tpu.obs import Telemetry

    params, cfg, tok = lm
    reqs = [
        {"prompt": "ab cd ef gh ij", "max_new": 6},
        {"prompt": "kl", "max_new": 3},
        {"prompt": "ab cd ab cd ab", "max_new": 8},
        {"prompt": "mn ef cd", "max_new": 4, "temperature": 0.9, "seed": 3},
        {"prompt": "gh ij kl mn", "max_new": 5},
    ]
    tel = Telemetry()
    tapped = []
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=2, max_total=32, prefill_chunk=3,
        speculate_k=speculate_k, telemetry=tel, span_tap=tapped.append, **kw,
    )
    out = sched.run([dict(r) for r in reqs])
    assert all("continuation" in a for a in out)
    reg = tel.registry
    steps = sched.stats["steps"]
    assert steps > 0
    assert reg.histogram("serve_step_seconds").hist.count == steps
    assert reg.counter("serve_steps_total").value == steps
    answered = sum(t["new_tokens"] for t in tapped)
    assert answered >= len(reqs)
    assert reg.counter("serve_generated_tokens_total").value == answered
    assert sched.stats["admitted"] == len(reqs)
    assert reg.histogram("serve_prefill_seconds").hist.count == len(reqs)
    if speculate_k:
        drafted = reg.counter("serve_spec_drafted_total").value
        assert drafted == sched.stats["drafted"] > 0
        assert reg.counter("serve_spec_accepted_total").value == (
            sched.stats["accepted"]
        )


def test_sampling_groups_alternate_dispatch_and_fetch(lm):
    """Greedy and sampled requests side by side make two pick groups: the
    step dispatches and fetches one after the other, in that order."""
    reqs = [
        {"prompt": "ab cd ef", "max_new": 6},
        {"prompt": "gh ij", "max_new": 6, "temperature": 0.8, "seed": 5},
    ]
    _, spans, _ = _buffered_run(lm, reqs, num_slots=2)
    both = [
        s for s in spans if s["name"] == "scheduler.step" and s["active"] == 2
    ]
    assert both
    for step in both:
        names = [
            k["name"] for k in _children(spans, step)
            if k["name"] in ("step.dispatch", "step.fetch")
        ]
        assert names == ["step.dispatch", "step.fetch"] * 2


def test_admit_spans_and_request_gaps(lm):
    """``serve.admit`` holds its phases; ``prefill_s`` ends after the first
    pick's sync; ``itl_max_s`` lies between the shortest and the longest
    gap between the steps that emitted the request's tokens."""
    # A prompt of a power of two of tokens (BOS included) is prefilled whole
    # and picks its first token at admission; any other walks its tail.
    tok = lm[2]
    prompt = next(
        p for p in ("ab cd ef", "ab cd ef gh", "ab cd ef gh ij", "ab cd")
        if prefill_len_for(1 + len(tok.encode(p))) == 1 + len(tok.encode(p)) >= 4
    )
    reqs = [{"prompt": prompt, "max_new": 6}]
    _, spans, tapped = _buffered_run(lm, reqs, num_slots=1)
    (admit,) = [s for s in spans if s["name"] == "serve.admit"]
    kids = _children(spans, admit)
    assert [k["name"] for k in kids] == [
        "admit.encode", "admit.prefill_dispatch", "admit.first_pick",
    ]
    for k in kids:
        assert k["t0_mono"] >= admit["t0_mono"] and _end(k) <= _end(admit) + 1e-6
    assert admit["prompt_tokens"] == admit["prefill_tokens"] >= 4
    (req,) = tapped
    assert req["new_tokens"] >= 3
    # Admission to the prompt in cache: no earlier than the first pick's end.
    first_pick = kids[-1]
    assert req["prefill_s"] >= (_end(first_pick) - admit["t0_mono"]) - 0.05
    assert req["prefill_s"] <= admit["dur_s"] + 1e-3
    (root,) = [s for s in spans if s["name"] == "serve.request"]
    assert root["itl_max_s"] == req["itl_max_s"] > 0
    # A prompt that walks its tail gets every token from a step. Token
    # stamps are taken inside a step's bookkeeping, so a gap between the
    # tokens of two consecutive steps lies between these two distances.
    _, spans, tapped = _buffered_run(
        lm, [{"prompt": prompt + " ij", "max_new": 6}], num_slots=1
    )
    assert "admit.first_pick" not in {s["name"] for s in spans}
    emitting = [
        s for s in spans if s["name"] == "scheduler.step" and s["emitted"]
    ]
    books = sorted(
        (
            k for s in emitting for k in _children(spans, s)
            if k["name"] == "step.bookkeep"
        ),
        key=lambda s: s["t0_mono"],
    )
    shortest = min(b["t0_mono"] - _end(a) for a, b in zip(books, books[1:]))
    longest = max(_end(b) - a["t0_mono"] for a, b in zip(books, books[1:]))
    assert shortest <= tapped[0]["itl_max_s"] <= longest
    # A paged admission also allocates its blocks under a span of its own.
    _, spans, _ = _buffered_run(
        lm, reqs, num_slots=1, kv_layout="paged", kv_block=4
    )
    (admit,) = [s for s in spans if s["name"] == "serve.admit"]
    assert [k["name"] for k in _children(spans, admit)] == [
        "admit.encode", "admit.blocks", "admit.prefill_dispatch",
        "admit.first_pick",
    ]
    # One output token has no gap to report.
    _, spans, tapped = _buffered_run(
        lm, [{"prompt": "ab", "max_new": 1}], num_slots=1
    )
    assert "itl_max_s" not in tapped[0]


def test_idle_pool_leaves_no_step_span(lm):
    from transformer_tpu.obs.trace import buffer

    params, cfg, tok = lm
    buffer().clear()
    sched = ContinuousScheduler(params, cfg, tok, num_slots=2)
    for _ in range(5):
        sched.step()
    assert len(buffer()) == 0
