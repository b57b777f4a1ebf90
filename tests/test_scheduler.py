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
    """Every ``scheduler.step`` span holds its phases inside itself, the
    enqueue of the next step before the fetch of the one in flight, and its
    counts, which are the fetched step's, add up: a stepped slot either
    emitted a token or walked a prompt-tail token, and the run's output
    tokens are the steps' ``emitted`` plus the tokens picked at admission."""
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
        for k in kids:
            assert k["t0_mono"] >= step["t0_mono"]
            assert _end(k) <= _end(step) + 1e-6
            assert k["lane"] == "scheduler" and k["trace"] == step["trace"]
        # The phases come in order: a build and a dispatch an enqueued step
        # (two where nothing was in flight, with a second prepare between
        # them; a build and no dispatch where every slot's budget ends in
        # flight), then the call's one fetch, of the step before.
        assert names[0] == "step.prepare" and names[1] == "step.build"
        assert names[-2:] == ["step.fetch", "step.bookkeep"]
        assert names.count("step.fetch") == names.count("step.bookkeep") == 1
        enqueues = names[1:-2]
        assert enqueues in (
            ["step.build"],
            ["step.build", "step.dispatch"],
            ["step.build", "step.dispatch", "step.prepare", "step.build"],
            ["step.build", "step.dispatch", "step.prepare", "step.build",
             "step.dispatch"],
        ), names
        # The step fetched was enqueued with the device at work, or says
        # why not: a call that found nothing in flight fetches the first of
        # its own two; an earlier call's step is ahead unless an admission's
        # first pick had waited for the device before it.
        assert step["ahead"] == ("drain" not in step)
        if names.count("step.prepare") == 2:
            assert step["drain"] in ("idle", "spent", "no_block")
        else:
            assert step.get("drain", "first_pick") == "first_pick"
        assert step["prefill_tokens"] >= step["prefills"] >= 0
        assert step["continued"] <= step["emitted"]
        assert step["emitted"] + step["walked"] <= step["active"]
        if not ended_early:
            assert step["emitted"] + step["walked"] == step["active"]
        # The choice of the input tokens, the pool step, and a pick a
        # sampling group with a merge of the picks between two of them.
        dispatched = [k for k in kids if k["name"] == "step.dispatch"]
        assert all(k["programs"] in (3, 5) for k in dispatched)
    # Only the first call, and one after the pool drained, found nothing in
    # flight.
    assert steps[0]["ahead"] == 0 and steps[1]["ahead"] == 1
    assert sum(s["overstepped"] for s in steps) >= 1  # a budget's end, at least
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


def test_sampling_groups_share_one_dispatch_and_one_fetch(lm):
    """Greedy and sampled requests side by side make two pick groups: one
    dispatch enqueues both picks and their merge, and the call fetches the
    one merged vector."""
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
        kids = [
            k["name"] for k in _children(spans, step)
            if k["name"] in ("step.dispatch", "step.fetch")
        ]
        assert kids[-1] == "step.fetch" and kids.count("step.fetch") == 1
    # The choice, the pool step, two picks and their merge: as often as a
    # step fed both requests (the dispatch is a call earlier than the fetch).
    programs = [s["programs"] for s in spans if s["name"] == "step.dispatch"]
    assert programs.count(5) >= len(both) and set(programs) <= {3, 5}


def test_admit_spans_and_request_gaps(lm):
    """``serve.admit`` holds its phases; ``prefill_s`` ends after the first
    pick's sync; ``itl_max_s`` lies between the shortest and the longest
    gap between the steps that emitted the request's tokens."""
    # A prompt of a power of two of tokens (BOS included) is prefilled whole
    # and picks its first token at admission; any other walks its tail.
    prompt = _whole_prompt(lm[2])
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


# --------------------------------------------------------------------------
# one decode step always in flight: step t + 1 is enqueued before step t's
# picks are fetched, and every request still gets what ``generate`` gives it

LAYOUTS = {
    "dense": dict(),
    "paged": dict(kv_layout="paged", kv_block=4),
    "paged_flash": dict(
        kv_layout="paged", kv_block=4, decode_kernel="paged_flash"
    ),
}
layouts = pytest.mark.parametrize(
    "layout", list(LAYOUTS.values()), ids=list(LAYOUTS)
)


def _tokens(tok, prompt):
    return 1 + len(tok.encode(prompt))


def _whole_prompt(tok):
    """A prompt prefilled whole (a power of two of tokens, BOS included):
    its first token is picked at admission, none of it walks."""
    return next(
        p for p in ("ab cd ef", "ab cd ef gh", "ab cd ef gh ij", "ab cd")
        if prefill_len_for(_tokens(tok, p)) == _tokens(tok, p) >= 4
    )


PARITY_CASES = {
    # Every prompt leaves a tail that walks one token a step.
    "greedy_tail": lambda tok: [
        {"prompt": _whole_prompt(tok) + " ij kl", "max_new": 7},
        {"prompt": _whole_prompt(tok) + " mn", "max_new": 4},
        {"prompt": _whole_prompt(tok) + " ab cd ef", "max_new": 5},
    ],
    # No tail: the step's first input is the token picked at admission.
    "greedy_whole": lambda tok: [
        {"prompt": _whole_prompt(tok), "max_new": 6},
        {"prompt": _whole_prompt(tok), "max_new": 3},
        {"prompt": _whole_prompt(tok), "max_new": 5},
    ],
    "sampled": lambda tok: [
        {"prompt": "ab cd", "max_new": 8, "temperature": 0.9, "seed": 3},
        {"prompt": _whole_prompt(tok), "max_new": 6, "temperature": 0.9,
         "seed": 11},
        {"prompt": "gh ij kl mn ab", "max_new": 5, "temperature": 0.9,
         "seed": 2**31 + 5},
    ],
    # Greedy, sampled and sampled-with-top-k slots in one step.
    "groups": lambda tok: [dict(r) for r in REQS],
}


@layouts
@pytest.mark.parametrize("case", list(PARITY_CASES))
def test_step_in_flight_matches_generate(lm, layout, case):
    """Token for token what each request alone gets from ``generate``,
    through 2 slots (so slots are recycled beside a step in flight)."""
    params, cfg, tok = lm
    reqs = PARITY_CASES[case](tok)
    want = _sequential(params, cfg, tok, reqs)
    sched = ContinuousScheduler(params, cfg, tok, num_slots=2, **layout)
    got = sched.run([dict(r) for r in reqs])
    assert [g.get("continuation") for g in got] == want
    assert not sched.busy and not sched._flights and len(sched._free) == 2
    if sched.paged:
        sched.pool.alloc.check_consistency()
        assert sched.pool.alloc.used_blocks == 0


@pytest.mark.parametrize(
    "seed", [0, 1, 7, 2**31 - 1, 2**31, 2**32 - 1, 2**32, 2**40 + 5, -1, -(2**31)]
)
def test_request_key_is_jax_prngkey(seed):
    import numpy as np

    from transformer_tpu.serve.scheduler import _request_key

    want = np.asarray(jax.random.PRNGKey(seed))
    got = _request_key(seed)
    assert got.dtype == want.dtype and got.tolist() == want.tolist()


class _EosAt:
    """The tokenizer with another token for its EOS."""

    def __init__(self, tok, eos_id):
        self._tok, self.eos_id = tok, eos_id

    def __getattr__(self, name):
        return getattr(self._tok, name)


def _emitted_ids(params, cfg, tok, req):
    sched = ContinuousScheduler(params, cfg, tok, num_slots=1)
    sched.submit(dict(req))
    sched.admit()
    (st,) = sched._active.values()
    while sched.busy:
        sched.step()
    return list(st.emitted)


@layouts
def test_eos_is_seen_one_step_late_and_its_row_dropped(lm, layout):
    """An EOS in mid-stream: the slot's row of the step already enqueued runs
    and is dropped, and the slot's next occupant, admitted beside that step
    in flight, never sees its pick."""
    from transformer_tpu.obs import Telemetry

    params, cfg, tok = lm
    # Sampled: a random model's greedy output repeats one token.
    ends = {"prompt": _whole_prompt(tok) + " ij", "max_new": 10,
            "temperature": 0.9, "seed": 0}
    ids = _emitted_ids(params, cfg, tok, ends)
    # The first token that differs from every one before it, third or later.
    k = next(i for i in range(2, len(ids)) if ids[i] not in ids[:i])
    eos = _EosAt(tok, ids[k])
    reqs = [
        ends,                                              # ends at the EOS
        {"prompt": "kl mn ab cd ef gh", "max_new": 14},    # decodes on
        {"prompt": "mn ef", "max_new": 6},                 # the slot's next
    ]
    want = _sequential(params, cfg, eos, reqs)
    assert want[0] == _sequential(params, cfg, tok, reqs[:1])[0][: len(want[0])]
    tel = Telemetry()
    tapped = []
    sched = ContinuousScheduler(
        params, cfg, eos, num_slots=2, telemetry=tel,
        span_tap=tapped.append, **layout,
    )
    got = sched.run([dict(r) for r in reqs])
    assert [g.get("continuation") for g in got] == want
    assert sorted(tapped, key=lambda t: t["order"])[0]["new_tokens"] == k
    # The EOS's row, and the row of each budget that ended.
    assert tel.registry.counter("serve_oversteps_total").value >= 2
    assert tel.registry.counter("serve_steps_ahead_total").value >= (
        sched.stats["steps"] - 2
    )


@layouts
def test_budget_end_leaves_the_published_prompt_blocks_alone(lm, layout):
    """A slot whose budget ends in flight still runs a row of the next step:
    behind its last valid row, never at position 0 of blocks that its
    retirement publishes to the prefix cache."""
    from transformer_tpu.serve.prefix_cache import PrefixCache

    params, cfg, tok = lm
    base = "ab cd ef gh ij kl mn ab cd"
    reqs = [
        {"prompt": base, "max_new": 3},
        {"prompt": base + " ef gh", "max_new": 4},
        {"prompt": base + " ij", "max_new": 2},
    ]
    want = _sequential(params, cfg, tok, reqs)
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=1,
        prefix_cache=PrefixCache(cfg, block_tokens=4, budget_mb=1), **layout,
    )
    got = [sched.run([dict(r)])[0] for r in reqs]
    assert [g.get("continuation") for g in got] == want
    assert sched.stats["prefix_hit_tokens"] >= 8


@layouts
def test_request_that_ends_exactly_at_max_total(lm, layout):
    """Prompt and answer fill the slot to its last token: the answer is whole
    (its last token is never fed), and the row run past the budget's end
    lands on the slot's last position, where nobody reads."""
    params, cfg, tok = lm
    prompt = _whole_prompt(tok) + " ij kl"
    max_new = 6
    reqs = [{"prompt": prompt, "max_new": max_new}] * 2
    want = _sequential(params, cfg, tok, reqs)
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=2,
        max_total=_tokens(tok, prompt) + max_new, **layout,
    )
    got = sched.run([dict(r) for r in reqs])
    assert [g.get("continuation") for g in got] == want
    # Asked for more, the budget is cut to what the slot holds.
    more = sched.run([{"prompt": prompt, "max_new": max_new + 5}])
    assert more[0]["continuation"] == want[0]


@layouts
@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_slot_freed_beside_a_step_in_flight(lm, layout, how):
    """A cancellation or an expiry with a step in flight: the slot's pick of
    that step is dropped, its neighbour and its next occupant get their own
    tokens."""
    import time

    params, cfg, tok = lm
    stay = {"prompt": "kl mn ab cd ef gh", "max_new": 12}
    nxt = {"prompt": "mn ef ab", "max_new": 5, "temperature": 0.8, "seed": 4}
    want = _sequential(params, cfg, tok, [stay, nxt])
    sched = ContinuousScheduler(params, cfg, tok, num_slots=2, **layout)
    gone = sched.submit({"prompt": "ab cd ef", "max_new": 20})
    sched.submit(dict(stay))
    sched.admit()
    sched.step()
    sched.step()
    assert sched._flights and sched.active_count == 2
    if how == "cancel":
        assert sched.cancel(gone)
    else:
        st = next(s for s in sched._active.values() if s.order == gone)
        st.deadline = time.perf_counter() - 1.0
    sched.submit(dict(nxt))
    out = sched.run([])
    assert out[0]["code"] == ("cancelled" if how == "cancel" else "deadline")
    assert "partial" in out[0]
    assert [o.get("continuation") for o in out[1:]] == want
    assert not sched._flights and len(sched._free) == 2


@pytest.mark.parametrize(
    "layout", [LAYOUTS["paged"], LAYOUTS["paged_flash"]],
    ids=["paged", "paged_flash"],
)
def test_pool_exhaustion_beside_a_step_in_flight(lm, layout):
    """No block for the step ahead: that step is not enqueued, and the
    preemption is decided with nothing in flight, as it always was. The
    preempted request's partial answer is a prefix of its whole one, the
    other request's is whole."""
    params, cfg, tok = lm
    reqs = [
        {"prompt": "ab cd ef gh ij kl", "max_new": 14},
        {"prompt": "mn ef cd ab kl ij", "max_new": 14},
    ]
    want = _sequential(params, cfg, tok, reqs)
    # 5 blocks of 4 tokens for two requests of 21 tokens each.
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=2, max_total=32,
        **{**layout, "kv_pool_blocks": 6}, admission_retries=0,
    )
    out = sched.run([dict(r) for r in reqs])
    assert sched.stats["kv_preempted"] >= 1
    codes = [o.get("code") for o in out]
    assert "resource" in codes and None in codes
    for o, w in zip(out, want):
        if o.get("code") == "resource":
            assert w.startswith(o.get("partial", ""))
        else:
            assert o["continuation"] == w
    sched.pool.alloc.check_consistency()
    assert sched.pool.alloc.used_blocks == 0 and not sched._flights


@layouts
def test_weights_staged_beside_a_step_in_flight(lm, layout):
    """A stage with a step in flight: the requests in the pool finish on the
    weights they were admitted under, the flip waits for the pool to drain
    (and the step in flight with it), the next request runs on the new."""
    params, cfg, tok = lm
    new = transformer_init(jax.random.PRNGKey(1), cfg)
    reqs = [
        {"prompt": "ab cd ef gh ij", "max_new": 6},
        {"prompt": "kl mn", "max_new": 9},
    ]
    after = {"prompt": "ab cd ef gh ij", "max_new": 6}
    want_old = _sequential(params, cfg, tok, reqs)
    want_new = _sequential(new, cfg, tok, [after])
    assert want_new[0] != want_old[0]
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=2, weight_version="old", **layout
    )
    for r in reqs:
        sched.submit(dict(r))
    sched.admit()
    sched.step()
    assert sched._flights
    sched.stage_params(new, "new")
    sched.submit(dict(after))
    out = sched.run([])
    assert [o["weight_version"] for o in out] == ["old", "old", "new"]
    assert [o["continuation"] for o in out] == want_old + want_new
    assert sched.consume_swap_events() == [{"ok": True, "version": "new"}]


@layouts
def test_next_step_is_enqueued_before_the_fetch(lm, layout):
    """The order itself: in every call after the first, the dispatch of the
    next step closes before the fetch of the step in flight opens, and that
    step had been enqueued ahead; one small program chooses every step's
    input tokens, compiled once."""
    from transformer_tpu.analysis.retrace import _cache_size
    from transformer_tpu.serve import scheduler as smod

    reqs = [
        {"prompt": "ab cd ef gh ij", "max_new": 12},
        {"prompt": "kl mn ab", "max_new": 12, "temperature": 0.7, "seed": 9},
    ]
    _buffered_run(lm, reqs, num_slots=2, **layout)  # compiles
    compiled = _cache_size(smod._choose)
    out, spans, _ = _buffered_run(lm, reqs, num_slots=2, **layout)
    assert all("continuation" in o for o in out)
    assert _cache_size(smod._choose) == compiled
    steps = sorted(
        (s for s in spans if s["name"] == "scheduler.step"),
        key=lambda s: s["t0_mono"],
    )
    assert len(steps) >= 12
    for i, step in enumerate(steps):
        kids = _children(spans, step)
        (fetch,) = [k for k in kids if k["name"] == "step.fetch"]
        dispatches = [k for k in kids if k["name"] == "step.dispatch"]
        assert all(_end(d) <= fetch["t0_mono"] for d in dispatches)
        assert step["ahead"] == (i > 0)
        # Two enqueued where nothing was in flight, one ever after, none
        # once every budget ends in the step in flight.
        assert len(dispatches) == (2 if i == 0 else 1) or i >= len(steps) - 2


# --------------------------------------------------------------------------
# what lies on the device's queue between two steps: the prefills a step's
# span counts, and why a step was not enqueued ahead (counts and strings,
# never durations)

two_layouts = pytest.mark.parametrize(
    "layout", [LAYOUTS["dense"], LAYOUTS["paged"]], ids=["dense", "paged"]
)


def _steps_of(spans):
    return sorted(
        (s for s in spans if s["name"] == "scheduler.step"),
        key=lambda s: s["t0_mono"],
    )


def _step_spans():
    from transformer_tpu.obs.trace import buffer

    return _steps_of(buffer().snapshot())


def _finish(sched):
    return [o.get("continuation") for o in sched.run([])]


@two_layouts
@pytest.mark.parametrize("k", [1, 3])
def test_step_span_counts_the_prefills_enqueued_before_it(lm, layout, k):
    """After k admissions between two steps the next step fetched carries
    ``prefills == k`` and the sum of their prefilled tokens, the one after
    carries 0; the first step of a run found the device idle."""
    from transformer_tpu.obs.trace import buffer

    params, cfg, tok = lm
    first = {"prompt": _whole_prompt(tok) + " ij kl", "max_new": 12}
    late = [
        {"prompt": _whole_prompt(tok) + " mn", "max_new": 6},
        {"prompt": "ab cd", "max_new": 5, "temperature": 0.9, "seed": 3},
        {"prompt": _whole_prompt(tok) + " ab cd ef", "max_new": 4},
    ][:k]
    want = _sequential(params, cfg, tok, [first, *late])
    buffer().clear()
    sched = ContinuousScheduler(params, cfg, tok, num_slots=4, **layout)
    sched.submit(dict(first))
    sched.admit()
    sched.step()  # enqueues two, fetches the first
    sched.step()
    for r in late:
        sched.submit(dict(r))
    sched.admit()  # k prefills behind the step in flight
    assert sched._prefills_queued == k
    for _ in range(3):
        sched.step()
    assert sched._prefills_queued == sched._prefill_tokens_queued == 0
    steps = _step_spans()
    assert len(steps) == 5
    fed = [
        prefill_len_for(_tokens(tok, r["prompt"])) for r in [first, *late]
    ]
    admits = [
        s["prefill_tokens"] for s in buffer().snapshot()
        if s["name"] == "serve.admit"
    ]
    assert sorted(admits) == sorted(fed)
    assert [s["prefills"] for s in steps] == [1, 0, 0, k, 0]
    assert [s["prefill_tokens"] for s in steps] == [fed[0], 0, 0, sum(fed[1:]), 0]
    assert [s["ahead"] for s in steps] == [0, 1, 1, 1, 1]
    assert steps[0]["drain"] == "idle"
    assert all("drain" not in s for s in steps[1:])
    assert _finish(sched) == want
    later = _step_spans()[5:]
    assert later and all(s["prefills"] == 0 for s in later)


@two_layouts
def test_first_pick_drains_the_flight(lm, layout):
    """A prompt of a power of two of tokens is prefilled whole and its first
    pick waits for the device, the step in flight included: the step
    enqueued next is not ahead, says ``first_pick``, and
    ``serve_steps_ahead_total`` does not count it."""
    from transformer_tpu.obs import Telemetry
    from transformer_tpu.obs.trace import buffer

    params, cfg, tok = lm
    reqs = [
        {"prompt": _whole_prompt(tok) + " ij", "max_new": 12},
        {"prompt": _whole_prompt(tok), "max_new": 6},
    ]
    want = _sequential(params, cfg, tok, reqs)
    buffer().clear()
    tel = Telemetry()
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=2, telemetry=tel, **layout
    )
    sched.submit(dict(reqs[0]))
    sched.admit()
    sched.step()
    sched.step()
    assert sched._flights and sched._drain is None
    sched.submit(dict(reqs[1]))
    sched.admit()
    assert sched._drain == "first_pick"
    sched.step()  # enqueues the drained step, fetches the one before it
    assert sched._drain is None
    sched.step()
    steps = _step_spans()
    assert [s["ahead"] for s in steps] == [0, 1, 1, 0]
    assert [s.get("drain") for s in steps] == ["idle", None, None, "first_pick"]
    assert [s["prefills"] for s in steps] == [1, 0, 0, 1]
    assert _finish(sched) == want
    steps = _step_spans()
    assert all(s["ahead"] == ("drain" not in s) for s in steps)
    assert tel.registry.counter("serve_steps_ahead_total").value == sum(
        s["ahead"] for s in steps
    ) == len(steps) - 2
    # With nothing in flight the first pick drains nothing: the pool was
    # idle (a serve loop's poll of the empty pool forgets the last cause).
    sched.step()
    buffer().clear()
    sched.run([dict(reqs[1])])
    assert _step_spans()[0]["drain"] == "idle"


@two_layouts
def test_budgets_that_all_end_in_flight_say_spent(lm, layout):
    """Every occupied slot's last step is in flight: the call enqueues
    nothing, and the step that feeds the slot's next occupant says so."""
    params, cfg, tok = lm
    reqs = [
        {"prompt": "ab cd ef", "max_new": 4},
        {"prompt": "kl mn ab", "max_new": 3},
    ]
    want = _sequential(params, cfg, tok, reqs)
    out, spans, _ = _buffered_run(lm, reqs, num_slots=1, **layout)
    assert [o["continuation"] for o in out] == want
    steps = _steps_of(spans)
    drains = [s["drain"] for s in steps if not s["ahead"]]
    assert drains == ["idle", "spent"]
    # The step that says it is the first the second request rode.
    (spent,) = [s for s in steps if s.get("drain") == "spent"]
    assert spent["prefills"] == 1
    # The call that enqueued nothing built a step and dispatched none.
    quiet = [
        s for s in steps
        if [k["name"] for k in _children(spans, s)].count("step.dispatch") == 0
    ]
    assert len(quiet) == 2  # one a request's end


@pytest.mark.parametrize(
    "layout", [LAYOUTS["paged"], LAYOUTS["paged_flash"]],
    ids=["paged", "paged_flash"],
)
def test_no_block_to_step_ahead_says_no_block(lm, layout):
    """The pool of ``test_pool_exhaustion_beside_a_step_in_flight``: the call
    that found no block enqueued nothing, so the next step found the device
    drained and names the cause."""
    reqs = [
        {"prompt": "ab cd ef gh ij kl", "max_new": 14},
        {"prompt": "mn ef cd ab kl ij", "max_new": 14},
    ]
    out, spans, _ = _buffered_run(
        lm, reqs, num_slots=2, max_total=32, admission_retries=0,
        **{**layout, "kv_pool_blocks": 6},
    )
    assert "resource" in [o.get("code") for o in out]
    steps = _steps_of(spans)
    drains = [s["drain"] for s in steps if not s["ahead"]]
    assert drains[0] == "idle" and "no_block" in drains
    assert set(drains) <= {"idle", "no_block", "spent"}
    # The step before a no_block one was fetched by a call that dispatched
    # nothing.
    i = next(i for i, s in enumerate(steps) if s.get("drain") == "no_block")
    names = [k["name"] for k in _children(spans, steps[i - 1])]
    assert "step.dispatch" not in names


# --------------------------------------------------------------------------
# the paged prefill donates its pool: a slot's rows and state are written in
# place, and a failure after the pool was handed over is the pool's

DONATING_POOLS = {
    # K/V heads of 128 lanes, as starcoder2-3b keeps them.
    "kv128": ("sc2-3b.chat-saturated", dict(d_model=256, num_heads=2, dtype="bfloat16")),
    # 64-wide heads two a lane row beside short-convolution state, as lfm2-8b-a1b.
    "kv64_conv": (
        "lfm2-8b-a1b.longform-saturated",
        dict(num_heads=8, num_kv_heads=8, head_size=64, dtype="bfloat16"),
    ),
    # A latent pool beside delta-rule matrix states, as kimi-linear-48b-a3b.
    "latent_kda": ("kimi-linear-48b-a3b.reasoning-saturated", {}),
    # State-space matrix states beside one K/V pool and layers that hold no
    # pool entry at all, as nemotron-3-super.
    "kv_ssm": ("nemotron-3-super.chat-saturated", {}),
}


@pytest.mark.parametrize("pool_layout", list(DONATING_POOLS))
def test_paged_prefill_aliases_every_pool_leaf(pool_layout):
    """Compiled from abstract shapes, the prefill's ``input_output_alias``
    maps every leaf of its pool argument onto the pool it returns: a lost
    donation fails here, where on the chip it costs a copy of every pool an
    admission (PR 38)."""
    import json
    import os
    import re

    import jax.numpy as jnp

    from transformer_tpu.serve import scheduler as S

    cell_name, widths = DONATING_POOLS[pool_layout]
    root = os.path.join(os.path.dirname(__file__), "..", "perfbench")
    with open(os.path.join(root, "workloads", cell_name + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(root, "configs", cell["config"] + ".json")) as f:
        model = json.load(f)["model"]
    cfg = ModelConfig(**{**model, **cell["rehearse"]["model"], **widths})
    params = jax.eval_shape(lambda k: transformer_init(k, cfg), jax.random.PRNGKey(0))
    pool, table, _ = S.abstract_paged_pool(cfg, 2, 32, 5, 16)
    leaves = jax.tree.leaves(pool)
    if pool_layout == "kv64_conv":
        assert {leaf.shape[2:] for leaf in leaves if leaf.ndim == 4} == {(4, 128)}
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    head = S._slot_prefill_paged.lower(
        params, pool, table, scalar, jax.ShapeDtypeStruct((1, 16), jnp.int32),
        scalar, cfg, 8, 16, 32,
    ).compile().as_text().split("\n", 1)[0]
    aliased = {
        int(param) for param in re.findall(
            r"\((\d+), \{\}, (?:may|must)-alias\)",
            head.split("input_output_alias=", 1)[1].split("}, entry", 1)[0],
        )
    }
    first = len(jax.tree.leaves(params))  # the pool's leaves follow the params'
    assert aliased == set(range(first, first + len(leaves)))


def _raising_after(real, n_ok: int, *, donate: bool):
    """``real``, but for call ``n_ok + 1``, which raises, having run the real
    program first (``donate``) or before it could be enqueued."""
    calls = []

    def prefill(params, pool_caches, *args):
        calls.append(len(calls))
        if len(calls) != n_ok + 1:
            return real(params, pool_caches, *args)
        if donate:
            real(params, pool_caches, *args)
            raise RuntimeError("lost after the dispatch")
        # jit refuses an argument that is no array before it runs anything
        return real(params, pool_caches, object(), *args[1:])

    return prefill


def test_prefill_refused_before_enqueue_answers_alone(lm):
    """A prefill whose dispatch raises before the program is enqueued left
    the donated pool whole: that one request answers an admission error and
    every other one what it gets alone from ``generate``."""
    from transformer_tpu.obs import Telemetry

    params, cfg, tok = lm
    tel = Telemetry()
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=2, telemetry=tel, **LAYOUTS["paged_flash"]
    )
    sched._fn_slot_prefill_paged = _raising_after(
        sched._fn_slot_prefill_paged, 2, donate=False
    )
    got = sched.run([dict(r) for r in REQS])
    failed = [i for i, g in enumerate(got) if "error" in g]
    assert len(failed) == 1 and got[failed[0]]["code"] == "validation"
    assert "TypeError" in got[failed[0]]["error"]
    want = _sequential(params, cfg, tok, REQS)
    assert [g.get("continuation") for g in got] == [
        None if i in failed else w for i, w in enumerate(want)
    ]
    assert tel.registry.counter("serve_admit_pool_lost_total").value == 0
    assert len(sched._free) == 2 and not sched.busy


def test_prefill_that_took_the_pool_stops_the_scheduler(lm):
    """A prefill that failed after its program took the donated pool leaves
    no pool to serve from: the serving loop raises the failure instead of
    answering it as that request's admission error, and
    ``serve_admit_pool_lost_total`` counts it."""
    from transformer_tpu.obs import Telemetry

    params, cfg, tok = lm
    tel = Telemetry()
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=2, telemetry=tel, **LAYOUTS["paged_flash"]
    )
    sched._fn_slot_prefill_paged = _raising_after(
        sched._fn_slot_prefill_paged, 2, donate=True
    )
    with pytest.raises(RuntimeError, match="lost after the dispatch"):
        sched.run([dict(r) for r in REQS])
    assert tel.registry.counter("serve_admit_pool_lost_total").value == 1
    assert not sched.drain_ready()  # nothing was answered as its error
