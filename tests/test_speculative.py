"""Speculative decoding contracts (``transformer_tpu/serve/speculative.py``):
greedy speculative output must be BYTE-IDENTICAL to plain greedy decode —
standalone (``lm_generate_speculative`` vs ``lm_generate``) and through the
continuous scheduler — across both drafters, k in {1, 2, 4}, chunked and
unchunked prefill, and the int8/GQA cache variants. Plus: rejection-sampling
acceptance, rolling-window refusal, O(1) rollback semantics, speculative
telemetry, and the zero-recompile guarantee across varying accept lengths."""

import dataclasses
import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transformer_tpu.config import PAD_ID, ModelConfig
from transformer_tpu.data.tokenizer import SubwordTokenizer
from transformer_tpu.models import transformer_init
from transformer_tpu.serve import ContinuousScheduler, ModelDrafter, NgramDrafter
from transformer_tpu.serve.speculative import (
    build_verify_row,
    judge_row,
    speculative_generate,
)
from transformer_tpu.train.decode import lm_generate, lm_generate_speculative

LM = ModelConfig(
    num_layers=2, d_model=16, num_heads=4, dff=32,
    input_vocab_size=48, target_vocab_size=48, max_position=64,
    decoder_only=True, tie_output=True, dtype="float32", dropout_rate=0.0,
)

# Speculation composes with every NON-ROLLING cache variant; rolling-window
# caches are structurally refused (eviction defeats rollback-by-index).
VARIANTS = {
    "base": LM,
    "int8": dataclasses.replace(LM, kv_cache_int8=True),
    "gqa": dataclasses.replace(LM, num_kv_heads=2),
}

PROMPTS = [
    [1, 5, 9, 5, 9, 7],           # repetitive: n-gram drafting lands
    [1, 11, 23, 7],               # irregular: drafts mostly miss
    [1],                          # bare BOS: drafting from nothing
]


class NoDrafter:
    """A drafter that never proposes — speculative machinery reduces to
    plain stepping, which must be EXACTLY plain decoding (incl. sampled
    draws, since bonus picks use the same position-keyed rng folding)."""

    def start(self, prompt_ids):
        return None

    def propose(self, state, context, k):
        return []


def _drafters(params, cfg):
    # The draft model IS the target model here: the ideal drafter (every
    # proposal accepted) — losslessness must hold at both extremes.
    return {
        "ngram": NgramDrafter(),
        "model": ModelDrafter(params, cfg, cfg.max_position + 1, eos_id=2),
    }


@pytest.mark.parametrize("name", sorted(VARIANTS))
@pytest.mark.parametrize("k", [1, 2, 4])
def test_greedy_lossless_standalone(name, k):
    """Greedy lm_generate_speculative == lm_generate, bit for bit, for both
    drafters and chunked/unchunked prefill (the PR's acceptance bar)."""
    cfg = VARIANTS[name]
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    max_new = 10
    for prompt in PROMPTS:
        want = np.asarray(
            lm_generate(
                params, jnp.asarray([prompt], jnp.int32), cfg, max_new,
                eos_id=2,
            )
        )[0]
        for dname, drafter in _drafters(params, cfg).items():
            for chunk in (0, 3):
                got, stats = lm_generate_speculative(
                    params, prompt, cfg, max_new, 2,
                    speculate_k=k, drafter=drafter, prefill_chunk=chunk,
                )
                padded = np.full(max_new, PAD_ID, np.int32)
                padded[: len(got)] = got
                np.testing.assert_array_equal(
                    padded, want,
                    err_msg=f"{name} k={k} drafter={dname} chunk={chunk}",
                )
                assert stats["verify_forwards"] >= 1
                assert 0 <= stats["accepted"] <= stats["drafted"]


def test_sampled_matches_plain_with_no_drafts():
    """With a drafter that never proposes, SAMPLED speculative generation
    must equal plain sampled lm_generate bit for bit: bonus picks fold the
    rng by absolute position exactly like the sequential loop, so the
    machinery itself adds no randomness."""
    cfg = LM
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    prompt = [1, 5, 9, 5, 9, 7]
    kw = dict(sample=True, temperature=0.8, top_k=8, top_p=0.9)
    want = np.asarray(
        lm_generate(
            params, jnp.asarray([prompt], jnp.int32), cfg, 8, eos_id=2,
            rng=jax.random.PRNGKey(7), **kw,
        )
    )[0]
    got, _ = speculative_generate(
        params, cfg, prompt, 8, 2, speculate_k=3, drafter=NoDrafter(),
        seed=7, **kw,
    )
    padded = np.full(8, PAD_ID, np.int32)
    padded[: len(got)] = got
    np.testing.assert_array_equal(padded, want)


def test_sampled_rejection_acceptance_runs():
    """Sampled + a live drafter: rejection-sampling acceptance produces a
    valid stream (distribution-losslessness is the design contract; the
    draw-level contract — no drafts == plain — is pinned above)."""
    cfg = LM
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    got, stats = speculative_generate(
        params, cfg, [1, 5, 9, 5, 9, 7], 10, 2, speculate_k=3,
        drafter=NgramDrafter(), sample=True, temperature=0.9, top_k=8,
        seed=3,
    )
    assert all(0 <= t < cfg.target_vocab_size for t in got)
    assert stats["verify_forwards"] >= 1
    # Deterministic: same seed, same stream.
    again, _ = speculative_generate(
        params, cfg, [1, 5, 9, 5, 9, 7], 10, 2, speculate_k=3,
        drafter=NgramDrafter(), sample=True, temperature=0.9, top_k=8,
        seed=3,
    )
    assert got == again


# --------------------------------------------------------------------------
# scheduler integration


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


REQS = [
    {"prompt": "ab cd ef gh ij", "max_new": 6},
    {"prompt": "kl", "max_new": 2},
    {"prompt": "ef", "max_new": 0},           # empty-budget edge
    {"prompt": "ab cd", "max_new": 8, "temperature": 0.9, "seed": 3},
    {"prompt": "mn ef cd", "max_new": 1},
    {"prompt": "gh ij kl mn", "max_new": 5, "temperature": 0.7, "top_k": 4,
     "seed": 1},
]


@pytest.mark.parametrize("k", [1, 2, 4])
def test_scheduler_greedy_parity(lm, k):
    """Speculative scheduler == plain scheduler for every GREEDY request
    (byte-identical continuations) under mixed traffic, for both drafters,
    while sampled requests still answer."""
    params, cfg, tok = lm
    plain = ContinuousScheduler(params, cfg, tok, num_slots=2).run(
        [dict(r) for r in REQS]
    )
    for dname, drafter in _drafters(params, cfg).items():
        sched = ContinuousScheduler(
            params, cfg, tok, num_slots=2, speculate_k=k, drafter=drafter
        )
        got = sched.run([dict(r) for r in REQS])
        for i, r in enumerate(REQS):
            assert "continuation" in got[i], (k, dname, got[i])
            if float(r.get("temperature", 0.0)) == 0.0:
                assert got[i] == plain[i], (k, dname, i)
        assert sched.stats["steps"] > 0
        # Slots recycled and the pool drained, like the plain path.
        assert not sched.busy and len(sched._free) == 2


def test_scheduler_no_drafts_full_parity(lm):
    """With a never-proposing drafter the speculative path must reproduce
    the plain scheduler EXACTLY — sampled requests included (bonus picks
    use the same position-keyed folding sequential serving uses)."""
    params, cfg, tok = lm
    plain = ContinuousScheduler(params, cfg, tok, num_slots=2).run(
        [dict(r) for r in REQS]
    )
    got = ContinuousScheduler(
        params, cfg, tok, num_slots=2, speculate_k=3, drafter=NoDrafter()
    ).run([dict(r) for r in REQS])
    assert got == plain


def test_scheduler_mixed_spec_and_chunked_prefill(lm):
    """Per-request "speculate": false rides the same verify step (padded
    row) with identical answers, and chunked prefill (tail-fed prompts)
    composes with speculation."""
    params, cfg, tok = lm
    plain = ContinuousScheduler(params, cfg, tok, num_slots=2).run(
        [dict(REQS[0]), dict(REQS[0]), dict(REQS[1])]
    )
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=2, speculate_k=2, prefill_chunk=2
    )
    got = sched.run(
        [dict(REQS[0]), dict(REQS[0], speculate=False), dict(REQS[1])]
    )
    assert [g["continuation"] for g in got] == [
        p["continuation"] for p in plain
    ]


def test_scheduler_error_isolation_with_speculation(lm):
    """Admission failures still answer alone and never leak a slot when
    speculation is on (the per-request isolation guarantee)."""
    params, cfg, tok = lm
    good = {"prompt": "ab cd", "max_new": 3}
    over = {"prompt": "ab cd ef gh " * 30, "max_new": 3}
    sched = ContinuousScheduler(params, cfg, tok, num_slots=2, speculate_k=2)
    got = sched.run([dict(good), dict(over), dict(good)])
    assert got[0]["continuation"] == got[2]["continuation"]
    assert "max_position" in got[1]["error"]
    assert len(sched._free) == 2


def test_rolling_window_refused():
    """Rolling-window caches cannot roll back (eviction): the scheduler,
    the standalone loop, and the cache helper itself all refuse."""
    from transformer_tpu.ops.attention import init_cache, rollback_cache

    cfg = dataclasses.replace(LM, attention_window=4)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="roll"):
        speculative_generate(params, cfg, [1, 5], 4, 2, speculate_k=2)
    tok = SubwordTokenizer.build_from_corpus(["ab cd"] * 3, target_vocab_size=280)
    cfg_tok = dataclasses.replace(
        cfg,
        input_vocab_size=tok.model_vocab_size,
        target_vocab_size=tok.model_vocab_size,
    )
    with pytest.raises(ValueError, match="rolling"):
        ContinuousScheduler(
            transformer_init(jax.random.PRNGKey(0), cfg_tok), cfg_tok, tok,
            num_slots=1, speculate_k=2,
        )
    with pytest.raises(ValueError, match="rolling"):
        rollback_cache(init_cache(1, 8, 2, 4, window=4), 0)


def test_model_drafter_vocab_mismatch_refused_at_construction():
    """A draft model whose vocab differs from the target's must fail at
    startup — a draft token id past the target's (V,) logits would
    otherwise crash the acceptance path mid-serve."""
    cfg = dataclasses.replace(LM, target_vocab_size=64, input_vocab_size=64)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="SHARED tokenizer"):
        ModelDrafter(params, cfg, 33, target_vocab_size=48)
    # Matching vocab constructs fine.
    ModelDrafter(params, cfg, 33, target_vocab_size=64)


# --------------------------------------------------------------------------
# planning/judging units


def test_ngram_drafter_prefers_full_continuations():
    """The drafter returns the most recent match with a FULL k-token
    continuation (a match hugging the context end has nothing after it)."""
    d = NgramDrafter(max_n=2)
    ctx = [1, 7, 8, 9, 5, 7, 8]
    # suffix (7, 8) matches at index 1 with continuation [9, 5].
    assert d.propose(None, ctx, 2) == [9, 5]
    assert d.propose(None, ctx, 1) == [9]
    assert d.propose(None, [1, 2, 3], 2) == []  # no repeat: nothing credible
    assert d.propose(None, [1], 2) == []


def test_build_verify_row_phases():
    """Prompt tail is teacher-forced ahead of drafts; drafts only extend
    the END of the determined history."""
    history = [1, 2, 3, 4, 5]  # prompt_len 5, nothing generated

    class Fixed:
        def propose(self, state, context, k):
            return [9] * k

    # Mid-prompt: forced tokens fill the row before any proposal.
    row, n = build_verify_row(history, 1, 2, Fixed(), None)
    assert row == [2, 3, 4] and n == 0
    # Boundary-straddling: forced tail + proposals.
    row, n = build_verify_row(history, 3, 3, Fixed(), None)
    assert row == [4, 5, 9, 9] and n == 2
    # Generating (history ends at the pending token): all proposals.
    row, n = build_verify_row(history, 4, 2, Fixed(), None)
    assert row == [5, 9, 9] and n == 2


def test_judge_row_accept_reject_bonus():
    picks = {0: 9, 1: 9, 2: 4}
    accept = lambda j, d: (picks[j] == d, picks[j])  # noqa: E731
    bonus = lambda j: picks[j]  # noqa: E731
    # Full accept: every draft matches, bonus appended, all fed kept.
    emitted, keep, acc = judge_row([7, 9, 9], 5, 5, accept, bonus)
    assert (emitted, keep, acc) == ([9, 9, 4], 3, 2)
    # Mismatch at the second draft: its corrected pick is emitted, the
    # rejected tail is dropped (keep < row width).
    emitted, keep, acc = judge_row([7, 9, 8], 5, 5, accept, bonus)
    assert (emitted, keep, acc) == ([9, 9], 2, 1)
    # Entirely inside the prompt: nothing emitted, everything kept.
    emitted, keep, acc = judge_row([7, 9, 9], 0, 10, accept, bonus)
    assert (emitted, keep, acc) == ([], 3, 0)


@pytest.mark.parametrize(
    "temperature,top_k,top_p",
    [(1.0, 0, 1.0), (0.7, 0, 1.0), (1.0, 5, 1.0), (0.9, 0, 0.8),
     (0.8, 6, 0.9), (2.0, 3, 0.5)],
)
def test_filtered_probs_matches_sample_token_distribution(
    monkeypatch, temperature, top_k, top_p
):
    """``filtered_probs`` is the host-side twin of ``sample_token``'s
    truncated distribution — the rejection-sampling acceptance contract
    rests on the two agreeing. Pin them against the PRODUCTION path: grab
    the exact filtered logits ``sample_token`` hands to
    ``jax.random.categorical`` and compare softmax(those) to
    ``filtered_probs`` (a drift in either side's temperature/top-k/top-p
    semantics fails here, not as a silently biased output distribution)."""
    from transformer_tpu.serve.speculative import filtered_probs
    from transformer_tpu.train.decode import sample_token

    rng = np.random.default_rng(0)
    logits = rng.normal(size=(1, 32)).astype(np.float32) * 3.0
    captured = {}
    real = jax.random.categorical

    def spy(key, final_logits, axis=-1):
        captured["logits"] = np.asarray(final_logits, np.float32)
        return real(key, final_logits, axis=axis)

    monkeypatch.setattr(jax.random, "categorical", spy)
    sample_token(
        jnp.asarray(logits), jax.random.PRNGKey(0), sample=True,
        temperature=temperature, top_k=top_k, top_p=top_p,
    )
    device = captured["logits"][0]
    finite = np.isfinite(device)
    want = np.zeros_like(device)
    want[finite] = np.exp(device[finite] - device[finite].max())
    want /= want.sum()
    got = filtered_probs(logits[0], temperature, top_k, top_p)
    np.testing.assert_array_equal(got > 0, finite)  # identical support
    np.testing.assert_allclose(got, want, atol=1e-6)


# --------------------------------------------------------------------------
# telemetry + retrace


def test_speculative_telemetry_inert_and_counted(lm):
    """Telemetry on/off never changes speculative answers; spans carry
    drafted/accepted/forwards; summarize derives tokens-per-forward and
    acceptance rate; spec counters land in the registry."""
    from transformer_tpu.obs import EventLog, Telemetry
    from transformer_tpu.obs.__main__ import summarize_events

    params, cfg, tok = lm
    reqs = [dict(r) for r in REQS[:4]]
    plain = ContinuousScheduler(
        params, cfg, tok, num_slots=2, speculate_k=2
    ).run([dict(r) for r in reqs])
    buf = io.StringIO()
    tel = Telemetry(events=EventLog(buf), interval=0.0)
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=2, speculate_k=2, telemetry=tel
    )
    got = sched.run([dict(r) for r in reqs])
    assert got == plain  # answers byte-identical, metrics on or off

    events = [json.loads(line) for line in buf.getvalue().splitlines()]
    spans = [e for e in events if e.get("kind") == "serve.request"]
    assert spans and all("forwards" in s for s in spans if s.get("new_tokens"))
    assert any("drafted" in s for s in spans)
    report = summarize_events(events)
    assert report["serve"]["tokens_per_forward"] > 0
    spec = report["serve"]["speculative"]
    assert spec["drafted"] >= spec["accepted"] >= 0
    assert 0.0 <= spec["acceptance_rate"] <= 1.0
    snap = tel.registry.snapshot()
    assert snap["serve_spec_drafted_total"] == spec["drafted"]
    assert snap["serve_spec_accepted_total"] == spec["accepted"]


def test_speculative_zero_recompiles():
    """Acceptance criterion: varying accept lengths mint no new programs on
    the scheduler's speculative hot path (verify/pick/prefill/rollback)."""
    from transformer_tpu.analysis.retrace import speculative_retrace_report

    deltas = speculative_retrace_report(steps=3)
    assert len(deltas) == 4
    bad = [d.to_dict() for d in deltas if not d.within_budget]
    assert not bad, bad


def test_verify_contract_covers_cache_variants():
    """The verify-step cache-parity contract runs for every LM cache
    variant in the fast matrix (plain/int8/rolling/GQA)."""
    from transformer_tpu.analysis import run_contracts

    results = run_contracts("fast")
    verify = {r.config for r in results if r.contract == "verify_cache_parity"}
    assert {"lm_bf16", "lm_int8_cache", "lm_window", "lm_gqa"} <= verify
    assert all(
        r.ok for r in results if r.contract == "verify_cache_parity"
    ), [str(r) for r in results if r.contract == "verify_cache_parity"]


def test_tokens_per_forward_counts(lm):
    """On repetitive prompts with ``speculate_k=4`` the scheduler's own
    counters show what speculation is for: more than 1.5 tokens a target
    forward (incremental decode's ceiling is 1.0), and an acceptance rate
    that is a rate. Counts only, no clock."""
    from transformer_tpu.obs import Telemetry

    params, cfg, tok = lm
    tel = Telemetry()
    tapped = []
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=2, speculate_k=4, telemetry=tel,
        span_tap=tapped.append,
    )
    out = sched.run([
        {"prompt": "kl kl kl kl", "max_new": 24},
        {"prompt": "ab cd ab cd ab cd", "max_new": 20},
    ])
    assert all("continuation" in a for a in out)
    reg = tel.registry
    tokens = reg.counter("serve_generated_tokens_total").value
    forwards = sum(t["forwards"] for t in tapped)
    assert tokens == sum(t["new_tokens"] for t in tapped) == 44
    assert forwards > 0 and tokens / forwards > 1.5, (tokens, forwards)
    drafted = reg.counter("serve_spec_drafted_total").value
    accepted = reg.counter("serve_spec_accepted_total").value
    assert drafted > 0 and 0.0 <= accepted / drafted <= 1.0
    assert drafted == sum(t.get("drafted", 0) for t in tapped)
    assert accepted == sum(t.get("draft_accepted", 0) for t in tapped)
