"""transformer_tpu.analysis cost model + sharding analysis: hand-computable
canned programs (known FLOPs/bytes), liveness vs donation, the MQA/GQA
KV-bytes argument made numeric, the collective inventory, TPA201-205 corpus
twins, the budget-baseline workflow, CLI exit codes, and — slow-marked —
the two injected-regression canaries (a +1-buffer memory regression and a
stray all_gather) that prove the baseline gate actually detects what it
pins."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transformer_tpu.analysis.__main__ import main as analysis_main
from transformer_tpu.analysis.configs import FAST_MATRIX
from transformer_tpu.analysis.costs import (
    CostReport,
    canned_cost_reports,
    compare_to_baseline,
    default_costs_baseline_path,
    kv_cache_bytes,
    load_costs_baseline,
    program_costs,
    write_costs_baseline,
)
from transformer_tpu.analysis.sharding import (
    collective_inventory,
    run_sharding,
)

_FIXTURES = pathlib.Path(__file__).parent / "fixtures"
_SHARD_BAD = str(_FIXTURES / "tpa_shard_bad_corpus.py")
_SHARD_GOOD = str(_FIXTURES / "tpa_shard_good_corpus.py")

_f32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32)  # noqa: E731


# --------------------------------------------------------------------------
# the cost model on hand-computable programs


def test_dot_flops_and_bytes():
    """(8,16) @ (16,4) f32: FLOPs = 2*8*16*4 = 1024; bytes moved = the
    dot's operands + result = 512 + 256 + 128 = 896; peak = both inputs
    live + the output = 896 (nothing is donated)."""
    r = program_costs("dot", lambda a, b: a @ b, _f32(8, 16), _f32(16, 4))
    assert r.flops == 1024
    assert r.bytes_moved == 896
    assert r.peak_bytes == 896
    assert r.collectives == {}
    assert r.arg_bytes == 768 and r.out_bytes == 128


def test_batched_dot_flops():
    """Batch dims multiply through: (4,8,16) @ (4,16,4) = 4 * 1024 FLOPs."""
    r = program_costs(
        "bmm",
        lambda a, b: jax.lax.dot_general(
            a, b, (((2,), (1,)), ((0,), (0,)))
        ),
        _f32(4, 8, 16), _f32(4, 16, 4),
    )
    assert r.flops == 4 * 2 * 8 * 16 * 4


def test_reduce_flops_counts_operand():
    r = program_costs("red", lambda a: jnp.sum(a), _f32(32, 4))
    assert r.flops == 128  # one op per reduced element


def test_liveness_chain_vs_donation():
    """y=a+1; z=y+1; w=z+1 over 1KiB buffers. Non-donated: the input is
    caller-held for the whole program, so the worst instant holds a + y + z
    = 3 buffers. Donated: `a` dies after the first add — the worst instant
    holds only 2 buffers. The delta IS one buffer, which is exactly what
    the +1-buffer canary regression looks like."""
    n = 256  # f32 -> 1KiB per buffer
    buf = 4 * n

    def chain(a):
        y = a + 1.0
        z = y + 1.0
        return z + 1.0

    plain = program_costs("chain", chain, _f32(n))
    donated = program_costs("chain_d", chain, _f32(n), donate_argnums=(0,))
    assert plain.peak_bytes == 3 * buf
    assert donated.peak_bytes == 2 * buf
    assert plain.peak_bytes - donated.peak_bytes == buf


def test_donated_buffer_counts_until_last_use():
    """A donated input that is ALSO the last operand read must stay in the
    peak until that read: peak = a + b + out at the dot, not less."""
    r = program_costs(
        "dot_d", lambda a, b: a @ b, _f32(8, 16), _f32(16, 4),
        donate_argnums=(0, 1),
    )
    assert r.peak_bytes == 896  # donation frees nothing before the only use


def test_dead_output_not_held():
    """An intermediate nobody reads dies immediately; it still costs its
    transient allocation at its own equation but does not stack onto later
    peaks."""
    n = 256
    buf = 4 * n

    def f(a):
        _ = a * 2.0  # dead
        return a + 1.0

    r = program_costs("dead", f, _f32(n))
    assert r.peak_bytes == 2 * buf  # a + one live buffer at a time


# --------------------------------------------------------------------------
# KV budgets: the MQA/one-write-head argument, numerically


def test_kv_bytes_mqa_ratio():
    """GQA with n_kv_heads=1 vs full MHA: KV bytes per token shrink by
    exactly num_heads — the one-write-head paper's claim on this repo's
    own cache layout."""
    plain = kv_cache_bytes(FAST_MATRIX["lm_bf16"], 32)
    mqa = kv_cache_bytes(FAST_MATRIX["lm_gqa"], 32)
    heads = FAST_MATRIX["lm_bf16"].num_heads
    assert FAST_MATRIX["lm_gqa"].num_kv_heads == 1
    assert plain["bytes_per_token"] == heads * mqa["bytes_per_token"]
    assert plain["bytes_per_slot"] == heads * mqa["bytes_per_slot"]


def test_kv_bytes_hand_computed():
    """lm_bf16: 2 layers x (k + v) x 32 tokens x 2 kv-heads x 8 head-dim
    x 2 bytes = 4096 bytes/slot, 128 bytes/token."""
    kv = kv_cache_bytes(FAST_MATRIX["lm_bf16"], 32)
    assert kv["bytes_per_slot"] == 4096
    assert kv["bytes_per_token"] == 128


def test_kv_bytes_int8_and_window():
    """int8 stores 1-byte codes + 4-byte fp32 scales per (token, head):
    (2*8*1 + 2*4) = 24 B/token per buffer pair per layer -> 96 B/token
    total; a rolling window bounds the BUFFER, not the per-token cost."""
    int8 = kv_cache_bytes(FAST_MATRIX["lm_int8_cache"], 32)
    window = kv_cache_bytes(FAST_MATRIX["lm_window"], 32)
    assert int8["bytes_per_token"] == 96
    assert window["buffer_tokens"] == 8  # min(window, max_total)
    assert window["bytes_per_slot"] == 4096 // 4


# --------------------------------------------------------------------------
# collective inventory


def test_collective_inventory_attribution():
    from transformer_tpu.analysis.sharding import _mesh_1d
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _mesh_1d("seq", 2)
    if mesh is None:
        pytest.skip("needs >= 2 devices")

    def body(x):
        y = jax.lax.ppermute(x, "seq", [(0, 1), (1, 0)])
        return jax.lax.psum(y, "seq")

    fn = shard_map(
        body, mesh=mesh, in_specs=P("seq"), out_specs=P(None),
        check_vma=False,
    )
    closed = jax.make_jaxpr(fn)(_f32(4, 8))
    inv = collective_inventory(closed, {"seq": 2})
    assert set(inv) == {"ppermute[seq]", "psum[seq]"}
    assert inv["ppermute[seq]"]["count"] == 1
    # per-shard (2,8) f32 = 64B; one ring hop moves the whole shard.
    assert inv["ppermute[seq]"]["bytes"] == 64
    # ring all-reduce: 2*(n-1)/n of the buffer.
    assert inv["psum[seq]"]["bytes"] == 64


def test_scan_weighting_multiplies_collective_counts():
    from transformer_tpu.analysis.sharding import _mesh_1d
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _mesh_1d("seq", 2)
    if mesh is None:
        pytest.skip("needs >= 2 devices")

    def body(x):
        def hop(c, _):
            return jax.lax.ppermute(c, "seq", [(0, 1), (1, 0)]), ()

        out, _ = jax.lax.scan(hop, x, None, length=3)
        return out

    fn = shard_map(
        body, mesh=mesh, in_specs=P("seq"), out_specs=P("seq"),
        check_vma=False,
    )
    inv = collective_inventory(jax.make_jaxpr(fn)(_f32(4, 8)), {"seq": 2})
    assert inv["ppermute[seq]"]["count"] == 3


# --------------------------------------------------------------------------
# canned programs + the checked-in budget baseline (THE CI gate)


@pytest.fixture(scope="module")
def canned():
    """One canned-program sweep shared by the assertions below (the sweep
    is pure — tracing the same abstract programs again yields byte-equal
    reports, pinned by the CLI determinism the baseline gate relies on)."""
    return canned_cost_reports()


def test_canned_programs_cover_acceptance_surface(canned):
    reports, skipped = canned
    names = {r.name for r in reports} | set(skipped)
    for expected in (
        "serve.pool_step[lm_bf16]",
        "serve.pool_step[lm_int8_cache]",
        "serve.pool_step[lm_window]",
        "serve.pool_step[lm_gqa]",
        "serve.slot_prefill[lm_bf16,n=8]",
        "serve.pool_verify[lm_bf16,W=4]",
        "serve.slot_restore[lm_bf16,blocks=4]",
        "train.step[lm_bf16]",
        "parallel.ring_attention[seq=2]",
        "parallel.tp_ffn[model=2]",
    ):
        assert expected in names, f"missing canned program {expected}"
    by_name = {r.name: r for r in reports}
    for name, r in by_name.items():
        assert r.peak_bytes > 0, name
        if name.startswith(("serve.", "train.")):
            # the decode/train hot paths are single-chip: collective-free.
            assert r.collectives == {}, name
    assert by_name["serve.pool_step[lm_bf16]"].flops > 0
    # admission ingests 8 tokens per call vs 1 for a decode step: more
    # arithmetic per byte of weights touched.
    assert (
        by_name["serve.slot_prefill[lm_bf16,n=8]"].intensity
        > by_name["serve.pool_step[lm_bf16]"].intensity
    )
    if "parallel.ring_attention[seq=2]" in by_name:
        inv = by_name["parallel.ring_attention[seq=2]"].collectives
        assert any(k.startswith("ppermute[seq]") for k in inv), inv


def test_checked_in_baseline_matches_current_tree(canned):
    """The budget gate itself: the shipped costs_baseline.json must match
    the shipped code with zero regressions (peak bytes, KV bytes/slot,
    collective sets)."""
    reports, skipped = canned
    base = load_costs_baseline(default_costs_baseline_path())
    assert base, "costs_baseline.json is missing"
    kv = {v: kv_cache_bytes(FAST_MATRIX[v], 32)
          for v in ("lm_bf16", "lm_int8_cache", "lm_window", "lm_gqa")}
    regressions, _ = compare_to_baseline(reports, kv, base, skipped)
    assert regressions == [], "\n".join(regressions)


def test_pool_verify_donates_pool(canned):
    """The verify program's peak must NOT pay for two full pools: the pool
    is donated, so its buffers die as the updated pool is built. A lost
    donation annotation roughly doubles the cache term — assert the peak
    stays under params + 2x pool-cache bytes."""
    reports, _ = canned
    by_name = {r.name: r for r in reports}
    step = by_name["serve.pool_step[lm_bf16]"]
    kv = kv_cache_bytes(FAST_MATRIX["lm_bf16"], 32)
    pool_kv = 2 * kv["bytes_per_slot"]
    assert step.extras["kv_bytes_per_slot"] == kv["bytes_per_slot"]
    assert step.peak_bytes < step.arg_bytes + 2 * pool_kv


@pytest.mark.parametrize("donated", [True, False], ids=["donated", "control"])
def test_slot_prefill_paged_donates_pool(donated):
    """The paged prefill's peak holds ONE pool: donated, as the program is
    (PR 38), each layer's pool dies as the slot's rows are scattered into
    it, so the peak stays under the arguments plus one pool; the same
    program undonated ends holding the old pool and the new one and does
    not. Priced at 33 blocks of 8, where the pool and not the forward's
    temporaries makes the peak, as on a deployed chip (at the canned 5
    blocks the two read the same)."""
    from transformer_tpu.analysis.costs import kv_pool_bytes
    from transformer_tpu.models.transformer import transformer_init
    from transformer_tpu.serve.scheduler import (
        _slot_prefill_paged,
        abstract_paged_pool,
    )

    cfg = FAST_MATRIX["lm_bf16"]
    blocks, block, total = 33, 8, 32
    params = jax.eval_shape(
        lambda k: transformer_init(k, cfg), jax.random.PRNGKey(0)
    )
    pool, table, _ = abstract_paged_pool(cfg, 2, total, blocks, block)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
    raw = _slot_prefill_paged.__wrapped__
    r = program_costs(
        "prefill",
        lambda p, c, tb, s, pr, st: raw(p, c, tb, s, pr, st, cfg, 0, block, total),
        params, pool, table, i32(), i32(1, 8), i32(),
        donate_argnums=(1,) if donated else (),
    )
    one_pool = kv_pool_bytes(cfg, total, 2, blocks, block)["pool_bytes"]
    assert (r.peak_bytes < r.arg_bytes + one_pool) == donated


# --------------------------------------------------------------------------
# baseline workflow


def _tiny_report(name="prog", peak=1000, flops=10, moved=100, coll=None):
    return CostReport(
        name=name, peak_bytes=peak, flops=flops, bytes_moved=moved,
        collectives=coll or {}, arg_bytes=0, out_bytes=0,
    )


def test_baseline_roundtrip_and_regressions(tmp_path):
    path = str(tmp_path / "budget.json")
    kv = {"lm_bf16": {"bytes_per_slot": 4096, "bytes_per_token": 128,
                      "buffer_tokens": 32, "max_total": 32, "layers": 2}}
    write_costs_baseline([_tiny_report()], kv, path)
    base = load_costs_baseline(path)

    # clean: identical numbers
    regs, _ = compare_to_baseline([_tiny_report()], kv, base)
    assert regs == []

    # +1 buffer: peak regression flagged
    regs, _ = compare_to_baseline([_tiny_report(peak=1000 + 4096)], kv, base)
    assert any("peak_bytes" in r for r in regs)

    # stray collective: flagged
    regs, _ = compare_to_baseline(
        [_tiny_report(coll={"all_gather[fsdp]": {"count": 1, "bytes": 64}})],
        kv, base,
    )
    assert any("stray collective" in r for r in regs)

    # KV growth: flagged
    kv2 = {"lm_bf16": dict(kv["lm_bf16"], bytes_per_slot=8192)}
    regs, _ = compare_to_baseline([_tiny_report()], kv2, base)
    assert any("kv_cache[lm_bf16]" in r for r in regs)

    # improvement: note, not regression
    regs, notes = compare_to_baseline([_tiny_report(peak=500)], kv, base)
    assert regs == [] and any("improved" in n for n in notes)

    # lost coverage: flagged; skipped programs tolerated
    regs, _ = compare_to_baseline([], kv, base)
    assert any("no longer produced" in r for r in regs)
    regs, notes = compare_to_baseline([], kv, base, skipped=["prog"])
    assert regs == [] and any("skipped" in n for n in notes)

    # unbaselined program: flagged
    regs, _ = compare_to_baseline(
        [_tiny_report(), _tiny_report(name="new")], kv, base
    )
    assert any("new" in r and "baseline" in r for r in regs)


# --------------------------------------------------------------------------
# injected-regression canaries: prove the gate detects what it pins


@pytest.mark.slow
def test_canary_one_extra_buffer_is_detected():
    """A 'refactor' of the pool step that keeps one extra live copy of the
    logits (the classic accidental-residency bug) must fail the shipped
    baseline's peak budget."""
    from transformer_tpu.serve import scheduler as sched
    from transformer_tpu.serve.scheduler import abstract_pool_caches
    from transformer_tpu.analysis.costs import _abstract_model

    cfg = FAST_MATRIX["lm_bf16"]
    params = _abstract_model(cfg)
    pool = abstract_pool_caches(cfg, 2, 32)
    toks = jax.ShapeDtypeStruct((2,), np.int32)
    raw = sched._pool_step.__wrapped__

    def leaky(p, c, t):
        logits, caches = raw(p, c, t, cfg)
        # the regression: a second copy of the pool pinned alongside the
        # result (the "stash the old cache for a rollback I never free"
        # shape of bug)
        stash = jax.tree.map(lambda x: x + x.dtype.type(0), caches)
        return logits, caches, stash

    r = program_costs(
        "serve.pool_step[lm_bf16]", leaky, params, pool, toks,
        donate_argnums=(1,),
    )
    base = load_costs_baseline(default_costs_baseline_path())
    regs, _ = compare_to_baseline([r], {}, base)
    assert any(
        "serve.pool_step[lm_bf16]" in x and "peak_bytes" in x for x in regs
    ), regs


@pytest.mark.slow
def test_canary_stray_all_gather_is_detected():
    """A stray all_gather smuggled into the pool step must fail the shipped
    baseline's (empty) collective set for that program."""
    from transformer_tpu.analysis.sharding import _mesh_1d
    from jax import shard_map
    from transformer_tpu.serve import scheduler as sched
    from transformer_tpu.serve.scheduler import abstract_pool_caches
    from transformer_tpu.analysis.costs import _abstract_model
    from jax.sharding import PartitionSpec as P

    mesh = _mesh_1d("data", 2)
    if mesh is None:
        pytest.skip("needs >= 2 devices")
    cfg = FAST_MATRIX["lm_bf16"]
    params = _abstract_model(cfg)
    pool = abstract_pool_caches(cfg, 2, 32)
    toks = jax.ShapeDtypeStruct((2,), np.int32)
    raw = sched._pool_step.__wrapped__

    def gathered(p, c, t):
        logits, caches = raw(p, c, t, cfg)
        spread = shard_map(
            lambda x: jax.lax.all_gather(x, "data"),
            mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False,
        )(logits)
        return spread, caches

    r = program_costs(
        "serve.pool_step[lm_bf16]", gathered, params, pool, toks,
        donate_argnums=(1,), axis_sizes={"data": 2},
    )
    assert r.collectives, "the injected all_gather must be inventoried"
    base = load_costs_baseline(default_costs_baseline_path())
    regs, _ = compare_to_baseline([r], {}, base)
    assert any("stray collective" in x and "all_gather" in x for x in regs), regs


# --------------------------------------------------------------------------
# TPA201-205: corpus twins + package cleanliness + CLI


def test_shard_bad_corpus_fires_every_rule():
    report = run_sharding(paths=[_SHARD_BAD], baseline_path=None)
    assert sorted({f.code for f in report.findings}) == [
        "TPA201", "TPA202", "TPA203", "TPA204", "TPA205",
    ]


def test_shard_good_corpus_clean():
    report = run_sharding(paths=[_SHARD_GOOD], baseline_path=None)
    assert report.findings == [], "\n".join(str(f) for f in report.findings)


def test_shard_package_clean():
    report = run_sharding()  # package + checked-in (empty) baseline
    assert report.findings == [], "\n".join(str(f) for f in report.findings)


def test_shard_suppression_and_baseline(tmp_path):
    import textwrap

    src = textwrap.dedent("""\
        from jax.sharding import Mesh, PartitionSpec as P
        MESH = Mesh(DEVICES, ("data",))
        SPEC = P("bogus")  # tpa: disable=TPA202 — exercised by the test
        OTHER = P("bogus2")
    """)
    f = tmp_path / "m.py"
    f.write_text(src)
    report = run_sharding(paths=[str(f)], baseline_path=None)
    assert [x.code for x in report.findings] == ["TPA202"]  # only OTHER
    # grandfather the remaining finding, then the run is clean
    from transformer_tpu.analysis.baselines import write_baseline

    bl = str(tmp_path / "bl.json")
    write_baseline(report, bl)
    again = run_sharding(paths=[str(f)], baseline_path=bl)
    assert again.findings == [] and len(again.baselined) == 1


def test_cli_sharding_exit_codes(capsys):
    assert analysis_main(["sharding"]) == 0
    assert analysis_main(["sharding", "--paths", _SHARD_BAD]) == 1
    assert analysis_main(["sharding", "--paths", _SHARD_GOOD]) == 0
    capsys.readouterr()


def test_cli_costs_exit_codes_and_json(tmp_path, capsys, canned):
    # clean run against the shipped baseline: exit 0, diffable JSON
    assert analysis_main(["costs", "--format", "json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["ok"] is True
    assert {p["name"] for p in payload["programs"]} >= {
        "serve.pool_step[lm_bf16]", "train.step[lm_bf16]",
    }
    assert payload["kv_cache"]["lm_bf16"]["bytes_per_slot"] == 4096
    # a baseline with an impossible budget must fail the gate with exit 1
    reports, _ = canned
    kv = {v: kv_cache_bytes(FAST_MATRIX[v], 32)
          for v in ("lm_bf16", "lm_int8_cache", "lm_window", "lm_gqa")}
    tight = str(tmp_path / "tight.json")
    write_costs_baseline(reports, kv, tight)
    data = json.load(open(tight))
    first = next(iter(data["programs"]))
    data["programs"][first]["peak_bytes"] -= 1
    json.dump(data, open(tight, "w"))
    assert analysis_main(["costs", "--baseline", tight]) == 1
    capsys.readouterr()


def test_cli_all_aggregates(capsys):
    # fast subset: the lint families (full `all` incl. costs/contracts/
    # retrace/schedules is the pre-merge gate, exercised under -m slow)
    assert analysis_main(["all", "--only", "rules,sharding"]) == 0
    capsys.readouterr()
    assert analysis_main(["all", "--only", "nosuch"]) == 2
    capsys.readouterr()


@pytest.mark.slow
def test_cli_all_full_gate(capsys):
    assert analysis_main(["all"]) == 0
    capsys.readouterr()


# --------------------------------------------------------------------------
# obs summarize cross-check (prediction vs measured memory)


def test_summarize_memory_vs_prediction():
    from transformer_tpu.obs.__main__ import render_text, summarize_events

    events = [
        {"kind": "train.predicted", "ts": 1.0, "program": "train_step",
         "peak_bytes": 1000, "flops": 5000, "bytes_moved": 2000,
         "tokens_per_step": 16},
        {"kind": "train.memory", "ts": 2.0,
         "devices": {"0": {"bytes_in_use": 900, "peak_bytes_in_use": 1500}}},
    ]
    rep = summarize_events(events)
    pred = rep["train"]["predicted"]
    assert pred["measured_peak_bytes"] == 1500
    assert pred["measured_over_predicted"] == 1.5
    assert "measured/predicted 1.5x" in render_text(rep)
    # tolerant when either side is absent
    only_pred = summarize_events(events[:1])["train"]["predicted"]
    assert "measured_peak_bytes" not in only_pred
    only_mem = summarize_events(events[1:])["train"]
    assert "predicted" not in only_mem and only_mem["memory"]
    # and when the memory payload is malformed
    rep = summarize_events(
        [events[0], {"kind": "train.memory", "ts": 3.0, "devices": "garbled"}]
    )
    assert "measured_peak_bytes" not in rep["train"]["predicted"]


def test_trainer_emits_prediction(tmp_path):
    """A telemetry-enabled fit() leaves one train.predicted event whose
    peak matches the cost model run directly (same config, same trace)."""
    from transformer_tpu.analysis.configs import TINY_TRAIN
    from transformer_tpu.obs import Telemetry
    from transformer_tpu.obs.events import EventLog, read_events
    from transformer_tpu.train.state import create_train_state
    from transformer_tpu.train.trainer import Trainer

    cfg = FAST_MATRIX["lm_bf16"]
    train_cfg = TINY_TRAIN
    state = create_train_state(jax.random.PRNGKey(0), cfg, train_cfg)
    log = tmp_path / "events.jsonl"
    telemetry = Telemetry(events=EventLog(str(log)), interval=0.0)
    trainer = Trainer(cfg, train_cfg, state, telemetry=telemetry,
                     log_fn=lambda *_: None)
    B, L = train_cfg.batch_size, train_cfg.sequence_length
    vocab = cfg.input_vocab_size

    class DS:
        def __len__(self):
            return 2

        def batches(self, epoch):
            r = np.random.default_rng(epoch)
            for _ in range(2):
                ids = r.integers(1, vocab, size=(B, L)).astype(np.int32)
                yield ids, ids

    trainer.fit(DS())
    telemetry.close()
    events = [e for e in read_events(str(log)) if e["kind"] == "train.predicted"]
    assert len(events) == 1
    assert events[0]["program"] == "train_step"
    assert events[0]["peak_bytes"] > 0 and events[0]["flops"] > 0
    assert events[0]["tokens_per_step"] == B * L
    # the exported gauge mirrors the event (one prediction, two surfaces)
    snap = telemetry.registry.snapshot()
    assert snap["train_predicted_peak_bytes"] == events[0]["peak_bytes"]
