"""Serving-path microbenchmark: prefill tokens/sec vs incremental decode.

CPU-runnable: bench.py measures the training hot path on the chip; this
measures the SHAPE of the serving hot path, which survives the platform (the
prompt phase is matmul-rich and batched, the decode phase is one
bandwidth-bound step per token, per "Fast Transformer Decoding" (Shazeer,
arXiv:1911.02150)). Its times on a CPU are not device metrics.

    JAX_PLATFORMS=cpu python benchmarks/decode_bench.py

Prints ONE JSON line:

    {"prefill_tokens_per_sec": ..., "decode_tokens_per_sec": ...,
     "decode_steps_per_sec": ..., "prefill_vs_decode": ...,
     "prefill_forward_calls": ...}

``prefill_vs_decode`` is the headline: how many times faster the single-pass
chunked prefill ingests a prompt token than the token-by-token decode loop
does. ``prefill_forward_calls`` pins the structural claim — a 64-token
prompt compiles to ceil(prompt_len / chunk) decoder forwards, not 64
sequential steps. ``--prefix_reuse`` adds the cross-request dimension: a
repeated-system-prompt workload through the continuous scheduler with the
prefix KV cache on vs off, reporting the prompt-token hit rate and the
prefill forwards the trie restore saved (greedy answers asserted identical).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class _IdTok:
    """Tokens ARE ids ("3 17 5" -> [3, 17, 5]): the scheduler needs only
    encode/decode/bos/eos, and a real subword vocab would just blur the
    token accounting the scheduler sweeps report."""

    bos_id, eos_id = 1, 2

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, toks):
        return " ".join(str(t) for t in toks)


def _system_prompt_requests(rng, vocab: int, prompt_len: int, n: int):
    """The repeated-system-prompt workload both scheduler sweeps serve:
    every request carries one shared system prompt plus a 4-id tail."""
    system = rng.integers(3, vocab - 2, prompt_len)
    return [
        {
            "prompt": " ".join(
                map(str, [*system, *rng.integers(3, vocab - 2, 4)])
            ),
            "max_new": 4,
        }
        for _ in range(n)
    ]


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt_len", type=int, default=64)
    p.add_argument("--decode_steps", type=int, default=32)
    p.add_argument("--chunk", type=int, default=0,
                   help="prefill chunk size (0 = whole prompt in one forward)")
    p.add_argument("--speculate_k", type=str, default="",
                   help="comma-separated speculative lookahead sweep (e.g. "
                        "'2,4'): per k, decode batch-1 speculatively with "
                        "the n-gram drafter and report tokens/s, "
                        "tokens-per-forward, and draft acceptance rate")
    p.add_argument("--prefix_reuse", action="store_true",
                   help="run a repeated-system-prompt workload through the "
                        "continuous scheduler with the cross-request prefix "
                        "cache on vs off, reporting prompt-token hit rate "
                        "and prefill forwards saved")
    p.add_argument("--prefix_requests", type=int, default=16,
                   help="requests in the --prefix_reuse workload (each = "
                        "shared system prompt + small unique tail)")
    p.add_argument("--prefix_block", type=int, default=16,
                   help="prefix-cache block granularity for --prefix_reuse")
    p.add_argument("--kv_layout", type=str, default="",
                   help="comma-separated KV layout sweep ('dense,paged'): "
                        "run the repeated-system-prompt workload through "
                        "the continuous scheduler per layout and report "
                        "tokens/s, predicted peak bytes, KV bytes/slot, "
                        "and max concurrent slots before OOM-by-budget "
                        "(answers asserted byte-identical across layouts)")
    p.add_argument("--decode_kernel", type=str, default="",
                   help="comma-separated decode-kernel sweep "
                        "('xla,paged_flash'): per KV-cache variant "
                        "(bf16/int8/gqa), run the repeated-system-prompt "
                        "workload through the paged continuous scheduler "
                        "with each kernel and report tokens/s plus the cost "
                        "model's predicted_bytes_moved and the kernel "
                        "verifier's predicted_vmem_bytes for the batched "
                        "pool step (answers asserted byte-identical across "
                        "kernels)")
    p.add_argument("--tpu", action="store_true",
                   help="demand real-Pallas (interpret=False) decode-kernel "
                        "rows: on a TPU backend the sweep rows compile the "
                        "kernels for the MXU; anywhere else this is an "
                        "error")
    p.add_argument("--kv_pool_mb", type=float, default=0.0,
                   help="device-memory budget (MiB) the --kv_layout "
                        "max-slots column is computed against (0 = the "
                        "dense pool's own footprint, so the column reads "
                        "as 'how many more slots fit in the same memory')")
    p.add_argument("--rows_out", type=str, default="",
                   help="append bench_rows.jsonl-compatible rows for the "
                        "--speculate_k / --prefix_reuse sweeps to this file "
                        "('' = print them to stderr; stdout stays one "
                        "summary JSON line)")
    p.add_argument("--metrics_jsonl", type=str, default="",
                   help="append obs telemetry events for the scheduler "
                        "sweeps to this JSONL (each sweep row's final "
                        "metrics.snapshot carries its per-program perf_* "
                        "profiler metrics) — the episode `python -m "
                        "transformer_tpu.obs roofline` replays ('' = no "
                        "event log; the profiler still runs and the "
                        "measured_* columns still populate)")
    p.add_argument("--mesh", type=str, default="",
                   help="comma-separated serving mesh sizes (e.g. '1,2,4'): "
                        "run the repeated-system-prompt workload through a "
                        "--mesh N ContinuousScheduler per size, dense AND "
                        "paged, reporting per-mesh tokens/s + the predicted "
                        "cross-shard collective bytes per decode step "
                        "(answers asserted byte-identical to the unsharded "
                        "scheduler); grows a virtual CPU device platform "
                        "when the host has too few devices")
    p.add_argument("--reps", type=int, default=5,
                   help="timed repetitions (best-of is reported)")
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--d_model", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--dff", type=int, default=512)
    p.add_argument("--vocab", type=int, default=8192)
    args = p.parse_args()

    # The --mesh sweep needs >= max(mesh) devices, and XLA only honours the
    # virtual-device flag if it is in the environment BEFORE jax is imported
    # — so grow XLA_FLAGS here, between argparse and the import below.
    mesh_sizes = [int(x) for x in args.mesh.split(",") if x.strip()]
    if any(m < 1 for m in mesh_sizes):
        p.error("--mesh sizes must be >= 1")
    if mesh_sizes:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={max(mesh_sizes)}"
            ).strip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from transformer_tpu.config import ModelConfig
    from transformer_tpu.models import transformer_init
    from transformer_tpu.models.decoder import init_decoder_caches
    from transformer_tpu.models.transformer import (
        transformer_decode_step,
        transformer_prefill,
    )

    total = args.prompt_len + args.decode_steps + 1
    cfg = ModelConfig(
        num_layers=args.layers, d_model=args.d_model, num_heads=args.heads,
        dff=args.dff, input_vocab_size=args.vocab, target_vocab_size=args.vocab,
        max_position=total, decoder_only=True, tie_output=True,
        dtype="float32", dropout_rate=0.0,
    )
    dev = jax.devices()[0]
    print(f"decode bench on {dev.platform}:{dev.device_kind}", file=sys.stderr)
    if args.tpu and dev.platform != "tpu":
        raise SystemExit(
            f"--tpu demands a TPU backend; JAX found "
            f"{dev.platform}:{dev.device_kind}"
        )
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(1, args.vocab - 2, (args.batch, args.prompt_len)),
        jnp.int32,
    )

    calls = [0]
    prefill = jax.jit(
        lambda params, prompt, caches: transformer_prefill(
            params, prompt, None, None, caches, 0, cfg, chunk=args.chunk
        ),
        static_argnames=(),
    )

    # Count the decoder forwards the prefill TRACES to (the structural
    # O(prompt_len / chunk) claim) by intercepting decoder_apply once.
    from transformer_tpu.models import decoder as decoder_mod

    real_apply = decoder_mod.decoder_apply

    def counting_apply(*a, **kw):
        calls[0] += 1
        return real_apply(*a, **kw)

    decoder_mod.decoder_apply = counting_apply
    try:
        caches0 = init_decoder_caches(cfg, args.batch, total)
        logits, caches = prefill(params, prompt, caches0)
        jax.block_until_ready(logits)
    finally:
        decoder_mod.decoder_apply = real_apply
    prefill_calls = calls[0]

    best = float("inf")
    for _ in range(args.reps):
        caches0 = init_decoder_caches(cfg, args.batch, total)
        t0 = time.perf_counter()
        logits, caches = prefill(params, prompt, caches0)
        jax.block_until_ready(logits)
        best = min(best, time.perf_counter() - t0)
    prefill_tok_s = args.batch * args.prompt_len / best

    # Incremental decode: one bandwidth-bound step per token from the
    # prefilled cache (greedy feedback keeps the loop honest — each step
    # consumes the previous step's output, like serving does).
    step = jax.jit(
        lambda params, tok, caches, pos: transformer_decode_step(
            params, tok, None, None, caches, pos, cfg
        )
    )
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    _, warm = step(params, tok, caches, jnp.int32(args.prompt_len))
    jax.block_until_ready(warm[0]["k"])

    best_dec = float("inf")
    for _ in range(args.reps):
        t, c = tok, caches
        t0 = time.perf_counter()
        for i in range(args.decode_steps):
            logits_i, c = step(params, t, c, jnp.int32(args.prompt_len + i))
            t = jnp.argmax(logits_i, axis=-1).astype(jnp.int32)[:, None]
        jax.block_until_ready(t)
        best_dec = min(best_dec, time.perf_counter() - t0)
    decode_steps_s = args.decode_steps / best_dec
    decode_tok_s = args.batch * args.decode_steps / best_dec

    # Cost-model predictions (analysis/costs.py, abstract trace — no device
    # execution): the decode step's peak live-buffer bytes next to its
    # measured tokens/s, so bench_rows.jsonl ties prediction to measurement
    # and a memory regression shows up in the same file as a speed one.
    from transformer_tpu.analysis.costs import program_costs

    def _costs(fn, *abstract_args, donate_argnums=()):
        return program_costs(
            "bench", fn, *abstract_args, donate_argnums=donate_argnums
        )

    def _predict(fn, *abstract_args, donate_argnums=()):
        return _costs(fn, *abstract_args, donate_argnums=donate_argnums).peak_bytes

    # Measured side of the roofline (obs/profile.py): each scheduler sweep
    # row runs with a FRESH telemetry bundle + profiler (its own registry),
    # so measured_step_p50_ms is that row's own number rather than an
    # aggregate across variants; every bundle appends its final
    # metrics.snapshot to the same --metrics_jsonl, which is exactly the
    # episode `python -m transformer_tpu.obs roofline` joins against the
    # cost model.
    from transformer_tpu.obs import EventLog, Telemetry
    from transformer_tpu.obs.profile import roofline_ratio

    def _sweep_telemetry():
        events = EventLog(args.metrics_jsonl) if args.metrics_jsonl else None
        tel = Telemetry(events=events, interval=1e9)
        tel.arm_profiler()
        return tel

    def _measured_step(tel, program):
        """Pull ``program``'s measured row from the bundle's profiler, then
        close the bundle (forcing the final metrics.snapshot flush)."""
        row = tel.profiler.summary().get(program) or {}
        tel.close()
        return row

    decode_peak = _predict(
        lambda p, t, c, pos: transformer_decode_step(
            p, t, None, None, c, pos, cfg
        ),
        params, tok, caches, jnp.int32(0),
    )

    # ---- speculative decoding sweep (batch-1, n-gram drafter) -------------
    # Headline: tokens emitted per target-model VERIFY forward — the number
    # speculation exists to push past 1.0 (incremental decode's ceiling).
    # The prompt tiles a short motif so prompt-lookup drafting has honest
    # traction (the repetitive-text regime it is built for).
    speculative = []
    ks = [int(x) for x in args.speculate_k.split(",") if x.strip()]
    if ks:
        from transformer_tpu.serve.speculative import (
            NgramDrafter,
            speculative_generate,
        )

        from transformer_tpu.models.transformer import transformer_verify

        motif = rng.integers(1, args.vocab - 2, 8)
        spec_prompt = [int(motif[i % 8]) for i in range(args.prompt_len)]
        for k in ks:
            if k < 1:
                continue
            verify_peak = _predict(
                lambda p, t, c, pos: transformer_verify(p, t, c, pos, cfg),
                params,
                jnp.zeros((1, k + 1), jnp.int32),
                init_decoder_caches(cfg, 1, total),
                jnp.int32(0),
            )
            stats = {}
            toks: list = []
            best_spec = float("inf")
            for _ in range(args.reps):
                t0 = time.perf_counter()
                toks, stats = speculative_generate(
                    params, cfg, spec_prompt, args.decode_steps, eos_id=-1,
                    speculate_k=k, drafter=NgramDrafter(),
                    prefill_chunk=args.chunk,
                )
                best_spec = min(best_spec, time.perf_counter() - t0)
            tpf = len(toks) / max(stats["verify_forwards"], 1)
            acc = stats["accepted"] / max(stats["drafted"], 1)
            speculative.append({
                "k": k,
                "tokens_per_sec": round(len(toks) / best_spec, 1),
                "tokens_per_forward": round(tpf, 3),
                "acceptance_rate": round(acc, 4),
                "verify_forwards": stats["verify_forwards"],
                "new_tokens": len(toks),
                "predicted_peak_bytes": verify_peak,
            })

    # ---- cross-request prefix reuse (continuous scheduler) ----------------
    # Headline: the fraction of prompt tokens served from stored KV blocks
    # instead of a prefill forward, on the workload the prefix cache exists
    # for — every request carrying the same system prompt plus a small
    # unique tail (docs/SERVING.md "Cross-request prefix KV cache").
    prefix = None
    if args.prefix_reuse:
        from transformer_tpu.serve import ContinuousScheduler, PrefixCache
        from transformer_tpu.serve.scheduler import (
            _pool_step,
            abstract_pool_caches,
        )

        pool_peak = _predict(
            lambda p, c, t: _pool_step.__wrapped__(p, c, t, cfg),
            params,
            abstract_pool_caches(cfg, 2, total),
            jnp.zeros((2,), jnp.int32),
            donate_argnums=(1,),  # mirrors _pool_step's jit (and the budget)
        )

        tok = _IdTok()
        reqs = _system_prompt_requests(
            rng, args.vocab, args.prompt_len, args.prefix_requests
        )

        results = {}
        for label, cache in (
            ("off", None),
            ("on", PrefixCache(
                cfg, block_tokens=args.prefix_block, budget_mb=64)),
        ):
            sched = ContinuousScheduler(
                params, cfg, tok, num_slots=2,
                prefill_chunk=args.chunk, prefix_cache=cache,
            )
            t0 = time.perf_counter()
            out = sched.run([dict(r) for r in reqs])
            wall = time.perf_counter() - t0
            assert all("continuation" in r for r in out), out
            results[label] = {
                "answers": [r["continuation"] for r in out],
                "wall_s": wall,
                **{k: sched.stats[k] for k in (
                    "prompt_tokens", "prefix_hit_tokens", "prefill_forwards",
                )},
            }
        assert results["on"]["answers"] == results["off"]["answers"], (
            "prefix cache changed greedy answers"
        )
        on, off = results["on"], results["off"]
        prefix = {
            "requests": args.prefix_requests,
            "system_prompt_tokens": args.prompt_len,
            "block_tokens": args.prefix_block,
            "prompt_tokens": on["prompt_tokens"],
            "prefix_hit_tokens": on["prefix_hit_tokens"],
            "hit_rate": round(
                on["prefix_hit_tokens"] / on["prompt_tokens"], 4
            ),
            "prefill_forwards": on["prefill_forwards"],
            "prefill_forwards_saved": (
                off["prefill_forwards"] - on["prefill_forwards"]
            ),
            "wall_s_on": round(on["wall_s"], 3),
            "wall_s_off": round(off["wall_s"], 3),
            "predicted_peak_bytes": pool_peak,
        }

    # ---- paged vs dense KV layout (continuous scheduler) ------------------
    # Headline: KV bytes/slot and max concurrent slots under one device
    # budget — the paged pool bounds resident KV by USED tokens, so the
    # same memory admits more slots; answers are byte-identical either
    # way (asserted) and tokens/s rides along for the CPU shape check.
    kv_layouts = [x.strip() for x in args.kv_layout.split(",") if x.strip()]
    layout_rows = []
    if kv_layouts:
        from transformer_tpu.analysis.costs import kv_cache_bytes, kv_pool_bytes
        from transformer_tpu.serve import ContinuousScheduler
        from transformer_tpu.serve.scheduler import (
            _pool_step,
            _pool_step_paged,
            abstract_paged_pool,
            abstract_pool_caches,
        )

        ltok = _IdTok()
        lreqs = _system_prompt_requests(
            np.random.default_rng(1), args.vocab, args.prompt_len,
            args.prefix_requests,
        )
        slots = 2
        block = args.prefix_block
        used_tokens = args.prompt_len + 4 + 4 + 1  # prompt + tail + gen + bos
        used_blocks = -(-used_tokens // block)
        # Serving provisions max_total for the WORST-case request (4x this
        # workload's typical length here); dense reserves that many rows
        # per slot up front, paged pays only for the blocks a request
        # actually touches — exactly the waste the cost model prices.
        serve_total = 4 * total
        slot_blocks = -(-serve_total // block)
        dense_kv = kv_cache_bytes(cfg, serve_total)
        budget_bytes = (
            args.kv_pool_mb * (1 << 20)
            if args.kv_pool_mb
            else slots * dense_kv["bytes_per_slot"]
        )
        answers = {}
        for layout in kv_layouts:
            ltel = _sweep_telemetry()
            sched = ContinuousScheduler(
                params, cfg, ltok, num_slots=slots,
                prefill_chunk=args.chunk, kv_layout=layout, kv_block=block,
                max_total=serve_total, telemetry=ltel,
            )
            t0 = time.perf_counter()
            out = sched.run([dict(r) for r in lreqs])
            wall = time.perf_counter() - t0
            assert all("continuation" in r for r in out), out
            answers[layout] = [r["continuation"] for r in out]
            new_tokens = sum(
                len(ltok.encode(r["continuation"])) for r in out
            )
            if layout == "paged":
                pool_blocks = 1 + slots * slot_blocks
                kv = kv_pool_bytes(cfg, serve_total, slots, pool_blocks, block)
                raw = _costs(
                    lambda p, c, tb, ix, t: _pool_step_paged.__wrapped__(
                        p, c, tb, ix, t, cfg, block, serve_total
                    ),
                    params,
                    *abstract_paged_pool(
                        cfg, slots, serve_total, pool_blocks, block
                    ),
                    jnp.zeros((slots,), jnp.int32),
                    donate_argnums=(1,),
                )
                # Paged residency is per USED block: one slot costs
                # used_blocks x block-bytes (+ its table row) — the
                # budget admits proportionally more concurrent slots.
                block_bytes = kv["pool_bytes"] / max(1, kv["pool_blocks"])
                max_slots = int(budget_bytes // (used_blocks * block_bytes))
                bytes_per_slot = int(used_blocks * block_bytes)
            else:
                raw = _costs(
                    lambda p, c, t: _pool_step.__wrapped__(p, c, t, cfg),
                    params,
                    abstract_pool_caches(cfg, slots, serve_total),
                    jnp.zeros((slots,), jnp.int32),
                    donate_argnums=(1,),
                )
                max_slots = int(budget_bytes // dense_kv["bytes_per_slot"])
                bytes_per_slot = dense_kv["bytes_per_slot"]
            step_prog = (
                "serve.pool_step_paged" if layout == "paged"
                else "serve.pool_step"
            )
            measured = _measured_step(ltel, step_prog)
            step_p50_ms = measured.get("p50_ms")
            # None on a device with no entry in the peak table (the CPU).
            step_ratio = roofline_ratio(
                raw.bytes_moved, measured.get("p50_s") or 0.0, dev.device_kind
            )
            assert step_p50_ms, (
                f"kv_layout={layout}: no measured {step_prog} dispatches — "
                "the profiler should have clocked every pool step"
            )
            layout_rows.append({
                "kv_layout": layout,
                "tokens_per_sec": round(new_tokens / wall, 1) if wall else None,
                "wall_s": round(wall, 3),
                "predicted_peak_bytes": raw.peak_bytes,
                "predicted_bytes_moved": raw.bytes_moved,
                "measured_step_p50_ms": step_p50_ms,
                "roofline_ratio": step_ratio,
                "kv_bytes_per_slot": bytes_per_slot,
                "max_slots_in_budget": max_slots,
                "budget_bytes": int(budget_bytes),
                "used_tokens_per_slot": used_tokens,
            })
        first = kv_layouts[0]
        for layout in kv_layouts[1:]:
            assert answers[layout] == answers[first], (
                f"kv_layout={layout} changed answers vs {first}"
            )

    # ---- decode kernel sweep (paged continuous scheduler) -----------------
    # Headline: tokens/s per kernel next to the cost model's
    # predicted_bytes_moved for the batched pool step — the fused
    # paged_flash path exists to cut the gathered-view HBM pass, so the
    # prediction that justifies it lands in the same row as the
    # measurement. On CPU the kernels run in Pallas interpret mode (shape
    # check, not a speed claim); --tpu demands the interpret=False rows.
    kernels = [x.strip() for x in args.decode_kernel.split(",") if x.strip()]
    if args.tpu and not kernels:
        kernels = ["xla", "paged_flash"]
    kernel_rows = []
    if kernels:
        from transformer_tpu.serve import ContinuousScheduler
        from transformer_tpu.serve.scheduler import (
            _pool_step_paged,
            _pool_step_paged_flash,
            abstract_paged_pool,
        )

        on_tpu = dev.platform == "tpu"
        cache_variants = {
            "bf16": {},
            "int8": {"kv_cache_int8": True},
            "gqa": {"num_kv_heads": max(1, args.heads // 2)},
        }
        kslots = 2
        kblock = args.prefix_block
        kreqs = _system_prompt_requests(
            np.random.default_rng(2), args.vocab, args.prompt_len,
            args.prefix_requests,
        )
        ktok = _IdTok()
        # Workload rows per slot: bos + system prompt + 4-id tail + 4
        # generated; pad so tiny smoke configs never trip the prompt-length
        # validator.
        ktotal = max(total, args.prompt_len + 16)
        slot_blocks = -(-ktotal // kblock)
        pool_blocks = 1 + kslots * slot_blocks
        for vname, overrides in cache_variants.items():
            vcfg = ModelConfig(
                num_layers=args.layers, d_model=args.d_model,
                num_heads=args.heads, dff=args.dff,
                input_vocab_size=args.vocab, target_vocab_size=args.vocab,
                max_position=ktotal, decoder_only=True, tie_output=True,
                dtype="bfloat16", dropout_rate=0.0, **overrides,
            )
            vparams = transformer_init(jax.random.PRNGKey(0), vcfg)
            vanswers = {}
            for kernel in kernels:
                ktel = _sweep_telemetry()
                sched = ContinuousScheduler(
                    vparams, vcfg, ktok, num_slots=kslots,
                    prefill_chunk=args.chunk, kv_layout="paged",
                    kv_block=kblock, max_total=ktotal, decode_kernel=kernel,
                    telemetry=ktel,
                )
                t0 = time.perf_counter()
                out = sched.run([dict(r) for r in kreqs])
                wall = time.perf_counter() - t0
                assert all("continuation" in r for r in out), out
                vanswers[kernel] = [r["continuation"] for r in out]
                new_tokens = sum(
                    len(ktok.encode(r["continuation"])) for r in out
                )
                kernel_vmem = {}
                if kernel == "paged_flash":
                    step_fn = lambda p, c, tb, ix, t, vcfg=vcfg: (  # noqa: E731
                        _pool_step_paged_flash.__wrapped__(
                            p, c, tb, ix, t, vcfg, kblock, False
                        )
                    )
                    step_args = (
                        vparams,
                        *abstract_paged_pool(
                            vcfg, kslots, ktotal, pool_blocks, kblock
                        ),
                        jnp.zeros((kslots,), jnp.int32),
                    )
                    raw = _costs(step_fn, *step_args, donate_argnums=(1,))
                    # The verifier's per-grid-step VMEM model for each
                    # Pallas kernel in the step; kernels run sequentially,
                    # so the program's kernel-VMEM high-water mark is the
                    # max, not the sum.
                    from transformer_tpu.analysis.kernels import (
                        program_kernel_vmem,
                    )

                    kernel_vmem = program_kernel_vmem(step_fn, *step_args)
                else:
                    raw = _costs(
                        lambda p, c, tb, ix, t, vcfg=vcfg: (
                            _pool_step_paged.__wrapped__(
                                p, c, tb, ix, t, vcfg, kblock, ktotal
                            )
                        ),
                        vparams,
                        *abstract_paged_pool(
                            vcfg, kslots, ktotal, pool_blocks, kblock
                        ),
                        jnp.zeros((kslots,), jnp.int32),
                        donate_argnums=(1,),
                    )
                step_prog = (
                    "serve.pool_step_paged_flash" if kernel == "paged_flash"
                    else "serve.pool_step_paged"
                )
                measured = _measured_step(ktel, step_prog)
                step_p50_ms = measured.get("p50_ms")
                step_ratio = roofline_ratio(
                    raw.bytes_moved, measured.get("p50_s") or 0.0,
                    dev.device_kind,
                )
                assert step_p50_ms, (
                    f"{vname}/{kernel}: no measured {step_prog} dispatches"
                )
                kernel_rows.append({
                    "cache_variant": vname,
                    "decode_kernel": kernel,
                    "tokens_per_sec": (
                        round(new_tokens / wall, 1) if wall else None
                    ),
                    "wall_s": round(wall, 3),
                    "predicted_bytes_moved": raw.bytes_moved,
                    "predicted_peak_bytes": raw.peak_bytes,
                    "measured_step_p50_ms": step_p50_ms,
                    "roofline_ratio": step_ratio,
                    "predicted_vmem_bytes": (
                        max(kernel_vmem.values()) if kernel_vmem else 0
                    ),
                    "predicted_vmem_by_kernel": kernel_vmem,
                    "interpret": kernel == "paged_flash" and not on_tpu,
                })
                if kernel_vmem:
                    per = ", ".join(
                        f"{k}={v}" for k, v in sorted(kernel_vmem.items())
                    )
                    print(
                        f"[decode_bench] {vname}/{kernel}: "
                        f"predicted_vmem_bytes={max(kernel_vmem.values())} "
                        f"({per})",
                        file=sys.stderr,
                    )
            base = kernels[0]
            for kernel in kernels[1:]:
                assert vanswers[kernel] == vanswers[base], (
                    f"decode_kernel={kernel} changed answers vs {base} "
                    f"({vname})"
                )

    # ---- sharded replica sweep (--mesh) -----------------------------------
    # One replica = one multi-device pjit program (serve/sharded.py): params
    # replicated over a 1-D "data" mesh, pool KV sharded on its leading
    # storage axis (dense: slot rows, paged: block rows).  Per mesh size the
    # row pairs measured tokens/s with the layout's PREDICTED cross-shard
    # collective bytes per decode step: dense is collective-free by
    # construction (the compiled-HLO gate in analysis/sharding.py enforces
    # it), and paged pays for the gathered-view rows that live on other
    # shards — view_bytes * (m - 1) / m.  Answers are asserted byte-identical
    # to the unsharded scheduler per layout, greedy AND seeded-sampled.
    mesh_rows = []
    if mesh_sizes:
        from transformer_tpu.analysis.costs import kv_cache_bytes
        from transformer_tpu.serve import ContinuousScheduler

        assert jax.device_count() >= max(mesh_sizes), (
            f"--mesh {max(mesh_sizes)} needs >= that many devices, got "
            f"{jax.device_count()} — the XLA_FLAGS bootstrap above only "
            "works if no conflicting xla_force_host_platform_device_count "
            "was already set"
        )
        mtok = _IdTok()
        mreqs = _system_prompt_requests(
            np.random.default_rng(2), args.vocab, args.prompt_len, 8
        )
        msampled = 0
        for i, r in enumerate(mreqs):
            r["max_new"] = args.decode_steps
            if i % 3 == 2:
                r.update(temperature=0.8, top_k=8, seed=1000 + i)
                msampled += 1
        mslots = 4  # divisible by every mesh size the sweep targets (1/2/4)
        m_total = args.prompt_len + 4 + 1 + args.decode_steps
        view_bytes = mslots * kv_cache_bytes(cfg, m_total)["bytes_per_slot"]
        for layout in ("dense", "paged"):
            want = None
            for m in [None, *mesh_sizes]:
                sched = ContinuousScheduler(
                    params, cfg, mtok, num_slots=mslots,
                    prefill_chunk=args.chunk, kv_layout=layout,
                    kv_block=args.prefix_block, max_total=m_total,
                    mesh=m,
                )
                t0 = time.perf_counter()
                out = sched.run([dict(r) for r in mreqs])
                wall = time.perf_counter() - t0
                assert all("continuation" in r for r in out), out
                got = [r["continuation"] for r in out]
                if m is None:
                    want = got
                    continue
                assert got == want, (
                    f"mesh={m} ({layout}) changed answers vs the unsharded "
                    "scheduler"
                )
                new_tokens = sum(len(mtok.encode(c)) for c in got)
                mesh_rows.append({
                    "mesh": f"data={m}",
                    "kv_layout": layout,
                    "tokens_per_sec": (
                        round(new_tokens / wall, 1) if wall else None
                    ),
                    "wall_s": round(wall, 3),
                    "predicted_collective_bytes_per_step": (
                        0 if layout == "dense"
                        else int(view_bytes * (m - 1) / m)
                    ),
                    "byte_parity": True,
                    "slots": mslots,
                    "requests": len(mreqs),
                    "sampled_requests": msampled,
                })

    print(json.dumps({
        "prefill_tokens_per_sec": round(prefill_tok_s, 1),
        "decode_tokens_per_sec": round(decode_tok_s, 1),
        "decode_steps_per_sec": round(decode_steps_s, 1),
        "prefill_vs_decode": round(prefill_tok_s / decode_tok_s, 2),
        "prefill_forward_calls": prefill_calls,
        "predicted_peak_bytes": decode_peak,
        "batch": args.batch,
        "prompt_len": args.prompt_len,
        "decode_steps": args.decode_steps,
        "chunk": args.chunk,
        "device": f"{dev.platform}:{dev.device_kind}",
        **({"speculative": speculative} if speculative else {}),
        **({"prefix_reuse": prefix} if prefix else {}),
        **({"kv_layouts": layout_rows} if layout_rows else {}),
        **({"decode_kernels": kernel_rows} if kernel_rows else {}),
        **({"mesh_sweep": mesh_rows} if mesh_rows else {}),
    }))

    if kernel_rows:
        rows = [
            json.dumps({
                "metric": "decode kernel tokens/s",
                "value": r["tokens_per_sec"],
                "unit": "tokens/sec",
                "config": {
                    "layers": args.layers, "d_model": args.d_model,
                    "heads": args.heads, "dff": args.dff,
                    "prompt_len": args.prompt_len,
                    "cache_variant": r["cache_variant"],
                    "decode_kernel": r["decode_kernel"],
                    "kv_layout": "paged",
                    "block_tokens": args.prefix_block,
                    "interpret": r["interpret"],
                },
                "predicted_bytes_moved": r["predicted_bytes_moved"],
                "predicted_peak_bytes": r["predicted_peak_bytes"],
                "predicted_vmem_bytes": r["predicted_vmem_bytes"],
                "measured_step_p50_ms": r["measured_step_p50_ms"],
                "roofline_ratio": r["roofline_ratio"],
                "device": f"{dev.platform}:{dev.device_kind}",
                "vs_baseline": None,
            })
            for r in kernel_rows
        ]
        if args.rows_out:
            with open(args.rows_out, "a", encoding="utf-8") as f:
                f.write("\n".join(rows) + "\n")
        else:
            for row in rows:
                print(row, file=sys.stderr)

    if layout_rows:
        rows = [
            json.dumps({
                "metric": "kv layout max concurrent slots in budget",
                "value": r["max_slots_in_budget"],
                "unit": "slots",
                "config": {
                    "layers": args.layers, "d_model": args.d_model,
                    "heads": args.heads, "dff": args.dff,
                    "prompt_len": args.prompt_len,
                    "kv_layout": r["kv_layout"],
                    "block_tokens": args.prefix_block,
                    "budget_bytes": r["budget_bytes"],
                },
                "tokens_per_sec": r["tokens_per_sec"],
                "kv_bytes_per_slot": r["kv_bytes_per_slot"],
                "predicted_peak_bytes": r["predicted_peak_bytes"],
                "predicted_bytes_moved": r["predicted_bytes_moved"],
                "measured_step_p50_ms": r["measured_step_p50_ms"],
                "roofline_ratio": r["roofline_ratio"],
                "device": f"{dev.platform}:{dev.device_kind}",
                "vs_baseline": None,
            })
            for r in layout_rows
        ]
        if args.rows_out:
            with open(args.rows_out, "a", encoding="utf-8") as f:
                f.write("\n".join(rows) + "\n")
        else:
            for row in rows:
                print(row, file=sys.stderr)

    if prefix:
        row = json.dumps({
            "metric": "prefix cache prompt-token hit rate",
            "value": prefix["hit_rate"],
            "unit": "fraction",
            "config": {
                "layers": args.layers, "d_model": args.d_model,
                "heads": args.heads, "dff": args.dff,
                "prompt_len": args.prompt_len,
                "requests": args.prefix_requests,
                "block_tokens": args.prefix_block,
                "chunk": args.chunk,
            },
            "prefill_forwards_saved": prefix["prefill_forwards_saved"],
            "prefix_hit_tokens": prefix["prefix_hit_tokens"],
            "predicted_peak_bytes": prefix["predicted_peak_bytes"],
            "device": f"{dev.platform}:{dev.device_kind}",
            "vs_baseline": None,
        })
        if args.rows_out:
            with open(args.rows_out, "a", encoding="utf-8") as f:
                f.write(row + "\n")
        else:
            print(row, file=sys.stderr)

    if speculative:
        # bench_rows.jsonl-compatible rows: one per sweep point, so rounds
        # can diff speculative throughput like any other bench metric.
        rows = [
            json.dumps({
                "metric": "speculative decode tokens-per-forward",
                "value": s["tokens_per_forward"],
                "unit": "tokens/forward",
                "config": {
                    "layers": args.layers, "d_model": args.d_model,
                    "heads": args.heads, "dff": args.dff,
                    "prompt_len": args.prompt_len,
                    "decode_steps": args.decode_steps,
                    "speculate_k": s["k"], "drafter": "ngram",
                },
                "tokens_per_sec": s["tokens_per_sec"],
                "acceptance_rate": s["acceptance_rate"],
                "predicted_peak_bytes": s["predicted_peak_bytes"],
                "device": f"{dev.platform}:{dev.device_kind}",
                "vs_baseline": None,
            })
            for s in speculative
        ]
        if args.rows_out:
            with open(args.rows_out, "a", encoding="utf-8") as f:
                f.write("\n".join(rows) + "\n")
        else:
            for row in rows:
                print(row, file=sys.stderr)

    if mesh_rows:
        rows = [
            json.dumps({
                "metric": "sharded decode tokens/s",
                "value": r["tokens_per_sec"],
                "unit": "tokens/sec",
                "config": {
                    "layers": args.layers, "d_model": args.d_model,
                    "heads": args.heads, "dff": args.dff,
                    "prompt_len": args.prompt_len,
                    "decode_steps": args.decode_steps,
                    "mesh": r["mesh"],
                    "kv_layout": r["kv_layout"],
                    "slots": r["slots"],
                    "requests": r["requests"],
                    "sampled_requests": r["sampled_requests"],
                },
                "predicted_collective_bytes_per_step": (
                    r["predicted_collective_bytes_per_step"]
                ),
                "byte_parity": r["byte_parity"],
                "wall_s": r["wall_s"],
                "device": f"{dev.platform}:{dev.device_kind}",
                "vs_baseline": None,
            })
            for r in mesh_rows
        ]
        if args.rows_out:
            with open(args.rows_out, "a", encoding="utf-8") as f:
                f.write("\n".join(rows) + "\n")
        else:
            for row in rows:
                print(row, file=sys.stderr)


if __name__ == "__main__":
    main()
