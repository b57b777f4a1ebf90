"""Benchmark suite: train-step throughput for every BASELINE.json config.

``bench.py`` at the repo root stays the driver contract (one JSON line for
the flagship config); this runner measures all five configs and prints one
JSON line each, for filling in BASELINE.md:

    python benchmarks/run.py [--steps N] [--configs tiny,base,...]

Configs (BASELINE.json "configs"):
  tiny   2L Transformer-tiny (the CPU smoke config)
  base   6L d_model=512 8H dff=2048 (Vaswani base)
  big    6L d_model=1024 16H dff=4096 + label smoothing 0.1
  tied   base + tied src/tgt embeddings + tied output projection
  long4k 4096-token decoder-only causal LM with flash attention

Throughput counts *target* tokens per optimizer step (batch × (seq−1)):
the unit BLEU-side throughput is quoted in; src+tgt would double-count the
same sentence pair.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _configs():
    from transformer_tpu.config import ModelConfig, TrainConfig

    # (model_cfg, train_cfg, batch, seq) per benchmark point.
    out = {}
    out["tiny"] = (
        ModelConfig(
            num_layers=2, d_model=128, num_heads=4, dff=512,
            input_vocab_size=32002, target_vocab_size=32002,
            max_position=64, dtype="bfloat16",
        ),
        TrainConfig(batch_size=64, sequence_length=64, warmup_steps=4000),
        64, 64,
    )
    out["base"] = (
        ModelConfig(
            num_layers=6, d_model=512, num_heads=8, dff=2048,
            input_vocab_size=32002, target_vocab_size=32002,
            max_position=64, dtype="bfloat16",
        ),
        TrainConfig(batch_size=64, sequence_length=64, warmup_steps=4000),
        64, 64,
    )
    out["big"] = (
        ModelConfig(
            num_layers=6, d_model=1024, num_heads=16, dff=4096,
            input_vocab_size=32002, target_vocab_size=32002,
            max_position=64, dtype="bfloat16",
        ),
        TrainConfig(
            batch_size=32, sequence_length=64, warmup_steps=4000,
            label_smoothing=0.1,
        ),
        32, 64,
    )
    out["tied"] = (
        ModelConfig(
            num_layers=6, d_model=512, num_heads=8, dff=2048,
            input_vocab_size=32002, target_vocab_size=32002,
            max_position=64, dtype="bfloat16",
            tie_embeddings=True, tie_output=True,
        ),
        TrainConfig(batch_size=64, sequence_length=64, warmup_steps=4000),
        64, 64,
    )
    out["long4k"] = (
        ModelConfig(
            num_layers=6, d_model=512, num_heads=8, dff=2048,
            input_vocab_size=32002, target_vocab_size=32002,
            max_position=4096, dtype="bfloat16",
            decoder_only=True, attention_impl="flash",
        ),
        TrainConfig(batch_size=4, sequence_length=4096, warmup_steps=4000),
        4, 4096,
    )
    return out


def bench_config(
    name: str, n_steps: int = 20, mode: str = "full", profile_dir: str = "",
    loss_chunks: int = 1, batch_override: int = 0, seq_override: int = 0,
    flash_block: int = 0, attn_impl: str = "",
) -> dict:
    """One measurement. ``mode`` attributes step time without trace tooling:

    - full:       the real train step (forward + backward + Adam)
    - fwd:        eval step only — isolates the backward+optimizer share
    - smallvocab: train step with a 2k-row OUTPUT vocab (input embedding
                  untouched) — isolates the vocab-projection/CE share
                  (32k-vocab logits matmul is the prime MFU suspect at seq 64)
    - deviceloop: all n_steps run inside ONE jitted lax.scan, so the host
                  dispatches once — (full − deviceloop) throughput is the
                  per-step dispatch overhead share, the prime
                  suspect for the low measured MFU at batch 64 × seq 64
                  (BASELINE.md r2 analysis). Same math as `full`: the scan
                  carries the donated state through real optimizer steps.
    - multistep:  the production dispatch-amortization path
                  (TrainConfig.steps_per_dispatch / trainer.
                  make_multistep_train_step): n_steps DISTINCT batches
                  stacked into one (K,B,S) transfer, K optimizer steps per
                  dispatch — what `--steps_per_dispatch K` buys a real
                  training run (deviceloop is its upper bound).

    ``loss_chunks > 1`` additionally runs the chunked vocab-projection/CE
    path (TrainConfig.loss_chunks) for A/B against the monolithic loss.

    Serving-side modes (the reference has no working decode to measure,
    SURVEY §2.3.2/.11 — these rows are framework-only):
    - decode:    KV-cached greedy decode, generated tokens/sec.
    - decodeq8:  same with the int8 KV cache (--kv_cache_int8 A/B).
    """
    import dataclasses

    import jax
    import numpy as np

    from transformer_tpu.train import (
        create_train_state,
        make_eval_step,
        make_train_step,
    )
    from transformer_tpu.utils import enable_compilation_cache

    # One subprocess per measurement means every row re-compiles; the
    # persistent cache makes repeat rows and A/B variants pay compile once
    # per distinct executable.
    enable_compilation_cache()

    model_cfg, train_cfg, batch, seq = _configs()[name]
    if mode in ("decode", "decodeq8"):
        return _bench_decode(name, model_cfg, batch, seq, n_steps, mode)
    if batch_override or seq_override:
        # MFU-ceiling probes: the BASELINE shapes are fixed for comparability,
        # but utilization scales with tokens/step — overrides find the knee.
        batch = batch_override or batch
        seq = seq_override or seq
        model_cfg = dataclasses.replace(
            model_cfg, max_position=max(model_cfg.max_position, seq)
        )
        train_cfg = dataclasses.replace(
            train_cfg, batch_size=batch, sequence_length=seq
        )
    if loss_chunks > 1:
        train_cfg = dataclasses.replace(train_cfg, loss_chunks=loss_chunks)
    if flash_block:
        # Flash-kernel tile sweep (long4k): the 128 default was chosen for
        # VMEM safety, not measured; bigger k-tiles amortize the per-tile
        # loop overhead at 4096 if they fit.
        model_cfg = dataclasses.replace(
            model_cfg, flash_block_q=flash_block, flash_block_k=flash_block
        )
    if attn_impl:
        # Attention-impl A/B (the flash kernel has to EARN its 763 lines):
        # long4k with attention_impl="xla" materializes the (B,H,S,S) fp32
        # scores the way the reference does — if XLA's own lowering matches
        # the Pallas kernel on-chip, flash should not be the default.
        model_cfg = dataclasses.replace(model_cfg, attention_impl=attn_impl)
    if mode == "smallvocab":
        model_cfg = dataclasses.replace(model_cfg, target_vocab_size=2048)
    dev = jax.devices()[0]
    state = create_train_state(jax.random.PRNGKey(0), model_cfg, train_cfg)
    rng = jax.random.PRNGKey(1)
    r = np.random.default_rng(0)
    top = min(32000, model_cfg.target_vocab_size - 2)
    if mode == "multistep":
        # The PRODUCTION dispatch-amortization path (TrainConfig.
        # steps_per_dispatch): distinct stacked batches, one (K,B,S) host
        # transfer, K real optimizer steps per dispatch — unlike deviceloop
        # (same batch re-scanned), this is what a training run would see.
        src = jax.device_put(
            r.integers(1, top, (n_steps, batch, seq), dtype=np.int32)
        )
        tgt = jax.device_put(
            r.integers(1, top, (n_steps, batch, seq), dtype=np.int32)
        )
    else:
        src = jax.device_put(r.integers(1, top, (batch, seq), dtype=np.int32))
        tgt = jax.device_put(r.integers(1, top, (batch, seq), dtype=np.int32))

    if mode == "fwd":
        eval_step = jax.jit(make_eval_step(model_cfg, train_cfg))
        step = lambda state, src, tgt, rng: (state, eval_step(state, src, tgt))  # noqa: E731
    elif mode == "deviceloop":
        inner = make_train_step(model_cfg, train_cfg)

        def scan_steps(state, src, tgt, rng):
            def body(s, _):
                return inner(s, src, tgt, rng)

            state, ms = jax.lax.scan(body, state, None, length=n_steps)
            # The last step's metrics are a scan output: waiting on them
            # waits for the whole device loop.
            return state, jax.tree.map(lambda x: x[-1], ms)

        step = jax.jit(scan_steps, donate_argnums=(0,))
    elif mode == "multistep":
        from transformer_tpu.train.trainer import make_multistep_train_step

        step = jax.jit(
            make_multistep_train_step(make_train_step(model_cfg, train_cfg)),
            donate_argnums=(0,),
        )
    else:
        step = jax.jit(make_train_step(model_cfg, train_cfg), donate_argnums=(0,))

    warmups = 2 if mode in ("deviceloop", "multistep") else 3  # compile + settle
    for _ in range(warmups):
        state, metrics = step(state, src, tgt, rng)
    jax.block_until_ready(metrics)

    import contextlib

    ctx = (
        jax.profiler.trace(profile_dir) if profile_dir else contextlib.nullcontext()
    )
    with ctx:
        t0 = time.perf_counter()
        if mode in ("deviceloop", "multistep"):
            # ONE dispatch covering all n_steps optimizer steps on device.
            state, metrics = step(state, src, tgt, rng)
        else:
            for _ in range(n_steps):
                state, metrics = step(state, src, tgt, rng)
        jax.block_until_ready(metrics)
        dt = time.perf_counter() - t0
    final_loss = float(metrics["loss"])
    if final_loss != final_loss:
        raise RuntimeError("NaN loss")

    tokens_per_step = batch * (seq - 1)
    value = tokens_per_step * n_steps / dt
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    tag = (
        (f" [{mode}]" if mode != "full" else "")
        + (f" [chunks={loss_chunks}]" if loss_chunks > 1 else "")
        + (f" [b{batch}xs{seq}]" if batch_override or seq_override else "")
        + (f" [fb{flash_block}]" if flash_block else "")
        + (f" [{attn_impl}]" if attn_impl else "")
    )
    return {
        "metric": f"{name} train throughput" + tag,
        "value": round(value, 1),
        "unit": "tokens/sec/chip",
        "config": {
            "layers": model_cfg.num_layers,
            "d_model": model_cfg.d_model,
            "heads": model_cfg.num_heads,
            "dff": model_cfg.dff,
            "batch": batch,
            "seq": seq,
            "decoder_only": model_cfg.decoder_only,
            "params_millions": round(n_params / 1e6, 1),
        },
        "step_ms": round(dt / n_steps * 1e3, 2),
        "device": f"{dev.platform}:{dev.device_kind}",
        "vs_baseline": None,  # reference publishes no numbers (BASELINE.md)
    }


def _bench_decode(
    name: str, model_cfg, batch: int, seq: int, n_iters: int, mode: str
) -> dict:
    """Greedy-decode throughput: generated tokens/sec with the KV cache
    (fp, or int8 when mode == 'decodeq8'). EOS is set outside the vocab so
    every row decodes the full max_len — deterministic token counts."""
    import dataclasses
    import time as _time

    import jax
    import numpy as np

    from transformer_tpu.train.decode import greedy_decode

    if mode == "decodeq8":
        model_cfg = dataclasses.replace(model_cfg, kv_cache_int8=True)
    # Serving shape: decode length = the config's training sequence length,
    # batch capped so the long4k cache fits comfortably.
    batch = min(batch, 32)
    max_len = min(seq, 128)
    src_len = min(seq, 64)
    dev = jax.devices()[0]
    from transformer_tpu.models import transformer_init

    params = transformer_init(jax.random.PRNGKey(0), model_cfg)
    r = np.random.default_rng(0)
    if model_cfg.decoder_only:
        # Long-context LM continuation — the int8-KV-cache showcase shape:
        # a long prompt fills the cache (prefill rides the same scan), then
        # generation attends over the whole context every step.
        from transformer_tpu.train.decode import lm_generate

        batch = min(batch, 4)
        prompt_len = min(seq // 2, 2048)
        max_len = min(seq - prompt_len, 512)
        prompt = jax.device_put(
            r.integers(
                1, model_cfg.target_vocab_size - 2, (batch, prompt_len),
                dtype=np.int32,
            )
        )
        run = lambda: lm_generate(  # noqa: E731
            params, prompt, model_cfg, max_new=max_len,
            eos_id=model_cfg.target_vocab_size + 7,  # unreachable: full rows
        )
        src_len = prompt_len
    else:
        src = jax.device_put(
            r.integers(
                1, model_cfg.input_vocab_size - 2, (batch, src_len),
                dtype=np.int32,
            )
        )
        run = lambda: greedy_decode(  # noqa: E731
            params, src, model_cfg, max_len=max_len,
            bos_id=model_cfg.target_vocab_size - 2,
            eos_id=model_cfg.target_vocab_size + 7,  # unreachable: full-length rows
        )
    jax.block_until_ready(run())
    t0 = _time.perf_counter()
    for _ in range(n_iters):
        out = run()
    jax.block_until_ready(out)
    dt = _time.perf_counter() - t0
    value = batch * max_len * n_iters / dt
    return {
        "metric": f"{name} decode throughput [{mode}]",
        "value": round(value, 1),
        "unit": "generated tokens/sec/chip",
        "config": {
            "batch": batch, "src_len": src_len, "max_len": max_len,
            "kv_cache_int8": model_cfg.kv_cache_int8,
        },
        "ms_per_token": round(dt / (max_len * n_iters) * 1e3, 3),
        # Serving view of the same measurement (cli.serve --serve_batch
        # aggregates concurrent requests into exactly this shape): each
        # decode completes `batch` requests together, so p50 request
        # latency = one decode's wall time.
        "requests_per_sec": round(batch * n_iters / dt, 2),
        "p50_request_ms": round(dt / n_iters * 1e3, 1),
        "device": f"{dev.platform}:{dev.device_kind}",
        "vs_baseline": None,  # reference decode is broken (SURVEY §2.3.2/.11)
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument(
        "--configs", default="tiny,base,big,tied,long4k",
        help="comma-separated subset",
    )
    ap.add_argument(
        "--modes", default="full",
        help="comma-separated subset of full,fwd,smallvocab,deviceloop,"
        "multistep (step-time attribution; deviceloop = all steps in one "
        "jitted scan of ONE batch, isolating per-step dispatch overhead; "
        "multistep = the production steps_per_dispatch path: distinct "
        "stacked batches, one transfer + K steps per dispatch)",
    )
    ap.add_argument(
        "--profile_dir", default="",
        help="capture a jax.profiler trace of the timing loop into this dir",
    )
    ap.add_argument(
        "--loss_chunks", type=int, default=1,
        help="A/B the chunked vocab-projection/CE path (TrainConfig."
        "loss_chunks); 1 = monolithic loss",
    )
    ap.add_argument(
        "--batch", type=int, default=0,
        help="override the config's batch size (MFU-ceiling probes; 0 = keep)",
    )
    ap.add_argument(
        "--seq", type=int, default=0,
        help="override the config's sequence length (0 = keep)",
    )
    ap.add_argument(
        "--flash_block", type=int, default=0,
        help="override flash_block_q/k (flash-kernel tile sweep; 0 = keep)",
    )
    ap.add_argument(
        "--attn_impl", default="",
        help="override ModelConfig.attention_impl (flash-vs-xla A/B at "
        "long4k; empty = keep the config's impl)",
    )
    args = ap.parse_args()
    names = [n.strip() for n in args.configs.split(",") if n.strip()]
    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    known = {
        "full", "fwd", "smallvocab", "deviceloop", "multistep",
        "decode", "decodeq8",
    }
    bad = [m for m in modes if m not in known]
    if bad:  # an unknown mode would silently time the full step mislabeled
        ap.error(f"unknown mode(s) {bad}; choose from {sorted(known)}")

    if len(names) * len(modes) > 1:
        # One subprocess per measurement, one at a time: this parent stays
        # off JAX, so each child in turn is the one process holding the
        # chip. Every measurement is attempted; any failure fails the run.
        import subprocess

        failed = []
        for name in names:
            for mode in modes:
                proc = subprocess.run(
                    [sys.executable, __file__, "--steps", str(args.steps),
                     "--configs", name, "--modes", mode,
                     "--profile_dir", args.profile_dir,
                     "--loss_chunks", str(args.loss_chunks),
                     "--batch", str(args.batch), "--seq", str(args.seq),
                     "--flash_block", str(args.flash_block),
                     "--attn_impl", args.attn_impl],
                    check=False,
                )
                if proc.returncode:
                    failed.append(f"{name}[{mode}] rc={proc.returncode}")
        if failed:
            sys.exit(f"measurements failed: {', '.join(failed)}")
        return

    name, mode = names[0], modes[0]
    print(f"benchmarking {name} [{mode}]...", file=sys.stderr)
    print(
        json.dumps(
            bench_config(
                name, args.steps, mode, args.profile_dir,
                loss_chunks=args.loss_chunks,
                batch_override=args.batch, seq_override=args.seq,
                flash_block=args.flash_block, attn_impl=args.attn_impl,
            )
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
