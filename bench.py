"""Benchmark: Transformer-base training throughput, tokens/sec/chip.

Runs the flagship train step (BASELINE.json configs[1]: 6L, d_model=512,
8 heads, dff=2048, bf16 compute) on one TPU chip, times steady-state steps,
and prints ONE JSON line:

    {"metric": "...", "value": N, "unit": "tokens/sec/chip", "vs_baseline": X,
     "platform": "tpu", "device_kind": "...", "device_count": 1}

``vs_baseline`` is null: the reference publishes no numbers (BASELINE.md —
README is a bare feature list), so there is nothing to normalize against.

One process, no retry and no stored result: where JAX finds no TPU the
script fails with a traceback and a non-zero exit.
"""

from __future__ import annotations

import json
import sys
import time

_METRIC = "transformer-base train throughput (6L/512/8H/2048, bf16, batch 64, seq 64)"


def main() -> None:
    import jax
    import numpy as np

    from transformer_tpu.config import ModelConfig, TrainConfig
    from transformer_tpu.train import create_train_state, make_train_step
    from transformer_tpu.train.trainer import make_multistep_train_step
    from transformer_tpu.utils import enable_compilation_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures a TPU; JAX found {dev.platform}:{dev.device_kind}"
        )
    print(f"benchmarking on {dev.platform}:{dev.device_kind}", file=sys.stderr)
    enable_compilation_cache()

    batch, seq = 64, 64
    model_cfg = ModelConfig(
        num_layers=6,
        d_model=512,
        num_heads=8,
        dff=2048,
        input_vocab_size=32002,
        target_vocab_size=32002,
        max_position=seq,
        dropout_rate=0.1,
        dtype="bfloat16",
    )
    train_cfg = TrainConfig(
        batch_size=batch, sequence_length=seq, warmup_steps=4000,
    )

    state = create_train_state(jax.random.PRNGKey(0), model_cfg, train_cfg)
    step = jax.jit(make_train_step(model_cfg, train_cfg), donate_argnums=(0,))
    rng = jax.random.PRNGKey(1)
    r = np.random.default_rng(0)
    src = jax.device_put(r.integers(1, 32000, (batch, seq), dtype=np.int32))
    tgt = jax.device_put(r.integers(1, 32000, (batch, seq), dtype=np.int32))

    # Warmup: compile + 2 steady steps.
    for _ in range(3):
        state, metrics = step(state, src, tgt, rng)
    jax.block_until_ready(metrics)

    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step(state, src, tgt, rng)
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0
    final_loss = float(metrics["loss"])
    if final_loss != final_loss:
        raise RuntimeError("NaN loss")

    # Tokens processed per optimizer step: target tokens (the unit BLEU-side
    # throughput is quoted in). src+tgt would double-count the same sentence.
    tokens_per_step = batch * (seq - 1)
    value = tokens_per_step * n_steps / dt

    # Rough MFU estimate for context (stderr only): 6*P FLOPs/token fwd+bwd*3.
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    flops_per_token = 6 * n_params
    print(
        f"{n_steps} steps in {dt:.2f}s, {value:,.0f} tok/s, "
        f"~{value * flops_per_token / 1e12:.2f} TFLOP/s model-flops "
        f"({n_params / 1e6:.1f}M params)",
        file=sys.stderr,
    )

    # Production dispatch path (TrainConfig.steps_per_dispatch): the same 20
    # optimizer steps inside ONE jitted scan with distinct stacked batches —
    # what --steps_per_dispatch buys a real run by amortizing per-step host
    # dispatch. Reported as an extra field; the headline stays the plain
    # per-step dispatch number.
    multi = jax.jit(
        make_multistep_train_step(make_train_step(model_cfg, train_cfg)),
        donate_argnums=(0,),
    )
    srcs = jax.device_put(
        r.integers(1, 32000, (n_steps, batch, seq), dtype=np.int32)
    )
    tgts = jax.device_put(
        r.integers(1, 32000, (n_steps, batch, seq), dtype=np.int32)
    )
    state, metrics = multi(state, srcs, tgts, rng)  # compile + warm
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    state, metrics = multi(state, srcs, tgts, rng)
    jax.block_until_ready(metrics)
    ms_dt = time.perf_counter() - t0

    print(json.dumps({
        "metric": _METRIC,
        "value": round(value, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "multistep_tokens_per_sec": round(tokens_per_step * n_steps / ms_dt, 1),
        "multistep_note": (
            f"steps_per_dispatch={n_steps}: one dispatch, {n_steps} "
            "optimizer steps on distinct stacked batches"
        ),
    }))


if __name__ == "__main__":
    main()
