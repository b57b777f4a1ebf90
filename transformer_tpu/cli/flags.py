"""Flag surface.

Preserves the reference's 16-flag namespace verbatim (``utils.py:17-33``:
dataset_path, buffer_size, src_vocab_file, tgt_vocab_file, sequence_length,
epochs, batch_size, per_replica_batch_size, num_layers, d_model, dff,
num_heads, enable_function, max_ckpt_keep, ckpt_path, dropout_rate) and adds
the TPU-native knobs (mesh axes, dtype, platform, variants). ``flags_to_*``
materialize the namespace into the framework's config dataclasses — the
counterpart of ``flags_dict()`` + ``main(**kwargs)`` splatting
(``utils.py:36-62``, ``train.py:216-220``).
"""

from __future__ import annotations

from absl import flags

from transformer_tpu.config import MeshConfig, ModelConfig, TrainConfig

FLAGS = flags.FLAGS


# Literal so flag definition stays jax-import-free (the CLIs defer `import
# jax` into main() on purpose — env/platform setup must run first);
# tests/test_flags.py pins this against ops.ffn.FFN_ACTIVATIONS.
_FFN_ACTIVATION_NAMES = ("geglu", "gelu", "reglu", "relu", "silu", "swiglu")

# The one table of the presets: the reference's five configurations
# (BASELINE.json "configs") by name. Values land on flags the user did NOT
# set explicitly (explicit flags always win).
_PRESETS: dict[str, dict] = {
    "tiny": dict(num_layers=2, d_model=128, num_heads=4, dff=512, batch_size=64),
    "base": dict(num_layers=6, d_model=512, num_heads=8, dff=2048, batch_size=64),
    "big": dict(
        num_layers=6, d_model=1024, num_heads=16, dff=4096,
        label_smoothing=0.1, batch_size=32,
    ),
    "tied": dict(
        num_layers=6, d_model=512, num_heads=8, dff=2048,
        tie_embeddings=True, tie_output=True, batch_size=64,
    ),
    "long4k": dict(
        num_layers=6, d_model=512, num_heads=8, dff=2048,
        decoder_only=True, attention_impl="flash", sequence_length=4096,
        remat=True, batch_size=4,
    ),
}


def apply_preset() -> None:
    """Fold ``--preset`` values into unset flags (idempotent; called by the
    flags_to_* materializers so every CLI gets it)."""
    if not FLAGS.preset:
        return
    for name, value in _PRESETS[FLAGS.preset].items():
        if not FLAGS[name].present:
            setattr(FLAGS, name, value)


def define_metrics_flags() -> None:
    """Telemetry knobs (docs/OBSERVABILITY.md) — shared by the training
    CLIs (via ``define_flags``) and the export-serving CLIs (``cli.serve``
    defines its own surface), hence the idempotence guard."""
    if "metrics_jsonl" in FLAGS:
        return
    flags.DEFINE_string(
        "metrics_jsonl", "",
        "write structured telemetry (JSONL events + periodic metric "
        "snapshots) to this file; a Prometheus text exposition is rewritten "
        "alongside it at <file>.prom. Summarize with "
        "`python -m transformer_tpu.obs summarize <file>`. '' = off")
    flags.DEFINE_integer(
        "metrics_port", 0,
        "serve a Prometheus /metrics scrape endpoint on this port "
        "(0 = off; train/distributed_train/serve). Works with or without "
        "--metrics_jsonl")
    flags.DEFINE_float(
        "metrics_interval", 10.0,
        "seconds between periodic metric-snapshot flushes (prom file + "
        "metrics.snapshot events)")
    flags.DEFINE_boolean(
        "trace", False,
        "also write every closed span (request-scoped distributed tracing, "
        "docs/OBSERVABILITY.md) as a trace.span event into --metrics_jsonl; "
        "spans are always kept in memory (obs.trace.buffer()) and mirrored "
        "into a profiler trace while one is taken. Export with "
        "`python -m transformer_tpu.obs trace <file> --out trace.json` and "
        "load in chrome://tracing / Perfetto. Answers and compiled programs "
        "are unaffected (contract-checked)")
    flags.DEFINE_boolean(
        "flight_recorder", True,
        "always-on bounded flight recorder (obs/flight.py): keep the last "
        "seconds of events/spans/snapshots in memory and dump them to "
        "<metrics_jsonl>.flight.json on signal/close plus a periodic "
        "autodump (crash durability). Needs --metrics_jsonl")


def define_flags() -> None:
    flags.DEFINE_enum(
        "preset", "", ["", *sorted(_PRESETS)],
        "start from one of the reference's configurations (BASELINE.json: "
        "tiny/base/big/tied/long4k); "
        "explicitly-passed flags override preset values")
    # --- reference-surface flags (utils.py:18-33 defaults) ---
    flags.DEFINE_string("dataset_path", "data", "directory with src/tgt line files")
    flags.DEFINE_integer(
        "buffer_size", 100000,
        "shuffle buffer size: with --streaming this bounds host memory (the "
        "reference's utils.py:154 semantics); the in-memory path ignores it "
        "(full permutation is free there)")
    flags.DEFINE_string("src_vocab_file", "src_vocab.subwords", "source subword vocab path")
    flags.DEFINE_string("tgt_vocab_file", "tgt_vocab.subwords", "target subword vocab path")
    flags.DEFINE_integer("sequence_length", 50, "max sequence length (tokens incl. BOS/EOS)")
    flags.DEFINE_integer("epochs", 4, "training epochs")
    flags.DEFINE_integer("batch_size", 64, "global batch size")
    flags.DEFINE_integer("per_replica_batch_size", 16, "compat flag; derived from batch_size/mesh")
    flags.DEFINE_integer("num_layers", 4, "transformer layers per stack")
    flags.DEFINE_integer("d_model", 512, "model width")
    flags.DEFINE_integer("dff", 1024, "FFN hidden width")
    flags.DEFINE_integer("num_heads", 4, "attention heads")
    flags.DEFINE_integer(
        "num_kv_heads", 0,
        "grouped-query attention: k/v heads, each serving "
        "num_heads/num_kv_heads query heads (smaller decode KV cache); "
        "0 = num_heads (standard MHA)")
    flags.DEFINE_boolean("enable_function", True, "jit the train/eval steps (False = eager debug)")
    flags.DEFINE_integer("max_ckpt_keep", 5, "checkpoints to retain")
    flags.DEFINE_string("ckpt_path", "model_dist", "checkpoint directory")
    flags.DEFINE_float("dropout_rate", 0.1, "dropout rate")
    # --- framework extensions ---
    flags.DEFINE_integer("target_vocab_size", 2**15, "subword vocab build target")
    flags.DEFINE_integer(
        "warmup_steps", 60000,
        "LR warmup steps, shared by every --lr_schedule; the 60000 default "
        "is reference-noam parity — set a small value (hundreds) for "
        "cosine/constant runs")
    flags.DEFINE_enum(
        "lr_schedule", "noam", ["noam", "cosine", "constant"],
        "LR schedule: noam (reference), or warmup + cosine-decay / constant "
        "at --peak_lr (modern-LM schedules)")
    flags.DEFINE_float("peak_lr", 0.0, "peak LR for cosine/constant schedules")
    flags.DEFINE_integer(
        "lr_decay_steps", 0, "cosine horizon (decays to peak_lr/10 here)")
    flags.DEFINE_float("label_smoothing", 0.0, "label smoothing epsilon")
    flags.DEFINE_enum("loss_normalization", "tokens", ["tokens", "batch"],
                      "CE normalization ('batch' = reference rule)")
    flags.DEFINE_float("max_grad_norm", 0.0, "global-norm gradient clip (0 = off)")
    flags.DEFINE_enum(
        "optimizer", "adam", ["adam", "adafactor", "adamw"],
        "adam = reference optimizer; adafactor = factored second moments "
        "(far less optimizer-state memory for big models); adamw = "
        "decoupled weight decay on matrices (--weight_decay)")
    flags.DEFINE_float(
        "weight_decay", 0.0,
        "adamw decoupled weight decay (vectors — biases/layernorms — exempt)")
    flags.DEFINE_boolean("tie_embeddings", False, "share src/tgt embedding tables")
    flags.DEFINE_boolean("tie_output", False, "tie output projection to embedding")
    flags.DEFINE_enum("norm_scheme", "post", ["post", "pre"], "residual LayerNorm wiring")
    flags.DEFINE_enum(
        "ffn_activation", "relu", list(_FFN_ACTIVATION_NAMES),
        "FFN activation (reference: relu); swiglu/geglu/reglu are the gated "
        "three-matmul variants")
    flags.DEFINE_enum(
        "position_scheme", "sinusoidal", ["sinusoidal", "rope"],
        "position encoding: additive sinusoidal table (reference behavior) "
        "or rotary q/k embeddings (long-context; relative positions)")
    flags.DEFINE_boolean(
        "decoder_only", False,
        "causal-LM mode (cli.train and cli.distributed_train): train a "
        "decoder-only model on the target-side corpus chunked into "
        "sequence_length windows (BASELINE configs[4]); translation-side "
        "flags are ignored")
    flags.DEFINE_enum(
        "objective", "causal", ["causal", "mlm"],
        "training objective: 'causal' (teacher-forcing seq2seq / LM) or "
        "'mlm' (BERT-style masked-LM on an encoder-only model: trains on "
        "target-side LM windows like --decoder_only, masks dynamically "
        "in-step, reserves the top input id for [MASK])")
    flags.DEFINE_float(
        "mlm_mask_rate", 0.15,
        "fraction of non-pad positions selected per MLM step (80/10/10 "
        "mask/random/keep split within the selection)")
    flags.DEFINE_enum("attention_impl", "xla", ["xla", "flash", "ring", "ulysses"],
                      "attention kernel (ring/ulysses = sequence-parallel, use with --sp>1)")
    flags.DEFINE_string("dtype", "bfloat16", "compute dtype")
    flags.DEFINE_integer(
        "moe_experts", 0,
        "Mixture-of-Experts FFN: experts per MoE layer (0 = dense FFN). "
        "Shard over devices with --ep.")
    flags.DEFINE_integer("moe_top_k", 2, "experts each token routes to")
    flags.DEFINE_float("moe_capacity_factor", 1.25,
                       "slack over the even-split expert capacity")
    flags.DEFINE_integer("moe_every", 1,
                         "MoE cadence: every k-th layer carries the MoE FFN")
    flags.DEFINE_float("moe_aux_weight", 0.01,
                       "load-balance auxiliary loss weight")
    flags.DEFINE_boolean(
        "remat", False,
        "rematerialize layer activations in backward (less HBM, ~1/3 more "
        "FLOPs) — the long-context memory lever")
    flags.DEFINE_string("tb_log_dir", "logs", "TensorBoard log root")
    flags.DEFINE_integer("seed", 0, "PRNG seed")
    flags.DEFINE_string("platform", "", "force a jax platform (e.g. 'cpu') before first use")
    flags.DEFINE_boolean("native_loader", True,
                         "prefetch batches via the C++ loader when available")
    flags.DEFINE_string(
        "length_buckets", "",
        "comma-separated ascending batch widths (e.g. '24,36,50', last <= "
        "sequence_length): batches pad to the smallest fitting bucket — "
        "one compile per bucket, far fewer padding FLOPs ('' = off)")
    flags.DEFINE_boolean(
        "streaming", False,
        "stream the train corpus from disk with a --buffer_size shuffle "
        "buffer instead of loading it into RAM (corpora larger than host "
        "memory; needs pre-built vocab files; seq2seq pipeline only)")
    flags.DEFINE_string("profile_dir", "", "capture a jax.profiler trace into this dir")
    flags.DEFINE_integer("profile_start_step", 2, "first step of the profile window")
    flags.DEFINE_integer("profile_num_steps", 3, "profile window length in steps")
    define_metrics_flags()
    # --- mesh knobs (distributed) ---
    flags.DEFINE_integer("dp", 0, "data-parallel mesh size (0 = all devices)")
    flags.DEFINE_integer("fsdp", 1, "fsdp (param-shard) mesh size")
    flags.DEFINE_integer("tp", 1, "tensor-parallel mesh size")
    flags.DEFINE_integer("sp", 1, "sequence-parallel mesh size")
    flags.DEFINE_integer(
        "pp", 1,
        "pipeline-parallel mesh size (GPipe stages). Note: pipe partitions "
        "compute only; combine with --fsdp to shard stage params/optimizer "
        "state, else each device holds a full param replica.")
    flags.DEFINE_integer(
        "ep", 1,
        "expert-parallel mesh size (MoE expert weights sharded; tokens reach "
        "their experts via an ICI all-to-all). The expert axis also splits "
        "the batch, so it contributes to the data-parallel divisibility check.")
    flags.DEFINE_integer(
        "pp_microbatches", 0,
        "GPipe microbatches per step (0 = one per stage); more microbatches "
        "shrink the pipeline bubble at the cost of smaller per-shard matmuls")
    flags.DEFINE_enum(
        "pp_schedule", "gpipe", ["gpipe", "1f1b"],
        "pipeline schedule: 'gpipe' (autodiff backward, activation stash "
        "grows with pp_microbatches) or '1f1b' (interleaved manual backward, "
        "stash bounded at 2*stages-1 microbatches — raise pp_microbatches "
        "freely; decoder-only dense models on data x pipe meshes)")
    flags.DEFINE_integer(
        "dcn_data", 1,
        "multi-slice: how many DCN-connected slices (processes off-TPU) the "
        "data axis spans; must divide --dp. Slow DCN hops then carry only "
        "the data-parallel gradient all-reduce — every other axis stays on "
        "intra-slice ICI.")
    flags.DEFINE_integer(
        "eval_max_batches", 8,
        "cap on in-loop eval batches (0 = full test set each eval)")
    flags.DEFINE_integer(
        "early_stop_patience", 0,
        "stop after this many consecutive epochs without eval-loss "
        "improvement (0 = run all epochs, the reference behavior)")
    flags.DEFINE_integer(
        "grad_accum", 1,
        "gradient-accumulation micro-steps per optimizer update (1 = off)")
    flags.DEFINE_integer(
        "loss_chunks", 1,
        "compute the vocab projection + CE over this many sequence slices so "
        "the full (B,S,V) logits tensor is never materialized (1 = off) — "
        "the memory lever for big-vocab/long-context configs")
    flags.DEFINE_enum(
        "remat_policy", "full", ["full", "dots"],
        "what remat may keep: 'full' recomputes everything (min memory); "
        "'dots' saves matmul outputs, recomputes only elementwise ops "
        "(most of the memory win at a fraction of the recompute)")
    flags.DEFINE_integer(
        "attention_window", 0,
        "sliding-window causal self-attention: each position attends only "
        "the last N positions (0 = full attention); structural tile-skip "
        "in the flash kernel, banded mask under xla, honored by decode")
    flags.DEFINE_integer(
        "steps_per_dispatch", 1,
        "optimizer steps per host dispatch, run inside one jitted lax.scan "
        "(1 = off) — amortizes per-step dispatch overhead when step times "
        "are small; log/eval/preemption granularity becomes this many steps")
    flags.DEFINE_boolean(
        "consistency_check", False,
        "after every epoch (and at end of run), assert that all processes "
        "hold bit-identical replicated state (catches silent per-host "
        "RNG/data-order divergence; utils/consistency.py)")
    flags.DEFINE_boolean(
        "async_checkpoint", False,
        "write checkpoints from a background thread (device snapshot stays "
        "synchronous); multi-process sharded states fall back to sync saves")
    flags.DEFINE_boolean(
        "eval_bleu", True,
        "compute corpus BLEU on the test split after training")
    flags.DEFINE_integer(
        "bleu_limit", 200,
        "cap on test pairs scored for end-of-run BLEU (0 = all)")


def flags_to_model_config(input_vocab_size: int, target_vocab_size: int) -> ModelConfig:
    apply_preset()
    if FLAGS.objective == "mlm":
        # Reserve the top input id for [MASK] (train/mlm.py): the model
        # vocab is one larger than the tokenizer's; head and embedding
        # share the single (extended) id space.
        input_vocab_size += 1
        target_vocab_size = input_vocab_size
    return ModelConfig(
        num_layers=FLAGS.num_layers,
        d_model=FLAGS.d_model,
        num_heads=FLAGS.num_heads,
        num_kv_heads=FLAGS.num_kv_heads,
        dff=FLAGS.dff,
        input_vocab_size=input_vocab_size,
        target_vocab_size=target_vocab_size,
        dropout_rate=FLAGS.dropout_rate,
        max_position=max(FLAGS.sequence_length, 64),
        norm_scheme=FLAGS.norm_scheme,
        position_scheme=FLAGS.position_scheme,
        decoder_only=FLAGS.decoder_only,
        encoder_only=FLAGS.objective == "mlm",
        tie_embeddings=FLAGS.tie_embeddings,
        tie_output=FLAGS.tie_output,
        ffn_activation=FLAGS.ffn_activation,
        dtype=FLAGS.dtype,
        attention_impl=FLAGS.attention_impl,
        attention_window=FLAGS.attention_window,
        remat=FLAGS.remat,
        remat_policy=FLAGS.remat_policy,
        moe_experts=FLAGS.moe_experts,
        moe_top_k=FLAGS.moe_top_k,
        moe_capacity_factor=FLAGS.moe_capacity_factor,
        moe_every=FLAGS.moe_every,
        moe_aux_weight=FLAGS.moe_aux_weight,
    )


def flags_to_train_config() -> TrainConfig:
    apply_preset()
    return TrainConfig(
        batch_size=FLAGS.batch_size,
        sequence_length=FLAGS.sequence_length,
        epochs=FLAGS.epochs,
        warmup_steps=FLAGS.warmup_steps,
        lr_schedule=FLAGS.lr_schedule,
        peak_lr=FLAGS.peak_lr,
        lr_decay_steps=FLAGS.lr_decay_steps,
        label_smoothing=FLAGS.label_smoothing,
        loss_normalization=FLAGS.loss_normalization,
        max_grad_norm=FLAGS.max_grad_norm,
        optimizer=FLAGS.optimizer,
        weight_decay=FLAGS.weight_decay,
        buffer_size=FLAGS.buffer_size,
        max_ckpt_keep=FLAGS.max_ckpt_keep,
        ckpt_path=FLAGS.ckpt_path,
        enable_function=FLAGS.enable_function,
        seed=FLAGS.seed,
        pp_microbatches=FLAGS.pp_microbatches,
        pp_schedule=FLAGS.pp_schedule,
        eval_max_batches=FLAGS.eval_max_batches,
        early_stop_patience=FLAGS.early_stop_patience,
        grad_accum_steps=FLAGS.grad_accum,
        loss_chunks=FLAGS.loss_chunks,
        steps_per_dispatch=FLAGS.steps_per_dispatch,
        objective=FLAGS.objective,
        mlm_mask_rate=FLAGS.mlm_mask_rate,
    )


def flags_to_profiler():
    """Profiler from --profile_* flags, or None when profiling is off."""
    if not FLAGS.profile_dir:
        return None
    from transformer_tpu.utils.profiling import Profiler

    return Profiler(
        FLAGS.profile_dir,
        start_step=FLAGS.profile_start_step,
        num_steps=FLAGS.profile_num_steps,
    )


def flags_to_telemetry():
    """obs.Telemetry from --metrics_* flags, or None when telemetry is off
    (--metrics_jsonl unset and --metrics_port 0 — the zero-overhead
    default). Owns the whole --metrics_* interpretation, including starting
    the /metrics scrape endpoint, so every CLI wires telemetry identically.
    The jax-free obs import keeps flag materialization safe to run before
    platform setup, like the rest of this module."""
    if not FLAGS.metrics_jsonl and not FLAGS.metrics_port:
        return None
    from absl import logging

    from transformer_tpu.obs import EventLog, Telemetry
    from transformer_tpu.obs.breaker import CircuitBreaker
    from transformer_tpu.obs.trace import BUFFER_CAPACITY

    events = None
    if FLAGS.metrics_jsonl:
        # Sink circuit breaker (docs/ROBUSTNESS.md): a transiently full
        # disk costs an outage window with a half-open re-probe every 30s,
        # not the rest of the process's telemetry. Direct EventLog
        # construction (no breaker) keeps the historical
        # first-failure-disables contract.
        events = EventLog(
            FLAGS.metrics_jsonl,
            breaker=CircuitBreaker("event_sink", threshold=3, cooldown_s=30.0),
        )
    if FLAGS.trace and events is None:
        # Spans are recorded either way (obs.trace.buffer()); without an
        # event sink there is nowhere to write them — tell the operator.
        logging.warning(
            "--trace without --metrics_jsonl: spans are kept in memory only "
            "(the last %d, obs.trace.buffer()); no trace.span event is "
            "written", BUFFER_CAPACITY,
        )
    telemetry = Telemetry(
        events=events,
        prom_path=f"{FLAGS.metrics_jsonl}.prom" if FLAGS.metrics_jsonl else None,
        interval=FLAGS.metrics_interval,
        trace=FLAGS.trace and events is not None,
    )
    if FLAGS.flight_recorder and FLAGS.metrics_jsonl:
        from transformer_tpu.obs.flight import flight_path_for

        recorder = telemetry.arm_flight(
            flight_path_for(FLAGS.metrics_jsonl), autodump_s=2.0
        )
        recorder.install_signal_handlers()
    if FLAGS.metrics_port:
        port = telemetry.start_prometheus_server(FLAGS.metrics_port)
        logging.info("Prometheus /metrics (+ /healthz) on port %d", port)
    return telemetry


def flags_to_mesh_config(n_devices: int) -> MeshConfig:
    non_dp = FLAGS.fsdp * FLAGS.tp * FLAGS.sp * FLAGS.pp * FLAGS.ep
    dp = FLAGS.dp or max(1, n_devices // non_dp)
    return MeshConfig(
        data=dp, fsdp=FLAGS.fsdp, model=FLAGS.tp, seq=FLAGS.sp, pipe=FLAGS.pp,
        expert=FLAGS.ep, dcn_data=FLAGS.dcn_data,
    )


def maybe_force_platform() -> None:
    """``--platform`` override, plus the persistent compilation cache
    (every CLI process re-pays full XLA compiles otherwise; see
    ``utils.enable_compilation_cache`` for where it lives)."""
    if FLAGS.platform:
        import jax

        jax.config.update("jax_platforms", FLAGS.platform)
    from transformer_tpu.utils.profiling import enable_compilation_cache

    enable_compilation_cache()
