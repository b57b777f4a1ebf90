"""Distributed training entry point.

Counterpart of the reference's ``python distributed_train.py --num_gpu=N``
(``distributed_train.py:124-179``), rebuilt for TPU: instead of
MirroredStrategy over a GPU list, a ``Mesh`` over all visible devices with
axes sized by ``--dp/--fsdp/--tp/--sp``. Run:

    python -m transformer_tpu.cli.distributed_train --dataset_path=data \
        --dp=0 --fsdp=1 --tp=1      # dp=0: all devices data-parallel

Multi-host (pod slices) works through the same entry point: each process
feeds its shard of every global batch (``Seq2SeqDataset.shard_index``) and
host 0 writes checkpoints/logs.
"""

from __future__ import annotations

import os

from absl import app, flags, logging

from transformer_tpu.cli.flags import (
    define_flags,
    flags_to_mesh_config,
    flags_to_model_config,
    flags_to_train_config,
    maybe_force_platform,
)

FLAGS = flags.FLAGS


def _reject_cpu_virtual_bf16(jax, dtype: str) -> None:
    """Refuse the one combination known to abort inside XLA, loudly.

    XLA:CPU's collective rendezvous aborts the whole process (not a Python
    exception) when a single-process, multi-virtual-device mesh runs the
    full fit machinery in bfloat16 (bisected in round 4; fp32 and the
    pytest/dryrun shard_map paths are unaffected). The
    reference's precedent is its batch-divisibility ``ValueError``
    (``distributed_train.py:154-158``): fail with a message, never abort.
    ``TRANSFORMER_TPU_ALLOW_CPU_BF16=1`` re-enables the path for probing
    whether a newer XLA fixed it.
    """
    if os.environ.get("TRANSFORMER_TPU_ALLOW_CPU_BF16") == "1":
        return
    if (
        dtype == "bfloat16"
        and jax.default_backend() == "cpu"
        and jax.process_count() == 1
        and len(jax.devices()) > 1
    ):
        raise app.UsageError(
            "dtype=bfloat16 on a single-process multi-device CPU mesh "
            f"({len(jax.devices())} virtual devices) aborts in XLA:CPU's "
            "collective rendezvous (known XLA:CPU bug). "
            "Pass --dtype=float32 for CPU runs, or set "
            "TRANSFORMER_TPU_ALLOW_CPU_BF16=1 to try anyway."
        )


def main(argv) -> None:
    del argv
    from transformer_tpu.cli.flags import apply_preset

    apply_preset()  # before ANY direct FLAGS read (e.g. decoder_only)
    maybe_force_platform()
    import jax

    from transformer_tpu.data import load_dataset
    from transformer_tpu.parallel import DistributedTrainer, make_mesh
    from transformer_tpu.parallel.mesh import initialize_distributed
    from transformer_tpu.train import AsyncCheckpointManager, CheckpointManager
    from transformer_tpu.train.checkpoint import export_params
    from transformer_tpu.train.decode import translate

    initialize_distributed()
    _reject_cpu_virtual_bf16(jax, FLAGS.dtype)
    mesh_cfg = flags_to_mesh_config(len(jax.devices()))
    mesh = make_mesh(mesh_cfg)
    logging.info(
        "mesh: %s over %d devices (%d processes)",
        dict(zip(mesh.axis_names, mesh.devices.shape)),
        len(jax.devices()), jax.process_count(),
    )

    train_cfg = flags_to_train_config()
    buckets = tuple(
        int(x) for x in FLAGS.length_buckets.split(",") if x.strip()
    )
    # Same LM-window predicate as cli.train: shared data path and
    # perplexity (not translate/BLEU) epilogue.
    lm_mode = FLAGS.decoder_only or FLAGS.objective == "mlm"
    if lm_mode:
        if buckets:
            raise app.UsageError(
                "--length_buckets applies to the seq2seq pipeline only; LM "
                "windows are already fixed-width (drop the flag with "
                "--decoder_only / --objective=mlm)"
            )
        from transformer_tpu.data.pipeline import load_lm_splits

        train_ds, test_ds, tok = load_lm_splits(
            FLAGS.dataset_path,
            FLAGS.tgt_vocab_file,
            batch_size=train_cfg.batch_size,
            sequence_length=train_cfg.sequence_length,
            target_vocab_size=FLAGS.target_vocab_size,
            seed=train_cfg.seed,
            shard_index=jax.process_index(),
            shard_count=jax.process_count(),
        )
        src_tok = tgt_tok = tok
    else:
        train_ds, test_ds, src_tok, tgt_tok = load_dataset(
            FLAGS.dataset_path,
            FLAGS.src_vocab_file,
            FLAGS.tgt_vocab_file,
            batch_size=train_cfg.batch_size,
            sequence_length=train_cfg.sequence_length,
            target_vocab_size=FLAGS.target_vocab_size,
            seed=train_cfg.seed,
            shard_index=jax.process_index(),
            shard_count=jax.process_count(),
            prefetch=FLAGS.native_loader,  # composes with length_buckets (native bucketed plan)
            length_buckets=buckets,
        )
    model_cfg = flags_to_model_config(
        src_tok.model_vocab_size, tgt_tok.model_vocab_size
    )
    ckpt_cls = AsyncCheckpointManager if FLAGS.async_checkpoint else CheckpointManager
    ckpt = ckpt_cls(train_cfg.ckpt_path, train_cfg.max_ckpt_keep)
    import datetime

    stamp = datetime.datetime.now().strftime("%Y%m%d-%H%M%S")
    from transformer_tpu.cli.flags import flags_to_profiler, flags_to_telemetry

    # Host 0 owns telemetry, like logs/checkpoints: per-host event files
    # would interleave badly and the metrics are already globally reduced.
    telemetry = flags_to_telemetry() if jax.process_index() == 0 else None
    trainer = DistributedTrainer(
        model_cfg, train_cfg, mesh,
        log_dir=os.path.join(FLAGS.tb_log_dir, stamp)
        if jax.process_index() == 0
        else None,
        checkpoint=ckpt,
        log_fn=logging.info,
        profiler=flags_to_profiler() if jax.process_index() == 0 else None,
        telemetry=telemetry,
    )
    if FLAGS.consistency_check:
        from transformer_tpu.utils.consistency import (
            assert_cross_process_consistent,
        )

        def check_consistency(epoch, tr):
            assert_cross_process_consistent(
                tr.state.params, label=f"params after epoch {epoch + 1}"
            )

        trainer.fit(train_ds, test_ds, epoch_callback=check_consistency)
        assert_cross_process_consistent(trainer.state.params, label="final params")
    else:
        trainer.fit(train_ds, test_ds)

    # Multi-host: params are sharded across processes, but the epilogue
    # (sample decode, export, BLEU) runs on host 0 alone — device_get/jit on
    # arrays with non-addressable shards would fail or deadlock. Gather to
    # host-local numpy on EVERY process (allgather is a collective), then
    # let host 0 proceed.
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        host_params = multihost_utils.process_allgather(trainer.state.params)
    else:
        host_params = trainer.state.params

    if jax.process_index() == 0:
        if lm_mode:
            # LM quality metric: perplexity from fit()'s final-epoch full
            # eval (MLM: pseudo-perplexity over the deterministically-masked
            # eval positions) — the same epilogue cli.train prints.
            if test_ds is not None and trainer.eval_metrics.weight > 0:
                import math

                logging.info(
                    "eval loss %.4f, perplexity %.2f",
                    trainer.eval_metrics.loss,
                    math.exp(min(trainer.eval_metrics.loss, 30.0)),
                )
            elif test_ds is not None:
                logging.warning("eval split produced no tokens; no perplexity")
        else:
            sample = ["he goes to school"]
            out = translate(
                host_params, model_cfg, src_tok, tgt_tok, sample,
                max_len=train_cfg.sequence_length,
            )
            logging.info("sample translation %r -> %r", sample[0], out[0])
        export_params(host_params, model_cfg, "model")
        logging.info("exported params to ./model")

        # End-of-run BLEU on the test split (same epilogue as cli.train so
        # both entry points report the north-star metric).
        if FLAGS.eval_bleu and not lm_mode:
            from transformer_tpu.train.evaluate import bleu_on_test_files

            bleu_on_test_files(
                host_params, model_cfg, src_tok, tgt_tok,
                FLAGS.dataset_path,
                batch_size=train_cfg.batch_size,
                max_len=train_cfg.sequence_length,
                limit=FLAGS.bleu_limit,
                log_fn=logging.info,
            )
    if telemetry is not None:
        telemetry.close()


def run() -> None:
    define_flags()
    app.run(main)


if __name__ == "__main__":
    run()
