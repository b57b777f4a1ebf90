"""Convergence run: train on the bundled corpus and publish corpus BLEU.

The BASELINE.json north star is "eval BLEU on src/tgt" — this script is the
committed reproduction command behind the BLEU number in BASELINE.md:

    python benchmarks/bleu_run.py [--config base|small|tiny] [--epochs N]

Trains on data/src-train.txt → tgt-train.txt (10k pairs, the corpus the
reference bundles), greedy-decodes the bundled 500-pair test split, and
prints one JSON line: {"metric": "...", "bleu": ..., "epochs": ..., ...}.

Notes on the setup (documented so the number is interpretable):
- warmup defaults to 2000, not the reference's 60000 (``train.py:22``): on a
  10k-pair corpus an epoch is ~150 steps, so a 60k-step warmup would keep the
  LR near zero for the entire run.
- the test split is drawn from the tail of the training corpus
  (data/README.md) because the reference ships no test files. By default the
  run HOLDS THOSE PAIRS OUT of training (``--holdout 1`` →
  ``load_dataset(exclude_test_overlap=True)``) so the reported BLEU is
  genuinely out-of-sample; ``--holdout 0`` reproduces the in-sample behavior.
- the run is RESUMABLE: it restores from its own workdir checkpoints, and
  ``--epoch_budget N`` trains at most N epochs per invocation, printing a
  progress JSON line (no "bleu" key) until the target epoch count is reached
  — a caller invokes it repeatedly, so interrupted runs accumulate progress
  instead of restarting a 40-epoch run from scratch.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One shapes table for every consumer (score_ckpt.py imports it): drift
# between the trainer's architecture and a scorer's would restore cleanly
# into the wrong model whenever param shapes happen to match (num_heads).
CONFIG_SHAPES = {
    "tiny": dict(num_layers=2, d_model=128, num_heads=4, dff=512),
    "small": dict(num_layers=2, d_model=256, num_heads=8, dff=1024),
    "medium": dict(num_layers=4, d_model=256, num_heads=8, dff=1024),
    "base": dict(num_layers=6, d_model=512, num_heads=8, dff=2048),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--config", default="base", choices=["tiny", "small", "medium", "base"],
        help="tiny/small/medium are CPU-fallback scales (medium = 4L/256, "
        "the next capacity step of the capacity+smoothing recipe the r3 2x2 "
        "showed compounds); base is the headline Transformer-base run",
    )
    ap.add_argument("--epochs", type=int, default=40)
    ap.add_argument("--warmup", type=int, default=2000)
    ap.add_argument("--seq_len", type=int, default=50)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=2**15)
    ap.add_argument("--bleu_max_len", type=int, default=64)
    ap.add_argument(
        "--holdout", type=int, default=1,
        help="1 (default): exclude the test pairs from training so BLEU is "
        "out-of-sample; 0: train on the full corpus (in-sample BLEU)",
    )
    ap.add_argument(
        "--epoch_budget", type=int, default=0,
        help="train at most this many epochs THIS invocation, then print a "
        "progress line and exit (0 = train to --epochs in one go); the run "
        "resumes from its checkpoints either way",
    )
    ap.add_argument(
        "--dtype", default="bfloat16", choices=["bfloat16", "float32"],
        help="compute dtype (float32 is much faster on the CPU fallback "
        "path, where bf16 matmuls are emulated)",
    )
    ap.add_argument(
        "--label_smoothing", type=float, default=0.0,
        help="label smoothing for the convergence run. Default 0 keeps the "
        "published CPU-fallback numbers reproducible by their committed "
        "commands; pass 0.1 (the standard NMT setting, Vaswani et al.) "
        "for the base run.",
    )
    ap.add_argument(
        "--native_loader", type=int, default=1,
        help="1 (default): assemble batches in the C++ prefetching loader "
        "(composes with the length buckets), overlapping host batch "
        "assembly with device steps; 0: Python batcher",
    )
    ap.add_argument(
        "--bleu_every", type=int, default=0,
        help="also score a 64-pair BLEU probe every N epochs during "
        "training (0 = end-of-run only)",
    )
    ap.add_argument(
        "--stop_patience", type=int, default=0,
        help="with --bleu_every: stop after this many consecutive probes "
        "without a new best BLEU, keep the best probe's params as the "
        "scored model (0 = train the full --epochs budget; best-params "
        "tracking still runs). The bundled-corpus ladder showed BLEU "
        "peaking then DROPPING (small+smoothing: 2.34 at epoch 60 -> 2.08 "
        "at 70), so a fixed budget can overshoot into memorization.",
    )
    ap.add_argument(
        "--workdir", default="",
        help="vocab/checkpoint directory; default derives from the run "
        "parameters so different corpora/configs never share stale vocabs "
        "or restore each other's checkpoints",
    )
    ap.add_argument(
        "--data_dir", default=os.path.join(REPO, "data"),
        help="corpus directory (override for smoke tests on subsets)",
    )
    args = ap.parse_args()
    if not args.workdir:
        import hashlib

        # Every training-relevant knob is in the key: a rerun with ANY
        # different parameter gets a fresh dir, so restore-before-train can
        # only ever resume an identical interrupted run — never silently
        # continue a different one and misreport "epochs".
        key = hashlib.md5(
            f"{os.path.abspath(args.data_dir)}|{args.config}|{args.vocab}|"
            f"{args.seq_len}|{args.epochs}|{args.warmup}|{args.batch}|"
            f"h{args.holdout}|{args.dtype}|ls{args.label_smoothing}".encode()
        ).hexdigest()[:10]
        # Repo-local, NOT /tmp: the round-4 run lost 16 banked epochs when
        # /tmp was wiped between rounds. .bleu_runs/ is gitignored (the
        # base-config state is ~1.1 GB) but survives on the repo volume.
        args.workdir = os.path.join(REPO, ".bleu_runs", f"bleu_run_{key}")
    # Fail before training, not after: the scoring split must exist.
    for name in ("src-test.txt", "tgt-test.txt"):
        path = os.path.join(args.data_dir, name)
        if not os.path.exists(path):
            raise SystemExit(
                f"missing {path}: the BLEU run needs a test split "
                "(data/README.md describes the bundled one)"
            )
    # Persist the run parameters next to the checkpoints: scorers
    # (benchmarks/score_ckpt.py) read holdout/config from here instead of
    # trusting their own flags, so an in-sample run can never be mislabeled
    # "held out" in the evidence JSONL by a default argument.
    os.makedirs(args.workdir, exist_ok=True)
    with open(os.path.join(args.workdir, "args.json"), "w") as f:
        json.dump(vars(args), f, indent=1)

    import jax

    from transformer_tpu.config import ModelConfig, TrainConfig
    from transformer_tpu.data import load_dataset
    from transformer_tpu.train import (
        AsyncCheckpointManager,
        Trainer,
        create_train_state,
        export_params,
        load_exported_params,
    )
    from transformer_tpu.train.evaluate import bleu_on_pairs, read_lines
    from transformer_tpu.train.probe_stop import ProbeKeepBest
    from transformer_tpu.utils import enable_compilation_cache

    # Each invocation is a fresh process: without a persistent cache it
    # re-pays the base-model compile before training a single step.
    enable_compilation_cache()
    dev = jax.devices()[0]
    print(f"training on {dev.platform}:{dev.device_kind}", file=sys.stderr)

    # Length buckets: most bundled-corpus sentences are far shorter than 50
    # tokens; three widths cut padding FLOPs roughly in half at the cost of
    # three compiles.
    buckets = (24, 36, args.seq_len) if args.seq_len >= 48 else ()
    train_ds, test_ds, src_tok, tgt_tok = load_dataset(
        args.data_dir,
        os.path.join(args.workdir, "src_vocab.subwords"),
        os.path.join(args.workdir, "tgt_vocab.subwords"),
        batch_size=args.batch,
        sequence_length=args.seq_len,
        target_vocab_size=args.vocab,
        seed=0,
        length_buckets=buckets,
        exclude_test_overlap=bool(args.holdout),
        prefetch=bool(args.native_loader),
    )
    if args.holdout:
        print(
            f"holdout: training on {train_ds.num_examples} pairs "
            "(test pairs excluded)",
            file=sys.stderr,
        )
    if len(train_ds) == 0:
        # batch_size > surviving examples (the length filter drops pairs
        # longer than --seq_len after tokenization): every epoch would be
        # zero steps and the run would "finish" untrained.
        raise SystemExit(
            f"no full batches: {train_ds.num_examples} examples survive the "
            f"seq_len={args.seq_len} length filter but batch_size="
            f"{args.batch} (drop_remainder) needs at least one full batch"
        )
    shapes = CONFIG_SHAPES[args.config]
    model_cfg = ModelConfig(
        **shapes,
        input_vocab_size=src_tok.model_vocab_size,
        target_vocab_size=tgt_tok.model_vocab_size,
        max_position=max(args.seq_len, args.bleu_max_len, 64),
        dropout_rate=0.1,
        dtype=args.dtype,
    )
    # Peek at the latest checkpoint STEP (metadata only — Trainer.fit does
    # the actual restore) to learn how far a previous invocation got, so
    # --epoch_budget can cap THIS invocation's work while the target epoch
    # count stays the contract for when BLEU is finally scored.
    # Async: the npz write happens off the training thread, so each save
    # costs only the device->host snapshot of the ~1.1 GB base-config
    # state.
    ckpt = AsyncCheckpointManager(os.path.join(args.workdir, "ckpt"), 2)
    steps_per_epoch = max(len(train_ds), 1)
    done_epochs = min((ckpt.latest_step or 0) // steps_per_epoch, args.epochs)
    target_epochs = (
        min(args.epochs, done_epochs + args.epoch_budget)
        if args.epoch_budget
        else args.epochs
    )
    # Keep-best / stop accounting is persisted in the workdir, so the
    # decision survives the repeated-invocation pattern: a stop decided two
    # invocations ago still skips training now and goes straight to
    # scoring the best snapshot.
    stopper = ProbeKeepBest(
        os.path.join(args.workdir, "probe_bleu.json"),
        patience=args.stop_patience,
    )
    best_dir = os.path.join(args.workdir, "best")
    # The rule only acts when THIS invocation enables it: probes need
    # --bleu_every, stopping needs --stop_patience. A rerun with the rule
    # disabled (the flags are outside the workdir hash) must train the full
    # budget, not silently honor a marker from a differently-flagged run.
    probing = args.bleu_every > 0
    stopping = probing and args.stop_patience > 0
    if stopping and stopper.stopped_epoch is not None:
        print(
            f"probe-stop marker present (stopped after epoch "
            f"{stopper.stopped_epoch}, best {stopper.best_value} at epoch "
            f"{stopper.best_epoch}); skipping training",
            file=sys.stderr,
        )
        target_epochs = done_epochs
    elif done_epochs:
        print(
            f"resuming: {done_epochs}/{args.epochs} epochs done, training to "
            f"{target_epochs} this invocation",
            file=sys.stderr,
        )
    train_cfg = TrainConfig(
        batch_size=args.batch,
        sequence_length=args.seq_len,
        epochs=target_epochs,
        warmup_steps=args.warmup,
        ckpt_path=os.path.join(args.workdir, "ckpt"),
        eval_every_steps=0,  # end-of-epoch metrics only; BLEU at the end
        # Every SECOND epoch is a resume point (earlier round, earlier
        # backend: a save took minutes, so saving every epoch doubled the
        # run's wall clock; not re-measured on the attached chip).
        # Pass boundaries (epoch_budget multiples) still always save.
        checkpoint_every_epochs=2,
        label_smoothing=args.label_smoothing,
    )
    state = create_train_state(jax.random.PRNGKey(0), model_cfg, train_cfg)
    trainer = Trainer(
        model_cfg, train_cfg, state,
        checkpoint=ckpt,
        log_fn=lambda msg: print(msg, file=sys.stderr),
    )
    src_lines = read_lines(os.path.join(args.data_dir, "src-test.txt"))
    ref_lines = read_lines(os.path.join(args.data_dir, "tgt-test.txt"))

    callback = None
    probe_s = [0.0]  # probe decode time (incl. its compile) is NOT training
    if args.bleu_every:
        def callback(epoch, tr):
            if (epoch + 1) % args.bleu_every:
                return False
            t = time.perf_counter()
            probe, _ = bleu_on_pairs(
                tr.state.params, model_cfg, src_tok, tgt_tok,
                src_lines[:64], ref_lines[:64],
                batch_size=args.batch, max_len=args.bleu_max_len,
            )
            # Export BEFORE recording the new best, and atomically (tmp dir
            # + per-file os.replace): a process death mid-export must never
            # leave probe_bleu.json claiming best@N while best/ holds the
            # previous peak's params or a truncated npz. Crash before the
            # record: this probe is simply re-run next invocation.
            if stopper.would_be_best(probe):
                # Snapshot ONLY the params (export format, ~1/3 the size of
                # a full train-state checkpoint): the rotating keep-2
                # checkpoint window will have discarded this epoch by the
                # time a later probe proves it was the peak.
                tmp_dir = best_dir + ".tmp"
                export_params(tr.state.params, model_cfg, tmp_dir)
                os.makedirs(best_dir, exist_ok=True)
                for name in ("params.npz", "config.json"):
                    os.replace(
                        os.path.join(tmp_dir, name),
                        os.path.join(best_dir, name),
                    )
                os.rmdir(tmp_dir)
            decision = stopper.update(epoch + 1, probe)
            probe_s[0] += time.perf_counter() - t
            print(
                f"epoch {epoch + 1}: probe BLEU {probe:.2f} [{decision}; "
                f"best {stopper.best_value:.2f} @ {stopper.best_epoch}]",
                file=sys.stderr,
            )
            return decision == "stop"

    t0 = time.perf_counter()
    try:
        trainer.fit(train_ds, test_ds, epoch_callback=callback)
    finally:
        # fit's own epilogue waits on async saves, but only if it is
        # reached: a raise mid-epoch (backend failure) must not lose an
        # in-flight background checkpoint write on top of it.
        ckpt.wait()
    train_s = time.perf_counter() - t0 - probe_s[0]
    stopped = stopping and stopper.stopped_epoch is not None
    if not stopped and target_epochs < args.epochs:
        # Budget-limited invocation: report progress (NO "bleu" key — the
        # caller keeps re-invoking until the final line lands) and stop.
        progress = {
            "metric": f"{args.config} BLEU run progress",
            "epochs_done": target_epochs,
            "epochs_target": args.epochs,
            "train_seconds": round(train_s, 1),
            "device": f"{dev.platform}:{dev.device_kind}",
        }
        if stopper.best_epoch is not None:
            progress["probe_best"] = stopper.best_value
            progress["probe_best_epoch"] = stopper.best_epoch
        print(json.dumps(progress), flush=True)
        return
    # Final scoring: the run either trained its full budget or the probe
    # rule stopped it. Score the BEST probe's params when a snapshot
    # exists — the ladder's peak-then-drop curves are exactly the case
    # where final != best.
    early_stopped = stopped
    epochs_trained = (
        min(stopper.stopped_epoch, args.epochs) if early_stopped
        else args.epochs
    )
    score_params = trainer.state.params
    scored = "final"
    if probing and stopper.best_epoch is not None and os.path.isdir(best_dir):
        score_params = load_exported_params(best_dir, trainer.state.params)
        scored = f"best@{stopper.best_epoch}"
    t1 = time.perf_counter()
    bleu, hyps = bleu_on_pairs(
        score_params, model_cfg, src_tok, tgt_tok,
        src_lines, ref_lines,
        batch_size=args.batch, max_len=args.bleu_max_len,
        log_fn=lambda msg: print(msg, file=sys.stderr),
    )
    eval_s = time.perf_counter() - t1
    for src, hyp, ref in list(zip(src_lines, hyps, ref_lines))[:3]:
        print(f"SRC {src}\nHYP {hyp}\nREF {ref}\n", file=sys.stderr)
    row = {
        "metric": (
            f"{args.config} corpus BLEU (bundled test split, greedy, "
            + ("held out" if args.holdout else "in-sample")
            + ")"
        ),
        "bleu": round(bleu, 2),
        "n_pairs": len(src_lines),
        "epochs": epochs_trained,
        "epochs_budget": args.epochs,
        "scored": scored,
        "vocab": args.vocab,
        "dtype": args.dtype,
        "label_smoothing": args.label_smoothing,
        "holdout": bool(args.holdout),
        "train_seconds": round(train_s, 1),
        "eval_seconds": round(eval_s, 1),
        "device": f"{dev.platform}:{dev.device_kind}",
    }
    if early_stopped:
        row["early_stopped"] = True
        row["probe_best"] = stopper.best_value
        row["probe_best_epoch"] = stopper.best_epoch
    print(json.dumps(row), flush=True)

    # The greedy headline is committed above; now rescore the SAME model
    # with the two quality levers validated at tiny scale (BASELINE.md):
    # beam-4 and checkpoint averaging. Extra JSON lines, best-effort — a
    # decode failure here must not cost the recorded headline.
    def _rescore(tag: str, p, beam: int) -> None:
        try:
            t = time.perf_counter()
            b, _ = bleu_on_pairs(
                p, model_cfg, src_tok, tgt_tok, src_lines, ref_lines,
                batch_size=args.batch, max_len=args.bleu_max_len,
                beam_size=beam,
            )
            print(
                json.dumps(
                    {
                        "metric": f"{args.config} corpus BLEU [{tag}]",
                        "bleu": round(b, 2),
                        "n_pairs": len(src_lines),
                        "holdout": bool(args.holdout),
                        "eval_seconds": round(time.perf_counter() - t, 1),
                    }
                ),
                flush=True,
            )
        except Exception as e:  # noqa: BLE001
            print(f"rescore [{tag}] failed: {e!r}", file=sys.stderr)

    _rescore("beam4", score_params, beam=4)
    steps = ckpt.all_steps()[-2:]
    if len(steps) > 1:
        from transformer_tpu.train.checkpoint import average_checkpoints

        # trainer.state is the live template (the init-time `state` buffers
        # were donated into the jitted step).
        avg = average_checkpoints(ckpt, trainer.state, steps)
        _rescore(f"avg{len(steps)}+greedy", avg, beam=1)
        _rescore(f"avg{len(steps)}+beam4", avg, beam=4)


if __name__ == "__main__":
    main()
