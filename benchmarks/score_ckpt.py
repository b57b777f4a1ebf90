"""Score ANY bleu_run checkpoint (including an in-flight run's latest) on
the held-out test split, without touching the training process.

    python benchmarks/score_ckpt.py --workdir .bleu_runs/bleu_run_<hash> \
        --config small [--dtype float32] [--step N] [--beam 4]

Prints one JSON line: {"metric": ..., "bleu": ..., "step": ..., ...}.
Exists because resumable runs only self-score at their final epoch target
(``bleu_run.py``): when an interruption or round boundary lands mid-run, the
partial convergence is still checkpointed — this recovers a real number
from it. Reconstructs the model EXACTLY as bleu_run does (same shapes
table, the run's own workdir vocabs, same specials).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workdir", required=True, help="the bleu_run workdir")
    ap.add_argument(
        "--config", default=None,
        choices=["tiny", "small", "medium", "base"],
        help="default: read from the run's own args.json (falls back to "
        "'small' for pre-args.json workdirs) — the scorer must rebuild the "
        "run's architecture, not its own default's",
    )
    ap.add_argument("--dtype", default=None)
    ap.add_argument("--step", type=int, default=0, help="0 = latest")
    ap.add_argument("--beam", type=int, default=1)
    ap.add_argument("--seq_len", type=int, default=None,
                    help="the run's --seq_len (sizes the positional table); "
                    "default: from the run's args.json")
    ap.add_argument("--holdout", type=int, default=-1,
                    help="-1 (default): read the run's own --holdout from "
                    "the args.json bleu_run persists in its workdir (emits "
                    "null if the run predates that file) — the label is "
                    "derived from the run, not from this scorer's flags, so "
                    "an in-sample run can't be mislabeled held-out by a "
                    "default; 0/1 override explicitly")
    ap.add_argument("--best", action="store_true",
                    help="score the run's keep-best params snapshot "
                    "(workdir/best, written by --stop_patience/--bleu_every "
                    "probes) instead of a checkpoint step")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--bleu_max_len", type=int, default=None)
    ap.add_argument("--data_dir", default=os.path.join(REPO, "data"))
    args = ap.parse_args()

    # Model-shaping parameters default to the RUN'S OWN (args.json, written
    # by bleu_run next to the vocabs): a scorer default that disagrees with
    # the run would restore garbage (wrong architecture) or mis-size the
    # positional table. Explicit flags still override; pre-args.json
    # workdirs fall back to the historical defaults.
    run_args = {}
    run_args_path = os.path.join(args.workdir, "args.json")
    if os.path.exists(run_args_path):
        with open(run_args_path) as f:
            run_args = json.load(f)
    for name, fallback in (
        ("config", "small"), ("dtype", "float32"), ("seq_len", 50),
        ("batch", 64), ("bleu_max_len", 64),
    ):
        if getattr(args, name) is None:
            setattr(args, name, run_args.get(name, fallback))

    import jax

    from transformer_tpu.config import ModelConfig, TrainConfig
    from transformer_tpu.data.tokenizer import SubwordTokenizer
    from transformer_tpu.train import CheckpointManager, create_train_state
    from transformer_tpu.train.evaluate import bleu_on_pairs, read_lines
    from transformer_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    src_tok = SubwordTokenizer.load(os.path.join(args.workdir, "src_vocab.subwords"))
    tgt_tok = SubwordTokenizer.load(os.path.join(args.workdir, "tgt_vocab.subwords"))
    from bleu_run import CONFIG_SHAPES  # benchmarks/ sibling: one table

    shapes = CONFIG_SHAPES[args.config]
    model_cfg = ModelConfig(
        **shapes,
        input_vocab_size=src_tok.model_vocab_size,
        target_vocab_size=tgt_tok.model_vocab_size,
        max_position=max(args.seq_len, args.bleu_max_len, 64),
        dropout_rate=0.1,
        dtype=args.dtype,
    )
    state = create_train_state(
        jax.random.PRNGKey(0), model_cfg,
        TrainConfig(batch_size=args.batch, sequence_length=args.seq_len, warmup_steps=2000),
    )
    # The holdout label comes from the run itself (args.json, persisted by
    # bleu_run next to the vocabs) unless explicitly overridden: a scorer
    # flag default must not be able to label an in-sample run "held out".
    holdout: bool | None = bool(args.holdout) if args.holdout >= 0 else None
    if holdout is None and "holdout" in run_args:
        holdout = bool(run_args["holdout"])

    if args.best:
        from transformer_tpu.train import load_exported_params

        if args.step:
            raise SystemExit(
                "--best scores the keep-best snapshot (no checkpoint step); "
                "drop --step or drop --best"
            )
        best_dir = os.path.join(args.workdir, "best")
        if not os.path.isdir(best_dir):
            raise SystemExit(f"no keep-best snapshot at {best_dir}")
        params = load_exported_params(best_dir, state.params)
        probe_path = os.path.join(args.workdir, "probe_bleu.json")
        best_epoch = None
        if os.path.exists(probe_path):
            with open(probe_path) as f:
                best_epoch = json.load(f).get("best_epoch")
        which = (
            f"best snapshot (epoch {best_epoch})" if best_epoch
            else "best snapshot"
        )
        step = 0
    else:
        ckpt = CheckpointManager(os.path.join(args.workdir, "ckpt"), 2)
        step = args.step or ckpt.latest_step
        if not step:
            raise SystemExit(f"no checkpoints in {args.workdir}/ckpt")
        params = ckpt.restore(state, step).params
        which = f"ckpt step {step}"
    src_lines = read_lines(os.path.join(args.data_dir, "src-test.txt"))
    ref_lines = read_lines(os.path.join(args.data_dir, "tgt-test.txt"))
    t0 = time.perf_counter()
    bleu, _ = bleu_on_pairs(
        params, model_cfg, src_tok, tgt_tok, src_lines, ref_lines,
        batch_size=args.batch, max_len=args.bleu_max_len,
        beam_size=args.beam,
    )
    print(
        json.dumps(
            {
                "metric": f"{args.config} corpus BLEU [{which}"
                + (f", beam{args.beam}" if args.beam > 1 else ", greedy")
                + "]",
                "bleu": round(bleu, 2),
                "n_pairs": len(src_lines),
                "step": int(step),
                "holdout": holdout,
                "eval_seconds": round(time.perf_counter() - t0, 1),
                "device": f"{jax.devices()[0].platform}",
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
