"""Kind ``train_steps``: a training job through ``Trainer.fit``, input pipeline running.

The window is cut out of ONE call to ``fit`` by a thin iterable around the
dataset: it first hands the trainer two batches of every bucket width (the
trainer compiles each step shape on them), synchronises, then hands batches
for ``seconds`` seconds, synchronises again and ends the epoch, so ``fit``
returns by itself. Work counted: the non-pad target tokens of the batches
handed out inside the window; time: between the two synchronisations.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from perfbench import flops_bytes, traffic
from perfbench import program_api as api
import importlib

# Two comparisons with the plain float32 reference, on one seeded batch, dropout off.
#
# MATHEMATICS: the program's own forward and loss computed in float32 at the
# highest matmul precision against the reference. A fault of structure (a
# sublayer, a mask, a bias or a scale left out) moves some leaf's gradient by
# 0.7 of its norm or more, all leaves together by 0.18 or more and the loss by
# 7e-4 or more (perfbench/tests/test_references.py makes five such faults, at
# the rehearsal's size).
# What rounding does is far smaller but not tiny, because the FFN's ReLU makes
# the gradient discontinuous: a hidden unit whose pre-activation at one token
# is zero to rounding is on in one computation and off in the other. That
# moves one column of that layer's ``ffn.in`` kernel gradient by the token's
# whole contribution (95 to 100 % of the leaf's squared error in one column),
# and the token's gradient in every layer below it, which is large against the
# small query and key gradients there. Over 200 seeds on the chip (my chip run,
# PR 24, PERF.md section 6) the worst leaf read 4.4e-5 to 2.44e-3 of its norm
# (median 1.4e-4, 99 in 100 under 1.8e-3, one over 2e-3), all leaves together
# 2.6e-7 to 9.6e-4, the loss 1.7e-7 at most; 60 seeds on the CPU read alike
# (worst leaf up to 2.6e-3). The first version held every leaf to 2e-3 and the
# driver's check met a seed that failed. The limits stand ten times or more over
# the widest reading and well under the smallest fault. A key bias
# adds the same number to every score of a row, which the softmax cancels, so
# its gradient is zero but for rounding: such a leaf is held to a floor (1e-3
# of the mean leaf norm) and not to its own norm.
MATH_LEAF_REL_TOL = 3e-2
MATH_GLOBAL_REL_TOL = 1e-2
MATH_LOSS_REL_TOL = 1e-4
# PRECISION, as configured: the program in bfloat16 (relative rounding 2**-9 an
# operation) against the reference. Leaf by leaf this cannot be tight: through
# the softmax and LayerNorm Jacobians of 12 layers the rounding of a query or
# key kernel's gradient grows to a large part of its norm (0.22-0.89 of it on
# the chip, PR 24), which is bfloat16 training as users get it, not a fault.
# So the gate is the error over ALL leaves against the norm over all leaves
# (0.033-0.045 on the chip over 200 seeds, 0.036-0.051 on the CPU over 60 with
# a standard deviation of 0.003, PR 24) and the loss (9.4e-5 at most; limit 5e-3).
# Computing in a lower precision than the configuration states (8-bit floats
# round 32 times coarser) passes neither.
PRECISION_GLOBAL_REL_TOL = 0.08
LOSS_REL_TOL = 0.005


class WindowedBatches:
    """What ``Trainer.fit`` iterates: ``batches(epoch)`` of the dataset, with
    the warm-up, the two synchronisations and the counting around it."""

    def __init__(self, ds, trainer, widths, seconds, ctx, trace_for_s):
        self.ds, self.trainer, self.widths = ds, trainer, tuple(widths)
        self.seconds, self.ctx = seconds, ctx
        self.trace_for_s = trace_for_s
        self.t0 = self.t1 = None
        self.first_loss = None
        self.data_wait_s = 0.0
        self.steps = 0
        self.src_lens: list[np.ndarray] = []
        self.tgt_lens: list[np.ndarray] = []
        self.positions = 0
        self.passes = 0

    def __len__(self):
        return len(self.ds)

    def _passes(self, epoch: int):
        """The corpus again and again, reshuffled by the dataset each pass: the
        job's epochs, seen by the trainer as one, so that a window of any
        length finds data."""
        while True:
            self.passes += 1
            yield from self.ds.batches(epoch + self.passes - 1)

    def batches(self, epoch: int = 0):
        it = self._passes(epoch)
        # Warm-up: read on until every width has come twice; hand the trainer
        # the first two of each, keep the others for the window's start.
        seen = {w: 0 for w in self.widths}
        warm, held = [], []
        while min(seen.values()) < 2:
            batch = next(it)
            w = int(batch[1].shape[1])
            seen[w] += 1
            (warm if seen[w] <= 2 else held).append(batch)
        for i, batch in enumerate(warm):
            yield batch
            if i == 0:
                self.first_loss = api.trainer_last_loss(self.trainer)
        api.trainer_sync(self.trainer)
        self.ctx.mark("two steps of each width")
        self.t0 = self.ctx.window_opens()
        tracing = False
        held.reverse()
        while True:
            now = time.perf_counter()
            if now - self.t0 >= self.seconds:
                break
            # The traced slice is the end of the window: the profiler is
            # stopped (which takes seconds) after the window has closed.
            if self.ctx.trace and not tracing and now - self.t0 >= self.seconds - self.trace_for_s:
                api.trainer_sync(self.trainer)
                self.ctx.trace_start()
                tracing = True
            t = time.perf_counter()
            with self.ctx.span("data.next"):
                batch = held.pop() if held else next(it)
            self.data_wait_s += time.perf_counter() - t
            src, tgt = np.asarray(batch[0]), np.asarray(batch[1])
            self.src_lens.append((src != 0).sum(1))
            self.tgt_lens.append(np.maximum((tgt != 0).sum(1) - 1, 0))
            self.positions += int(tgt.shape[0] * (tgt.shape[1] - 1))
            self.steps += 1
            with self.ctx.span("trainer.step"):
                yield batch
        api.trainer_sync(self.trainer)
        self.t1 = time.perf_counter()
        self.ctx.window_closes()
        if tracing:
            self.ctx.trace_stop()


def _leaf_errors(grads, ref_leaves):
    """(worst error of a leaf over its norm, that leaf, error over all leaves
    over the norm over all leaves). A NaN is worse than any number."""
    floor = 1e-3 * float(np.mean([np.linalg.norm(r) for r in ref_leaves]))
    worst, worst_leaf, err2, ref2 = 0.0, "", 0.0, 0.0
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads), ref_leaves):
        err = float(np.linalg.norm(np.asarray(g, np.float32) - r))
        rel = err / max(float(np.linalg.norm(r)), floor)
        err2, ref2 = err2 + err * err, ref2 + float(np.sum(np.square(r, dtype=np.float64)))
        if not np.isfinite(rel) or rel > worst:
            worst, worst_leaf = rel, jax.tree_util.keystr(path)
    return worst, worst_leaf, float(np.sqrt(err2 / ref2))


def check_batch(seed: int, cell: dict, vocab: int):
    """The seeded batch the comparison runs on: BOS, ids, EOS, padding."""
    rng = np.random.default_rng([seed, 7])
    rows, width = cell["check"]["rows"], cell["check"]["width"]

    def side():
        ids = np.zeros((rows, width), np.int32)
        for r in range(rows):
            n = int(rng.integers(width // 2, width - 1))
            ids[r, 0], ids[r, n + 1] = vocab - 2, vocab - 1
            ids[r, 1 : n + 1] = rng.integers(1, vocab - 2, n)
        return ids

    return side(), side()


def check(ctx, config, cell, trainer) -> dict:
    src, tgt = check_batch(ctx.seed, cell, config["model"]["target_vocab_size"])
    params = api.trainer_params(trainer)
    ls = cell["train"]["label_smoothing"]
    reference = importlib.import_module(f"perfbench.reference.{config['family']}")
    ref = jax.jit(lambda p, s, t: reference.loss_and_grads(p, s, t, config["model"], ls))
    r_loss, r_grads = ref(params, src, tgt)
    r_loss = float(r_loss)
    r_leaves = [np.asarray(r, np.float32) for r in jax.tree_util.tree_leaves(r_grads)]
    m_loss, m_grads = api.program_loss_and_grads(params, src, tgt, config, ls, dtype="float32")
    m_worst, m_leaf, m_global = _leaf_errors(m_grads, r_leaves)
    del m_grads
    p_loss, p_grads = api.program_loss_and_grads(params, src, tgt, config, ls)
    p_worst, p_leaf, p_global = _leaf_errors(p_grads, r_leaves)
    loss_rel = abs(float(p_loss) - r_loss) / abs(r_loss)
    m_loss_rel = abs(float(m_loss) - r_loss) / abs(r_loss)
    # What is held: (what it read, its limit). A NaN is over every limit.
    held = {
        "math_worst_leaf_rel": (m_worst, MATH_LEAF_REL_TOL),
        "math_global_grad_rel": (m_global, MATH_GLOBAL_REL_TOL),
        "math_loss_rel": (m_loss_rel, MATH_LOSS_REL_TOL),
        "as_configured_global_grad_rel": (p_global, PRECISION_GLOBAL_REL_TOL),
        "as_configured_loss_rel": (loss_rel, LOSS_REL_TOL),
    }
    failed = [f"{k} {v:.3g} over {tol:g}" for k, (v, tol) in held.items() if not v <= tol]
    return {"ok": not failed, "failed": failed, "reference_loss": r_loss,
            "math": {"loss_rel": m_loss_rel, "global_grad_rel": m_global, "worst_leaf_rel": m_worst,
                     "worst_leaf": m_leaf},
            "as_configured": {"loss_rel": loss_rel, "global_grad_rel": p_global, "worst_leaf_rel": p_worst,
                              "worst_leaf": p_leaf},
            "tolerance": {k: tol for k, (_, tol) in held.items()},
            "compared": {k: [v, "<=", tol] for k, (v, tol) in held.items()}}


def run(ctx, config: dict, cell: dict) -> dict:
    m, train = config["model"], cell["train"]
    logs: list[str] = []
    trainer = api.make_trainer(config, train, ctx.seed, logs.append)
    ctx.mark("weights and optimizer state")
    verdict = check(ctx, config, cell, trainer)
    ctx.say("check", verdict)
    ctx.mark("check")
    src, tgt = traffic.seq2seq_corpus(cell["corpus"], ctx.seed, m["target_vocab_size"])
    # The dataset shuffles by the corpus's fixed shape_seed, not by --seed: the
    # multiset of lengths is the same for every seed, so every run then meets
    # the same sequence of batch widths (with other sentences in them), and a
    # window holds the same mix of step shapes.
    ds = api.make_seq2seq_dataset(
        src, tgt, train["batch_size"], train["sequence_length"], cell["length_buckets"],
        cell["corpus"]["shape_seed"],
    )
    wrapped = WindowedBatches(
        ds, trainer, cell["length_buckets"], ctx.seconds, ctx,
        min(cell["trace"]["for_s"], 0.3 * ctx.seconds),
    )
    ctx.mark("corpus and dataset")
    with ctx.span("trainer.fit"):
        trainer.fit(wrapped, None, rng=api.jax_key(ctx.seed + 1))
    last_loss = api.trainer_last_loss(trainer)
    window_s = wrapped.t1 - wrapped.t0
    src_lens = np.concatenate(wrapped.src_lens) if wrapped.src_lens else np.zeros(0)
    tgt_lens = np.concatenate(wrapped.tgt_lens) if wrapped.tgt_lens else np.zeros(0)
    fell = wrapped.first_loss is not None and np.isfinite(last_loss) and last_loss < wrapped.first_loss
    why_not = verdict["failed"] + ([] if fell else [f"loss did not fall: {wrapped.first_loss} -> {last_loss}"])
    ctx.say("train", {"steps": wrapped.steps, "window_s": window_s, "first_loss": wrapped.first_loss,
                      "last_loss": last_loss, "passes_over_corpus": wrapped.passes,
                      "padded_target_positions": wrapped.positions,
                      "nonpad_target_tokens": int(tgt_lens.sum()), "trainer_log_tail": logs[-2:]})
    return {
        "correct": not why_not,
        "why_not_correct": why_not,
        "compared": verdict["compared"],
        "attempted": wrapped.steps,
        "failed": 0,
        "window_s": window_s,
        "train": {
            "steps": wrapped.steps,
            "nonpad_target_tokens": int(tgt_lens.sum()),
            "data_wait_s": wrapped.data_wait_s,
            "matmul_flops": flops_bytes.seq2seq_train_flops(m, src_lens, tgt_lens),
        },
    }
