"""Kind ``train_steps_mesh``: kind ``train_steps`` with the trainer made over a
device mesh (the cell's ``mesh``, a ``MeshConfig``): the reference's
"distributed training", data-parallel over the chips of one host.

Everything that is counted is ``train_steps``'s: the same window cut out of
one call to ``fit``, the same non-pad target tokens, the same comparison with
the plain reference (of the replicated parameters' loss and gradients on one
device) and the same limits. ``train_tok_s`` divides by the cell's ``chips``.

That comparison never runs a program over the mesh, so the kind adds one that
does (``mesh_check``): ONE step of the trainer's sharded step program on a
seeded global batch of the cell's size (``mesh_check``: 1,024 rows, a quarter
a chip), and the loss, the token count and the all-reduced gradient that step
made of it against the plain reference on the WHOLE batch, on one device. A
step that left the all-reduce out, or saw 256 of the 1,024 rows, counts other
tokens and holds a gradient a third of the reference's norm off, where the
whole batch's reads a hundredth and the limit is 0.08
(``perfbench/tests/mesh_check_controls.py`` makes both; PERF.md section 6 has
the chip's readings). The loss alone would pass either.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import flops_bytes, traffic
from perfbench import program_api as api
from perfbench import program_api_mesh as mesh_api
from perfbench.kinds.train_steps import (
    LOSS_REL_TOL, PRECISION_GLOBAL_REL_TOL, WindowedBatches, _leaf_errors, check, check_batch,
)

# The reference takes the whole batch this many rows at a time (its logits are
# rows x width x vocabulary in float32: 0.6 GB at 64 rows of 64).
REFERENCE_ROWS_A_CALL = 64


def whole_batch_reference(config: dict, cell: dict, params, src, tgt) -> dict:
    """The plain reference's loss and gradient on the whole global batch, on
    one device and with nothing of the mesh: a few rows a call, each call's
    mean weighted by its share of the batch's non-pad target tokens (the mean
    over the batch is that sum)."""
    reference = importlib.import_module(f"perfbench.reference.{config['family']}")
    ls = cell["train"]["label_smoothing"]

    def add(acc, p, s, t, share):
        loss, grads = reference.loss_and_grads(p, s, t, config["model"], ls)
        return jax.tree.map(lambda a, g: a + share * g, acc, (loss, grads))

    add = jax.jit(add, donate_argnums=0)
    params = mesh_api.on_one_device(params)
    acc = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), (jnp.zeros(()), params))
    tokens = (tgt[:, 1:] != 0).sum(1)
    for r in range(0, len(src), REFERENCE_ROWS_A_CALL):
        rows = slice(r, r + REFERENCE_ROWS_A_CALL)
        acc = add(acc, params, src[rows], tgt[rows], np.float32(tokens[rows].sum() / tokens.sum()))
    loss, grads = acc
    return {"loss": float(loss), "tokens": int(tokens.sum()),
            "leaves": [np.asarray(g, np.float32) for g in jax.tree_util.tree_leaves(grads)]}


def mesh_compare(trainer, step, seed: int, src, tgt, ref: dict) -> dict:
    """One step of ``step`` over the mesh on (src, tgt) against ``ref``."""
    got = mesh_api.mesh_step_once(trainer, step, src, tgt, seed)
    worst, leaf, overall = _leaf_errors(got.pop("grads"), ref["leaves"])
    held = {
        "mesh_step_tokens_off": (abs(got["weight"] - ref["tokens"]), 0),
        "mesh_step_global_grad_rel": (overall, PRECISION_GLOBAL_REL_TOL),
        "mesh_step_loss_rel": (abs(got["loss"] - ref["loss"]) / abs(ref["loss"]), LOSS_REL_TOL),
    }
    failed = [f"{k} {v:.3g} over {tol:g}" for k, (v, tol) in held.items() if not v <= tol]
    return {"ok": not failed, "failed": failed, "reference_loss": ref["loss"], "tokens": ref["tokens"],
            "step": got, "worst_leaf_rel": worst, "worst_leaf": leaf,
            "compared": {k: [v, "<=", tol] for k, (v, tol) in held.items()}}


def mesh_batch(seed: int, config: dict, cell: dict):
    return check_batch(seed, {"check": cell["mesh_check"]}, config["model"]["target_vocab_size"])


def mesh_check(ctx, config: dict, cell: dict, trainer) -> dict:
    src, tgt = mesh_batch(ctx.seed, config, cell)
    ref = whole_batch_reference(config, cell, api.trainer_params(trainer), src, tgt)
    return mesh_compare(trainer, mesh_api.make_mesh_check_step(trainer), ctx.seed, src, tgt, ref)


def run(ctx, config: dict, cell: dict) -> dict:
    m, train = config["model"], cell["train"]
    logs: list[str] = []
    trainer = mesh_api.make_mesh_trainer(config, train, cell["mesh"], ctx.seed, logs.append)
    ctx.mark("weights and optimizer state")
    verdict = check(ctx, config, cell, trainer)
    ctx.say("check", verdict)
    ctx.mark("check")
    over_mesh = mesh_check(ctx, config, cell, trainer)
    ctx.say("mesh_check", over_mesh)
    ctx.mark("mesh check")
    src, tgt = traffic.seq2seq_corpus(cell["corpus"], ctx.seed, m["target_vocab_size"])
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
    why_not = verdict["failed"] + over_mesh["failed"] + ([] if fell else [f"loss did not fall: {wrapped.first_loss} -> {last_loss}"])
    ctx.say("train", {"steps": wrapped.steps, "window_s": window_s, "first_loss": wrapped.first_loss,
                      "last_loss": last_loss, "passes_over_corpus": wrapped.passes,
                      "mesh": cell["mesh"], "mesh_devices": mesh_api.mesh_devices(trainer),
                      "padded_target_positions": wrapped.positions,
                      "nonpad_target_tokens": int(tgt_lens.sum()), "trainer_log_tail": logs[-2:]})
    return {
        "correct": not why_not,
        "why_not_correct": why_not,
        "compared": {**verdict["compared"], **over_mesh["compared"]},
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
