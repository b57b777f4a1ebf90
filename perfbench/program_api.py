"""The one file of the benchmark that imports the system under test.

Everything else under ``perfbench/`` reaches ``transformer_tpu`` through the
functions here, so that when a refactor renames a class or a private program,
a later ``benchmark`` PR edits this file and nothing else. The private names
(``_pool_step_paged_flash``, ``_slot_prefill_paged``, ``_paged_ensure``,
``_last_metrics``) are the ones ``chip_smoke.py`` and the serve loop use.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from transformer_tpu.config import PAD_ID, ModelConfig, TrainConfig
from transformer_tpu.data.pipeline import Seq2SeqDataset
from transformer_tpu.models.transformer import transformer_apply, transformer_init
from transformer_tpu.obs.telemetry import Telemetry
from transformer_tpu.serve import scheduler as _sched
from transformer_tpu.train.loss import masked_cross_entropy
from transformer_tpu.train.state import create_train_state
from transformer_tpu.train.trainer import Trainer

def model_config(config: dict) -> ModelConfig:
    return ModelConfig(**config["model"])


def jax_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed) % (2**31 - 1))


def _roughen(tree, key):
    """Biases and norm parameters start at 0 and 1 in the program's init, where
    a wrong bias or scale would not show against the reference: move them."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for i, (path, x) in enumerate(leaves):
        name = str(getattr(path[-1], "key", ""))
        if name in ("bias", "scale") and jnp.issubdtype(x.dtype, jnp.floating):
            noise = jax.random.normal(jax.random.fold_in(key, i), x.shape, jnp.float32)
            x = (x.astype(jnp.float32) + 0.05 * noise).astype(x.dtype)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------- training


def make_trainer(config: dict, train: dict, seed: int, log_fn):
    """``Trainer`` over a state made on the device in one jitted call."""
    cfg = model_config(config)
    tc = TrainConfig(seed=int(seed) % (2**31 - 1), **train)

    def init(key):
        state = create_train_state(key, cfg, tc)
        return dataclasses.replace(state, params=_roughen(state.params, key))

    state = jax.jit(init)(jax_key(seed))
    return Trainer(cfg, tc, state, log_dir=None, checkpoint=None, log_fn=log_fn, telemetry=None)


def make_seq2seq_dataset(src, tgt, batch_size: int, width: int, buckets, seed: int):
    """The dataset ``cli.train`` builds: bucketed, shuffled, prefetching."""
    return Seq2SeqDataset(
        src, tgt, batch_size=batch_size, src_len=width, tgt_len=width, shuffle=True,
        seed=int(seed) % (2**31 - 1), length_buckets=tuple(buckets), prefetch=True,
    )


def trainer_sync(trainer) -> None:
    jax.block_until_ready(trainer.state.step)


def trainer_last_loss(trainer) -> float | None:
    m = trainer._last_metrics
    return None if m is None else float(m["loss"])


def trainer_params(trainer):
    return trainer.state.params


def program_loss_and_grads(params, src, tgt, config: dict, label_smoothing: float, dtype: str | None = None):
    """The program's own forward and loss, dropout off: in its configured compute
    dtype, or (``dtype="float32"``) the same code in float32 at the highest
    matmul precision, where it has to agree with the reference to rounding."""
    cfg = model_config(config)
    if dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=dtype)

    def loss_fn(p, src, tgt):
        logits, _ = transformer_apply(p, src, tgt[:, :-1], cfg, rng=None, deterministic=True)
        loss, _ = masked_cross_entropy(logits, tgt[:, 1:], label_smoothing=label_smoothing)
        return loss

    with jax.default_matmul_precision("highest" if dtype == "float32" else "default"):
        return jax.jit(jax.value_and_grad(loss_fn))(params, src, tgt)


# ----------------------------------------------------------------- serving


class IdTokenizer:
    """Tokens are ids ("3 17 5" -> [3, 17, 5]). ``eos_id`` lies outside every
    vocabulary, so a request emits exactly ``max_new`` tokens."""

    bos_id, eos_id = 1, -1

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, toks):
        return " ".join(str(t) for t in toks)


def init_lm_params(config: dict, seed: int):
    """Random weights in the served dtype, one jitted call on the device."""
    cfg = model_config(config)
    return jax.jit(lambda k: _roughen(transformer_init(k, cfg), k))(jax_key(seed))


def make_scheduler(params, config: dict, deployment: dict, span_tap):
    """``ContinuousScheduler`` as ``cli.serve`` builds it, with a metrics-only
    telemetry bundle (no event sink, no tracer, no periodic flush)."""
    tel = Telemetry(interval=1e12)
    sched = _sched.ContinuousScheduler(
        params, model_config(config), IdTokenizer(), telemetry=tel, span_tap=span_tap, **deployment
    )
    return sched, tel


def generated_tokens(tel) -> float:
    return tel.registry.counter("serve_generated_tokens_total").value


def pool_usage(sched) -> tuple[int, int]:
    """(blocks in use, blocks in the pool) from the pool's own accounting."""
    a = sched.pool.alloc
    return a.used_blocks, a.used_blocks + a.free_blocks


def pool_forward_logits(sched, prompts: np.ndarray, steps: int) -> np.ndarray:
    """Prefill each row of ``prompts`` (R, n) into a slot of its own and decode
    ``steps`` more tokens of it through the jitted pool programs the scheduler
    dispatches; returns float32 logits (R, steps + 1, V): at the last prompt
    position, then after each decoded token (fed greedily). The pool is idle
    when this is called, and is left idle."""
    cfg, pool = sched.cfg, sched.pool
    if not (sched.paged and sched.decode_kernel == "paged_flash"):
        raise ValueError("written for the paged_flash deployment the cells use")
    rows, n = prompts.shape
    out = np.zeros((rows, steps + 1, cfg.target_vocab_size), np.float32)
    toks = np.full((sched.num_slots,), PAD_ID, np.int32)
    index = np.zeros((sched.num_slots,), np.int32)
    try:
        for slot in range(rows):
            sched._paged_ensure(slot, n + steps)
            logits, pool.caches = _sched._slot_prefill_paged(
                sched.params, pool.caches, pool.alloc.table_device(), jnp.int32(slot),
                jnp.asarray(prompts[slot : slot + 1], jnp.int32), jnp.int32(0), cfg,
                sched.prefill_chunk, pool.block_tokens, pool.buf_len,
            )
            out[slot, 0] = np.asarray(logits[0], np.float32)
            toks[slot] = int(out[slot, 0].argmax())
            index[slot] = n
        for k in range(steps):
            logits, pool.caches = _sched._pool_step_paged_flash(
                sched.params, pool.caches, pool.alloc.table_device(), jnp.asarray(index),
                jnp.asarray(toks), cfg, pool.block_tokens, sched._kernel_interpret,
            )
            got = np.asarray(logits[:rows], np.float32)
            out[:, k + 1] = got
            toks[:rows] = got.argmax(-1)
            index[:rows] += 1
    finally:
        for slot in range(rows):
            pool.alloc.free_slot(slot)
    return out
