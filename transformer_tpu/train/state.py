"""Train state and optimizer construction.

The state is a plain pytree dataclass — params, optimizer state, step — so it
jits, shards with PartitionSpecs, and checkpoints as a flat array tree.
Counterpart of the reference's ``Train.__init__`` wiring (optimizer + model
refs, ``train.py:55-80``), without the Keras object graph.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import optax

from transformer_tpu.config import ModelConfig, TrainConfig
from transformer_tpu.models import transformer_init
from transformer_tpu.train.schedule import noam_schedule


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any


def make_lr_schedule(model_cfg: ModelConfig, train_cfg: TrainConfig):
    """THE learning-rate schedule — single definition shared by the optimizer
    and observability (TensorBoard's learning_rate scalar), so the plotted
    curve can never drift from the one actually applied."""
    if train_cfg.lr_schedule == "cosine":
        from transformer_tpu.train.schedule import cosine_schedule

        return cosine_schedule(
            train_cfg.peak_lr, train_cfg.warmup_steps, train_cfg.lr_decay_steps
        )
    if train_cfg.lr_schedule == "constant":
        from transformer_tpu.train.schedule import constant_schedule

        return constant_schedule(train_cfg.peak_lr, train_cfg.warmup_steps)
    return noam_schedule(model_cfg.d_model, train_cfg.warmup_steps)


def make_optimizer(model_cfg: ModelConfig, train_cfg: TrainConfig) -> optax.GradientTransformation:
    """Adam(β1=0.9, β2=0.98, ε=1e-9) under the noam schedule — the reference's
    optimizer exactly (``train.py:65-66``) — or Adafactor
    (``train_cfg.optimizer="adafactor"``: factored second moments, the
    big-model optimizer-memory lever; its state leaves replicate under the
    path-rule shardings, which is fine — they are vectors, not matrices).
    Plus optional global-norm clipping (absent from the reference; off by
    default)."""
    schedule = make_lr_schedule(model_cfg, train_cfg)
    if train_cfg.optimizer == "adafactor":
        tx = optax.adafactor(learning_rate=schedule)
    elif train_cfg.optimizer == "adamw":
        # Decoupled weight decay (Loshchilov & Hutter). Biases and layernorm
        # params are exempt — decaying them hurts and no modern recipe does
        # it. The mask keys on the leaf NAME, not rank: the pre-split qkv
        # biases are 2-D (H, head_dim) and must still be exempt.
        def _decay_mask(params):
            def keep(path, p):
                last = path[-1]
                name = str(getattr(last, "key", getattr(last, "name", last)))
                return p.ndim >= 2 and name != "bias"

            return jax.tree_util.tree_map_with_path(keep, params)

        tx = optax.adamw(
            learning_rate=schedule,
            b1=train_cfg.adam_beta1,
            b2=train_cfg.adam_beta2,
            eps=train_cfg.adam_epsilon,
            weight_decay=train_cfg.weight_decay,
            mask=_decay_mask,
        )
    else:
        tx = optax.adam(
            learning_rate=schedule,
            b1=train_cfg.adam_beta1,
            b2=train_cfg.adam_beta2,
            eps=train_cfg.adam_epsilon,
        )
    if train_cfg.max_grad_norm > 0:
        tx = optax.chain(optax.clip_by_global_norm(train_cfg.max_grad_norm), tx)
    return tx


def _own_buffers(tree: Any) -> Any:
    """Give every leaf its own buffer. ``tie_embeddings`` hands the decoder
    the encoder's table itself — one array referenced twice — and a donated
    train step takes each leaf's buffer: the TPU runtime refuses to donate
    one buffer twice (INVALID_ARGUMENT at the first step, measured on the
    v5e in PR 21; the CPU ignores donation, so no CPU run sees it). The
    optimizer updates the two leaves separately from the first step on, so
    a copy changes no result."""
    seen: set[int] = set()

    def own(x):
        if id(x) in seen:
            return jnp.copy(x)
        seen.add(id(x))
        return x

    return jax.tree.map(own, tree)


def create_train_state(
    rng: jax.Array, model_cfg: ModelConfig, train_cfg: TrainConfig
) -> TrainState:
    params = _own_buffers(transformer_init(rng, model_cfg))
    tx = make_optimizer(model_cfg, train_cfg)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
    )
