"""Jitted train/eval steps and the epoch loop.

Counterpart of the reference's ``Train`` engine (``train.py:37-213``):
teacher-forcing shift, gradient step, streaming metrics, periodic eval,
TensorBoard scalars, checkpoint rotation. Deliberate fixes over the reference
(SURVEY.md §2.3): checkpoints save on the *intended* cadence (every
``checkpoint_every_epochs`` or last epoch — the reference's condition is
inverted by operator precedence, ``train.py:208``); in-loop eval runs a
bounded number of batches instead of the full test set every 100 steps
(``train.py:193-195``); restore happens *before* training so crash-resume
works (the reference restores only after, ``train.py:242-243``).
"""

from __future__ import annotations

import time
from collections.abc import Callable, Iterable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

from transformer_tpu.config import ModelConfig, TrainConfig
from transformer_tpu.models import transformer_apply
from transformer_tpu.obs.telemetry import timed_call
from transformer_tpu.obs.trace import traced_call
from transformer_tpu.train.checkpoint import CheckpointManager
from transformer_tpu.train.loss import (
    chunked_cross_entropy_from_hidden,
    masked_cross_entropy,
)
from transformer_tpu.train.state import TrainState, make_optimizer
from transformer_tpu.utils.preemption import PreemptionGuard
from transformer_tpu.utils.profiling import Profiler, StepTimer, mirrored_tracer
from transformer_tpu.utils.tensorboard import SummaryWriter


def _shift_targets(tgt: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Teacher forcing: feed ``tgt[:, :-1]``, predict ``tgt[:, 1:]``
    (reference ``train.py:130-131``)."""
    return tgt[:, :-1], tgt[:, 1:]


def _check_objective(model_cfg: ModelConfig, train_cfg: TrainConfig) -> None:
    if (train_cfg.objective == "mlm") != model_cfg.encoder_only:
        raise ValueError(
            "objective='mlm' and ModelConfig.encoder_only go together "
            "(the masked-LM loss needs the bidirectional encoder stack, and "
            "an encoder-only model has no causal shift to train on): got "
            f"objective={train_cfg.objective!r}, "
            f"encoder_only={model_cfg.encoder_only}"
        )


def _prepare_batch(
    model_cfg: ModelConfig, train_cfg: TrainConfig, tgt, step_rng
):
    """-> (model_input, labels, fwd_rng) for one step.

    causal: the teacher-forcing shift (``_shift_targets``). mlm: BERT-style
    dynamic masking from the step rng — fresh masks every step
    (``train/mlm.py``); eval passes ``step_rng=None`` and gets a CONSTANT
    mask key, so eval losses are deterministic and comparable across
    epochs/runs (the same positions are always scored).
    """
    if train_cfg.objective == "mlm":
        from transformer_tpu.train.mlm import mask_tokens

        if step_rng is None:
            r_mask, fwd_rng = jax.random.PRNGKey(train_cfg.seed), None
        else:
            r_mask, fwd_rng = jax.random.split(step_rng)
        excluded = train_cfg.mlm_excluded_ids
        if excluded is None:
            # Auto: BOS/EOS sit at the two ids below [MASK] in the
            # framework's MLM vocab layout (config.py mlm_excluded_ids).
            mask_id = model_cfg.input_vocab_size - 1
            excluded = (mask_id - 2, mask_id - 1)
        inp, labels = mask_tokens(
            tgt, r_mask, model_cfg.input_vocab_size, train_cfg.mlm_mask_rate,
            excluded_ids=excluded,
        )
        return inp, labels, fwd_rng
    tar_inp, tar_out = _shift_targets(tgt)
    return tar_inp, tar_out, step_rng


def make_train_step(
    model_cfg: ModelConfig,
    train_cfg: TrainConfig,
    tx: optax.GradientTransformation | None = None,
    forward_fn: Callable | None = None,
    hidden_forward_fn: Callable | None = None,
) -> Callable[[TrainState, jax.Array, jax.Array, jax.Array], tuple[TrainState, dict]]:
    """Build the (jittable) train step: forward, masked CE, grad, Adam update.

    The returned function is pure — jit it (single chip), or jit with
    shardings (distributed): gradients summed across the ``data`` axis emerge
    from XLA's psum with no explicit collective here.

    ``forward_fn(params, src, tar_inp, rng, deterministic) -> logits``
    overrides the forward pass (e.g. the GPipe-pipelined forward when the
    mesh has a ``pipe`` axis); default is the plain ``transformer_apply``.

    ``hidden_forward_fn`` is the pre-vocab-projection counterpart (returns
    (B, S, d_model) hiddens), used when ``train_cfg.loss_chunks > 1``: the
    chunked vocab-projection/CE path then composes with custom forwards
    (pipeline / sequence-parallel) and with gradient accumulation — the
    long-context-at-scale combination (ring attention + 32k vocab) is
    exactly where the (B, S, V) logits OOM.
    """
    tx = tx or make_optimizer(model_cfg, train_cfg)
    _check_objective(model_cfg, train_cfg)
    chunked = train_cfg.loss_chunks > 1
    if chunked:
        if forward_fn is not None and hidden_forward_fn is None:
            raise ValueError(
                "loss_chunks>1 needs the hidden-state forward: a custom "
                "forward_fn must come with the matching hidden_forward_fn "
                "(parallel.distributed.make_sharded_steps builds both)"
            )
        hidden_forward = hidden_forward_fn or _default_hidden_forward(model_cfg)
    if forward_fn is None:
        forward_fn = _default_forward(model_cfg)
    accum = max(1, train_cfg.grad_accum_steps)

    def _apply(state, grads, metrics):
        # Pre-clip global gradient norm: the training-health scalar every
        # telemetry sink exports (docs/OBSERVABILITY.md). Computed here so
        # the plain and grad-accum paths report the same quantity (the
        # accum path passes already-normalized whole-batch grads).
        metrics = {
            **metrics,
            "grad_norm": optax.global_norm(grads).astype(jnp.float32),
        }
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            step=state.step + 1, params=new_params, opt_state=new_opt_state
        )
        return new_state, metrics

    def train_step(state: TrainState, src, tgt, rng):
        step_rng = jax.random.fold_in(rng, state.step)
        tar_inp, tar_out, fwd_rng = _prepare_batch(
            model_cfg, train_cfg, tgt, step_rng
        )

        def loss_fn(params):
            if chunked:
                x, aux = hidden_forward(params, src, tar_inp, fwd_rng, False)
                loss, metrics = _chunked_loss(params, x, tar_out, model_cfg, train_cfg)
            else:
                logits, aux = _split_forward_out(
                    forward_fn(params, src, tar_inp, fwd_rng, False)
                )
                loss, metrics = masked_cross_entropy(
                    logits, tar_out,
                    label_smoothing=train_cfg.label_smoothing,
                    normalization=train_cfg.loss_normalization,
                    batch_size=train_cfg.batch_size,
                )
            metrics = {"loss": loss, **metrics}
            total = loss
            if aux is not None:
                # MoE load-balance loss: differentiated (keeps the router
                # honest) but reported separately — "loss" stays comparable
                # CE across dense and MoE configs.
                total = loss + model_cfg.moe_aux_weight * aux
            if model_cfg.moe_experts:
                # Key presence follows the CONFIG, not the forward's return
                # shape, so metric pytrees (and distributed out_shardings)
                # stay fixed even under a custom aux-less forward_fn.
                metrics["moe_aux"] = jnp.float32(0.0) if aux is None else aux
            return total, metrics

        (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        return _apply(state, grads, metrics)

    def accum_train_step(state: TrainState, src, tgt, rng):
        """Gradient accumulation: lax.scan over ``accum`` micro-steps, each a
        full forward/backward on 1/accum of the batch; gradients are summed
        in the un-normalized (loss-SUM) domain and divided once at the end,
        so the update equals the whole-batch gradient exactly (for "tokens"
        normalization the denominator is the global non-pad token count —
        chunk-mean averaging would weight chunks unequally)."""
        step_rng = jax.random.fold_in(rng, state.step)
        tar_inp, tar_out, step_rng = _prepare_batch(
            model_cfg, train_cfg, tgt, step_rng
        )
        batch = src.shape[0]
        if batch % accum:
            raise ValueError(
                f"grad_accum_steps {accum} must divide the batch {batch}"
            )
        mb = batch // accum
        chunks = (
            src.reshape(accum, mb, *src.shape[1:]),
            tar_inp.reshape(accum, mb, *tar_inp.shape[1:]),
            tar_out.reshape(accum, mb, *tar_out.shape[1:]),
            jnp.arange(accum),
        )

        def sum_loss_fn(params, s, ti, to, r):
            if chunked:
                x, aux = hidden_forward(params, s, ti, r, False)
                _, m = chunked_cross_entropy_from_hidden(
                    params, x, to, model_cfg,
                    num_chunks=train_cfg.loss_chunks,
                    label_smoothing=train_cfg.label_smoothing,
                    normalization="tokens",  # only the sums are consumed
                )
            else:
                logits, aux = _split_forward_out(forward_fn(params, s, ti, r, False))
                _, m = masked_cross_entropy(
                    logits, to,
                    label_smoothing=train_cfg.label_smoothing,
                    normalization="tokens",  # only the sums are consumed
                )
            obj = m["loss_sum"]
            if model_cfg.moe_experts:  # key presence follows the config
                # Scaled so that the /denom at the end yields a mean of
                # per-chunk aux losses in BOTH normalizations: token-weighted
                # under "tokens" (denom = total non-pad tokens), uniform under
                # "batch" (denom = batch_size) — without the scale matching
                # the denominator, the effective aux weight would grow with
                # tokens-per-sample under the reference's "batch" rule.
                if train_cfg.loss_normalization == "tokens":
                    aux_scale = m["weight"]
                else:
                    aux_scale = jnp.float32(train_cfg.batch_size) / accum
                m["moe_aux_sum"] = (0.0 if aux is None else aux) * aux_scale
                obj = obj + model_cfg.moe_aux_weight * m["moe_aux_sum"]
            return obj, m

        grad_fn = jax.grad(sum_loss_fn, has_aux=True)

        def body(acc, chunk):
            acc_g, acc_m = acc
            s, ti, to, i = chunk
            g, m = grad_fn(state.params, s, ti, to, jax.random.fold_in(step_rng, i))
            acc_g = jax.tree.map(jnp.add, acc_g, g)
            acc_m = {k: acc_m[k] + m[k] for k in acc_m}
            return (acc_g, acc_m), None

        zero_g = jax.tree.map(jnp.zeros_like, state.params)
        zero_m = {
            "loss_sum": jnp.zeros((), jnp.float32),
            "weight": jnp.zeros((), jnp.float32),
            "correct": jnp.zeros((), jnp.float32),
        }
        if model_cfg.moe_experts:
            zero_m["moe_aux_sum"] = jnp.zeros((), jnp.float32)
        (grads, m), _ = jax.lax.scan(body, (zero_g, zero_m), chunks)
        if train_cfg.loss_normalization == "tokens":
            denom = jnp.maximum(m["weight"], 1.0)
        else:  # "batch": the reference's rule, train.py:88
            denom = jnp.float32(train_cfg.batch_size)
        grads = jax.tree.map(lambda g: g / denom, grads)
        loss = m["loss_sum"] / denom
        aux_sum = m.pop("moe_aux_sum", None)
        metrics = {"loss": loss, **m}
        if aux_sum is not None:
            metrics["moe_aux"] = aux_sum / denom  # mean per-chunk aux (see above)
        return _apply(state, grads, metrics)

    return accum_train_step if accum > 1 else train_step


def make_multistep_train_step(
    step_fn: Callable,
    has_moe: bool = False,
    loss_normalization: str = "tokens",
    batch_size: int = 0,
) -> Callable[[TrainState, jax.Array, jax.Array, jax.Array], tuple[TrainState, dict]]:
    """Wrap a train step so K optimizer steps run inside ONE ``lax.scan``
    per host dispatch (``TrainConfig.steps_per_dispatch``).

    Input batches are stacked on a leading axis: ``src``/``tgt`` are
    (K, B, S). Per-step dropout keys stay exactly what K sequential calls
    would have used — ``step_fn`` folds ``state.step`` into ``rng`` and the
    step counter advances inside the scan — so the trajectory matches K
    separate dispatches to float tolerance (XLA compiles one fused scan
    program, so low-order bits can differ; parity asserted at rtol≈1e-5 in
    tests/test_train.py).

    Metrics come back pre-reduced ON DEVICE over the K steps (sums for
    ``loss_sum``/``weight``/``correct``; token-weighted mean for
    ``moe_aux``), in the exact form ``MetricAccumulator.update`` expects —
    no (K,)-shaped host transfer, async dispatch preserved.
    """

    def multistep(state: TrainState, src, tgt, rng):
        def body(s, xs):
            sb, tb = xs
            s, m = step_fn(s, sb, tb, rng)
            return s, m

        state, ms = jax.lax.scan(body, state, (src, tgt))
        k = ms["loss_sum"].shape[0]
        out = {
            "loss_sum": ms["loss_sum"].sum(0),
            "weight": ms["weight"].sum(0),
            "correct": ms["correct"].sum(0),
        }
        if loss_normalization == "batch" and batch_size:
            # Match the single-step metric's normalization (reference rule,
            # train.py:88): mean of the K per-step losses, each loss_sum/B.
            out["loss"] = out["loss_sum"] / jnp.float32(batch_size * k)
        else:
            out["loss"] = out["loss_sum"] / jnp.maximum(out["weight"], 1.0)
        if has_moe:
            # update() re-multiplies moe_aux by weight; pre-dividing the
            # weighted sum here keeps the epoch aggregate the same
            # token-weighted mean K separate updates would produce.
            out["moe_aux"] = (ms["moe_aux"] * ms["weight"]).sum(0) / jnp.maximum(
                out["weight"], 1.0
            )
        if "grad_norm" in ms:
            # Mean over the K optimizer steps: one representative
            # training-health scalar per dispatch (guarded — custom step_fns
            # without the metric stay supported).
            out["grad_norm"] = ms["grad_norm"].mean(0)
        return state, out

    return multistep


def _split_forward_out(out) -> tuple[jax.Array, jax.Array | None]:
    """Forward functions return logits, or (logits, moe_aux_loss) for MoE
    configs — normalize to a pair."""
    return out if isinstance(out, tuple) else (out, None)


def _collect_moe_aux(attn: dict) -> jax.Array:
    """Sum the stacks' reserved load-balance keys (models/encoder.py
    encoder_apply docstring) into one fp32 scalar."""
    return jnp.asarray(
        attn.get("moe_aux_encoder", 0.0) + attn.get("moe_aux_decoder", 0.0),
        jnp.float32,
    )


def _chunked_loss(params, hidden, tar_out, model_cfg, train_cfg):
    """The train/eval-shared call into the chunked vocab-projection/CE path."""
    return chunked_cross_entropy_from_hidden(
        params, hidden, tar_out, model_cfg,
        num_chunks=train_cfg.loss_chunks,
        label_smoothing=train_cfg.label_smoothing,
        normalization=train_cfg.loss_normalization,
        batch_size=train_cfg.batch_size,
    )


def _default_hidden_forward(model_cfg: ModelConfig) -> Callable:
    """Like ``_default_forward`` but stops before the vocab projection:
    returns ((B, S, d_model) hiddens, moe_aux|None) for the chunked-loss
    path (``train_cfg.loss_chunks``)."""
    from transformer_tpu.models import transformer_hidden_apply

    def forward(params, src, tar_inp, rng, deterministic):
        x, attn = transformer_hidden_apply(
            params, src, tar_inp, model_cfg,
            rng=None if deterministic else rng, deterministic=deterministic,
        )
        return x, _collect_moe_aux(attn) if model_cfg.moe_experts else None

    return forward


def _default_forward(model_cfg: ModelConfig) -> Callable:
    if model_cfg.moe_experts:

        def forward_moe(params, src, tar_inp, rng, deterministic):
            logits, attn = transformer_apply(
                params, src, tar_inp, model_cfg,
                rng=None if deterministic else rng, deterministic=deterministic,
            )
            return logits, _collect_moe_aux(attn)

        return forward_moe

    def forward(params, src, tar_inp, rng, deterministic):
        logits, _ = transformer_apply(
            params, src, tar_inp, model_cfg,
            rng=None if deterministic else rng, deterministic=deterministic,
        )
        return logits

    return forward


def make_eval_step(
    model_cfg: ModelConfig,
    train_cfg: TrainConfig,
    forward_fn: Callable | None = None,
    hidden_forward_fn: Callable | None = None,
) -> Callable[[TrainState, jax.Array, jax.Array], dict]:
    """Forward-only eval step (reference ``test_step``, ``train.py:144-157``)."""
    _check_objective(model_cfg, train_cfg)
    chunked = train_cfg.loss_chunks > 1
    if chunked and forward_fn is not None and hidden_forward_fn is None:
        # Same contract as make_train_step: silently materializing the full
        # (B, S, V) logits would OOM in exactly the config loss_chunks exists
        # to protect.
        raise ValueError(
            "loss_chunks>1 needs the hidden-state forward: a custom "
            "forward_fn must come with the matching hidden_forward_fn "
            "(parallel.distributed.make_sharded_steps builds both)"
        )
    if chunked:
        hidden_forward = hidden_forward_fn or _default_hidden_forward(model_cfg)
    if forward_fn is None:
        forward_fn = _default_forward(model_cfg)

    def eval_step(state: TrainState, src, tgt):
        tar_inp, tar_out, _ = _prepare_batch(model_cfg, train_cfg, tgt, None)
        if chunked:
            x, aux = hidden_forward(state.params, src, tar_inp, None, True)
            loss, metrics = _chunked_loss(state.params, x, tar_out, model_cfg, train_cfg)
            metrics = {"loss": loss, **metrics}
            if model_cfg.moe_experts:
                metrics["moe_aux"] = jnp.float32(0.0) if aux is None else aux
            return metrics
        logits, aux = _split_forward_out(
            forward_fn(state.params, src, tar_inp, None, True)
        )
        loss, metrics = masked_cross_entropy(
            logits, tar_out,
            label_smoothing=train_cfg.label_smoothing,
            normalization=train_cfg.loss_normalization,
            batch_size=train_cfg.batch_size,
        )
        metrics = {"loss": loss, **metrics}
        if model_cfg.moe_experts:  # key presence follows the config
            metrics["moe_aux"] = jnp.float32(0.0) if aux is None else aux
        return metrics

    return eval_step


class MetricAccumulator:
    """Exact accumulation of device-computed sums — replacement for the
    reference's Keras streaming metrics (``train.py:70-73,181-184``).

    Sums are kept as (device) arrays and added lazily, so updating metrics
    every step does NOT force a host-device sync — reading ``.loss`` /
    ``.accuracy`` (at log boundaries) is the only blocking point. This
    preserves JAX async dispatch: step N+1 enqueues while N runs.
    """

    _KEYS = ("loss_sum", "weight", "correct")

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self._sums: dict[str, Any] | None = None

    def update(self, metrics: dict[str, Any]) -> None:
        part = {k: metrics[k] for k in self._KEYS}
        if "moe_aux" in metrics:
            # Token-weighted so the epoch aggregate is the same weighted mean
            # the per-step metric reports (steps with more real tokens count
            # proportionally).
            part["moe_aux_w"] = metrics["moe_aux"] * metrics["weight"]
        if self._sums is None:
            self._sums = part
        else:
            self._sums = {k: self._sums.get(k, 0.0) + part[k] for k in part}

    def _get(self, key: str) -> float:
        return 0.0 if self._sums is None else float(self._sums[key])

    @property
    def loss_sum(self) -> float:
        return self._get("loss_sum")

    @property
    def weight(self) -> float:
        return self._get("weight")

    @property
    def correct(self) -> float:
        return self._get("correct")

    @property
    def loss(self) -> float:
        return self.loss_sum / max(self.weight, 1.0)

    @property
    def accuracy(self) -> float:
        return self.correct / max(self.weight, 1.0)

    @property
    def moe_aux(self) -> float | None:
        """Token-weighted mean MoE load-balance loss, or None for dense runs."""
        if self._sums is None or "moe_aux_w" not in self._sums:
            return None
        return float(self._sums["moe_aux_w"]) / max(self.weight, 1.0)


def _dispatch_groups(batches, k: int):
    """Group consecutive SAME-SHAPE batches into stacks of up to ``k`` for
    the multi-step dispatch path: yields ``(src, tgt, n)`` with src/tgt
    stacked to (n, B, S) when n > 1, or the single batch unstacked when a
    group has one member (shape change mid-group, epoch tail). Grouping
    only ever joins identical shapes, so length-bucketed pipelines work —
    each distinct (n, B, S) signature costs one jit re-trace, bounded by
    #buckets × #tail-lengths per run."""
    buf: list = []
    sig = None
    for b in batches:
        s = (b[0].shape, b[1].shape)
        if buf and s != sig:
            yield _stack_group(buf)
            buf = []
        buf.append(b)
        sig = s
        if len(buf) == k:
            yield _stack_group(buf)
            buf = []
    if buf:
        yield _stack_group(buf)


def _stack_group(buf: list):
    if len(buf) == 1:
        src, tgt = buf[0]
        return src, tgt, 1
    return (
        np.stack([b[0] for b in buf]),
        np.stack([b[1] for b in buf]),
        len(buf),
    )


class Trainer:
    """Epoch-driven training loop.

    ``enable_function=False`` runs the steps un-jitted — the reference's eager
    debug mode (``--enable_function``, ``train.py:175-177``).
    """

    def __init__(
        self,
        model_cfg: ModelConfig,
        train_cfg: TrainConfig,
        state: TrainState,
        log_dir: str | None = None,
        checkpoint: CheckpointManager | None = None,
        donate_state: bool = True,
        log_fn: Callable[[str], None] = print,
        profiler: "Profiler | None" = None,
        telemetry=None,
    ) -> None:
        self.model_cfg = model_cfg
        self.train_cfg = train_cfg
        self.state = state
        self.checkpoint = checkpoint
        self.log_fn = log_fn
        self.profiler = profiler
        self.step_timer = StepTimer(
            tokens_per_step=train_cfg.batch_size * train_cfg.sequence_length
        )
        self.train_metrics = MetricAccumulator()
        self.eval_metrics = MetricAccumulator()
        self.writers = {}
        if log_dir:
            self.writers = {
                "train": SummaryWriter(f"{log_dir}/train"),
                "test": SummaryWriter(f"{log_dir}/test"),
            }
        # Telemetry (obs.Telemetry | None): host-side recording at the sync
        # points the loop already has (log/eval/epoch boundaries) — zero new
        # device ops, zero recompiles (analysis telemetry_inert contract).
        self.telemetry = telemetry
        # Spans (train.fit/train.step/train.data_wait/train.eval/ckpt.*) on
        # the "train" lane: always into the in-memory buffer, into the event
        # log under --trace, into the profiler's trace while one is taken.
        self._tracer = mirrored_tracer(telemetry)
        self._last_metrics: dict | None = None
        self._window_mark = (0, 0, 0.0)  # (steps, tokens, time) at last record
        if telemetry is not None:
            reg = telemetry.registry
            self._m_loss = reg.gauge("train_loss", "streaming epoch train loss")
            self._m_acc = reg.gauge("train_accuracy", "streaming token accuracy")
            self._m_gnorm = reg.gauge("train_grad_norm", "latest global grad norm")
            self._m_eloss = reg.gauge("train_eval_loss", "latest eval loss")
            self._m_eacc = reg.gauge("train_eval_accuracy", "latest eval accuracy")
            self._m_steps = reg.counter("train_steps_total", "optimizer steps")
            self._m_tokens = reg.counter("train_tokens_total", "target tokens")
            # Bound to the SAME sample stream StepTimer populates — the
            # registry exports it, no duplicate quantile accounting.
            reg.histogram(
                "train_step_seconds", "per-step wall time (synced windows)",
                hist=self.step_timer.histogram,
            )

        train_step = make_train_step(model_cfg, train_cfg)
        eval_step = make_eval_step(model_cfg, train_cfg)
        self.multi_step = None
        if train_cfg.enable_function:
            if train_cfg.steps_per_dispatch > 1:
                # K optimizer steps per host dispatch (one jitted scan):
                # amortizes the per-step dispatch overhead. jit re-traces per
                # distinct stacked shape (tail groups, length buckets) and
                # caches.
                self.multi_step = jax.jit(
                    make_multistep_train_step(
                        train_step,
                        has_moe=bool(model_cfg.moe_experts),
                        loss_normalization=train_cfg.loss_normalization,
                        batch_size=train_cfg.batch_size,
                    ),
                    donate_argnums=(0,) if donate_state else (),
                )
            # Donating the state buffers lets XLA update params in place —
            # halves peak HBM for the optimizer step.
            train_step = jax.jit(train_step, donate_argnums=(0,) if donate_state else ())
            eval_step = jax.jit(eval_step)
        self.train_step = train_step
        self.eval_step = eval_step
        self._wrap_steps_for_dispatch_timing()

    def _wrap_steps_for_dispatch_timing(self) -> None:
        """Route the step callables through ``obs.telemetry.timed_call`` —
        the jaxpr-inert wrapper the ``telemetry_inert`` contract pins. Under
        async dispatch this histogram measures host dispatch latency (a
        host-stall detector); StepTimer's synced windows stay the
        device-throughput source of truth. DistributedTrainer re-invokes
        this after swapping in its sharded steps. With or without a
        telemetry bundle the same callables run through
        ``obs.trace.traced_call`` — one ``train.step`` span per dispatch,
        parented under the open ``train.fit`` span (the contract pins that
        wrapper's jaxpr inertness too). The wrappers chain ``__wrapped__``,
        and every probe that needs the jitted fn unwraps the CHAIN, not one
        level."""
        if self.telemetry is not None:
            self._m_dispatch = self.telemetry.registry.histogram(
                "train_dispatch_seconds", "host dispatch latency per step call"
            )
            self.train_step = timed_call(self.train_step, self._m_dispatch)
            if self.multi_step is not None:
                self.multi_step = timed_call(self.multi_step, self._m_dispatch)
        self.train_step = traced_call(
            self.train_step, self._tracer, "train.step", lane="train"
        )
        if self.multi_step is not None:
            self.multi_step = traced_call(
                self.multi_step, self._tracer, "train.step", lane="train"
            )

    # ------------------------------------------------------------------ loop
    def _span(self, name: str, **attrs):
        """A ``train``-lane tracing span — the trainer's sites all parent
        via the thread-local stack (everything nests under the ``train.fit``
        root)."""
        return self._tracer.span(name, lane="train", **attrs)

    def evaluate(
        self,
        batches: Iterable,
        max_batches: int | None = None,
        guard: "PreemptionGuard | None" = None,
    ) -> None:
        with self._span("train.eval"):
            self.eval_metrics.reset()
            for i, (src, tgt) in enumerate(batches):
                if max_batches is not None and i >= max_batches:
                    break
                if guard is not None and guard.should_stop:
                    return  # preemption: abandon eval, caller checkpoints
                m = self.eval_step(self.state, src, tgt)
                self.eval_metrics.update(m)

    def fit(
        self,
        train_ds,
        test_ds=None,
        rng: jax.Array | None = None,
        epoch_callback: Callable[[int, "Trainer"], object] | None = None,
    ) -> None:
        """Tracing wrapper: the whole run is one ``train.fit`` span —
        every step/eval/checkpoint span nests under it via the tracer's
        thread-local stack, and the ``with`` closes it on every exit path
        (returns, preemption, exceptions)."""
        with self._span("train.fit", epochs=self.train_cfg.epochs):
            self._fit(train_ds, test_ds, rng, epoch_callback)

    def _fit(
        self,
        train_ds,
        test_ds=None,
        rng: jax.Array | None = None,
        epoch_callback: Callable[[int, "Trainer"], object] | None = None,
    ) -> None:
        """``epoch_callback(epoch, trainer)``, if given, runs after each
        epoch's metrics/eval/summaries and before the checkpoint save —
        the hook for in-training quality tracking (e.g. periodic BLEU in
        ``benchmarks/bleu_run.py``). A truthy return value requests an
        early stop: the epoch's checkpoint is still saved, then the loop
        exits — the hook for metric-driven stopping rules (keep-best BLEU,
        ``train/probe_stop.py``) that watch something other than the eval
        loss the built-in ``early_stop_patience`` plateau rule uses."""
        cfg = self.train_cfg
        if cfg.steps_per_dispatch > 1 and self.multi_step is None:
            # Plain Trainer in eager-debug mode: no scanned step was built
            # (DistributedTrainer always jits and installs its own), so the
            # feature would silently no-op — refuse instead.
            raise ValueError(
                "steps_per_dispatch > 1 requires enable_function=True on the "
                "single-process Trainer: the multi-step dispatch is a jitted "
                "lax.scan; in eager-debug mode it would silently fall back "
                "to single-step dispatch"
            )
        rng = rng if rng is not None else jax.random.PRNGKey(cfg.seed)
        self._emit_cost_prediction()
        # Restore BEFORE training (fixes reference restore-after, train.py:242-243).
        if self.checkpoint is not None:
            def _ckpt_fallback(step, exc):
                self.log_fn(
                    f"checkpoint at step {step} unreadable "
                    f"({type(exc).__name__}); falling back"
                )
                if self.telemetry is not None:
                    self.telemetry.emit(
                        "ckpt.fallback", step=int(step),
                        reason=f"{type(exc).__name__}: {exc}",
                    )

            with self._span("ckpt.restore"):
                restored = self.checkpoint.restore_latest(
                    self.state, on_fallback=_ckpt_fallback
                )
            if restored is not None:
                self.state = restored
                self.log_fn(f"restored checkpoint at step {int(self.state.step)}")

        # Host-side step mirror: consulting state.step (a device array) every
        # iteration would block async dispatch.
        step = int(self.state.step)
        # Resume at the right EPOCH, not just the right step: a restored run
        # must train only the remaining epochs (and continue the (seed,
        # epoch)-keyed data order), not cfg.epochs more. Possible only when
        # the dataset advertises its per-epoch length.
        start_epoch = 0
        try:
            steps_per_epoch = len(train_ds)
        except TypeError:
            steps_per_epoch = 0
        if step and steps_per_epoch:
            start_epoch = min(step // steps_per_epoch, cfg.epochs)
            if start_epoch:
                self.log_fn(
                    f"resuming at epoch {start_epoch + 1}/{cfg.epochs} "
                    f"(step {step})"
                )
        if cfg.early_stop_patience and self._early_stop_marker_exists():
            # A previous run of this checkpoint directory already stopped on
            # an eval-loss plateau; a relaunch (job-scheduler retry) must not
            # train past it and overwrite the early-stopped checkpoint.
            self.log_fn(
                "early-stop marker present in checkpoint dir; not training "
                "further (delete the EARLY_STOPPED file to continue)"
            )
            return
        best_eval = float("inf")
        epochs_since_best = 0
        if cfg.early_stop_patience:
            # Plateau accounting is persisted next to the checkpoints (a tiny
            # sidecar JSON, written by the primary process at every save):
            # a preempted-and-resumed run continues its patience window
            # instead of restarting it and training `patience` extra epochs.
            best_eval, epochs_since_best = self._load_plateau_state(step)
            if epochs_since_best:
                self.log_fn(
                    f"resumed early-stop window: best eval {best_eval:.4f}, "
                    f"{epochs_since_best} epoch(s) without improvement"
                )
        with PreemptionGuard() as guard:
            for epoch in range(start_epoch, cfg.epochs):
                self.train_metrics.reset()
                self.step_timer.reset()
                self._window_mark = (0, 0, 0.0)
                epoch_start = time.time()
                batch_iter = train_ds.batches(epoch)
                if self.multi_step is not None:
                    groups = _dispatch_groups(batch_iter, cfg.steps_per_dispatch)
                else:
                    groups = ((s, t, 1) for s, t in batch_iter)
                while True:
                    # What the loop waits for the input pipeline: one span a
                    # batch (or dispatch group), and one (``end``) for the
                    # call that finds the epoch exhausted.
                    with self._span("train.data_wait") as wait:
                        group = next(groups, None)
                        if group is None:
                            wait.set(end=True)
                            break
                    src, tgt, k = group
                    if self.profiler is not None:
                        self.profiler.maybe_trace(step, block_on=self.state)
                    if k == 1:
                        self.state, m = self.train_step(self.state, src, tgt, rng)
                        # Actual target tokens this step (length-bucketed
                        # batches are narrower than the nominal length).
                        tokens = src.shape[0] * max(tgt.shape[1] - 1, 1)
                    else:
                        # K stacked same-shape batches, one dispatch, K
                        # optimizer steps inside a jitted scan; metrics come
                        # back pre-reduced over the group.
                        self.state, m = self.multi_step(self.state, src, tgt, rng)
                        tokens = k * src.shape[1] * max(tgt.shape[2] - 1, 1)
                    self.train_metrics.update(m)
                    self._last_metrics = m  # host ref only; read at syncs
                    self.step_timer.tick(tokens, steps=k)
                    prev_step = step
                    step += k
                    if guard.should_stop:
                        self._preempt(step, guard)
                        return
                    # Boundary-crossing (not ==0) so a K-step dispatch that
                    # jumps over a log/eval boundary still triggers it; for
                    # k == 1 this is exactly the step % N == 0 cadence.
                    if cfg.log_every_steps and (
                        step // cfg.log_every_steps
                        != prev_step // cfg.log_every_steps
                    ):
                        loss = self.train_metrics.loss  # device_get: blocks
                        self.step_timer.sync()
                        aux = self.train_metrics.moe_aux
                        self.log_fn(
                            f"epoch {epoch + 1} step {step} "
                            f"loss {loss:.4f} "
                            f"acc {self.train_metrics.accuracy:.4f} "
                            + (f"moe_aux {aux:.3f} " if aux is not None else "")
                            + f"({self.step_timer.steps_per_sec:.2f} steps/s)"
                        )
                        self._record_train_window(epoch, step)
                    if (
                        test_ds is not None
                        and cfg.eval_every_steps
                        and step // cfg.eval_every_steps
                        != prev_step // cfg.eval_every_steps
                    ):
                        # Bounded in-loop eval (fixes reference full-test-set
                        # stall, train.py:193-195, and 1-batch quirk §2.3.3).
                        self.step_timer.sync()
                        self.evaluate(
                            test_ds.batches(epoch),
                            max_batches=cfg.eval_max_batches or None,
                            guard=guard,
                        )
                        self.log_fn(
                            f"  eval loss {self.eval_metrics.loss:.4f} "
                            f"acc {self.eval_metrics.accuracy:.4f}"
                        )
                        self._record_eval(epoch, step)

                epoch_loss = self.train_metrics.loss  # device_get: blocks
                self.step_timer.sync()
                if guard.should_stop:
                    self._preempt(step, guard)
                    return
                if test_ds is not None:
                    self.evaluate(test_ds.batches(epoch), guard=guard)
                    if guard.should_stop:
                        self._preempt(step, guard)
                        return
                    self._record_eval(epoch, step)
                self._write_epoch_summaries(epoch)
                self._record_train_window(epoch, step)
                self._record_epoch_telemetry(epoch, step)
                self.log_fn(
                    f"epoch {epoch + 1}/{cfg.epochs} done in "
                    f"{time.time() - epoch_start:.1f}s: "
                    f"loss {epoch_loss:.4f} "
                    f"acc {self.train_metrics.accuracy:.4f}; "
                    f"{self.step_timer.summary()}"
                )
                callback_stop = False
                if epoch_callback is not None:
                    callback_stop = bool(epoch_callback(epoch, self))
                stop_early = False
                if (
                    cfg.early_stop_patience
                    and test_ds is not None
                    and self.eval_metrics.weight > 0  # empty eval: no signal
                ):
                    # The full end-of-epoch eval above populated eval_metrics.
                    if self.eval_metrics.loss < best_eval - 1e-6:
                        best_eval = self.eval_metrics.loss
                        epochs_since_best = 0
                    else:
                        epochs_since_best += 1
                        stop_early = epochs_since_best >= cfg.early_stop_patience
                self._best_eval = best_eval
                self._epochs_since_best = epochs_since_best
                if self.checkpoint is not None and (
                    (epoch + 1) % cfg.checkpoint_every_epochs == 0
                    or (epoch + 1) == cfg.epochs
                    or stop_early
                    or callback_stop
                ):
                    with self._span("ckpt.save", step=step):
                        self.checkpoint.save(self.state)
                    if cfg.early_stop_patience:
                        self._save_plateau_state(step)
                if stop_early:
                    self.log_fn(
                        f"early stop after epoch {epoch + 1}: eval loss has "
                        f"not improved for {epochs_since_best} epoch(s) "
                        f"(best {best_eval:.4f})"
                    )
                    self._mark_early_stopped(epoch + 1)
                    break
                if callback_stop:
                    # The callback owns its own stop persistence (e.g. the
                    # probe tracker's JSON) — no EARLY_STOPPED marker here,
                    # that file gates the plateau rule's resume path.
                    self.log_fn(
                        f"stop requested by epoch callback after epoch "
                        f"{epoch + 1}"
                    )
                    break
        if self.checkpoint is not None:
            # Async managers write in the background; don't return (or let the
            # process exit) with the final checkpoint still uncommitted.
            self.checkpoint.wait()
        if self.profiler is not None:
            self.profiler.stop(block_on=self.state)
        if self.telemetry is not None:
            self.telemetry.maybe_flush(force=True)

    # ------------------------------------------------------------- telemetry
    # All recorders run at points where the loop has ALREADY paid a blocking
    # metric read (train_metrics.loss / eval_metrics.loss device_get) and a
    # step_timer.sync() — they add host float reads, never device ops.

    def _record_train_window(self, epoch: int, step: int) -> None:
        if self.telemetry is None:
            return
        st = self.step_timer
        m_steps, m_tokens, m_time = self._window_mark
        d_steps = st.count - m_steps
        if d_steps <= 0:
            return
        d_tokens = st.total_tokens - m_tokens
        window_s = st.total_time_s - m_time
        self._window_mark = (st.count, st.total_tokens, st.total_time_s)
        loss = self.train_metrics.loss
        acc = self.train_metrics.accuracy
        self._m_loss.set(loss)
        self._m_acc.set(acc)
        self._m_steps.inc(d_steps)
        self._m_tokens.inc(d_tokens)
        event = {
            "epoch": epoch + 1, "step": step, "steps": d_steps,
            "tokens": d_tokens, "window_s": round(window_s, 6),
            "loss": round(loss, 6), "accuracy": round(acc, 6),
        }
        if window_s > 0:
            event["steps_per_sec"] = round(d_steps / window_s, 3)
            event["tokens_per_sec"] = round(d_tokens / window_s, 1)
        if self._last_metrics is not None and "grad_norm" in self._last_metrics:
            gnorm = float(self._last_metrics["grad_norm"])
            self._m_gnorm.set(gnorm)
            event["grad_norm"] = round(gnorm, 6)
        self.telemetry.emit("train.window", **event)
        self.telemetry.maybe_flush()

    def _record_eval(self, epoch: int, step: int) -> None:
        if self.telemetry is None or self.eval_metrics.weight <= 0:
            return
        loss, acc = self.eval_metrics.loss, self.eval_metrics.accuracy
        self._m_eloss.set(loss)
        self._m_eacc.set(acc)
        self.telemetry.emit(
            "train.eval", epoch=epoch + 1, step=step,
            loss=round(loss, 6), accuracy=round(acc, 6),
        )

    def _emit_cost_prediction(self) -> None:
        """One ``train.predicted`` event at fit start: the jaxpr cost
        model's peak-bytes/FLOPs estimate for THIS run's plain train step
        (``analysis/costs.py``, abstract trace — no device execution).
        ``obs summarize`` cross-checks it against the ``train.memory``
        samples ``_record_epoch_telemetry`` records from
        ``device.memory_stats()`` and reports the measured/predicted ratio.
        Single-device prediction: sharded/pipelined trainers inherit it as
        a per-replica upper bound, and summarize stays tolerant when the
        event is absent. Purely advisory, so it must never break training.
        Emitted once per Trainer — callers (cli/train.py length-bucket
        loops) may invoke fit() repeatedly on the same step functions."""
        if self.telemetry is None or getattr(self, "_cost_predicted", False):
            return
        self._cost_predicted = True
        try:
            from transformer_tpu.analysis.costs import train_step_costs

            r = train_step_costs(self.model_cfg, self.train_cfg)
        except Exception as e:  # tpa: disable=TPA006 — advisory-only: any config the cost model cannot trace (custom forwards, exotic objectives) must degrade to "no prediction", never to a failed training run
            self.log_fn(f"cost-model prediction unavailable ({type(e).__name__}: {e})")
            return
        self.telemetry.registry.gauge(
            "train_predicted_peak_bytes",
            "jaxpr cost model: train-step peak live-buffer bytes",
        ).set(r.peak_bytes)
        self.telemetry.emit(
            "train.predicted",
            program="train_step",
            peak_bytes=r.peak_bytes,
            flops=r.flops,
            bytes_moved=r.bytes_moved,
            tokens_per_step=r.extras.get("tokens_per_step"),
        )

    def _record_epoch_telemetry(self, epoch: int, step: int) -> None:
        """Epoch-boundary extras: device memory stats (where the backend
        exposes them) and jit compile-cache accounting — recompiles surface
        as a visible counter, not just a retrace-sentinel test failure."""
        if self.telemetry is None:
            return
        from transformer_tpu.obs import device_memory_stats

        devices = {}
        for d in jax.local_devices():
            stats = device_memory_stats(d)
            if stats:
                devices[str(d.id)] = stats
        if devices:
            first = next(iter(devices.values()))
            for key in ("bytes_in_use", "peak_bytes_in_use"):
                if key in first:
                    self.telemetry.registry.gauge(
                        f"device_{key}", "PJRT allocator stats, device 0"
                    ).set(first[key])
            self.telemetry.emit(
                "train.memory", epoch=epoch + 1, step=step, devices=devices
            )
        cache_sizes = {}
        # *_fn variants: DistributedTrainer keeps the jitted sharded steps
        # there (its train_step attribute is a host-side placement wrapper).
        for name in ("train_step", "multi_step", "eval_step",
                     "train_step_fn", "multi_step_fn", "eval_step_fn"):
            fn = getattr(self, name, None)
            # Through the telemetry wrapper chain (timed_call, traced_call —
            # tracing adds a second __wrapped__ layer), stopping at the
            # jitted callable: jax.jit ALSO sets __wrapped__, and unwrapping
            # past it would reach the raw Python fn, which has no cache.
            while (
                fn is not None
                and not hasattr(fn, "_cache_size")
                and hasattr(fn, "__wrapped__")
            ):
                fn = fn.__wrapped__
            probe = getattr(fn, "_cache_size", None)
            if probe is not None:
                # The same accounting the analysis/retrace.py sentinel
                # budgets: compiled-program counts per jitted hot path.
                cache_sizes[name] = int(probe())
        if cache_sizes:
            self.telemetry.registry.gauge(
                "train_compiled_programs",
                "compiled executables across the jitted step caches",
            ).set(sum(cache_sizes.values()))
            self.telemetry.emit(
                "train.compile", epoch=epoch + 1, step=step,
                cache_sizes=cache_sizes,
            )
        self.telemetry.maybe_flush(force=True)

    # ---------------------------------------------------------- plateau state
    # Host-side early-stop accounting, persisted so crash-resume keeps the
    # patience window (round-2 VERDICT weak #8). Same writer discipline as
    # the EARLY_STOPPED marker: primary process writes, everyone reads.
    _best_eval: float = float("inf")
    _epochs_since_best: int = 0

    def _plateau_state_path(self) -> str | None:
        if self.checkpoint is None:
            return None
        import os

        return os.path.join(self.checkpoint.directory, "plateau.json")

    def _load_plateau_state(self, step: int) -> tuple[float, int]:
        import json
        import os

        path = self._plateau_state_path()
        if path is None or not os.path.exists(path):
            return float("inf"), 0
        try:
            with open(path) as f:
                d = json.load(f)
        except (ValueError, OSError):
            return float("inf"), 0
        if int(d.get("step", -1)) > step:
            # Sidecar is ahead of the restored checkpoint (an older rotation
            # slot was restored): its counters describe evals this run will
            # redo — reset rather than double-count them.
            return float("inf"), 0
        return (
            float(d.get("best_eval", float("inf"))),
            int(d.get("epochs_since_best", 0)),
        )

    def _save_plateau_state(self, step: int) -> None:
        import json
        import os

        path = self._plateau_state_path()
        if path is None or not getattr(self.checkpoint, "is_primary", True):
            return
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(
                {
                    "step": step,
                    "best_eval": self._best_eval,
                    "epochs_since_best": self._epochs_since_best,
                },
                f,
            )
        os.replace(tmp, path)

    def _early_stop_marker_path(self) -> str | None:
        if self.checkpoint is None:
            return None
        import os

        return os.path.join(self.checkpoint.directory, "EARLY_STOPPED")

    def _early_stop_marker_exists(self) -> bool:
        import os

        path = self._early_stop_marker_path()
        return path is not None and os.path.exists(path)

    def _mark_early_stopped(self, epoch: int) -> None:
        path = self._early_stop_marker_path()
        if path is None or not getattr(self.checkpoint, "is_primary", True):
            return
        with open(path, "w") as f:
            f.write(f"early stop after epoch {epoch}\n")

    def _preempt(self, step: int, guard: "PreemptionGuard") -> None:
        """Graceful shutdown on SIGTERM/SIGINT: checkpoint, flush, report."""
        if self.profiler is not None:
            self.profiler.stop(block_on=self.state)
        prefix = f"preemption (signal {guard.signal_received}) at step {step}: "
        if self.checkpoint is not None:
            with self._span("ckpt.save", step=step, preempt=True):
                path = self.checkpoint.save(self.state)
                # The save must be durable before we report it (and exit).
                self.checkpoint.wait()
            if self.train_cfg.early_stop_patience:
                self._save_plateau_state(step)
            if path is not None:
                self.log_fn(prefix + f"checkpoint saved to {path}")
            else:
                # Non-primary process in a multi-host run: host 0 persists.
                self.log_fn(prefix + "checkpoint written by primary process")
        else:
            self.log_fn(prefix + "no checkpoint manager configured, state lost")
        for w in self.writers.values():
            w.flush()
        if self.telemetry is not None:
            self.telemetry.emit(
                "train.preempt", step=step, signal=guard.signal_received
            )
            self.telemetry.maybe_flush(force=True)

    def _write_epoch_summaries(self, epoch: int) -> None:
        if not self.writers:
            return
        from transformer_tpu.train.state import make_lr_schedule

        w = self.writers["train"]
        w.scalar("loss", self.train_metrics.loss, epoch)
        w.scalar("accuracy", self.train_metrics.accuracy, epoch)
        if self.train_metrics.moe_aux is not None:
            w.scalar("moe_aux", self.train_metrics.moe_aux, epoch)
        lr = make_lr_schedule(self.model_cfg, self.train_cfg)(
            int(jax.device_get(self.state.step))
        )
        w.scalar("learning_rate", float(lr), epoch)
        w.scalar("tokens_per_sec", self.step_timer.tokens_per_sec, epoch)
        if self._last_metrics is not None and "grad_norm" in self._last_metrics:
            w.scalar("grad_norm", float(self._last_metrics["grad_norm"]), epoch)
        # Step-duration distribution (p50/p95/p99 in TensorBoard's histogram
        # dashboard) — the tfevents face of the obs step-time histogram.
        w.histogram("step_time_s", self.step_timer.histogram, epoch)
        w.flush()
        if self.eval_metrics.weight > 0:
            w = self.writers["test"]
            w.scalar("loss", self.eval_metrics.loss, epoch)
            w.scalar("accuracy", self.eval_metrics.accuracy, epoch)
            w.flush()
