"""Training-engine tests (SURVEY.md §4 plan): schedule curve, loss masking,
label smoothing, checkpoint round-trip + rotation, overfit-one-batch
integration, greedy decode EOS semantics, TensorBoard wire format, BLEU."""

import math
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transformer_tpu.config import ModelConfig, TrainConfig
from transformer_tpu.models import transformer_init
from transformer_tpu.train import (
    CheckpointManager,
    create_train_state,
    greedy_decode,
    make_eval_step,
    make_train_step,
    masked_cross_entropy,
    noam_schedule,
)
from transformer_tpu.train.checkpoint import export_params, load_exported_params
from transformer_tpu.train.decode import translate
from transformer_tpu.utils.bleu import corpus_bleu
from transformer_tpu.utils.tensorboard import SummaryWriter, _masked_crc

TINY = ModelConfig(
    num_layers=1, d_model=16, num_heads=2, dff=32,
    input_vocab_size=30, target_vocab_size=30, max_position=32, dtype="float32",
    dropout_rate=0.0,
)
TCFG = TrainConfig(batch_size=4, sequence_length=8, epochs=1, warmup_steps=100)


class TestSchedule:
    def test_noam_curve(self):
        """Closed-form check: rises linearly to warmup, then decays as
        rsqrt(step) (reference train.py:30-34)."""
        sched = noam_schedule(d_model=512, warmup_steps=4000)
        s = np.asarray([sched(i) for i in [0, 999, 3999, 7999, 99999]])
        # linear region: lr(1000)/lr(4000) ≈ 1000/4000
        np.testing.assert_allclose(s[1] / s[2], 1000 / 4000, rtol=1e-4)
        # peak at warmup boundary
        expected_peak = 512**-0.5 * 4000**-0.5
        np.testing.assert_allclose(s[2], expected_peak, rtol=1e-4)
        # decay region: lr ∝ step^-0.5
        np.testing.assert_allclose(s[3] / s[4], (100000 / 8000) ** 0.5, rtol=1e-3)

    def test_warmup_default_matches_reference(self):
        assert TrainConfig().warmup_steps == 60000

    def test_cosine_curve(self):
        from transformer_tpu.train.schedule import cosine_schedule

        sched = cosine_schedule(1e-3, warmup_steps=100, decay_steps=1000)
        # Linear warmup hits the peak at the boundary.
        np.testing.assert_allclose(float(sched(99)), 1e-3, rtol=1e-5)
        np.testing.assert_allclose(float(sched(49)), 5e-4, rtol=2e-2)
        # Midpoint of the cosine: halfway between peak and floor.
        np.testing.assert_allclose(float(sched(550)), (1e-3 + 1e-4) / 2, rtol=1e-4)
        # Floor (peak/10) at and beyond the horizon.
        np.testing.assert_allclose(float(sched(1000)), 1e-4, rtol=1e-5)
        np.testing.assert_allclose(float(sched(5000)), 1e-4, rtol=1e-5)

    def test_constant_curve(self):
        from transformer_tpu.train.schedule import constant_schedule

        sched = constant_schedule(3e-4, warmup_steps=10)
        np.testing.assert_allclose(float(sched(4)), 1.5e-4, rtol=1e-5)
        np.testing.assert_allclose(float(sched(10)), 3e-4, rtol=1e-6)
        np.testing.assert_allclose(float(sched(9999)), 3e-4, rtol=1e-6)

    def test_cosine_trains_through_config(self):
        import dataclasses

        tc = dataclasses.replace(
            TCFG, lr_schedule="cosine", peak_lr=1e-3,
            warmup_steps=20, lr_decay_steps=200,
        )
        state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        step = jax.jit(make_train_step(TINY, tc))
        r = np.random.default_rng(0)
        src = jnp.asarray(r.integers(1, 28, (4, 8)), jnp.int32)
        tgt = jnp.asarray(r.integers(1, 28, (4, 8)), jnp.int32)
        rng = jax.random.PRNGKey(1)
        first = None
        for _ in range(60):
            state, m = step(state, src, tgt, rng)
            first = float(m["loss"]) if first is None else first
        assert float(m["loss"]) < first * 0.6

    def test_cosine_requires_peak_and_horizon(self):
        with pytest.raises(ValueError, match="peak_lr"):
            TrainConfig(lr_schedule="cosine", lr_decay_steps=10**6)
        with pytest.raises(ValueError, match="lr_decay_steps"):
            TrainConfig(lr_schedule="cosine", peak_lr=1e-3)


class TestLoss:
    def test_pad_positions_contribute_zero(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 10))
        targets = jnp.array([[1, 2, 0, 0], [3, 0, 0, 0]])
        loss, m = masked_cross_entropy(logits, targets)
        assert float(m["weight"]) == 3.0
        # changing logits at pad positions must not change the loss
        logits2 = logits.at[:, 2:, :].add(100.0)
        loss2, _ = masked_cross_entropy(logits2, targets)
        np.testing.assert_allclose(float(loss), float(loss2), rtol=1e-6)

    def test_matches_numpy_oracle(self):
        logits = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 5))
        targets = jnp.array([[1, 2, 3], [4, 1, 0]])
        loss, _ = masked_cross_entropy(logits, targets)
        lp = np.asarray(jax.nn.log_softmax(logits, -1), dtype=np.float64)
        t = np.asarray(targets)
        per = -lp[np.arange(2)[:, None], np.arange(3)[None, :], t]
        mask = t != 0
        np.testing.assert_allclose(float(loss), per[mask].mean(), rtol=1e-5)

    def test_batch_normalization_parity(self):
        """'batch' mode reproduces the reference rule: sum/batch_size
        (train.py:88)."""
        logits = jax.random.normal(jax.random.PRNGKey(2), (4, 3, 5))
        targets = jnp.ones((4, 3), jnp.int32)
        loss, m = masked_cross_entropy(
            logits, targets, normalization="batch", batch_size=4
        )
        np.testing.assert_allclose(float(loss), float(m["loss_sum"]) / 4, rtol=1e-6)

    def test_label_smoothing_raises_loss_on_confident_model(self):
        logits = jnp.full((1, 2, 5), -10.0).at[..., 1].set(10.0)
        targets = jnp.ones((1, 2), jnp.int32)
        sharp, _ = masked_cross_entropy(logits, targets)
        smooth, _ = masked_cross_entropy(logits, targets, label_smoothing=0.1)
        assert float(smooth) > float(sharp)


class _FixedBatches:
    """Minimal dataset stub: the same batch ``n`` times per epoch."""

    def __init__(self, n=4, seed=0):
        self.n = n
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        self.src = np.asarray(jax.random.randint(k1, (4, 8), 1, 30))
        self.tgt = np.asarray(jax.random.randint(k2, (4, 8), 1, 30))

    def __len__(self):
        return self.n

    def batches(self, epoch=0):
        for _ in range(self.n):
            yield self.src, self.tgt


class _VariedBatches:
    """Dataset stub with per-step-DISTINCT batches (so trajectory parity is
    meaningful) and an optional narrower final batch (so the multi-step
    grouper's shape-change flush is exercised)."""

    def __init__(self, n=7, seed=0, narrow_last=False):
        self.n = n
        self.seed = seed
        self.narrow_last = narrow_last

    def __len__(self):
        return self.n

    def batches(self, epoch=0):
        for i in range(self.n):
            k = jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch * 1000 + i)
            k1, k2 = jax.random.split(k)
            w = 6 if (self.narrow_last and i == self.n - 1) else 8
            yield (
                np.asarray(jax.random.randint(k1, (4, w), 1, 30)),
                np.asarray(jax.random.randint(k2, (4, w), 1, 30)),
            )


class TestMultistepDispatch:
    @pytest.mark.slow  # heavyweight: slow tier (fast tier keeps a specimen)
    def test_scan_matches_sequential(self):
        """K optimizer steps inside one jitted scan (steps_per_dispatch)
        must reproduce K separate dispatches: same params, same metric sums
        (pre-reduced on device)."""
        from transformer_tpu.train.trainer import make_multistep_train_step

        K = 4
        rng = jax.random.PRNGKey(3)
        srcs = np.asarray(
            jax.random.randint(jax.random.PRNGKey(1), (K, 4, 8), 1, 30)
        )
        tgts = np.asarray(
            jax.random.randint(jax.random.PRNGKey(2), (K, 4, 8), 1, 30)
        )
        step = make_train_step(TINY, TCFG)

        s_ref = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        jstep = jax.jit(step)
        sums = {"loss_sum": 0.0, "weight": 0.0, "correct": 0.0}
        for i in range(K):
            s_ref, m = jstep(s_ref, srcs[i], tgts[i], rng)
            for k in sums:
                sums[k] += float(m[k])

        s_multi = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        multi = jax.jit(make_multistep_train_step(step))
        s_multi, mm = multi(s_multi, srcs, tgts, rng)

        assert int(s_multi.step) == K
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
            s_ref.params, s_multi.params,
        )
        for k in sums:
            np.testing.assert_allclose(float(mm[k]), sums[k], rtol=1e-5, err_msg=k)
        np.testing.assert_allclose(
            float(mm["loss"]), sums["loss_sum"] / max(sums["weight"], 1.0),
            rtol=1e-5,
        )

    @pytest.mark.slow  # heavyweight: slow tier (fast tier keeps a specimen)
    def test_trainer_trajectory_parity(self):
        """A full Trainer.fit with steps_per_dispatch=3 over 7 varied batches
        (groups 3+3+1, final batch a different width → shape-change flush)
        must land on the same params and epoch metrics as the plain loop."""
        import dataclasses

        from transformer_tpu.train import Trainer

        def run(spd):
            tc = dataclasses.replace(
                TCFG, epochs=2, warmup_steps=10, steps_per_dispatch=spd,
                eval_every_steps=0, log_every_steps=0,
            )
            state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
            tr = Trainer(TINY, tc, state, log_fn=lambda s: None)
            tr.fit(_VariedBatches(n=7, seed=5, narrow_last=True))
            return tr

        ref, multi = run(1), run(3)
        assert int(multi.state.step) == 14
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
            ref.state.params, multi.state.params,
        )
        np.testing.assert_allclose(
            multi.train_metrics.loss, ref.train_metrics.loss, rtol=1e-5
        )
        np.testing.assert_allclose(
            multi.train_metrics.accuracy, ref.train_metrics.accuracy, rtol=1e-5
        )

    def test_log_eval_boundary_crossing(self):
        """A K-step dispatch that jumps OVER a log/eval boundary must still
        trigger the log/eval (boundary-crossing check, not step % N == 0)."""
        import dataclasses

        from transformer_tpu.train import Trainer

        tc = dataclasses.replace(
            TCFG, epochs=1, warmup_steps=10, steps_per_dispatch=3,
            log_every_steps=5, eval_every_steps=5, eval_max_batches=1,
        )
        state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        logs = []
        tr = Trainer(TINY, tc, state, log_fn=logs.append)
        # 6 identical-shape batches -> dispatches end at steps 3 and 6;
        # step 5 is never hit exactly, but 3->6 crosses it.
        tr.fit(_FixedBatches(n=6, seed=0), _FixedBatches(n=1, seed=7))
        assert any("step 6 " in l for l in logs), logs
        assert any("eval loss" in l for l in logs), logs

    def test_rejects_bad_config(self):
        import dataclasses

        with pytest.raises(ValueError, match="steps_per_dispatch"):
            dataclasses.replace(TCFG, steps_per_dispatch=0)

    def test_rejects_eager_mode(self):
        import dataclasses

        from transformer_tpu.train import Trainer

        tc = dataclasses.replace(
            TCFG, steps_per_dispatch=2, enable_function=False
        )
        state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        tr = Trainer(TINY, tc, state, log_fn=lambda s: None)
        # The guard fires at fit() time, where only the plain eager Trainer
        # lacks a scanned step (DistributedTrainer always jits its own).
        with pytest.raises(ValueError, match="enable_function"):
            tr.fit(_FixedBatches(n=2, seed=0))

    @pytest.mark.slow  # heavyweight: slow tier (fast tier keeps a specimen)
    def test_batch_normalization_loss_metric(self):
        """Under loss_normalization='batch' the per-dispatch 'loss' must be
        the mean of the K per-step batch-normalized losses, not the
        token-normalized ratio."""
        import dataclasses

        from transformer_tpu.train.trainer import make_multistep_train_step

        cfg = dataclasses.replace(TCFG, loss_normalization="batch")
        K = 3
        srcs = np.asarray(
            jax.random.randint(jax.random.PRNGKey(1), (K, 4, 8), 1, 30)
        )
        tgts = np.asarray(
            jax.random.randint(jax.random.PRNGKey(2), (K, 4, 8), 1, 30)
        )
        rng = jax.random.PRNGKey(3)
        step = make_train_step(TINY, cfg)

        s_ref = create_train_state(jax.random.PRNGKey(0), TINY, cfg)
        jstep = jax.jit(step)
        per_step = []
        for i in range(K):
            s_ref, m = jstep(s_ref, srcs[i], tgts[i], rng)
            per_step.append(float(m["loss"]))

        s_multi = create_train_state(jax.random.PRNGKey(0), TINY, cfg)
        multi = jax.jit(
            make_multistep_train_step(
                step, loss_normalization="batch", batch_size=cfg.batch_size
            )
        )
        _, mm = multi(s_multi, srcs, tgts, rng)
        np.testing.assert_allclose(
            float(mm["loss"]), np.mean(per_step), rtol=1e-5
        )


class TestEarlyStopping:
    def test_stops_when_eval_plateaus(self):
        """Overfitting a fixed batch while evaluating on a DIFFERENT fixed
        batch: eval loss rises/plateaus once the model memorizes, so
        patience=2 must end the run well before the epoch budget."""
        import dataclasses

        from transformer_tpu.train import Trainer

        tc = dataclasses.replace(
            TCFG, epochs=40, warmup_steps=10, early_stop_patience=2,
            eval_every_steps=0, log_every_steps=0,
        )
        state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        logs = []
        tr = Trainer(TINY, tc, state, log_fn=logs.append)
        tr.fit(_FixedBatches(n=8, seed=0), _FixedBatches(n=2, seed=7))
        done = [l for l in logs if "done in" in l]
        assert any("early stop" in l for l in logs), logs[-3:]
        assert len(done) < 40  # stopped before the epoch budget

    @pytest.mark.slow  # heavyweight: slow tier (fast tier keeps a specimen)
    def test_marker_blocks_relaunch(self, tmp_path):
        """A relaunch after an early stop must not retrain past the stopped
        checkpoint (job-scheduler retries would otherwise overwrite it)."""
        import dataclasses

        from transformer_tpu.train import Trainer

        tc = dataclasses.replace(
            TCFG, epochs=40, warmup_steps=10, early_stop_patience=2,
            eval_every_steps=0, log_every_steps=0, checkpoint_every_epochs=1,
        )
        mgr = CheckpointManager(str(tmp_path), max_to_keep=2, is_primary=True)
        state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        logs = []
        tr = Trainer(TINY, tc, state, checkpoint=mgr, log_fn=logs.append)
        tr.fit(_FixedBatches(n=8, seed=0), _FixedBatches(n=2, seed=7))
        assert any("early stop" in l for l in logs)
        assert (tmp_path / "EARLY_STOPPED").exists()
        saved_steps = mgr.all_steps()

        relaunch_logs = []
        state2 = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        mgr2 = CheckpointManager(str(tmp_path), max_to_keep=2, is_primary=True)
        tr2 = Trainer(TINY, tc, state2, checkpoint=mgr2, log_fn=relaunch_logs.append)
        tr2.fit(_FixedBatches(n=8, seed=0), _FixedBatches(n=2, seed=7))
        assert any("marker present" in l for l in relaunch_logs)
        assert not any("done in" in l for l in relaunch_logs)  # no training
        assert mgr2.all_steps() == saved_steps  # checkpoints untouched

    @pytest.mark.slow  # heavyweight: slow tier (fast tier keeps a specimen)
    def test_plateau_window_survives_resume(self, tmp_path):
        """Crash-resume keeps the patience window (plateau.json sidecar): a
        run preempted after a plateau epoch must NOT get a fresh window and
        train `patience` extra epochs past the original plateau."""
        import dataclasses

        from transformer_tpu.train import Trainer

        # Warmup so large the LR is ~0: eval loss is bit-identical every
        # epoch, so epoch 1 sets best_eval and every later epoch plateaus.
        def cfg(epochs):
            return dataclasses.replace(
                TCFG, epochs=epochs, warmup_steps=10**9,
                early_stop_patience=2, eval_every_steps=0, log_every_steps=0,
                checkpoint_every_epochs=1,
            )

        mgr = CheckpointManager(str(tmp_path), max_to_keep=2, is_primary=True)
        state = create_train_state(jax.random.PRNGKey(0), TINY, cfg(2))
        logs = []
        tr = Trainer(TINY, cfg(2), state, checkpoint=mgr, log_fn=logs.append)
        tr.fit(_FixedBatches(n=2, seed=0), _FixedBatches(n=1, seed=7))
        # Epoch 1: best. Epoch 2: one plateau epoch. The exhausted epoch
        # budget plays the part of the preemption.
        assert not any("early stop" in l for l in logs)
        assert (tmp_path / "plateau.json").exists()

        mgr2 = CheckpointManager(str(tmp_path), max_to_keep=2, is_primary=True)
        state2 = create_train_state(jax.random.PRNGKey(0), TINY, cfg(40))
        logs2 = []
        tr2 = Trainer(TINY, cfg(40), state2, checkpoint=mgr2, log_fn=logs2.append)
        tr2.fit(_FixedBatches(n=2, seed=0), _FixedBatches(n=1, seed=7))
        assert any("resumed early-stop window" in l for l in logs2), logs2[:3]
        done = [l for l in logs2 if "done in" in l]
        # The persisted window already counts 1 plateau epoch, so ONE more
        # (epoch 3) reaches patience=2 — a fresh window would need two.
        assert len(done) == 1, logs2
        assert any("early stop" in l for l in logs2)

    def test_empty_eval_gives_no_signal(self):
        """A zero-weight eval (empty test split) must not lock best_eval at
        0.0 and fire a spurious stop."""
        import dataclasses

        from transformer_tpu.train import Trainer

        class _Empty:
            def __len__(self):
                return 0

            def batches(self, epoch=0):
                return iter(())

        tc = dataclasses.replace(
            TCFG, epochs=4, warmup_steps=10, early_stop_patience=1,
            eval_every_steps=0, log_every_steps=0,
        )
        state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        logs = []
        tr = Trainer(TINY, tc, state, log_fn=logs.append)
        tr.fit(_FixedBatches(n=2, seed=0), _Empty())
        assert len([l for l in logs if "done in" in l]) == 4
        assert not any("early stop" in l for l in logs)

    @pytest.mark.slow  # heavyweight: slow tier (fast tier keeps a specimen)
    def test_disabled_runs_all_epochs(self):
        import dataclasses

        from transformer_tpu.train import Trainer

        tc = dataclasses.replace(
            TCFG, epochs=4, warmup_steps=10, early_stop_patience=0,
            eval_every_steps=0, log_every_steps=0,
        )
        state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        logs = []
        tr = Trainer(TINY, tc, state, log_fn=logs.append)
        tr.fit(_FixedBatches(n=2, seed=0), _FixedBatches(n=1, seed=7))
        assert len([l for l in logs if "done in" in l]) == 4
        assert not any("early stop" in l for l in logs)


class TestCheckpointAveraging:
    def test_average_is_elementwise_mean(self, tmp_path):
        """The classic Transformer eval trick: export the mean of the last N
        rotated checkpoints."""
        from transformer_tpu.train.checkpoint import average_checkpoints

        base = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        mgr = CheckpointManager(str(tmp_path), max_to_keep=5, is_primary=True)
        import dataclasses as dc

        scales = [1.0, 2.0, 6.0]
        for i, s in enumerate(scales):
            scaled = dc.replace(
                base, params=jax.tree.map(lambda x: x * s, base.params)
            )
            mgr.save(scaled, step=i)
        avg = average_checkpoints(mgr, base, mgr.all_steps())  # params tree
        want = float(np.mean(scales))
        for a, b in zip(jax.tree.leaves(avg), jax.tree.leaves(base.params)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b) * want, atol=1e-5
            )

    def test_rejects_empty(self, tmp_path):
        from transformer_tpu.train.checkpoint import average_checkpoints

        mgr = CheckpointManager(str(tmp_path), is_primary=True)
        state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        with pytest.raises(ValueError, match="at least one"):
            average_checkpoints(mgr, state, [])


class TestAdamW:
    def test_overfit_one_batch(self):
        import dataclasses

        tc = dataclasses.replace(
            TCFG, optimizer="adamw", weight_decay=0.01, warmup_steps=20
        )
        state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        step = jax.jit(make_train_step(TINY, tc))
        r = np.random.default_rng(0)
        src = jnp.asarray(r.integers(1, 28, (4, 8)), jnp.int32)
        tgt = jnp.asarray(r.integers(1, 28, (4, 8)), jnp.int32)
        rng = jax.random.PRNGKey(1)
        first = last = None
        for _ in range(120):
            state, m = step(state, src, tgt, rng)
            if first is None:
                first = float(m["loss"])
            last = float(m["loss"])
        assert last < 0.5 * first, (first, last)

    def test_decay_hits_matrices_not_vectors(self):
        """With zero gradients, adamw's update is pure decay: matrices
        shrink, vectors (biases, layernorm params) stay untouched."""
        import dataclasses

        from transformer_tpu.train.state import make_optimizer

        tc = dataclasses.replace(
            TCFG, optimizer="adamw", weight_decay=0.1, warmup_steps=1
        )
        state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        tx = make_optimizer(TINY, tc)
        zero_g = jax.tree.map(jnp.zeros_like, state.params)
        opt_state = tx.init(state.params)
        # A few steps past warmup so the schedule LR is nonzero.
        updates = None
        for _ in range(3):
            updates, opt_state = tx.update(zero_g, opt_state, state.params)
        for path, u in jax.tree_util.tree_flatten_with_path(updates)[0]:
            name = "/".join(str(getattr(e, "key", e)) for e in path)
            # Exempt by NAME (qkv biases are 2-D), not rank.
            if np.asarray(u).ndim >= 2 and not name.endswith("bias"):
                assert float(jnp.max(jnp.abs(u))) > 0.0, name
            else:
                np.testing.assert_array_equal(np.asarray(u), 0.0, err_msg=name)

    def test_decay_requires_adamw(self):
        import dataclasses

        with pytest.raises(ValueError, match="weight_decay"):
            dataclasses.replace(TCFG, weight_decay=0.1)


class TestAdafactor:
    def test_overfit_one_batch(self):
        import dataclasses

        tc = dataclasses.replace(TCFG, optimizer="adafactor", warmup_steps=20)
        state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        step = jax.jit(make_train_step(TINY, tc))
        r = np.random.default_rng(0)
        src = jnp.asarray(r.integers(1, 28, (4, 8)), jnp.int32)
        tgt = jnp.asarray(r.integers(1, 28, (4, 8)), jnp.int32)
        rng = jax.random.PRNGKey(1)
        first = None
        for _ in range(60):
            state, m = step(state, src, tgt, rng)
            first = float(m["loss"]) if first is None else first
        assert float(m["loss"]) < first * 0.6

    def test_state_is_factored(self):
        """The point of Adafactor: optimizer state far smaller than Adam's
        2x-params (factored second moments). Matrices must be >=128 on both
        dims to factor (optax default min_dim_size_to_factor), so this uses a
        model at that scale."""
        import dataclasses

        cfg = dataclasses.replace(
            TINY, d_model=128, dff=256, num_heads=4,
            input_vocab_size=512, target_vocab_size=512,
        )
        tc_a = TCFG
        tc_f = dataclasses.replace(TCFG, optimizer="adafactor")

        def elems(state_field):
            return sum(
                int(np.prod(np.shape(x))) for x in jax.tree.leaves(state_field)
            )

        s_a = create_train_state(jax.random.PRNGKey(0), cfg, tc_a)
        s_f = create_train_state(jax.random.PRNGKey(0), cfg, tc_f)
        n_params = elems(s_a.params)
        assert elems(s_a.opt_state) >= 2 * n_params
        assert elems(s_f.opt_state) < n_params / 2

    def test_rejects_unknown_optimizer(self):
        with pytest.raises(ValueError, match="optimizer"):
            TrainConfig(optimizer="sgd")


class TestTopPSampling:
    def test_nucleus_truncates_tail(self):
        """With a peaked distribution and small top_p, sampling must only
        ever return the top token; with top_p=1.0 the tail stays reachable."""
        from transformer_tpu.train.decode import lm_generate
        from transformer_tpu.models import transformer_init

        cfg = ModelConfig(
            num_layers=1, d_model=16, num_heads=2, dff=32,
            input_vocab_size=30, target_vocab_size=30, max_position=32,
            dtype="float32", dropout_rate=0.0, decoder_only=True,
        )
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jnp.asarray([[28, 5, 9]], jnp.int32)  # BOS-led
        greedy = lm_generate(params, prompt, cfg, 8, eos_id=29)
        nucleus = lm_generate(
            params, prompt, cfg, 8, eos_id=29,
            rng=jax.random.PRNGKey(3), sample=True,
            temperature=1e-3, top_p=0.5,
        )
        # Tiny temperature concentrates all mass on the argmax; the nucleus
        # then contains exactly the top token, so sampling == greedy.
        np.testing.assert_array_equal(np.asarray(greedy), np.asarray(nucleus))

    def test_top_p_one_is_unfiltered_sampling(self):
        from transformer_tpu.train.decode import lm_generate
        from transformer_tpu.models import transformer_init

        cfg = ModelConfig(
            num_layers=1, d_model=16, num_heads=2, dff=32,
            input_vocab_size=30, target_vocab_size=30, max_position=32,
            dtype="float32", dropout_rate=0.0, decoder_only=True,
        )
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        prompt = jnp.asarray([[28, 5, 9]], jnp.int32)
        a = lm_generate(
            params, prompt, cfg, 8, eos_id=29,
            rng=jax.random.PRNGKey(7), sample=True, temperature=1.0,
        )
        b = lm_generate(
            params, prompt, cfg, 8, eos_id=29,
            rng=jax.random.PRNGKey(7), sample=True, temperature=1.0,
            top_p=1.0,
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestAsyncCheckpoint:
    """AsyncCheckpointManager: background disk writes, synchronous device
    snapshot (so donated-buffer invalidation can't corrupt a pending save)."""

    def test_roundtrip_matches_sync(self, tmp_path):
        from transformer_tpu.train import AsyncCheckpointManager

        state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        a = AsyncCheckpointManager(str(tmp_path / "async"), max_to_keep=3)
        s = CheckpointManager(str(tmp_path / "sync"), max_to_keep=3)
        a.save(state, step=5)
        s.save(state, step=5)
        a.wait()
        other = create_train_state(jax.random.PRNGKey(1), TINY, TCFG)
        ra = a.restore_latest(other)
        rs = s.restore_latest(other)
        for x, y in zip(jax.tree.leaves(ra), jax.tree.leaves(rs)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_snapshot_survives_donation(self, tmp_path):
        """The state buffers are donated to the next train step immediately
        after save() returns — the checkpoint must hold the OLD values."""
        from transformer_tpu.train import AsyncCheckpointManager

        state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        step = jax.jit(make_train_step(TINY, TCFG), donate_argnums=(0,))
        r = np.random.default_rng(0)
        src = jnp.asarray(r.integers(1, 28, (4, 8)), jnp.int32)
        tgt = jnp.asarray(r.integers(1, 28, (4, 8)), jnp.int32)
        mgr = AsyncCheckpointManager(str(tmp_path), max_to_keep=3)
        before = jax.tree.map(lambda a: np.asarray(a).copy(), state.params)
        mgr.save(state, step=0)
        # Donate the old buffers right away; the pending write must not see it.
        state, _ = step(state, src, tgt, jax.random.PRNGKey(1))
        mgr.wait()
        restored = mgr.restore(
            create_train_state(jax.random.PRNGKey(2), TINY, TCFG), 0
        )
        for x, y in zip(jax.tree.leaves(before), jax.tree.leaves(restored.params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_sequential_saves_rotate(self, tmp_path):
        from transformer_tpu.train import AsyncCheckpointManager

        state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        mgr = AsyncCheckpointManager(str(tmp_path), max_to_keep=2)
        for i in range(4):
            mgr.save(state, step=i)
        mgr.wait()
        assert mgr.all_steps() == [2, 3]

    def test_worker_failure_surfaces_on_wait(self, tmp_path):
        """A failed background WRITE (ENOSPC, permissions, ...) must re-raise
        from wait(), not vanish with the worker thread."""
        from transformer_tpu.train import AsyncCheckpointManager

        state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        mgr = AsyncCheckpointManager(str(tmp_path / "x"), max_to_keep=2)

        def boom(flat, step):
            raise OSError("disk full")

        mgr._write_replicated = boom
        mgr.save(state, step=0)
        with pytest.raises(OSError, match="disk full"):
            mgr.wait()
        # The failure is consumed: the manager is usable again afterwards.
        del mgr.__dict__["_write_replicated"]
        mgr.save(state, step=1)
        mgr.wait()
        assert mgr.all_steps() == [1]


class TestChunkedLoss:
    """loss_chunks: vocab projection + CE over sequence slices
    (train/loss.py chunked_cross_entropy_from_hidden) — must match the
    monolithic path exactly in loss, metrics, and gradients."""

    def _batch(self, seed=0):
        r = np.random.default_rng(seed)
        src = jnp.asarray(r.integers(1, 28, (4, 9)), jnp.int32)
        tgt = jnp.asarray(r.integers(1, 28, (4, 9)), jnp.int32)
        return src, tgt

    @pytest.mark.parametrize(
        "chunks",
        [2, pytest.param(3, marks=pytest.mark.slow)],  # 3 does not divide S-1=8;
        # the non-dividing case is the slow-tier sweep, chunks=2 the fast specimen
    )
    def test_train_step_matches_monolithic(self, chunks):
        import dataclasses

        src, tgt = self._batch()
        rng = jax.random.PRNGKey(1)
        tc_mono = TCFG
        tc_chunk = dataclasses.replace(TCFG, loss_chunks=chunks)
        s1 = create_train_state(jax.random.PRNGKey(0), TINY, tc_mono)
        s2 = create_train_state(jax.random.PRNGKey(0), TINY, tc_chunk)
        s1, m1 = jax.jit(make_train_step(TINY, tc_mono))(s1, src, tgt, rng)
        s2, m2 = jax.jit(make_train_step(TINY, tc_chunk))(s2, src, tgt, rng)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-6)
        for k in ("loss_sum", "weight", "correct"):
            np.testing.assert_allclose(float(m1[k]), float(m2[k]), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def test_eval_step_matches_monolithic(self):
        import dataclasses

        src, tgt = self._batch(1)
        state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        m1 = jax.jit(make_eval_step(TINY, TCFG))(state, src, tgt)
        tc = dataclasses.replace(TCFG, loss_chunks=4)
        m2 = jax.jit(make_eval_step(TINY, tc))(state, src, tgt)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-6)

    @pytest.mark.slow  # heavyweight: slow tier (fast tier keeps a specimen)
    def test_tied_output_supported(self):
        import dataclasses

        cfg = dataclasses.replace(TINY, tie_embeddings=True, tie_output=True)
        tc = dataclasses.replace(TCFG, loss_chunks=2)
        src, tgt = self._batch(2)
        state = create_train_state(jax.random.PRNGKey(0), cfg, tc)
        state, m = jax.jit(make_train_step(cfg, tc))(state, src, tgt, jax.random.PRNGKey(1))
        assert np.isfinite(float(m["loss"]))

    def test_composes_with_grad_accum(self):
        """Both sequential memory levers at once (r2 VERDICT missing-#3):
        loss_chunks × grad_accum_steps must reproduce the monolithic
        whole-batch trajectory."""
        import dataclasses

        import optax

        tc = dataclasses.replace(TCFG, loss_chunks=2, grad_accum_steps=2)
        r = np.random.default_rng(5)
        src = jnp.asarray(r.integers(1, 28, (8, 8)), jnp.int32)
        tgt = jnp.asarray(r.integers(1, 28, (8, 8)), jnp.int32)
        tgt = tgt.at[:, 6:].set(0)  # pad tail: exercise token weighting
        rng = jax.random.PRNGKey(3)
        # SGD so params reflect raw gradient sums: Adam's m/sqrt(v) would
        # amplify fp32 summation-order noise on near-zero gradients into
        # O(1) relative update differences (the accum-only test compares
        # losses for the same reason).
        from transformer_tpu.train.state import TrainState

        sgd = optax.sgd(0.5)
        params = create_train_state(jax.random.PRNGKey(0), TINY, TCFG).params
        s_ref = TrainState(
            step=jnp.int32(0), params=params, opt_state=sgd.init(params)
        )
        s_c = TrainState(
            step=jnp.int32(0), params=params, opt_state=sgd.init(params)
        )
        step_ref = jax.jit(make_train_step(TINY, TCFG, tx=sgd))
        step_c = jax.jit(make_train_step(TINY, tc, tx=sgd))
        for _ in range(3):
            s_ref, m_ref = step_ref(s_ref, src, tgt, rng)
            s_c, m_c = step_c(s_c, src, tgt, rng)
            np.testing.assert_allclose(
                float(m_c["loss"]), float(m_ref["loss"]), rtol=2e-5
            )
        for a, b in zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_c.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def test_custom_forward_requires_hidden_forward(self):
        """A custom forward_fn without its hidden counterpart must still be
        rejected under loss_chunks — silently materializing (B, S, V) logits
        would OOM exactly where chunking matters."""
        import dataclasses

        tc = dataclasses.replace(TCFG, loss_chunks=2)
        fake_forward = lambda params, s, ti, r, det: None  # noqa: E731
        with pytest.raises(ValueError, match="hidden_forward_fn"):
            make_train_step(TINY, tc, forward_fn=fake_forward)
        with pytest.raises(ValueError, match="hidden_forward_fn"):
            make_eval_step(TINY, tc, forward_fn=fake_forward)


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        mgr = CheckpointManager(str(tmp_path), max_to_keep=3, is_primary=True)
        state2 = create_train_state(jax.random.PRNGKey(1), TINY, TCFG)
        mgr.save(state, step=7)
        restored = mgr.restore_latest(state2)
        for a, b in zip(
            jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_rotation_keeps_max(self, tmp_path):
        state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        mgr = CheckpointManager(str(tmp_path), max_to_keep=2, is_primary=True)
        for s in [1, 2, 3, 4]:
            mgr.save(state, step=s)
        assert mgr.all_steps() == [3, 4]

    def test_restore_latest_none_when_empty(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), max_to_keep=2, is_primary=True)
        assert mgr.restore_latest(None) is None

    def test_shape_mismatch_rejected(self, tmp_path):
        state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        mgr = CheckpointManager(str(tmp_path), is_primary=True)
        mgr.save(state, step=1)
        other = create_train_state(
            jax.random.PRNGKey(0),
            ModelConfig(
                num_layers=1, d_model=32, num_heads=2, dff=32,
                input_vocab_size=30, target_vocab_size=30, max_position=32,
                dtype="float32",
            ),
            TCFG,
        )
        with pytest.raises(ValueError):
            mgr.restore(other, 1)

    def test_export_load(self, tmp_path):
        params = transformer_init(jax.random.PRNGKey(0), TINY)
        export_params(params, TINY, str(tmp_path / "export"))
        template = transformer_init(jax.random.PRNGKey(1), TINY)
        loaded = load_exported_params(str(tmp_path / "export"), template)
        np.testing.assert_array_equal(
            np.asarray(loaded["encoder"]["embedding"]["table"]),
            np.asarray(params["encoder"]["embedding"]["table"]),
        )


class TestTrainStep:
    def test_tied_tables_start_in_their_own_buffers(self):
        """``tie_embeddings`` hands the decoder the encoder's table itself;
        a donated train step on a TPU refuses one buffer donated twice
        (INVALID_ARGUMENT — the CPU ignores donation, so only this check
        guards it here). The train state holds every leaf in its own
        buffer, equal in value."""
        import dataclasses

        cfg = dataclasses.replace(TINY, tie_embeddings=True, tie_output=True)
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        assert (
            params["encoder"]["embedding"]["table"]
            is params["decoder"]["embedding"]["table"]
        )
        state = create_train_state(jax.random.PRNGKey(0), cfg, TCFG)
        leaves = jax.tree.leaves(state)
        assert len({id(x) for x in leaves}) == len(leaves)
        enc = state.params["encoder"]["embedding"]["table"]
        dec = state.params["decoder"]["embedding"]["table"]
        assert enc.unsafe_buffer_pointer() != dec.unsafe_buffer_pointer()
        np.testing.assert_array_equal(enc, dec)

    def test_overfit_one_batch(self):
        """Integration: loss falls by >60% in 150 steps on a fixed batch."""
        tcfg = TrainConfig(
            batch_size=4, sequence_length=8, epochs=1,
            warmup_steps=20, loss_normalization="tokens",
        )
        state = create_train_state(jax.random.PRNGKey(0), TINY, tcfg)
        step = jax.jit(make_train_step(TINY, tcfg))
        src = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 1, 30)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 1, 30)
        rng = jax.random.PRNGKey(3)
        first = last = None
        for _ in range(150):
            state, m = step(state, src, tgt, rng)
            if first is None:
                first = float(m["loss"])
            last = float(m["loss"])
        assert last < 0.4 * first, (first, last)
        assert int(state.step) == 150

    @pytest.mark.slow  # heavyweight: slow tier (fast tier keeps a specimen)
    def test_grad_accum_matches_whole_batch(self):
        """grad_accum_steps=4 must produce the same optimizer trajectory as
        the whole-batch step (dropout off), for both normalizations."""
        import dataclasses

        for norm in ("tokens", "batch"):
            base = TCFG if TCFG.loss_normalization == norm else dataclasses.replace(
                TCFG, loss_normalization=norm
            )
            accum_cfg = dataclasses.replace(base, grad_accum_steps=4)
            src = jax.random.randint(jax.random.PRNGKey(1), (8, 8), 1, 30)
            tgt = jax.random.randint(jax.random.PRNGKey(2), (8, 8), 1, 30)
            tgt = tgt.at[:, 6:].set(0)  # pad tail: exercise token weighting
            rng = jax.random.PRNGKey(3)

            s_ref = create_train_state(jax.random.PRNGKey(0), TINY, base)
            s_acc = create_train_state(jax.random.PRNGKey(0), TINY, accum_cfg)
            step_ref = jax.jit(make_train_step(TINY, base))
            step_acc = jax.jit(make_train_step(TINY, accum_cfg))
            for _ in range(3):
                s_ref, m_ref = step_ref(s_ref, src, tgt, rng)
                s_acc, m_acc = step_acc(s_acc, src, tgt, rng)
                np.testing.assert_allclose(
                    float(m_acc["loss"]), float(m_ref["loss"]), rtol=2e-5,
                    err_msg=norm,
                )

    def test_grad_accum_must_divide_batch(self):
        import dataclasses

        import pytest

        cfg = dataclasses.replace(TCFG, grad_accum_steps=3)
        state = create_train_state(jax.random.PRNGKey(0), TINY, cfg)
        step = jax.jit(make_train_step(TINY, cfg))
        src = jax.random.randint(jax.random.PRNGKey(1), (8, 8), 1, 30)
        with pytest.raises(ValueError, match="divide"):
            step(state, src, src, jax.random.PRNGKey(2))

    def test_eval_step_deterministic(self):
        state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        eval_step = jax.jit(make_eval_step(TINY, TCFG))
        src = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 1, 30)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 1, 30)
        m1 = eval_step(state, src, tgt)
        m2 = eval_step(state, src, tgt)
        assert float(m1["loss"]) == float(m2["loss"])


class TestGreedyDecode:
    def test_shapes_and_pad_after_eos(self):
        params = transformer_init(jax.random.PRNGKey(0), TINY)
        src = jax.random.randint(jax.random.PRNGKey(1), (3, 6), 1, 30)
        out = np.asarray(greedy_decode(params, src, TINY, 10, bos_id=28, eos_id=29))
        assert out.shape == (3, 10)
        for row in out:
            seen_eos = False
            for t in row:
                if seen_eos:
                    assert t == 0
                if t == 29:
                    seen_eos = True

    def test_translate_accepts_str_and_list(self):
        """The reference's predict(str) decodes one character (quirk §2.3.11);
        both spellings must work here."""
        from transformer_tpu.data.tokenizer import SubwordTokenizer

        tok = SubwordTokenizer.build_from_corpus(
            ["ab cd ef"] * 3, target_vocab_size=270
        )
        cfg = ModelConfig(
            num_layers=1, d_model=16, num_heads=2, dff=32,
            input_vocab_size=tok.model_vocab_size,
            target_vocab_size=tok.model_vocab_size,
            max_position=32, dtype="float32", dropout_rate=0.0,
        )
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        single = translate(params, cfg, tok, tok, "ab cd", max_len=5)
        double = translate(params, cfg, tok, tok, ["ab cd", "ef"], max_len=5)
        assert len(single) == 1 and len(double) == 2
        assert all(isinstance(t, str) for t in double)

    def test_translate_buckets_widths_one_compile(self):
        """Varying source widths/batch sizes within one bucket must reuse one
        compiled executable (the decode-side recompile bomb: reference decode
        re-traces per shape, train.py:109-118; round-1 translate() recompiled
        per source width)."""
        from transformer_tpu.data.tokenizer import SubwordTokenizer
        from transformer_tpu.train.decode import greedy_decode

        tok = SubwordTokenizer.build_from_corpus(
            ["ab cd ef gh ij"] * 3, target_vocab_size=270
        )
        cfg = ModelConfig(
            num_layers=1, d_model=16, num_heads=2, dff=32,
            input_vocab_size=tok.model_vocab_size,
            target_vocab_size=tok.model_vocab_size,
            max_position=32, dtype="float32", dropout_rate=0.0,
        )
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        before = greedy_decode._cache_size()
        # Different sentence counts and raw token widths — all land in the
        # (batch<=1-pow2, width<=16) bucket, so exactly one new compile.
        translate(params, cfg, tok, tok, "ab", max_len=5)
        translate(params, cfg, tok, tok, "ab cd ef", max_len=5)
        translate(params, cfg, tok, tok, "ab cd ef gh ij", max_len=5)
        assert greedy_decode._cache_size() == before + 1

    def test_bucket_rounding(self):
        from transformer_tpu.train.decode import _bucket

        assert _bucket(3, 4096) == 16   # floor
        assert _bucket(17, 4096) == 32  # next pow2
        assert _bucket(100, 64) == 64   # capped
        assert _bucket(5, 4096, floor=1) == 8

    def test_translate_overlong_input_fails_loudly(self):
        """A sentence longer than max_position must raise, not silently
        truncate away its EOS (src_len= opts into explicit truncation)."""
        import pytest

        from transformer_tpu.data.tokenizer import SubwordTokenizer

        tok = SubwordTokenizer.build_from_corpus(
            ["ab cd ef gh"] * 3, target_vocab_size=270
        )
        cfg = ModelConfig(
            num_layers=1, d_model=16, num_heads=2, dff=32,
            input_vocab_size=tok.model_vocab_size,
            target_vocab_size=tok.model_vocab_size,
            max_position=8, dtype="float32", dropout_rate=0.0,
        )
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        long_sentence = "ab cd ef gh " * 8
        with pytest.raises(ValueError, match="max_position"):
            translate(params, cfg, tok, tok, long_sentence, max_len=4)
        # Explicit src_len still allows truncation.
        out = translate(params, cfg, tok, tok, long_sentence, max_len=4, src_len=8)
        assert len(out) == 1


class TestExportRoundTrip:
    def test_export_load_identical_decode(self, tmp_path, monkeypatch):
        """Export → load via the serving CLI path → decode output must be
        identical to decoding with the in-memory params (the reference's
        SavedModel capability, train.py:246, exercised end-to-end)."""
        from transformer_tpu.cli.translate import load_export
        from transformer_tpu.data.tokenizer import SubwordTokenizer
        from transformer_tpu.train.checkpoint import export_params

        tok = SubwordTokenizer.build_from_corpus(
            ["ab cd ef gh"] * 3, target_vocab_size=270
        )
        cfg = ModelConfig(
            num_layers=1, d_model=16, num_heads=2, dff=32,
            input_vocab_size=tok.model_vocab_size,
            target_vocab_size=tok.model_vocab_size,
            max_position=32, dtype="float32", dropout_rate=0.0,
        )
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        export_params(params, cfg, str(tmp_path / "model"))

        loaded_params, loaded_cfg = load_export(str(tmp_path / "model"))
        assert loaded_cfg == cfg
        want = translate(params, cfg, tok, tok, ["ab cd", "ef gh"], max_len=6)
        got = translate(loaded_params, loaded_cfg, tok, tok, ["ab cd", "ef gh"], max_len=6)
        assert want == got


class TestQuantizedExport:
    def _model(self):
        # d_model 64 so the big leaves clear the _Q8_MIN_SIZE threshold.
        cfg = ModelConfig(
            num_layers=1, d_model=64, num_heads=2, dff=128,
            input_vocab_size=300, target_vocab_size=300, max_position=32,
            dtype="float32", dropout_rate=0.0,
        )
        return cfg, transformer_init(jax.random.PRNGKey(0), cfg)

    def test_int8_roundtrip_error_bound(self, tmp_path):
        """Every quantized leaf must come back within half a quantization
        step of its group scale; small leaves (biases, layernorms) must be
        bit-exact."""
        from transformer_tpu.train.checkpoint import (
            _Q8_MIN_SIZE,
            _flatten,
            _q8_group_axes,
            export_params,
            load_exported_params,
        )

        cfg, params = self._model()
        export_params(params, cfg, str(tmp_path / "q"), quantize="int8")
        loaded = load_exported_params(str(tmp_path / "q"), params)
        for (k, want), got in zip(
            _flatten(params).items(),
            _flatten(loaded).values(),
        ):
            want, got = np.asarray(want), np.asarray(got)
            if want.ndim < 2 or want.size < _Q8_MIN_SIZE or k.endswith("/bias"):
                np.testing.assert_array_equal(want, got, err_msg=k)
            else:
                axis = _q8_group_axes(k, want)
                step = np.max(np.abs(want), axis=axis, keepdims=True) / 127.0
                assert np.all(np.abs(want - got) <= step * 0.5 + 1e-8), k

    def test_int8_artifact_smaller(self, tmp_path):
        import os

        from transformer_tpu.train.checkpoint import export_params

        cfg, params = self._model()
        export_params(params, cfg, str(tmp_path / "fp"))
        export_params(params, cfg, str(tmp_path / "q"), quantize="int8")
        fp = os.path.getsize(tmp_path / "fp" / "params.npz")
        q = os.path.getsize(tmp_path / "q" / "params.npz")
        assert q < fp / 2.5, (fp, q)

    def test_quantized_decode_close(self, tmp_path):
        """The serving path must work unchanged on a quantized export, and
        the int8 error must not change a greedy decode of an untrained
        model's argmax chain wildly — compare logits, not strings."""
        from transformer_tpu.models import transformer_apply
        from transformer_tpu.train.checkpoint import (
            export_params,
            load_exported_params,
        )

        cfg, params = self._model()
        export_params(params, cfg, str(tmp_path / "q"), quantize="int8")
        loaded = load_exported_params(str(tmp_path / "q"), params)
        src = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 1, 290)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 1, 290)
        want, _ = transformer_apply(params, src, tgt, cfg, deterministic=True)
        got, _ = transformer_apply(loaded, src, tgt, cfg, deterministic=True)
        err = float(jnp.max(jnp.abs(want - got)))
        spread = float(jnp.max(want) - jnp.min(want))
        assert err < 0.05 * spread, (err, spread)

    def test_rejects_unknown_scheme(self, tmp_path):
        from transformer_tpu.train.checkpoint import export_params

        cfg, params = self._model()
        with pytest.raises(ValueError, match="quantize"):
            export_params(params, cfg, str(tmp_path / "x"), quantize="int4")

    def test_moe_biases_stay_exact(self, tmp_path):
        """Per-expert MoE biases are 2-D and large but additive — they must
        NOT be quantized (bit-exact roundtrip)."""
        from transformer_tpu.train.checkpoint import (
            export_params,
            load_exported_params,
        )

        cfg = ModelConfig(
            num_layers=1, d_model=64, num_heads=2, dff=128,
            input_vocab_size=300, target_vocab_size=300, max_position=32,
            dtype="float32", dropout_rate=0.0,
            moe_experts=8, moe_top_k=2, moe_every=1,
        )
        params = transformer_init(jax.random.PRNGKey(0), cfg)
        export_params(params, cfg, str(tmp_path / "q"), quantize="int8")
        loaded = load_exported_params(str(tmp_path / "q"), params)

        def check(path, want, got):
            key = "/".join(str(getattr(e, "key", getattr(e, "name", e))) for e in path)
            if key.endswith("bias"):
                np.testing.assert_array_equal(
                    np.asarray(want), np.asarray(got), err_msg=key
                )

        jax.tree_util.tree_map_with_path(
            check, params, loaded
        )

    def test_bfloat16_params_quantize(self, tmp_path):
        """bf16 leaves must quantize too (ml_dtypes' bfloat16 is not
        np.floating — matched by dtype name instead)."""
        import os

        from transformer_tpu.train.checkpoint import export_params

        cfg, params = self._model()
        bf16 = jax.tree.map(
            lambda w: np.asarray(w, dtype=jnp.bfloat16.dtype), params
        )
        export_params(bf16, cfg, str(tmp_path / "fp"))
        export_params(bf16, cfg, str(tmp_path / "q"), quantize="int8")
        fp = os.path.getsize(tmp_path / "fp" / "params.npz")
        q = os.path.getsize(tmp_path / "q" / "params.npz")
        assert q < fp / 1.4, (fp, q)  # int8 < bf16 on the big leaves


class TestTensorBoardWriter:
    def test_record_framing_and_crc(self, tmp_path):
        w = SummaryWriter(str(tmp_path))
        w.scalar("loss", 1.5, step=3)
        w.close()
        data = open(w.path, "rb").read()
        # record 1: file_version; record 2: our scalar
        off = 0
        records = []
        while off < len(data):
            (length,) = struct.unpack_from("<Q", data, off)
            (len_crc,) = struct.unpack_from("<I", data, off + 8)
            assert len_crc == _masked_crc(data[off : off + 8])
            payload = data[off + 12 : off + 12 + length]
            (payload_crc,) = struct.unpack_from("<I", data, off + 12 + length)
            assert payload_crc == _masked_crc(payload)
            records.append(payload)
            off += 12 + length + 4
        assert len(records) == 2
        assert b"brain.Event:2" in records[0]
        assert b"loss" in records[1]
        assert struct.pack("<f", 1.5) in records[1]

    def test_crc32c_known_vector(self):
        from transformer_tpu.utils.tensorboard import _crc32c

        # RFC 3720 test vector: 32 zero bytes -> 0x8A9136AA
        assert _crc32c(b"\x00" * 32) == 0x8A9136AA


class TestBleu:
    def test_perfect_match_is_100(self):
        refs = ["the cat sat on the mat", "hello world foo bar"]
        assert corpus_bleu(refs, refs, smooth=False) == pytest.approx(100.0)

    def test_zero_overlap_is_0(self):
        assert corpus_bleu(["a b c d"], ["x y z w"], smooth=False) == 0.0

    def test_brevity_penalty(self):
        refs = ["a b c d e f g h"]
        full = corpus_bleu(refs, ["a b c d e f g h"])
        short = corpus_bleu(refs, ["a b c d"])
        assert short < full
        # BP formula: exp(1 - ref/hyp)
        assert short == pytest.approx(
            100 * math.exp(1 - 8 / 4) * math.exp(
                (math.log(4 / 4) + math.log(4 / 4) + math.log(3 / 3) + math.log(2 / 2)) / 4
            ),
            rel=1e-6,
        )
