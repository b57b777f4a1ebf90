"""Keep-best / probe-stop rule tests (VERDICT r4 #2): the flagship BLEU
run's stopping logic — consecutive-miss patience, best tracking, JSON
persistence across resumed invocations, and the Trainer.fit callback-stop
hook it rides on."""

import dataclasses

import jax
import numpy as np

from transformer_tpu.config import ModelConfig, TrainConfig
from transformer_tpu.train import CheckpointManager, Trainer, create_train_state
from transformer_tpu.train.probe_stop import ProbeKeepBest

TINY = ModelConfig(
    num_layers=1, d_model=16, num_heads=2, dff=32,
    input_vocab_size=30, target_vocab_size=30, max_position=32,
    dtype="float32", dropout_rate=0.0,
)
TCFG = TrainConfig(batch_size=4, sequence_length=8, epochs=1, warmup_steps=100)


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


class TestProbeKeepBest:
    def test_first_probe_is_best(self, tmp_path):
        s = ProbeKeepBest(str(tmp_path / "p.json"), patience=2)
        assert s.update(10, 0.21) == "new_best"
        assert s.best_epoch == 10 and s.best_value == 0.21

    def test_stops_after_patience_misses(self, tmp_path):
        s = ProbeKeepBest(str(tmp_path / "p.json"), patience=2)
        assert s.update(10, 1.0) == "new_best"
        assert s.update(14, 2.0) == "new_best"
        assert s.update(18, 1.9) == "continue"
        assert s.update(22, 1.8) == "stop"
        assert s.stopped_epoch == 22
        assert s.best_epoch == 14  # the peak, not the stop point

    def test_recovery_resets_the_window(self, tmp_path):
        """A miss followed by a new best must NOT carry the miss count
        forward — only CONSECUTIVE misses since the best count."""
        s = ProbeKeepBest(str(tmp_path / "p.json"), patience=2)
        s.update(4, 1.0)
        s.update(8, 0.9)          # miss
        assert s.update(12, 1.5) == "new_best"
        assert s.update(16, 1.4) == "continue"  # 1 miss, not 2
        assert s.stopped_epoch is None

    def test_persistence_across_instances(self, tmp_path):
        """The resumable-run pattern: each invocation is a fresh process;
        the decision state must ride the JSON, not the object."""
        path = str(tmp_path / "p.json")
        s = ProbeKeepBest(path, patience=2)
        s.update(10, 2.0)
        s.update(14, 1.9)
        s2 = ProbeKeepBest(path, patience=2)  # "next invocation"
        assert s2.best_epoch == 10 and s2.misses_since_best == 1
        assert s2.update(18, 1.8) == "stop"
        s3 = ProbeKeepBest(path, patience=2)
        assert s3.stopped_epoch == 18  # a stop decided last window holds

    def test_reprobe_same_epoch_replaces(self, tmp_path):
        """A resumed invocation re-probing its restore-point epoch must not
        double-count a miss."""
        s = ProbeKeepBest(str(tmp_path / "p.json"), patience=2)
        s.update(10, 2.0)
        s.update(14, 1.9)
        s.update(14, 1.9)  # same epoch again: replace, not append
        assert s.misses_since_best == 1
        assert len(s.probes) == 2

    def test_min_delta_gates_new_best(self, tmp_path):
        s = ProbeKeepBest(str(tmp_path / "p.json"), patience=3, min_delta=0.1)
        s.update(4, 1.0)
        assert s.update(8, 1.05) == "continue"  # within delta: a miss
        assert s.best_epoch == 4

    def test_patience_zero_never_stops(self, tmp_path):
        s = ProbeKeepBest(str(tmp_path / "p.json"), patience=0)
        s.update(4, 2.0)
        for e in (8, 12, 16, 20):
            assert s.update(e, 1.0) == "continue"
        assert s.stopped_epoch is None
        assert s.best_epoch == 4  # best-tracking still runs (keep-best export)


class TestTrainerCallbackStop:
    def test_truthy_callback_return_stops_fit(self, tmp_path):
        """The hook the probe rule rides on: a truthy epoch_callback return
        ends fit after that epoch, and the epoch's checkpoint is saved even
        off the every-N cadence."""
        tc = dataclasses.replace(
            TCFG, epochs=6, warmup_steps=10, eval_every_steps=0,
            log_every_steps=0, checkpoint_every_epochs=5,
        )
        mgr = CheckpointManager(str(tmp_path), max_to_keep=2, is_primary=True)
        state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        logs, seen = [], []

        def cb(epoch, tr):
            seen.append(epoch)
            return epoch == 1  # stop after the second epoch

        tr = Trainer(TINY, tc, state, checkpoint=mgr, log_fn=logs.append)
        tr.fit(_FixedBatches(n=4, seed=0), epoch_callback=cb)
        assert seen == [0, 1]  # epoch 2..5 never ran
        assert any("stop requested by epoch callback" in l for l in logs)
        # 2 epochs x 4 steps, saved at the stop despite cadence 5:
        assert mgr.all_steps() == [8]
        # No EARLY_STOPPED marker: that file gates the plateau rule only.
        assert not (tmp_path / "EARLY_STOPPED").exists()

    def test_none_return_keeps_training(self):
        tc = dataclasses.replace(
            TCFG, epochs=3, warmup_steps=10, eval_every_steps=0,
            log_every_steps=0,
        )
        state = create_train_state(jax.random.PRNGKey(0), TINY, tc)
        seen = []
        tr = Trainer(TINY, tc, state, log_fn=lambda s: None)
        tr.fit(_FixedBatches(n=4, seed=0),
               epoch_callback=lambda e, t: seen.append(e))
        assert seen == [0, 1, 2]  # list.append returns None: no stop
