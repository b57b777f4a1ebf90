"""Aux subsystems (SURVEY §5): profiling/tracing, preemption handling,
determinism audits, and their Trainer integration."""

import os
import signal

import jax
import numpy as np
import pytest

from transformer_tpu.config import ModelConfig, TrainConfig
from transformer_tpu.models import transformer_init
from transformer_tpu.train import CheckpointManager, Trainer, create_train_state
from transformer_tpu.utils import (
    PreemptionGuard,
    Profiler,
    StepTimer,
    annotate,
    tree_checksum,
)

TINY = ModelConfig(
    num_layers=1, d_model=16, num_heads=2, dff=32,
    input_vocab_size=30, target_vocab_size=30, max_position=16,
    dropout_rate=0.0, dtype="float32",
)
TCFG = TrainConfig(
    batch_size=4, sequence_length=8, epochs=1, warmup_steps=10,
    log_every_steps=0, eval_every_steps=0, checkpoint_every_epochs=1,
)


class _OneBatch:
    """Minimal dataset: the same batch, n times per epoch."""

    def __init__(self, n=4, stop_after=None, on_batch=None):
        self.n = n
        self.on_batch = on_batch
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        self.src = np.asarray(jax.random.randint(k1, (4, 8), 1, 30))
        self.tgt = np.asarray(jax.random.randint(k2, (4, 8), 1, 30))

    def batches(self, epoch=0):
        for i in range(self.n):
            if self.on_batch is not None:
                self.on_batch(i)
            yield self.src, self.tgt


class TestProfiler:
    def test_trace_produces_dump(self, tmp_path):
        prof = Profiler(str(tmp_path / "prof"), start_step=1, num_steps=2)
        x = jax.numpy.ones((8, 8))
        for step in range(5):
            prof.maybe_trace(step)
            with annotate("matmul"):
                jax.block_until_ready(x @ x)
        prof.stop()
        dumped = []
        for root, _, files in os.walk(tmp_path / "prof"):
            dumped.extend(os.path.join(root, f) for f in files)
        assert dumped, "profiler produced no trace files"

    @pytest.mark.slow
    def test_trainer_integration(self, tmp_path):
        prof = Profiler(str(tmp_path / "prof"), start_step=1, num_steps=2)
        state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        tr = Trainer(TINY, TCFG, state, log_fn=lambda *_: None, profiler=prof)
        tr.fit(_OneBatch(n=4))
        assert prof._done and not prof._active
        assert any(files for _, _, files in os.walk(tmp_path / "prof"))


class TestCompilationCache:
    @pytest.fixture
    def restore_cache_config(self):
        from jax.experimental.compilation_cache import compilation_cache

        old_dir = jax.config.jax_compilation_cache_dir
        old_min = jax.config.jax_persistent_cache_min_compile_time_secs
        yield
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", old_min)
        compilation_cache.reset_cache()

    def test_env_dir_is_left_alone(self, tmp_path, monkeypatch, restore_cache_config):
        """With JAX_COMPILATION_CACHE_DIR set, JAX reads it; the code sets
        no directory of its own (here: the config keeps what it had)."""
        from transformer_tpu.utils import enable_compilation_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert enable_compilation_cache() == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == "sentinel"
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0

    def test_default_is_fixed_path_in_checkout(self, monkeypatch, restore_cache_config):
        from transformer_tpu.utils import enable_compilation_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        d = enable_compilation_cache()
        assert d == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == d
        assert enable_compilation_cache() == d  # no pid/time in the name


class TestStepTimer:
    def test_stats(self):
        t = StepTimer(tokens_per_step=100)
        for _ in range(5):
            t.tick()
        assert t.count == 0  # unsynced window: no timing claims yet
        t.sync()  # caller blocked on step outputs here
        assert t.count == 5
        assert t.mean_s > 0.0
        assert t.steps_per_sec > 0
        assert t.tokens_per_sec == pytest.approx(t.steps_per_sec * 100)
        assert "steps/s" in t.summary()

    def test_sync_without_ticks_is_noop(self):
        t = StepTimer()
        t.sync()
        assert t.count == 0

    def test_empty_summary(self):
        assert StepTimer().summary() == "no steps timed"


class TestPreemptionGuard:
    def test_latches_and_restores(self):
        before = signal.getsignal(signal.SIGTERM)
        with PreemptionGuard(signals=(signal.SIGTERM,)) as g:
            assert not g.should_stop
            os.kill(os.getpid(), signal.SIGTERM)
            assert g.should_stop
            assert g.signal_received == signal.SIGTERM
        assert signal.getsignal(signal.SIGTERM) is before

    def test_trainer_checkpoints_on_signal(self, tmp_path):
        """SIGTERM mid-epoch: the loop must save a checkpoint and exit."""
        tcfg = TrainConfig(
            batch_size=4, sequence_length=8, epochs=3, warmup_steps=10,
            log_every_steps=0, eval_every_steps=0,
        )
        state = create_train_state(jax.random.PRNGKey(0), TINY, tcfg)
        ckpt = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=2)
        logs = []

        def send_signal(i):
            if i == 2:  # third batch of the first epoch
                os.kill(os.getpid(), signal.SIGINT)

        tr = Trainer(TINY, tcfg, state, checkpoint=ckpt, log_fn=logs.append)
        tr.fit(_OneBatch(n=8, on_batch=send_signal))
        # Stopped early (3 steps, not 24) and saved.
        assert int(jax.device_get(tr.state.step)) == 3
        assert ckpt.latest_step == 3
        assert any("preemption" in msg for msg in logs)

    @pytest.mark.slow
    def test_resume_after_preemption(self, tmp_path):
        """The saved preemption checkpoint restores at next start."""
        tcfg = TrainConfig(
            batch_size=4, sequence_length=8, epochs=1, warmup_steps=10,
            log_every_steps=0, eval_every_steps=0, checkpoint_every_epochs=5,
        )
        state = create_train_state(jax.random.PRNGKey(0), TINY, tcfg)
        ckpt = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=2)
        tr = Trainer(TINY, tcfg, state, checkpoint=ckpt, log_fn=lambda *_: None)

        def send_signal(i):
            if i == 1:
                os.kill(os.getpid(), signal.SIGINT)

        tr.fit(_OneBatch(n=4, on_batch=send_signal))
        saved_step = ckpt.latest_step
        assert saved_step == 2

        state2 = create_train_state(jax.random.PRNGKey(7), TINY, tcfg)
        logs = []
        tr2 = Trainer(TINY, tcfg, state2, checkpoint=ckpt, log_fn=logs.append)
        tr2.fit(_OneBatch(n=4))
        assert any("restored checkpoint" in m for m in logs)
        assert int(jax.device_get(tr2.state.step)) == saved_step + 4


class TestTreeChecksum:
    def test_equal_trees_equal_checksums(self):
        p1 = transformer_init(jax.random.PRNGKey(0), TINY)
        p2 = transformer_init(jax.random.PRNGKey(0), TINY)
        assert tree_checksum(p1) == tree_checksum(p2)

    def test_different_trees_differ(self):
        p1 = transformer_init(jax.random.PRNGKey(0), TINY)
        p2 = jax.tree.map(lambda x: x + 1e-3, p1)
        assert tree_checksum(p1) != tree_checksum(p2)

    @pytest.mark.slow
    def test_train_determinism_audit(self):
        """Two identical runs of the jitted step must produce bit-identical
        states — the cross-run determinism guarantee the audit relies on."""
        from transformer_tpu.train import make_train_step

        def run():
            state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
            step = jax.jit(make_train_step(TINY, TCFG))
            src = np.asarray(
                jax.random.randint(jax.random.PRNGKey(1), (4, 8), 1, 30)
            )
            tgt = np.asarray(
                jax.random.randint(jax.random.PRNGKey(2), (4, 8), 1, 30)
            )
            for _ in range(3):
                state, _ = step(state, src, tgt, jax.random.PRNGKey(3))
            return tree_checksum(state.params)

        assert run() == run()


class TestConsistency:
    """utils/consistency.py — the SURVEY §5 'race detection' equivalent.
    (The real 2-process positive/negative checks run in
    tests/test_multiprocess.py via multiproc_worker.py.)"""

    def test_fingerprint_detects_change(self):
        from transformer_tpu.utils.consistency import (
            fingerprints_equal,
            tree_fingerprint,
        )

        params = transformer_init(jax.random.PRNGKey(0), TINY)
        a = tree_fingerprint(params)
        b = tree_fingerprint(params)
        assert fingerprints_equal(a, b) == []
        bumped = jax.tree.map(lambda x: x, params)
        bumped["final"]["bias"] = params["final"]["bias"] + 1e-3
        diff = fingerprints_equal(a, tree_fingerprint(bumped))
        assert diff == ["final/bias"], diff

    def test_single_process_consistency_trivially_passes(self):
        from transformer_tpu.utils.consistency import (
            assert_cross_process_consistent,
        )

        params = transformer_init(jax.random.PRNGKey(0), TINY)
        assert_cross_process_consistent(params)  # must not raise

    def test_step_determinism_assert(self):
        from transformer_tpu.train import make_train_step
        from transformer_tpu.utils.consistency import (
            assert_step_deterministic,
        )

        state = create_train_state(jax.random.PRNGKey(0), TINY, TCFG)
        step = jax.jit(make_train_step(TINY, TCFG))  # NOT donated
        src = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (4, 8), 1, 30))
        tgt = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (4, 8), 1, 30))
        assert_step_deterministic(step, state, src, tgt, jax.random.PRNGKey(3))

        calls = []

        def impure(x):
            calls.append(1)
            return np.float32(len(calls)) * np.asarray(x)

        with pytest.raises(RuntimeError, match="nondeterministic"):
            assert_step_deterministic(impure, np.ones(3), label="impure fn")
