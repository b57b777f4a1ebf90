"""REAL multi-process distributed training (SURVEY §2.4 multi-host).

Round-1 VERDICT: the multi-host path was "code-complete but never executed
with >1 process". This test launches two actual OS processes that join one
JAX distributed runtime over a localhost coordinator (4 virtual CPU devices
each → a global 8-device mesh), train data×fsdp steps where each process
feeds only its shard of the global batch, and round-trip a multi-process
sharded checkpoint. Cross-checked against the in-process single-run oracle.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

# Heavyweight module (interpret-mode Pallas / 8-device shard_map /
# multi-process): excluded from the fast path, pytest -m 'not slow'.
pytestmark = pytest.mark.slow


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_training_matches_single(tmp_path):
    port = _free_port()
    coordinator = f"127.0.0.1:{port}"
    env = {
        **os.environ,
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
            + os.environ.get("PYTHONPATH", "").split(os.pathsep)
        ),
    }
    # The CPU platform is forced inside the worker itself.
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)), "multiproc_worker.py")
    procs = [
        subprocess.Popen(
            [sys.executable, worker, coordinator, str(pid), str(tmp_path)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=600)
            assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
            outs.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        # A failed/timed-out worker leaves its peer blocked in a collective;
        # never orphan them.
        for p in procs:
            if p.poll() is None:
                p.kill()

    # Both processes observed the same global mesh and identical losses.
    for o in outs:
        assert o["n_processes"] == 2
        assert o["n_devices"] == 8
    assert outs[0]["losses"] == outs[1]["losses"]
    # Both restored identical params from the shared sharded checkpoint.
    assert outs[0]["restore_checksum"] == outs[1]["restore_checksum"]
    # The hybrid multi-slice mesh (data over process-granule "DCN", fsdp
    # intra-process) reproduces the flat-mesh numerics on the same batches
    # (device arrangement must not change the math, only the transport).
    assert outs[0]["hybrid_losses"] == outs[1]["hybrid_losses"]
    np.testing.assert_allclose(
        outs[0]["hybrid_losses"], outs[0]["losses"], atol=2e-5
    )
    # Consistency sanitizer (utils/consistency.py): identical replicated
    # state passes (and fsdp-sharded leaves are skipped, not false-
    # positived), while per-process divergence is detected on BOTH hosts.
    for o in outs:
        assert o["consistency_ok"], o
        assert o["divergence_caught"], o

    # The 2-process run must match the single-process 8-device oracle.
    import jax

    from transformer_tpu.config import MeshConfig, ModelConfig, TrainConfig
    from transformer_tpu.parallel import (
        create_sharded_state,
        make_mesh,
        make_sharded_steps,
        put_batch,
    )

    model_cfg = ModelConfig(
        num_layers=2, d_model=16, num_heads=4, dff=32,
        input_vocab_size=32, target_vocab_size=32, max_position=32,
        dtype="float32", dropout_rate=0.0,
    )
    train_cfg = TrainConfig(
        batch_size=16, sequence_length=8, warmup_steps=10,
        loss_normalization="tokens",
    )
    mesh = make_mesh(MeshConfig(data=4, fsdp=2))
    state, shardings = create_sharded_state(
        jax.random.PRNGKey(0), model_cfg, train_cfg, mesh
    )
    step_fn, _ = make_sharded_steps(
        mesh, model_cfg, train_cfg, shardings, donate=False
    )
    rng = jax.random.PRNGKey(42)
    want = []
    for i in range(3):
        ks, kt = jax.random.split(jax.random.PRNGKey(100 + i))
        src = np.asarray(jax.random.randint(ks, (16, 8), 1, 32), np.int32)
        tgt = np.asarray(jax.random.randint(kt, (16, 8), 1, 32), np.int32)
        state, m = step_fn(state, put_batch(src, mesh), put_batch(tgt, mesh), rng)
        want.append(round(float(m["loss"]), 6))
    np.testing.assert_allclose(outs[0]["losses"], want, rtol=2e-4)
