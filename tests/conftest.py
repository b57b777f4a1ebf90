"""Test harness: force a virtual 8-device CPU platform.

This is the JAX-native analogue of a fake multi-GPU backend (SURVEY.md §4):
distributed tests build a real ``jax.sharding.Mesh`` over 8 host-platform
devices, so sharding/collective code paths compile and execute without TPU
hardware. Both variables are set before jax is imported, which is when
it reads them.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight tests (interpret-mode Pallas kernels, 8-device "
        "shard_map, multi-process) — `pytest -m 'not slow'` is the fast "
        "core-parity path (see README)",
    )
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection tests (docs/ROBUSTNESS.md) — a fast "
        "deterministic subset rides tier-1; the full sweep is also marked "
        "slow (`pytest -m chaos` runs every drill)",
    )
    config.addinivalue_line(
        "markers",
        "pallas: Pallas kernel parity/retrace tests (interpret mode on "
        "CPU) — a fast subset rides tier-1; the full variant x block-size "
        "sweep is also marked slow (`pytest -m pallas` runs every kernel "
        "test)",
    )


def launch_analysis_all_gate():
    """The ONE definition of the `analysis all` gate invocation — the
    pre-launch hook below and test_analysis_all_cli_gate's synchronous
    fallback must run the IDENTICAL command or the two paths drift."""
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.Popen(
        [sys.executable, "-m", "transformer_tpu.analysis", "all",
         "--format=json"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        # Lowest priority: the gate soaks IDLE core time next to the
        # single-threaded suite; it must never stretch the suite's own
        # critical path on a small box (tier-1 runs under a hard timeout).
        preexec_fn=lambda: os.nice(19),
    )


def pytest_collection_finish(session):
    """The `analysis all` pre-merge gate (test_analysis.py) shells a
    ~80s-CPU subprocess. pytest itself is single-threaded, so on any
    multi-core box that subprocess can run CONCURRENTLY with the rest of
    the suite instead of serially at the end: launch it the moment
    collection (and marker deselection) confirms the gate test will run,
    and let the test collect the result. The Popen handle rides on the
    config object; the test falls back to launching synchronously when
    run without this hook having fired."""
    if getattr(session.config.option, "collectonly", False):
        return  # --collect-only runs no test: nothing to pre-warm
    if any(
        item.name == "test_analysis_all_cli_gate" for item in session.items
    ):
        session.config._analysis_all_gate = launch_analysis_all_gate()


def pytest_sessionfinish(session, exitstatus):
    """Reap the gate subprocess if the gate test never consumed it (run
    aborted with -x / Ctrl-C): an orphaned 80s-CPU child must not outlive
    the pytest invocation that spawned it."""
    proc = getattr(session.config, "_analysis_all_gate", None)
    if proc is not None and proc.poll() is None:
        proc.kill()
        proc.communicate()
