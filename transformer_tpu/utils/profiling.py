"""Tracing / profiling subsystem.

The reference's only observability is wall-clock prints (``train.py:186,213``,
``distributed_train.py:76,81,99,121``) plus loss/accuracy scalars; its
de-facto "debug mode" is ``--enable_function=False`` (``utils.py:30``,
``train.py:175-177``), which this framework preserves as the un-jitted eager
path. This module is the TPU-native upgrade:

- :class:`Profiler` captures an XLA device trace for a step window
  ``[start_step, start_step + num_steps)`` via ``jax.profiler`` and writes a
  TensorBoard-profile-compatible dump.
- :func:`annotate` labels host-side regions so they show up on the trace
  timeline; :func:`mirrored_tracer` hands it to the span tracer, so every
  ``tracer.span(...)`` context is also a host event in the profiler's trace.
- :class:`StepTimer` keeps an online step-duration distribution and
  throughput estimate — the structured replacement for the reference's
  printed per-step deltas.
"""

from __future__ import annotations

import os
import time

import jax
from jax.experimental.compilation_cache import compilation_cache

from transformer_tpu.obs.quantiles import StreamingHistogram
from transformer_tpu.obs.trace import Tracer, default_tracer


# Fixed so that every process of this checkout — CLIs, benchmarks, replica
# workers and their respawns — shares one cache: the directory is part of
# the cache key, so a path that moves (a uid, a pid, a tmp dir) never hits.
_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """Persist compiled executables across processes; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set here; otherwise the cache lives at ``<checkout>/.jax_cache``.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Small compiles are cheaper to redo than to hash + load.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    # jax binds the cache directory ONCE, lazily, at the first jit after
    # import — a dir configured after any compile has happened is silently
    # ignored for the life of the process. Reset so this call's dir takes
    # effect no matter when it runs (the CLI enables the cache after flag
    # parsing, by which point absl/jax warmup may already have compiled).
    compilation_cache.reset_cache()
    return cache_dir


class Profiler:
    """Capture one jax.profiler trace over a window of training steps.

    Drive it from a training loop with ``maybe_trace(step)`` once per step;
    the trace starts when ``step == start_step`` and stops ``num_steps``
    later (or at ``close()``, whichever comes first).
    """

    def __init__(self, log_dir: str, start_step: int = 2, num_steps: int = 3):
        self.log_dir = log_dir
        # Relative to the first observed step, so a run restored at step N
        # still skips `start_step` warmup (compile) steps before tracing.
        self.start_step = start_step
        self.num_steps = num_steps
        self._first_step: int | None = None
        self._active = False
        self._done = False

    def maybe_trace(self, step: int, block_on=None) -> None:
        if self._done:
            return
        if self._first_step is None:
            self._first_step = step
        rel = step - self._first_step
        if not self._active and rel >= self.start_step:
            os.makedirs(self.log_dir, exist_ok=True)
            jax.profiler.start_trace(self.log_dir)
            self._active = True
            self._stop_at = step + self.num_steps
        elif self._active and step >= self._stop_at:
            self.stop(block_on)

    def stop(self, block_on=None) -> None:
        """End the capture. Pass the training state (or any output of the
        profiled steps) as ``block_on`` so enqueued device work finishes
        inside the trace — without it, async-dispatched steps may still be
        running when the capture closes."""
        if self._active:
            if block_on is not None:
                jax.block_until_ready(block_on)
            jax.profiler.stop_trace()
            self._active = False
            self._done = True

    close = stop


def annotate(name: str):
    """A context manager that labels a host-side region on the profiler's
    timeline (a flag test while no profiler session runs)."""
    return jax.profiler.TraceAnnotation(name)


def mirrored_tracer(telemetry) -> Tracer:
    """The tracer serving and training code records spans into: the
    telemetry bundle's, or the process's buffer-only default where none was
    passed. Its ``span()`` contexts are mirrored into the profiler's trace
    through :func:`annotate` (``obs/`` itself imports no jax)."""
    tracer = getattr(telemetry, "tracer", None) or default_tracer()
    if tracer.annotate is None:
        tracer.annotate = annotate
    return tracer


class StepTimer:
    """Step throughput from wall clock between *sync points*.

    Under async dispatch a jitted step returns as soon as it is enqueued, so
    per-call deltas measure host dispatch, not device time. This timer only
    trusts windows closed by :meth:`sync`, which the caller invokes right
    after a genuinely blocking read (a metric ``device_get``, an epoch
    boundary): ``tick()`` counts steps; ``sync()`` closes the window and
    attributes its wall time to the steps inside it.
    """

    def __init__(self, tokens_per_step: int = 0):
        self.tokens_per_step = tokens_per_step
        # Online step-duration distribution (p50/p95/p99), fed one window at
        # a time by sync(). The histogram instance is the obs-registry reuse
        # point: Trainer binds it into a registry Histogram
        # (`registry.histogram(name, hist=timer.histogram)`), so telemetry
        # exports the SAME sample stream with no duplicate quantile code.
        # Survives reset(): reset() reopens the throughput window per epoch,
        # but the duration distribution is a run-level statistic.
        self.histogram = StreamingHistogram()
        self.reset()

    def reset(self) -> None:
        self._window_steps = 0
        self._window_tokens = 0
        self._window_start: float | None = None
        self._total_steps = 0
        self._total_tokens = 0
        self._total_time = 0.0

    def tick(self, tokens: int | None = None, steps: int = 1) -> None:
        """Call once per dispatch. ``tokens`` overrides the fixed
        ``tokens_per_step`` for that dispatch — length-bucketed batches
        process fewer tokens than the nominal batch×sequence_length.
        ``steps`` > 1 when one dispatch covers several optimizer steps
        (TrainConfig.steps_per_dispatch); ``tokens`` then counts the whole
        group."""
        if self._window_start is None:
            self._window_start = time.perf_counter()
        self._window_steps += steps
        self._window_tokens += (
            self.tokens_per_step * steps if tokens is None else tokens
        )

    def sync(self) -> None:
        """Close the current window — call immediately after a blocking read
        of step outputs, so the elapsed time covers completed device work."""
        if self._window_start is None or self._window_steps == 0:
            return
        window = time.perf_counter() - self._window_start
        self._total_time += window
        self._total_steps += self._window_steps
        self._total_tokens += self._window_tokens
        # Per-step duration is only observable at window granularity under
        # async dispatch: attribute the window's wall time evenly to the
        # steps inside it (n identical samples keeps step-count weighting).
        self.histogram.observe(window / self._window_steps, n=self._window_steps)
        self._window_steps = 0
        self._window_tokens = 0
        self._window_start = None

    @property
    def count(self) -> int:
        return self._total_steps

    @property
    def total_tokens(self) -> int:
        return self._total_tokens

    @property
    def total_time_s(self) -> float:
        return self._total_time

    @property
    def mean_s(self) -> float:
        return self._total_time / self._total_steps if self._total_steps else 0.0

    @property
    def steps_per_sec(self) -> float:
        return self._total_steps / self._total_time if self._total_time > 0 else 0.0

    @property
    def tokens_per_sec(self) -> float:
        return self._total_tokens / self._total_time if self._total_time > 0 else 0.0

    def summary(self) -> str:
        if not self._total_steps:
            return "no steps timed"
        msg = (
            f"{self.count} steps: mean {self.mean_s * 1e3:.1f}ms "
            f"({self.steps_per_sec:.2f} steps/s"
        )
        if self._total_tokens:
            msg += f", {self.tokens_per_sec:,.0f} tokens/s"
        msg += ")"
        if self.histogram.count:
            p = self.histogram.percentiles()
            msg += (
                f" p50 {p['p50'] * 1e3:.1f}ms p95 {p['p95'] * 1e3:.1f}ms "
                f"p99 {p['p99'] * 1e3:.1f}ms"
            )
        return msg
