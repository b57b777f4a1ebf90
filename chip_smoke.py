"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py               one TPU chip, one process
    python chip_smoke.py --multichip   one host with four chips

Default run, in one process that holds the chip: train the seq2seq base
model for a short epoch and translate one sentence, train the decoder-only
base model (6L, d_model 512, 8 heads, dff 2048, bf16) for two short epochs
through ``transformer_tpu.cli.train``'s own ``main``, export it, and serve a
handful of JSONL prompts through the continuous-batching path of
``cli.serve`` with ``--decode_kernel xla`` and ``paged_flash``, plain and
with ``--speculate_k 2``. Every phase checks what came out and a failed
check is a non-zero exit. Weights are random from a seed; vocabularies,
checkpoints and exports are built under the output directory from the
tracked ``data/`` files.

``--multichip`` runs only what exists across chips and what it is compared
with: four one-chip replicas behind ``cli.router`` against one replica, and
``DistributedTrainer`` on a data=2 x fsdp=2 mesh against the single-device
train step.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
with the device as JAX reports it. Without ``--rehearse`` the script refuses
any platform but ``tpu``. ``--rehearse`` shrinks every size so the same code
runs on CPU devices (``JAX_PLATFORMS=cpu``), and then reports that platform.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import queue
import re
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# The base preset's widths (cli/flags.py _PRESETS["base"]); --rehearse swaps
# in sizes a CPU compiles in seconds.
BASE = dict(num_layers=6, d_model=512, num_heads=8, dff=2048)
TINY = dict(num_layers=2, d_model=64, num_heads=4, dff=128)

PROMPTS = [
    '{"prompt": "Das ist", "max_new": 16}',
    '{"prompt": "Ich möchte", "max_new": 12}',
    "Es ist wichtig , dass",
    '{"prompt": "Die Kommission hat", "max_new": 16, "temperature": 0.8, "seed": 7}',
    '{"prompt": "Wir müssen", "max_new": 8, "temperature": 1.0, "top_k": 20, "seed": 3}',
    '{"prompt": "Herr Präsident , ich", "max_new": 10}',
]
# The kernel tests' bf16 tolerance (tests/test_paged_kernel.py _TOL): the
# two decode kernels round in a different order, so on the chip in bf16
# they agree to this, not to the byte.
KERNEL_TOL = 3e-2


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"[chip_smoke] FAILED: {msg}")


def read_events(path: str) -> list[dict]:
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, as JAX reports
    them through ``jax.monitoring`` — what falls when the cache is warm."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def lap(self, since: tuple) -> str:
        s, p, h = since
        return (
            f"compile {self.seconds - s:.1f}s over {self.programs - p} "
            f"program(s), {self.cache_hits - h} persistent-cache hit(s)"
        )

    def mark(self) -> tuple:
        return (self.seconds, self.programs, self.cache_hits)


def write_train_files(out: str, lines: int) -> str:
    """The training split of the tracked corpus (no test files: the smoke
    scores nothing), whole or cut to ``lines`` pairs for rehearsal."""
    data = os.path.join(out, "data")
    os.makedirs(data)
    for name in ("src-train.txt", "tgt-train.txt"):
        with open(os.path.join(REPO, "data", name), encoding="utf-8") as f:
            kept = f.readlines()[:lines] if lines else f.readlines()
        with open(os.path.join(data, name), "w", encoding="utf-8") as f:
            f.writelines(kept)
    return data


def run_train_cli(workdir: str, argv: list[str]) -> list[dict]:
    """``transformer_tpu.cli.train``'s own ``main`` under ``argv``, from
    ``workdir`` (it exports to ./model); returns its telemetry events."""
    from absl import flags

    from transformer_tpu.cli import train as train_cli

    os.makedirs(workdir, exist_ok=True)
    jsonl = os.path.join(workdir, "train.jsonl")
    flags.FLAGS.unparse_flags()
    flags.FLAGS([
        "chip_smoke", *argv,
        f"--ckpt_path={os.path.join(workdir, 'ckpt')}",
        f"--tb_log_dir={os.path.join(workdir, 'logs')}",
        f"--metrics_jsonl={jsonl}",
        "--noeval_bleu",
    ])
    cwd = os.getcwd()
    os.chdir(workdir)
    try:
        train_cli.main([])
    finally:
        os.chdir(cwd)
    return read_events(jsonl)


def check_training(events: list[dict], on_tpu: bool) -> None:
    kinds = [e["kind"] for e in events]
    check("train.predicted" in kinds, "the cost model emitted no train.predicted")
    windows = [e for e in events if e["kind"] == "train.window"]
    first = [e for e in windows if e["epoch"] == 1][-1]
    last = [e for e in windows if e["epoch"] == max(w["epoch"] for w in windows)][-1]
    for e in (first, last):
        check(e["loss"] == e["loss"] and abs(e["loss"]) < 1e9, f"loss not finite: {e}")
    check(
        last["epoch"] > first["epoch"] and last["loss"] < first["loss"],
        f"loss did not fall: epoch {first['epoch']} {first['loss']} -> "
        f"epoch {last['epoch']} {last['loss']}",
    )
    compiles = [e["cache_sizes"] for e in events if e["kind"] == "train.compile"]
    check(
        compiles[0].get("train_step") == 1 and all(c == compiles[0] for c in compiles),
        f"recompiles after warm-up: {compiles}",
    )
    if on_tpu:
        memory = [e for e in events if e["kind"] == "train.memory"]
        stats = next(iter(memory[-1]["devices"].values())) if memory else {}
        check(
            "bytes_in_use" in stats and "peak_bytes_in_use" in stats,
            f"device.memory_stats() gave no usable keys on the chip: {memory[-1:]}",
        )
        say(f"device memory after training: {stats}")
    per_window = [
        f"epoch {w['epoch']}: {w['steps']} steps at {1e3 * w['window_s'] / w['steps']:.2f} ms"
        for w in windows
    ]
    say(
        f"loss {first['loss']:.4f} (epoch {first['epoch']}) -> {last['loss']:.4f} "
        f"(epoch {last['epoch']}); step time by window (first dispatch and "
        f"its compile excluded; smoke, not a benchmark): {per_window}; "
        f"compiled programs {compiles[-1]}, unchanged since warm-up"
    )


def serve_once(params, cfg, tok, *, decode_kernel: str, speculate_k: int):
    """The ``cli.serve`` continuous-batching path (``--serve_slots 8
    --kv_layout paged --prefix_block 16``) over PROMPTS; returns the
    responses and the scheduler."""
    from transformer_tpu.cli.serve import serve_continuous
    from transformer_tpu.serve import ContinuousScheduler, drafter_from_flags

    drafter = None
    if speculate_k:
        drafter = drafter_from_flags(
            "", 3, cfg.max_position + 1, eos_id=tok.eos_id,
            target_vocab_size=cfg.target_vocab_size,
        )
    sched = ContinuousScheduler(
        params, cfg, tok, num_slots=8, default_max_new=16,
        speculate_k=speculate_k, drafter=drafter,
        kv_layout="paged", kv_block=16, decode_kernel=decode_kernel,
    )
    q: queue.Queue = queue.Queue()
    for line in PROMPTS:
        q.put(line + "\n")
    q.put(None)
    stdout = io.StringIO()
    with contextlib.redirect_stdout(stdout):
        serve_continuous(q, sched, cfg)
    return [json.loads(line) for line in stdout.getvalue().splitlines()], sched


def check_decode_programs(sched, on_tpu: bool) -> None:
    """The two decode-step programs on the same pool state: the compiled
    paged_flash program holds the Mosaic custom call, and its logits agree
    with the gather path's to the kernel tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from transformer_tpu.serve import scheduler as sched_mod

    n, block = sched.num_slots, sched.pool.block_tokens
    table = sched.pool.alloc.table_device()
    index = jnp.full((n,), 3, jnp.int32)
    toks = jnp.arange(5, 5 + n, dtype=jnp.int32)
    copy = lambda: jax.tree.map(jnp.copy, sched.pool.caches)  # both donate  # noqa: E731
    flash_args = (sched.params, copy(), table, index, toks, sched.cfg, block,
                  sched._kernel_interpret)
    text = sched_mod._pool_step_paged_flash.lower(*flash_args).compile().as_text()
    calls = text.count("tpu_custom_call")
    if on_tpu:
        check(
            calls >= 2 * sched.cfg.num_layers,
            f"paged_flash step holds {calls} Mosaic custom calls (interpret mode?)",
        )
    flash, _ = sched_mod._pool_step_paged_flash(*flash_args)
    xla, _ = sched_mod._pool_step_paged(
        sched.params, copy(), table, index, toks, sched.cfg, block,
        sched.pool.buf_len,
    )
    flash, xla = np.asarray(flash, np.float32), np.asarray(xla, np.float32)
    check(np.isfinite(flash).all() and np.isfinite(xla).all(), "non-finite logits")
    scale = float(np.abs(xla).max())
    diff = float(np.abs(flash - xla).max())
    agree = int((flash.argmax(-1) == xla.argmax(-1)).sum())
    say(
        f"decode step, same pool state: {calls} tpu_custom_call(s) in the "
        f"paged_flash program; logits {flash.shape} max|flash-xla| {diff:.4g} "
        f"at max|logit| {scale:.4g} (tolerance {KERNEL_TOL} of it); argmax "
        f"agrees on {agree}/{n} slots"
    )
    check(diff <= KERNEL_TOL * scale, f"decode kernels disagree: {diff} > {KERNEL_TOL} * {scale}")


def check_kernel_parity() -> None:
    """``paged_flash_attention`` against the XLA gather oracle at the base
    head shape, decode and verify rows — the kernel tests' own comparison,
    run by the chip's compiler instead of the interpreter. Both ways the
    pool may hold these heads: by heads (the tiled route) and, as
    ``init_block_pool`` lays 8 bf16 heads of 64 out, two a lane row (the
    streamed route, the one the server below runs)."""
    import jax.numpy as jnp
    import numpy as np

    from transformer_tpu.kernels.flash_attention import paged_attention
    from transformer_tpu.kernels.paged_flash import heads_per_lane_row

    rng = np.random.default_rng(0)
    n, h, d, block, blocks, nmax = 8, 8, 64, 16, 41, 5
    for s_q in (1, 3):
        q = jnp.asarray(rng.standard_normal((n, s_q, h, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((blocks, block, h, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((blocks, block, h, d)), jnp.bfloat16)
        table = jnp.asarray(rng.permutation(np.arange(1, blocks))[: n * nmax].reshape(n, nmax), jnp.int32)
        lengths = jnp.asarray(rng.integers(s_q, nmax * block, (n,)), jnp.int32)
        want = np.asarray(paged_attention(q, k, v, table, lengths, impl="xla"), np.float32)
        for per_row in sorted({1, heads_per_lane_row(h, d, k.dtype)}):
            page = (blocks, block, h // per_row, d * per_row)
            got = np.asarray(
                paged_attention(q, k.reshape(page), v.reshape(page), table, lengths, impl="paged_flash"),
                np.float32,
            )
            np.testing.assert_allclose(got, want, rtol=KERNEL_TOL, atol=KERNEL_TOL)
            say(f"paged_flash_attention vs XLA oracle, S_q={s_q}, {per_row} head(s) a lane row: "
                f"max|diff| {np.abs(got - want).max():.4g} (tolerance {KERNEL_TOL})")


def serve_phase(export_dir: str, vocab: str, on_tpu: bool):
    """Serve the export four ways; returns the exported model config."""
    from transformer_tpu.cli.translate import load_export
    from transformer_tpu.data.tokenizer import SubwordTokenizer

    params, cfg = load_export(export_dir)
    tok = SubwordTokenizer.load(vocab)
    check_kernel_parity()
    answers = {}
    for speculate_k in (0, 2):
        for kernel in ("xla", "paged_flash"):
            t0 = time.perf_counter()
            out, sched = serve_once(
                params, cfg, tok, decode_kernel=kernel, speculate_k=speculate_k
            )
            check(len(out) == len(PROMPTS), f"{len(out)} answers for {len(PROMPTS)} requests")
            bad = [r for r in out if "continuation" not in r]
            check(not bad, f"{kernel} k={speculate_k}: unanswered requests {bad}")
            answers[kernel, speculate_k] = [r["continuation"] for r in out]
            say(
                f"served {len(out)} requests, decode_kernel={kernel} "
                f"speculate_k={speculate_k}: {time.perf_counter() - t0:.1f}s "
                f"(compiles included), stats {sched.stats['steps']} steps"
            )
            if kernel == "paged_flash" and not speculate_k:
                check_decode_programs(sched, on_tpu)
        same = sum(
            a == b for a, b in zip(answers["xla", speculate_k], answers["paged_flash", speculate_k])
        )
        say(
            f"speculate_k={speculate_k}: {same}/{len(PROMPTS)} answers "
            "byte-identical between xla and paged_flash (byte identity is the "
            "CPU tests' contract; in bf16 on the chip the kernels agree to "
            f"{KERNEL_TOL} on logits, checked above, and a near-tie may pick "
            "another token)"
        )
    say(f"sample answer: {PROMPTS[0]} -> {answers['xla', 0][0]!r}")
    return cfg


def time_train_step(model_cfg, train_cfg) -> None:
    """The same jitted train step timed two ways: ended by
    ``jax.block_until_ready`` and ended by fetching a value (ROADMAP S9)."""
    import jax
    import numpy as np

    from transformer_tpu.train import create_train_state, make_train_step

    state = create_train_state(jax.random.PRNGKey(0), model_cfg, train_cfg)
    step = jax.jit(make_train_step(model_cfg, train_cfg), donate_argnums=(0,))
    r = np.random.default_rng(0)
    shape = (train_cfg.batch_size, train_cfg.sequence_length)
    src = jax.device_put(r.integers(1, model_cfg.input_vocab_size - 2, shape, dtype=np.int32))
    tgt = jax.device_put(r.integers(1, model_cfg.target_vocab_size - 2, shape, dtype=np.int32))
    rng = jax.random.PRNGKey(1)
    for _ in range(3):
        state, metrics = step(state, src, tgt, rng)
    jax.block_until_ready(metrics)
    n = 20
    results = {"block_until_ready": [], "value fetch": []}
    for _ in range(2):
        for how in results:
            t0 = time.perf_counter()
            for _ in range(n):
                state, metrics = step(state, src, tgt, rng)
            if how == "block_until_ready":
                jax.block_until_ready(metrics)
            else:
                float(metrics["loss"])
            results[how].append(1e3 * (time.perf_counter() - t0) / n)
    t0 = time.perf_counter()
    for _ in range(n):
        state, metrics = step(state, src, tgt, rng)
    enqueue = 1e3 * (time.perf_counter() - t0) / n
    jax.block_until_ready(metrics)
    say(
        f"train step, {n} steps per reading, ms/step: block_until_ready "
        f"{results['block_until_ready']}, value fetch {results['value fetch']}, "
        f"enqueue only {enqueue:.2f}"
    )


def one_chip(args, out: str) -> dict:
    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not args.rehearse:
        check(on_tpu, f"JAX found {dev.platform}:{dev.device_kind}, not a TPU")
    import importlib.metadata

    import jaxlib

    from transformer_tpu import native
    from transformer_tpu.utils.profiling import enable_compilation_cache

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(
        f"device {dev.platform}:{dev.device_kind} x{len(jax.devices())}; jax "
        f"{jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}; "
        f"transformer_tpu.native: "
        f"{'C++ library loaded' if native.get_lib() is not None else 'Python fallback'}; "
        f"compile cache at {enable_compilation_cache()}"
    )
    clock = CompileClock()

    from transformer_tpu.cli.flags import define_flags

    define_flags()
    widths = TINY if args.rehearse else BASE
    data = write_train_files(out, lines=600 if args.rehearse else 0)
    common = [
        *(f"--{k}={v}" for k, v in widths.items()),
        f"--dataset_path={data}",
        f"--batch_size={16 if args.rehearse else 64}",
        f"--sequence_length={32 if args.rehearse else 64}",
        f"--target_vocab_size={2048 if args.rehearse else 2 ** 15}",
        "--dtype=bfloat16", "--warmup_steps=100", "--seed=0",
    ]

    # ---- seq2seq: the reference's own model ----
    mark, t0 = clock.mark(), time.perf_counter()
    s2s = os.path.join(out, "seq2seq")
    events = run_train_cli(s2s, [
        *common, "--epochs=1",
        f"--src_vocab_file={os.path.join(s2s, 'src.subwords')}",
        f"--tgt_vocab_file={os.path.join(s2s, 'tgt.subwords')}",
    ])
    window = [e for e in events if e["kind"] == "train.window"][-1]
    check(window["loss"] == window["loss"], f"seq2seq loss not finite: {window}")
    say(
        f"seq2seq base: {window['step']} steps, epoch loss {window['loss']:.4f}, "
        f"{time.perf_counter() - t0:.1f}s wall; {clock.lap(mark)}"
    )
    # One sentence through the KV-cached greedy decode (train/decode.py),
    # from the export cli.train just wrote.
    from transformer_tpu.cli.translate import load_export
    from transformer_tpu.data.tokenizer import SubwordTokenizer
    from transformer_tpu.train.decode import translate

    params, cfg = load_export(os.path.join(s2s, "model"))
    sentence = "he goes to school"
    translated = translate(
        params, cfg,
        SubwordTokenizer.load(os.path.join(s2s, "src.subwords")),
        SubwordTokenizer.load(os.path.join(s2s, "tgt.subwords")),
        sentence, max_len=32,
    )
    check(
        len(translated) == 1 and isinstance(translated[0], str),
        f"translate returned {translated!r}",
    )
    say(f"seq2seq greedy decode: {sentence!r} -> {translated[0]!r}")
    del params

    # ---- decoder-only base: train -> export ----
    mark, t0 = clock.mark(), time.perf_counter()
    lm = os.path.join(out, "lm")
    vocab = os.path.join(lm, "tgt.subwords")
    events = run_train_cli(lm, [
        *common, "--decoder_only", "--epochs=2", f"--tgt_vocab_file={vocab}",
    ])
    say(f"decoder-only base: {time.perf_counter() - t0:.1f}s wall; {clock.lap(mark)}")
    check_training(events, on_tpu)
    export_dir = os.path.join(lm, "model")
    check(
        os.path.exists(os.path.join(export_dir, "params.npz")),
        "cli.train left no export",
    )

    # ---- serve the export ----
    mark, t0 = clock.mark(), time.perf_counter()
    lm_cfg = serve_phase(export_dir, vocab, on_tpu)
    say(f"serve: {time.perf_counter() - t0:.1f}s wall; {clock.lap(mark)}")

    # ---- how a timing loop may end: the step cli.train just ran ----
    from transformer_tpu.cli.flags import flags_to_train_config

    time_train_step(lm_cfg, flags_to_train_config())
    say(f"whole run: {clock.lap((0.0, 0, 0))}")
    return {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


# ---------------------------------------------------------------------------
# --multichip


def router_answers(out: str, spec_path: str, replicas: int, rehearse: bool):
    """``cli.router`` as a user starts it: N replica workers, PROMPTS on
    stdin, answers on stdout. This process has not touched JAX yet, so
    every chip is free for the workers. Returns the answers and each
    replica's ready line."""
    proc = subprocess.run(
        [sys.executable, "-m", "transformer_tpu.cli.router",
         f"--replicas={replicas}", f"--model_spec={spec_path}",
         "--serve_slots=8", "--kv_layout=paged", "--prefix_block=16",
         "--max_len=16", "--heartbeat_timeout=600",
         f"--metrics_jsonl={os.path.join(out, f'router{replicas}.jsonl')}"],
        input="\n".join(PROMPTS) + "\n", capture_output=True, text=True,
        timeout=900, cwd=REPO,
        env={**os.environ, "PYTHONPATH": os.pathsep.join(
            [REPO, *filter(None, [os.environ.get("PYTHONPATH")])])},
    )
    sys.stderr.write(proc.stderr[-4000:])
    check(proc.returncode == 0, f"cli.router --replicas={replicas} exited {proc.returncode}")
    answers = [json.loads(line) for line in proc.stdout.splitlines() if line.strip()]
    ready = [
        json.loads(m.group(1))
        for m in re.finditer(r"^replica ready: (\{.*\})$", proc.stderr, re.M)
    ]
    check(len(answers) == len(PROMPTS), f"{len(answers)} answers for {len(PROMPTS)} requests")
    bad = [a for a in answers if "continuation" not in a]
    check(not bad, f"unanswered: {bad}")
    check(len(ready) == replicas, f"{len(ready)} ready lines for {replicas} replicas")
    for r in ready:
        say(f"{replicas}-replica fleet: {r['replica']} ready on {r['device']}")
        if not rehearse:
            check(r["device"]["platform"] == "tpu", f"replica not on a TPU: {r}")
    return [a["continuation"] for a in answers], ready


def router_phase(out: str, rehearse: bool) -> None:
    with open(os.path.join(REPO, "data", "tgt-train.txt"), encoding="utf-8") as f:
        corpus = f.read().splitlines()
    spec = {
        "config": {
            **(TINY if rehearse else BASE), "max_position": 64,
            "decoder_only": True, "dtype": "bfloat16", "dropout_rate": 0.0,
        },
        "seed": 0,
        "corpus": corpus[:600] if rehearse else corpus,
        "target_vocab_size": 2048 if rehearse else 2 ** 15,
    }
    spec_path = os.path.join(out, "model_spec.json")
    with open(spec_path, "w", encoding="utf-8") as f:
        json.dump(spec, f)
    t0 = time.perf_counter()
    four, ready = router_answers(out, spec_path, 4, rehearse)
    say(f"four replicas answered in {time.perf_counter() - t0:.1f}s (start-up and compiles included)")
    if not rehearse:
        chips = [r["device"]["chips"] for r in ready]
        check(
            None not in chips and len(set(chips)) == 4,
            f"replicas do not hold four different chips: {chips}",
        )
    one, _ = router_answers(out, spec_path, 1, rehearse)
    check(four == one, f"four replicas answered differently from one:\n{four}\n{one}")
    say(f"four replicas and one replica: {len(one)} answers byte-identical")


def sharded_train_phase(rehearse: bool) -> None:
    import jax
    import numpy as np

    from transformer_tpu.config import MeshConfig, ModelConfig, TrainConfig
    from transformer_tpu.parallel import DistributedTrainer, make_mesh
    from transformer_tpu.train import create_train_state, make_train_step
    from transformer_tpu.utils.profiling import enable_compilation_cache

    enable_compilation_cache()
    devices = jax.devices()
    check(len(devices) >= 4, f"--multichip needs four devices, JAX found {len(devices)}")
    vocab, batch, seq = (512, 16, 32) if rehearse else (26880, 64, 64)
    # The decoder-only base model the one-chip smoke trains and serves.
    model_cfg = ModelConfig(
        **(TINY if rehearse else BASE), input_vocab_size=vocab,
        target_vocab_size=vocab, max_position=seq, dtype="bfloat16",
        decoder_only=True,
    )
    train_cfg = TrainConfig(batch_size=batch, sequence_length=seq, warmup_steps=100)
    mesh = make_mesh(MeshConfig(data=2, fsdp=2), devices=devices[:4])
    r = np.random.default_rng(0)
    batches = [
        (r.integers(1, vocab - 2, (batch, seq), dtype=np.int32),
         r.integers(1, vocab - 2, (batch, seq), dtype=np.int32))
        for _ in range(5)
    ]
    rng = jax.random.PRNGKey(1)

    trainer = DistributedTrainer(
        model_cfg, train_cfg, mesh, rng=jax.random.PRNGKey(0), log_fn=say,
    )
    sharded = []
    for src, tgt in batches:
        trainer.state, metrics = trainer.train_step(trainer.state, src, tgt, rng)
        sharded.append(float(metrics["loss"]))
    per_device = {
        str(d): (d.memory_stats() or {}).get("bytes_in_use") for d in devices[:4]
    }
    say(f"sharded state, bytes in use per device: {per_device}")
    if not rehearse:
        used = list(per_device.values())
        check(
            all(used) and max(used) < 2 * min(used),
            f"state is not spread over the four chips: {per_device}",
        )
    from transformer_tpu.parallel.distributed import put_batch

    text = trainer.train_step_fn.lower(
        trainer.state, put_batch(batches[0][0], mesh),
        put_batch(batches[0][1], mesh), rng,
    ).compile().as_text()
    collectives = {
        op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
        for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                   "collective-permute")
    }
    say(f"collectives in the compiled data=2 x fsdp=2 train step: {collectives}")
    check(sum(collectives.values()) > 0, "no collective in the sharded train step")

    with jax.default_device(devices[0]):
        state = create_train_state(jax.random.PRNGKey(0), model_cfg, train_cfg)
        step = jax.jit(make_train_step(model_cfg, train_cfg), donate_argnums=(0,))
        single = []
        for src, tgt in batches:
            state, metrics = step(state, src, tgt, rng)
            single.append(float(metrics["loss"]))
    tol = 2e-2
    say(
        f"losses over {len(batches)} steps, data=2 x fsdp=2 mesh {sharded} "
        f"against one device {single} (bf16 tolerance: relative {tol})"
    )
    check(
        all(np.isfinite(sharded)) and np.allclose(sharded, single, rtol=tol),
        "sharded and single-device losses disagree",
    )


def multichip(args, out: str) -> dict:
    # Replicas first, while this process is still off JAX: a parent that has
    # touched JAX holds the chips its children need.
    router_phase(out, args.rehearse)
    check("jax" not in sys.modules, "the router phase pulled jax into this process")
    import jax

    dev = jax.devices()[0]
    if not args.rehearse:
        check(dev.platform == "tpu", f"JAX found {dev.platform}:{dev.device_kind}, not a TPU")
    say(f"device {dev.platform}:{dev.device_kind} x{len(jax.devices())}; jax {jax.__version__}")
    sharded_train_phase(args.rehearse)
    return {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--multichip", action="store_true",
        help="run only the four-chip phase: four one-chip replicas behind "
        "cli.router against one, and a data=2 x fsdp=2 train step against "
        "the single-device step",
    )
    ap.add_argument(
        "--rehearse", action="store_true",
        help="tiny sizes, any platform: the CPU rehearsal of the same code "
        "(JAX_PLATFORMS=cpu; with --multichip also "
        "XLA_FLAGS=--xla_force_host_platform_device_count=4)",
    )
    args = ap.parse_args()
    out = os.path.join(REPO, "chip_smoke_out", "multichip" if args.multichip else "run")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    t0 = time.perf_counter()
    device = (multichip if args.multichip else one_chip)(args, out)
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
