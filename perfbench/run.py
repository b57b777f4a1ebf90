"""The benchmark's one command.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Finds the cell's file, its configuration, its kind and the metric
readers by name; nothing here lists a name. Fails (exit 2, no result line)
where JAX finds no TPU or fewer chips than the cell asks for; ``--rehearse``
runs a tiny size on whatever JAX finds and prints no time, rate or share.
The last line of standard output is the result; earlier lines are notes.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_reader(directory: str, metric: str):
    """``perfbench/<directory>/<metric>.py`` -> its ``read`` function, or None."""
    path = os.path.join(HERE, directory, metric + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(f"perfbench.{directory}.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, group by group."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def wanted_metrics(section: str, cell: str) -> list[dict]:
    """The metrics of ``BENCHMARK.json`` this cell reports. A cell that is not
    in it yet (one being built) is offered every metric; a reader that finds
    nothing to read returns nothing and the metric is left out."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = any(w["name"] == cell for w in bench["workloads"])
    return [m for m in bench[section] if not listed or "workloads" not in m or cell in m["workloads"]]


class Context:
    def __init__(self, args, jax):
        self.workload, self.seed, self.seconds = args.workload, args.seed, args.seconds
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self._jax = jax
        self.setup_s = None
        self.t0 = self.t1 = None
        self.trace_t0 = self.trace_t1 = None
        self.trace_dir = None
        self._window_span = None
        self.compile_times: list[float] = []
        self.span_names: set[str] = set()  # host spans the kind wrote into the profiler's trace
        self.marks: list[tuple[str, float]] = []  # (what was done, seconds since the process began)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.compile_times.append(time.perf_counter())

    def say(self, label: str, fields: dict) -> None:
        """A note line: everything before the last line of standard output."""
        print(json.dumps({"note": label, **fields}, default=float), flush=True)

    def mark(self, what: str) -> None:
        """Where set-up time goes: printed with the window's note."""
        self.marks.append((what, round(time.perf_counter() - T_PROCESS, 2)))

    def span(self, name: str):
        self.span_names.add(name)
        return self._jax.profiler.TraceAnnotation(name)

    def window_opens(self) -> float:
        self.t0 = time.perf_counter()
        self.setup_s = self.t0 - T_PROCESS
        return self.t0

    def window_closes(self) -> None:
        self.t1 = time.perf_counter()

    def compiles_in_window(self) -> int:
        return sum(1 for t in self.compile_times if self.t0 <= t <= self.t1)

    def trace_start(self) -> None:
        self.trace_dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        self._jax.profiler.start_trace(self.trace_dir)
        self._window_span = self._jax.profiler.TraceAnnotation("perfbench.trace")
        self._window_span.__enter__()
        self.trace_t0 = time.perf_counter()

    def trace_stop(self) -> None:
        self.trace_t1 = time.perf_counter()
        self._window_span.__exit__(None, None, None)
        self._jax.profiler.stop_trace()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    if args.rehearse:
        # The cell's ``rehearse`` block is laid over the cell, its ``model`` over
        # the configuration's: the same code at a size the CPU can run.
        tiny = dict(cell["rehearse"])
        config["model"].update(tiny.pop("model"))
        cell = merged(cell, tiny)

    import jax

    # The compile cache lives inside the checkout at a fixed path (the path is
    # part of the cache's key), holds small programs too, and is never pruned.
    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"perfbench: JAX found no device: {e}", file=sys.stderr)
        return 2
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.rehearse and (platform != "tpu" or len(devices) < cell["chips"]):
        print(f"perfbench: cell {args.workload} needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} x {platform}:{kind}. Only --rehearse runs off a TPU.", file=sys.stderr)
        return 2
    table = load_json("peaks.json")
    peaks = table.get(kind)
    if peaks is None:
        if not args.rehearse:
            print(f"perfbench: device kind {kind!r} is not in perfbench/peaks.json", file=sys.stderr)
            return 2
        peaks = next(v for k, v in table.items() if not k.startswith("_"))  # nothing of it is printed
    try:
        kind_mod = importlib.import_module(f"perfbench.kinds.{cell['kind']}")
    except ImportError as e:
        print(f"perfbench: cannot import the system under test: {e}", file=sys.stderr)
        return 3

    ctx = Context(args, jax)
    ctx.mark("imports and device")
    record = kind_mod.run(ctx, config, cell)
    record.update(cell_name=args.workload, cell=cell, config=config, peaks=peaks,
                  setup_s=ctx.setup_s, t0=ctx.t0, t1=ctx.t1)
    compiles = ctx.compiles_in_window()
    ctx.say("window", {"setup_s": ctx.setup_s, "window_s": ctx.t1 - ctx.t0,
                       "programs_compiled_or_loaded_in_window": compiles,
                       "programs_compiled_or_loaded": len(ctx.compile_times), "setup_done_at_s": ctx.marks})
    why_not = list(record.get("why_not_correct", [])) + ([f"{compiles} programs compiled or loaded inside the window"] if compiles else [])
    correct = bool(record["correct"] and compiles == 0)
    # Each number compared beside its limit, [reading, "<=" or ">=", limit]: the
    # last lines of standard error and the last key of the result's line.
    compared = {**record.get("compared", {}), "programs_compiled_in_window": [compiles, "<=", 0]}
    if not correct:
        print(f"perfbench: {args.workload} seed {args.seed} is not correct: {'; '.join(why_not) or 'no reason given'}",
              file=sys.stderr, flush=True)

    device = {"platform": platform, "kind": kind, "count": len(devices)}
    stats = [d.memory_stats() or {} for d in devices[: cell["chips"]]]
    device["memory_peak_bytes"] = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    result = {"correct": correct, "attempted": record["attempted"], "failed": record["failed"]}
    if args.trace:
        reduced = None
        if ctx.trace_dir is not None:
            from perfbench import trace_reduce

            reduced = trace_reduce.reduce_dir(ctx.trace_dir, ctx.span_names)
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        record["trace"] = reduced
        record["trace_host"] = (ctx.trace_t0, ctx.trace_t1)
        if reduced is not None:
            device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["ops_by_label"][:10],
                "idle_gaps": [[n, s] for n, s in reduced["idle_by_span"][:10]],
            }
            ctx.say("trace", {k: reduced[k] for k in ("window_s", "window_source", "busy_s", "lines",
                                                      "longest_gaps")} | {"modules": reduced["modules"][:8]})
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in wanted_metrics(section, args.workload):
        reader = load_reader("layer_metrics" if args.trace else "end_to_end", m["name"])
        value = None if reader is None else reader(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for name, (value, holds, limit) in compared.items():
        print(f"perfbench: compared {name} = {value} (has to be {holds} {limit})", file=sys.stderr, flush=True)
    if args.rehearse:
        # A CPU run gives no time, rate or share: only the names that WOULD be reported.
        print(json.dumps({"rehearsal": True, "correct": correct, "attempted": result["attempted"],
                          "failed": result["failed"], "would_report": sorted(metrics),
                          "device": {"platform": platform, "kind": kind, "count": len(devices)}}))
        return 0 if correct else 1
    result["metrics"] = metrics
    result["device"] = device
    result["compared"] = compared
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
