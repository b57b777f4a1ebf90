"""The two checks of a latent-attention expert model, and the controls that
show each is not blind, on the chip:

    python3 -m perfbench.tests.mla_moe_controls --workload ax-k1.longdoc-saturated \
        --seeds 3900000001,3900000002,3900000003 [--grid 0.07:5,0.15:5:2] [--routed] [--out controls.json] [--rehearse]

``--routed``: the routed expert layers' check of ``serve_open_loop_routed``
alone, as the configuration states it and with each of ``ROUTED_CONTROLS``;
no scheduler is built. Otherwise:

One process, one scheduler of the cell's deployment (its programs are the ones a
run of the cell compiles). For each point of ``--grid`` (the routed experts' out
kernels' factor ``moe_out_init_scale`` : the query kernel's factor
``latent_query_init_scale`` [: the router's factor ``moe_router_init_scale``];
the configuration's own point first; ``--grid_seeds`` seeds a point) and seed: the weights, the check's prompts, what the
program gives through its pool programs, and the reference's logits as the
configuration states them and with each control of
``perfbench/reference/mla_moe_lm.py``. Each reading is the check's: the largest
|difference| over the largest |logit|, against ``LOGIT_REL_TOL``. A control
has to read above it; the check itself below.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROLS = {
    "no_score_factor": {"no_score_factor": True},
    "no_key_rotation": {"no_key_rotation": True},
    "no_group_limit": {"no_group_limit": True},
    "experts_float8": {"expert_dtype": "float8_e4m3fn"},
    "weights_float8": {"weights_dtype": "float8_e4m3fn"},
}
ROUTED_CONTROLS = {
    "no_group_limit": {"no_group_limit": True},
    "group_max": {"group_max": True},
    "experts_float8": {"expert_dtype": "float8_e4m3fn"},
    "experts_float8_e5m2": {"expert_dtype": "float8_e5m2"},
}


def routed_rows(config: dict, cell: dict, seeds: list[int]) -> list[dict]:
    """One row a seed: the routed check's reading with the configuration's
    mathematics, and with each control."""
    import types

    from perfbench import program_api as api
    from perfbench.kinds.serve_open_loop_routed import ROUTED_REL_TOL, routed_check

    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        params = api.init_lm_params(config, seed)
        ctx = types.SimpleNamespace(seed=seed)
        row = {"seed": seed}
        for name, alter in [("check", None), *ROUTED_CONTROLS.items()]:
            v = routed_check(ctx, config, cell, alter=alter, params=params)
            row[name] = v["rel"]
            if name == "check":
                row["by_layer_and_rows"] = v["by_layer_and_rows"]
                row["rows"], row["rows_left_out_near_a_tie"] = v["rows"], v["rows_left_out_near_a_tie"]
        row["seen"] = all(row[k] > ROUTED_REL_TOL for k in ROUTED_CONTROLS) and row["check"] <= ROUTED_REL_TOL
        row["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(row), flush=True)
        rows.append(row)
        del params
        gc.collect()
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--grid", default="")
    ap.add_argument("--grid_seeds", type=int, default=1, help="seeds of each --grid point (the first of --seeds)")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true", help="the cell's rehearsal size, on whatever JAX finds")
    ap.add_argument("--routed", action="store_true", help="the routed expert layers' check alone")
    args = ap.parse_args()
    import importlib

    import jax

    from transformer_tpu.utils.profiling import enable_compilation_cache

    enable_compilation_cache()
    from perfbench import program_api as api
    from perfbench.kinds.serve_open_loop import LOGIT_REL_TOL

    with open(os.path.join(HERE, "workloads", args.workload + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    if args.rehearse:
        from perfbench.run import merged

        tiny = dict(cell["rehearse"])
        config["model"].update(tiny.pop("model"))
        cell = merged(cell, tiny)
    reference = importlib.import_module(f"perfbench.reference.{config['family']}")
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.routed:
        from perfbench.kinds.serve_open_loop_routed import ROUTED_REL_TOL

        rows = routed_rows(config, cell, seeds)
        stats = [d.memory_stats() or {} for d in jax.devices()[:1]]
        summary = {"limit": ROUTED_REL_TOL, "rows": rows, "memory_peak_bytes": stats[0].get("peak_bytes_in_use")}
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(summary, f, indent=1)
        print(json.dumps({"routed_controls_seen_on_every_seed": all(r["seen"] for r in rows),
                          "memory_peak_bytes": summary["memory_peak_bytes"]}))
        return 0
    kind0 = config["model"]["attention_kinds"][0]
    m0 = config["model"]
    grid = [(m0["moe_out_init_scale"], kind0.get("latent_query_init_scale", 1.0), m0.get("moe_router_init_scale", 1.0))]
    grid += [(*(float(v) for v in g.split(":")), m0.get("moe_router_init_scale", 1.0))[:3] for g in args.grid.split(",") if g]
    c = cell["check"]
    sched, rows = None, []
    for g, (out_scale, query_scale, router_scale) in enumerate(grid):
        for seed in seeds if g == 0 else seeds[: args.grid_seeds]:
            t0 = time.perf_counter()
            model = dict(config["model"], moe_out_init_scale=out_scale, moe_router_init_scale=router_scale,
                         attention_kinds=[dict(k, latent_query_init_scale=query_scale) for k in config["model"]["attention_kinds"]])
            if sched is not None:
                sched.params = None  # the old weights go before the new are made: both do not fit
                gc.collect()
            params = api.init_lm_params(dict(config, model=model), seed)
            if sched is None:
                sched, _ = api.make_scheduler(params, dict(config, model=model), cell["deployment"], None)
            sched.params = params
            rng = np.random.default_rng([seed, 11])
            n, steps = c["prompt_tokens"], c["decode_tokens"]
            prompts = rng.integers(3, model["target_vocab_size"], size=(c["sequences"], n), dtype=np.int32)
            prompts[:, 0] = api.IdTokenizer.bos_id
            got = api.pool_forward_logits(sched, prompts, steps)
            full = np.concatenate([prompts, got[:, :steps].argmax(-1).astype(np.int32)], axis=1)
            t1 = time.perf_counter()
            row = {"seed": seed, "moe_out_init_scale": out_scale, "latent_query_init_scale": query_scale,
                   "moe_router_init_scale": router_scale}
            for name, alter in [("check", None), *CONTROLS.items()]:
                want = np.asarray(reference.logits(params, full, model, first=n - 1, alter=alter), np.float32)
                row[name] = float(np.abs(got - want).max() / np.abs(want).max())
                if name == "check":
                    row["argmax_agree"] = float((got.argmax(-1) == want.argmax(-1)).mean())
            row["seen"] = all(row[k] > LOGIT_REL_TOL for k in CONTROLS) and row["check"] <= LOGIT_REL_TOL
            row["program_s"], row["reference_s"] = round(t1 - t0, 1), round(time.perf_counter() - t1, 1)
            print(json.dumps(row), flush=True)
            rows.append(row)
            del params
    stats = [d.memory_stats() or {} for d in jax.devices()[:1]]
    summary = {"limit": LOGIT_REL_TOL, "rows": rows, "memory_peak_bytes": stats[0].get("peak_bytes_in_use")}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({"controls_seen_on_every_seed": all(r["seen"] for r in rows[: len(seeds)]),
                      "memory_peak_bytes": summary["memory_peak_bytes"]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
