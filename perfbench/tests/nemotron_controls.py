"""The three checks of a state-space expert model, and the controls that show
each is not blind, on the chip:

    python3 -m perfbench.tests.nemotron_controls --workload nemotron-3-super.chat-saturated \
        --seeds 4100000001,4100000002 [--grid 0.1,0.05] [--out controls.json] [--rehearse]

One process, one scheduler of the cell's deployment (its programs are the ones
a run of the cell compiles). For each point of ``--grid`` (the routed experts'
out kernels' factor ``moe_out_init_scale``; the configuration's own point
first, with every seed; the others with the first) and seed: the weights, the
check's prompts, what the program gives through its pool programs, and the
reference's logits as the configuration states them and with each control of
``perfbench/reference/nemotron_h_lm.py``; then the routed expert layers'
check of ``serve_open_loop_routed``, as the configuration states it and with
each of ``ROUTED_CONTROLS``; then the state-space layers' check of
``serve_open_loop_routed_state``, as the configuration states it and with
each of ``SSM_CONTROLS``. Each reading is its check's: the largest
|difference| over the largest |value|, against its limit. A control has to
read above one of the limits; the checks themselves below all of them.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import time
import types

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROLS = {
    "state_bfloat16": {"state_bfloat16": True},
    "no_D": {"no_D": True},
    "norm_whole": {"norm_whole": True},
    "no_conv_bias": {"no_conv_bias": True},
    "rope_attention": {"rope_attention": True},
    "router_latent": {"router_latent": True},
    "experts_float8": {"expert_dtype": "float8_e4m3fn"},
    "weights_float8": {"weights_dtype": "float8_e4m3fn"},
}
ROUTED_CONTROLS = {
    "router_latent": {"router_latent": True},
    "experts_float8": {"expert_dtype": "float8_e4m3fn"},
    "experts_float8_e5m2": {"expert_dtype": "float8_e5m2"},
    "weights_float8": {"weights_dtype": "float8_e4m3fn"},
    "weights_float8_e5m2": {"weights_dtype": "float8_e5m2"},
}
# ``state_kept_bfloat16`` is given the check's prompt length where it is read.
SSM_CONTROLS = {
    "state_kept_bfloat16": {"state_kept_bfloat16": None},
    "state_bfloat16": {"state_bfloat16": True},
    "no_D": {"no_D": True},
    "weights_float8": {"weights_dtype": "float8_e4m3fn"},
    "weights_float8_e5m2": {"weights_dtype": "float8_e5m2"},
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--grid", default="", help="further moe_out_init_scale points, first seed only")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true", help="the cell's rehearsal size, on whatever JAX finds")
    ap.add_argument("--controls", default="", help="the logits controls to read (default: all); 'none' for none")
    ap.add_argument("--routed", default="", help="the routed controls to read (default: all); 'none' for none")
    ap.add_argument("--ssm", default="", help="the state-space controls to read (default: all); 'none' for none")
    args = ap.parse_args()
    import importlib

    import jax

    from transformer_tpu.utils.profiling import enable_compilation_cache

    enable_compilation_cache()
    from perfbench import program_api as api
    from perfbench.kinds.serve_open_loop import LOGIT_REL_TOL
    from perfbench.kinds.serve_open_loop_routed import ROUTED_REL_TOL, routed_check
    from perfbench.kinds.serve_open_loop_routed_state import SSM_OUT_REL_TOL, SSM_STATE_REL_TOL, ssm_check

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
    grid = [config["model"]["moe_out_init_scale"]] + [float(g) for g in args.grid.split(",") if g]

    def chosen(table, names):
        return table if not names else {k: v for k, v in table.items() if k in names.split(",")}

    controls, routed_controls = chosen(CONTROLS, args.controls), chosen(ROUTED_CONTROLS, args.routed)
    ssm_controls = {k: {a: (cell["ssm_check"]["prompt_tokens"] if v is None else v) for a, v in alter.items()}
                    for k, alter in chosen(SSM_CONTROLS, args.ssm).items()}
    c = cell["check"]
    sched, rows = None, []
    for g, out_scale in enumerate(grid):
        for seed in seeds if g == 0 else seeds[:1]:
            t0 = time.perf_counter()
            model = dict(config["model"], moe_out_init_scale=out_scale)
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
            row = {"seed": seed, "moe_out_init_scale": out_scale}
            for name, alter in [("check", None), *controls.items()]:
                want = np.asarray(reference.logits(params, full, model, first=n - 1, alter=alter), np.float32)
                row[name] = float(np.abs(got - want).max() / np.abs(want).max())
                if name == "check":
                    row["argmax_agree"] = float((got.argmax(-1) == want.argmax(-1)).mean())
            t2 = time.perf_counter()
            ctx = types.SimpleNamespace(seed=seed)
            for name, alter in [("check", None), *routed_controls.items()]:
                v = routed_check(ctx, dict(config, model=model), cell, alter=alter, params=params)
                row[f"routed.{name}"] = v["rel"]
                if name == "check":
                    row["routed.rows"], row["routed.rows_left_out_near_a_tie"] = v["rows"], v["rows_left_out_near_a_tie"]
            t3 = time.perf_counter()
            for name, alter in [("check", None), *ssm_controls.items()]:
                v = ssm_check(ctx, dict(config, model=model), cell, params, alter=alter)
                row[f"ssm.{name}"] = [v["out_rel"], v["state_rel"]]
            row["seen"] = {k: row.get(k, 0.0) > LOGIT_REL_TOL or row.get(f"routed.{k}", 0.0) > ROUTED_REL_TOL
                           or row.get(f"ssm.{k}", [0.0, 0.0])[0] > SSM_OUT_REL_TOL
                           or row.get(f"ssm.{k}", [0.0, 0.0])[1] > SSM_STATE_REL_TOL
                           for k in {*controls, *routed_controls, *ssm_controls}}
            row["checks_pass"] = (row["check"] <= LOGIT_REL_TOL and row["routed.check"] <= ROUTED_REL_TOL
                                  and row["ssm.check"][0] <= SSM_OUT_REL_TOL and row["ssm.check"][1] <= SSM_STATE_REL_TOL)
            row["program_s"], row["reference_s"], row["routed_s"], row["ssm_s"] = (
                round(t1 - t0, 1), round(t2 - t1, 1), round(t3 - t2, 1), round(time.perf_counter() - t3, 1))
            print(json.dumps(row), flush=True)
            rows.append(row)
            del params
    stats = [d.memory_stats() or {} for d in jax.devices()[:1]]
    summary = {"limits": [LOGIT_REL_TOL, ROUTED_REL_TOL, SSM_OUT_REL_TOL, SSM_STATE_REL_TOL], "rows": rows,
               "memory_peak_bytes": stats[0].get("peak_bytes_in_use")}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({"memory_peak_bytes": summary["memory_peak_bytes"],
                      "checks_pass": [r["checks_pass"] for r in rows],
                      "unseen": sorted({k for r in rows[: len(seeds)] for k, v in r["seen"].items() if not v})}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
