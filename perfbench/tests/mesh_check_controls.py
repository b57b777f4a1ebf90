"""The ``train_steps_mesh`` kind's comparison over the mesh, and its controls.

    python3 perfbench/tests/mesh_check_controls.py --workload tbig-ende.train-dp4 \
        --seeds 3300000001,3300000002,3300000003 [--rehearse]

One trainer over the cell's mesh (weights from the first seed) and, a seed,
one global batch of the cell's ``mesh_check`` size. A line a (seed, case):

``whole``          the check as a run makes it: one step of the trainer's
                   sharded program on the batch, against the plain reference
                   on the whole batch. Has to come out correct.
``quarter_k``      the same step handed chip k's quarter of the rows on every
                   chip, against the same reference: what chip k holds after a
                   step whose all-reduce was left out (its own quarter's
                   gradient, averaged over its own tokens).
``first_quarter_only``  the step handed the batch with every row past the
                   first quarter padded out: a trainer that trains on 256 of
                   the 1,024 rows, all-reduce and all.
Every control has to come out NOT correct; the script exits 1 otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="tbig-ende.train-dp4")
    ap.add_argument("--seeds", default="3300000001")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    import jax
    import numpy as np

    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from perfbench import program_api as api
    from perfbench import program_api_mesh as mesh_api
    from perfbench.kinds import train_steps_mesh as kind
    from perfbench.run import load_json, merged

    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    if args.rehearse:
        tiny = dict(cell["rehearse"])
        config["model"].update(tiny.pop("model"))
        cell = merged(cell, tiny)
    t0 = time.perf_counter()
    trainer = mesh_api.make_mesh_trainer(config, cell["train"], cell["mesh"], seeds[0], lambda line: None)
    step = mesh_api.make_mesh_check_step(trainer)
    chips = mesh_api.mesh_devices(trainer)
    wrong = 0
    for seed in seeds:
        src, tgt = kind.mesh_batch(seed, config, cell)
        ref = kind.whole_batch_reference(config, cell, api.trainer_params(trainer), src, tgt)
        part = len(src) // chips
        cases = {"whole": (src, tgt)}
        for k in range(chips):
            rows = slice(k * part, (k + 1) * part)
            cases[f"quarter_{k}"] = (np.tile(src[rows], (chips, 1)), np.tile(tgt[rows], (chips, 1)))
        only = (src.copy(), tgt.copy())
        only[0][part:], only[1][part:] = 0, 0
        cases["first_quarter_only"] = only
        for name, (s, t) in cases.items():
            v = kind.mesh_compare(trainer, step, seed, s, t, ref)
            as_wanted = v["ok"] == (name == "whole")
            wrong += not as_wanted
            print(json.dumps({"seed": seed, "case": name, "correct": v["ok"], "as_wanted": as_wanted,
                              "compared": v["compared"], "step": v["step"], "reference_loss": v["reference_loss"],
                              "tokens": v["tokens"], "worst_leaf_rel": v["worst_leaf_rel"],
                              "at_s": round(time.perf_counter() - t0, 1)}), flush=True)
    dev = jax.devices()[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()[:chips])
    print(json.dumps({"device": f"{dev.platform}:{dev.device_kind}", "chips": chips, "memory_peak_bytes": peak,
                      "rows": len(src), "not_as_wanted": wrong, "took_s": round(time.perf_counter() - t0, 1)}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
