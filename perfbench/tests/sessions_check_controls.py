"""The ``serve_sessions`` check, and the planted faults that show it is not
blind, on the chip:

    python3 -m perfbench.tests.sessions_check_controls --workload sc2-3b.sessions-shared-prefix \
        --seeds 3900000301,3900000302,3900000303 [--out controls.json] [--rehearse]

One process, one scheduler of the cell's deployment with its prefix cache,
given each seed's weights in turn. For each seed: the check as the cell runs it, then once with each fault of
``FAULTS`` planted in the scheduler for the length of one check (each on
prompts of its own, so no fault leaves blocks that a later check's prompts
would match). A fault has to read not ``ok``; the check itself ``ok``.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import time
import types

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _shifted_alias(sched, slot):
    """Each entry of the slot's table that a hit aliased names the block one
    entry later (the last, the suffix's first block): every cached row read
    one block late, until the steps are done."""
    table = sched.pool.alloc.table
    a = sched._active[slot].prefix_hit // sched.pool.block_tokens
    was = table[slot].copy()
    table[slot, :a] = was[1 : a + 1]
    sched.pool.alloc._dirty = True

    def undo():
        table[slot, :a] = was[:a]
        sched.pool.alloc._dirty = True

    return undo


def _stale_first(sched, slot):
    """The slot's first block holds what its second holds: a block whose
    content is not the positions the cache says it holds. (Its rows merely
    permuted would be no fault: attention reads a block's rows as a set.)"""
    b0, b1 = (int(b) for b in sched.pool.alloc.table[slot, :2])
    sched.pool.caches = _block_copier()(sched.pool.caches, b0, b1)


@functools.cache
def _block_copier():
    """Block ``s``'s rows written over block ``d``'s in every pool, in place:
    the pools are donated, as the scheduler's own programs donate them (a copy
    of every pool does not fit beside the weights at the cell's size)."""
    import jax

    return jax.jit(lambda c, d, s: jax.tree.map(lambda x: x.at[d].set(x[s]), c), donate_argnums=0)


def _after_restore(fault):
    """Runs ``fault(sched, slot)`` after the admission aliases a hit's blocks
    and before the suffix is prefilled over them."""

    def plant(sched):
        restore = sched._paged_restore

        def wrapped(slot, hit, m):
            aliased = restore(slot, hit, m)
            fault(sched, slot)
            return aliased

        sched._paged_restore = wrapped
        return lambda: delattr(sched, "_paged_restore")

    return plant


def _suffix_one_late(sched):
    """A prefill that starts after a hit writes and turns its rows one
    position late."""
    import jax.numpy as jnp

    prefill = sched._fn_slot_prefill_paged

    def wrapped(params, caches, table, slot, toks, first, *rest):
        return prefill(params, caches, table, slot, toks, jnp.where(first > 0, first + 1, first), *rest)

    sched._fn_slot_prefill_paged = wrapped
    return lambda: setattr(sched, "_fn_slot_prefill_paged", prefill)


# name: (plant(sched) -> undo, or None; the check's ``before_steps``, or None)
FAULTS = {
    "shifted_alias": (None, _shifted_alias),
    "stale_block": (_after_restore(_stale_first), None),
    "suffix_one_late": (_suffix_one_late, None),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true", help="the cell's rehearsal size, on whatever JAX finds")
    args = ap.parse_args()
    import jax
    import numpy as np

    from perfbench import program_api as api
    from transformer_tpu.utils.profiling import enable_compilation_cache

    enable_compilation_cache()
    from perfbench import program_api_sessions
    from perfbench.kinds.serve_open_loop import _warm_shapes
    from perfbench.kinds.serve_sessions import check

    with open(os.path.join(HERE, "workloads", args.workload + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    if args.rehearse:
        from perfbench.run import merged

        tiny = dict(cell["rehearse"])
        config["model"].update(tiny.pop("model"))
        cell = merged(cell, tiny)
    rows, sched = [], None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if sched is not None:
            sched.params = None  # the old weights go before the new are made
            gc.collect()
        params = api.init_lm_params(config, seed)
        if sched is None:
            sched, _ = program_api_sessions.make_scheduler(params, config, dict(cell["deployment"]), cell["prefix_cache"], None)
            _warm_shapes(sched, {"prompt": cell["warm"]}, config["model"]["target_vocab_size"], np.random.default_rng([seed, 5]))
        sched.params = params
        row = {"seed": seed}
        for i, (name, (plant, before_steps)) in enumerate([("check", (None, None)), *FAULTS.items()]):
            undo = plant(sched) if plant is not None else None
            try:
                v = check(types.SimpleNamespace(seed=seed * 8 + i), config, cell, sched, before_steps)
            finally:
                if undo is not None:
                    undo()
            row[name] = {k: v[k] for k in ("ok", "rel", "rows_rel", "hit_and_aliased_tokens")}
        row["seen"] = row["check"]["ok"] and not any(row[k]["ok"] for k in FAULTS)
        row["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(row), flush=True)
        rows.append(row)
        del params
    stats = [d.memory_stats() or {} for d in jax.devices()[:1]]
    summary = {"rows": rows, "memory_peak_bytes": stats[0].get("peak_bytes_in_use")}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({"faults_seen_on_every_seed": all(r["seen"] for r in rows),
                      "memory_peak_bytes": summary["memory_peak_bytes"]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
