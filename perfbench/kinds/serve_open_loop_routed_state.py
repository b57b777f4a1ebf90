"""Kind ``serve_open_loop_routed_state``: ``serve_open_loop_routed``, whose
``correct`` also holds each state-space layer alone to the reference, before
the traffic begins.

The logits of the whole model hardly see the precision a state-space layer
keeps its state in: a state rounded to bfloat16 between positions moves them
by less than the program's own bfloat16 rounding does over a check's few
decoded tokens, and so do its projections in float8. Here each state-space
layer is given the same rows of its normalised input on both sides, in the
served dtype: the program runs ``prompt_tokens`` of them as its prefill does,
then ``decode_tokens`` more one at a time as its pool's step does, from the
state its pool allocates and kept between positions as the pool keeps it;
the reference runs its float32 recurrence position by position. Compared:
the layer's outputs at every position, and the state after the last. A state
kept in fewer bytes a value than the cell's ``ssm_check.state_dtype`` (the
configuration's) is not ``correct`` whatever it reads.
"""

from __future__ import annotations

import gc
import importlib

import jax.numpy as jnp
import numpy as np

from perfbench import program_api as api
from perfbench import program_api_ssm
from perfbench.kinds import serve_open_loop_routed

# The largest |difference| of a layer's outputs over their largest |value|;
# of its final state, the same a head (over the head's own largest |value|:
# the heads' decays differ by orders of magnitude, and a state rounded a step
# at a time drifts most in the slow heads, whose state is the smallest), the
# largest over the heads. The program rounds the projections' outputs to
# bfloat16 (2**-9 of a value) and sums the state in float32; each limit is set
# between what the program reads and what the reference reads with its state
# kept in bfloat16 after the prompt, and with every weight in float8 (PERF.md
# section 6).
SSM_OUT_REL_TOL = 0.02
SSM_STATE_REL_TOL = 0.02


def ssm_check(ctx, config: dict, cell: dict, params, alter: dict | None = None) -> dict:
    """Each state-space layer, for ``cell["ssm_check"]["sequences"]`` random
    sequences of its normalised input, against ``reference.mixer``
    (``alter``: one of the reference's controls)."""
    m = config["model"]
    c = cell["ssm_check"]
    reference = importlib.import_module(f"perfbench.reference.{config['family']}")
    rng = np.random.default_rng([ctx.seed, 17])
    n, steps = c["prompt_tokens"], c["decode_tokens"]
    outs, states, kept, finite = [], [], set(), True
    for layer in program_api_ssm.ssm_layers(params):
        h = rng.standard_normal((c["sequences"], n + steps, m["d_model"]), dtype=np.float32)
        h = np.asarray(jnp.asarray(h, m.get("dtype", "float32")), np.float32)  # what both sides are given
        got, got_state, dtype = program_api_ssm.mixer_output(params, config, layer, h, n)
        want, want_state = (np.asarray(a, np.float32) for a in reference.mixer(params, layer, h, m, alter))
        finite &= bool(np.isfinite(got).all() and np.isfinite(got_state).all())
        outs.append(float(np.abs(got - want).max() / np.abs(want).max()))
        by_head = np.abs(got_state - want_state).max(axis=(-2, -1)) / np.abs(want_state).max(axis=(-2, -1))
        states.append(float(by_head.max()))
        kept.add(dtype)
    # Bytes a value of the state as the pool keeps it, against the configuration's.
    kept_bytes, want_bytes = min(np.dtype(d).itemsize for d in kept), np.dtype(c["state_dtype"]).itemsize
    ok = finite and max(outs) <= SSM_OUT_REL_TOL and max(states) <= SSM_STATE_REL_TOL and kept_bytes >= want_bytes
    return {"ok": bool(ok), "out_rel": max(outs), "state_rel": max(states), "state_kept_as": sorted(kept),
            "state_bytes_a_value": [kept_bytes, want_bytes],
            "tolerance_rel": [SSM_OUT_REL_TOL, SSM_STATE_REL_TOL], "out_by_layer": outs, "state_by_layer": states}


def run(ctx, config: dict, cell: dict) -> dict:
    params = api.init_lm_params(config, ctx.seed)
    verdict = ssm_check(ctx, config, cell, params)
    del params
    gc.collect()  # the weights are gone before the routed check makes its own
    ctx.mark("state-space layers against the reference")
    ctx.say("ssm_check", verdict)
    record = serve_open_loop_routed.run(ctx, config, cell)
    want = cell["ssm_check"]["state_dtype"]
    kept_bytes, want_bytes = verdict["state_bytes_a_value"]
    record["compared"]["ssm_out_off_rel"] = [verdict["out_rel"], "<=", SSM_OUT_REL_TOL]
    record["compared"]["ssm_state_off_rel"] = [verdict["state_rel"], "<=", SSM_STATE_REL_TOL]
    record["compared"]["ssm_state_bytes_a_value"] = [kept_bytes, ">=", want_bytes]
    if not verdict["ok"]:
        record["correct"] = False
        record["why_not_correct"].append(
            f"state-space layers off by {verdict['out_rel']:.3g} (outputs) and {verdict['state_rel']:.3g} (state) "
            f"of the largest, over {SSM_OUT_REL_TOL:g} / {SSM_STATE_REL_TOL:g}, or the state kept as "
            f"{verdict['state_kept_as']} where the configuration states {want}")
    return record
