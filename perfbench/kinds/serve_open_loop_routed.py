"""Kind ``serve_open_loop_routed``: ``serve_open_loop``, whose ``correct`` also
holds each expert layer's routed experts to the reference's, one layer at a
time, before the traffic begins.

``serve_open_loop``'s check compares the logits of the whole model. At a
checkpoint-like shape its own error is the rows that choose an expert near a
tie one way in bfloat16 and the other in float32, and a router that ignores
its groups, or experts computed in a precision below the configuration's,
move the logits by no more than those flips do. Here both sides are given
the same rows in the served dtype and route them with float32 sums of the
same products, so they part only at a tie: the rows whose choice lies within
``TIE`` of one (the reference's ``route_margin``) are left out, and what is
left differs by the experts' rounding alone.
"""

from __future__ import annotations

import gc
import importlib

import jax.numpy as jnp
import numpy as np

from perfbench import program_api as api
from perfbench import program_api_routed
from perfbench.kinds import serve_open_loop

# The largest |difference| of a layer's routed output over its largest
# |value|, over the rows not left out. The program rounds the experts' rows
# and the layer's output to bfloat16 (2**-9 of a value); the limit is set
# between what the program reads on the chip and what the same rows read
# against the reference with the routed experts in float8, and with the
# group limit off (PERF.md section 6).
ROUTED_REL_TOL = 0.03
# Two float32 sums of the same bfloat16 products differ by about 1e-6 of a
# sigmoid score; a row nearer a tie than this may fall either way.
TIE = 1e-4


def routed_check(ctx, config: dict, cell: dict, alter: dict | None = None, params=None) -> dict:
    """Each expert layer, for ``cell["routed_check"]["rows"]`` random rows of
    its normalised input a time, against ``reference.routed`` (``alter``:
    one of the reference's controls). Makes the weights from ``ctx.seed``
    where ``params`` is not given."""
    m = config["model"]
    reference = importlib.import_module(f"perfbench.reference.{config['family']}")
    if params is None:
        params = api.init_lm_params(config, ctx.seed)
    rng = np.random.default_rng([ctx.seed, 13])
    readings, left_out, total, finite = [], 0, 0, True
    for layer in program_api_routed.expert_layers(params):
        for rows in cell["routed_check"]["rows"]:
            x = rng.standard_normal((rows, m["d_model"]), dtype=np.float32)
            x = np.asarray(jnp.asarray(x, m.get("dtype", "float32")), np.float32)  # what both sides are given
            got, _ = program_api_routed.routed_output(params, config, layer, x)
            want, margin = (np.asarray(a, np.float32) for a in reference.routed(params, layer, x, m, alter))
            keep = margin >= TIE
            finite &= bool(np.isfinite(got).all())
            scale = float(np.abs(want[keep]).max())
            readings.append(float(np.abs(got[keep] - want[keep]).max()) / scale)
            left_out += int(rows - keep.sum())
            total += rows
    worst = max(readings)
    return {"ok": bool(finite and worst <= ROUTED_REL_TOL), "rel": worst, "tolerance_rel": ROUTED_REL_TOL,
            "by_layer_and_rows": readings, "rows": total, "rows_left_out_near_a_tie": left_out, "tie": TIE}


def run(ctx, config: dict, cell: dict) -> dict:
    verdict = routed_check(ctx, config, cell)  # its weights are gone before the run makes its own
    gc.collect()
    ctx.mark("routed expert layers against the reference")
    ctx.say("routed_check", verdict)
    record = serve_open_loop.run(ctx, config, cell)
    record["compared"]["routed_off_rel"] = [verdict["rel"], "<=", ROUTED_REL_TOL]
    if not verdict["ok"]:
        record["correct"] = False
        record["why_not_correct"].append(
            f"routed experts off by {verdict['rel']:.3g} of the largest, over {ROUTED_REL_TOL:g}")
    return record
