"""The A.X K1 reference (``reference/mla_moe_lm.py``) against the program at a
tiny size on the CPU, through the scheduler's own pool programs as the cell's
check runs them, and the parts of the mathematics its comparison has to see
(the controls of PERF.md section 6; ``tests/mla_moe_controls.py`` reads them
on the chip at the cell's size)."""

import json
import os

import numpy as np
import pytest

from perfbench import program_api as api
from perfbench.kinds.serve_open_loop import LOGIT_REL_TOL
from perfbench.reference import mla_moe_lm

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "ax-k1.longdoc-saturated"


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    config, cell = load("configs", "ax-k1.json"), load("workloads", CELL + ".json")
    config["model"].update(cell["rehearse"]["model"])
    # The routed branch at Glorot size: in float32 no near-tie parts the two
    # computations, and a fault of the router then shows at its full size.
    config["model"].update(moe_out_init_scale=1.0)
    params = api.init_lm_params(config, 5)
    dep = {**cell["deployment"], **cell["rehearse"]["deployment"]}
    sched, _ = api.make_scheduler(params, config, dep, None)
    prompts = np.random.default_rng(1).integers(3, 256, (2, 21)).astype(np.int32)  # a block of 16 and a tail
    got = api.pool_forward_logits(sched, prompts, 3)
    full = np.concatenate([prompts, got[:, :3].argmax(-1).astype(np.int32)], axis=1)
    return config["model"], params, got, full


def rel(got, want):
    want = np.asarray(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_prefill_and_decode_through_the_pool_programs(tiny):
    model, params, got, full = tiny
    assert rel(got, mla_moe_lm.logits(params, full, model, first=20)) < 1e-4
    assert model["layer_pattern"] == ["mla"] and model["moe_leading_dense"] == 1


@pytest.mark.parametrize("alter", [
    {"no_score_factor": True}, {"no_key_rotation": True}, {"no_group_limit": True}, {"group_max": True},
    {"weights_dtype": "float8_e4m3fn"},
])
def test_the_check_refuses_a_part_left_out(alter, tiny):
    """The kind's limit (3 % of the largest logit) leaves room for bfloat16; a
    part of the mathematics left out of the reference, or its weights in a
    precision below the configuration's, moves the logits past it."""
    model, params, got, full = tiny
    assert rel(got, mla_moe_lm.logits(params, full, model, first=20, alter=alter)) > LOGIT_REL_TOL


def test_the_routed_experts_in_float8_move_the_logits(tiny):
    """The routed experts' weights and rows at 3 mantissa bits: seen here,
    where the branch is at Glorot size and nothing flips; at the cell's size
    on the chip the check's limit does not part it from bfloat16 (PERF.md
    section 6)."""
    model, params, got, full = tiny
    assert rel(got, mla_moe_lm.logits(params, full, model, first=20, alter={"expert_dtype": "float8_e4m3fn"})) > 1e-3
