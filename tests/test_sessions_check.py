"""The sessions cell's check (``perfbench/kinds/serve_sessions.py``) at the
cell's rehearsal size on the CPU: second turns admitted through the prefix
cache, their first turn's blocks aliased from the pool and their suffix
prefilled at its offset, agree with the plain reference over the whole
history; and each fault ``perfbench/tests/sessions_check_controls.py``
plants is seen."""

import json
import os
import types

import pytest

from perfbench.tests.sessions_check_controls import FAULTS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "sc2-3b.sessions-shared-prefix"


@pytest.fixture(scope="module")
def rehearsal():
    from perfbench import program_api as api
    from perfbench.run import merged

    with open(os.path.join(ROOT, "perfbench", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(ROOT, "perfbench", "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    tiny = dict(cell["rehearse"])
    config["model"].update(tiny.pop("model"))
    cell = merged(cell, tiny)
    return config, cell, api.init_lm_params(config, 5)


@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_the_second_turn_served_from_the_cache_against_the_reference(rehearsal, fault):
    from perfbench import program_api_sessions
    from perfbench.kinds.serve_open_loop import LOGIT_REL_TOL
    from perfbench.kinds.serve_sessions import ROWS_REL_TOL, check

    config, cell, params = rehearsal
    sched, _ = program_api_sessions.make_scheduler(params, config, dict(cell["deployment"]), cell["prefix_cache"], None)
    plant, before_steps = FAULTS[fault] if fault else (None, None)
    undo = plant(sched) if plant is not None else None
    got = check(types.SimpleNamespace(seed=21), config, cell, sched, before_steps)
    if undo is not None:
        undo()
    assert all(hit == aliased >= 16 for hit, aliased in got["hit_and_aliased_tokens"])
    if fault is None:
        assert got["ok"] and got["rel"] < 1e-5 and got["rows_rel"] < 1e-5
    else:  # at this size the logits see each fault too; the rows see it by a whole row's size
        assert not got["ok"] and got["rel"] > LOGIT_REL_TOL and got["rows_rel"] > 5 * ROWS_REL_TOL
    assert not sched.busy
    sched.pool.alloc.check_consistency()  # the check leaves the pool's accounting whole, faults undone
