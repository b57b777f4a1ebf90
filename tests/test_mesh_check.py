"""What the benchmark's comparison over a mesh (``perfbench/kinds/train_steps_mesh.py::mesh_check``)
takes from the program: one step of ``DistributedTrainer``'s sharded program on
a global batch hands back, through Adam's first moment, the all-reduced
gradient of the WHOLE batch, with the loss and the token count of all of it."""

import json
import os

import jax
import numpy as np
import pytest

from perfbench import program_api as api
from perfbench import program_api_mesh as mesh_api
from perfbench.kinds import train_steps_mesh as kind
from perfbench.run import merged

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3300000007


def load(*parts):
    with open(os.path.join(ROOT, "perfbench", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rehearsal():
    cell = load("workloads", "tbig-ende.train-dp4.json")
    config = load("configs", cell["config"] + ".json")
    tiny = dict(cell["rehearse"])
    config["model"].update(tiny.pop("model"))
    cell = merged(cell, tiny)
    trainer = mesh_api.make_mesh_trainer(config, cell["train"], cell["mesh"], SEED, lambda line: None)
    src, tgt = kind.mesh_batch(SEED, config, cell)
    return config, cell, trainer, mesh_api.make_mesh_check_step(trainer), src, tgt


def flat(tree):
    return np.concatenate([np.asarray(x, np.float32).ravel() for x in jax.tree_util.tree_leaves(tree)])


def test_one_mesh_step_hands_back_the_whole_batch_gradient(rehearsal):
    config, cell, trainer, step, src, tgt = rehearsal
    assert mesh_api.mesh_devices(trainer) == 4 and len(src) == 16
    before = flat(trainer.state.params)
    got = mesh_api.mesh_step_once(trainer, step, src, tgt, SEED)
    loss, grads = api.program_loss_and_grads(api.trainer_params(trainer), src, tgt, config,
                                             cell["train"]["label_smoothing"], dtype="float32")
    assert got["weight"] == (tgt[:, 1:] != 0).sum()
    assert got["loss"] == pytest.approx(float(loss), rel=1e-5)
    want = flat(grads)
    assert np.linalg.norm(flat(got["grads"]) - want) < 1e-4 * np.linalg.norm(want)
    # The trainer goes on from the state it had: nothing was donated or stepped.
    assert int(trainer.state.step) == 0 and np.array_equal(flat(trainer.state.params), before)


def test_mesh_check_passes_the_whole_batch_and_fails_a_quarter(rehearsal):
    config, cell, trainer, step, src, tgt = rehearsal
    ref = kind.whole_batch_reference(config, cell, api.trainer_params(trainer), src, tgt)
    assert ref["tokens"] == (tgt[:, 1:] != 0).sum()
    whole = kind.mesh_compare(trainer, step, SEED, src, tgt, ref)
    assert whole["ok"] and whole["compared"]["mesh_step_global_grad_rel"][0] < 1e-4
    quarter = kind.mesh_compare(trainer, step, SEED, np.tile(src[:4], (4, 1)), np.tile(tgt[:4], (4, 1)), ref)
    assert not quarter["ok"] and quarter["compared"]["mesh_step_global_grad_rel"][0] > 0.5
    padded = (src.copy(), tgt.copy())
    padded[0][4:], padded[1][4:] = 0, 0
    rest_left_out = kind.mesh_compare(trainer, step, SEED, *padded, ref)
    assert not rest_left_out["ok"] and rest_left_out["compared"]["mesh_step_tokens_off"][0] > 0


def test_the_reference_takes_the_batch_in_pieces_weighted_by_their_tokens(rehearsal, monkeypatch):
    config, cell, trainer, _, src, tgt = rehearsal
    params = api.trainer_params(trainer)
    at_once = kind.whole_batch_reference(config, cell, params, src, tgt)
    monkeypatch.setattr(kind, "REFERENCE_ROWS_A_CALL", 6)  # 6 + 6 + 4 rows, of unequal token counts
    in_pieces = kind.whole_batch_reference(config, cell, params, src, tgt)
    assert in_pieces["loss"] == pytest.approx(at_once["loss"], rel=1e-5)
    a, b = np.concatenate([x.ravel() for x in at_once["leaves"]]), np.concatenate([x.ravel() for x in in_pieces["leaves"]])
    assert np.linalg.norm(a - b) < 1e-4 * np.linalg.norm(a)


def test_mesh_step_once_refuses_a_trainer_that_has_stepped(rehearsal):
    config, cell, _, _, src, tgt = rehearsal
    trainer = mesh_api.make_mesh_trainer(config, cell["train"], cell["mesh"], SEED, lambda line: None)
    step = mesh_api.make_mesh_check_step(trainer)
    trainer.state = jax.tree.map(lambda x: x, trainer.state)
    trainer.state.step = trainer.state.step + 1
    with pytest.raises(ValueError, match="has stepped already"):
        mesh_api.mesh_step_once(trainer, step, src, tgt, SEED)
