"""The two plain references against the program at a tiny size on the CPU, in
float32, where they have to agree to rounding."""

import json
import os

import jax
import numpy as np
import pytest

from perfbench import program_api as api
from perfbench.reference import decoder_lm, seq2seq

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny(config_name, cell_name):
    with open(os.path.join(HERE, "configs", config_name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "workloads", cell_name + ".json")) as f:
        cell = json.load(f)
    config["model"].update(cell["rehearse"]["model"])
    return config, cell


def test_seq2seq_loss_and_gradients():
    config, cell = tiny("transformer-big-ende", "tbig-ende.train-1chip")
    trainer = api.make_trainer(config, {**cell["train"], "batch_size": 4}, 5, lambda s: None)
    params = api.trainer_params(trainer)
    rng = np.random.default_rng(0)
    src = rng.integers(1, 500, (3, 12)).astype(np.int32)
    tgt = rng.integers(1, 500, (3, 12)).astype(np.int32)
    src[0, 9:] = 0
    tgt[1, 7:] = 0
    p_loss, p_grads = api.program_loss_and_grads(params, src, tgt, config, 0.1)
    r_loss, r_grads = seq2seq.loss_and_grads(params, src, tgt, config["model"], 0.1)
    assert abs(float(p_loss) - float(r_loss)) < 1e-5
    for g, r in zip(jax.tree_util.tree_leaves(p_grads), jax.tree_util.tree_leaves(r_grads)):
        assert np.allclose(np.asarray(g), np.asarray(r), atol=2e-6, rtol=1e-3)


def test_decoder_lm_prefill_and_decode_through_the_pool_programs():
    config, cell = tiny("starcoder2-3b", "sc2-3b.chat-saturated")
    params = api.init_lm_params(config, 5)
    dep = {**cell["deployment"], **cell["rehearse"]["deployment"]}
    sched, _ = api.make_scheduler(params, config, dep, None)
    rng = np.random.default_rng(1)
    prompts = rng.integers(3, 256, (2, 16)).astype(np.int32)
    got = api.pool_forward_logits(sched, prompts, 3)
    full = np.concatenate([prompts, got[:, :3].argmax(-1).astype(np.int32)], axis=1)
    want = np.asarray(decoder_lm.logits(params, full, config["model"], first=15))
    assert got.shape == want.shape == (2, 4, 256)
    assert np.abs(got - want).max() < 1e-4
    assert api.pool_usage(sched)[0] <= 1  # the pool is left idle (the sink block aside)


def _drop_ffn_bias(m):
    m.setattr(seq2seq, "ffn", lambda p, x: jax.nn.relu(x @ p["in"]["kernel"]) @ p["out"]["kernel"] + p["out"]["bias"])


def _drop_norm_bias(m):
    plain = seq2seq.layer_norm
    m.setattr(seq2seq, "layer_norm", lambda p, x, eps: plain(p, x, eps) - p["bias"])


def _drop_embedding_scale(m):
    m.setattr(seq2seq, "embed", lambda table, ids, d: table[ids] + seq2seq.sinusoids(ids.shape[1], d)[None])


def _drop_padding_mask(m):
    plain = seq2seq.attention
    m.setattr(seq2seq, "attention", lambda p, q, kv, allowed: plain(p, q, kv, allowed | (allowed.shape[-2] == 1)))


def _scale_scores_by_model_width(m):
    plain = seq2seq.attention
    heads = lambda p: p["query"]["kernel"].shape[1]  # noqa: E731
    m.setattr(seq2seq, "attention", lambda p, q, kv, allowed: plain(
        {**p, "query": jax.tree_util.tree_map(lambda x: x / np.sqrt(heads(p)), p["query"])}, q, kv, allowed))


@pytest.mark.parametrize("fault", [_drop_ffn_bias, _drop_norm_bias, _drop_embedding_scale, _drop_padding_mask,
                                   _scale_scores_by_model_width])
def test_training_check_refuses_a_structural_fault(fault, monkeypatch):
    """The limits of the training check leave room for what rounding does around
    a ReLU (kinds/train_steps.py); a fault of structure is far over each of them."""
    from perfbench.kinds import train_steps

    config, cell = tiny("transformer-big-ende", "tbig-ende.train-1chip")
    cell = {**cell, "check": cell["rehearse"]["check"]}
    trainer = api.make_trainer(config, {**cell["train"], "batch_size": 4}, 5, lambda s: None)
    ctx = type("Ctx", (), {"seed": 5})()
    assert train_steps.check(ctx, config, cell, trainer)["ok"]
    fault(monkeypatch)
    verdict = train_steps.check(ctx, config, cell, trainer)
    math = verdict["math"]
    assert not verdict["ok"], verdict
    assert math["worst_leaf_rel"] > 5 * train_steps.MATH_LEAF_REL_TOL, verdict
    assert math["global_grad_rel"] > 5 * train_steps.MATH_GLOBAL_REL_TOL, verdict
    assert math["loss_rel"] > 3 * train_steps.MATH_LOSS_REL_TOL, verdict
