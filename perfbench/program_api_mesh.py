"""What a kind that trains over a mesh needs of the system under test, beside
``perfbench/program_api.py`` (which may not change): the trainer the
reference's ``distributed_train.py`` stands for, ``DistributedTrainer`` over a
device mesh, made as ``cli.distributed_train`` makes it.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from perfbench.program_api import _roughen, jax_key, model_config
from transformer_tpu.config import MeshConfig, TrainConfig
from transformer_tpu.parallel.distributed import DistributedTrainer, make_sharded_steps, put_batch
from transformer_tpu.parallel.mesh import make_mesh


def make_mesh_trainer(config: dict, train: dict, mesh: dict, seed: int, log_fn):
    """``DistributedTrainer`` over the first ``MeshConfig(**mesh).num_devices``
    devices; its state is made in its shards by the trainer itself and then
    roughened as ``program_api.make_trainer`` roughens the one-chip state (the
    same key, so a parameter replicated over the mesh is the one-chip cell's)."""
    cfg = model_config(config)
    tc = TrainConfig(seed=int(seed) % (2**31 - 1), **train)
    mc = MeshConfig(**mesh)
    devices = jax.devices()[: mc.num_devices]
    key = jax_key(seed)
    trainer = DistributedTrainer(
        cfg, tc, make_mesh(mc, devices), rng=key,
        log_dir=None, checkpoint=None, log_fn=log_fn, telemetry=None,
    )
    rough = jax.jit(
        lambda state: dataclasses.replace(state, params=_roughen(state.params, key)),
        out_shardings=trainer.shardings, donate_argnums=0,
    )
    trainer.state = rough(trainer.state)
    return trainer


def mesh_devices(trainer) -> int:
    return int(trainer.mesh.devices.size)


def make_mesh_check_step(trainer):
    """The sharded step program the comparison runs: the one
    ``DistributedTrainer`` builds for itself (``make_sharded_steps`` over its
    mesh, its state's shardings and its ``TrainConfig``), with the two
    differences a comparison with a reference forces: ``dropout_rate`` 0 (the
    reference drops nothing) and no donation (the trainer goes on from its
    state)."""
    cfg = dataclasses.replace(trainer.model_cfg, dropout_rate=0.0)
    step, _ = make_sharded_steps(trainer.mesh, cfg, trainer.train_cfg, trainer.shardings, trainer.shard_seq, donate=False)
    return step


def mesh_step_once(trainer, step, src, tgt, seed: int) -> dict:
    """ONE step of ``step`` on a global batch, from the trainer's own state,
    which it leaves as it was. The batch is placed as the trainer's
    ``_sharded_train_step`` places it (``put_batch``: a quarter of the rows to
    each chip of a data=4 mesh).

    What comes back is what the step itself made of the whole batch: the loss
    and the count of target tokens it averaged over (its ``metrics``), and the
    gradient its optimizer was handed, read out of Adam's first moment, which
    after one step from zero is ``(1 - beta1) * gradient``: the all-reduced
    gradient, as every chip applied it.
    """
    if int(trainer.state.step) != 0:
        raise ValueError("mesh_step_once reads the gradient out of Adam's first step: the trainer has stepped already")
    new_state, metrics = step(
        trainer.state, put_batch(np.asarray(src), trainer.mesh, trainer.shard_seq),
        put_batch(np.asarray(tgt), trainer.mesh, trainer.shard_seq), jax_key(seed),
    )
    moments = [s for s in jax.tree_util.tree_leaves(new_state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
               if hasattr(s, "mu")]
    if len(moments) != 1:
        raise ValueError(f"expected one Adam state in the optimizer's, found {len(moments)}")
    keep = 1.0 - trainer.train_cfg.adam_beta1
    grads = jax.jit(lambda mu: jax.tree.map(lambda m: m / keep, mu))(moments[0].mu)
    return {"loss": float(metrics["loss"]), "weight": float(metrics["weight"]), "grads": grads}


def on_one_device(tree):
    """A copy of a (replicated) tree on the first device alone, where the
    reference runs: nothing of the reference goes through the mesh."""
    return jax.device_put(tree, jax.devices()[0])
