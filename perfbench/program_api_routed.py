"""What the ``serve_open_loop_routed`` kind needs of the system under test
beyond ``perfbench/program_api.py``: one routed expert layer of the program,
called as the decoder calls it (``models/encoder.py::dropless_moe``: the
model's routing constants, ``kernels/moe_ffn.py`` on the chip). Like
``program_api.py``, the one place a rename in the program is followed.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.program_api import model_config
from transformer_tpu.models.encoder import dropless_moe


def expert_layers(params) -> list[int]:
    """The indices of the decoder's layers that hold a routed expert layer."""
    return [i for i, lp in enumerate(params["decoder"]["layers"]) if "moe" in lp]


@partial(jax.jit, static_argnames=("cfg",))
def _routed(moe_params, x, cfg):
    return dropless_moe(moe_params, x, cfg, None)


def routed_output(params, config: dict, layer: int, x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Layer ``layer``'s routed experts held here, without the shared expert,
    for rows ``x`` (T, d_model) given in the served dtype: (float32 (T,
    d_model), the layer's counts [picks held here, experts hit, rows of the
    most-loaded expert, tokens with a pick here])."""
    cfg = model_config(config)
    routed = {k: v for k, v in params["decoder"]["layers"][layer]["moe"].items() if k != "shared"}
    y, counts = _routed(routed, jnp.asarray(x, cfg.compute_dtype), cfg)
    return np.asarray(y, np.float32), np.asarray(counts)
