"""What the ``serve_open_loop_routed_state`` kind needs of the system under
test beyond ``perfbench/program_api.py``: one state-space layer of the
program, from the state its slot pool allocates (``models/decoder.py::
init_layer_state``), the prompt as its prefill runs it (``ops/ssd.py::
ssm_apply``, SSD's chunked form) and each later position as the pool's step
runs it (``models/paged_decode.py::ssm_pool_step``, ``kernels/ssm_step.py``
on the chip), the state stored between them in the dtype the pool keeps.
Like ``program_api.py``, the one place a rename in the program is followed.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.program_api import model_config
from transformer_tpu.models.decoder import init_layer_state
from transformer_tpu.models.paged_decode import ssm_pool_step
from transformer_tpu.ops.ssd import ssm_apply


def ssm_layers(params) -> list[int]:
    """The indices of the decoder's layers that hold a state-space mixer."""
    return [i for i, lp in enumerate(params["decoder"]["layers"]) if "ssm" in lp]


@partial(jax.jit, static_argnames=("cfg", "layer", "prompt"))
def _mixer(p, h, cfg, layer, prompt):
    kind = cfg.layer_kind(layer)
    kept = init_layer_state(cfg, layer, h.shape[0])
    out, state = ssm_apply(
        p, h[:, :prompt], kept, groups=kind.ssm_groups, state_size=kind.ssm_state,
        chunk=kind.ssm_chunk, epsilon=cfg.layernorm_epsilon,
    )
    state = {key: state[key].astype(kept[key].dtype) for key in kept}  # as the prefill stores it
    live = jnp.ones((h.shape[0],), bool)

    def step(state, ht):
        y, state = ssm_pool_step(p, ht[:, None], state, live, kind.ssm_groups, cfg.layernorm_epsilon)
        return state, y[:, 0]

    state, later = jax.lax.scan(step, state, jnp.moveaxis(h[:, prompt:], 1, 0))
    return jnp.concatenate([out, jnp.moveaxis(later, 0, 1)], axis=1), state["ssm_state"]


def mixer_output(params, config: dict, layer: int, h: np.ndarray, prompt: int):
    """Layer ``layer``'s mixer for sequences ``h`` (B, S, d_model) of its
    normalised input, given in the served dtype: the first ``prompt``
    positions as one prefill, the rest one step at a time. Returns (float32
    outputs (B, S, d_model), the state after the last position as float32
    (B, H, P, N), the name of the dtype the pool keeps it in)."""
    cfg = model_config(config)
    out, state = _mixer(params["decoder"]["layers"][layer]["ssm"], jnp.asarray(h, cfg.compute_dtype),
                        cfg, layer, prompt)
    return np.asarray(out, np.float32), np.asarray(state, np.float32), str(state.dtype)
