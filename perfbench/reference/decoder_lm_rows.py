"""The keys and values a serving cache has to hold for a sequence of the
``decoder_lm`` family, layer by layer, from the plain reference
(``perfbench/reference/decoder_lm.py``): each layer's keys turned at their
positions and its values, computed from that layer's normalised input in
float32 at the highest matmul precision. It imports nothing from the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from perfbench.reference import decoder_lm as ref


@partial(jax.jit, static_argnames=("eps",))
def _keys_values(lp, x, eps):
    with jax.default_matmul_precision("highest"):
        h, p = ref.layer_norm(lp["ln1"], x, eps), lp["self_mha"]
        k = jnp.einsum("bsm,mhd->bshd", h, ref._f(p["key"]["kernel"])) + ref._f(p["key"]["bias"])
        v = jnp.einsum("bsm,mhd->bshd", h, ref._f(p["value"]["kernel"])) + ref._f(p["value"]["bias"])
        return ref.rope(k), v


def rows(params, ids, cfg: dict) -> list[tuple[jax.Array, jax.Array]]:
    """(1, S) ids -> for each layer (keys, values), each (S, KV heads, head size)."""
    dec, eps = params["decoder"], cfg["layernorm_epsilon"]
    x = ref._embed(dec["embedding"]["table"], ids, cfg["d_model"])
    out = []
    for lp in dec["layers"]:
        k, v = _keys_values(lp, x, eps)
        out.append((k[0], v[0]))
        x = ref._layer(lp, x, eps)
    return out
