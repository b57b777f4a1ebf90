"""Seeded BAD corpus for the TPA300 kernel verifier (tests/test_kernel_analysis.py).

Every entry here traces fine and stays in-bounds / under budget — the
point is that each kernel carries exactly one LINT defect (TPA301-305),
plus one module-level pallas_call that no entry covers (TPA300). No
conformance violations: the corpus must survive ``--update-baseline``.
The good twin is tpa_kernel_good_corpus.py.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ARB = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


# -- TPA301: bf16 accumulator scratch (init/flush discipline is correct) ----
def _acc_bf16_kernel(x_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += x_ref[...].astype(jnp.bfloat16)

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(jnp.float32)


def entry_acc_bf16():
    def fn(x):
        return pl.pallas_call(
            _acc_bf16_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.bfloat16)],
            compiler_params=_ARB,
            interpret=True,
        )(x)

    return fn, (jax.ShapeDtypeStruct((16, 128), jnp.float32),)


# -- TPA302: fp32 accumulator with NO init write at all ---------------------
def _no_init_kernel(x_ref, o_ref, acc_ref):
    acc_ref[...] += x_ref[...]

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _fin():
        o_ref[...] = acc_ref[...]


def entry_no_init():
    def fn(x):
        return pl.pallas_call(
            _no_init_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
            compiler_params=_ARB,
            interpret=True,
        )(x)

    return fn, (jax.ShapeDtypeStruct((16, 128), jnp.float32),)


# -- TPA303: exp of masked scores without a _MASK_GUARD clamp ---------------
def _masked_exp_kernel(x_ref, m_ref, o_ref):
    s = jnp.where(m_ref[...] > 0, x_ref[...], -1e30)
    o_ref[...] = jnp.exp(s - 1.0)


def entry_masked_exp():
    def fn(x, m):
        return pl.pallas_call(
            _masked_exp_kernel,
            grid=(2,),
            in_specs=[
                pl.BlockSpec((8, 128), lambda i: (i, 0)),
                pl.BlockSpec((8, 128), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
            interpret=True,
        )(x, m)

    return fn, (
        jax.ShapeDtypeStruct((16, 128), jnp.float32),
        jax.ShapeDtypeStruct((16, 128), jnp.int32),
    )


# -- TPA304: lane dim neither 128-aligned nor the full array dim ------------
def _misaligned_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def entry_misaligned():
    def fn(x):
        return pl.pallas_call(
            _misaligned_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((8, 100), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 100), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 200), jnp.float32),
            interpret=True,
        )(x)

    return fn, (jax.ShapeDtypeStruct((16, 200), jnp.float32),)


# -- TPA305: RNG (threefry) inside the kernel body --------------------------
def _rng_kernel(x_ref, o_ref):
    seed = x_ref[0, 0].astype(jnp.uint32)
    key = jax.random.PRNGKey(seed)
    noise = jax.random.uniform(key, x_ref.shape, jnp.float32)
    o_ref[...] = x_ref[...] + noise


def entry_rng():
    def fn(x):
        return pl.pallas_call(
            _rng_kernel,
            grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=True,
        )(x)

    return fn, (jax.ShapeDtypeStruct((8, 128), jnp.float32),)


# -- TPA300: a pallas_call no entry exercises -------------------------------
def orphan_kernel_caller(x):
    def _orphan_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    return pl.pallas_call(
        _orphan_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        interpret=True,
    )(x)


ANALYSIS_KERNEL_ENTRIES = {
    "acc_bf16": entry_acc_bf16,
    "no_init": entry_no_init,
    "masked_exp": entry_masked_exp,
    "misaligned": entry_misaligned,
    "rng": entry_rng,
}
