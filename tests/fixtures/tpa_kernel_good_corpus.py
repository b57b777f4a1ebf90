"""GOOD twin of tpa_kernel_bad_corpus.py — same kernels with the defects
fixed; the verifier must report ZERO findings and ZERO violations here."""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ARB = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


# -- twin of acc_bf16: accumulator widened to fp32 --------------------------
def _acc_f32_kernel(x_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += x_ref[...]

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _fin():
        o_ref[...] = acc_ref[...]


def entry_acc_f32():
    def fn(x):
        return pl.pallas_call(
            _acc_f32_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
            compiler_params=_ARB,
            interpret=True,
        )(x)

    return fn, (jax.ShapeDtypeStruct((16, 128), jnp.float32),)


# -- twin of no_init: first-grid-step @pl.when init -------------------------
def _init_kernel(x_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += x_ref[...]

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _fin():
        o_ref[...] = acc_ref[...]


def entry_init():
    def fn(x):
        return pl.pallas_call(
            _init_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
            compiler_params=_ARB,
            interpret=True,
        )(x)

    return fn, (jax.ShapeDtypeStruct((16, 128), jnp.float32),)


# -- twin of masked_exp: guard clamp around the exp -------------------------
def _guarded_exp_kernel(x_ref, m_ref, o_ref):
    s = jnp.where(m_ref[...] > 0, x_ref[...], -1e30)
    o_ref[...] = jnp.where(s > -1e29, jnp.exp(s - 1.0), 0.0)


def entry_guarded_exp():
    def fn(x, m):
        return pl.pallas_call(
            _guarded_exp_kernel,
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


# -- twin of misaligned: lane dim padded up to the native 128 ---------------
def _aligned_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def entry_aligned():
    def fn(x):
        return pl.pallas_call(
            _aligned_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
            interpret=True,
        )(x)

    return fn, (jax.ShapeDtypeStruct((16, 128), jnp.float32),)


# -- twin of rng: noise generated OUTSIDE the kernel ------------------------
def _add_kernel(x_ref, n_ref, o_ref):
    o_ref[...] = x_ref[...] + n_ref[...]


def entry_noise_outside():
    def fn(x, noise):
        return pl.pallas_call(
            _add_kernel,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((8, 128), lambda i: (0, 0)),
                pl.BlockSpec((8, 128), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=True,
        )(x, noise)

    return fn, (
        jax.ShapeDtypeStruct((8, 128), jnp.float32),
        jax.ShapeDtypeStruct((8, 128), jnp.float32),
    )


ANALYSIS_KERNEL_ENTRIES = {
    "acc_f32": entry_acc_f32,
    "init": entry_init,
    "guarded_exp": entry_guarded_exp,
    "aligned": entry_aligned,
    "noise_outside": entry_noise_outside,
}
