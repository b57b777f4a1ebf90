"""Time ``ssm_step`` alone on the chip, and its two halves.

    python benchmarks/ssm_kernel_bench.py [--variants kernel copies body]
        [--files FILE ...] [--seed 0] [--iters 20]

Runs each variant at the decode shape of ``nemotron-3-super.chat-saturated``
(the cell's 256 slots, every one live; a Mamba layer's 128 heads of 64 x 128
float32 state in 8 groups of B and C, from ``perfbench/configs/
nemotron-3-super.json``), the state donated and aliased in place as the pool
step runs it. Prints one JSON line a (version, variant): the seconds a call,
the microseconds a slot-layer, and what the roofline counts (a slot's state
and convolution inputs read once and written once, 8.51 MB a layer,
``perfbench/flops_bytes_nemotron.py``, at the chip's 819 GB/s) as a share of
that time.

The variants split the kernel's work, each built from the version's own
``pallas_call``:

- ``kernel``: the version's kernel as it is.
- ``copies``: the same grid, blocks and aliasing, the body ``s_out = s`` (and
  a read-out of zeros): what the copies of the state alone take.
- ``body``: the version's body over ONE slot's state block that stays resident
  (the state's block index is the same at every grid step, so it is fetched
  once and written back once): the update and read-out with no state copied.

The versions: the package's ``kernels/ssm_step.py``, and each file ``--files``
names (another version of it, e.g. the parent's from ``git show``), under the
file's stem, for a comparison in one process.

Only on a TPU: the timings of the Pallas interpreter say nothing of the chip.
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import os
import sys
import time
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from perfbench import flops_bytes_nemotron  # noqa: E402

CONFIG = os.path.join(REPO, "perfbench", "configs", "nemotron-3-super.json")
CELL = os.path.join(REPO, "perfbench", "workloads", "nemotron-3-super.chat-saturated.json")
HBM_BYTES_PER_S = 819e9  # TPU v5e (Google Cloud, "TPU v5e")
VARIANTS = ["kernel", "copies", "body"]


def _copies_body(*refs, **_):
    """The body of ``copies``: the state written back as it came, zeros read out."""
    s_ref, y_ref, s_out_ref = refs[-3:]
    s_out_ref[...] = s_ref[...]
    y_ref[...] = jnp.zeros_like(y_ref)


def _resident(spec):
    """The state's block (the one with four axes) at block 0 on every grid step."""
    if len(spec.block_shape) != 4:
        return spec
    return pl.BlockSpec(spec.block_shape, lambda i, *_: (0, 0, 0, 0))


def _rewired(variant):
    """A stand-in for ``pallas`` whose ``pallas_call`` builds ``variant``."""

    def pallas_call(kernel, *, grid_spec, **kw):
        if variant == "copies":
            kernel = _copies_body
        elif variant == "body":
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=grid_spec.num_scalar_prefetch,
                grid=grid_spec.grid,
                in_specs=[_resident(s) for s in grid_spec.in_specs],
                out_specs=[_resident(s) for s in grid_spec.out_specs],
            )
            kw["compiler_params"] = pltpu.CompilerParams(
                dimension_semantics=("arbitrary",), vmem_limit_bytes=kw["compiler_params"].vmem_limit_bytes
            )
        return pl.pallas_call(kernel, grid_spec=grid_spec, **kw)

    return types.SimpleNamespace(**{**vars(pl), "pallas_call": pallas_call})


def _load(path, variant):
    """``ssm_step`` of the version at ``path``, built as ``variant``."""
    spec = importlib.util.spec_from_file_location(f"ssm_step_{variant}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if variant != "kernel":
        module.pl = _rewired(variant)
    return module.ssm_step


def _stem(path):
    return os.path.splitext(os.path.basename(path))[0]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--variants", nargs="+", default=VARIANTS, choices=VARIANTS)
    ap.add_argument("--files", nargs="*", default=[], help="versions of kernels/ssm_step.py to time beside")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("ssm_kernel_bench times the chip: no TPU is attached")

    with open(CONFIG) as f:
        model = json.load(f)["model"]
    with open(CELL) as f:
        slots = json.load(f)["deployment"]["num_slots"]
    layers = flops_bytes_nemotron.ssm_layers(model)
    kind = layers[0]
    heads, p, n_state, groups = kind["ssm_heads"], kind["ssm_head_dim"], kind["ssm_state"], kind["ssm_groups"]
    layer_bytes = 2 * flops_bytes_nemotron.state_bytes_per_slot(model) / len(layers)

    k = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    x = jax.random.normal(k[0], (slots, heads, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (slots, heads)) - 1.0)
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=0.0, maxval=2.0))
    bmat = jax.random.normal(k[3], (slots, groups, n_state))
    cmat = jax.random.normal(k[4], (slots, groups, n_state))
    live = jnp.ones((slots,), jnp.int32)
    device = jax.devices()[0]

    versions = [("package", os.path.join(REPO, "transformer_tpu", "kernels", "ssm_step.py"))]
    versions += [(_stem(path), path) for path in args.files]
    for version, path in versions:
        for variant in args.variants:
            step = _load(path, variant)
            fn = jax.jit(lambda s, *rest, step=step: step(s, *rest)[::-1], donate_argnums=(0,))
            state = jax.random.normal(k[5], (slots, heads, p, n_state))
            t0 = time.perf_counter()
            state, y = fn(state, x, dt, a, bmat, cmat, live)
            jax.block_until_ready((state, y))
            compile_s = time.perf_counter() - t0
            for _ in range(3):
                state, y = fn(state, x, dt, a, bmat, cmat, live)
            jax.block_until_ready((state, y))
            t0 = time.perf_counter()
            for _ in range(args.iters):
                state, y = fn(state, x, dt, a, bmat, cmat, live)
            jax.block_until_ready((state, y))
            seconds = (time.perf_counter() - t0) / args.iters
            print(json.dumps({
                "version": version, "variant": variant, "slots": slots, "heads": heads, "head_dim": p,
                "state": n_state, "groups": groups, "call_s": seconds,
                "us_per_slot_layer": round(seconds * 1e6 / slots, 3),
                "roofline_pct": round(100 * layer_bytes * slots / HBM_BYTES_PER_S / seconds, 2),
                "compile_s": round(compile_s, 2), "device": device.device_kind,
            }), flush=True)
            del state, y


if __name__ == "__main__":
    main()
