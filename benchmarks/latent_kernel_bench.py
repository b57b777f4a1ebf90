"""Time ``paged_latent_attention`` alone on the chip, and its two halves.

    python benchmarks/latent_kernel_bench.py [--widths ax-k1 kimi]
        [--variants kernel copies copies_block fold] [--files FILE ...]
        [--seed 0] [--iters 20]

Runs each variant at the decode shapes of the two latent cells (256 slots
of rows ``[c ; kp ; 0]`` in 640 lanes, rank 512, pages of 16; 64 query heads
over a 384-page table for ``ax-k1``, 32 over 512 pages for ``kimi``), the slot
lengths drawn as those cells hold them: a prompt from the cell's log-normal
plus a uniform share of an output from its own (mean about 2,600 and 2,200
positions). Prints one JSON line a (width, variant): the seconds a call and
the nanoseconds a attended position, and what the roofline counts (1,152 B a
position at the chip's 819 GB/s) as a share of that time.

The variants split the kernel's work:

- ``kernel``: the kernel as the package has it.
- ``copies``: the copy machinery alone, on the grid and block walk of the
  kernel: the live pages of each block copied from the pool through the table
  (the next block's started before this block's wait), one wait a page.
- ``copies_block``: the same, one wait a block (a partial block by
  sub-chunks of 128 positions, then by pages).
- ``fold``: the fold alone over a buffer that stays resident: each block's
  score product, mask, online-softmax update and value product, one block
  after another, no copy and no wait.
- each file ``--files`` names (another version of
  ``kernels/paged_latent.py``): its kernel, under the file's stem, for a
  comparison in one process.

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

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from transformer_tpu.kernels.flash_attention import _MASK_GUARD, _MASKED, _compiler_params  # noqa: E402
from transformer_tpu.kernels.paged_latent import paged_latent_attention  # noqa: E402

SLOTS, RANK, WIDTH, PAGE, BLOCK = 256, 512, 640, 16, 512
ROW_BYTES = 2 * (512 + 64)  # what the roofline counts a position: rank + shared key part, bf16
HBM_BYTES_PER_S = 819e9  # TPU v5e (Google Cloud, "TPU v5e")

# The cells' decode shapes and how their slots' lengths are drawn
# (perfbench/workloads/<cell>.json: prompt and output sizes, pool and table).
WIDTHS = {
    "ax-k1": dict(heads=64, table_pages=384, pool_pages=46704,
                  prompt=(2048, 0.5, 512, 4096), output=(768, 0.6, 128, 2048)),
    "kimi": dict(heads=32, table_pages=512, pool_pages=42480,
                 prompt=(1024, 0.7, 128, 4096), output=(1536, 0.6, 256, 3072)),
}


def draw_lengths(rng, prompt, output, table_pages):
    def size(median, sigma, lo, hi):
        return np.clip(np.round(median * np.exp(sigma * rng.standard_normal(SLOTS))), lo, hi)

    lengths = size(*prompt) + np.floor(rng.uniform(0, 1, SLOTS) * size(*output))
    return np.clip(lengths, 1, table_pages * PAGE).astype(np.int32)


# --------------------------------------------------------------- the halves


def _walk(lengths_ref, pages):
    """The block walk the kernel makes: the number of blocks of slot ``seq``
    and the live pages of its block ``j``."""
    tokens = pages * PAGE

    def nblk(seq):
        return jnp.maximum(pl.cdiv(lengths_ref[seq], tokens), 1)

    def live(seq, j):
        return jnp.clip(pl.cdiv(lengths_ref[seq], PAGE) - j * pages, 0, pages)

    return nblk, live


def _copies_kernel(table_ref, lengths_ref, pool_hbm, out_ref, buf, sems, slot_ref, *, pages, per_block):
    s, n = pl.program_id(0), pl.num_programs(0)
    nblk, live = _walk(lengths_ref, pages)

    def start(seq, j, slot):
        def copy_page(p, carry):
            pltpu.make_async_copy(pool_hbm.at[table_ref[seq, j * pages + p]], buf.at[slot, p], sems.at[slot]).start()
            return carry

        jax.lax.fori_loop(0, live(seq, j), copy_page, 0)

    def wait_for(slot, size):
        def one(_, carry):
            part = buf.at[slot, pl.ds(0, size)]
            pltpu.make_async_copy(part, part, sems.at[slot]).wait()
            return carry

        return one

    def wait(slot, count):
        if per_block:
            chunk = 128 // PAGE
            jax.lax.fori_loop(0, count // pages, wait_for(slot, pages), 0)
            rest = count % pages
            jax.lax.fori_loop(0, rest // chunk, wait_for(slot, chunk), 0)
            jax.lax.fori_loop(0, rest % chunk, wait_for(slot, 1), 0)
        else:
            jax.lax.fori_loop(0, count, wait_for(slot, 1), 0)

    @pl.when(s == 0)
    def _prime():
        slot_ref[0] = 0
        start(0, 0, 0)

    count = nblk(s)

    def block_step(j, slot):
        last = j + 1 == count
        nxt = jnp.where(last, s + 1, s)

        @pl.when(nxt < n)
        def _prefetch():
            start(nxt, jnp.where(last, 0, j + 1), 1 - slot)

        wait(slot, live(s, j))
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, count, block_step, slot_ref[0])
    out_ref[0] = buf[slot_ref[0], 0, :8, :RANK].astype(jnp.float32)


def copies(q, pool, table, lengths, *, per_block):
    n = q.shape[0]
    pages = BLOCK // PAGE
    return pl.pallas_call(
        functools.partial(_copies_kernel, pages=pages, per_block=per_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 8, RANK), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, PAGE, WIDTH), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, 8, RANK), jnp.float32),
        compiler_params=_compiler_params(("arbitrary",)),
        name="latent_copies",
    )(table, lengths, pool)


def _fold_kernel(table_ref, lengths_ref, q_ref, rows_ref, out_ref, m_scr, l_scr, acc_scr, *, pages):
    del table_ref
    s = pl.program_id(0)
    tokens = pages * PAGE
    nblk, _ = _walk(lengths_ref, pages)
    length = lengths_ref[s]
    m_scr[...] = jnp.full_like(m_scr, _MASKED)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def block_step(j, carry):
        rows = rows_ref[...]
        scores = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        pos = j * tokens + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(pos < length, scores, _MASKED)
        m_prev, l_prev = m_scr[...][:, :1], l_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(scores > _MASK_GUARD, jnp.exp(scores - m_new), 0.0)
        l_scr[...] = jnp.broadcast_to(alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(rows.dtype), rows[:, :RANK], preferred_element_type=jnp.float32
        )
        return carry

    jax.lax.fori_loop(0, nblk(s), block_step, 0)
    out_ref[0] = (acc_scr[...] / l_scr[...][:, :1]).astype(out_ref.dtype)


def fold(q, pool, table, lengths):
    n, heads, width = q.shape
    pages = BLOCK // PAGE
    rows = pool[1 : pages + 1].reshape(BLOCK, width)  # one block, resident in VMEM
    return pl.pallas_call(
        functools.partial(_fold_kernel, pages=pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[
                pl.BlockSpec((1, heads, width), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec((BLOCK, width), lambda s, *_: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, heads, RANK), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((heads, 128), jnp.float32),
                pltpu.VMEM((heads, 128), jnp.float32),
                pltpu.VMEM((heads, RANK), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, heads, RANK), q.dtype),
        compiler_params=_compiler_params(("arbitrary",)),
        name="latent_fold",
    )(table, lengths, q, rows)


def _stem(path):
    return os.path.splitext(os.path.basename(path))[0]


def _load(path):
    """The kernel of another version of ``kernels/paged_latent.py``."""
    spec = importlib.util.spec_from_file_location(_stem(path), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return functools.partial(module.paged_latent_attention, rank=RANK)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--widths", nargs="+", default=list(WIDTHS), choices=list(WIDTHS))
    ap.add_argument("--variants", nargs="*", default=["kernel", "copies", "copies_block", "fold"],
                    choices=["kernel", "copies", "copies_block", "fold"])
    ap.add_argument("--files", nargs="*", default=[], help="versions of kernels/paged_latent.py to time beside")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    fns = {
        "kernel": functools.partial(paged_latent_attention, rank=RANK),
        "copies": functools.partial(copies, per_block=False),
        "copies_block": functools.partial(copies, per_block=True),
        "fold": fold,
    }
    fns.update({_stem(path): _load(path) for path in args.files})
    if jax.default_backend() != "tpu":
        raise SystemExit("latent_kernel_bench times the chip: no TPU is attached")

    device = jax.devices()[0]
    for name in args.widths:
        w = WIDTHS[name]
        rng = np.random.default_rng(args.seed)
        lengths_np = draw_lengths(rng, w["prompt"], w["output"], w["table_pages"])
        table_np = rng.integers(1, w["pool_pages"], (SLOTS, w["table_pages"]), dtype=np.int32)
        table_np[np.arange(w["table_pages"])[None, :] * PAGE >= lengths_np[:, None]] = 0
        k_pool, k_q = jax.random.split(jax.random.PRNGKey(args.seed))
        pool = jax.random.normal(k_pool, (w["pool_pages"], PAGE, WIDTH), jnp.bfloat16)
        q = (0.05 * jax.random.normal(k_q, (SLOTS, w["heads"], WIDTH))).astype(jnp.bfloat16)
        table, lengths = jnp.asarray(table_np), jnp.asarray(lengths_np)
        positions = int(lengths_np.sum())
        for variant in args.variants + [_stem(path) for path in args.files]:
            fn = jax.jit(fns[variant])
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, pool, table, lengths))
            compile_s = time.perf_counter() - t0
            for _ in range(3):
                out = fn(q, pool, table, lengths)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = fn(q, pool, table, lengths)
            jax.block_until_ready(out)
            seconds = (time.perf_counter() - t0) / args.iters
            ns = seconds * 1e9 / positions
            print(json.dumps({
                "width": name, "variant": variant, "heads": w["heads"], "slots": SLOTS,
                "mean_length": round(positions / SLOTS, 1), "call_s": seconds, "ns_per_position": round(ns, 4),
                "roofline_pct": round(100 * ROW_BYTES * positions / HBM_BYTES_PER_S / seconds, 2),
                "compile_s": round(compile_s, 2), "device": device.device_kind,
            }), flush=True)


if __name__ == "__main__":
    main()
