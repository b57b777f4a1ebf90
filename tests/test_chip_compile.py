"""AOT compiles for a described (not attached) TPU v5e: the kernels of the
main path, and the serving step that fuses them, at real widths.

Interpret mode and the abstract kernel verifier (``analysis kernels``) both
passed a paged-decode kernel the chip's compiler refused, so the compiler is
asked directly. The TPU compiler ships with the installed libtpu and needs
no chip: ``get_topology_desc`` describes a ``v5e:2x2`` host and ``jit(...)
.lower(shapes).compile()`` raises whatever the chip would raise. Nothing
runs, so these say nothing about results or times.

Only one process may load the TPU library, so the topology is described
inside a module-scoped fixture (never at import or collection), every
compile runs in this process, and every such test lives in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the compiler away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next run warns and compiles
    again), so the cache stays off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _compile(fn, *args, **jit_kwargs):
    compiled = jax.jit(fn, **jit_kwargs).lower(*args).compile()
    # The Mosaic custom call, not an interpret-mode emulation of the kernel.
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _placed(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


@pytest.mark.parametrize("seq,batch", [(64, 8), (4096, 1)])
def test_flash_attention_fwd_and_grad(one_chip, seq, batch):
    from transformer_tpu.kernels.flash_attention import flash_attention

    qkv = jax.ShapeDtypeStruct((batch, seq, 8, 64), BF16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)


# (id, slots, heads, KV heads, head size, block_tokens, table width, pool
# blocks, S_q, int8), the pool laid out as ``init_block_pool`` lays it out.
# The first six are the base preset's width in its cache variants: 8 bf16
# heads of 64 are kept two a lane row and streamed (a table of 128 positions:
# one short block); the int8 pool and 2 bf16 heads of 64 (half a packed
# sublane) keep pages by heads, every page of a compute block its own
# BlockSpec. ``lfm2`` is the LFM2 cell's shape, 32 heads over 8 KV heads of 64
# as 4 lane rows under 128 slots, decode and verify rows; ``cell``
# is the StarCoder2-3B cells' own shape, 24 heads over 2 KV heads of 128 under
# 48 slots and a table of 128 pages of 16 (the pools left in HBM, live pages
# copied by hand into blocks of 512 positions, a fold a width of live
# sub-chunks), decode and verify rows, and its int8 twin; ``wide`` the same
# route with four packed sublanes of heads.
_PAGED_CASES = [
    (f"{variant}-{h_kv}-{block}-{s_q}", 8, 8, h_kv, 64, block, 8, 64, s_q,
     variant == "int8")
    for variant, h_kv, block in [("bf16", 8, 16), ("int8", 8, 32), ("gqa", 2, 16)]
    for s_q in (1, 3)
] + [
    ("cell-bf16-1", 48, 24, 2, 128, 16, 128, 6145, 1, False),
    ("cell-bf16-4", 48, 24, 2, 128, 16, 128, 6145, 4, False),
    ("cell-int8-1", 48, 24, 2, 128, 32, 64, 3073, 1, True),
    ("wide-bf16-3", 8, 16, 8, 128, 16, 40, 330, 3, False),
    ("lfm2-bf16-1", 128, 32, 8, 64, 16, 256, 9216, 1, False),
    ("lfm2-bf16-2", 128, 32, 8, 64, 16, 256, 9216, 2, False),
]


def _assert_streamed_block(block_tokens, h_kv, d, nmax):
    """A streamed call at a deployment's shape works in blocks of 512 key
    positions in sub-chunks of 128, its four block buffers inside the budget
    (the compile that follows holds the whole kernel to the chip's VMEM)."""
    from transformer_tpu.kernels import paged_flash as pf

    pages, chunk = pf._pages_per_block(block_tokens, h_kv, d, 2, False, nmax, True)
    assert (pages * block_tokens, chunk * block_tokens) == (512, 128)
    assert 4 * pages * pf._page_vmem_bytes(block_tokens, h_kv, d, 2) <= pf._BUFFER_BUDGET


@pytest.mark.parametrize(
    "n,h,h_kv,d,block_tokens,nmax,num_blocks,s_q,quantized",
    [c[1:] for c in _PAGED_CASES], ids=[c[0] for c in _PAGED_CASES],
)
def test_paged_flash_attention(
    one_chip, n, h, h_kv, d, block_tokens, nmax, num_blocks, s_q, quantized
):
    from transformer_tpu.kernels.paged_flash import (
        _streamable,
        heads_per_lane_row,
        paged_flash_attention,
    )

    per_row = heads_per_lane_row(h_kv, d, BF16, quantized)
    page = (h_kv // per_row, d * per_row)
    assert (per_row == 2) == (h_kv == 8 and d == 64 and not quantized)
    if not quantized and _streamable(*page, BF16) and nmax * block_tokens >= 512:
        _assert_streamed_block(block_tokens, *page, nmax)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((num_blocks, block_tokens, *page), jnp.int8 if quantized else BF16)
    args = [sds((n, s_q, h, d), BF16), pool, pool, sds((n, nmax), jnp.int32),
            sds((n,), jnp.int32)]
    if quantized:
        scale = sds((num_blocks, block_tokens, h_kv, 1), jnp.float32)
        args += [scale, scale]

    def fn(q, k_pool, v_pool, table, lengths, k_scale=None, v_scale=None):
        return paged_flash_attention(
            q, k_pool, v_pool, table, lengths,
            k_scale=k_scale, v_scale=v_scale, interpret=False,
        )

    text = _compile(fn, *args).as_text()
    # One custom call, named, with the pools among its operands in their own
    # shape: what the benchmark's kernel metrics find it by.
    call = re.search(r"%paged_flash_attention[\w.]* = [^\n]*custom-call\([^\n]*", text)
    assert call and text.count("tpu_custom_call") == 1, text[-2000:]
    assert f"[{num_blocks},{block_tokens},{page[0]},{page[1]}]" in call.group(0)


@pytest.mark.parametrize("heads,window,s_q", [(72, 512, 1), (48, 0, 1), (72, 512, 3), (48, 0, 3)],
                         ids=["laguna-window", "laguna-full", "laguna-window-verify", "laguna-full-verify"])
def test_paged_flash_attention_band(one_chip, heads, window, s_q):
    """The Laguna cell's two layer kinds over one pool: 8 KV heads of 128,
    pages of 16, a table 256 wide, 32 slots; the window layer's band is static
    and does not bound the block: both kinds work in blocks of 512 positions
    (two K/V buffers of 4 MB between them, the unpacked heads of the widest
    fold beside them: the compile holds that to the chip's scoped VMEM)."""
    from transformer_tpu.kernels.paged_flash import _streamable, paged_flash_attention

    assert _streamable(8, 128, BF16)
    _assert_streamed_block(16, 8, 128, 256)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((5632, 16, 8, 128), BF16)

    def fn(q, k_pool, v_pool, table, lengths):
        return paged_flash_attention(q, k_pool, v_pool, table, lengths, window=window, interpret=False)

    text = _compile(fn, sds((32, s_q, heads, 128), BF16), pool, pool, sds((32, 256), jnp.int32),
                    sds((32,), jnp.int32)).as_text()
    assert re.search(r"%paged_flash_attention[\w.]* = [^\n]*custom-call\(", text) and text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("tokens", [32, 2048], ids=["decode", "prefill"])
def test_moe_expert_ffn_at_laguna_widths(one_chip, tokens):
    """The dropless expert layer at the cell's shapes: 128 held experts of
    3 x 3072 x 1024, 10 picks a token over 256; 16-row tiles at decode and
    128-row tiles at a 2,048-token prefill. The grouped kernel is named."""
    from transformer_tpu.ops.moe import dropless_tile_rows, moe_apply_dropless, moe_init

    assert dropless_tile_rows(tokens, 10, 256) == (16 if tokens == 32 else 128)
    p = jax.eval_shape(lambda: moe_init(jax.random.PRNGKey(0), 3072, 1024, 256, BF16, experts_held=128,
                                        activation="swiglu", shared_dff=1024))
    x = jax.ShapeDtypeStruct((tokens, 3072), BF16, sharding=one_chip)

    def fn(p, x):
        return moe_apply_dropless(p, x, num_experts=256, top_k=10, routed_scale=2.5, interpret=False)

    text = _compile(fn, _placed(p, one_chip), x).as_text()
    assert re.search(r"%moe_expert_ffn[\w.]* = [^\n]*custom-call\(", text) and text.count("tpu_custom_call") == 1


def test_moe_expert_ffn_at_kimi_widths(one_chip):
    """The same layer at the Kimi Linear cell's decode shapes: 128 held experts
    of 3 x 2304 x 1024 (a model width of 18 lane rows), 8 picks a token over
    256 sigmoid scores with a selection bias, 256 slots a step."""
    from transformer_tpu.ops.moe import moe_apply_dropless, moe_init

    p = jax.eval_shape(lambda: moe_init(jax.random.PRNGKey(0), 2304, 1024, 256, BF16, experts_held=128,
                                        activation="swiglu", shared_dff=1024, select_bias=True))
    x = jax.ShapeDtypeStruct((256, 2304), BF16, sharding=one_chip)

    def fn(p, x):
        return moe_apply_dropless(p, x, num_experts=256, top_k=8, routed_scale=2.446, score="sigmoid",
                                  renorm_epsilon=1e-20, interpret=False)

    text = _compile(fn, _placed(p, one_chip), x).as_text()
    assert re.search(r"%moe_expert_ffn[\w.]* = [^\n]*custom-call\(", text) and text.count("tpu_custom_call") == 1


def test_kda_step_at_kimi_widths(one_chip):
    """The delta-rule state update at the published widths: 32 heads of 128 x
    128 float32 (2.10 MB a slot) under the cell's 256 slots, one slot's state
    a block, the state aliased input to output. The kernel is named."""
    from transformer_tpu.kernels.kda_step import kda_step

    sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    n, h, d = 256, 32, 128

    def fn(state, q, k, v, g, beta, live):
        return kda_step(state, q, k, v, g, beta, live, interpret=False)

    compiled = _compile(fn, sds((n, h, d, d)), sds((n, h, d)), sds((n, h, d)), sds((n, h, d)), sds((n, h, d)),
                        sds((n, h)), sds((n,), jnp.int32), donate_argnums=(0,))
    text = compiled.as_text()
    assert re.search(r"%kda_step[\w.]* = [^\n]*custom-call\(", text) and text.count("tpu_custom_call") == 1
    assert compiled.memory_analysis().alias_size_in_bytes >= n * h * d * d * 4  # in place: no second 537 MB


def test_paged_latent_attention_at_kimi_widths(one_chip):
    """The latent decode attention at the published widths: 32 query heads
    against rows of 512 + 64 channels kept in 640 lanes, 256 slots, a table of
    512 pages of 16 (8,192 positions), the pool left in HBM. The kernel is
    named."""
    from transformer_tpu.kernels.paged_latent import paged_latent_attention
    from transformer_tpu.ops.mla import latent_width

    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)  # noqa: E731
    n, width = 256, latent_width(512, 64)
    assert width == 640

    def fn(q, pool, table, lengths):
        return paged_latent_attention(q, pool, table, lengths, rank=512, interpret=False)

    text = _compile(fn, sds((n, 32, width), BF16), sds((42480, 16, width), BF16), sds((n, 512), jnp.int32),
                    sds((n,), jnp.int32)).as_text()
    assert re.search(r"%paged_latent_attention[\w.]* = [^\n]*custom-call\(", text) and text.count("tpu_custom_call") == 1


def test_fused_ln_ffn(one_chip):
    from transformer_tpu.ops.ffn import ffn_init, fused_ln_ffn
    from transformer_tpu.ops.nn import layernorm_init

    d, dff = 512, 2048
    ffn = jax.eval_shape(lambda: ffn_init(jax.random.PRNGKey(0), d, dff))
    ln = jax.eval_shape(lambda: layernorm_init(d))
    x = jax.ShapeDtypeStruct((8, d), BF16, sharding=one_chip)

    def fn(ln_params, ffn_params, x):
        return fused_ln_ffn(ln_params, ffn_params, x, interpret=False)

    _compile(fn, _placed(ln, one_chip), _placed(ffn, one_chip), x)


def test_pool_step_paged_flash_at_base_widths(one_chip):
    """The whole decode step ``--decode_kernel paged_flash`` dispatches:
    6 layers x (paged attention + fused LN/FFN) at the base preset's widths,
    sized as ``--serve_slots 8 --kv_layout paged --prefix_block 16``."""
    from transformer_tpu.config import ModelConfig
    from transformer_tpu.models.transformer import transformer_init
    from transformer_tpu.serve import scheduler as sched

    cfg = ModelConfig(
        num_layers=6, d_model=512, num_heads=8, dff=2048,
        input_vocab_size=26880, target_vocab_size=26880, max_position=64,
        decoder_only=True, dtype="bfloat16",
    )
    slots, max_total, block = 8, 65, 16
    pool_blocks = 1 + slots * -(-max_total // block)
    key = jax.ShapeDtypeStruct((2,), np.uint32)
    params = jax.eval_shape(lambda k: transformer_init(k, cfg), key)
    pool, table, index = sched.abstract_paged_pool(
        cfg, slots, max_total, pool_blocks, block
    )
    toks = jax.ShapeDtypeStruct((slots,), np.int32)
    step = sched._pool_step_paged_flash.__wrapped__

    compiled = _compile(
        lambda p, c, tb, ix, t: step(p, c, tb, ix, t, cfg, block, False),
        *_placed((params, pool, table, index, toks), one_chip),
        donate_argnums=(1,),
    )
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 * cfg.num_layers
    # Each Mosaic call carries its kernel's own name (``pallas_call(name=)``):
    # what the profiler's device events, and the benchmark's kernel metrics,
    # are told apart by. Without it every call is %<jitted function>.<n>.
    for kernel in ("paged_flash_attention", "fused_ln_ffn"):
        named = re.findall(rf"%{kernel}(?:\.\d+)* = [^\n]*custom-call\(", text)
        assert len(named) == cfg.num_layers, (kernel, len(named))


def test_flash_attention_kernels_are_named(one_chip):
    from transformer_tpu.kernels.flash_attention import flash_attention

    qkv = jax.ShapeDtypeStruct((2, 256, 8, 64), BF16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv).as_text()
    # Under ``grad`` the transformations wrap the name (%jvp_<name>_.<n>,
    # %transpose_jvp_<name>__.<n>); the kernel's own stays in it.
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv"):
        assert re.search(rf"%\w*{kernel}[\w.]* = [^\n]*custom-call\(", text), kernel
