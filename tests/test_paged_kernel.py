"""Fused Pallas paged-decode kernels (``--decode_kernel paged_flash``):
interpreter-mode parity of the block-table flash kernel against the XLA
gather oracle across cache variants (bf16/int8/GQA) x speculative verify
rows (S_q = k + 1, per-row offset causality) x fragmented/aliased tables;
end-to-end answer byte-identity through the continuous scheduler (greedy +
seeded sampling, chunked prefill, speculate_k, prefix aliasing incl. the
CoW write-guard path); and the paged_flash retrace budget — zero
steady-state recompiles across alloc/free/alias/spill admissions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transformer_tpu.config import ModelConfig
from transformer_tpu.data.tokenizer import SubwordTokenizer
from transformer_tpu.kernels.flash_attention import paged_attention
from transformer_tpu.models import transformer_init
from transformer_tpu.ops.attention import _quantize_kv
from transformer_tpu.serve import ContinuousScheduler, PrefixCache

pytestmark = pytest.mark.pallas


def _cfg(tok, **kw) -> ModelConfig:
    base = dict(
        num_layers=2, d_model=16, num_heads=2, dff=32,
        input_vocab_size=tok.model_vocab_size,
        target_vocab_size=tok.model_vocab_size,
        max_position=64, decoder_only=True, tie_output=True,
        dtype="float32", dropout_rate=0.0,
    )
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def tok():
    return SubwordTokenizer.build_from_corpus(
        ["ab cd ef gh ij kl mn"] * 3, target_vocab_size=300
    )


# Same acceptance matrix as the paged-vs-dense parity suite
# (tests/test_kv_pool.py): bf16, int8, GQA; the windowed variant REFUSES
# paged_flash (pinned below) because the kernel carries no band mask.
VARIANTS = {
    "bf16": dict(dtype="bfloat16"),
    "int8": dict(kv_cache_int8=True),
    "gqa": dict(num_kv_heads=1),
}

WAVES = [
    [
        {"prompt": "ab cd ef gh ij", "max_new": 6},
        {"prompt": "ab cd ef gh kl", "max_new": 5, "temperature": 0.9,
         "seed": 3},
    ],
    [
        {"prompt": "ab cd ef gh ij", "max_new": 6},          # full hit
        {"prompt": "ab cd ef gh mn", "max_new": 4, "temperature": 0.7,
         "top_k": 4, "seed": 1},                             # partial hit
    ],
]


# --------------------------------------------------------------------------
# kernel-level parity: paged_flash vs the XLA gather oracle
#
# The oracle ("xla") is bitwise-identical to the dense cache path
# (test_kv_pool.test_paged_attention_matches_dense), so agreement here
# chains to the dense math. The kernel's per-element scores match the
# oracle exactly (the QK contraction is only over D); what differs is the
# softmax/PV reduction ORDER (online accumulation across blocks vs one
# dense reduction), a low-bit effect bounded per compute dtype.

_TOL = {
    "fp32": 5e-6, "bf16": 3e-2, "int8": 3e-2, "gqa": 3e-2,
    "serve_bf16": 3e-2, "serve_int8": 3e-2, "lane_fp32": 5e-6, "lane_int8": 3e-2,
    "pair_bf16": 3e-2, "pair_fp32": 5e-6, "pair2_fp32": 5e-6, "pair4_bf16": 3e-2,
    "lone_bf16": 3e-2, "odd_fp32": 5e-6, "half_bf16": 3e-2,
}

# The first four are the toy widths (D = 8): their pages do not fill a lane
# row, so the kernel feeds every page of a block through its own BlockSpec.
# The others have pages of whole tiles (D = 128), which the kernel copies by
# hand from the pool left in HBM, except ``serve_int8``: two int8 heads are
# half a packed sublane. ``serve_*`` are the serving cells' ratios at toy
# size: 2 KV heads of 128 under 12 query heads each, pages of 16. ``pair*``
# are heads of 64 that fill whole lane rows, which the pool keeps two a row
# (``heads_per_lane_row``) and the kernel streams: the LFM2 cell's 8 under 32
# query heads in both dtypes, and the fewest heads that pair in each. The last
# three are heads of 64 that do NOT pair (one head, three, and two bf16 heads:
# one lane row of bf16 is half a packed sublane): pages by heads, tiled.
_KERNEL_VARIANTS = {
    "fp32": dict(dtype=jnp.float32, h_q=2, h_kv=2, quantized=False),
    "bf16": dict(dtype=jnp.bfloat16, h_q=2, h_kv=2, quantized=False),
    "int8": dict(dtype=jnp.bfloat16, h_q=2, h_kv=2, quantized=True),
    "gqa": dict(dtype=jnp.bfloat16, h_q=4, h_kv=1, quantized=False),
    "serve_bf16": dict(dtype=jnp.bfloat16, h_q=24, h_kv=2, quantized=False,
                       d=128, block_tokens=16),
    "serve_int8": dict(dtype=jnp.bfloat16, h_q=24, h_kv=2, quantized=True,
                       d=128, block_tokens=16),
    "lane_fp32": dict(dtype=jnp.float32, h_q=4, h_kv=2, quantized=False, d=128),
    "lane_int8": dict(dtype=jnp.bfloat16, h_q=8, h_kv=4, quantized=True,
                      d=128, block_tokens=32),
    "pair_bf16": dict(dtype=jnp.bfloat16, h_q=32, h_kv=8, quantized=False,
                      d=64, block_tokens=16),
    "pair_fp32": dict(dtype=jnp.float32, h_q=32, h_kv=8, quantized=False, d=64),
    "pair2_fp32": dict(dtype=jnp.float32, h_q=4, h_kv=2, quantized=False, d=64),
    "pair4_bf16": dict(dtype=jnp.bfloat16, h_q=4, h_kv=4, quantized=False,
                       d=64, block_tokens=16),
    "lone_bf16": dict(dtype=jnp.bfloat16, h_q=4, h_kv=1, quantized=False, d=64),
    "odd_fp32": dict(dtype=jnp.float32, h_q=6, h_kv=3, quantized=False, d=64),
    "half_bf16": dict(dtype=jnp.bfloat16, h_q=2, h_kv=2, quantized=False, d=64),
}
_PAIRED = ("pair_bf16", "pair_fp32", "pair2_fp32", "pair4_bf16")


def _page(variant: str) -> tuple[int, int]:
    """A token's page as ``init_block_pool`` lays it out for this variant:
    (H_kv, D), or whole lane rows of ``128 // D`` heads each."""
    from transformer_tpu.kernels.paged_flash import heads_per_lane_row

    spec = _KERNEL_VARIANTS[variant]
    d = spec.get("d", 8)
    per_row = heads_per_lane_row(spec["h_kv"], d, spec["dtype"], spec["quantized"])
    return spec["h_kv"] // per_row, d * per_row


def _block_positions(variant: str, block_tokens: int) -> tuple[int, int]:
    """Key positions in one compute block of the kernel, and in one of its
    sub-chunks (the block itself where it has none), for this variant's
    shapes under a table wide enough not to bound it, by the kernel's rule:
    the route decides, and a window layer's band does not."""
    from transformer_tpu.kernels.paged_flash import _pages_per_block, _streamable

    spec = _KERNEL_VARIANTS[variant]
    rows, lanes = _page(variant)
    itemsize = 1 if spec["quantized"] else jnp.dtype(spec["dtype"]).itemsize
    streamed = not spec["quantized"] and _streamable(rows, lanes, spec["dtype"])
    pages, chunk = _pages_per_block(
        block_tokens, rows, lanes, itemsize, spec["quantized"], 1 << 20, streamed
    )
    return block_tokens * pages, block_tokens * chunk


def _pool_case(
    variant: str, s_q: int, block_tokens: int | None = None, seed: int = 0,
    wide: bool = False,
):
    """A deliberately hostile pool: every row filled with random data (stale
    rows hold garbage the mask must hide), fragmented out-of-order tables,
    a slot aliasing another's first blocks (a prefix hit / pre-CoW share),
    unused entries parked on sink block 0, and lengths that end mid-block.

    Narrow (the default): 7 blocks under a table of 4 entries, one compute
    block. ``wide``: a table of two compute blocks and a half, so not a whole
    number of them (1,280 positions under the streamed route's 512), over
    slots whose newest position lies (a) inside the first page, (b) on the
    last position of the first compute block, (c) on the first of the second,
    (d) at the table's full width, (e) on the last position of the first
    sub-chunk and (f) on the first of the next (with ``s_q`` > 1 the query
    rows then straddle that edge), beside (g) a free slot: length ``s_q``
    under an all-sink table.

    The pool comes out in the layout ``init_block_pool`` gives these shapes
    (``_page``): drawn by heads, then the same bytes as whole lane rows where
    the heads pair."""
    spec = _KERNEL_VARIANTS[variant]
    block_tokens = block_tokens or spec.get("block_tokens", 8)
    rng = np.random.default_rng(seed)
    d = spec.get("d", 8)
    if wide:
        positions, chunk = _block_positions(variant, block_tokens)
        nmax = 5 * positions // (2 * block_tokens)
        lengths = np.array(
            [max(s_q, block_tokens // 2), positions, positions + 1,
             nmax * block_tokens, chunk, chunk + 1, s_q], np.int32,
        )
        owned = -(-lengths // block_tokens)
        owned[-1] = 0
        n, blocks = len(lengths), 1 + int(owned.sum())
        ids = rng.permutation(np.arange(1, blocks))
        table = np.zeros((n, nmax), np.int32)
        for slot, count in enumerate(owned):
            table[slot, :count], ids = ids[:count], ids[count:]
        table[2, :2] = table[1, :2]  # slot 2 aliases slot 1's first two blocks
        table, lengths = jnp.asarray(table), jnp.asarray(lengths)
    else:
        blocks, n = 7, 3
        table = jnp.asarray(
            [[3, 5, 1, 0], [6, 2, 4, 0], [3, 5, 2, 0]], jnp.int32
        )
        index = jnp.asarray(
            [block_tokens + 2, block_tokens // 2, 2 * block_tokens - 2],
            jnp.int32,
        )
        lengths = index + s_q
    kf = rng.standard_normal((blocks, block_tokens, spec["h_kv"], d))
    vf = rng.standard_normal((blocks, block_tokens, spec["h_kv"], d))
    kf, vf = (x.reshape(blocks, block_tokens, *_page(variant)) for x in (kf, vf))
    q = jnp.asarray(
        rng.standard_normal((n, s_q, spec["h_q"], d)), spec["dtype"]
    )
    if spec["quantized"]:
        k, k_scale = _quantize_kv(jnp.asarray(kf, jnp.float32))
        v, v_scale = _quantize_kv(jnp.asarray(vf, jnp.float32))
        return q, k, v, table, lengths, dict(k_scale=k_scale, v_scale=v_scale)
    return (
        q,
        jnp.asarray(kf, spec["dtype"]),
        jnp.asarray(vf, spec["dtype"]),
        table,
        lengths,
        {},
    )


def _assert_kernel_parity(
    variant: str, s_q: int, block_tokens: int | None = None, wide: bool = False
):
    q, k, v, table, lengths, kw = _pool_case(
        variant, s_q, block_tokens, wide=wide
    )
    want = paged_attention(q, k, v, table, lengths, impl="xla", **kw)
    got = paged_attention(
        q, k, v, table, lengths, impl="paged_flash", interpret=True, **kw
    )
    assert got.shape == want.shape and got.dtype == want.dtype
    tol = _TOL[variant]
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("s_q", [1, 3])
@pytest.mark.parametrize("variant", sorted(_KERNEL_VARIANTS))
def test_kernel_parity_matrix(variant, s_q):
    """paged_flash vs the XLA oracle, per-variant tolerance: decode rows
    (S_q=1) and speculative verify rows (S_q=k+1 — query i attends pool
    positions <= lengths - S_q + i, per-row offset causality the S_q=1
    flash impl cannot express), on fragmented/aliased tables."""
    _assert_kernel_parity(variant, s_q)


@pytest.mark.parametrize("s_q", [1, 3])
@pytest.mark.parametrize("variant", sorted(_KERNEL_VARIANTS))
def test_kernel_parity_wide_table(variant, s_q):
    """The schedule's own edges: a table wider than one compute block and
    not a whole number of them; lengths inside the first page, on a
    compute-block boundary, one past it and at the table's full width; a
    free slot (all-sink table) beside full ones. Both ways the kernel has of
    fetching a block (``_KERNEL_VARIANTS``), decode and verify rows."""
    _assert_kernel_parity(variant, s_q, wide=True)


@pytest.mark.slow
@pytest.mark.parametrize("block_tokens", [4, 16])
@pytest.mark.parametrize("variant", sorted(_KERNEL_VARIANTS))
def test_kernel_parity_block_sizes(variant, block_tokens):
    """The full sweep: every variant x non-default pool block sizes
    (tier-1 pins block_tokens=8 above), verify-shaped rows throughout."""
    _assert_kernel_parity(variant, 3, block_tokens)


def test_kernel_rejects_untileable_block_tokens():
    """Regression: a pool whose block_tokens neither divides nor is a
    multiple of the dtype's native sublane (bf16 -> 16) used to reach the
    kernel and produce silently wrong tiling; it must be rejected up front
    with an actionable error."""
    q, k, v, table, lengths, kw = _pool_case("bf16", 1, block_tokens=6)
    with pytest.raises(ValueError, match="block_tokens 6 is incompatible"):
        paged_attention(
            q, k, v, table, lengths, impl="paged_flash", interpret=True, **kw
        )
    # The boundary cases stay accepted: divisor of the sublane and an
    # exact multiple of it.
    for ok_bt in (4, 32):
        _assert_kernel_parity("bf16", 1, ok_bt)


@pytest.mark.parametrize(
    "variant,wide,window",
    [("bf16", False, 0), ("bf16", True, 0), ("serve_bf16", True, 0),
     ("bf16", True, 24), ("serve_bf16", True, 200),
     ("pair_bf16", True, 0), ("pair_bf16", True, 200)],
    ids=["last_entry", "every_dead_entry", "every_dead_entry_streamed",
         "before_the_band", "before_the_band_streamed",
         "every_dead_entry_paired", "before_the_band_paired"],
)
def test_kernel_skips_sink_blocks(variant, wide, window):
    """Out-of-length table entries are never read: rewriting them to
    arbitrary (even out-of-range-of-length) block ids leaves the output
    bit-identical, pinning the stale-row/sink masking the pool's free
    list relies on. Narrow: the one dead entry of each slot. Wide: every
    entry past each slot's length, those inside a live compute block (and
    inside a live sub-chunk) and those of whole dead blocks, on both ways of
    fetching a block. With a band: every entry whose page ends before it
    too, in the band's first block and in the blocks before it."""
    q, k, v, table, lengths, kw = _pool_case(variant, 1, wide=wide)
    if window:
        kw["window"] = window
    base = paged_attention(
        q, k, v, table, lengths, impl="paged_flash", interpret=True, **kw
    )
    if wide:
        block_tokens, blocks = k.shape[1], k.shape[0]
        entry = jnp.arange(table.shape[1])[None, :]
        dead = entry * block_tokens >= lengths[:, None]
        if window:
            before = (entry + 1) * block_tokens <= lengths[:, None] - window
            assert int(before.sum()) > table.shape[1] // 2
            dead |= before
        noise = jnp.asarray(
            np.random.default_rng(1).integers(0, blocks, table.shape), jnp.int32
        )
        hostile = jnp.where(dead, noise, table)
        assert int(jnp.sum(hostile != table)) > table.shape[1]
    else:
        hostile = table.at[:, -1].set(jnp.asarray([4, 1, 6], jnp.int32))
    got = paged_attention(
        q, k, v, hostile, lengths, impl="paged_flash", interpret=True, **kw
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    if window:
        want = paged_attention(q, k, v, table, lengths, impl="xla", **kw)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=_TOL[variant], atol=_TOL[variant],
        )


@pytest.mark.parametrize(
    "h_kv,d,dtype,quantized,per_row",
    [(2, 128, "bfloat16", False, 1), (8, 128, "bfloat16", False, 1),
     (8, 64, "bfloat16", False, 2), (8, 64, "float32", False, 2),
     (8, 64, "bfloat16", True, 1), (2, 64, "float32", False, 2),
     (2, 64, "bfloat16", False, 1), (1, 64, "bfloat16", False, 1),
     (3, 64, "float32", False, 1), (8, 32, "bfloat16", False, 4),
     (2, 8, "float32", False, 1), (4, 96, "float32", False, 1)],
    ids=["starcoder2-3b", "laguna-s-2.1", "lfm2-8b-a1b", "lfm2-fp32", "lfm2-int8",
         "two-fp32", "two-bf16-half-a-sublane", "one-head", "three-heads",
         "four-a-row", "toy", "no-divisor"],
)
def test_heads_per_lane_row_rule(h_kv, d, dtype, quantized, per_row):
    """The one rule that lays a pool out: heads narrower than a lane row are
    kept ``128 // D`` a row exactly where they fill whole rows and such a page
    streams. Of the three served configurations' shapes only LFM2's packs;
    ``init_block_pool`` allocates what the rule says, the same bytes either
    way, and the kernel's route follows from the pool it is handed."""
    from transformer_tpu.kernels.paged_flash import heads_per_lane_row, streams
    from transformer_tpu.ops.attention import init_block_pool

    assert heads_per_lane_row(h_kv, d, jnp.dtype(dtype), quantized) == per_row
    pool = jax.eval_shape(
        lambda: init_block_pool(5, 16, h_kv, d, jnp.dtype(dtype), quantize=quantized)
    )
    assert pool["k"].shape == pool["v"].shape == (5, 16, h_kv // per_row, d * per_row)
    if per_row > 1:
        assert streams(pool["k"].shape, pool["k"].dtype, quantized) and pool["k"].shape[-1] == 128
    if quantized:
        assert pool["k_scale"].shape == (5, 16, h_kv, 1) and not streams(pool["k"].shape, pool["k"].dtype, True)


@pytest.mark.parametrize("variant", sorted(_KERNEL_VARIANTS))
def test_kernel_route_by_variant(variant):
    """Which way each variant's pool is fetched, by the kernel's own rule
    over the pool as laid out: the paired heads stream; heads of 64 that do
    not pair keep pages by heads and the tiled route."""
    from transformer_tpu.kernels.paged_flash import streams

    _, k, _, _, _, kw = _pool_case(variant, 1)
    spec = _KERNEL_VARIANTS[variant]
    paired = variant in _PAIRED
    assert (k.shape[-1] != spec.get("d", 8)) == paired
    streamed = {"serve_bf16", "lane_fp32", *_PAIRED}
    assert streams(k.shape, k.dtype, bool(kw)) == (variant in streamed)


@pytest.mark.parametrize("variant", _PAIRED)
def test_paired_pool_is_the_same_bytes(variant):
    """A pool kept two heads a lane row is a reshape of the pool by heads:
    the XLA oracle reads both to the same bits (so the parity matrix's oracle
    on a paired pool IS the dense mathematics), and the kernel handed the
    by-heads shape (the tiled route) agrees with the streamed one."""
    q, k, v, table, lengths, _ = _pool_case(variant, 3)
    spec = _KERNEL_VARIANTS[variant]
    by_heads = [x.reshape(*x.shape[:2], spec["h_kv"], spec["d"]) for x in (k, v)]
    want = paged_attention(q, *by_heads, table, lengths, impl="xla")
    got = paged_attention(q, k, v, table, lengths, impl="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    tiled = paged_attention(q, *by_heads, table, lengths, impl="paged_flash", interpret=True)
    streamed = paged_attention(q, k, v, table, lengths, impl="paged_flash", interpret=True)
    np.testing.assert_allclose(
        np.asarray(streamed, np.float32), np.asarray(tiled, np.float32),
        rtol=_TOL[variant], atol=_TOL[variant],
    )


def test_kernel_rejects_a_pool_of_another_head_size():
    q, k, v, table, lengths, _ = _pool_case("pair_fp32", 1)
    with pytest.raises(ValueError, match="head_dim mismatch"):
        paged_attention(
            q[..., :48], k, v, table, lengths, impl="paged_flash", interpret=True
        )


# --------------------------------------------------------------------------
# end-to-end: scheduler answers byte-identical paged_flash vs xla


def _kernel_stack_parity(tok, variant: str, speculate_k: int) -> None:
    """Greedy AND seeded-sampled answers byte-identical between
    --decode_kernel xla and paged_flash on the SAME paged layout, composed
    with chunked prefill, speculative decoding, and prefix reuse (wave 2
    replays wave 1's prompts as aliased device hits; divergent tails
    exercise the CoW write guard), at zero steady-state recompiles of the
    fused per-step program."""
    from transformer_tpu.serve import scheduler as sched

    cfg = _cfg(tok, **VARIANTS[variant])
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    common = dict(
        num_slots=2, max_total=48, default_max_new=4, prefill_chunk=3,
        speculate_k=speculate_k, kv_layout="paged",
    )
    waves = [list(WAVES[0]), list(WAVES[1])]

    s_ref = ContinuousScheduler(
        params, cfg, tok, decode_kernel="xla",
        prefix_cache=PrefixCache(cfg, block_tokens=4, budget_mb=8), **common,
    )
    want = [s_ref.run([dict(q) for q in w]) for w in waves]

    s = ContinuousScheduler(
        params, cfg, tok, decode_kernel="paged_flash",
        prefix_cache=PrefixCache(cfg, block_tokens=4, budget_mb=8), **common,
    )
    step_fn = (
        sched._pool_verify_paged_flash if speculate_k
        else sched._pool_step_paged_flash
    )
    got = [s.run([dict(q) for q in waves[0]])]
    before = step_fn._cache_size()
    got.append(s.run([dict(q) for q in waves[1]]))
    after = step_fn._cache_size()
    assert got == want, f"paged_flash answers diverged from xla ({variant})"
    assert any(r.get("continuation") for wave in got for r in wave), (
        "vacuous parity: every continuation empty"
    )
    assert after == before, "steady-state recompile on the fused step"
    # wave 2 replays wave 1's prompts: the fused path must still serve
    # them as pure device-tier table aliases.
    assert s.stats["prefix_hit_tokens"] > 0
    assert s.stats["prefix_alias_tokens"] == s.stats["prefix_hit_tokens"]
    s.pool.alloc.check_consistency()


def test_kernel_stack_parity_speculative(tok):
    """Tier-1 composition pin: bf16 + speculative verify (the fused
    verify program) + chunked prefill + prefix aliasing."""
    _kernel_stack_parity(tok, "bf16", speculate_k=1)


def test_kernel_stack_parity_plain(tok):
    """Tier-1 pin for the plain fused step (S_q = 1)."""
    _kernel_stack_parity(tok, "bf16", speculate_k=0)


@pytest.mark.slow
@pytest.mark.parametrize("variant", ["int8", "gqa"])
@pytest.mark.parametrize("speculate_k", [0, 1])
def test_kernel_stack_parity_variant_matrix(tok, variant, speculate_k):
    """The remaining answer-parity cross product: int8/GQA x plain and
    speculative (full suite; bf16 rides tier-1)."""
    _kernel_stack_parity(tok, variant, speculate_k=speculate_k)


def test_windowed_config_refuses_paged_flash(tok):
    """The kernel has no sliding-window band mask: attention_window
    configs must be refused at scheduler init, not silently mis-served."""
    cfg = _cfg(tok, attention_window=8)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="paged_flash|attention_window"):
        ContinuousScheduler(
            params, cfg, tok, num_slots=2, max_total=48,
            decode_kernel="paged_flash",
        )


def test_unknown_decode_kernel_rejected(tok):
    cfg = _cfg(tok)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="decode_kernel"):
        ContinuousScheduler(
            params, cfg, tok, num_slots=2, max_total=48,
            decode_kernel="mxu_magic",
        )


# --------------------------------------------------------------------------
# retrace budget: zero steady-state recompiles of the fused step


def test_paged_flash_retrace_budget(tok):
    """Steady-state paged_flash serving across every admission outcome —
    fresh allocs, frees at retirement, device-tier alias hits, and
    spill-to-host followed by batched restore — compiles ZERO new fused
    step/prefill programs after one warmup round (the same budget
    analysis/retrace.paged_retrace_report holds the gather path to);
    greedy answers are byte-identical round over round."""
    from transformer_tpu.analysis.retrace import RetraceSentinel
    from transformer_tpu.serve import scheduler as sched

    cfg = _cfg(tok)
    params = transformer_init(jax.random.PRNGKey(0), cfg)
    cache = PrefixCache(cfg, block_tokens=4, budget_mb=8)
    s = ContinuousScheduler(
        params, cfg, tok, num_slots=2, max_total=48, default_max_new=4,
        prefix_cache=cache, kv_layout="paged", decode_kernel="paged_flash",
    )
    wave = [
        {"prompt": "ab cd ef gh ij"},
        {"prompt": "ab cd ef kl"},
    ]

    def one_round():
        out = s.run([dict(r) for r in wave])       # miss / alias / partial
        # Spill rung: evict every device-tier block to the host trie, then
        # re-serve — hits restore through the batched host write and are
        # re-adopted, so the NEXT round aliases again.
        s.stats["kv_spilled_blocks"] += cache.release_device_blocks(1 << 30)
        out2 = s.run([dict(r) for r in wave])
        s.pool.alloc.check_consistency()
        return [r.get("continuation") for r in out + out2]

    want = one_round()
    assert any(want), "vacuous retrace drill: every continuation empty"
    sentinel = RetraceSentinel()
    sentinel.watch(
        "decode(_pool_step_paged_flash)", sched._pool_step_paged_flash,
        budget=0,
    )
    sentinel.watch(
        "prefill(_slot_prefill_paged)", sched._slot_prefill_paged, budget=0
    )
    sentinel.snapshot()
    for i in range(2):
        assert one_round() == want, f"round {i} changed greedy answers"
    sentinel.assert_within_budget()
