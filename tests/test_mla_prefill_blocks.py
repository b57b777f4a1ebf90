"""The latent layer's unabsorbed form walks its keys (``ops/mla.py::_attend_rows``):
each query block against the key blocks up to its last query's position, an
online softmax carried across them. Held here, at blocks of 16, to the form
that scores every query against the whole buffer, kept below as the plain
reference: for both kinds of latent layer, at a start of 0 and at a prefix
hit's, for chunks of one and of several query blocks, and on shapes that are
no whole number of blocks (padded to whole blocks)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transformer_tpu.ops import mla as mla_ops
from transformer_tpu.ops.mla import init_latent_cache, latent_keys_read, mla_apply, mla_init
from transformer_tpu.ops.positional import kind_rope

EPS = 1e-6
BLOCK = 16
RANK, NOPE, SHARED, VALUE, HEADS, D_MODEL = 128, 16, 8, 16, 4, 32

# (first, chunk, rows): whole blocks, then rows or a chunk padded to them.
WHOLE = [(0, 16, 48), (0, 48, 64), (16, 32, 64), (5, 32, 48), (3, 8, 32)]
PADDED = [(0, 16, 40), (4, 24, 64), (21, 19, 40)]
SHAPES = WHOLE + PADDED


@pytest.fixture
def blocks(monkeypatch):
    monkeypatch.setattr(mla_ops, "_QUERY_BLOCK", BLOCK)
    monkeypatch.setattr(mla_ops, "_KEY_BLOCK", BLOCK)


def _layer(kind):
    """``rotated``: A.X-K1's kind (compressed query, YaRN-turned shared key,
    a score factor); ``nope``: Kimi Linear's (one query projection, nothing
    turned, factor 1). Returns the parameters and ``latent_position_args``."""
    if kind == "rotated":
        p = mla_init(jax.random.PRNGKey(1), D_MODEL, HEADS, RANK, NOPE, SHARED, VALUE, jnp.float32,
                     query_scale=3.0, query_rank=32)
        p["q_norm"]["scale"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(9), (32,))
        from transformer_tpu.config import AttentionKind

        k = AttentionKind("mla", num_heads=HEADS, latent_rank=RANK, latent_nope_dim=NOPE, latent_shared_dim=SHARED,
                          latent_value_dim=VALUE, latent_query_rank=32, latent_rotary=True, yarn_factor=32.0,
                          yarn_original_max_position=64, yarn_mscale_all_dim=1.0)
        return p, {"rope": kind_rope(k), "score_factor": k.latent_score_factor}
    p = mla_init(jax.random.PRNGKey(2), D_MODEL, HEADS, RANK, NOPE, SHARED, VALUE, jnp.float32, query_scale=3.0)
    return p, {"rope": None, "score_factor": 1.0}


def _inputs(first, s, length, seed=0):
    kh, kr = jax.random.split(jax.random.PRNGKey(seed))
    h = jax.random.normal(kh, (2, s, D_MODEL))
    width = mla_ops.latent_width(RANK, SHARED)
    rows = jax.random.normal(kr, (2, length, width)).at[..., RANK + SHARED :].set(0.0)
    return h, rows


def _whole(p, h, rows, first, rope, score_factor):
    """The plain reference: every query against every row, causally masked,
    one softmax over the whole buffer."""
    s, length = h.shape[1], rows.shape[1]
    pos = first + jnp.arange(s)
    q = mla_ops._queries(p, h, EPS, jnp.broadcast_to(pos, h.shape[:2]) if rope else None, rope)
    kv = jnp.einsum("blr,rhd->blhd", rows[..., :RANK], p["kv_b"]["kernel"], precision="highest")
    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q[..., :NOPE], kv[..., :NOPE], precision="highest")
        + jnp.einsum("bqhd,bkd->bhqk", q[..., NOPE:], rows[..., RANK : RANK + SHARED], precision="highest")
    ) * ((NOPE + SHARED) ** -0.5 * score_factor)
    seen = jnp.arange(length)[None, :] <= pos[:, None]
    w = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, kv[..., NOPE:], precision="highest")


def _attend(p, h, rows, first, at, traced=True):
    """``_attend_rows`` with ``first`` traced, as a cache's index is, or as a
    Python int, as the forward without a cache has it."""
    if traced:
        return jax.jit(lambda f: mla_ops._attend_rows(p, h, rows, f, EPS, **at))(jnp.int32(first))
    return mla_ops._attend_rows(p, h, rows, first, EPS, **at)


@pytest.mark.parametrize("kind", ["rotated", "nope"])
@pytest.mark.parametrize("traced", [True, False], ids=["traced_start", "static_start"])
@pytest.mark.parametrize("first,s,length", SHAPES)
def test_the_walk_equals_the_whole_buffer_form(blocks, kind, traced, first, s, length):
    p, at = _layer(kind)
    h, rows = _inputs(first, s, length)
    got = _attend(p, h, rows, first, at, traced)
    want = _whole(p, h, rows, first, **at)
    assert got.shape == (2, s, HEADS, VALUE)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("kind", ["rotated", "nope"])
@pytest.mark.parametrize("first,s,length", SHAPES)
def test_rows_past_the_chunk_are_never_read(blocks, kind, first, s, length):
    """NaN in every row past ``first + S - 1`` (a slot buffer's stale or
    unwritten positions): the walk's output stays finite and as before."""
    p, at = _layer(kind)
    h, rows = _inputs(first, s, length, seed=1)
    poisoned = rows.at[:, first + s :].set(jnp.nan)
    got = _attend(p, h, poisoned, first, at)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, _whole(p, h, rows, first, **at), atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("first,s,length", SHAPES)
def test_the_keys_read_are_the_walks_own_trip_counts(blocks, monkeypatch, first, s, length):
    """``latent_keys_read`` against the key blocks the traced program walks,
    counted as it runs."""
    share = latent_keys_read(first, s, length)
    reaches = []
    reach = mla_ops._reach

    def counted(first, end, key_block):
        r = reach(first, end, key_block)
        jax.debug.callback(lambda v: reaches.append(int(v)), r)
        return r

    monkeypatch.setattr(mla_ops, "_reach", counted)
    p, at = _layer("nope")
    h, rows = _inputs(first, s, length)
    jax.block_until_ready(_attend(p, h, rows, first, at))
    jax.effects_barrier()
    assert len(reaches) == -(-s // min(s, BLOCK))
    assert share == pytest.approx(sum(reaches) / (len(reaches) * -(-length // BLOCK)))
    assert max(reaches) * BLOCK >= first + s and (max(reaches) - 1) * BLOCK < first + s


def test_the_share_at_the_served_sizes():
    """Blocks of 512: the cells' prefills from position 0 against their slot
    buffers (6,144 positions for A.X-K1, 8,192 for Kimi Linear)."""
    assert latent_keys_read(0, 2048, 6144) == pytest.approx(10 / 48)
    assert latent_keys_read(0, 1024, 6144) == pytest.approx(3 / 24)
    assert latent_keys_read(0, 512, 6144) == pytest.approx(1 / 12)
    assert latent_keys_read(0, 4096, 6144) == pytest.approx(36 / 96)
    assert latent_keys_read(0, 128, 8192) == pytest.approx(1 / 16)
    assert latent_keys_read(512, 1024, 6144) == pytest.approx((2 + 3) * 512 / (2 * 6144))
    assert latent_keys_read(0, 700, 6144) == pytest.approx((1 + 2) / 24)  # 512 queries, then 188 padded
    assert latent_keys_read(0, 16, 64) == 1.0  # the rehearsal's buffer: one key block


def _whole_rows(p, h, rows, first, epsilon, rope=None, score_factor=1.0):
    """``_whole`` in ``_attend_rows``'s place."""
    return _whole(p, h, rows, first, rope, score_factor)


@pytest.mark.parametrize("kind", ["rotated", "nope"])
def test_the_forward_without_a_cache_walks_and_differentiates(blocks, monkeypatch, kind):
    """At 48 positions the forward walks (static trip counts: a scan); its
    gradients equal the plain reference's."""
    p, at = _layer(kind)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 48, D_MODEL))

    def loss(p, h):
        out, _ = mla_apply(p, h, None, EPS, **at)
        return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape))).sum()

    walked = jax.grad(loss, argnums=(0, 1))(p, h)
    monkeypatch.setattr(mla_ops, "_attend_rows", _whole_rows)
    whole = jax.grad(loss, argnums=(0, 1))(p, h)
    for got, want in zip(jax.tree.leaves(walked), jax.tree.leaves(whole)):
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("kind", ["rotated", "nope"])
def test_a_cached_chunk_at_an_offset_walks_to_the_same_output(blocks, monkeypatch, kind):
    """Through ``mla_apply``'s cache: 16 positions, then a chunk of 32 at
    index 16 against the 64-row buffer, then one position absorbed; the same
    outputs and rows as with the plain reference."""
    p, at = _layer(kind)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 49, D_MODEL))

    def serve():
        cache = init_latent_cache(2, 64, RANK, SHARED, jnp.float32)
        outs = []
        for a, b in ((0, 16), (16, 48), (48, 49)):
            out, cache = jax.jit(lambda x, c: mla_apply(p, x, c, EPS, **at))(h[:, a:b], cache)
            outs.append(out)
        return jnp.concatenate(outs, axis=1), cache["ckv"]

    walked, rows = serve()
    monkeypatch.setattr(mla_ops, "_attend_rows", _whole_rows)
    whole, rows_whole = serve()
    np.testing.assert_allclose(walked, whole, atol=2e-6, rtol=1e-5)
    np.testing.assert_array_equal(rows, rows_whole)
