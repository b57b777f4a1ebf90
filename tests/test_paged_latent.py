"""The paged latent decode kernel (``kernels/paged_latent.py``) in interpret
mode against a plain float32 softmax over the rows gathered through the table.

The kernel walks slot after slot, block after block, as a pipeline: the step
that finishes a block scores the next one (the next slot's first, with the
next slot's queries, at a slot's end) and starts the copies of the one after.
So each case runs a slot of ``length`` beside slots of three and four blocks
and a slot of one position after a long one: the walk fills, crosses slot
ends in every arrangement (a one-block slot first, between and last) and
drains. Entries past a slot's length are never dereferenced: hostile ids there
change nothing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transformer_tpu.kernels.paged_latent import paged_latent_attention

# (heads, lanes, rank, table pages): the two latent cells' widths, rows of
# 512 + 64 channels kept in 640 lanes, over a table of 104 pages of 16 (a
# padded fourth block), and the small shape of a table narrower than a block.
AX_K1, KIMI, SMALL = (64, 640, 512, 104), (32, 640, 512, 104), (4, 256, 128, 6)
BLOCK = 16
# float32 throughout: the small shape's tolerance as it stood; at 640 lanes
# and 1,600 positions the reference's own sums round to about 4e-6.
ATOL = {SMALL: 2e-6, AX_K1: 1e-5, KIMI: 1e-5}


def _case(shape, lengths):
    heads, width, rank, nmax = shape
    n = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    blocks = n * nmax + 1
    pool = jax.random.normal(ks[0], (blocks, BLOCK, width))
    q = jax.random.normal(ks[1], (n, heads, width)) * 0.2
    table = jax.random.permutation(ks[2], jnp.arange(1, blocks)).reshape(n, nmax)
    return q, pool, table.astype(jnp.int32), jnp.asarray(lengths, jnp.int32), rank


def _reference(q, pool, table, lengths, rank):
    n, nmax = table.shape
    rows = pool[table].reshape(n, nmax * BLOCK, pool.shape[-1])
    scores = jnp.einsum("nhw,nlw->nhl", q, rows, precision="highest")
    scores = jnp.where(jnp.arange(nmax * BLOCK)[None, None] < lengths[:, None, None], scores, -1e9)
    return jnp.einsum("nhl,nlr->nhr", jax.nn.softmax(scores, -1), rows[..., :rank], precision="highest")


_WIDE = [1, 15, 16, 17, 511, 512, 513, 1024, 1025, 1600]


@pytest.mark.parametrize(
    "shape, lengths",
    [(AX_K1, [length, 1600, 1, 1025, length]) for length in _WIDE]
    + [(KIMI, [length, 1600, 1, 1025, length]) for length in _WIDE]
    + [(SMALL, [1, 37, 96]), (SMALL, [16, 17, 33])],
    ids=[f"ax-k1-{length}" for length in _WIDE] + [f"kimi-{length}" for length in _WIDE]
    + ["small-1-37-96", "small-16-17-33"],
)
def test_paged_latent_attention_reads_each_row_once_through_the_table(shape, lengths):
    q, pool, table, lengths, rank = _case(shape, lengths)
    got = paged_latent_attention(q, pool, table, lengths, rank=rank)
    np.testing.assert_allclose(got, _reference(q, pool, table, lengths, rank), atol=ATOL[shape])
    hostile = jnp.where(jnp.arange(table.shape[1])[None, :] * BLOCK >= lengths[:, None], 10**6, table)
    np.testing.assert_array_equal(paged_latent_attention(q, pool, hostile, lengths, rank=rank), got)
