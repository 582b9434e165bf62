# layers.scatter_paged_rows against a plain NumPy loop (PR 25): the row
# scatter was respelled so that XLA:TPU updates the donated pool leaf in
# place (all three leading axes indexed; tests/test_chip_compile.py holds
# the compiled form).  It is the same write: row (s, w) lands at
# pool[dest[s, w], :, offsets[s, w]], an out-of-range dest drops, and
# every other element of the pool stays as it was, bit for bit, for the
# native leaves and for both planes of the int8 form.  The paged-against-
# dense parity matrix is tests/test_paged_kv.py.

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import layers as L

BLOCKS, HEADS, BLOCK, HEAD_DIM = 13, 4, 8, 16


def destinations(rng, slots, width):
    """[slots, width] destinations as `_paged_scatter` forms them:
    distinct (block, offset) cells in rising order, so that blocks
    REPEAT at distinct offsets (a slot's steps fill one block), and a
    share of rows sent to the out-of-range id `BLOCKS` (inactive slots,
    rejected drafts, positions past the table), which must drop."""
    cells = np.sort(rng.permutation(BLOCKS * BLOCK)[:slots * width])
    dest = (cells // BLOCK).astype(np.int32).reshape(slots, width)
    offsets = (cells % BLOCK).astype(np.int32).reshape(slots, width)
    assert slots * width <= BLOCKS or len(np.unique(dest)) < dest.size
    dropped = rng.random((slots, width)) < 0.3
    dropped[0, 0] = False       # something always lands
    return np.where(dropped, BLOCKS, dest).astype(np.int32), offsets, dropped


def by_hand(pool, dest, offsets, rows):
    """pool[dest[s, w], :, offsets[s, w]] = rows[s, :, w], in range only."""
    out = np.array(pool)
    for s in range(dest.shape[0]):
        for w in range(dest.shape[1]):
            if 0 <= dest[s, w] < out.shape[0]:
                out[dest[s, w], :, offsets[s, w]] = rows[s, :, w]
    return out


SHAPES = {"step-6x4": (6, 4), "spec-3x5": (3, 5), "extend-1x24": (1, 24),
          "one-row": (1, 1)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_native_rows_land_where_the_loop_puts_them(shape, dtype):
    slots, width = SHAPES[shape]
    rng = np.random.default_rng(slots * 100 + width)
    dest, offsets, dropped = destinations(rng, slots, width)
    pool = jnp.asarray(rng.standard_normal(
        (BLOCKS, HEADS, BLOCK, HEAD_DIM)), dtype)
    rows = jnp.asarray(rng.standard_normal(
        (slots, HEADS, width, HEAD_DIM)), dtype)
    got = jax.jit(L.scatter_paged_rows)(pool, jnp.asarray(dest),
                                        jnp.asarray(offsets), rows)
    assert got.dtype == dtype and got.shape == pool.shape
    np.testing.assert_array_equal(
        np.asarray(got),
        by_hand(np.asarray(pool), dest, offsets, np.asarray(rows)))
    # the dropped rows changed nothing: as many rows differ from the old
    # pool as landed
    changed = np.any(np.asarray(got) != np.asarray(pool), axis=(1, 3))
    assert changed.sum() == (~dropped).sum()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_int8_planes_land_where_the_loop_puts_them(shape):
    slots, width = SHAPES[shape]
    rng = np.random.default_rng(slots * 1000 + width)
    dest, offsets, _ = destinations(rng, slots, width)
    pool = {"q": rng.integers(-127, 128, (BLOCKS, HEADS, BLOCK, HEAD_DIM),
                              dtype=np.int8),
            "s": rng.random((BLOCKS, HEADS, BLOCK)).astype(np.float32)}
    side = jnp.asarray(rng.standard_normal(
        (slots, HEADS, width, HEAD_DIM)), jnp.bfloat16)
    rows = L.quantize_kv_cache(side)        # once, before the write
    got = jax.jit(L.scatter_paged_rows)(
        jax.tree.map(jnp.asarray, pool), jnp.asarray(dest),
        jnp.asarray(offsets), rows)
    assert set(got) == {"q", "s"}
    assert got["q"].dtype == jnp.int8 and got["s"].dtype == jnp.float32
    for plane in ("q", "s"):
        np.testing.assert_array_equal(
            np.asarray(got[plane]),
            by_hand(pool[plane], dest, offsets, np.asarray(rows[plane])))


def test_every_row_out_of_range_leaves_the_pool_as_it_was():
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.standard_normal(
        (BLOCKS, HEADS, BLOCK, HEAD_DIM)), jnp.bfloat16)
    rows = jnp.ones((2, HEADS, 3, HEAD_DIM), jnp.bfloat16)
    dest = jnp.full((2, 3), BLOCKS, jnp.int32)
    got = L.scatter_paged_rows(pool, dest, jnp.zeros((2, 3), jnp.int32), rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(pool))
