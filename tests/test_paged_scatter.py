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


# -- layers.write_paged_runs (PR 32): the same write by whole blocks ----------
# A slot's RUN of consecutive rows, positions [start, start + W) of its
# table row, goes to the pool as the one or two (or seventeen) blocks it
# falls in: read, the rows laid in, written back.  Held to the plain loop
# AND to scatter_paged_rows over destinations formed as _paged_scatter
# forms them: every cell of the pool bit-equal, the cells a live row owns
# and the cells nothing was to touch alike.

def run_by_hand(pool, tables, starts, rows, live):
    """pool[tables[s, p // B], :, p % B] = rows[s, :, p - starts[s]] for
    each position p of a live slot's run that the table covers."""
    out = np.array(pool)
    block = out.shape[2]
    for s in range(rows.shape[0]):
        for w in range(rows.shape[2]):
            position = starts[s] + w
            if live[s] and position // block < tables.shape[1]:
                out[tables[s, position // block], :, position % block] = \
                    rows[s, :, w]
    return out


def run_destinations(tables, starts, width, live, block, num_total):
    """[S, W] destinations and offsets of the runs, as
    serving_paged._paged_scatter forms them from positions."""
    positions = starts[:, None] + np.arange(width)[None]
    blocks = positions // block
    dest = np.take_along_axis(
        tables, np.clip(blocks, 0, tables.shape[1] - 1), axis=1)
    dest = np.where(live[:, None] & (blocks < tables.shape[1]), dest,
                    num_total)
    return dest.astype(np.int32), (positions % block).astype(np.int32)


# name: (leaf [N, H, B, D], table blocks a slot, run width, starts, live,
#        int8); a slot's table is its own blocks, no two slots share one
RUNS = {
    # four rows inside one block: ONE image of it is written, the second
    # block of the pair drops (the id that would repeat)
    "inside-one-block": ((41, 4, 8, 16), 5, 4, [9, 17, 2], [1, 1, 1], False),
    "across-a-block-edge": ((41, 4, 8, 16), 5, 4, [6, 13, 30], [1, 1, 1],
                            False),
    # the run ends past the table's 5 x 8 positions: the tail drops
    "ends-past-the-table": ((41, 4, 8, 16), 5, 4, [38, 37, 39], [1, 1, 1],
                            False),
    "a-slot-that-is-not-live": ((41, 4, 8, 16), 5, 4, [9, 6, 17], [1, 0, 1],
                                False),
    # a chunk of 512 in blocks of 32: 17 blocks from an unaligned start
    # (the final chunk slid back to the prompt's tail), 16 from an aligned
    "chunk-512-unaligned": ((70, 2, 32, 8), 64, 512, [1000 - 512], [1],
                            False),
    "chunk-512-aligned": ((70, 2, 32, 8), 64, 512, [1024], [1], False),
    "int8-step": ((41, 4, 8, 16), 5, 4, [9, 6, 38], [1, 1, 0], True),
    "int8-chunk": ((41, 4, 8, 16), 12, 24, [21, 40], [1, 1], True),
    # ax-k1's leaf: one head, a row of 640 lanes (a step there keeps the
    # row form by the static choice; the writer serves it all the same)
    "latent-leaf-step": ((9, 1, 32, 640), 4, 4, [30, 70], [1, 1], False),
    "latent-leaf-chunk": ((9, 1, 32, 640), 4, 64, [33, 0], [1, 0], False),
    # an extend of width 2 with one pad row: table row of nulls, not valid
    "extend-with-a-pad-row": ((41, 4, 8, 16), 12, 24, [21, 0], [1, 0],
                              False),
    # the two ways rows are laid into the images: a select a row up to
    # layers._SELECT_ROWS, a slice update a slot beyond
    "rows-32-by-selects": ((41, 4, 8, 16), 12, 32, [3, 16, 61], [1, 1, 1],
                           False),
    "rows-33-by-slices": ((41, 4, 8, 16), 12, 33, [3, 16, 61], [1, 1, 1],
                          False),
}


@pytest.mark.parametrize("case", sorted(RUNS))
def test_runs_land_by_whole_blocks_where_the_rows_would(case):
    leaf, table_blocks, width, starts, live, int8 = RUNS[case]
    num_total, heads, block, lanes = leaf
    slots = len(starts)
    rng = np.random.default_rng(len(case) * 131 + width)
    starts = np.asarray(starts, np.int32)
    live = np.asarray(live, bool)
    # each slot owns its own blocks (1.., block 0 is the null block); a
    # pad row's table is all null, as _extend_group leaves it
    ids = 1 + rng.permutation(num_total - 1)[:slots * table_blocks]
    tables = ids.reshape(slots, table_blocks).astype(np.int32)
    if case == "extend-with-a-pad-row":
        tables[1] = 0
    side = jnp.asarray(rng.standard_normal((slots, heads, width, lanes)),
                       jnp.bfloat16)
    if int8:
        pool = {"q": rng.integers(-127, 128, leaf, dtype=np.int8),
                "s": rng.random(leaf[:3]).astype(np.float32)}
        rows = L.quantize_kv_cache(side)    # once, before the write
    else:
        pool = np.asarray(jnp.asarray(rng.standard_normal(leaf),
                                      jnp.bfloat16))
        rows = side
    device_pool = jax.tree.map(jnp.asarray, pool)
    got = jax.jit(L.write_paged_runs)(
        device_pool, jnp.asarray(tables), jnp.asarray(starts), rows,
        jnp.asarray(live))
    dest, offsets = run_destinations(tables, starts, width, live, block,
                                     num_total)
    by_rows = jax.jit(L.scatter_paged_rows)(
        device_pool, jnp.asarray(dest), jnp.asarray(offsets), rows)
    planes = ("q", "s") if int8 else (None,)
    for plane in planes:
        pick = (lambda tree: np.asarray(tree[plane])) if plane else np.asarray
        assert pick(got).dtype == pick(pool).dtype
        np.testing.assert_array_equal(
            pick(got), run_by_hand(pick(pool), tables, starts, pick(rows),
                                   live))
        np.testing.assert_array_equal(pick(got), pick(by_rows))
    # something landed, and a slot that is not live changed nothing of
    # its blocks
    values = np.asarray(got["q"] if int8 else got)
    before = pool["q"] if int8 else pool
    assert np.any(values != before)
    for s in np.flatnonzero(~live):
        owned = tables[s][tables[s] > 0]
        np.testing.assert_array_equal(values[owned], before[owned])


def test_the_block_form_is_taken_where_it_has_fewer_windows():
    """The static choice (serving_paged._paged_write_runs): a block
    form's windows count twice (it reads its blocks before it writes
    them) against one a row and head."""
    # mistral's step (8 heads x 4 rows) and chunk, ax-k1's chunk
    assert L.writes_runs_by_blocks(8, 4, 32)
    assert L.writes_runs_by_blocks(8, 512, 32)
    assert L.writes_runs_by_blocks(1, 512, 32)
    # ax-k1's step: four rows of one head against two blocks twice
    assert not L.writes_runs_by_blocks(1, 4, 32)
    assert not L.writes_runs_by_blocks(1, 1, 32)
    assert [L.run_blocks(width, 32) for width in (1, 4, 32, 33, 512)] == \
        [2, 2, 2, 3, 17]
