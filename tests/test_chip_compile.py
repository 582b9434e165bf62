# Chip-compile rehearsal kept as tests (guide on-chip-measurement §2 step 3):
# every pallas kernel of the main path, compiled with interpret=False for a
# DESCRIBED v5e — the TPU compiler is installed here though no chip is — at
# the shapes the serving stack gives it.  Interpret mode cannot see what
# these see: PR 16's paged kernel passed every interpret-mode test and was
# refused by mosaic at every serving geometry (a dynamic lane-dimension
# slice at kv_block=32).  Nothing runs, so these say nothing about results
# or times.  Plus the compile-cache helper's placement contract.  The
# cells' WHOLE programs are compiled in test_0_chip_*.py, a file a
# configuration (README.md, "Test-suite wall-time budget").

import importlib
import math
import os

import jax
import jax.numpy as jnp
import pytest

from paged_model_cases import (DescribedCell, block_windows, no_copy_of,
                               shaped)


# Llama-3.2-1B attention geometry, the serving stack's pool block
HKV, GROUPS, HEAD_DIM, BLOCK = 8, 4, 64, 32



def _flash(causal):
    from aiko_services_tpu.ops.attention import flash_attention
    # the 3000-frame whisper bucket: n_audio_ctx 1536, 12 heads
    shape = ((4, 12, 1536, 64), jnp.bfloat16)
    return (lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                            interpret=False),
            [shape, shape, shape])


def _cross_decode():
    from aiko_services_tpu.ops.attention import cross_decode_attention
    kv = ((32, 12, 250, 64), jnp.bfloat16)
    return (lambda q, k, v: cross_decode_attention(q, k, v,
                                                   interpret=False),
            [((32, 12, 1, 64), jnp.bfloat16), kv, kv])


def _paged(int8, fold, width, side, slots=8, t_cap=1280,
           head_dim=HEAD_DIM):
    """side = steps_per_sync * width for a decode/spec round, or the
    chunk length for a chunked-prefill extend (width = chunk)."""
    from aiko_services_tpu.ops.paged_attention import \
        paged_decode_attention
    nb = t_cap // BLOCK
    pool_shape = (slots * nb + 1, HKV, BLOCK, head_dim)
    pool = {"q": (pool_shape, jnp.int8),
            "s": (pool_shape[:3], jnp.float32)} if int8 \
        else (pool_shape, jnp.bfloat16)
    side_kv = ((slots, HKV, side, head_dim), jnp.bfloat16)
    return (lambda q, k_pool, v_pool, tables, k_side, v_side, valid,
            entry: paged_decode_attention(
                q, k_pool, v_pool, tables, k_side, v_side, valid, entry,
                groups=GROUPS, fold_scales=fold, interpret=False),
            [((slots, HKV, GROUPS * width, head_dim), jnp.bfloat16),
             pool, pool, ((slots, nb), jnp.int32), side_kv, side_kv,
             ((slots, width, side), jnp.bool_), ((slots,), jnp.int32)])


def _delta_chunk(tokens, heads, dk, dv, by_head, rows=1):
    """ops/delta_chunk.py over a prompt's piece: the state as the pool
    keeps it, heads side by side where the gate is a head's."""
    from aiko_services_tpu.ops.delta_chunk import delta_chunk_scan
    f32 = jnp.float32
    vector = lambda *tail: ((rows, tokens, heads) + tail, f32)  # noqa: E731
    return (lambda *args: delta_chunk_scan(*args, interpret=False)[0],
            [vector(dk), vector(dk), vector(dv),
             vector() if by_head else vector(dk), vector(),
             ((rows, dk, heads * dv) if by_head else (rows, heads, dk, dv),
              f32)])


def _ssm_chunk(tokens, rows=1, heads=64, width=64, state=128):
    """ops/ssm_chunk.py over a prompt's piece (ISSUE 46: Mamba-2's chunked
    form): ONE B and ONE C a row, the state [N, H x P] as the pool keeps
    it."""
    from aiko_services_tpu.ops.ssm_chunk import ssm_chunk_scan
    f32 = jnp.float32
    return (lambda *args: ssm_chunk_scan(*args, interpret=False)[0],
            [((rows, tokens, heads, width), f32), ((rows, tokens, heads), f32),
             ((rows, tokens, state), f32), ((rows, tokens, state), f32),
             ((heads,), f32), ((rows, state, heads * width), f32)])


def _ssm_state(slots, heads=64, width=64, state=128):
    """ops/kda_step.py's plain decayed rule (ISSUE 45: Mamba-2): ONE k and
    ONE q a slot, the state [N, H x P] with the heads side by side."""
    from aiko_services_tpu.ops.kda_step import kda_live_step
    f32 = jnp.float32
    return (lambda q, k, v, g, memory, active: kda_live_step(
        q, k, v, g, None, memory, active, interpret=False)[0],
            [((slots, state), f32), ((slots, state), f32),
             ((slots, heads, width), f32), ((slots, heads), f32),
             ((slots, state, heads * width), f32), ((slots,), jnp.bool_)])


def _paged_rows(slots, width=1, side=4, t_cap=2048, head_dim=64):
    """The walk over ONE leaf whose row is a head's V then its K (ISSUE 45:
    K/V heads of 64, a row of 128 lanes): the query zero over V's lanes,
    the result the weighted rows' leading lanes, as a latent pool's."""
    from aiko_services_tpu.ops.paged_attention import \
        paged_decode_attention
    nb = t_cap // BLOCK
    bf16 = jnp.bfloat16
    return (lambda q, pool, tables, rows, valid, entry:
            paged_decode_attention(
                q, pool, None, tables, rows, rows[..., :head_dim], valid,
                entry, groups=GROUPS, scale=1 / 64, interpret=False),
            [((slots, HKV, GROUPS * width, 2 * head_dim), bf16),
             ((slots * nb + 1, HKV, BLOCK, 2 * head_dim), bf16),
             ((slots, nb + 1), jnp.int32),
             ((slots, HKV, side, 2 * head_dim), bf16),
             ((slots, width, side), jnp.bool_), ((slots,), jnp.int32)])


MISTRAL = {"slots": 24, "t_cap": 2048, "head_dim": 128}

CASES = {
    "flash-s1536": lambda: _flash(False),
    "flash-s1536-causal": lambda: _flash(True),
    "cross-decode-t250": _cross_decode,
    # decode scan (W=1) and speculative verify (W=1+k, k=4)
    "paged-bf16-fold-w1": lambda: _paged(False, True, 1, 4),
    "paged-bf16-nofold-w1": lambda: _paged(False, False, 1, 4),
    "paged-bf16-fold-w5": lambda: _paged(False, True, 5, 20),
    "paged-bf16-nofold-w5": lambda: _paged(False, False, 5, 20),
    "paged-int8-fold-w1": lambda: _paged(True, True, 1, 4),
    "paged-int8-nofold-w1": lambda: _paged(True, False, 1, 4),
    "paged-int8-fold-w5": lambda: _paged(True, True, 5, 20),
    "paged-int8-nofold-w5": lambda: _paged(True, False, 5, 20),
    # a wide serving shape: 256 slots, 64 steps per sync, int8 KV
    "paged-int8-bench-s256": lambda: _paged(True, True, 1, 64,
                                            slots=256, t_cap=1024),
    # chunked-prefill extend: the chunk rides as the side buffer and
    # the G*chunk query rows tile to fit VMEM (_row_tile)
    "paged-bf16-extend-c256": lambda: _paged(False, False, 256, 256,
                                             slots=4),
    "paged-int8-extend-c64": lambda: _paged(True, False, 64, 64,
                                            slots=4, t_cap=1024),
    # mistral-7b-v0.3-d16 as the benchmark's cells serve it: head 128,
    # 24 slots, 64 table entries.  A native pool whose minor axis is
    # whole lanes takes the body that walks live blocks by hand (the
    # cases above, head 64, and every int8 pool keep the table body:
    # mosaic slices no block out of an HBM operand narrower than 128)
    "paged-bf16-d128-step-w1": lambda: _paged(False, True, 1, 4, **MISTRAL),
    "paged-bf16-d128-spec-w5": lambda: _paged(False, True, 5, 20,
                                              **MISTRAL),
    "paged-bf16-d128-extend-c512": lambda: _paged(
        False, False, 512, 512, **(MISTRAL | {"slots": 1})),
    "paged-int8-d128-step-w1": lambda: _paged(True, True, 1, 4, **MISTRAL),
    "paged-int8-d128-extend-c512": lambda: _paged(
        True, False, 512, 512, **(MISTRAL | {"slots": 1})),
    # the chunked delta rule as one kernel a layer (ISSUE 41), at the two
    # cells' heads and pieces: olmo-hybrid's admit (a bucket of 512, 30
    # heads of [96, 192], a gate a head: pairs of heads are 384 lanes of
    # the state, a head's own 192 are cut out of them at no vector's edge)
    # and a piece that is no whole chunk; glm's extend (512 tokens of 64
    # heads of [128, 128], a gate a channel)
    "delta-chunk-gdn-t512": lambda: _delta_chunk(512, 30, 96, 192, True),
    "delta-chunk-gdn-t200-two-rows": lambda: _delta_chunk(
        200, 30, 96, 192, True, rows=2),
    "delta-chunk-kda-t512": lambda: _delta_chunk(512, 64, 128, 128, False),
    # sides that are no whole lanes, a gate of each grain: what
    # `scans_chunks` lets through off the published widths
    "delta-chunk-gdn-small-heads": lambda: _delta_chunk(128, 8, 8, 16, True),
    "delta-chunk-kda-half-lanes": lambda: _delta_chunk(128, 32, 64, 64,
                                                       False),
    # granite-4.0-h-micro as ssm_chat_open_loop serves it (ISSUE 45): the
    # plain decayed rule over 64 heads of [128, 64] that share k and q, 32
    # slots and a count that is no multiple of 8; and the walk over the
    # attention layers' one leaf, a row a head's V and K side by side
    "ssm-state-s32": lambda: _ssm_state(32),
    "ssm-state-s5": lambda: _ssm_state(5),
    "paged-rows-v64-k64-step-w1": lambda: _paged_rows(32),
    # the same cell's pieces (ISSUE 46): the chunked recurrence as one
    # kernel a layer at the extend's 512 tokens and at every admit the
    # cell warms up (buckets of 128, 256 and 512 under a budget of 512
    # tokens a round: four, two and one row); a piece that is no whole
    # chunk; and what `scans_ssm_chunks` lets through off the published
    # widths: sixteen heads of 8 inside one vector, a head of two vectors
    "ssm-chunk-t512": lambda: _ssm_chunk(512),
    "ssm-chunk-t128": lambda: _ssm_chunk(128),
    "ssm-chunk-t128-two-rows": lambda: _ssm_chunk(128, rows=2),
    "ssm-chunk-t128-four-rows": lambda: _ssm_chunk(128, rows=4),
    "ssm-chunk-t256": lambda: _ssm_chunk(256),
    "ssm-chunk-t256-two-rows": lambda: _ssm_chunk(256, rows=2),
    "ssm-chunk-t200-two-rows": lambda: _ssm_chunk(200, rows=2),
    "ssm-chunk-small-heads": lambda: _ssm_chunk(128, heads=16, width=8,
                                                state=16),
    "ssm-chunk-wide-heads": lambda: _ssm_chunk(128, heads=4, width=256,
                                               state=64),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    fn, shapes = CASES[case]()
    args = jax.tree.map(lambda leaf: shaped(chip, *leaf), shapes,
                        is_leaf=lambda leaf: isinstance(leaf, tuple))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the recurrent layers' convolution (ISSUE 47) -----------------------------------

# a configuration's file -> (its model's module, its benchmark driver, the
# block of one recurrent layer, the layer's key, the scope its convolution
# runs under): granite's Mamba layer, olmo-hybrid's Gated-DeltaNet layer,
# glm's KDA layer
CONV_LAYERS = {
    "granite-4.0-h-micro": ("ssm_hybrid", "ssm_hybrid_decoder",
                            "_mamba_block", "mamba", "aiko.ssm_conv"),
    "olmo-hybrid-7b-d16": ("gated_delta", "gated_delta_decoder",
                           "_gdn_block", "gdn", "aiko.gdn_conv"),
    "glm-5.3-flash-ep8-d5": ("hybrid_sparse", "hybrid_sparse_decoder",
                             "_kda_block", "kda", "aiko.kda_core"),
}


@pytest.mark.parametrize("block", ["step", "piece"])
@pytest.mark.parametrize("name", sorted(CONV_LAYERS))
def test_recurrent_convolution_rolls_its_tail_without_a_gather(chip, name,
                                                               block):
    """ONE recurrent layer of each of the three models at its cell's slots
    and widths, as the chip traces it: the decode step (a token a slot, the
    state through ops/kda_step's kernel) and a prompt's piece of one row
    (`jit_extend`, most of `jit_admit`).  layers.conv_tail rolls a slot's
    tail by one select in the step and one slice in the piece: NO operation
    under the convolution's scope comes from a `gather` (the `vmap` of
    `dynamic_slice_in_dim` lowered to a `kCustom` fusion a layer, ISSUE
    47), the step never lays tail and token end to end as [slots, conv, C],
    and the tail stays [slots, (conv-1) x C], its positions side by side
    on the lanes: nothing makes it three rows of a tile again.  Nothing
    runs and nothing is timed."""
    module, driver, layer_block, key, scope = CONV_LAYERS[name]
    M = importlib.import_module("aiko_services_tpu.models." + module)
    cell = DescribedCell(chip, name + ".json", getattr(M, module + "_init"),
                         importlib.import_module(driver).model_config)
    config = cell.config
    at = next(i for i, layer in enumerate(cell.params["layers"])
              if key in layer)
    rows, tokens = (cell.slots, 1) if block == "step" \
        else (1, cell.serve["prefill_chunk"])
    state = tuple(cell.shaped((rows,) + leaf.shape[1:], leaf.dtype)
                  for leaf in cell.state[0][at])
    taps = config.conv_width
    channels = state[1].shape[1] // (taps - 1)
    assert state[1].shape == (rows, (taps - 1) * channels)
    assert channels % 128 == 0

    def one_layer(layer, x, state, live):
        return getattr(M, layer_block)(layer, config, x, state, live,
                                       live_only=block == "step")

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        text = jax.jit(one_layer).lower(
            cell.params["layers"][at],
            cell.shaped((rows, tokens, config.dim), jnp.bfloat16), state,
            cell.shaped((rows, tokens), bool)).compile().as_text()
    under = [line.strip() for line in text.splitlines() if scope in line]
    assert under and "tpu_custom_call" in text
    assert not [line[:200] for line in under if "gather" in line]
    if block == "step":
        for shape in ((rows, taps, channels), (rows, taps - 1, channels)):
            assert "[%d,%d,%d]" % shape not in text


# mistral-7b-v0.3-d16's pool leaf in the benchmark's cells: 24 slots x 64
# blocks + the null block, 8 KV heads, 32 tokens a block, head 128
POOL = (1537, 8, 32, 128)
POOL_LEAVES = {"bf16": (POOL, jnp.bfloat16), "s8": (POOL, jnp.int8),
               "scale-f32": (POOL[:3], jnp.float32)}


def _compiled_donating(fn, sharding, *leaves):
    """`fn` compiled for `sharding` with its first argument donated;
    every leaf is (shape, dtype)."""
    return jax.jit(fn, donate_argnums=(0,)).lower(*(
        shaped(sharding, *leaf) for leaf in leaves)).compile()


def _assert_in_place(compiled, shape, dtype):
    """No `copy` in the optimized HLO has a result of the pool leaf's
    shape (in whatever layout), and the temporaries are under a leaf."""
    no_copy_of(compiled, shape,
               temporaries=math.prod(shape) * jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("leaf", sorted(POOL_LEAVES))
@pytest.mark.parametrize("dest", [(24, 4), (1, 512)],
                         ids=["step-24x4", "extend-1x512"])
def test_row_scatter_updates_the_donated_pool_in_place(chip, dest, leaf):
    """The decode step's merge (24 slots x 4 steps) and the chunked
    extend's (1 x 512) write a few rows into a 100 MB leaf.  With the
    heads axis sliced between two indexed axes, XLA copies the whole
    leaf before the scatter and back after it: a fifth of the decode
    step (PR 25)."""
    from aiko_services_tpu.models import layers
    shape, dtype = POOL_LEAVES[leaf]
    slots, width = dest
    compiled = _compiled_donating(
        layers.scatter_paged_rows, chip, (shape, dtype), (dest, jnp.int32),
        (dest, jnp.int32), ((slots, shape[1], width) + shape[3:], dtype))
    assert "scatter" in compiled.as_text()
    _assert_in_place(compiled, shape, dtype)


def test_row_scatter_stays_on_its_shard_of_a_heads_sharded_pool(v5e):
    """Tensor parallel serving shards a pool leaf over its heads axis
    (`create_mesh({"model": 4})`): the scatter keeps that axis an axis
    of its own, so each chip writes its two heads' rows into its own
    quarter of the leaf, in place, and no collective moves a leaf."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from aiko_services_tpu.models import layers
    mesh = Mesh(np.array(v5e.devices).reshape(4), ("model",))
    heads = NamedSharding(mesh, PartitionSpec(None, "model"))
    whole = NamedSharding(mesh, PartitionSpec())
    compiled = jax.jit(layers.scatter_paged_rows, donate_argnums=(0,),
                       out_shardings=heads).lower(
        shaped(heads, POOL, jnp.bfloat16), shaped(whole, (24, 4), jnp.int32),
        shaped(whole, (24, 4), jnp.int32),
        shaped(heads, (24, POOL[1], 4, POOL[3]), jnp.bfloat16)).compile()
    text = compiled.as_text()
    shard = (POOL[0], POOL[1] // 4) + POOL[2:]
    assert "scatter" in text
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text
    _assert_in_place(compiled, shard, jnp.bfloat16)


def test_block_write_updates_the_donated_pool_in_place(chip):
    """The admit's whole-block write at 512 x 1 (16 blocks a row)."""
    from aiko_services_tpu.models import layers
    compiled = _compiled_donating(
        layers.write_paged_blocks, chip, (POOL, jnp.bfloat16),
        ((1, 512 // POOL[2]), jnp.int32),
        ((1, POOL[1], 512, POOL[3]), jnp.bfloat16))
    _assert_in_place(compiled, POOL, jnp.bfloat16)


# a run's shape: (slots, rows a slot, table blocks a slot, what the leaf is)
RUN_WRITES = {
    "step-24x4-bf16": (24, 4, 65, "bf16"),
    "step-24x4-s8": (24, 4, 65, "s8"),
    "step-24x4-scale-f32": (24, 4, 65, "scale-f32"),
    "extend-1x512-bf16": (1, 512, 64, "bf16"),
    "extend-2x512-bf16": (2, 512, 64, "bf16"),
    "extend-1x512-scale-f32": (1, 512, 64, "scale-f32"),
}




@pytest.mark.parametrize("case", sorted(RUN_WRITES))
def test_run_write_is_whole_blocks_in_place(chip, case):
    """layers.write_paged_runs (PR 32) at the step's merge and at a
    chunk: the pool leaf is read by ONE gather whose window is a whole
    block, (W - 1) // 32 + 2 of them a slot, and written by ONE scatter
    of whole blocks into the donated leaf; no copy of a value leaf's
    shape, temporaries under a leaf (the images)."""
    from aiko_services_tpu.models import layers
    slots, width, table, leaf = RUN_WRITES[case]
    shape, dtype = POOL_LEAVES[leaf]
    compiled = _compiled_donating(
        layers.write_paged_runs, chip, (shape, dtype),
        ((slots, table), jnp.int32), ((slots,), jnp.int32),
        ((slots, shape[1], width) + shape[3:], dtype), ((slots,), bool))
    text = compiled.as_text()
    if leaf == "scale-f32":
        # the device keeps a [N, H, 32] plane with N minor-most, where a
        # block is no contiguous window: its 1.5 MB pass through fast
        # memory in the other order and back (no int8 pool is in a cell)
        assert compiled.memory_analysis().temp_size_in_bytes < 0.5e6
    else:
        _assert_in_place(compiled, shape, dtype)
    reads, writes = block_windows(text, shape, jnp.dtype(dtype).name
                                   .replace("bfloat16", "bf16")
                                   .replace("float32", "f32")
                                   .replace("int8", "s8"))
    nblk = layers.run_blocks(width, POOL[2])
    assert len(reads) == 1 and len(writes) == 1, (reads, writes)
    assert math.prod(int(n) for n in reads[0].split(",")) == slots * nblk


def test_run_write_stays_on_its_shard_of_a_heads_sharded_pool(v5e):
    """As the row scatter above: tensor parallel serving shards a leaf
    over its heads, and the block form's images, a gather and a scatter
    whose windows keep the heads axis whole, stay on each chip's two
    heads: no collective, no copy of the shard."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from aiko_services_tpu.models import layers
    mesh = Mesh(np.array(v5e.devices).reshape(4), ("model",))
    heads = NamedSharding(mesh, PartitionSpec(None, "model"))
    whole = NamedSharding(mesh, PartitionSpec())
    compiled = jax.jit(layers.write_paged_runs, donate_argnums=(0,),
                       out_shardings=heads).lower(
        shaped(heads, POOL, jnp.bfloat16), shaped(whole, (24, 65), jnp.int32),
        shaped(whole, (24,), jnp.int32),
        shaped(heads, (24, POOL[1], 4, POOL[3]), jnp.bfloat16),
        shaped(whole, (24,), bool)).compile()
    text = compiled.as_text()
    shard = (POOL[0], POOL[1] // 4) + POOL[2:]
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text
    _assert_in_place(compiled, shard, jnp.bfloat16)
    reads, writes = block_windows(text, shard, "bf16")
    assert len(reads) == 1 and len(writes) == 1, (reads, writes)


# -- the latent pool's row (ISSUE 31) ---------------------------------------------

def test_latent_walk_compiles_at_a_row_of_640_lanes(chip):
    """The layout Mosaic takes: ONE leaf whose row is [c_kv 512 | k_rope
    64 | 64 zero lanes], K the whole row and V its leading 512 lanes, one
    shared "KV head" and 64 query rows a slot."""
    from aiko_services_tpu.ops.paged_attention import paged_decode_attention
    slots, blocks, lanes = 32, 257, 640

    def walk(q, pool, tables, side, valid, lengths):
        return paged_decode_attention(
            q, pool, None, tables, side, side[..., :512], valid, lengths,
            groups=64, scale=0.13, interpret=False)

    compiled = jax.jit(walk).lower(*(
        shaped(chip, shape, kind) for shape, kind in [
            ((slots, 1, 64, lanes), jnp.bfloat16),
            ((slots * 256 + 1, 1, BLOCK, lanes), jnp.bfloat16),
            ((slots, blocks), jnp.int32),
            ((slots, 1, 4, lanes), jnp.bfloat16),
            ((slots, 1, 4), jnp.bool_), ((slots,), jnp.int32)])).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes", [576, 64])
def test_latent_rows_that_are_not_whole_lanes_are_refused(chip, lanes):
    """Why the row is padded: a 576-lane row, or a 64-lane leaf of its
    own for k_rope, cannot be sliced out of HBM block by block."""
    from aiko_services_tpu.ops import paged_attention as pa
    assert not pa.walks_live_blocks(lanes, False)
    assert pa.walks_live_blocks(640, False)

    def walk(q, pool, tables, side, valid, lengths):
        return pa.paged_decode_attention(
            q, pool, None, tables, side, side[..., :lanes // 2], valid,
            lengths, groups=64, scale=0.13, interpret=False)

    with pytest.raises(ValueError, match="latent pool"):
        jax.jit(walk).lower(*(
            shaped(chip, shape, kind) for shape, kind in [
                ((2, 1, 64, lanes), jnp.bfloat16),
                ((65, 1, BLOCK, lanes), jnp.bfloat16), ((2, 32), jnp.int32),
                ((2, 1, 4, lanes), jnp.bfloat16), ((2, 1, 4), jnp.bool_),
                ((2,), jnp.int32)]))


def test_paged_row_tile_stays_inside_vmem_budget():
    from aiko_services_tpu.ops import paged_attention as pa
    # a decode row fits whole; an extend's G*chunk rows are tiled
    assert pa._row_tile(4, 40, HKV, BLOCK) == 4
    rows = pa._row_tile(1024, 40, HKV, BLOCK)
    assert rows < 1024 and 1024 % rows == 0 and rows % 8 == 0
    assert rows * 40 * HKV * 128 * 4 <= pa._SCORES_VMEM_BUDGET
    with pytest.raises(ValueError, match="VMEM"):
        pa._row_tile(8, 1 << 16, HKV, BLOCK)


class TestCompileCachePlacement:
    def test_environment_places_the_cache(self, monkeypatch, tmp_path):
        from aiko_services_tpu import compute
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compute.enable_compile_cache() == str(tmp_path)
        # no directory is set in code when the environment names one
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_inside_the_checkout(self, monkeypatch):
        from aiko_services_tpu import compute
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            checkout = os.path.dirname(os.path.dirname(
                os.path.abspath(compute.__file__)))
            expected = os.path.join(checkout, ".jax_cache")
            assert compute.enable_compile_cache() == expected
            assert compute.enable_compile_cache() == expected   # stable
            assert jax.config.jax_compilation_cache_dir == expected
        finally:
            # the suite itself runs cache-free
            jax.config.update("jax_compilation_cache_dir", before)

