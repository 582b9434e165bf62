# Chip-compile rehearsal kept as tests (guide on-chip-measurement §2 step 3):
# every pallas kernel of the main path, compiled with interpret=False for a
# DESCRIBED v5e — the TPU compiler is installed here though no chip is — at
# the shapes the serving stack gives it.  Interpret mode cannot see what
# these see: PR 16's paged kernel passed every interpret-mode test and was
# refused by mosaic at every serving geometry (a dynamic lane-dimension
# slice at kv_block=32).  Nothing runs, so these say nothing about results
# or times.  Plus the compile-cache helper's placement contract.

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

# Llama-3.2-1B attention geometry, the serving stack's pool block
HKV, GROUPS, HEAD_DIM, BLOCK = 8, 4, 64, 32


@pytest.fixture(scope="module")
def v5e():
    """A described v5e 2x2; the persistent compilation cache is off
    around the module (an executable compiled for a described device
    cannot be read back without one — a warm cache would only add
    warnings)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:           # no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e: {exc!r}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(v5e):
    """SingleDeviceSharding on one chip of it."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e.devices[0])


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
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    fn, shapes = CASES[case]()
    args = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], leaf[1], sharding=chip),
        shapes, is_leaf=lambda leaf: isinstance(leaf, tuple))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# mistral-7b-v0.3-d16's pool leaf in the benchmark's cells: 24 slots x 64
# blocks + the null block, 8 KV heads, 32 tokens a block, head 128
POOL = (1537, 8, 32, 128)
POOL_LEAVES = {"bf16": (POOL, jnp.bfloat16), "s8": (POOL, jnp.int8),
               "scale-f32": (POOL[:3], jnp.float32)}


def _compiled_donating(fn, sharding, *leaves):
    """`fn` compiled for `sharding` with its first argument donated;
    every leaf is (shape, dtype)."""
    return jax.jit(fn, donate_argnums=(0,)).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in leaves)).compile()


def _assert_in_place(compiled, shape, dtype):
    """No `copy` in the optimized HLO has a result of the pool leaf's
    shape (in whatever layout), and the temporaries are under a leaf."""
    result = re.escape("[" + ",".join(map(str, shape)) + "]")
    assert re.findall(rf"= \w+{result}\S* copy\(.*", compiled.as_text()) == []
    assert compiled.memory_analysis().temp_size_in_bytes < \
        math.prod(shape) * jnp.dtype(dtype).itemsize


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
        jax.ShapeDtypeStruct(POOL, jnp.bfloat16, sharding=heads),
        jax.ShapeDtypeStruct((24, 4), jnp.int32, sharding=whole),
        jax.ShapeDtypeStruct((24, 4), jnp.int32, sharding=whole),
        jax.ShapeDtypeStruct((24, POOL[1], 4, POOL[3]), jnp.bfloat16,
                             sharding=heads)).compile()
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


def _block_windows(text, leaf_shape, dtype_name):
    """(how many, of what shape) the whole-block gather reads of the pool
    leaf and the scatter writes back to it, from the optimized HLO."""
    # (the compiler drops a unit axis: a latent leaf's one head)
    leaf_shape = [n for n in leaf_shape if n != 1]
    leaf = re.escape("[" + ",".join(map(str, leaf_shape)) + "]")
    window = ",".join(map(str, leaf_shape[1:]))
    reads = re.findall(
        rf"= {dtype_name}\[([\d,]+),{window}\]\S* gather\(.*"
        rf"slice_sizes=\{{1,{window}\}}", text)
    writes = re.findall(
        rf"= {dtype_name}{leaf}\S* scatter\(.*inserted_window_dims=\{{0\}}, "
        rf"scatter_dims_to_operand_dims=\{{0\}}", text)
    return reads, writes


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
    reads, writes = _block_windows(text, shape, jnp.dtype(dtype).name
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
        jax.ShapeDtypeStruct(POOL, jnp.bfloat16, sharding=heads),
        jax.ShapeDtypeStruct((24, 65), jnp.int32, sharding=whole),
        jax.ShapeDtypeStruct((24,), jnp.int32, sharding=whole),
        jax.ShapeDtypeStruct((24, POOL[1], 4, POOL[3]), jnp.bfloat16,
                             sharding=heads),
        jax.ShapeDtypeStruct((24,), bool, sharding=whole)).compile()
    text = compiled.as_text()
    shard = (POOL[0], POOL[1] // 4) + POOL[2:]
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text
    _assert_in_place(compiled, shard, jnp.bfloat16)
    reads, writes = _block_windows(text, shard, "bf16")
    assert len(reads) == 1 and len(writes) == 1, (reads, writes)


def _mistral_cell(chip):
    """mistral-7b-v0.3-d16 as the benchmark's cells serve it, as shapes
    on `chip`: the configuration, its serving block, the weights, a full
    pool side (24 slots x 64 blocks and the null block) and `shaped`."""
    import json
    from aiko_services_tpu.models.llama import LlamaConfig
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mistral-7b-v0.3-d16.json")) as f:
        sizes = json.load(f)
    serve = sizes["serving"]
    config = LlamaConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        ffn_dim=sizes["intermediate_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        max_seq_len=serve["max_seq"], rope_theta=sizes["rope_theta"],
        dtype=jnp.bfloat16)

    def shaped(shape, kind):
        return jax.ShapeDtypeStruct(shape, kind, sharding=chip)

    from aiko_services_tpu.models.llama import llama_init
    params = jax.tree.map(
        lambda leaf: shaped(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: llama_init(jax.random.PRNGKey(0), config)))
    pool = [shaped(POOL, jnp.bfloat16) for _ in range(config.num_layers)]
    return config, serve, params, pool, shaped


def _mistral_step(chip, kernel, width):
    """`jit_step` x 4 of mistral-7b-v0.3-d16 as the benchmark's cells
    run it, whole, compiled for `chip`: 24 slots, a full pool, the
    table at its constant 65 blocks (64 and the merge's headroom)."""
    from aiko_services_tpu import serving_paged
    config, serve, params, pool, shaped = _mistral_cell(chip)
    slots, table = serve["max_slots"], 65
    return serving_paged._paged_step_for(config, kernel).lower(
        params, shaped((slots,), jnp.int32), shaped((slots,), jnp.int32),
        shaped((slots,), bool), shaped((slots,), jnp.int32), pool, pool,
        shaped((slots, table), jnp.int32), num_steps=4, eos=-1,
        t_cap=width).compile()


VIEW = r"bf16\[24,(\d+),8,32,128\]"       # a slot-major K or V view
MERGE_IMAGES = {"2"}    # and the merge's: the two blocks four rows fall in


@pytest.mark.parametrize("width, blocks, temporaries", [
    (1024, 32, 2.3e9),      # the width of every decode_saturated round
    (2048, 64, 4.56e9),     # the cap: what it was before the ladder
], ids=["half", "cap"])
def test_step_views_follow_the_attend_width(chip, width, blocks,
                                            temporaries):
    """The gather step's views are gathered at the width's blocks and
    no wider, whatever the table holds, and the temporaries shrink with
    them; the cap's program is not widened to the table's 65."""
    compiled = _mistral_step(chip, False, width)
    views = set(re.findall(VIEW, compiled.as_text())) - MERGE_IMAGES
    assert views == {str(blocks)}, views
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries


def test_kernel_step_builds_no_views_and_copies_no_pool(chip, monkeypatch):
    """The step a cell runs on the chip (PR 30): attention through the
    pallas kernel, lowered with mosaic (this host's backend is the CPU,
    where the kernel would pick the interpreter, whose loops copy every
    pool leaf: the test says "tpu" for it).  Sixteen kernels, one a
    layer; no slot-major view; no `copy` of a pool leaf's shape (a
    pool-shaped operand handed to the kernel by value would be one);
    temporaries a twentieth of the gather step's at the cap."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _mistral_step(chip, True, 2048)
    text = compiled.as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 16
    assert set(re.findall(VIEW, text)) == MERGE_IMAGES
    result = re.escape("[" + ",".join(map(str, POOL)) + "]")
    assert re.findall(rf"= \w+{result}\S* copy\(.*", text) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    # the merge (PR 32): each of the 32 leaves is read by one gather of
    # whole blocks, two a slot, and written by one scatter of them
    reads, writes = _block_windows(text, POOL, "bf16")
    assert reads == ["24,2"] * 32 and len(writes) == 32
    assert len(re.findall(r" scatter\(", text)) == 32


def test_mistral_extend_writes_its_chunk_by_whole_blocks(chip):
    """`jit_extend` 512 x 1 of the cells (the gather path, as a cell
    runs it): each leaf's chunk goes back as 17 whole blocks, in place;
    the temporaries are the one slot's views at the cap and the chunk's
    activations."""
    from aiko_services_tpu import serving_paged
    config, serve, params, pool, shaped = _mistral_cell(chip)
    slots = serve["max_slots"]
    vector = shaped((1,), jnp.int32)
    compiled = serving_paged._paged_extend_fn_for(
        config, 512, 1, False, False, False).lower(
        params, pool, pool, shaped((slots,), jnp.int32),
        shaped((slots,), jnp.int32), shaped((1, 1), jnp.int32),
        shaped((1, 512), jnp.int32), vector, vector, shaped((1,), bool),
        shaped((1,), bool), vector,
        shaped((1, serve["max_seq"] // POOL[2]), jnp.int32),
        t_cap=serve["max_seq"]).compile()
    text = _no_pool_copy(compiled, POOL, 0.5e9)
    reads, writes = _block_windows(text, POOL, "bf16")
    # (the 64-block gathers are the prefix views of the one slot)
    assert sorted(reads) == ["17"] * 32 + ["64"] * 32 and len(writes) == 32
    assert len(re.findall(r" scatter\(", text)) == 32


# -- the latent pool (ISSUE 31): ax-k1-ep16-d6 at the cell's geometry -------------

def _latent_cell(chip):
    """The configuration `doc_qa_open_loop` serves, as shapes on `chip`:
    32 slots x 8,192 positions of one [blocks, 1, 32, 640] leaf a layer,
    6 layers at the published widths, 12 of 192 experts held."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for path in (root, os.path.join(root, "benchmark", "drivers")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import latent_moe_decoder
    from aiko_services_tpu.models.latent_moe import latent_moe_init
    with open(os.path.join(root, "benchmark", "configs",
                           "ax-k1-ep16-d6.json")) as f:
        sizes = json.load(f)
    serve = sizes["serving"]
    config = latent_moe_decoder.model_config(sizes, serve["max_seq"],
                                             jnp.bfloat16)

    def shaped(shape, kind):
        return jax.ShapeDtypeStruct(shape, kind, sharding=chip)

    params = jax.tree.map(
        lambda leaf: shaped(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: latent_moe_init(jax.random.PRNGKey(0),
                                               config)))
    slots, block = serve["max_slots"], serve["kv_block"]
    leaf = (slots * serve["max_seq"] // block + 1, 1, block,
            config.row_lanes)
    pool = [shaped(leaf, jnp.bfloat16) for _ in range(config.num_layers)]
    state = [shaped((slots,), jnp.int32), shaped((slots,), jnp.int32)]
    return config, serve, params, pool, state, leaf, shaped


def _no_pool_copy(compiled, leaf, temporaries):
    text = compiled.as_text()
    result = re.escape("[" + ",".join(map(str, leaf)) + "]")
    assert re.findall(rf"= \w+{result}\S* copy\(.*", text) == []
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries
    return text


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
        jax.ShapeDtypeStruct(shape, kind, sharding=chip)
        for shape, kind in [
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
            jax.ShapeDtypeStruct(shape, kind, sharding=chip)
            for shape, kind in [
                ((2, 1, 64, lanes), jnp.bfloat16),
                ((65, 1, BLOCK, lanes), jnp.bfloat16), ((2, 32), jnp.int32),
                ((2, 1, 4, lanes), jnp.bfloat16), ((2, 1, 4), jnp.bool_),
                ((2,), jnp.int32)]))


def test_latent_step_walks_the_pool_and_copies_none_of_it(chip, monkeypatch):
    """The whole 6-layer `jit_step` x 4 of the cell: six walks, one a
    layer, no pool-shaped copy, temporaries under 0.3 GB (the experts
    that a token reached run inside conditionals; nothing is expanded)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from aiko_services_tpu import serving_paged
    config, serve, params, pool, state, leaf, shaped = _latent_cell(chip)
    slots = serve["max_slots"]
    table = -(-(serve["max_seq"] + serve["steps_per_sync"])
              // serve["kv_block"])
    compiled = serving_paged._paged_step_for(config, True).lower(
        params, *state, shaped((slots,), bool), shaped((slots,), jnp.int32),
        pool, [], shaped((slots, table), jnp.int32),
        num_steps=serve["steps_per_sync"], eos=-1,
        t_cap=serve["max_seq"]).compile()
    text = _no_pool_copy(compiled, leaf, 0.3e9)
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == config.num_layers
    # the merge keeps its ROWS here (PR 32): four windows of one head a
    # slot against two blocks read and two written, 160 KiB for 5
    assert _block_windows(text, leaf, "bf16") == ([], [])
    assert len(re.findall(r" scatter\(", text)) == config.num_layers
    memory = compiled.memory_analysis()
    # 8.33 GB of weights + 2.01 GB of pool, the pool aliased in and out
    assert 10.2e9 < memory.argument_size_in_bytes < 10.5e9
    assert memory.alias_size_in_bytes > 2.0e9


@pytest.mark.parametrize("program", ["admit-512x1", "admit-256x2",
                                     "extend-512x1"])
def test_latent_prefill_programs_compile_and_copy_no_pool(chip, program):
    """Admit and extend go the EXPANDED way: no kernel, the prefix read
    piece by piece through the table, the chunk's rows scattered in
    place; the temporaries (a piece's per-head keys and values, the
    expert tiles) stay under 0.3 GB."""
    from aiko_services_tpu import serving_paged
    config, serve, params, pool, state, leaf, shaped = _latent_cell(chip)
    kind, _, size = program.partition("-")
    tokens, width = (int(n) for n in size.split("x"))
    block = serve["kv_block"]
    context = shaped((1, 1), jnp.int32)
    vector = shaped((width,), jnp.int32)
    if kind == "admit":
        lowered = serving_paged._paged_admit_fn_for(
            config, tokens, width, False, False).lower(
            params, pool, [], *state, context,
            shaped((width, tokens), jnp.int32), vector, vector,
            shaped((width,), bool),
            shaped((width, -(-tokens // block)), jnp.int32))
    else:
        lowered = serving_paged._paged_extend_fn_for(
            config, tokens, width, False, False, False).lower(
            params, pool, [], *state, context,
            shaped((width, tokens), jnp.int32), vector, vector,
            shaped((width,), bool), shaped((width,), bool), vector,
            shaped((width, serve["max_seq"] // block), jnp.int32),
            t_cap=serve["max_seq"])
    text = _no_pool_copy(lowered.compile(), leaf, 0.3e9)
    assert "tpu_custom_call" not in text
    if kind == "extend":
        # the chunk goes back as 17 whole blocks a leaf (PR 32); the
        # 16-block gathers are the prefix, piece by piece
        reads, writes = _block_windows(text, leaf, "bf16")
        assert reads.count("17") == config.num_layers
        assert len(writes) == config.num_layers
        assert len(re.findall(r" scatter\(", text)) == config.num_layers


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


# what hands an array on in optimized HLO without making it
_HLO_CARRIES = {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
                "call", "conditional"}


@pytest.fixture(scope="module")
def hybrid_step(chip):
    """The whole 5-layer `jit_step` x 4 of `long_doc_open_loop` as the
    cell's decoder builds it on the chip (`step_kernel`: the KDA layers'
    recurrence through ops/kda_step.py), compiled once for the tests
    below: -> (compiled, config, the sizes' `serving`, pool blocks)."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for path in (root, os.path.join(root, "benchmark", "drivers")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import hybrid_sparse_decoder
    from aiko_services_tpu import serving_paged
    from aiko_services_tpu.models import hybrid_sparse as M
    with open(os.path.join(root, "benchmark", "configs",
                           "glm-5.3-flash-ep8-d5.json")) as f:
        sizes = json.load(f)
    serve = sizes["serving"]
    config = hybrid_sparse_decoder.model_config(sizes, serve["max_seq"],
                                                jnp.bfloat16)

    def shaped(shape, kind):
        return jax.ShapeDtypeStruct(tuple(shape), kind, sharding=chip)

    params = jax.tree.map(
        lambda leaf: shaped(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: M.hybrid_sparse_init(jax.random.PRNGKey(0),
                                                    config)))
    slots, block = serve["max_slots"], serve["kv_block"]
    blocks = slots * serve["max_seq"] // block + 1
    leaves = serving_paged.layer_leaves(config)
    k_pools, v_pools = (
        [shaped((blocks, layer[side][0], block // layer[side][2],
                 layer[side][1]), jnp.bfloat16) if layer else None
         for layer in leaves] for side in (0, 1))
    state = [tuple(shaped((slots,) + tuple(shape), kind)
                   for shape, kind in layer) for layer in config.slot_state]
    table = -(-(serve["max_seq"] + serve["steps_per_sync"]) // block)
    vector = shaped((slots,), jnp.int32)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = serving_paged._paged_step_for(config, True).lower(
            params, vector, vector, shaped((slots,), bool), vector, k_pools,
            v_pools, shaped((slots, table), jnp.int32), state,
            num_steps=serve["steps_per_sync"], eos=-1,
            t_cap=serve["max_seq"]).compile()
    return compiled, config, serve, blocks


def test_hybrid_step_fetches_whole_tiles_from_the_latent_leaf_as_it_lies(
        hybrid_step):
    """The gather of the chosen groups takes the 8-row tile that holds a
    group from the leaf as it lies (ISSUE 37; until then the compiler
    laid the WHOLE 1.07 GB leaf out anew by groups, once a round): no
    `reshape`, `copy` or fusion makes an array of the leaf's size, under
    any scope but the merge's, whose scatter writes the round's rows in
    place; and the gather's operand is the loop's own leaf seen through a
    bitcast.  `aiko.dsa_relayout` is still a scope of the source and
    holds that bitcast alone, so `dsa_step_relayout_ms` reads 0.0."""
    from aiko_services_tpu.models import hybrid_sparse as M
    from aiko_services_tpu.models.llama import SCOPE_KV_MERGE
    compiled, config, serve, blocks = hybrid_step
    rows = blocks * serve["kv_block"]
    lines = [line.strip() for line in compiled.as_text().splitlines()]
    made = [line for line in lines for found in [re.search(
        r"= bf16\[([\d,]+)\]\S* ([a-z\-]+)\(", line)]
        if found and found.group(2) not in _HLO_CARRIES and
        math.prod(map(int, found.group(1).split(","))) ==
        rows * config.kv_rank]
    assert all(SCOPE_KV_MERGE in line and
               re.search(r" (scatter|fusion)\(", line) for line in made), \
        [line[:200] for line in made]
    assert len([line for line in made if " fusion(" in line]) == 1
    assert [" bitcast(" in line for line in lines
            if M.SCOPE_DSA_RELAYOUT in line] == [True]
    # [window x top_groups, 8, rank] a window of the slots that decode
    # (ISSUE 42; every slot's until then), out of the leaf seen by tiles
    tiles = "bf16[%d,8,%d]" % (rows // 8, config.kv_rank)
    fetched = "bf16[%d,8,%d]" % (M._STEP_WINDOW * config.top_groups,
                                 config.kv_rank)
    gathers = [line for line in lines
               if re.match(r"%\S+ = " + re.escape(fetched), line) and
               " fusion(" in line and "/gather" in line]
    assert len(gathers) == 1 and M.SCOPE_ATTN_CORE in gathers[0], gathers
    operand = re.search(r" fusion\((%[\w.\-]+),", gathers[0]).group(1)
    source = next(line for line in lines if line.startswith(operand + " = "))
    assert re.match(re.escape(f"{operand} = {tiles}") +
                    r"\S* bitcast\(%get-tuple-element", source), source[:200]
    memory = compiled.memory_analysis()
    # 9.44 GB of weights, 1.14 GB of pool, 0.56 GB of slot state; the
    # temporaries held the leaf's copy (1.07 GB) beside the step's own
    assert 11.0e9 < memory.argument_size_in_bytes < 11.3e9
    assert memory.temp_size_in_bytes < 0.6e9


def test_hybrid_step_moves_slot_state_through_the_kernel_alone(hybrid_step):
    """The same program (PR 34): the four KDA layers' recurrence is four
    custom calls under `aiko.kda_core`, their state argument aliased to
    their result, and NO other computing operation makes a whole state
    leaf `f32[32,64,128,128]`: no fusion over every slot's state, no copy
    that a failed aliasing would put before the kernel (it would also
    show as 134 MB a layer of temporaries: the bounds above)."""
    from aiko_services_tpu.models import hybrid_sparse as M
    compiled, config, serve, _ = hybrid_step
    leaf = "f32[%d,%d,%d,%d]" % (
        serve["max_slots"], config.kda_heads, config.kda_head_dim,
        config.kda_head_dim)
    made = [line.strip() for line in compiled.as_text().splitlines()
            if re.search(r"= \(?[^=]*%s\S* (\S+)\(" % re.escape(leaf), line)]
    kinds = [re.search(r"\S* ([a-z\-]+)\(", line.split(" = ", 1)[1]).group(1)
             for line in made]
    carried = _HLO_CARRIES | {"custom-call"}
    assert set(kinds) <= carried, [
        line[:200] for line, kind in zip(made, kinds) if kind not in carried]
    kernels = [line for line, kind in zip(made, kinds)
               if kind == "custom-call"]
    kda_layers = sum(kind == "kda" for kind in config.layer_types)
    assert len(kernels) == kda_layers == 4
    assert all(M.SCOPE_KDA_CORE in line and "tpu_custom_call" in line and
               "output_to_operand_aliasing" in line for line in kernels)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


# -- three leaves a layer, keys chosen token by token (ISSUE 38) -----------------

def _sparse_gqa_cell(chip):
    """keye-vl-2.0-30b-a3b-ep8-d12 at the cell's sizes, as shapes on the
    described chip: -> (config, serving, params, k_pools, v_pools, shaped)."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for path in (root, os.path.join(root, "benchmark", "drivers")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import sparse_gqa_decoder
    from aiko_services_tpu import serving_paged
    from aiko_services_tpu.models import sparse_gqa as M
    with open(os.path.join(root, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b-ep8-d12.json")) as f:
        sizes = json.load(f)
    serve = sizes["serving"]
    config = sparse_gqa_decoder.model_config(sizes, serve["max_seq"],
                                             jnp.bfloat16)

    def shaped(shape, kind):
        return jax.ShapeDtypeStruct(tuple(shape), kind, sharding=chip)

    params = jax.tree.map(
        lambda leaf: shaped(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: M.sparse_gqa_init(jax.random.PRNGKey(0),
                                                 config)))
    block = serve["kv_block"]
    blocks = serve["max_slots"] * serve["max_seq"] // block + 1
    sides = [[shaped((blocks, heads, block, lanes), jnp.bfloat16)
              for heads, lanes, _ in (layer[side] for layer in
                                      serving_paged.layer_leaves(config))]
             for side in range(3)]
    return config, serve, params, sides[0], sides[1] + sides[2], shaped


def _leaf_shapes(config, serve):
    blocks = serve["max_slots"] * serve["max_seq"] // serve["kv_block"] + 1
    return [(blocks, heads, serve["kv_block"], lanes)
            for heads, lanes in config.cache_leaves]


def test_sparse_gqa_step_selects_exactly_and_copies_no_leaf(chip,
                                                            monkeypatch):
    """The whole 12-layer `jit_step` x 4 of `long_ctx_open_loop` as the
    cell's decoder builds it on the chip (`step_kernel`, ISSUE 39): it fits
    the chip beside its pool; a layer's attention is ONE Pallas call under
    `aiko.attn_core` (the walk, the chosen positions its mask) and no K or
    V leaf is gathered, as rows or otherwise; the choice is exact and has
    no sort (the sorts left are the router's and the one for the order in
    which the slots that decode are taken); no leaf is copied, and the
    three leaves of every layer are merged in place."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import dataclasses
    from aiko_services_tpu import serving_paged
    from aiko_services_tpu.models import sparse_gqa as M
    from aiko_services_tpu.serving import ContinuousDecoder
    config, serve, params, k_pools, v_pools, shaped = _sparse_gqa_cell(chip)
    # who decides: a decoder told nothing, at this model's head of 128
    small = dataclasses.replace(
        M.SPARSE_GQA_PRESETS["tiny"], head_dim=config.head_dim,
        mrope_section=config.mrope_section)
    decoder = ContinuousDecoder(
        M.sparse_gqa_init(jax.random.PRNGKey(0), small), small,
        paged_kv=True, kv_block=8, max_slots=2, max_seq=64, prefill_chunk=32,
        name="sparse-gqa-described")
    assert decoder.step_kernel and decoder._walks_live
    assert decoder._attend_widths == (64,)
    slots = serve["max_slots"]
    table = -(-(serve["max_seq"] + serve["steps_per_sync"])
              // serve["kv_block"])
    vector = shaped((slots,), jnp.int32)
    compiled = serving_paged._paged_step_for(config, True).lower(
        params, vector, vector, shaped((slots,), bool), vector, k_pools,
        v_pools, shaped((slots, table), jnp.int32),
        num_steps=serve["steps_per_sync"], eos=-1,
        t_cap=serve["max_seq"]).compile()
    text = compiled.as_text()
    walks = re.findall(r"custom-call\([^\n]*tpu_custom_call[^\n]*", text)
    assert len(walks) == config.num_layers
    assert all("aiko.attn_core" in walk for walk in walks)
    assert "ApproxTopK" not in text
    # what is gathered of a K or V leaf is the merge's whole blocks; a
    # leaf seen as rows gave single rows of a head's lanes
    row = "slice_sizes={1,%d}" % config.head_dim
    assert row not in text and " gather(" in text
    for leaf in _leaf_shapes(config, serve):
        result = re.escape("[" + ",".join(map(str, leaf)) + "]")
        assert re.findall(rf"= \w+{result}\S* copy\(.*", text) == []
    # a sort a layer for the router's eight, and ONE for the order in
    # which the slots that decode are taken (every layer's is the same:
    # the compiler keeps one); the positions are chosen without one
    assert len(re.findall(r" sort\(", text)) == config.num_layers + 1
    assert not re.findall(r"aiko\.dsa_select/[^\n\"]*(top_k|sort)", text)
    assert "aiko.dsa_select" in text
    merges = re.findall(r"fusion\([^\n]*aiko\.kv_merge/scatter", text)
    assert len(merges) == 3 * config.num_layers
    memory = compiled.memory_analysis()
    # 2.48 GB of weights + the pool, the pool aliased in and out; the
    # indexer keys of every layer gathered once a round are temporaries
    pool = sum(math.prod(leaf) * 2 for leaf in _leaf_shapes(config, serve)) \
        * config.num_layers
    assert memory.alias_size_in_bytes >= pool
    assert 2.4e9 < memory.argument_size_in_bytes - pool < 2.6e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 16.4e9


def test_sparse_gqa_extend_chooses_without_a_sort_and_copies_no_leaf(chip):
    """A 512-token chunk against a prefix of up to 32k: every query's
    2,048 positions come from a threshold found bit by bit (the only sort
    is the router's), the prefix is read piece by piece, the chunk's rows
    of the three leaves are scattered in place."""
    from aiko_services_tpu import serving_paged
    config, serve, params, k_pools, v_pools, shaped = _sparse_gqa_cell(chip)
    slots, chunk = serve["max_slots"], serve["prefill_chunk"]
    table = -(-(serve["max_seq"] + serve["steps_per_sync"])
              // serve["kv_block"])
    vector, one = shaped((slots,), jnp.int32), shaped((1,), jnp.int32)
    compiled = serving_paged._paged_extend_fn_for(
        config, chunk, 1, False, False, False).lower(
        params, k_pools, v_pools, vector, vector, shaped((slots, 1),
                                                         jnp.int32),
        shaped((1, chunk), jnp.int32), one, one, shaped((1,), bool),
        shaped((1,), bool), one, shaped((1, table), jnp.int32),
        t_cap=serve["max_seq"]).compile()
    text = compiled.as_text()
    for leaf in _leaf_shapes(config, serve):
        result = re.escape("[" + ",".join(map(str, leaf)) + "]")
        assert re.findall(rf"= \w+{result}\S* copy\(.*", text) == []
    assert len(re.findall(r" sort\(", text)) == config.num_layers
    assert "aiko.dsa_select" in text and "aiko.dsa_index" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9


# -- slot state beside a pool the shared kernel walks (ISSUE 40) -----------------

def _gated_delta_cell(chip):
    """`gdn_decode_saturated`'s model and the arguments its programs
    share, as shapes on `chip`: (config, `serving`, params, k_pools,
    v_pools, state, `shaped`)."""
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for path in (root, os.path.join(root, "benchmark", "drivers")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import gated_delta_decoder
    from aiko_services_tpu import serving_paged
    from aiko_services_tpu.models import gated_delta as M
    with open(os.path.join(root, "benchmark", "configs",
                           "olmo-hybrid-7b-d16.json")) as f:
        sizes = json.load(f)
    serve = sizes["serving"]
    config = gated_delta_decoder.model_config(sizes, serve["max_seq"],
                                              jnp.bfloat16)

    def shaped(shape, kind):
        return jax.ShapeDtypeStruct(tuple(shape), kind, sharding=chip)

    params = jax.tree.map(
        lambda leaf: shaped(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: M.gated_delta_init(jax.random.PRNGKey(0),
                                                  config)))
    slots, block = serve["max_slots"], serve["kv_block"]
    blocks = slots * serve["max_seq"] // block + 1
    leaves = serving_paged.layer_leaves(config)
    k_pools, v_pools = (
        [shaped((blocks, layer[side][0], block, layer[side][1]),
                jnp.bfloat16) if layer else None for layer in leaves]
        for side in (0, 1))
    state = [tuple(shaped((slots,) + tuple(shape), kind)
                   for shape, kind in layer) for layer in config.slot_state]
    return config, serve, params, k_pools, v_pools, state, shaped


@pytest.fixture(scope="module")
def gated_delta_step(chip):
    """The whole 16-layer `jit_step` x 4 of `gdn_decode_saturated` as the
    cell's decoder builds it on the chip (`step_kernel` for both reasons:
    the full layers' walk of the pool, the recurrent layers' state through
    ops/kda_step.py), compiled once: -> (compiled, config, `serving`)."""
    from aiko_services_tpu import serving_paged
    config, serve, params, k_pools, v_pools, state, shaped = \
        _gated_delta_cell(chip)
    slots, block = serve["max_slots"], serve["kv_block"]
    table = -(-(serve["max_seq"] + serve["steps_per_sync"]) // block)
    vector = shaped((slots,), jnp.int32)
    model = config.paged_model()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        # what a decoder that is told nothing finds on the chip
        assert model.walks(config, False, False) == "kernel"
        assert model.step_kernel(config, False) is True
        compiled = serving_paged._paged_step_for(config, True).lower(
            params, vector, vector, shaped((slots,), bool), vector, k_pools,
            v_pools, shaped((slots, table), jnp.int32), state,
            num_steps=serve["steps_per_sync"], eos=-1,
            t_cap=serve["max_seq"]).compile()
    return compiled, config, serve


def test_gated_delta_step_moves_slot_state_through_the_kernel_alone(
        gated_delta_step):
    """The twelve recurrent layers' recurrence is twelve custom calls under
    `aiko.gdn_state`, their state argument aliased to their result, and NO
    other computing operation makes a whole state leaf `f32[64,96,5760]`:
    no fusion over every slot's state, no copy that a failed aliasing would
    put before the kernel (it would also show as 141 MB a layer of
    temporaries: the bound below).  A live slot's state goes once in and
    once out, a slot that does not decode is not addressed."""
    from aiko_services_tpu.models import gated_delta as M
    compiled, config, serve = gated_delta_step
    leaf = "f32[%d,%d,%d]" % (serve["max_slots"], config.key_dim,
                              config.gdn_heads * config.value_dim)
    made = [line.strip() for line in compiled.as_text().splitlines()
            if re.search(r"= \(?[^=]*%s\S* (\S+)\(" % re.escape(leaf), line)]
    kinds = [re.search(r"\S* ([a-z\-]+)\(", line.split(" = ", 1)[1]).group(1)
             for line in made]
    carried = _HLO_CARRIES | {"custom-call"}
    assert set(kinds) <= carried, [
        line[:200] for line, kind in zip(made, kinds) if kind not in carried]
    kernels = [line for line, kind in zip(made, kinds)
               if kind == "custom-call"]
    recurrent = sum(kind == "gdn" for kind in config.layer_types)
    assert len(kernels) == recurrent == 12
    assert all(M.SCOPE_GDN_STATE in line and "tpu_custom_call" in line and
               "output_to_operand_aliasing" in line for line in kernels)
    memory = compiled.memory_analysis()
    # 8.20 GB of weights, 4.03 GB of pool, 1.75 GB of slot state
    assert 13.9e9 < memory.argument_size_in_bytes < 14.1e9
    assert memory.temp_size_in_bytes < 0.3e9


def test_gated_delta_admit_scans_a_prompt_in_one_kernel_a_layer(chip):
    """The cell's `jit_admit` (one prompt padded to the bucket of 512) as a
    decoder traces it on the chip: the twelve recurrent layers' chunked
    delta rule is twelve `gdn_chunk_scan` custom calls under
    `aiko.gdn_scan` (ISSUE 41), their state argument aliased to their
    result, and NO loop is left under that scope: XLA's form of
    models/delta_rule.chunked was a `while` of eight trips a layer."""
    from aiko_services_tpu import serving_paged
    from aiko_services_tpu.models import gated_delta as M
    config, serve, params, k_pools, v_pools, state, shaped = \
        _gated_delta_cell(chip)
    slots, block = serve["max_slots"], serve["kv_block"]
    bucket = serve["prefill_buckets"][-1]
    vector = shaped((slots,), jnp.int32)
    one, flag = shaped((1,), jnp.int32), shaped((1,), bool)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        assert config.paged_model().scan_kernel(config, False) is True
        serving_paged._paged_admit_fn_for.cache_clear()
        compiled = serving_paged._paged_admit_fn_for(
            config, bucket, 1, False, False).lower(
            params, k_pools, v_pools, vector, vector,
            shaped((1, 1), jnp.int32), shaped((1, bucket), jnp.int32), one,
            one, flag, shaped((1, -(-bucket // block)), jnp.int32),
            state).compile()
    serving_paged._paged_admit_fn_for.cache_clear()
    lines = [line.strip() for line in compiled.as_text().splitlines()]
    scans = [line for line in lines if "tpu_custom_call" in line and
             M.SCOPE_GDN_SCAN in line]
    recurrent = sum(kind == "gdn" for kind in config.layer_types)
    assert len(scans) == recurrent == 12
    assert all("gdn_chunk_scan" in line and
               "output_to_operand_aliasing" in line for line in scans)
    assert not [line[:160] for line in lines
                if re.search(r" while\(", line) and M.SCOPE_GDN_SCAN in line]
    # weights 8.20 GB, pool 4.03, slot state 1.75: the admit's own
    # temporaries a quarter of a gigabyte
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


def test_gated_delta_step_walks_the_full_layers_pool_and_copies_none_of_it(
        gated_delta_step):
    """The same program: the four full layers attend through four custom
    calls under `aiko.attn_core` (the shared walk, a group of 1) and no
    operation but the merge's in-place writes makes an array of a pool
    leaf's size; the convolution's tails are rewritten by fusions, small
    (64 x 3 x 11,520) as they are."""
    from aiko_services_tpu.models.llama import (SCOPE_ATTN_CORE,
                                                SCOPE_KV_MERGE)
    compiled, config, serve = gated_delta_step
    lines = [line.strip() for line in compiled.as_text().splitlines()]
    walks = [line for line in lines if "tpu_custom_call" in line and
             SCOPE_ATTN_CORE in line]
    assert len(walks) == sum(kind == "full" for kind in config.layer_types) \
        == 4
    blocks = serve["max_slots"] * serve["max_seq"] // serve["kv_block"] + 1
    leaf = "bf16[%d,%d,%d,%d]" % (blocks, config.num_heads, serve["kv_block"],
                                  config.head_dim)
    made = [line for line in lines for found in [re.search(
        r"= %s\S* ([a-z\-]+)\(" % re.escape(leaf), line)]
        if found and found.group(1) not in _HLO_CARRIES]
    assert made and all(SCOPE_KV_MERGE in line for line in made), \
        [line[:200] for line in made if SCOPE_KV_MERGE not in line]
