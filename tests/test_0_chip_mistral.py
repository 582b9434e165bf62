# mistral-7b-v0.3-d16's whole programs as the benchmark's cells run them,
# compiled for a DESCRIBED v5e (tests/test_chip_compile.py says what that
# can and cannot show): the gather step at two attend widths, the kernel
# step, the chunked extend (README.md, "Test-suite wall-time budget").

import re

import jax
import pytest

from paged_model_cases import DescribedCell, block_windows, no_copy_of


def _llama_config(sizes, max_seq, dtype):
    from aiko_services_tpu.models.llama import LlamaConfig
    return LlamaConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        ffn_dim=sizes["intermediate_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"], max_seq_len=max_seq,
        rope_theta=sizes["rope_theta"], dtype=dtype)


@pytest.fixture(scope="module")
def cell(chip):
    """24 slots, a full pool (24 x 64 blocks and the null block a leaf),
    the table at its constant 65 blocks (64 and the merge's headroom)."""
    from aiko_services_tpu.models.llama import llama_init
    cell = DescribedCell(chip, "mistral-7b-v0.3-d16.json", llama_init,
                         _llama_config)
    assert cell.table == 65
    assert cell.leaf_shapes == [[(1537, 8, 32, 128)] * 16] * 2
    return cell


VIEW = r"bf16\[24,(\d+),8,32,128\]"       # a slot-major K or V view
MERGE_IMAGES = {"2"}    # and the merge's: the two blocks four rows fall in


@pytest.mark.parametrize("width, blocks, temporaries", [
    (1024, 32, 2.3e9),      # the width of every decode_saturated round
    (2048, 64, 4.56e9),     # the cap: what it was before the ladder
], ids=["half", "cap"])
def test_step_views_follow_the_attend_width(cell, width, blocks,
                                            temporaries):
    """The gather step's views are gathered at the width's blocks and
    no wider, whatever the table holds, and the temporaries shrink with
    them; the cap's program is not widened to the table's 65."""
    compiled = cell.lower_step(False, width).compile()
    views = set(re.findall(VIEW, compiled.as_text())) - MERGE_IMAGES
    assert views == {str(blocks)}, views
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries


def test_kernel_step_builds_no_views_and_copies_no_pool(cell, monkeypatch):
    """The step a cell runs on the chip (PR 30): attention through the
    pallas kernel, lowered with mosaic (this host's backend is the CPU,
    where the kernel would pick the interpreter, whose loops copy every
    pool leaf: the test says "tpu" for it).  Sixteen kernels, one a
    layer; no slot-major view; no `copy` of a pool leaf's shape (a
    pool-shaped operand handed to the kernel by value would be one);
    temporaries a twentieth of the gather step's at the cap."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pool = cell.leaf_shapes[0][0]
    text = no_copy_of(cell.lower_step(True).compile(), pool,
                      temporaries=0.5e9)
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 16
    assert set(re.findall(VIEW, text)) == MERGE_IMAGES
    # the merge (PR 32): each of the 32 leaves is read by one gather of
    # whole blocks, two a slot, and written by one scatter of them
    reads, writes = block_windows(text, pool, "bf16")
    assert reads == ["24,2"] * 32 and len(writes) == 32
    assert len(re.findall(r" scatter\(", text)) == 32


def test_mistral_extend_writes_its_chunk_by_whole_blocks(cell):
    """`jit_extend` 512 x 1 of the cells (the gather path, as a cell
    runs it): each leaf's chunk goes back as 17 whole blocks, in place;
    the temporaries are the one slot's views at the cap and the chunk's
    activations."""
    pool = cell.leaf_shapes[0][0]
    text = no_copy_of(cell.lower_extend(512, 1).compile(), pool,
                      temporaries=0.5e9)
    reads, writes = block_windows(text, pool, "bf16")
    # (the 64-block gathers are the prefix views of the one slot)
    assert sorted(reads) == ["17"] * 32 + ["64"] * 32 and len(writes) == 32
    assert len(re.findall(r" scatter\(", text)) == 32
