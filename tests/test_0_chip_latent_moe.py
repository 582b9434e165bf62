# ax-k1-ep16-d6's whole programs as `doc_qa_open_loop` runs them (ISSUE
# 31), compiled for a DESCRIBED v5e (tests/test_chip_compile.py says what
# that can and cannot show): 32 slots x 8,192 positions of one [blocks, 1,
# 32, 640] leaf a layer, 6 layers at the published widths, 12 of 192
# experts held.  The row's own kernel cases are in test_chip_compile.py.

import re

import jax
import pytest

from paged_model_cases import DescribedCell, block_windows, no_copy_of


@pytest.fixture(scope="module")
def cell(chip):
    import latent_moe_decoder
    from aiko_services_tpu.models.latent_moe import latent_moe_init
    cell = DescribedCell(chip, "ax-k1-ep16-d6.json", latent_moe_init,
                         latent_moe_decoder.model_config)
    assert cell.leaf_shapes == [[(8193, 1, 32, 640)] * 6]
    assert cell.v_pools == [] and cell.state == []
    return cell


def test_latent_step_walks_the_pool_and_copies_none_of_it(cell, monkeypatch):
    """The whole 6-layer `jit_step` x 4 of the cell: six walks, one a
    layer, no pool-shaped copy, temporaries under 0.3 GB (the experts
    that a token reached run inside conditionals; nothing is expanded)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config, leaf = cell.config, cell.leaf_shapes[0][0]
    compiled = cell.lower_step(True).compile()
    text = no_copy_of(compiled, leaf, temporaries=0.3e9)
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) == config.num_layers
    # the merge keeps its ROWS here (PR 32): four windows of one head a
    # slot against two blocks read and two written, 160 KiB for 5
    assert block_windows(text, leaf, "bf16") == ([], [])
    assert len(re.findall(r" scatter\(", text)) == config.num_layers
    memory = compiled.memory_analysis()
    # 8.33 GB of weights + 2.01 GB of pool, the pool aliased in and out
    assert 10.2e9 < memory.argument_size_in_bytes < 10.5e9
    assert memory.alias_size_in_bytes > 2.0e9


@pytest.mark.parametrize("program", ["admit-512x1", "admit-256x2",
                                     "extend-512x1"])
def test_latent_prefill_programs_compile_and_copy_no_pool(cell, program):
    """Admit and extend go the EXPANDED way: no kernel, the prefix read
    piece by piece through the table, the chunk's rows scattered in
    place; the temporaries (a piece's per-head keys and values, the
    expert tiles) stay under 0.3 GB."""
    config, leaf = cell.config, cell.leaf_shapes[0][0]
    kind, _, size = program.partition("-")
    tokens, width = (int(n) for n in size.split("x"))
    lowered = cell.lower_admit(tokens, width) if kind == "admit" \
        else cell.lower_extend(tokens, width)
    text = no_copy_of(lowered.compile(), leaf, temporaries=0.3e9)
    assert "tpu_custom_call" not in text
    if kind == "extend":
        # the chunk goes back as 17 whole blocks a leaf (PR 32); the
        # 16-block gathers are the prefix, piece by piece
        reads, writes = block_windows(text, leaf, "bf16")
        assert reads.count("17") == config.num_layers
        assert len(writes) == config.num_layers
        assert len(re.findall(r" scatter\(", text)) == config.num_layers
