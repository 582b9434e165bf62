# keye-vl-2.0-30b-a3b-ep8-d12's 512-token extend as `long_ctx_open_loop`
# runs it (ISSUE 38), compiled ONCE for a DESCRIBED v5e
# (tests/test_chip_compile.py says what that can and cannot show) and read
# by four cases.  The step, and why two files of four cases, is in
# test_0_chip_sparse_gqa_step.py.

import re

import pytest

from paged_model_cases import no_copy_of
from test_0_chip_sparse_gqa_step import cell  # noqa: F401 (a fixture)


@pytest.fixture(scope="module")
def extend(cell):  # noqa: F811
    """`jit_extend` of a 512-token chunk against a prefix of up to 32k:
    -> (compiled, its text)."""
    compiled = cell.lower_extend(cell.serve["prefill_chunk"], 1,
                                 context=(cell.slots, 1),
                                 table=cell.table).compile()
    return compiled, compiled.as_text()


def test_sparse_gqa_extend_chooses_without_a_sort_and_copies_no_leaf(
        cell, extend):  # noqa: F811
    """Every query's 2,048 positions come from a threshold found bit by
    bit: the only sort is the router's; and no leaf is copied."""
    compiled, text = extend
    no_copy_of(compiled, *(side[0] for side in cell.leaf_shapes))
    assert len(re.findall(r" sort\(", text)) == cell.config.num_layers


def test_sparse_gqa_extend_selects_and_indexes_under_their_scopes(extend):
    assert "aiko.dsa_select" in extend[1] and "aiko.dsa_index" in extend[1]


def test_sparse_gqa_extend_scatters_its_chunks_rows_in_place(
        cell, extend):  # noqa: F811
    """The chunk's rows of the three leaves of every layer reach the pool
    under `aiko.kv_merge`."""
    merges = re.findall(r"aiko\.kv_merge/", extend[1])
    assert len(merges) >= 3 * cell.config.num_layers


def test_sparse_gqa_extend_reads_its_prefix_piece_by_piece(extend):
    """The prefix of up to 32k is read piece by piece: the temporaries
    stay under 0.7 GB."""
    assert extend[0].memory_analysis().temp_size_in_bytes < 0.7e9
