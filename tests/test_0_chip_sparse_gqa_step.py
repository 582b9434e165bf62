# keye-vl-2.0-30b-a3b-ep8-d12's decode step as `long_ctx_open_loop` runs it
# (ISSUE 38), compiled ONCE for a DESCRIBED v5e (tests/test_chip_compile.py
# says what that can and cannot show) and read by four cases; the extend is
# in test_0_chip_sparse_gqa_extend.py.  Two files: each compile is a minute
# or two; four cases each: xdist hands a file of one case out last
# (README.md, "Test-suite wall-time budget").

import dataclasses
import math
import re

import jax
import pytest

from paged_model_cases import DescribedCell, no_copy_of


@pytest.fixture(scope="module")
def cell(chip):
    import sparse_gqa_decoder
    from aiko_services_tpu.models.sparse_gqa import sparse_gqa_init
    return DescribedCell(chip, "keye-vl-2.0-30b-a3b-ep8-d12.json",
                         sparse_gqa_init, sparse_gqa_decoder.model_config)


@pytest.fixture(scope="module")
def step(cell):
    """The whole 12-layer `jit_step` x 4 as the cell's decoder builds it
    on the chip (`step_kernel`, ISSUE 39): -> (compiled, its text)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = cell.lower_step(True).compile()
    return compiled, compiled.as_text()


def test_a_decoder_told_nothing_takes_the_kernel_at_a_head_of_128(
        cell, monkeypatch):
    """Who decides that the step below is the one a cell runs."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from aiko_services_tpu.models import sparse_gqa as M
    from aiko_services_tpu.serving import ContinuousDecoder
    small = dataclasses.replace(
        M.SPARSE_GQA_PRESETS["tiny"], head_dim=cell.config.head_dim,
        mrope_section=cell.config.mrope_section)
    decoder = ContinuousDecoder(
        M.sparse_gqa_init(jax.random.PRNGKey(0), small), small,
        paged_kv=True, kv_block=8, max_slots=2, max_seq=64, prefill_chunk=32,
        name="sparse-gqa-described")
    assert decoder.step_kernel and decoder._walks_live
    assert decoder._attend_widths == (64,)


def test_sparse_gqa_step_selects_exactly_and_copies_no_leaf(cell, step):
    """A layer's attention is ONE Pallas call under `aiko.attn_core` (the
    walk, the chosen positions its mask) and no K or V leaf is gathered,
    as rows or otherwise; no leaf is copied."""
    compiled, text = step
    config = cell.config
    no_copy_of(compiled, *(side[0] for side in cell.leaf_shapes))
    walks = re.findall(r"custom-call\([^\n]*tpu_custom_call[^\n]*", text)
    assert len(walks) == config.num_layers
    assert all("aiko.attn_core" in walk for walk in walks)
    assert "ApproxTopK" not in text
    # what is gathered of a K or V leaf is the merge's whole blocks; a
    # leaf seen as rows gave single rows of a head's lanes
    row = "slice_sizes={1,%d}" % config.head_dim
    assert row not in text and " gather(" in text


def test_sparse_gqa_step_sorts_for_the_router_and_the_slots_order_alone(
        cell, step):
    """A sort a layer for the router's eight, and ONE for the order in
    which the slots that decode are taken (every layer's is the same: the
    compiler keeps one); the positions are chosen without one."""
    text = step[1]
    assert len(re.findall(r" sort\(", text)) == cell.config.num_layers + 1
    assert not re.findall(r"aiko\.dsa_select/[^\n\"]*(top_k|sort)", text)
    assert "aiko.dsa_select" in text


def test_sparse_gqa_step_merges_in_place_and_fits_the_chip(cell, step):
    """The three leaves of every layer are merged in place, and the step
    fits the chip beside its pool: 2.48 GB of weights + the pool, the pool
    aliased in and out; the indexer keys of every layer gathered once a
    round are temporaries."""
    compiled, text = step
    config = cell.config
    merges = re.findall(r"fusion\([^\n]*aiko\.kv_merge/scatter", text)
    assert len(merges) == 3 * config.num_layers
    memory = compiled.memory_analysis()
    pool = sum(math.prod(side[0]) * 2 for side in cell.leaf_shapes) \
        * config.num_layers
    assert memory.alias_size_in_bytes >= pool
    assert 2.4e9 < memory.argument_size_in_bytes - pool < 2.6e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 16.4e9
