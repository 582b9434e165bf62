# granite-4.0-h-micro's whole decode step as `ssm_chat_open_loop` runs it
# (ISSUE 45: Mamba-2 slot state beside a pool whose row is a K/V head's V
# and K side by side) and its admits and extend (ISSUE 46: the chunked
# recurrence as one kernel a layer), all 40 layers, compiled for a
# DESCRIBED v5e (tests/test_chip_compile.py says what that can and cannot
# show).

import re

import jax
import pytest

from paged_model_cases import HLO_CARRIES, DescribedCell, made_whole


@pytest.fixture(scope="module")
def cell(chip):
    import ssm_hybrid_decoder
    from aiko_services_tpu.models.ssm_hybrid import ssm_hybrid_init
    return DescribedCell(chip, "granite-4.0-h-micro.json", ssm_hybrid_init,
                         ssm_hybrid_decoder.model_config)


@pytest.fixture(scope="module")
def ssm_hybrid_step(cell):
    """The whole 40-layer `jit_step` x 4 as the cell's decoder builds it on
    the chip (`step_kernel` for both reasons: the attention layers' walk of
    the pool's rows, the Mamba layers' state through ops/kda_step.py)."""
    config = cell.config
    model = config.paged_model()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        # what a decoder that is told nothing finds on the chip: the ONE
        # flag keeps one meaning (ISSUE 45, route 1)
        assert model.walks(config, False, False) == "kernel"
        assert model.step_kernel(config, False) is True
        return cell.lower_step(True).compile()


def test_ssm_hybrid_step_moves_slot_state_through_the_kernel_alone(
        ssm_hybrid_step, cell):
    """The 36 Mamba layers' recurrence is 36 custom calls under
    `aiko.ssm_state`, their state argument aliased to their result, and NO
    other computing operation makes a whole state leaf `f32[40,128,4096]`:
    no fusion over every slot's state, no copy that a failed aliasing would
    put before the kernel (it would also show as 67 MB a layer of
    temporaries: the bound below).  A live slot's state goes once in and
    once out, a slot that does not decode is not addressed."""
    from aiko_services_tpu.models import ssm_hybrid as M
    compiled, config = ssm_hybrid_step, cell.config
    made, kinds = made_whole(compiled.as_text(), "f32[%d,%d,%d]" % (
        cell.slots, config.ssm_state, config.ssm_inner))
    carried = HLO_CARRIES | {"custom-call"}
    assert set(kinds) <= carried, [
        line[:200] for line, kind in zip(made, kinds) if kind not in carried]
    kernels = [line for line, kind in zip(made, kinds)
               if kind == "custom-call"]
    recurrent = sum(kind == "mamba" for kind in config.layer_types)
    assert len(kernels) == recurrent == 36
    assert all(M.SCOPE_SSM_STATE in line and "tpu_custom_call" in line and
               "output_to_operand_aliasing" in line for line in kernels)
    memory = compiled.memory_analysis()
    # 6.38 GB of weights, 0.67 GB of pool, 3.06 GB of slot state at the
    # cell's 40 slots: 60% of the chip's 15.75 GiB before temporaries
    assert 10.05e9 < memory.argument_size_in_bytes < 10.2e9
    assert memory.temp_size_in_bytes < 0.2e9


def test_ssm_hybrid_step_walks_the_one_leaf_and_not_the_table(
        ssm_hybrid_step, cell):
    """The same program: the four attention layers attend through four
    custom calls under `aiko.attn_core`, each ONE call of the body that
    walks a slot's live blocks by hand (grid (40 slots, 1 row tile), the
    pool operand left in HBM), not the table body's (40, 1, 2, 65) over
    every entry of every slot; and no operation but the merge's in-place
    writes makes an array of the leaf's size."""
    from aiko_services_tpu.models.llama import (SCOPE_ATTN_CORE,
                                                SCOPE_KV_MERGE)
    config = cell.config
    lines = [line.strip() for line in ssm_hybrid_step.as_text().splitlines()]
    walks = [line for line in lines if "tpu_custom_call" in line and
             SCOPE_ATTN_CORE in line]
    assert len(walks) == sum(kind == "attention"
                             for kind in config.layer_types) == 4
    shape = next(filter(None, cell.leaf_shapes[0]))
    assert shape[1:] == (8, 32, 128) and len(cell.leaf_shapes) == 1
    leaf = "bf16[%s]" % ",".join(map(str, shape))
    # the walk takes the leaf twice (its K rows and, a latent pool's way,
    # its V rows are the same operand) and a result of 64 lanes a head
    assert all(line.count(leaf) >= 2 and "f32[%d,8,4,64]" % cell.slots in line
               for line in walks), walks[0][:400]
    made = [line for line in lines for found in [re.search(
        r"= %s\S* ([a-z\-]+)\(" % re.escape(leaf), line)]
        if found and found.group(1) not in HLO_CARRIES]
    assert made and all(SCOPE_KV_MERGE in line for line in made), \
        [line[:200] for line in made if SCOPE_KV_MERGE not in line]


PIECES = {"admit-512": ("admit", 512, 1), "admit-256-two-rows":
          ("admit", 256, 2), "extend-512": ("extend", 512, 1)}


@pytest.mark.parametrize("piece", sorted(PIECES))
def test_ssm_hybrid_pieces_scan_a_prompt_in_one_kernel_a_layer(cell, piece):
    """The cell's `jit_admit` (one prompt padded to the bucket of 512; two
    of 256, where the rows' x is not of the state's shape) and `jit_extend`
    (a 512-token piece) as a decoder traces them on the chip: the 36 Mamba
    layers' chunked recurrence is 36
    `ssm_chunk_scan` custom calls under `aiko.ssm_scan` (ISSUE 46), their
    state argument aliased to their result, and NO loop is left under that
    scope: XLA's form of `ssm_chunked` was a `while` of four trips a layer.
    Under the scope a row's state `f32[rows,128,4096]` is computed by those
    calls alone: no copy that a failed aliasing would put before one, no
    fusion that lays the state out for it (what gathers an extend's rows
    out of the slots' state and scatters them back is the builder's, as in
    the parent)."""
    from aiko_services_tpu import serving_paged
    from aiko_services_tpu.models import ssm_hybrid as M
    kind, tokens, width = PIECES[piece]
    config = cell.config
    builder = getattr(serving_paged, "_paged_%s_fn_for" % kind)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        assert config.paged_model().scan_kernel(config, False) is True
        builder.cache_clear()
        compiled = getattr(cell, "lower_" + kind)(tokens, width).compile()
    builder.cache_clear()
    text = compiled.as_text()
    lines = [line.strip() for line in text.splitlines()]
    scans = [line for line in lines if "tpu_custom_call" in line and
             M.SCOPE_SSM_SCAN in line]
    recurrent = sum(kind == "mamba" for kind in config.layer_types)
    assert len(scans) == recurrent == 36
    assert all("ssm_chunk_scan" in line and
               "output_to_operand_aliasing" in line for line in scans)
    assert not [line[:160] for line in lines
                if re.search(r" while\(", line) and M.SCOPE_SSM_SCAN in line]
    made, kinds = made_whole(text, "f32[%d,%d,%d]" % (
        width, config.ssm_state, config.ssm_inner))
    under = [(line, kind) for line, kind in zip(made, kinds)
             if M.SCOPE_SSM_SCAN in line]
    assert sum(kind == "custom-call" for _, kind in under) == recurrent
    assert not [line[:200] for line, kind in under
                if kind in ("copy", "fusion")]
    # weights 6.38 GB, pool 0.67, slot state 3.06: a piece's own
    # temporaries under a gigabyte
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
