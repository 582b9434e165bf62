# glm-5.3-flash-ep8-d5's decode step as `long_doc_open_loop` runs it,
# compiled ONCE for a DESCRIBED v5e (tests/test_chip_compile.py says what
# that can and cannot show): the whole 5-layer `jit_step` x 4 as the cell's
# decoder builds it on the chip (`step_kernel`: the KDA layers' recurrence
# through ops/kda_step.py).

import math
import re

import jax
import pytest

from paged_model_cases import HLO_CARRIES, DescribedCell, made_whole


@pytest.fixture(scope="module")
def hybrid_step(chip):
    """-> (compiled, the cell)."""
    import hybrid_sparse_decoder
    from aiko_services_tpu.models.hybrid_sparse import hybrid_sparse_init
    cell = DescribedCell(chip, "glm-5.3-flash-ep8-d5.json",
                         hybrid_sparse_init,
                         hybrid_sparse_decoder.model_config)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return cell.lower_step(True).compile(), cell


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
    compiled, cell = hybrid_step
    config = cell.config
    rows = cell.blocks * cell.serve["kv_block"]
    lines = [line.strip() for line in compiled.as_text().splitlines()]
    made = [line for line in lines for found in [re.search(
        r"= bf16\[([\d,]+)\]\S* ([a-z\-]+)\(", line)]
        if found and found.group(2) not in HLO_CARRIES and
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
    compiled, cell = hybrid_step
    config = cell.config
    made, kinds = made_whole(compiled.as_text(), "f32[%d,%d,%d,%d]" % (
        cell.slots, config.kda_heads, config.kda_head_dim,
        config.kda_head_dim))
    carried = HLO_CARRIES | {"custom-call"}
    assert set(kinds) <= carried, [
        line[:200] for line, kind in zip(made, kinds) if kind not in carried]
    kernels = [line for line, kind in zip(made, kinds)
               if kind == "custom-call"]
    kda_layers = sum(kind == "kda" for kind in config.layer_types)
    assert len(kernels) == kda_layers == 4
    assert all(M.SCOPE_KDA_CORE in line and "tpu_custom_call" in line and
               "output_to_operand_aliasing" in line for line in kernels)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9
