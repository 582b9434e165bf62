# olmo-hybrid-7b-d16's whole programs as `gdn_decode_saturated` runs them
# (ISSUE 40: slot state beside a pool the shared kernel walks), compiled
# for a DESCRIBED v5e (tests/test_chip_compile.py says what that can and
# cannot show): the 16-layer step, once, and the admit of a prompt.

import re

import jax
import pytest

from aiko_services_tpu import serving_paged
from paged_model_cases import HLO_CARRIES, DescribedCell, made_whole


@pytest.fixture(scope="module")
def cell(chip):
    import gated_delta_decoder
    from aiko_services_tpu.models.gated_delta import gated_delta_init
    return DescribedCell(chip, "olmo-hybrid-7b-d16.json", gated_delta_init,
                         gated_delta_decoder.model_config)


@pytest.fixture(scope="module")
def gated_delta_step(cell):
    """The whole 16-layer `jit_step` x 6 as the cell's decoder builds it
    on the chip (`step_kernel` for both reasons: the full layers' walk of
    the pool, the recurrent layers' state through ops/kda_step.py)."""
    config = cell.config
    model = config.paged_model()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        # what a decoder that is told nothing finds on the chip
        assert model.walks(config, False, False) == "kernel"
        assert model.step_kernel(config, False) is True
        return cell.lower_step(True).compile()


def test_gated_delta_step_moves_slot_state_through_the_kernel_alone(
        gated_delta_step, cell):
    """The twelve recurrent layers' recurrence is twelve custom calls under
    `aiko.gdn_state`, their state argument aliased to their result, and NO
    other computing operation makes a whole state leaf `f32[64,96,5760]`:
    no fusion over every slot's state, no copy that a failed aliasing would
    put before the kernel (it would also show as 141 MB a layer of
    temporaries: the bound below).  A live slot's state goes once in and
    once out, a slot that does not decode is not addressed."""
    from aiko_services_tpu.models import gated_delta as M
    compiled, config = gated_delta_step, cell.config
    made, kinds = made_whole(compiled.as_text(), "f32[%d,%d,%d]" % (
        cell.slots, config.key_dim, config.gdn_heads * config.value_dim))
    carried = HLO_CARRIES | {"custom-call"}
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


def test_gated_delta_admit_scans_a_prompt_in_one_kernel_a_layer(cell):
    """The cell's `jit_admit` (one prompt padded to the bucket of 512) as a
    decoder traces it on the chip: the twelve recurrent layers' chunked
    delta rule is twelve `gdn_chunk_scan` custom calls under
    `aiko.gdn_scan` (ISSUE 41), their state argument aliased to their
    result, and NO loop is left under that scope: XLA's form of
    models/delta_rule.chunked was a `while` of eight trips a layer."""
    from aiko_services_tpu.models import gated_delta as M
    config = cell.config
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        assert config.paged_model().scan_kernel(config, False) is True
        serving_paged._paged_admit_fn_for.cache_clear()
        compiled = cell.lower_admit(cell.serve["prefill_buckets"][-1],
                                    1).compile()
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
        gated_delta_step, cell):
    """The same program: the four full layers attend through four custom
    calls under `aiko.attn_core` (the shared walk, a group of 1) and no
    operation but the merge's in-place writes makes an array of a pool
    leaf's size; the convolution's tails are rewritten by fusions, small
    (64 x 3 x 11,520) as they are."""
    from aiko_services_tpu.models.llama import (SCOPE_ATTN_CORE,
                                                SCOPE_KV_MERGE)
    config = cell.config
    lines = [line.strip() for line in gated_delta_step.as_text().splitlines()]
    walks = [line for line in lines if "tpu_custom_call" in line and
             SCOPE_ATTN_CORE in line]
    assert len(walks) == sum(kind == "full" for kind in config.layer_types) \
        == 4
    leaf = "bf16[%s]" % ",".join(map(str, next(filter(
        None, cell.leaf_shapes[0]))))
    made = [line for line in lines for found in [re.search(
        r"= %s\S* ([a-z\-]+)\(" % re.escape(leaf), line)]
        if found and found.group(1) not in HLO_CARRIES]
    assert made and all(SCOPE_KV_MERGE in line for line in made), \
        [line[:200] for line in made if SCOPE_KV_MERGE not in line]
