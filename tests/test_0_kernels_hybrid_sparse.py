# The hybrid decoder (tests/test_hybrid_sparse_layers.py has the suite's
# sizes and reference), its step's KERNELS and the slots' STATE in it: the
# sparse layer's step over the slots that decode against the body over
# every slot, whole `jit_step`s over random pools and slot state (ISSUE
# 42), and which form of the KDA recurrence a decoder takes and says it
# takes (ISSUE 34).  Nothing here serves a request.

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import aiko_services_tpu.serving as serving
from aiko_services_tpu.serving import ContinuousDecoder
from aiko_services_tpu.serving_paged import BlockPool, SlotState
from test_hybrid_sparse_layers import (CASES, LOGIT_TOLERANCE, SEED, SIZES,
                                       M, W, model_config)

# -- the step computes for the slots that decode (ISSUE 42) ----------------------

WINDOW = M._STEP_WINDOW
ROUND_SLOTS, ROUND_STEPS, ROUND_BLOCK = WINDOW + 4, 4, 8


def _round_inputs(config):
    """What `jit_step` takes for `ROUND_SLOTS` slots in the middle of
    their answers, as numpy: RANDOM pools and slot state (both forms of
    the layer read the same, whatever it is), lengths of 17 to 50 (every
    step chooses groups), each slot's table its own blocks."""
    rng = np.random.default_rng(42)
    width = -(-(64 + ROUND_STEPS) // ROUND_BLOCK)
    pool = BlockPool(config, ROUND_BLOCK, False,
                     initial_blocks=ROUND_SLOTS * width, name="windows")

    def drawn(leaf):
        return None if leaf is None else rng.standard_normal(
            leaf.shape).astype(np.float32)

    tables = 1 + rng.permutation(ROUND_SLOTS * width).reshape(
        ROUND_SLOTS, width).astype(np.int32)
    state = [tuple(0.3 * drawn(leaf) for leaf in layer)
             for layer in SlotState(config, ROUND_SLOTS).arrays]
    return dict(
        tokens=rng.integers(1, 256, ROUND_SLOTS).astype(np.int32),
        lengths=rng.integers(17, 50, ROUND_SLOTS).astype(np.int32),
        k_pools=[drawn(leaf) for leaf in pool.k_pools],
        v_pools=[drawn(leaf) for leaf in pool.v_pools],
        tables=tables, state=state)


@pytest.fixture(scope="module")
def round_programs():
    """`jit_step` of the tiny model twice: as it is, and with the sparse
    layer's body run ONCE over every slot (`_dsa_window` at the full
    width between the projections, what `_dsa_step` was before it took
    windows): -> (inputs,
    run(program name, active, budgets) -> the program's results)."""
    from aiko_services_tpu import serving_paged
    config, params = model_config(), CASES.params
    inputs = _round_inputs(config)

    def every_slot(layer, config, x, cos, sin, tables, leaves, sides, left,
                   entry_lengths, lengths, step_index, entry_active, active):
        o_lat, sides, left, counted = M._dsa_window(
            config, M._dsa_project(layer, config, x, cos, sin, lengths),
            tables, leaves, sides, left, entry_lengths, lengths, step_index,
            active)
        return (M.absorb_output(layer["attn"], config, o_lat, 1), sides,
                left, jnp.concatenate([counted, jnp.zeros((2,), jnp.int32)]))

    def arguments(active, budgets):
        return (params, inputs["tokens"], inputs["lengths"], active,
                budgets, inputs["k_pools"], inputs["v_pools"],
                inputs["tables"], inputs["state"])

    programs = {}
    first = arguments(np.ones(ROUND_SLOTS, bool),
                      np.ones(ROUND_SLOTS, np.int32))
    for name in ("windows", "every-slot"):
        with pytest.MonkeyPatch.context() as patch:
            if name == "every-slot":
                patch.setattr(M, "_dsa_step", every_slot)
            programs[name] = serving_paged._build_paged_step(
                config, False).lower(
                    *first, num_steps=ROUND_STEPS, eos=-1,
                    t_cap=128).compile()

    def run(name, active, budgets):
        emitted, emitted_active, _, lengths, k_pools, v_pools, counts, \
            state = programs[name](*jax.tree.map(
                jnp.asarray, arguments(active, budgets)))
        return dict(
            emitted=np.asarray(emitted), active=np.asarray(emitted_active),
            lengths=np.asarray(lengths), pools=jax.tree.map(
                np.asarray, [k_pools, v_pools]),
            counts=dict(zip(M.HYBRID_COUNTERS, np.asarray(counts).tolist())),
            state=jax.tree.map(np.asarray, state))

    return inputs, run


@pytest.mark.parametrize("live, slot_zero", [
    (0, False), (1, True), (1, False), (WINDOW - 1, False), (WINDOW, True),
    (WINDOW + 1, True), (WINDOW + 1, False), (ROUND_SLOTS, True)],
    ids=str)
def test_the_sparse_step_computes_for_the_slots_live_at_entry(
        round_programs, live, slot_zero):
    """A round of four steps over 12 slots of which `live` decode, slot 0
    among them or not, one of them out of budget after two steps: the
    step that takes the live slots in windows of 8 serves what the body
    over every slot serves (tokens exactly, the pools and the state of
    the live slots to the tolerance), leaves what a slot that was not
    live holds as it was to the last bit, counts the same positions and
    rows, and computes for whole windows of the live slots alone."""
    inputs, run = round_programs
    rng = np.random.default_rng(100 * live + slot_zero)
    others = 1 + rng.permutation(ROUND_SLOTS - 1)
    chosen = ([0] if slot_zero else []) + others.tolist()
    active = np.zeros(ROUND_SLOTS, bool)
    active[chosen[:live]] = True
    budgets = np.where(active, ROUND_STEPS, 0).astype(np.int32)
    if live:
        budgets[chosen[live - 1]] = 2
    ours, theirs = run("windows", active, budgets), \
        run("every-slot", active, budgets)
    assert (ours["emitted"] == theirs["emitted"]).all()
    assert (ours["active"] == theirs["active"]).all()
    assert ours["active"].sum() == max(0, 4 * live - 2)
    for one, other in zip(jax.tree.leaves(ours["pools"]),
                          jax.tree.leaves(theirs["pools"])):
        assert np.abs(one - other).max() < LOGIT_TOLERANCE
    for one, other, before in zip(*map(jax.tree.leaves, (
            ours["state"], theirs["state"], inputs["state"]))):
        assert np.abs(one - other)[active].max(initial=0) < LOGIT_TOLERANCE
        assert (one[~active] == before[~active]).all()
    for name in M._DSA_COUNTERS + M._KDA_COUNTERS:
        assert ours["counts"][name] == theirs["counts"][name], name
    assert (ours["counts"]["dsa_positions_live"] > 0) == (live > 0)
    steps = int(ours["active"].any(axis=1).sum())
    assert ours["counts"]["dsa_slots_computed"] == \
        -(-live // WINDOW) * WINDOW * steps
    assert ours["counts"]["dsa_slots_decoding"] == ours["active"].sum()


# -- the step's recurrence: which form, and what it counts (ISSUE 34) ------------

# a head of whole lanes, a tile of whole sublanes (ops/kda_step.py)
WIDE = SIZES | {"linear_attn_config": SIZES["linear_attn_config"] |
                {"head_dim": 128, "num_heads": 8}}


@functools.cache
def _wide_weights():
    return W.decoder_weights(W.key_for(SEED), WIDE, jnp.float32)


def _kda_attend(kernel, sizes, backend, monkeypatch):
    """The jaxpr of one KDA layer's token mixing in the decode step, as
    `_step_attention(kernel)` traces it on `backend`."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    config = model_config(sizes)
    layer = W.decoder_layer(W.key_for(SEED), 1, sizes, jnp.float32,
                            ("kda", "sparse"))
    state = tuple(jnp.zeros((3,) + shape, dtype)
                  for shape, dtype in config.slot_state[1])
    attend = M._step_attention(kernel)
    lengths = jnp.zeros((3,), jnp.int32)
    return jax.make_jaxpr(lambda x, state, active: attend(
        None, layer, config, x, None, None, [], None, [], lengths, lengths,
        0, active, state, active))(
            jnp.ones((3, 1, 64)), state, jnp.asarray([True, False, True]))


@pytest.mark.parametrize("kernel, sizes, backend, takes", [
    (True, WIDE, "tpu", True),        # the cell's case, at a head of 128
    (False, WIDE, "tpu", False),      # the decoder said no: sharded, asked
    (True, SIZES, "tpu", False),      # the `tiny` head of 16 on a chip
    (False, SIZES, "cpu", False),     # every CPU test
    (True, SIZES, "cpu", True),       # asked for off the chip: interpreter
], ids=["lanes-on-tpu", "not-chosen", "head-16-on-tpu", "cpu", "interpreter"])
def test_the_step_takes_the_kernel_where_the_head_is_whole_lanes(
        monkeypatch, kernel, sizes, backend, takes):
    text = str(_kda_attend(kernel, sizes, backend, monkeypatch))
    assert ("pallas_call" in text) is takes
    # the plain recurrence's two products over the state, or neither
    assert (text.count("dot_general") >= 2) or takes


@pytest.mark.parametrize("impl, sizes, backend, kernel", [
    (None, SIZES, "cpu", False), (None, SIZES, "tpu", False),
    (None, WIDE, "cpu", False), (None, WIDE, "tpu", True),
    ("two_pass", WIDE, "tpu", False), ("paged_kernel", SIZES, "cpu", True),
], ids=["tiny-cpu", "tiny-tpu", "lanes-cpu", "lanes-tpu", "lanes-tpu-gather",
        "tiny-cpu-asked"])
def test_the_decoder_chooses_and_says_which_form_of_the_recurrence(
        monkeypatch, impl, sizes, backend, kernel):
    """Told nothing, a hybrid decoder takes the kernel on a TPU at a head
    of whole lanes (its weights and state on one device) and
    `kda_recurrent` everywhere else; it says which on its logger."""
    import logging
    monkeypatch.setattr(serving, "ATTENTION_IMPL", impl)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    heard = []

    class Heard(logging.Handler):
        def emit(self, record):
            heard.append(record.getMessage())

    name = "form-%s-%d-%s" % (impl, len(str(sizes)), backend)
    logger = logging.getLogger(f"serving.{name}")
    handler = Heard(logging.INFO)
    logger.addHandler(handler)
    try:
        decoder = ContinuousDecoder(
            CASES.params if sizes is SIZES else _wide_weights(),
            model_config(sizes), paged_kv=True, kv_block=8, max_slots=2,
            max_seq=128, prefill_buckets=(8,), prefill_chunk=32, name=name)
    finally:
        logger.removeHandler(handler)
    assert decoder._walks_live and decoder.step_kernel is kernel
    said = [m for m in heard if "recurrence over slot state" in m]
    assert len(said) == 1, heard
    assert ("pallas kernel" in said[0]) is kernel
    assert ("every slot" in said[0]) is not kernel
