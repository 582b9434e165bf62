# ops/kda_step.py (ISSUE 34): one token of the gated delta rule over the
# state of the slots that decode, the pallas kernel in the interpreter on
# the CPU at a head of 128, against models/hybrid_sparse.kda_recurrent.
# What the interpreter cannot see (tiling, VMEM, the aliasing inside the
# whole step) is tests/test_chip_compile.py's; times are the chip's.

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models.hybrid_sparse import kda_recurrent
from aiko_services_tpu.ops import kda_step

SLOTS, HEADS, D = 5, 4, 128
# float32 against float32: a 128-term sum in another order, on a state of
# spread 1 and outputs of spread 0.1.  A bfloat16 product reads 1e-2
TOLERANCE = 2e-6

ACTIVE = {"mixed": [True, False, True, True, False],
          "none": [False] * SLOTS,
          "all": [True] * SLOTS,
          "last": [False] * (SLOTS - 1) + [True]}


def inputs(seed, slots=SLOTS, heads=HEADS):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(z):
        return z / jnp.linalg.norm(z, axis=-1, keepdims=True)

    shape = (slots, heads, D)
    return (unit(jax.random.normal(keys[0], shape)) * D ** -0.5,
            unit(jax.random.normal(keys[1], shape)),
            jax.random.normal(keys[2], shape),
            -5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], shape)),
            jax.nn.sigmoid(jax.random.normal(keys[4], shape[:2])),
            jax.random.normal(keys[5], shape + (D,)))


def oracle(q, k, v, g, beta, state, active):
    """kda_recurrent as the decode step calls it: g and beta zeroed
    where the slot decodes nothing."""
    return kda_recurrent(q, k, v, g * active[:, None, None],
                         beta * active[:, None], state)


@pytest.fixture(params=["one tile", "two tiles"])
def tiles(request, monkeypatch):
    """Heads a tile: all four of a slot, or two (the ring then passes a
    slot in two pieces, and twice as many items as its depth)."""
    if request.param == "two tiles":
        monkeypatch.setattr(kda_step, "_TILE_BYTES", 2 * 4 * D * D)
    return HEADS // kda_step._head_tile(HEADS, D)


@pytest.mark.parametrize("case", sorted(ACTIVE))
def test_live_slots_follow_the_recurrence_and_the_others_keep_their_bits(
        case, tiles):
    q, k, v, g, beta, state = inputs(3)
    active = jnp.asarray(ACTIVE[case])
    out, new = kda_step.kda_live_step(q, k, v, g, beta, state, active)
    want_out, want = oracle(q, k, v, g, beta, state, active)
    live = np.asarray(active)
    assert out.shape == q.shape and new.shape == state.shape
    assert out.dtype == new.dtype == jnp.float32
    # (with no slot live there is nothing to compare, and nothing moved)
    if live.any():
        assert np.abs(np.asarray(out - want_out)[live]).max() < TOLERANCE
        assert np.abs(np.asarray(new - want)[live]).max() < TOLERANCE
        assert not np.array_equal(np.asarray(new)[live],
                                  np.asarray(state)[live])
    # bit for bit, and zeros: not what the oracle reads there (q^T S)
    assert np.array_equal(np.asarray(new)[~live].view(np.uint32),
                          np.asarray(state)[~live].view(np.uint32))
    assert not np.asarray(out)[~live].any()


def test_what_a_slot_that_decodes_nothing_holds_is_never_read(tiles):
    """NaN in every vector of the idle slots and in their state: the live
    slots' results are what they are without it, the idle state is still
    the same bits."""
    q, k, v, g, beta, state = inputs(4)
    active = jnp.asarray(ACTIVE["mixed"])
    idle = ~active
    clean = kda_step.kda_live_step(q, k, v, g, beta, state, active)
    poison = [jnp.where(idle.reshape((-1,) + (1,) * (z.ndim - 1)), jnp.nan, z)
              for z in (q, k, v, g, beta, state)]
    out, new = kda_step.kda_live_step(*poison, active)
    live = np.asarray(active)
    assert np.array_equal(np.asarray(out)[live], np.asarray(clean[0])[live])
    assert np.array_equal(np.asarray(new)[live], np.asarray(clean[1])[live])
    assert np.isnan(np.asarray(new)[~live]).all()
    assert not np.asarray(out)[~live].any()


def test_four_steps_in_a_loop_with_the_state_donated_are_four_recurrences():
    """As the decode step runs it: inside a `lax.while_loop` under `jit`,
    the state carried and donated, the set of live slots changing from
    step to step (one slot stops, as a budget that runs out)."""
    q, k, v, g, beta, state = inputs(5)
    steps = 4
    # slot 1 never decodes, slot 3 stops after two steps
    lives = jnp.asarray([[True, False, True, True, True]] * 2 +
                        [[True, False, True, False, True]] * 2)

    def rolled(z, index):                 # other vectors every step
        return jnp.roll(z, index, axis=1)

    def run(state):
        def body(loop):
            index, state, outs = loop
            out, state = kda_step.kda_live_step(
                rolled(q, index), rolled(k, index), rolled(v, index),
                rolled(g, index), rolled(beta, index), state, lives[index])
            return index + 1, state, outs.at[index].set(out)

        return jax.lax.while_loop(
            lambda loop: loop[0] < steps, body,
            (jnp.int32(0), state, jnp.zeros((steps,) + q.shape)))[1:]

    want, want_outs = state, []
    for index in range(steps):
        out, want = oracle(rolled(q, index), rolled(k, index),
                           rolled(v, index), rolled(g, index),
                           rolled(beta, index), want, lives[index])
        want_outs.append(np.where(np.asarray(lives[index])[:, None, None],
                                  np.asarray(out), 0.0))
    kept = np.asarray(state[1])
    new, outs = jax.jit(run, donate_argnums=(0,))(state + 0.0)
    assert np.abs(np.asarray(new - want)).max() < 4 * TOLERANCE
    assert np.abs(np.asarray(outs) - np.stack(want_outs)).max() < \
        4 * TOLERANCE
    assert np.array_equal(np.asarray(new[1]), kept)


def test_a_wide_slot_passes_through_the_ring_tile_after_tile():
    """48 heads are two tiles of 24 by the rule alone (the rows of three
    vectors of a tile's heads share a block of 128), three slots live: six
    items through a ring of three."""
    q, k, v, g, beta, state = inputs(6, slots=4, heads=48)
    assert kda_step._head_tile(48, D) == 24
    active = jnp.asarray([True, True, False, True])
    out, new = kda_step.kda_live_step(q, k, v, g, beta, state, active)
    want_out, want = oracle(q, k, v, g, beta, state, active)
    live = np.asarray(active)
    assert np.abs(np.asarray(out - want_out)[live]).max() < TOLERANCE
    assert np.abs(np.asarray(new - want)[live]).max() < TOLERANCE
    assert np.array_equal(np.asarray(new)[2], np.asarray(state)[2])


@pytest.mark.parametrize("heads, head_dim, tile", [
    (64, 128, 32),      # the published widths: 2 MB of state a tile
    (2, 16, 2),         # the `tiny` preset (the interpreter only)
    (48, 128, 24),
    (64, 256, 8),       # a head of 256: 256 KB a head
    (7, 128, 7)])
def test_heads_a_tile(heads, head_dim, tile):
    assert kda_step._head_tile(heads, head_dim) == tile
    assert 3 * tile <= 128 and heads % tile == 0


@pytest.mark.parametrize("heads, head_dim, interpret, takes", [
    (64, 128, False, True), (64, 256, False, True), (48, 128, False, True),
    (2, 16, False, False), (64, 64, False, False),
    (7, 128, False, False),        # a tile of 7 heads: no whole sublanes
    (2, 16, True, True)])
def test_the_kernel_wants_whole_lanes_and_whole_sublanes(
        heads, head_dim, interpret, takes):
    assert kda_step.moves_live_states(heads, head_dim, interpret) is takes
