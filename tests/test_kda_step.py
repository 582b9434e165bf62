# ops/kda_step.py (ISSUE 34): one token of the gated delta rule over the
# state of the slots that decode, the pallas kernel in the interpreter on
# the CPU at a head of 128, against models/hybrid_sparse.kda_recurrent
# (models/delta_rule.recurrent); since ISSUE 40 at unequal head sides too,
# and with ONE gate a head (the sibling body, the heads side by side).
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


def inputs(seed, slots=SLOTS, heads=HEADS, dk=D, dv=D, by_head=False):
    """q, k, v, g, beta and the state as the kernel takes it: a gate a
    channel and S [slots, heads, dk, dv], or (`by_head`) a gate a head,
    beta up to 2 and S [slots, dk, heads x dv]."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(z):
        return z / jnp.linalg.norm(z, axis=-1, keepdims=True)

    shape = (slots, heads, dk)
    state = jax.random.normal(keys[5], shape + (dv,))
    return (unit(jax.random.normal(keys[0], shape)) * dk ** -0.5,
            unit(jax.random.normal(keys[1], shape)),
            jax.random.normal(keys[2], (slots, heads, dv)),
            -5.0 * jax.nn.sigmoid(jax.random.normal(
                keys[3], shape[:2] if by_head else shape)),
            jax.nn.sigmoid(jax.random.normal(keys[4], shape[:2])) * (
                2.0 if by_head else 1.0),
            kda_step.heads_side_by_side(state) if by_head else state)


def oracle(q, k, v, g, beta, state, active):
    """kda_recurrent as the decode step calls it: g and beta zeroed
    where the slot decodes nothing; a state that came with its heads side
    by side goes back so."""
    by_head = g.ndim == 2
    out, new = kda_recurrent(
        q, k, v, g * active.reshape((-1,) + (1,) * (g.ndim - 1)),
        beta * active[:, None],
        kda_step.heads_apart(state, q.shape[1]) if by_head else state)
    return out, kda_step.heads_side_by_side(new) if by_head else new


# (heads, key side, value side, one gate a head): the published square
# head with a gate a channel in one tile and in two, then ISSUE 40's: 6
# heads (no multiple of 8) of [8, 16], both grains of gate
SHAPES = {"a channel, 4 heads of 128, one tile": (HEADS, D, D, False),
          "a channel, 4 heads of 128, two tiles": (HEADS, D, D, False),
          "a channel, 6 heads of [8, 16]": (6, 8, 16, False),
          "a head, 6 heads of [8, 16]": (6, 8, 16, True)}


@pytest.fixture(params=sorted(SHAPES))
def tiles(request, monkeypatch):
    """The geometry of a case, as `inputs`' keywords.  Heads a tile (a
    gate a channel): all of a slot, or two of four (the ring then passes
    a slot in two pieces, and twice as many items as its depth); a gate a
    head passes a slot's whole state as one item."""
    heads, dk, dv, by_head = SHAPES[request.param]
    if request.param.endswith("two tiles"):
        monkeypatch.setattr(kda_step, "_TILE_BYTES", 2 * 4 * D * D)
        assert HEADS // kda_step._head_tile(HEADS, D) == 2
    return {"heads": heads, "dk": dk, "dv": dv, "by_head": by_head}


@pytest.mark.parametrize("case", sorted(ACTIVE))
def test_live_slots_follow_the_recurrence_and_the_others_keep_their_bits(
        case, tiles):
    q, k, v, g, beta, state = inputs(3, **tiles)
    active = jnp.asarray(ACTIVE[case])
    out, new = kda_step.kda_live_step(q, k, v, g, beta, state, active)
    want_out, want = oracle(q, k, v, g, beta, state, active)
    live = np.asarray(active)
    assert out.shape == v.shape and new.shape == state.shape
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
    q, k, v, g, beta, state = inputs(4, **tiles)
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


@pytest.mark.parametrize("shape", [
    name for name in sorted(SHAPES) if "two tiles" not in name])
def test_four_steps_in_a_loop_with_the_state_donated_are_four_recurrences(
        shape):
    """As the decode step runs it: inside a `lax.while_loop` under `jit`,
    the state carried and donated, the set of live slots changing from
    step to step (one slot stops, as a budget that runs out)."""
    heads, dk, dv, by_head = SHAPES[shape]
    q, k, v, g, beta, state = inputs(5, heads=heads, dk=dk, dv=dv,
                                     by_head=by_head)
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
            (jnp.int32(0), state, jnp.zeros((steps,) + v.shape)))[1:]

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


@pytest.mark.parametrize("heads, key_dim, value_dim, interpret, takes", [
    (30, 96, 192, False, True),     # the published Gated DeltaNet widths
    (64, 128, 128, False, True),    # square heads, one gate a head
    (30, 96, 200, False, False),    # 6,000 lanes a slot: no whole vectors
    (30, 100, 192, False, False),   # a key side of no whole sublanes
    (96, 96, 192, False, False),    # k and q of 96 heads pass a vector's halves
    (64, 256, 256, False, False),   # 16 MB a slot: no item of the ring
    (6, 8, 16, False, False),       # the `tiny` preset (the interpreter only)
    (6, 8, 16, True, True)])
def test_one_gate_a_head_wants_whole_vectors_a_slot_and_a_group(
        heads, key_dim, value_dim, interpret, takes):
    assert kda_step.moves_live_states(
        heads, key_dim, interpret, value_dim=value_dim, by_head=True) is takes


@pytest.mark.parametrize("heads, value_dim, group", [
    (30, 192, 2),       # two heads of 192 are three vectors of 128
    (64, 128, 1), (6, 16, 6), (8, 16, 8), (30, 96, 30)])
def test_heads_a_group(heads, value_dim, group):
    assert kda_step._head_group(heads, value_dim) == group


@pytest.mark.parametrize("heads, key_dim, value_dim, takes", [
    (64, 128, 256, True), (64, 256, 128, True), (64, 128, 192, False),
    (64, 96, 128, False)])
def test_a_gate_a_channel_takes_unequal_sides_of_whole_lanes(
        heads, key_dim, value_dim, takes):
    assert kda_step.moves_live_states(heads, key_dim, False,
                                      value_dim=value_dim) is takes


# -- the plain decayed rule (ISSUE 45: Mamba-2): S <- exp(g) S + k v^T, o = S^T q,
# k and q ONE vector for every head of a slot, a static argument of the body
# of a gate a head ------------------------------------------------------------------

PLAIN = {"6 heads of [16, 8]": (6, 16, 8), "4 heads of [128, 64]": (4, 128, 64)}


def plain_inputs(seed, heads, dk, dv, slots=SLOTS):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(keys[0], (slots, dk)),
            jax.random.normal(keys[1], (slots, dk)),
            jax.random.normal(keys[2], (slots, heads, dv)),
            -5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], (slots, heads))),
            jax.random.normal(keys[4], (slots, dk, heads * dv)))


def plain_oracle(q, k, v, g, state, active):
    """The four lines: decay a head over its lanes, the rank-one write, the
    read; a slot that decodes nothing keeps its state and reads zeros."""
    slots, heads, dv = v.shape
    decay = jnp.repeat(jnp.exp(g), dv, axis=-1)[:, None, :]
    new = state * decay + k[:, :, None] * v.reshape(slots, 1, -1)
    out = jnp.einsum("sd,sdl->sl", q, new,
                     precision=jax.lax.Precision.HIGHEST)
    live = active[:, None, None]
    return (jnp.where(live, out.reshape(v.shape), 0.0),
            jnp.where(live, new, state))


@pytest.mark.parametrize("shape", sorted(PLAIN))
@pytest.mark.parametrize("case", sorted(ACTIVE))
def test_the_plain_rule_moves_the_live_slots_and_no_other(case, shape):
    q, k, v, g, state = plain_inputs(7, *PLAIN[shape])
    active = jnp.asarray(ACTIVE[case])
    out, new = kda_step.kda_live_step(q, k, v, g, None, state, active)
    want_out, want = plain_oracle(q, k, v, g, state, active)
    live = np.asarray(active)
    assert out.shape == v.shape and new.shape == state.shape
    # a 16- or 128-term sum in another order: a few float32 ulps of outputs
    # that spread by the square root of their terms (11 at 128)
    assert np.abs(np.asarray(out - want_out)).max() < \
        4e-6 * max(1.0, float(np.abs(np.asarray(want_out)).max()))
    assert np.abs(np.asarray(new - want)).max() < 2e-6
    if live.any():
        assert not np.array_equal(np.asarray(new)[live],
                                  np.asarray(state)[live])
    assert np.array_equal(np.asarray(new)[~live].view(np.uint32),
                          np.asarray(state)[~live].view(np.uint32))
    assert not np.asarray(out)[~live].any()


def test_the_plain_rule_never_reads_an_idle_slot():
    q, k, v, g, state = plain_inputs(8, 6, 16, 8)
    active = jnp.asarray(ACTIVE["mixed"])
    clean = kda_step.kda_live_step(q, k, v, g, None, state, active)
    poison = [jnp.where((~active).reshape((-1,) + (1,) * (z.ndim - 1)),
                        jnp.nan, z) for z in (q, k, v, g, state)]
    out, new = kda_step.kda_live_step(*poison[:4], None, poison[4], active)
    live = np.asarray(active)
    assert np.array_equal(np.asarray(out)[live], np.asarray(clean[0])[live])
    assert np.array_equal(np.asarray(new)[live], np.asarray(clean[1])[live])
    assert np.isnan(np.asarray(new)[~live]).all()
    assert not np.asarray(out)[~live].any()


def test_four_plain_steps_in_a_loop_with_the_state_donated():
    """As the decode step runs it: inside a `lax.while_loop` under `jit`,
    the state carried and donated, one slot never live, one that stops."""
    q, k, v, g, state = plain_inputs(9, 6, 16, 8)
    steps = 4
    lives = jnp.asarray([[True, False, True, True, True]] * 2 +
                        [[True, False, True, False, True]] * 2)

    def run(state):
        def body(loop):
            index, state, outs = loop
            out, state = kda_step.kda_live_step(
                jnp.roll(q, index, axis=1), k, jnp.roll(v, index, axis=1),
                g, None, state, lives[index])
            return index + 1, state, outs.at[index].set(out)

        return jax.lax.while_loop(
            lambda loop: loop[0] < steps, body,
            (jnp.int32(0), state, jnp.zeros((steps,) + v.shape)))[1:]

    want, want_outs = state, []
    for index in range(steps):
        out, want = plain_oracle(jnp.roll(q, index, axis=1), k,
                                 jnp.roll(v, index, axis=1), g, want,
                                 lives[index])
        want_outs.append(np.asarray(out))
    kept = np.asarray(state[1])
    new, outs = jax.jit(run, donate_argnums=(0,))(state + 0.0)
    assert np.abs(np.asarray(new - want)).max() < 1e-5
    assert np.abs(np.asarray(outs) - np.stack(want_outs)).max() < 1e-4
    assert np.array_equal(np.asarray(new[1]), kept)


def test_the_published_state_space_heads_take_the_kernel():
    """64 heads of [128, 64]: 4,096 lanes a slot, pairs of heads a vector,
    2 MB a slot (`moves_live_states`' own arithmetic, ISSUE 45)."""
    assert kda_step.moves_live_states(64, 128, value_dim=64, by_head=True)
    assert kda_step._head_group(64, 64) == 2
    assert not kda_step.moves_live_states(6, 16, value_dim=8, by_head=True)
