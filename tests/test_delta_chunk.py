# ops/delta_chunk.py (ISSUE 41): the chunked form of the gated delta rule as
# one pallas kernel a layer, in the interpreter on the CPU, float32: against
# models/delta_rule.recurrent token by token and against delta_rule.chunked
# (XLA's form, which stays the CPU's and the oracle), both grains of gate.
# What the interpreter cannot see (tiles, lanes, VMEM) is
# tests/test_chip_compile.py's and chip_smoke.py's.

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import delta_rule
from aiko_services_tpu.ops import delta_chunk
from aiko_services_tpu.ops.kda_step import heads_apart, heads_side_by_side

GRAINS = pytest.mark.parametrize("channel", [False, True],
                                 ids=["a-gate-a-head", "a-gate-a-channel"])


def _inputs(key, rows, tokens, heads, dk, dv, channel, rate=1.0):
    """q, k unit length a head (q x dk^-0.5), beta over (0, 2), log-decays
    about -`rate` a token (a channel's held at -5, the model's own bound),
    a state that is not zero."""
    ks = jax.random.split(key, 6)
    unit = lambda z: z / jnp.linalg.norm(z, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (rows, tokens, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (rows, tokens, heads, dk)))
    v = jax.random.normal(ks[2], (rows, tokens, heads, dv))
    g = -rate * jax.nn.softplus(jax.random.normal(
        ks[3], (rows, tokens, heads) + ((dk,) if channel else ())))
    if channel:
        g = jnp.maximum(g, -5.0)
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(
        ks[4], (rows, tokens, heads)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (rows, heads, dk, dv))


def _token_by_token(q, k, v, g, beta, state):
    def one(state, xs):
        out, state = delta_rule.recurrent(*xs, state)
        return state, out
    state, out = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


def _kernel(q, k, v, g, beta, state):
    """The kernel over a state [A, H, Dk, Dv], laid for the gate's grain
    and back."""
    if g.ndim == 4:
        return delta_chunk.delta_chunk_scan(q, k, v, g, beta, state,
                                            interpret=True)
    out, after = delta_chunk.delta_chunk_scan(
        q, k, v, g, beta, heads_side_by_side(state), interpret=True)
    return out, heads_apart(after, k.shape[2])


def _worst(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


@GRAINS
@pytest.mark.parametrize("tokens, rate", [
    (64, 1.0), (512, 0.3), (200, 0.05), (200, 4.0), (7, 1.0)],
    ids=["one-chunk", "eight-chunks", "no-whole-chunk-slow-decay",
         "decay-past-e-4-a-token", "shorter-than-a-sub-block"])
def test_the_kernel_equals_the_recurrence_and_the_chunked_form(
        channel, tokens, rate):
    """Chunks of 64 in one call against one token at a time and against
    XLA's chunked form, at unequal sides (4 heads of [8, 16]), beta up to
    2, from a state that is not zero; T is padded to whole chunks inside.
    Outputs of spread ~0.5: float32 sums in another order."""
    q, k, v, g, beta, state = _inputs(jax.random.PRNGKey(tokens), 2, tokens,
                                      4, 8, 16, channel, rate)
    assert float(beta.max()) > 1.5
    want, want_state = _token_by_token(q, k, v, g, beta, state)
    xla, xla_state = delta_rule.chunked(q, k, v, g, beta, state)
    got, got_state = _kernel(q, k, v, g, beta, state)
    assert got.shape == v.shape and got_state.shape == state.shape
    assert np.isfinite(np.asarray(got)).all()
    assert float(np.abs(np.asarray(want)).max()) > 0.1
    assert _worst(got, want) < 2e-5 and _worst(got_state, want_state) < 2e-5
    assert _worst(got, xla) < 2e-5 and _worst(got_state, xla_state) < 2e-5


@pytest.mark.parametrize("channel, heads, dk, dv", [
    (False, 2, 96, 192), (True, 2, 128, 128), (False, 6, 8, 16),
    (True, 3, 16, 8)],
    ids=["published-gdn-head", "published-kda-head", "six-small-heads",
         "value-side-the-narrower"])
def test_a_head_of_unequal_sides_and_a_square_one(channel, heads, dk, dv):
    """The cells' own heads, [96, 192] with a gate a head and [128, 128]
    with a gate a channel, 130 tokens (two chunks and a piece of one)."""
    q, k, v, g, beta, state = _inputs(jax.random.PRNGKey(dk), 1, 130, heads,
                                      dk, dv, channel)
    want, want_state = _token_by_token(q, k, v, g, beta, state)
    got, got_state = _kernel(q, k, v, g, beta, state)
    assert _worst(got, want) < 2e-5 and _worst(got_state, want_state) < 2e-5


@GRAINS
@pytest.mark.parametrize("tokens, true_len", [(128, 37), (128, 64),
                                              (100, 99), (128, 0)],
                         ids=["tail-inside-a-chunk", "a-whole-chunk-of-tail",
                              "one-position-of-tail", "nothing-live"])
def test_a_tail_that_is_not_live_leaves_the_state_to_the_bit(
        channel, tokens, true_len):
    """beta = 0 and g = 0 past a true length: what q, k and v hold there
    reaches neither the state nor the live positions' outputs, BIT FOR BIT
    (the row of the inverse at such a position is the unit row, its u
    zero; a chunk with nothing live multiplies the state by exp(0) and
    adds zeros), so the state after the block is the state at its last
    live token: with nothing live, the state that came.  Against the block
    cut to its live positions (another program in the interpreter, whose
    sums XLA may order otherwise) to float32 rounding."""
    q, k, v, g, beta, state = _inputs(jax.random.PRNGKey(true_len), 2,
                                      tokens, 3, 8, 16, channel, 0.5)
    live = (jnp.arange(tokens) < true_len)[None, :, None]
    g, beta = g * (live[..., None] if channel else live), beta * live
    out, padded = _kernel(q, k, v, g, beta, state)
    blank, same = _kernel(*(z * live[..., None] for z in (q, k, v)), g, beta,
                          state)
    assert np.array_equal(np.asarray(padded), np.asarray(same))
    assert np.array_equal(np.asarray(out[:, :true_len]),
                          np.asarray(blank[:, :true_len]))
    if not true_len:
        assert np.array_equal(np.asarray(padded), np.asarray(state))
        return
    assert not np.array_equal(np.asarray(padded), np.asarray(state))
    cut = slice(0, true_len)
    want, short = _kernel(q[:, cut], k[:, cut], v[:, cut], g[:, cut],
                          beta[:, cut], state)
    assert _worst(padded, short) < 1e-5 and _worst(out[:, cut], want) < 1e-5


@GRAINS
def test_beta_two_reflects_the_state_along_its_key(channel):
    """beta = 2, no decay, v = 0, one key a chunk's first position and
    nothing live after it: S <- (I - 2 k k^T) S, eigenvalue -1 along k;
    the same piece again and the state is back."""
    key = jax.random.PRNGKey(3)
    k = jax.random.normal(key, (1, 64, 2, 8))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    state = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 8, 16))
    first = (jnp.arange(64) == 0)[None, :, None]
    g = jnp.zeros((1, 64, 2, 8) if channel else (1, 64, 2))
    beta = 2.0 * first * jnp.ones((1, 64, 2))
    v = jnp.zeros((1, 64, 2, 16))
    _, once = _kernel(k, k, v, g, beta, state)
    seen = jnp.einsum("ahd,ahdv->ahv", k[:, 0], once)
    assert _worst(seen, -jnp.einsum("ahd,ahdv->ahv", k[:, 0], state)) < 1e-5
    assert _worst(once, state) > 0.1
    _, twice = _kernel(k, k, v, g, beta, once)
    assert _worst(twice, state) < 1e-5


@GRAINS
def test_beta_near_two_at_every_position_of_unit_keys(channel):
    """Every position writes with beta in (1.9, 2) and unit keys, hardly
    any decay: I + beta kk is as far from the identity as the model lets
    it be, and the blocked inverse still equals the recurrence."""
    q, k, v, g, _, state = _inputs(jax.random.PRNGKey(5), 1, 192, 2, 8, 16,
                                   channel, 0.01)
    beta = 1.9 + 0.1 * jax.random.uniform(jax.random.PRNGKey(6), (1, 192, 2))
    want, want_state = _token_by_token(q, k, v, g, beta, state)
    got, got_state = _kernel(q, k, v, g, beta, state)
    assert _worst(got, want) < 1e-4 and _worst(got_state, want_state) < 1e-4


@GRAINS
def test_an_extends_second_piece_starts_from_the_state_the_first_left(
        channel):
    """A prompt in two pieces (128 tokens, then 72 from the state the
    first left) is the prompt in one call."""
    q, k, v, g, beta, state = _inputs(jax.random.PRNGKey(9), 2, 200, 3, 8,
                                      16, channel, 0.3)
    whole, whole_state = _kernel(q, k, v, g, beta, state)
    first, between = _kernel(*(z[:, :128] for z in (q, k, v, g, beta)),
                             state)
    second, after = _kernel(*(z[:, 128:] for z in (q, k, v, g, beta)),
                            between)
    assert _worst(jnp.concatenate([first, second], axis=1), whole) < 2e-5
    assert _worst(after, whole_state) < 2e-5
    want, want_state = _token_by_token(q, k, v, g, beta, state)
    assert _worst(whole, want) < 2e-5 and _worst(after, want_state) < 2e-5


@pytest.mark.parametrize("heads, dk, dv, by_head, takes", [
    (30, 96, 192, True, True),       # olmo-hybrid: pairs of 192 are 384 lanes
    (64, 128, 128, False, True),     # glm: whole lanes both sides
    (6, 8, 16, True, False),         # the tiny preset: 6 x 16 lanes, no
    (8, 8, 16, True, True),          #   group of whole vectors; 8 x 16 is one
    (4, 12, 16, False, False),       # a key side that is no whole sublanes
    (32, 64, 64, False, True)],
    ids=["olmo-hybrid", "glm", "tiny-gdn", "eight-small-heads",
         "key-side-of-12", "half-lane-heads"])
def test_the_predicate_reads_the_geometry(heads, dk, dv, by_head, takes):
    assert delta_chunk.scans_chunks(heads, dk, dv, by_head) is takes
    # the interpreter has no tiles
    assert delta_chunk.scans_chunks(heads, dk, dv, by_head, interpret=True)


@pytest.mark.parametrize("heads, dk, dv, by_head, group", [
    (30, 96, 192, True, 10), (64, 128, 128, False, 16), (6, 8, 16, True, 0),
    (3, 96, 192, True, 0), (8, 8, 16, True, 8), (5, 128, 128, False, 5)],
    ids=["olmo-hybrid", "glm", "tiny-gdn", "an-odd-count-of-192-lane-heads",
         "eight-small-heads-are-one-vector", "an-odd-count-of-heads"])
def test_heads_a_grid_step(heads, dk, dv, by_head, group):
    assert delta_chunk._group(heads, dk, dv, by_head, False) == group


def test_a_geometry_the_tiles_refuse_is_served_by_the_chunked_form(
        monkeypatch):
    """The tiny Gated DeltaNet preset (6 heads of [8, 16]) on a TPU: the
    model asks the predicate, is refused, and traces delta_rule.chunked;
    at the published heads it takes the kernel.  Nothing runs: the choice
    is made at trace time."""
    from aiko_services_tpu.models import gated_delta as M
    calls = []
    chunked = delta_rule.chunked
    monkeypatch.setattr(M.delta_rule, "chunked", lambda *a: calls.append(
        "chunked") or chunked(*a))
    monkeypatch.setattr(M, "delta_chunk_scan", lambda *a: calls.append(
        "kernel") or delta_chunk.delta_chunk_scan(*a, interpret=True))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tiny = M.GATED_DELTA_PRESETS["tiny"]
    assert not M._scan_kernel(tiny, False)
    wide = M.GatedDeltaConfig(
        vocab=64, dim=32, layer_types=("gdn",), ffn_dim=64, num_heads=2,
        head_dim=16, gdn_heads=8, key_dim=8, value_dim=16, max_seq_len=64)
    assert M._scan_kernel(wide, False) and not M._scan_kernel(wide, True)
    for config, form in ((tiny, "chunked"), (wide, "kernel")):
        calls.clear()
        params = M.gated_delta_init(jax.random.PRNGKey(0), config)
        jax.eval_shape(lambda p: M.gated_delta_forward(
            p, config, jnp.zeros((1, 12), jnp.int32)), params)
        assert set(calls) == {form}, (config, calls)
