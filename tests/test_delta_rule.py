# models/delta_rule.py (ISSUE 40): the gated delta rule with ONE gate a head
# (Gated DeltaNet), its chunked form against the recurrence token by token,
# on the CPU in float32.  The gate a channel (Kimi Delta Attention) shares
# the module and keeps its tests in tests/test_hybrid_sparse.py.

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import delta_rule
from aiko_services_tpu.models import hybrid_sparse


def _inputs(key, rows, tokens, heads, dk, dv, rate):
    """q, k unit length a head (q x dk^-0.5), beta in (0, 2), log-decays
    about -`rate` a token, a state that is not zero."""
    ks = jax.random.split(key, 6)
    unit = lambda z: z / jnp.linalg.norm(z, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (rows, tokens, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (rows, tokens, heads, dk)))
    v = jax.random.normal(ks[2], (rows, tokens, heads, dv))
    g = -rate * jax.nn.softplus(jax.random.normal(ks[3],
                                                  (rows, tokens, heads)))
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


@pytest.mark.parametrize("tokens, rate", [
    (200, 0.05), (200, 6.0), (64, 1.0), (40, 0.3), (7, 1.0), (1, 1.0)],
    ids=["slow-decay", "decay-past-e-6-a-token", "one-chunk",
         "no-whole-chunk", "shorter-than-a-sub-block", "one-token"])
def test_the_chunked_form_equals_the_recurrence(tokens, rate):
    """Chunks of 64 against one token at a time at UNEQUAL head sides (3
    heads of [8, 16]) and beta up to 2, from a state that is not zero.  One
    decay a head leaves the pair products as exp(G_t - G_s) with s <= t:
    no exponent is positive, so at e^-6 a token (e^-384 a chunk) nothing
    overflows and what underflows is the limit.  Outputs of spread ~0.5:
    float32 sums in another order."""
    q, k, v, g, beta, state = _inputs(jax.random.PRNGKey(tokens), 2, tokens,
                                      3, 8, 16, rate)
    assert float(beta.max()) > 1.5
    want, want_state = _token_by_token(q, k, v, g, beta, state)
    got, got_state = delta_rule.chunked(q, k, v, g, beta, state)
    assert got.shape == v.shape and got_state.shape == state.shape
    assert np.isfinite(np.asarray(got)).all()
    assert float(np.abs(np.asarray(want)).max()) > 0.1
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    assert np.abs(np.asarray(got_state - want_state)).max() < 2e-5


def test_a_position_that_is_not_live_leaves_the_state_as_it_was():
    """beta = 0 and g = 0 past a true length: the state after 37 live
    tokens of a block of 64 is the state after a block of 37, and the live
    positions' outputs are the same."""
    q, k, v, g, beta, state = _inputs(jax.random.PRNGKey(1), 2, 64, 3, 8, 16,
                                      0.5)
    live = (jnp.arange(64) < 37)[None]
    out, padded = delta_rule.chunked(q, k, v, g * live[..., None],
                                     beta * live[..., None], state)
    want, short = delta_rule.chunked(q[:, :37], k[:, :37], v[:, :37],
                                     g[:, :37], beta[:, :37], state)
    assert np.abs(np.asarray(padded - short)).max() < 1e-5
    assert np.abs(np.asarray(out[:, :37] - want)).max() < 1e-5


def test_one_gate_a_head_is_the_channel_gate_with_equal_channels():
    """The two grains meet where a channel gate is the same in every
    channel: one rule, two forms of the chunk."""
    q, k, v, g, beta, state = _inputs(jax.random.PRNGKey(2), 1, 50, 2, 16, 16,
                                      0.5)
    wide = jnp.broadcast_to(g[..., None], k.shape)
    by_head = delta_rule.chunked(q, k, v, g, beta, state)
    by_channel = delta_rule.chunked(q, k, v, wide, beta, state)
    for a, b in zip(by_head, by_channel):
        assert np.abs(np.asarray(a - b)).max() < 2e-5
    one = delta_rule.recurrent(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                               beta[:, 0], state)
    other = delta_rule.recurrent(q[:, 0], k[:, 0], v[:, 0], wide[:, 0],
                                 beta[:, 0], state)
    for a, b in zip(one, other):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_beta_two_reflects_the_state_along_its_key():
    """beta = 2, no decay, v = 0: S <- (I - 2 k k^T) S, eigenvalue -1 along
    k (`linear_allow_neg_eigval`): applied twice the state is back."""
    key = jax.random.PRNGKey(3)
    k = jax.random.normal(key, (1, 2, 8))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    state = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 8, 16))
    zeros, two = jnp.zeros((1, 2)), jnp.full((1, 2), 2.0)
    _, once = delta_rule.recurrent(k, k, jnp.zeros((1, 2, 16)), zeros, two,
                                   state)
    seen = jnp.einsum("ahd,ahdv->ahv", k, once)
    assert np.abs(np.asarray(
        seen + jnp.einsum("ahd,ahdv->ahv", k, state))).max() < 1e-5
    _, twice = delta_rule.recurrent(k, k, jnp.zeros((1, 2, 16)), zeros, two,
                                    once)
    assert np.abs(np.asarray(twice - state)).max() < 1e-5


def test_the_hybrid_models_names_are_this_modules_functions():
    assert hybrid_sparse.kda_recurrent is delta_rule.recurrent
    assert hybrid_sparse.kda_chunked is delta_rule.chunked
