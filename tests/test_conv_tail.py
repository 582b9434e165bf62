# layers.conv_tail (ISSUE 47): the recurrent layers' short convolution and
# the roll of a slot's tail, ONE helper where models/ssm_hybrid,
# gated_delta and hybrid_sparse each held a copy.  The copy it replaces is
# kept here as the plain form, and the helper is held to it BIT for bit at
# the three models' widths: a decode step with live and idle slots mixed
# (the select that took the gather's place), a prompt's piece of one row
# (one slice) and of several (a slice a row).  The helper's tail is a
# slot's conv-1 positions side by side on the lanes; the plain form's is
# the [conv-1, C] rows the models kept until then.

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import layers as L

TAPS = 4
# conv channels and whether the convolution has a bias: granite-4.0-h-micro
# (ssm_inner + 2 x ssm_state), olmo-hybrid-7b (2 x 30 x 96 + 30 x 192),
# glm-5.3-flash (3 x 64 x 128)
MODELS = {"ssm_hybrid": (4352, True), "gated_delta": (11520, False),
          "hybrid_sparse": (24576, False)}
PIECE = 6
# live positions of a piece's row: none, one, two, conv-1, all
COUNTS = (0, 1, 2, TAPS - 1, PIECE)


def plain(pre, tail, weights, bias, live):
    """What `_mamba_inputs`, `_gdn_inputs` and `_kda_inputs` each wrote out
    before ISSUE 47: tail and block laid end to end, the taps as windows of
    that, the new tail gathered a row at a time at the row's live count."""
    taps, t = weights.shape[0], pre.shape[1]
    full = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
    weights = weights.astype(jnp.float32)
    mixed = sum(full[:, i:i + t].astype(jnp.float32) * weights[i]
                for i in range(taps))
    if bias is not None:
        mixed = mixed + bias.astype(jnp.float32)
    count = live.sum(axis=1).astype(jnp.int32)
    new_tail = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
        rows, n, taps - 1, axis=0))(full, count)
    return jax.nn.silu(mixed), new_tail.astype(tail.dtype)


def _blocks(form):
    """-> [(rows, tokens, live [rows, tokens])] a form is tried at."""
    if form == "step":
        return [(7, 1, np.array([1, 0, 1, 1, 0, 0, 1], bool)[:, None])]
    lead = np.arange(PIECE)[None] < np.array(COUNTS)[:, None]
    if form == "piece-one-row":
        return [(1, PIECE, row[None]) for row in lead]
    return [(len(COUNTS), PIECE, lead)]


def _bits(array):
    array = np.asarray(array)
    return array.view({2: np.uint16, 4: np.uint32}[array.dtype.itemsize])


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("form", ["step", "piece-one-row", "piece-rows"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_conv_tail_is_the_plain_form_bit_for_bit(model, form, jitted):
    channels, biased = MODELS[model]
    keys = jax.random.split(jax.random.PRNGKey(channels), 4)
    weights = (jax.random.normal(keys[0], (TAPS, channels)) *
               TAPS ** -0.5).astype(jnp.bfloat16)
    bias = jax.random.normal(keys[1], (channels,)).astype(jnp.bfloat16) \
        if biased else None
    ours, theirs = (jax.jit(L.conv_tail), jax.jit(plain)) if jitted \
        else (L.conv_tail, plain)
    for rows, tokens, live in _blocks(form):
        pre = jax.random.normal(keys[2], (rows, tokens, channels)
                                ).astype(jnp.bfloat16)
        tail = jax.random.normal(keys[3], (rows, TAPS - 1, channels)
                                 ).astype(jnp.bfloat16)
        live = jnp.asarray(live)
        # the helper keeps a slot's tail lane-dense, [A, (conv-1) x C]
        mixed, rolled = ours(pre, tail.reshape(rows, -1), weights, bias,
                             live)
        assert rolled.shape == (rows, (TAPS - 1) * channels)
        rolled = rolled.reshape(tail.shape)
        want_mixed, want_rolled = theirs(pre, tail, weights, bias, live)
        assert mixed.dtype == jnp.float32 and rolled.dtype == tail.dtype
        assert mixed.shape == (rows, tokens, channels)
        assert np.array_equal(_bits(mixed), _bits(want_mixed))
        assert np.array_equal(_bits(rolled), _bits(want_rolled))
        # a slot that does not decode, a row with no live position: the
        # tail as it was
        idle = ~np.asarray(live).any(axis=1)
        assert np.array_equal(_bits(rolled)[idle], _bits(tail)[idle])

