# ops/ssm_chunk.py (ISSUE 46): the chunked form of the Mamba-2 recurrence as
# one pallas kernel a layer, in the interpreter on the CPU, float32: against
# models/ssm_hybrid.ssm_plain token by token and against ssm_chunked (XLA's
# form, which stays the CPU's and the oracle).  What the interpreter cannot
# see (tiles, lanes, VMEM) is tests/test_chip_compile.py's and
# chip_smoke.py's.

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import ssm_hybrid as M
from aiko_services_tpu.ops import ssm_chunk


def _inputs(key, rows, tokens, heads=6, width=8, state=16, rate=(1e-3, 0.1),
            a_log=(0.0, 2.77)):
    """x and a state that is not zero of order one, B and C of order one a
    component, dt log-uniform over `rate` and A = -exp(A_log) with A_log
    uniform over `a_log` (the cell's own: exp(A_log) in [1, 16])."""
    ks = jax.random.split(key, 6)
    low, high = np.log(rate[0]), np.log(rate[1])
    return (jax.random.normal(ks[0], (rows, tokens, heads, width)),
            jnp.exp(jax.random.uniform(ks[1], (rows, tokens, heads),
                                       minval=low, maxval=high)),
            jax.random.normal(ks[2], (rows, tokens, state)),
            jax.random.normal(ks[3], (rows, tokens, state)),
            -jnp.exp(jax.random.uniform(ks[4], (heads,), minval=a_log[0],
                                        maxval=a_log[1])),
            jax.random.normal(ks[5], (rows, state, heads * width)))


def _kernel(*args):
    return ssm_chunk.ssm_chunk_scan(*args, interpret=True)


def _worst(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


@pytest.mark.parametrize("rows, tokens, rate", [
    (1, 128, (1e-3, 0.1)), (2, 512, (1e-3, 0.1)), (2, 200, (1e-4, 1e-3)),
    (3, 300, (0.05, 2.0)), (2, 7, (1e-3, 0.1)), (1, 129, (1e-3, 0.1))],
    ids=["one-row-one-chunk", "four-chunks", "no-whole-chunk-slow-decay",
         "three-rows-fast-decay", "shorter-than-a-sublane-tile",
         "one-token-past-a-chunk"])
def test_the_kernel_equals_the_recurrence_and_the_chunked_form(
        rows, tokens, rate):
    """Chunks of 128 in one call against one token at a time and against
    XLA's chunked form, 6 heads of 8 that share B and C of 16, from a state
    that is not zero; T is padded to whole chunks inside.  Outputs to some
    tens: float32 sums in another order."""
    args = _inputs(jax.random.PRNGKey(tokens), rows, tokens, rate=rate)
    want, want_state = M.ssm_plain(*args)
    xla, xla_state = M.ssm_chunked(*args)
    got, got_state = _kernel(*args)
    assert got.shape == args[0].shape and got_state.shape == args[5].shape
    assert np.isfinite(np.asarray(got)).all()
    scale = float(np.abs(np.asarray(want)).max())
    assert scale > 1.0
    assert _worst(got, want) < 1e-5 * scale
    assert _worst(got_state, want_state) < 2e-5
    # XLA's form sums c_t in another order (`jnp.cumsum`): where a head
    # forgets fast it stands farther from the recurrence than the kernel
    assert _worst(got, xla) < 1e-5 * scale + _worst(xla, want)
    assert _worst(got_state, xla_state) < 2e-5 + _worst(xla_state,
                                                        want_state)


@pytest.mark.parametrize("heads, width, state, tokens", [
    (4, 64, 128, 130), (2, 128, 16, 130), (16, 8, 24, 130), (3, 16, 8, 70),
    (1, 256, 8, 40)],
    ids=["published-head-and-state", "a-head-is-a-vector", "sixteen-a-vector",
         "an-odd-count-of-heads", "a-head-of-two-vectors"])
def test_heads_inside_a_vector_and_heads_of_whole_vectors(heads, width,
                                                          state, tokens):
    """The cell's own head (64 wide, two a vector of lanes, N = 128), a
    head that is a vector or two, sixteen heads inside one, and a count
    that shares no vector evenly: two chunks and a piece of one."""
    args = _inputs(jax.random.PRNGKey(width), 1, tokens, heads, width, state)
    want, want_state = M.ssm_plain(*args)
    got, got_state = _kernel(*args)
    scale = float(np.abs(np.asarray(want)).max())
    assert _worst(got, want) < 1e-5 * scale
    assert _worst(got_state, want_state) < 2e-5


@pytest.mark.parametrize("tokens, true_len", [(256, 37), (256, 128),
                                              (200, 199), (256, 0)],
                         ids=["tail-inside-a-chunk", "a-whole-chunk-of-tail",
                              "one-position-of-tail", "nothing-live"])
def test_a_tail_with_dt_zero_leaves_the_state_to_the_bit(tokens, true_len):
    """dt = 0 past a true length: what x, B and C hold there reaches
    neither the state nor the live positions' outputs, BIT FOR BIT (such a
    position's rate is 0, its rows of `write` and `keeps` zeros; a chunk
    with nothing live multiplies the state by exp(0) and adds zeros), so
    the state after the block is the state at its last live token: with
    nothing live, the state that came.  Against the block cut to its live
    positions (another program in the interpreter) to float32 rounding."""
    x, dt, b, c, a, state = _inputs(jax.random.PRNGKey(true_len + 1), 2,
                                    tokens)
    live = (jnp.arange(tokens) < true_len)[None, :, None]
    dt = dt * live
    out, padded = _kernel(x, dt, b, c, a, state)
    blank, same = _kernel(x * live[..., None], dt, b * live, c * live, a,
                          state)
    assert np.array_equal(np.asarray(padded), np.asarray(same))
    assert np.array_equal(np.asarray(out[:, :true_len]),
                          np.asarray(blank[:, :true_len]))
    if not true_len:
        assert np.array_equal(np.asarray(padded), np.asarray(state))
        return
    assert not np.array_equal(np.asarray(padded), np.asarray(state))
    cut = slice(0, true_len)
    want, short = _kernel(x[:, cut], dt[:, cut], b[:, cut], c[:, cut], a,
                          state)
    scale = float(np.abs(np.asarray(want)).max())
    assert _worst(padded, short) < 2e-5
    assert _worst(out[:, cut], want) < 1e-5 * scale


@pytest.mark.parametrize("first", [128, 72, 1],
                         ids=["a-whole-chunk-first", "a-piece-of-a-chunk",
                              "one-token-first"])
def test_an_extends_second_piece_starts_from_the_state_the_first_left(
        first):
    """A prompt in two pieces (the second from the state the first left)
    is the prompt in one call."""
    args = _inputs(jax.random.PRNGKey(9), 2, 200)
    x, dt, b, c, a, state = args
    whole, whole_state = _kernel(*args)
    head, between = _kernel(*(z[:, :first] for z in (x, dt, b, c)), a, state)
    tail, after = _kernel(*(z[:, first:] for z in (x, dt, b, c)), a, between)
    scale = float(np.abs(np.asarray(whole)).max())
    assert _worst(jnp.concatenate([head, tail], axis=1), whole) < \
        1e-5 * scale
    assert _worst(after, whole_state) < 2e-5
    want, want_state = M.ssm_plain(*args)
    assert _worst(whole, want) < 1e-5 * scale
    assert _worst(after, want_state) < 2e-5


@pytest.mark.parametrize("rate, a_log, close", [
    ((20.0, 100.0), (2.7, 2.78), 2e-5), ((1e-6, 1e-5), (-1.0, 0.0), 2e-5),
    ((1e-4, 50.0), (-1.0, 2.78), 3e-4)],
    ids=["forgets-within-a-token", "hardly-decays", "both-ends-at-once"])
def test_dt_and_a_log_at_the_ends_of_their_ranges(rate, a_log, close):
    """dt of 20-100 with A to -16: c_t - c_s reaches -10^5 inside a chunk
    and its exponential is 0, never an `inf` or a `nan` (every exponent is
    a DIFFERENCE <= 0: nothing is factorised into exp(c_t) exp(-c_s), whose
    second factor would overflow); dt of 10^-6: the state is carried nearly
    whole; and positions of both kinds in one chunk, where a small
    difference of two running sums near -10^4 keeps four digits, in this
    form as in XLA's (`ssm_chunked` stands 0.5e-4 of the scale from the
    recurrence there, the kernel 1.1e-4)."""
    args = _inputs(jax.random.PRNGKey(17), 2, 300, rate=rate, a_log=a_log)
    want, want_state = M.ssm_plain(*args)
    got, got_state = _kernel(*args)
    assert np.isfinite(np.asarray(got)).all()
    assert np.isfinite(np.asarray(got_state)).all()
    scale = max(1.0, float(np.abs(np.asarray(want)).max()))
    assert _worst(got, want) < close * scale
    assert _worst(got_state, want_state) < close * max(
        1.0, float(np.abs(np.asarray(want_state)).max()))


def test_a_state_that_arrives_zero_and_one_that_does_not_differ():
    """The state that arrives is read: from zeros the same piece gives
    another output (an admit starts from zeros, an extend's piece does
    not), and both equal the recurrence."""
    x, dt, b, c, a, state = _inputs(jax.random.PRNGKey(4), 1, 140)
    for held in (state, jnp.zeros_like(state)):
        want, want_state = M.ssm_plain(x, dt, b, c, a, held)
        got, got_state = _kernel(x, dt, b, c, a, held)
        scale = float(np.abs(np.asarray(want)).max())
        assert _worst(got, want) < 1e-5 * scale
        assert _worst(got_state, want_state) < 2e-5
    assert _worst(_kernel(x, dt, b, c, a, state)[0],
                  _kernel(x, dt, b, c, a, jnp.zeros_like(state))[0]) > 0.1


@pytest.mark.parametrize("heads, width, state, takes", [
    (64, 64, 128, True),         # granite-4.0-h-micro: pairs are a vector
    (6, 8, 16, False),           # the tiny preset: 48 lanes, no whole vector
    (16, 8, 16, True),           # sixteen heads of 8 are one vector
    (64, 64, 12, False),         # a state that is no whole sublanes
    (8, 96, 128, False),         # a head that straddles a vector's edge
    (4, 256, 64, True)],         # a head of two vectors
    ids=["granite", "tiny", "sixteen-small-heads", "state-of-12",
         "head-of-96", "head-of-256"])
def test_the_predicate_reads_the_geometry(heads, width, state, takes):
    assert ssm_chunk.scans_ssm_chunks(heads, width, state) is takes
    # the interpreter has no tiles
    assert ssm_chunk.scans_ssm_chunks(heads, width, state, interpret=True)


@pytest.mark.parametrize("heads, width, state, group", [
    (64, 64, 128, 32), (6, 8, 16, 0), (16, 8, 16, 16), (3, 64, 128, 0),
    (128, 64, 128, 32), (4, 256, 64, 4)],
    ids=["granite", "tiny", "sixteen-small-heads-are-one-vector",
         "an-odd-count-of-half-vector-heads", "twice-granite",
         "heads-of-two-vectors"])
def test_heads_a_grid_step(heads, width, state, group):
    assert ssm_chunk._group(heads, width, state, False) == group


def test_a_geometry_the_tiles_refuse_is_served_by_the_chunked_form(
        monkeypatch):
    """The tiny preset (6 heads of 8, N = 16) on a TPU: the model asks the
    predicate, is refused, and traces `ssm_chunked`; at the published heads
    it takes the kernel.  Nothing runs: the choice is made at trace
    time."""
    calls = []
    chunked = M.ssm_chunked
    monkeypatch.setattr(M, "ssm_chunked", lambda *a: calls.append(
        "chunked") or chunked(*a))
    monkeypatch.setattr(M, "ssm_chunk_scan", lambda *a: calls.append(
        "kernel") or ssm_chunk.ssm_chunk_scan(*a, interpret=True))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tiny = M.SSM_HYBRID_PRESETS["tiny"]
    assert not M._scan_kernel(tiny, False)
    wide = M.SsmHybridConfig(
        vocab=64, dim=32, layer_types=("mamba",), ffn_dim=64, num_heads=2,
        num_kv_heads=1, head_dim=16, ssm_heads=2, ssm_head_dim=64,
        ssm_state=8, max_seq_len=64)
    assert M._scan_kernel(wide, False) and not M._scan_kernel(wide, True)
    assert wide.paged_model().scan_kernel is M._scan_kernel
    for config, form in ((tiny, "chunked"), (wide, "kernel")):
        calls.clear()
        params = M.ssm_hybrid_init(jax.random.PRNGKey(0), config)
        jax.eval_shape(lambda p: M.ssm_hybrid_forward(
            p, config, jnp.zeros((1, 12), jnp.int32)), params)
        assert set(calls) == {form}, (config, calls)
