"""Operations and bytes that the Mamba-2 hybrid decoder needs, from shapes
alone (see benchmark/ops_bytes.py for the rules: the least a chip must do,
every weight read once per pass over it, two operations per multiply-add),
and its parameter count from the published keys.  They count the WORK
(states, rows, weights), not what an implementation happens to touch.
Sizes are the configuration file's, under their published names.
"""

from __future__ import annotations

from benchmark.ops_bytes import ITEMSIZE, roofline_seconds  # noqa: F401

STATE_ITEMSIZE = 4          # the recurrent state is float32 whatever is served
TILE = (8, 128)             # rows and lanes of a float32 tile of the chip


# -- parameters ------------------------------------------------------------------

def mamba_shape(sizes: dict) -> tuple:
    """(heads, lanes a head, state lanes, convolution taps)."""
    return (sizes["mamba_n_heads"], sizes["mamba_d_head"],
            sizes["mamba_d_state"], sizes["mamba_d_conv"])


def mamba_params(sizes: dict) -> int:
    """A Mamba-2 layer's mixing: W_in (z, x, B, C and dt), the convolution
    and its bias, dt_bias, A_log and D, the gated norm, W_out."""
    dim = sizes["hidden_size"]
    heads, width, state, taps = mamba_shape(sizes)
    inner, channels = heads * width, heads * width + 2 * state
    return (dim * (inner + channels + heads) + (taps + 1) * channels
            + 3 * heads + inner + inner * dim)


def attention_params(sizes: dict) -> int:
    """An attention layer's mixing: W_q and W_o over every head, W_k and
    W_v over the K/V heads."""
    dim = sizes["hidden_size"]
    head = dim // sizes["num_attention_heads"]
    return 2 * dim * dim + 2 * dim * sizes["num_key_value_heads"] * head


def layer_params(sizes: dict, kind: str) -> int:
    dim = sizes["hidden_size"]
    mixing = mamba_params(sizes) if kind == "mamba" \
        else attention_params(sizes)
    return mixing + 3 * dim * sizes["shared_intermediate_size"] + 2 * dim


def params(sizes: dict) -> dict:
    """Parameter counts: what a decode step streams (the layers, the final
    norm and the embedding AS THE HEAD: it is tied, so the table is both
    gathered and streamed, and counted once)."""
    dim, vocab = sizes["hidden_size"], sizes["vocab_size"]
    streamed = sum(layer_params(sizes, kind)
                   for kind in sizes["layer_types"]) + dim + dim * vocab
    return {"streamed": streamed, "embedding": 0, "total": streamed}


def layers_of(sizes: dict, kind: str) -> int:
    return sum(k == kind for k in sizes["layer_types"])


# -- what a slot and a token hold ------------------------------------------------

def state_bytes(sizes: dict) -> int:
    """A slot's state S of ONE Mamba layer as it is laid out, [state lanes,
    heads x lanes a head] float32, padding to whole tiles included (none at
    the published widths: 128 rows and 4,096 lanes are whole)."""
    heads, width, state, _ = mamba_shape(sizes)
    rows = -(-state // TILE[0]) * TILE[0]
    lanes = -(-heads * width // TILE[1]) * TILE[1]
    return rows * lanes * STATE_ITEMSIZE


def tail_bytes(sizes: dict, itemsize: int) -> int:
    """The convolution's tail of ONE Mamba layer, a slot."""
    heads, width, state, taps = mamba_shape(sizes)
    return (taps - 1) * (heads * width + 2 * state) * itemsize


def kv_bytes_per_token(sizes: dict, itemsize: int) -> int:
    """K and V of every K/V head in every ATTENTION layer."""
    head = sizes["hidden_size"] // sizes["num_attention_heads"]
    return 2 * sizes["num_key_value_heads"] * head * itemsize * \
        layers_of(sizes, "attention")


# -- kernels ---------------------------------------------------------------------

def recurrence_flops(sizes: dict) -> int:
    """The rule's OWN count for one token of one layer, whatever form
    computes it: every head decays S (N x P), adds the rank-one write and
    reads it against C: 5 N P."""
    heads, width, state, _ = mamba_shape(sizes)
    return 5 * heads * width * state


def state_step(sizes: dict, states_moved: float) -> dict:
    """The step's recurrence over `states_moved` slot-layer states: each in
    from memory and out again once."""
    return {"flops": recurrence_flops(sizes) * states_moved,
            "bytes": 2 * state_bytes(sizes) * states_moved}
