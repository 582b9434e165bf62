"""Operations and bytes that the Gated-DeltaNet hybrid decoder needs, from
shapes alone (see benchmark/ops_bytes.py for the rules: the least a chip
must do, every weight read once per pass over it, two operations per
multiply-add), and its parameter count from the published keys.  They count
the WORK (states, rows, weights), not what an implementation happens to
touch.  Sizes are the configuration file's, under their published names.
"""

from __future__ import annotations

from benchmark.ops_bytes import ITEMSIZE, roofline_seconds  # noqa: F401

STATE_ITEMSIZE = 4          # the recurrent state is float32 whatever is served
TILE = (8, 128)             # rows and lanes of a float32 tile of the chip


# -- parameters ------------------------------------------------------------------

def gdn_shape(sizes: dict) -> tuple:
    """(heads, key lanes, value lanes a head, convolution taps)."""
    return (sizes["linear_num_value_heads"], sizes["linear_key_head_dim"],
            sizes["linear_value_head_dim"], sizes["linear_conv_kernel_dim"])


def gdn_params(sizes: dict) -> int:
    """A recurrent layer's mixing: W_q, W_k, W_v, W_g, W_o, the two gates'
    columns, the convolution, A_log, dt_bias and the output norm."""
    dim = sizes["hidden_size"]
    heads, dk, dv, taps = gdn_shape(sizes)
    return (2 * dim * heads * dk + 3 * dim * heads * dv
            + taps * heads * (2 * dk + dv) + 2 * dim * heads + 2 * heads + dv)


def full_params(sizes: dict) -> int:
    """A full layer's mixing: four square projections and the two norms
    over the whole width."""
    dim = sizes["hidden_size"]
    return 4 * dim * dim + 2 * dim


def layer_params(sizes: dict, kind: str) -> int:
    dim = sizes["hidden_size"]
    mixing = gdn_params(sizes) if kind == "linear_attention" \
        else full_params(sizes)
    return mixing + 3 * dim * sizes["intermediate_size"] + 2 * dim


def params(sizes: dict) -> dict:
    """Parameter counts: what a decode step streams (the layers, the final
    norm and the output head) and the embedding table it only gathers."""
    dim, vocab = sizes["hidden_size"], sizes["vocab_size"]
    streamed = sum(layer_params(sizes, kind)
                   for kind in sizes["layer_types"]) + dim + dim * vocab
    return {"streamed": streamed, "embedding": vocab * dim,
            "total": streamed + vocab * dim}


def layers_of(sizes: dict, kind: str) -> int:
    return sum(k == kind for k in sizes["layer_types"])


# -- what a slot and a token hold ------------------------------------------------

def state_bytes(sizes: dict) -> int:
    """A slot's state S of ONE recurrent layer as it is laid out, [key
    lanes, heads x value lanes] float32, padding to whole tiles included
    (none at the published widths: 96 rows and 5,760 lanes are whole)."""
    heads, dk, dv, _ = gdn_shape(sizes)
    rows = -(-dk // TILE[0]) * TILE[0]
    lanes = -(-heads * dv // TILE[1]) * TILE[1]
    return rows * lanes * STATE_ITEMSIZE


def tail_bytes(sizes: dict, itemsize: int) -> int:
    """The convolution's tail of ONE recurrent layer, a slot."""
    heads, dk, dv, taps = gdn_shape(sizes)
    return (taps - 1) * heads * (2 * dk + dv) * itemsize


def kv_bytes_per_token(sizes: dict, itemsize: int) -> int:
    """K and V of every head in every FULL layer."""
    return 2 * sizes["hidden_size"] * itemsize * \
        layers_of(sizes, "full_attention")


# -- kernels ---------------------------------------------------------------------

def recurrence_flops(sizes: dict) -> int:
    """The rule's OWN count for one token of one layer, whatever form
    computes it: every head decays S (Dk x Dv), reads it against k, writes
    the rank-one update and reads it against q: 7 Dk Dv."""
    heads, dk, dv, _ = gdn_shape(sizes)
    return 7 * heads * dk * dv


def state_step(sizes: dict, states_moved: float) -> dict:
    """The step's recurrence over `states_moved` slot-layer states: each in
    from memory and out again once."""
    return {"flops": recurrence_flops(sizes) * states_moved,
            "bytes": 2 * state_bytes(sizes) * states_moved}


def scan(sizes: dict, tokens: float, pieces: float) -> dict:
    """The chunked form over `tokens` prompt tokens in `pieces` pieces (all
    recurrent layers): the rule's own operations a token, the state in and
    out once a piece, q, k, v, o and the two gates a token."""
    heads, dk, dv, _ = gdn_shape(sizes)
    layers = layers_of(sizes, "linear_attention")
    rows = (2 * dk + 2 * dv + 2) * heads * STATE_ITEMSIZE
    return {"flops": recurrence_flops(sizes) * tokens * layers,
            "bytes": (2 * state_bytes(sizes) * pieces + rows * tokens)
            * layers}


def decode_step(sizes: dict, itemsize: int, live_slots: float,
                held_tokens: float, states_moved: float) -> dict:
    """One decode step over `live_slots` sequences that hold `held_tokens`
    of context between them: every streamed weight once (the head with
    them), the state of the slots that decode in and out with its
    convolution tail, every live key and value once and one new row a
    slot."""
    streamed = params(sizes)["streamed"]
    state = state_step(sizes, states_moved)
    full = layers_of(sizes, "full_attention")
    return {"bytes": streamed * itemsize + state["bytes"]
            + 2 * tail_bytes(sizes, itemsize) * states_moved
            + kv_bytes_per_token(sizes, itemsize) * (held_tokens + live_slots),
            "flops": 2 * streamed * live_slots + state["flops"]
            + 4 * sizes["hidden_size"] * held_tokens * full}
