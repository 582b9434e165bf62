# Grouped-query attention whose keys are chosen token by token by a
# lightning indexer (DeepSeek Sparse Attention, DeepSeek-V3.2-Exp), over
# softmax-routed experts with no shared expert (ISSUE 38: the language
# model of Keye-VL-2.0-30B-A3B, every layer the same).
#
#   attention  h = rms(x); q = W_q h (heads of D), k, v = W_k h, W_v h (a
#              few K/V heads); a learned RMSNorm a head on q and on k;
#              rotary in half-split pairs (i, i + D/2) on both.  A text
#              token carries its index in all three M-RoPE streams, for
#              which the sectioned rotary IS the plain one: the program
#              computes the plain form (the benchmark's reference the
#              sectioned one).
#   indexer    q_I = W_qI h (J heads of 64), k_I = layernorm(W_kI h) (ONE
#              head), w = W_w h x (J x 64)^-0.5, rotary on the leading
#              lanes of q_I and k_I;  I(t, s) = sum_j w_tj relu(q_I,tj .
#              k_I,s) in float32.  A query attends every s <= t where
#              there are `index_topk` or fewer, else the `index_topk` of
#              largest I(t, s), EXACTLY, ties to the lower position, the
#              same set for every head.
#   experts    p = softmax(W_r rms(y)) over ALL experts in float32, the
#              top_k largest, weights p_e over their sum; the experts HELD
#              HERE add g_e W_d (silu(W_g h) * W_u h).  models/latent_moe's
#              expert layer, told that the scores are a softmax's and that
#              there is no shared expert.
#
# What a layer keeps of a token is THREE leaves: K and V rows [kv heads,
# D] as a dense grouped-query model's, and the indexer key after its norm
# and rotary.  That key has 64 lanes, half a lane tile of the chip: the
# leaf pads it to 128 with zeros (`index_row_lanes`; the chip's memory
# pads a 64-lane minor axis anyway, so the pool's bytes are then what it
# says), and the scores read the leading lanes.  Laying two tokens in a
# row would halve the leaf and its read, at the price of a merge that
# writes half rows: left to a later change (ROADMAP).
#
# The model reads its pool by hand in every program (`walks` "model"), and
# the choice of positions is a MASK everywhere (a threshold found bit by
# bit, `top_positions`: no sort, no list of positions).  The decode step
# scores EVERY live position of a slot against the cached keys, marks the
# exact top `index_topk`, and attends the slot's live K and V blocks once
# with what was not chosen masked (ISSUE 39): on the chip through the walk
# of ops/paged_attention (`step_kernel`: a position is 1 KB a leaf and the
# walk streams at the memory's speed, where fetching the chosen twelfth
# row by row paid a row's latency 16,384 times a slot and layer; past some
# 50k live positions a slot at four decoding slots the rows would win
# again, ROADMAP K1), elsewhere through the attention an extend uses; the
# slots that decode a group at a time and the others not at all.  A
# chunk's queries each choose their own positions of the prefix (over as
# much of the window as a prefix reaches) and attend it masked, piece by
# piece.

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..ops.paged_attention import (paged_decode_attention, walk_positions,
                                   walks_live_blocks)
from . import layers as L
from .hybrid_sparse import SCOPE_DSA_INDEX, _index_scores
from .latent_moe import MOE_COUNTERS, layer_ffn
from .llama import SCOPE_ATTN_CORE, SCOPE_ATTN_PROJ, SCOPE_HEAD

__all__ = ["SparseGqaConfig", "SPARSE_GQA_PRESETS", "sparse_gqa_init",
           "sparse_gqa_forward", "SPARSE_GQA_COUNTERS", "SCOPE_DSA_SELECT"]

# the top-k alone, in the step and in a chunk: its latency is its own
SCOPE_DSA_SELECT = "aiko.dsa_select"

# what a decode step counts: the expert layers' four, then over the layers
# and the slots that decoded the positions that were live, those that were
# attended, the K/V positions that the step READ of the pool to attend them
# (the walk: a slot's live blocks, whole; the plain form: the pieces it
# gathers), and the slot-steps that had `index_topk` positions or fewer
# and attended them all
_DSA_COUNTERS = ("dsa_positions_live", "dsa_positions_attended",
                 "dsa_rows_fetched", "dsa_slot_steps_dense")
SPARSE_GQA_COUNTERS = MOE_COUNTERS + _DSA_COUNTERS

_PREFIX_PIECE = 512    # positions of the prefix an extend attends at once
_LANES = 128           # a lane tile of the chip
_SLOT_GROUP = 4        # slots whose step attention is computed together


@dataclass(frozen=True)
class SparseGqaConfig:
    vocab: int = 151936
    dim: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: tuple = (16, 24, 24)   # temporal, height, width
    index_heads: int = 16
    index_dim: int = 64
    index_rope_dim: int = 32         # leading lanes of the indexer rotated
    index_rope_theta: float = 1e7
    index_topk: int = 2048           # positions attended at most
    expert_ffn_dim: int = 768        # moe_intermediate_size
    num_experts: int = 128           # the router's width, always whole
    top_k: int = 8
    routed_scale: float = 1.0
    router_scores: str = "softmax"   # what latent_moe.moe_ffn reads
    experts_first: int = 0           # the experts held here:
    experts_held: int = 128          #   [first, first + held)
    norm_eps: float = 1e-6           # rms_norm_eps (layers.rms_norm's own)
    max_seq_len: int = 32768
    dtype: object = jnp.float32

    def __post_init__(self):
        if self.norm_eps != 1e-6:
            # the step's pass and the expert layer norm with the default
            raise ValueError("models/layers.rms_norm computes with 1e-6")
        if sum(self.mrope_section) != self.head_dim // 2:
            # only then is the plain rotary the sectioned one for a text
            raise ValueError(
                f"mrope_section {self.mrope_section} must name every "
                f"rotary pair of a head of {self.head_dim}")

    @property
    def index_row_lanes(self) -> int:
        """Lanes of a cached indexer key: `index_dim` padded up to whole
        lane tiles, the pad zeros."""
        return -(-self.index_dim // _LANES) * _LANES

    @property
    def cache_leaves(self) -> tuple:
        """(heads, lanes) of each leaf a layer keeps of a token: K, V and
        the indexer key."""
        kv = (self.num_kv_heads, self.head_dim)
        return (kv, kv, (1, self.index_row_lanes))

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    def paged_model(self):
        return _paged_model()


SPARSE_GQA_PRESETS = {
    # every mechanism at a size a CPU test holds: two layers, 8 heads over
    # 2 K/V heads, 8 experts, 16 positions attended at most
    "tiny": SparseGqaConfig(
        vocab=256, dim=64, num_layers=2, num_heads=8, num_kv_heads=2,
        head_dim=16, mrope_section=(2, 3, 3), index_heads=4, index_dim=8,
        index_rope_dim=4, index_topk=16, expert_ffn_dim=32, num_experts=8,
        top_k=2, experts_held=8, max_seq_len=128),
}


# -- parameters ------------------------------------------------------------------

def _lin(key, fan_in: int, fan_out: int, dtype):
    return L.linear_init(key, fan_in, fan_out, bias=False, dtype=dtype)


def _layer_init(key, config: SparseGqaConfig):
    keys = jax.random.split(key, 11)
    dim, dtype, d = config.dim, config.dtype, config.head_dim
    held, width = config.experts_held, config.expert_ffn_dim

    def stacked(k, fan_in, fan_out):
        return {"w": (jax.random.normal(k, (held, fan_in, fan_out)) *
                      fan_in ** -0.5).astype(dtype)}

    return {
        "ln_attn": L.rms_norm_init(dim, dtype),
        "attn": {"q": _lin(keys[0], dim, config.num_heads * d, dtype),
                 "k": _lin(keys[1], dim, config.num_kv_heads * d, dtype),
                 "v": _lin(keys[2], dim, config.num_kv_heads * d, dtype),
                 "o": _lin(keys[3], config.num_heads * d, dim, dtype),
                 "q_norm": L.rms_norm_init(d, dtype),
                 "k_norm": L.rms_norm_init(d, dtype)},
        "indexer": {"q": _lin(keys[4], dim,
                              config.index_heads * config.index_dim, dtype),
                    "k": _lin(keys[5], dim, config.index_dim, dtype),
                    "k_norm": L.layer_norm_init(config.index_dim, dtype),
                    "w": _lin(keys[6], dim, config.index_heads, dtype)},
        "ln_mlp": L.rms_norm_init(dim, dtype),
        "router": _lin(keys[7], dim, config.num_experts, dtype),
        "experts": {"gate": stacked(keys[8], dim, width),
                    "up": stacked(keys[9], dim, width),
                    "down": stacked(keys[10], width, dim)}}


def sparse_gqa_init(key, config: SparseGqaConfig):
    keys = jax.random.split(key, config.num_layers + 2)
    return {"embed": L.embedding_init(keys[0], config.vocab, config.dim,
                                      config.dtype),
            "layers": [_layer_init(keys[i + 1], config)
                       for i in range(config.num_layers)],
            "ln_out": L.rms_norm_init(config.dim, config.dtype),
            "lm_head": _lin(keys[-1], config.dim, config.vocab,
                            config.dtype)}


# -- projections -----------------------------------------------------------------

def rope_tables(config: SparseGqaConfig):
    """(cos, sin), each a pair: the attention's table over all the head's
    lanes and the indexer's over its leading `index_rope_dim`."""
    attention = L.rope_frequencies(config.head_dim, config.max_seq_len,
                                   config.rope_theta)
    indexer = L.rope_frequencies(config.index_rope_dim, config.max_seq_len,
                                 config.index_rope_theta)
    return (attention[0], indexer[0]), (attention[1], indexer[1])


def _rotate(x, cos, sin, positions):
    """Rotary in half-split pairs (i, i + r/2) on the leading r = 2 x
    cos.shape[-1] lanes of x [A, H, T, D] at positions [A, T]."""
    half = cos.shape[-1]
    c = jnp.take(cos, positions, axis=0)[:, None]             # [A, 1, T, r/2]
    s = jnp.take(sin, positions, axis=0)[:, None]
    low, high = x[..., :half], x[..., half:2 * half]
    turned = jnp.concatenate([low * c - high * s, high * c + low * s],
                             axis=-1).astype(x.dtype)
    if x.shape[-1] == 2 * half:
        return turned
    return jnp.concatenate([turned, x[..., 2 * half:]], axis=-1)


def _project(layer, config: SparseGqaConfig, x, cos, sin, positions):
    """x [A, T, dim] (normed) at positions [A, T] -> (q [A, H, T, D], k, v
    [A, KV, T, D], indexer queries [A, J, T, 64], the indexer key as it is
    cached [A, 1, T, index_row_lanes] and weights [A, T, J] f32)."""
    attn, indexer = layer["attn"], layer["indexer"]
    eps = config.norm_eps
    with jax.named_scope(SCOPE_ATTN_PROJ):
        q = L._split_heads(L.linear(attn["q"], x), config.num_heads)
        k = L._split_heads(L.linear(attn["k"], x), config.num_kv_heads)
        v = L._split_heads(L.linear(attn["v"], x), config.num_kv_heads)
        q = _rotate(L.rms_norm(attn["q_norm"], q, eps), cos[0], sin[0],
                    positions)
        k = _rotate(L.rms_norm(attn["k_norm"], k, eps), cos[0], sin[0],
                    positions)
    with jax.named_scope(SCOPE_DSA_INDEX):
        q_i = _rotate(L._split_heads(L.linear(indexer["q"], x),
                                     config.index_heads),
                      cos[1], sin[1], positions)
        k_i = L.layer_norm(indexer["k_norm"], L.linear(indexer["k"], x),
                           eps)[:, None]
        k_i = _rotate(k_i, cos[1], sin[1], positions)
        pad = config.index_row_lanes - config.index_dim
        k_i = jnp.pad(k_i, ((0, 0), (0, 0), (0, 0), (0, pad)))
        weights = L.linear(indexer["w"], x).astype(jnp.float32) * \
            (config.index_heads * config.index_dim) ** -0.5
    return q, k, v, q_i, k_i, weights


def _one_zero(scores):
    """-0.0 (relu's zero times a negative weight) as +0.0: the two are one
    score, and neither a sort by bits nor a top-k may tell them apart."""
    return jnp.where(scores == 0.0, 0.0, scores)


def _scores(config: SparseGqaConfig, q_i, weights, keys):
    """Index scores [A, T, G] of queries over cached keys [A, G, row
    lanes]."""
    return _one_zero(_index_scores(q_i, weights,
                                   keys[..., :config.index_dim]))


# -- selection -------------------------------------------------------------------

def _ordered(scores):
    """float32 -> uint32 whose unsigned order is the floats' (-inf the
    least)."""
    bits = jax.lax.bitcast_convert_type(_one_zero(scores), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def top_positions(scores, limit: int):
    """Which entries of scores [..., N] are among the `limit` largest of
    their row, EXACTLY and with ties to the lower index: a mask [..., N]
    with min(limit, N) entries set a row.  The limit-th largest value is
    found bit by bit (32 counting passes over the row, no sort; two bits a
    pass, three thresholds counted side by side, was half as fast again
    on the chip: the three counts did not share their read), then the
    entries above it and as many of those that tie with it, from the
    left, as there is room for."""
    keys = _ordered(scores)

    def bit(i, prefix):
        candidate = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (keys >= candidate[..., None]).sum(axis=-1) >= limit
        return jnp.where(enough, candidate, prefix)

    floor = jax.lax.fori_loop(0, 32, bit,
                              jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = keys > floor[..., None]
    tie = keys == floor[..., None]
    room = limit - above.sum(axis=-1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, axis=-1) <= room))


def _choose(scores, limit: int, own: int, offsets):
    """top_positions of a block's scores [A, C, P + C] (the pool's P
    positions, then the block's own C), where the rows' prefixes reach
    offsets [A]: a row at `limit` positions or fewer attends them all,
    and the search passes over the leading eighth, quarter or half of the
    pool's positions where no prefix reaches further (what lies past a
    prefix is -inf and never chosen), so a chunk early in its prompt does
    not pay for the whole window 32 times."""
    reach = scores.shape[-1] - own
    longest = offsets.max()

    def over(width: int):
        def run(scores):
            if width == reach:
                return top_positions(scores, limit)
            near = top_positions(jnp.concatenate(
                [scores[..., :width], scores[..., reach:]], axis=-1), limit)
            return jnp.concatenate(
                [near[..., :width],
                 jnp.zeros(scores.shape[:-1] + (reach - width,), bool),
                 near[..., width:]], axis=-1)
        return run

    widths = sorted({reach * part // 8 for part in (1, 2, 4, 8)} - {0}) \
        or [reach]
    branch = 1 + sum((longest > width).astype(jnp.int32)
                     for width in widths[:-1])
    return jax.lax.switch(
        jnp.where(longest + own > limit, branch, 0),
        [lambda scores: jnp.ones(scores.shape, bool)] +
        [over(width) for width in widths], scores)


# -- a block of tokens (an admit, a chunk, the uncached forward) ------------------

class _PoolPrefix:
    """What the pool holds of each row's prefix, read through the row's
    table a piece of `_PREFIX_PIECE` positions at a time, as far as the
    longest live prefix reaches."""

    def __init__(self, config, leaves, tables, block: int, longest):
        self.config = config
        self.k, self.v, self.keys = leaves
        self.piece_blocks = max(1, min(tables.shape[1],
                                       _PREFIX_PIECE // block))
        pad = -tables.shape[1] % self.piece_blocks
        self.tables = jnp.pad(tables, ((0, 0), (0, pad))) if pad else tables
        self.span = self.piece_blocks * block
        self.pieces = -(-longest // self.span)
        self.positions = self.tables.shape[1] * block

    def _piece(self, pool, j):
        """Piece j of every row's prefix [A, heads, span, lanes]."""
        ids = jax.lax.dynamic_slice_in_dim(
            self.tables, j * self.piece_blocks, self.piece_blocks, axis=1)
        rows = jnp.take(pool, ids, axis=0)        # [A, pb, heads, B, lanes]
        a, pb, heads, b, lanes = rows.shape
        return rows.transpose(0, 2, 1, 3, 4).reshape(a, heads, pb * b, lanes)

    def scores(self, q_i, weights, offsets):
        """Index scores of the block's queries over the pool's positions
        [A, C, positions], -inf from each row's own offset on."""
        a, _, c, _ = q_i.shape

        def piece(j, out):
            part = _scores(self.config, q_i, weights,
                           self._piece(self.keys, j)[:, 0])
            return jax.lax.dynamic_update_slice_in_dim(
                out, part, j * self.span, axis=2)

        out = jax.lax.fori_loop(
            0, self.pieces, piece,
            jnp.full((a, c, self.positions), -jnp.inf, jnp.float32))
        return jnp.where(jnp.arange(self.positions)[None, None] <
                         offsets[:, None, None], out, -jnp.inf)

    def attend(self, carry, attend, chosen):
        if chosen.shape[-1] < self.positions:   # a table cut mid-piece
            chosen = jnp.pad(chosen, [(0, 0)] * (chosen.ndim - 1) + [
                (0, self.positions - chosen.shape[-1])])

        def piece(j, carry):
            mask = jax.lax.dynamic_slice_in_dim(chosen, j * self.span,
                                                self.span, axis=2)
            return attend(carry, self._piece(self.k, j),
                          self._piece(self.v, j), mask)

        return jax.lax.fori_loop(0, self.pieces, piece, carry)


def _masked_attention(config: SparseGqaConfig, q, k, v, chosen, prefix=None):
    """One softmax of the queries q [A, H, C, D] over the rows k, v [A, KV,
    P, D] that came with them and, where `prefix` is given, over the
    pool's positions before those, read piece by piece: a query attends
    what `chosen` [A, C, the pool's positions ++ P] names, the same for
    every head.  -> [A, H, C, D] float32."""
    a, _, c, d = q.shape
    kv, group = config.num_kv_heads, config.num_heads // config.num_kv_heads
    own = k.shape[2]
    grouped = q.reshape(a, kv, group, c, d)

    def attend(carry, keys, values, mask):
        """Online softmax over one more piece of positions: keys, values
        [A, KV, P, D]; mask [A, C, P]."""
        row_max, row_sum, acc = carry
        s = jnp.einsum("akgcd,akpd->akgcp", grouped, keys,
                       preferred_element_type=jnp.float32) * \
            config.softmax_scale
        mask = mask[:, None, None]
        s = jnp.where(mask, s, -1e30)
        new_max = jnp.maximum(row_max, s.max(axis=-1, keepdims=True))
        w = jnp.where(mask, jnp.exp(s - new_max), 0.0)
        fade = jnp.exp(row_max - new_max)
        return (new_max, row_sum * fade + w.sum(-1, keepdims=True),
                acc * fade + jnp.einsum(
                    "akgcp,akpd->akgcd", w.astype(values.dtype), values,
                    preferred_element_type=jnp.float32))

    carry = (jnp.full((a, kv, group, c, 1), -1e30, jnp.float32),
             jnp.zeros((a, kv, group, c, 1), jnp.float32),
             jnp.zeros((a, kv, group, c, d), jnp.float32))
    carry = attend(carry, k, v, chosen[..., chosen.shape[-1] - own:])
    if prefix is not None:
        carry = prefix.attend(carry, attend,
                              chosen[..., :chosen.shape[-1] - own])
    _, row_sum, acc = carry
    return (acc / row_sum).reshape(a, config.num_heads, c, d)


def _attention_block(layer, config: SparseGqaConfig, x, cos, sin, offsets,
                     prefix=None):
    """The attention over a block x [A, C, dim] (normed) at positions
    offsets[a] + [0, C): causal among its own positions and, where
    `prefix` is given (an extend), after the pool's rows; every query
    attends the positions that its index scores choose.  Returns (out
    [A, C, dim], the rows of the three leaves)."""
    a, c, _ = x.shape
    offsets = jnp.broadcast_to(jnp.asarray(offsets, jnp.int32), (a,))
    positions = offsets[:, None] + jnp.arange(c)[None]
    q, k, v, q_i, k_i, weights = _project(layer, config, x, cos, sin,
                                          positions)
    limit = config.index_topk
    order = jnp.arange(c)
    with jax.named_scope(SCOPE_DSA_INDEX):
        scores = jnp.where((order[None, :] <= order[:, None])[None],
                           _scores(config, q_i, weights, k_i[:, 0]),
                           -jnp.inf)                          # [A, C, C]
        if prefix is not None:
            scores = jnp.concatenate(
                [prefix.scores(q_i, weights, offsets), scores], axis=-1)
    with jax.named_scope(SCOPE_DSA_SELECT):
        visible = scores > -jnp.inf
        if scores.shape[-1] > limit:
            chosen = visible & _choose(scores, limit, c, offsets)
        else:
            chosen = visible
    with jax.named_scope(SCOPE_ATTN_CORE):
        out = _masked_attention(config, q, k, v, chosen,
                                prefix).astype(x.dtype)
    with jax.named_scope(SCOPE_ATTN_PROJ):
        out = L.linear(layer["attn"]["o"], L._merge_heads(out))
    return out, (k, v, k_i.astype(x.dtype))


def _block_layer(layer, config: SparseGqaConfig, x, cos, sin, offsets,
                 live, prefix=None):
    """One layer over a block of tokens: -> (x, the rows of its leaves)."""
    with jax.named_scope(SCOPE_ATTN_PROJ):
        normed = L.rms_norm(layer["ln_attn"], x, config.norm_eps)
    attended, rows = _attention_block(layer, config, normed, cos, sin,
                                      offsets, prefix)
    x, _ = layer_ffn(layer, config, x + attended, live)
    return x, rows


def sparse_gqa_hidden(params, config: SparseGqaConfig, tokens, live=None):
    """tokens [A, T] from position 0 -> (hidden after the last norm [A, T,
    dim], per layer the rows of its three leaves)."""
    cos, sin = rope_tables(config)
    x = L.embedding(params["embed"], tokens).astype(config.dtype)
    rows = []
    for layer in params["layers"]:
        x, own = _block_layer(layer, config, x, cos, sin, jnp.int32(0), live)
        rows.append(own)
    with jax.named_scope(SCOPE_HEAD):
        return L.rms_norm(params["ln_out"], x, config.norm_eps), rows


def sparse_gqa_forward(params, config: SparseGqaConfig, tokens):
    """Teacher-forced full-sequence forward: tokens [A, T] -> f32 logits
    [A, T, vocab]."""
    hidden, _ = sparse_gqa_hidden(params, config, tokens)
    return L.linear_logits(params["lm_head"], hidden)


# -- the decode step -------------------------------------------------------------

def _attend_slots(config: SparseGqaConfig, kernel: bool, leaves, tables,
                  sides, q, q_i, weights, entry_lengths, lengths, step_index,
                  active):
    """Scores, choice and softmax for the slots given (a group of them:
    every argument's leading axis): -> (out [W, H, D], the counts of
    `_attention_step` over those of them that decode)."""
    k_pool, v_pool, key_pool = leaves
    k_side, v_side, key_side = sides
    slots_n, steps = q.shape[0], k_side.shape[2]
    block = k_pool.shape[2]
    held = tables.shape[1] * block          # positions a table reaches
    with jax.named_scope(SCOPE_DSA_INDEX):
        # the pool's keys and the round's are scored apart and the SCORES
        # joined: joined as keys, the gathered rows would be copied once
        # more
        cached = jnp.take(key_pool[:, 0], tables, axis=0)     # [W, nb, B, L]
        scores = jnp.concatenate(
            [_scores(config, q_i, weights,
                     cached.reshape(slots_n, held, -1))[:, 0],
             _scores(config, q_i, weights, key_side[:, 0])[:, 0]], axis=1)
        index = jnp.arange(held + steps)
        # the pool's rows before the round, the round's rows so far
        visible = jnp.where(
            index[None] < held, index[None] < entry_lengths[:, None],
            (index[None] - held <= step_index) &
            (entry_lengths[:, None] + index[None] - held <=
             lengths[:, None]))
        scores = jnp.where(visible, scores, -jnp.inf)
    with jax.named_scope(SCOPE_DSA_SELECT):
        # a mask and no list: nothing below wants a position's number
        chosen = visible
        if held + steps > config.index_topk:
            chosen = visible & top_positions(scores, config.index_topk)
        attended = chosen.sum(axis=1)
    walked = jnp.where(active, entry_lengths, 0)
    with jax.named_scope(SCOPE_ATTN_CORE):
        if kernel:
            # every live block of a slot that decodes once through VMEM,
            # what was not chosen masked: ops/paged_attention's walk
            kv = config.num_kv_heads
            group = config.num_heads // kv
            out = paged_decode_attention(
                q[:, :, 0].reshape(slots_n, kv, group, config.head_dim),
                k_pool, v_pool, tables, k_side, v_side,
                chosen[:, None, held:], walked, groups=group,
                scale=config.softmax_scale, chosen=chosen[:, :held])
            read = walk_positions(walked, block)
        else:
            # the same mask through the extend's attention, the pool read
            # piece by piece as far as the longest of these slots reaches
            prefix = _PoolPrefix(config, leaves, tables, block, walked.max())
            out = _masked_attention(config, q, k_side, v_side,
                                    chosen[:, None], prefix)
            read = jnp.where(active, prefix.pieces * prefix.span, 0)
        counted = jnp.stack([
            jnp.where(active, lengths + 1, 0).sum(),
            jnp.where(active, attended, 0).sum(), read.sum(),
            (active & (lengths + 1 <= config.index_topk)).sum()
        ]).astype(jnp.int32)
    return out.reshape(slots_n, config.num_heads, config.head_dim), counted


def _attention_step(layer, config: SparseGqaConfig, kernel: bool, x, cos,
                    sin, tables, leaves, sides, entry_lengths, lengths,
                    step_index, active):
    """The attention in a decode step, x [S, 1, dim] at position
    lengths[s]: index scores over the cached keys of the slot's whole
    length and this round's, the exact top `index_topk` of them as a MASK,
    and one softmax over the slot's live K and V rows and the round's own
    with what was not chosen masked: `kernel` walks the slot's live blocks
    through ops/paged_attention, else the pool is read as an extend reads
    it.  The scores and the choice cost the same for a slot that decodes
    nothing, so the slots that decode are taken first, `_SLOT_GROUP` at a
    time, and a group with none of them is left out: the step's time
    follows what is live.  Returns (out, the sides rewritten, [positions
    live, positions attended, positions read of the pool, slot-steps that
    attended everything] over the slots that decode)."""
    k_side, v_side, key_side = sides
    slots_n = x.shape[0]
    q, k, v, q_i, k_i, weights = _project(layer, config, x, cos, sin,
                                          lengths[:, None])
    with jax.named_scope(SCOPE_ATTN_PROJ):
        k_side = jax.lax.dynamic_update_slice_in_dim(k_side, k, step_index,
                                                     axis=2)
        v_side = jax.lax.dynamic_update_slice_in_dim(v_side, v, step_index,
                                                     axis=2)
    with jax.named_scope(SCOPE_DSA_INDEX):
        key_side = jax.lax.dynamic_update_slice_in_dim(
            key_side, k_i.astype(key_side.dtype), step_index, axis=2)
    sides = (k_side, v_side, key_side)
    width = min(_SLOT_GROUP, slots_n)
    groups = -(-slots_n // width)
    # the slots that decode first; past the last slot a row that drops
    order = jnp.pad(jnp.argsort(~active, stable=True).astype(jnp.int32),
                    (0, groups * width - slots_n), constant_values=slots_n)
    decoding = active.sum()

    def one(g, carry):
        rows = jax.lax.dynamic_slice_in_dim(order, g * width, width)

        def of(array):
            return jnp.take(array, rows, axis=0, mode="clip")

        def run(carry):
            out, counted = carry
            part, counts = _attend_slots(
                config, kernel, leaves, of(tables),
                [of(side) for side in sides],
                of(q), of(q_i), of(weights), of(entry_lengths), of(lengths),
                step_index, of(active) & (rows < slots_n))
            return (out.at[rows].set(part.astype(out.dtype), mode="drop"),
                    counted + counts)

        return jax.lax.cond(g * width < decoding, run,
                            lambda carry: carry, carry)

    out, counted = jax.lax.fori_loop(
        0, groups, one,
        (jnp.zeros((slots_n, config.num_heads, config.head_dim), x.dtype),
         jnp.zeros((len(_DSA_COUNTERS),), jnp.int32)))
    with jax.named_scope(SCOPE_ATTN_PROJ):
        out = L.linear(layer["attn"]["o"], L._merge_heads(out[:, :, None]))
    return out, sides, counted


# -- as a PagedModel (what serving_paged's builders call) ------------------------

def _step_argmax(params, config: SparseGqaConfig, token_block, attend, live):
    """serving._token_block_argmax (the embedding, the attention norm, the
    head) with the expert layer in its seam; the expert layers' counts add
    up over the layers (the attention's come through the builder)."""
    from ..serving import _token_block_argmax
    alive = jnp.broadcast_to(live[:, None], token_block.shape)
    counted = []

    def ffn(layer, x):
        x, counts = layer_ffn(layer, config, x, alive)
        counted.append(counts)
        return x

    tokens = _token_block_argmax(params, config, token_block, attend, ffn)
    moe = sum(counted, jnp.zeros((len(MOE_COUNTERS),), jnp.int32))
    return tokens, jnp.concatenate(
        [moe, jnp.zeros((len(_DSA_COUNTERS),), jnp.int32)])


def _step_kernel(config: SparseGqaConfig, interpret: bool) -> bool:
    return walks_live_blocks(config.head_dim, False, interpret)


def _step_attention(kernel: bool):
    """A layer's attention in the decode step: the model reads its pool
    itself, kernel or not, and builds no view.  `kernel` (the decoder's
    `step_kernel`: on a TPU, the weights on one device, nothing else asked
    for) lets a head of whole lanes take the walk of ops/paged_attention
    over the slots that decode, the chosen positions its mask."""

    def attend(tables, layer, config, x, cos, sin, leaves, views, sides,
               entry_lengths, lengths, step_index, entry_active, state,
               active):
        out, sides, counted = _attention_step(
            layer, config,
            kernel and _step_kernel(config, jax.default_backend() != "tpu"),
            x, cos, sin, tables, leaves, sides, entry_lengths, lengths,
            step_index, active)
        return out, sides, (), jnp.concatenate(
            [jnp.zeros((len(MOE_COUNTERS),), jnp.int32), counted])

    return attend


def _prefill(params, config: SparseGqaConfig, prompts, valid, true_lens):
    live = valid[:, None] & (jnp.arange(prompts.shape[1])[None] <
                             true_lens[:, None])
    return sparse_gqa_hidden(params, config, prompts, live)


def _extend_prepare(config: SparseGqaConfig, chunk_len: int, kernel: bool,
                    ctx):
    """The longest live prefix, and which of the chunk's tokens are
    real."""
    longest = jnp.max(jnp.where(ctx["valid"], ctx["offsets"], 0))
    live = ctx["valid"][:, None] & (
        ~ctx["finish"][:, None] |
        (jnp.arange(chunk_len)[None] <= ctx["final_idx"][:, None]))
    return {"longest": longest, "live": live}


def _extend_layer(kernel: bool):
    def extend_layer(layer, config, x, cos, sin, leaves, ctx, prepared):
        prefix = _PoolPrefix(config, leaves, ctx["tables_rows"],
                             ctx["block_tokens"], prepared["longest"])
        return _block_layer(layer, config, x, cos, sin, ctx["offsets"],
                            prepared["live"], prefix)

    return extend_layer


def _walks(config: SparseGqaConfig, kv_int8: bool, interpret: bool) -> str:
    return "model"


@functools.cache
def _paged_model():
    from ..serving_paged import PagedModel
    # the paths three leaves of two shapes are carried through: none
    # beyond the paged decoder itself
    return PagedModel(
        rope=rope_tables, token_block_argmax=_step_argmax,
        step_attention=_step_attention, prefill=_prefill,
        extend_prepare=_extend_prepare, extend_layer=_extend_layer,
        walks=_walks, step_kernel=_step_kernel,
        counters=SPARSE_GQA_COUNTERS, supports=frozenset())
