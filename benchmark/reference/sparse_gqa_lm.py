"""Plain reference for the sparse grouped-query decoder: the language model
of Keye-VL-2.0-30B-A3B as ISSUE 38 writes it down (grouped-query attention
with a norm a head and sectioned rotary, keys chosen a query by DeepSeek-
V3.2's lightning indexer, softmax-routed experts with no shared expert).

float32 throughout, `jax.default_matmul_precision("highest")`, no kernels,
no cache, no batching, nothing of the program: the rotary in its SECTIONED
form from three position streams, the selection as this file's own top-k
(a sort) over its own float32 scores, one softmax over the window masked to
the chosen positions, the held experts as a loop over all tokens.  One
sequence at a time, one layer at a time, tokens in blocks of BLOCK handed
one after another to a few small programs whose shapes do not follow the
sequence's length (keys and values are laid out to the served window and
masked), a K/V head at a time, so that 31k positions fit and nothing
compiles a second time.  Own weights from the seed
(benchmark/weights_sparse_gqa.py).

  attention  h = rms(x) (eps rms_norm_eps);  q, k, v = W_q h, W_k h, W_v h
             in heads of head_dim;  q, k <- rms over the head x a learned
             scale (assumed: Qwen3's QK-norm);  rotary on q and k: pairs
             (i, i + D/2), angle of pair i = p_s(i) x theta^(-2i/D), where
             the stream s(i) is the temporal position for i < m_0, the
             height for m_0 <= i < m_0 + m_1, the width after
             (`rope_scaling.mrope_section` m);  a text token has its index
             in all three.
  indexer    q_I = W_qI h (J heads), k_I = layernorm(W_kI h) (one head), w
             = W_w h x (J x lanes)^-0.5;  rotary on the leading
             `assumed_sizes.index_rope_head_dim` lanes of both, pairs
             (i, i + r/2), theta `index_rope_theta`, the temporal position;
             I(t, s) = sum_j w_tj relu(q_I,tj . k_I,s).
  selection  S_t = every s <= t where t + 1 <= topk, else the topk
             positions s <= t of largest I(t, s), ties to the lower s.
  output     o = softmax over S_t of q . k_s / sqrt(D) applied to v_s,
             heads / kv heads query heads a K/V head;  y = x + W_o o.
  experts    p = softmax(W_r rms(y)) over all published experts;  the 8
             largest;  g = p_e over their sum;  x' = y + sum over the
             chosen experts HELD of g_e W_d (silu(W_g h) * W_u h).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_sparse_gqa as W
from benchmark.reference.decoder_lm import logit_gaps

BLOCK = 512          # tokens handled at once


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


# -- rotary ----------------------------------------------------------------------

def sectioned_rotary(x, streams, sections, theta: float):
    """x [T, heads, D] at the positions streams [3, T] (temporal, height,
    width): lanes (i, i + D/2) turned by the angle streams[s(i)] x
    theta^(-2i / D), where pair i reads stream 0 for i < sections[0],
    stream 1 for the next sections[1] pairs, stream 2 for the rest."""
    half = x.shape[-1] // 2
    if sum(sections) != half:
        raise ValueError("mrope_section names every pair of the head")
    inverse = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    which = np.repeat(np.arange(3), sections)                    # [D/2]
    at = streams.astype(jnp.float32)[which, :].T                 # [T, D/2]
    angles = at * jnp.asarray(inverse, jnp.float32)[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    low, high = x[..., :half], x[..., half:]
    return jnp.concatenate([low * cos - high * sin,
                            high * cos + low * sin], axis=-1)


def _leading_rotary(x, positions, lanes: int, theta: float):
    """Plain rotary on the leading `lanes` lanes of x [T, heads, D]."""
    streams = jnp.broadcast_to(positions[None], (3,) + positions.shape)
    turned = sectioned_rotary(x[..., :lanes], streams, (lanes // 2, 0, 0),
                              theta)
    return jnp.concatenate([turned, x[..., lanes:]], axis=-1)


# -- one layer, block by block ---------------------------------------------------

def project(layer, x, streams, *, sizes: dict):
    """A block x [B, dim] at the positions streams [3, B] -> (h's queries
    [B, H, D], keys and values [B, KV, D], indexer queries [B, J, lanes],
    key [B, lanes] and weights [B, J])."""
    attn, indexer = layer["attn"], layer["indexer"]
    rows, eps = x.shape[0], sizes["rms_norm_eps"]
    d, theta = sizes["head_dim"], float(sizes["rope_theta"])
    sections = sizes["rope_scaling"]["mrope_section"]
    index_heads, index_dim = W.indexer_sizes(sizes)
    assumed = sizes["assumed_sizes"]
    h = _rms_norm(layer["ln_attn"]["scale"], x, eps)

    def heads(name):
        return (h @ attn[name]["w"]).reshape(rows, -1, d)

    q = sectioned_rotary(_rms_norm(attn["q_norm"]["scale"], heads("q"), eps),
                         streams, sections, theta)
    k = sectioned_rotary(_rms_norm(attn["k_norm"]["scale"], heads("k"), eps),
                         streams, sections, theta)
    lanes = assumed["index_rope_head_dim"]
    index_theta = float(assumed["index_rope_theta"])
    q_i = _leading_rotary((h @ indexer["q"]["w"]).reshape(
        rows, index_heads, index_dim), streams[0], lanes, index_theta)
    k_i = h @ indexer["k"]["w"]
    mean = k_i.mean(axis=-1, keepdims=True)
    k_i = (k_i - mean) * jax.lax.rsqrt(
        ((k_i - mean) ** 2).mean(axis=-1, keepdims=True) + eps) * \
        indexer["k_norm"]["scale"] + indexer["k_norm"]["bias"]
    k_i = _leading_rotary(k_i[:, None, :], streams[0], lanes,
                          index_theta)[:, 0]
    weights = (h @ indexer["w"]["w"]) * (index_heads * index_dim) ** -0.5
    return q, k, heads("v"), q_i, k_i, weights


def chosen_positions(scores, first, topk: int):
    """scores [B, T] of the queries at positions first + [0, B) over the
    window's positions -> which each attends [B, T]: every s <= t, or
    where those are more than topk the topk of largest score, ties to the
    lower s."""
    rows, window = scores.shape
    at = first + jnp.arange(rows)
    causal = jnp.arange(window)[None, :] <= at[:, None]
    if window <= topk:
        return causal
    scores = jnp.where(causal, scores, -jnp.inf)
    floor = jax.lax.top_k(scores, topk)[0][:, -1:]     # the topk-th largest
    above, tie = scores > floor, scores == floor
    room = topk - above.sum(axis=-1, keepdims=True)
    return causal & (above | (tie & (jnp.cumsum(tie, axis=-1) <= room)))


def visible_positions(q_i, weights, keys, first, *, sizes: dict):
    """Which of the window's positions [B, T] the queries of a block at
    positions first + [0, B) attend, from their index scores over the
    window's indexer keys [T, lanes]."""
    dots = jnp.einsum("qjd,sd->qjs", q_i, keys)
    scores = jnp.einsum("qj,qjs->qs", weights, jax.nn.relu(dots))
    return chosen_positions(scores, first, sizes["sa_config"]["topk"])


def attend(q, k, v, visible, *, sizes: dict):
    """A block's queries of ONE K/V head's group [B, G, D] over the
    window's keys and values of that head [T, D] where `visible` [B, T]:
    one softmax a query and head."""
    scores = jnp.einsum("qgd,sd->gqs", q, k) * sizes["head_dim"] ** -0.5
    scores = jnp.where(visible[None], scores, -jnp.inf)
    return jnp.einsum("gqs,sd->qgd", jax.nn.softmax(scores, -1), v)


def select(scores, top_k: int):
    """The top_k largest scores, each over their sum -> [T, E] (zero
    elsewhere)."""
    kth = jnp.sort(scores, axis=-1)[:, -top_k][:, None]
    kept = jnp.where(scores >= kth, scores, 0.0)
    return kept / kept.sum(axis=-1, keepdims=True)


def feed_forward(layer, y, *, sizes: dict):
    """A block y [B, dim] -> what the experts HELD add, one expert after
    another over every token."""
    h = _rms_norm(layer["ln_mlp"]["scale"], y, sizes["rms_norm_eps"])
    first = W.experts_first(sizes)
    weights = select(jax.nn.softmax(h @ layer["router"]["w"], axis=-1),
                     sizes["num_experts_per_tok"])

    def expert(out, e):
        one = jax.tree.map(lambda w: w[e], layer["experts"])
        gain = jax.lax.dynamic_index_in_dim(weights, first + e, axis=1)
        hidden = jax.nn.silu(h @ one["gate"]["w"]) * (h @ one["up"]["w"])
        return out + gain * (hidden @ one["down"]["w"]), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(y),
                          jnp.arange(sizes["num_experts"]))
    return out


class Programs:
    """The block programs, compiled once each whatever a sequence's
    length; `window` is the length that keys and values are laid out to
    (the served window, or a test's own sequence)."""

    def __init__(self, sizes: dict, window: int):
        if sizes["mlp_only_layers"] or sizes["decoder_sparse_step"] != 1 \
                or not sizes["norm_topk_prob"]:
            raise ValueError("every layer has experts, weights renormalised")
        self.sizes, self.window = sizes, window
        self.block = min(BLOCK, window)
        bound = functools.partial
        self.project = jax.jit(bound(project, sizes=sizes))
        self.visible = jax.jit(bound(visible_positions, sizes=sizes))
        self.attend = jax.jit(bound(attend, sizes=sizes))
        self.feed_forward = jax.jit(bound(feed_forward, sizes=sizes))
        self.add_projected = jax.jit(lambda x, w, o: x + o @ w)

    def layer(self, layer, blocks: list, streams, share: bool = False):
        """`share`: also leave the share of causal pairs attended in
        `attended_share` (a host sync: for the tests)."""
        sizes, block, window = self.sizes, self.block, self.window
        heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
        parts = [self.project(layer, x, streams[:, i * block:(i + 1) * block])
                 for i, x in enumerate(blocks)]
        pad = window - len(blocks) * block

        def whole(index):
            rows = jnp.concatenate([part[index] for part in parts])
            return jnp.pad(rows, ((0, pad),) + ((0, 0),) * (rows.ndim - 1))

        k, v, k_i = whole(1), whole(2), whole(4)
        visible = [self.visible(part[3], part[5], k_i, jnp.int32(i * block))
                   for i, part in enumerate(parts)]
        if share:
            length = len(blocks) * block
            self.attended_share = float(sum(m.sum() for m in visible)) / (
                length * (length + 1) // 2)
        out = []
        for i, (x, part) in enumerate(zip(blocks, parts)):
            q = part[0].reshape(block, kv, heads // kv, -1)
            o = jnp.concatenate(
                [self.attend(q[:, g], k[:, g], v[:, g], visible[i])
                 for g in range(kv)], axis=1).reshape(block, -1)
            y = self.add_projected(x, layer["attn"]["o"]["w"], o)
            out.append(y + self.feed_forward(layer, y))
        return out

    def embed(self, table, row) -> tuple:
        """A sequence's tokens -> (its blocks [B, dim], the three position
        streams of a text [3, T])."""
        block = self.block
        padded = np.zeros((-(-len(row) // block) * block,), np.int32)
        padded[:len(row)] = row
        x = table[padded].astype(jnp.float32)
        at = jnp.arange(len(padded), dtype=jnp.int32)
        return ([x[i:i + block] for i in range(0, len(padded), block)],
                jnp.broadcast_to(at[None], (3, len(padded))))


def _f32(tree):
    return jax.tree.map(lambda leaf: leaf.astype(jnp.float32), tree)


def _window(sizes: dict, longest: int) -> int:
    """The served window where the file names one that holds the
    sequence, else the sequence's own length (in whole blocks)."""
    window = sizes.get("serving", {}).get("max_seq", 0)
    if window >= longest:
        return window
    return longest if longest <= BLOCK else -(-longest // BLOCK) * BLOCK


def forward_logits(tokens, sizes: dict, seed: int, dtype, streams=None):
    """Logits [T, vocab] of one sequence, for the tests; `streams` [3, T]
    where its positions are not a text's."""
    key = W.key_for(seed)
    with jax.default_matmul_precision("highest"):
        programs = Programs(sizes, _window(sizes, len(tokens)))
        blocks, text = programs.embed(
            W.decoder_embed(key, sizes, dtype)["table"], tokens)
        if streams is not None:
            text = text.at[:, :len(tokens)].set(jnp.asarray(streams))
        for index in range(sizes["num_hidden_layers"]):
            layer = _f32(W.decoder_layer(key, index, sizes, dtype))
            blocks = programs.layer(layer, blocks, text)
        head = _f32(W.decoder_head(key, sizes, dtype))
        hidden = _rms_norm(head["ln_out"]["scale"], jnp.concatenate(blocks),
                           sizes["rms_norm_eps"])
        return (hidden @ head["lm_head"]["w"])[:len(tokens)]


def check(samples: list, sizes: dict, seed: int, dtype, control: bool = False,
          say=lambda message: None) -> dict:
    """samples: [{"prompt": [...], "served": [...]}].  Returns what
    latent_moe_lm.check does: `served_token_gap_std`, the widest gap of a
    sample's served tokens below the reference's best in standard
    deviations of that position's logits (a value a sample), and
    `served_token_gap_mean_std`, the mean over ALL the samples' served
    tokens (one value a run); for the control the same of the token that
    float8 weights put first.  A sequence at a time and a layer at a time:
    a layer's weights are made again for every sequence."""
    key = W.key_for(seed)
    eps = sizes["rms_norm_eps"]
    count = sizes["num_hidden_layers"]
    longest = max(len(s["prompt"]) + len(s["served"]) for s in samples)
    with jax.default_matmul_precision("highest"):
        programs = Programs(sizes, _window(sizes, longest))
        # the key is an argument: closed over, every seed would compile
        make = jax.jit(lambda key, i: _f32(
            W.decoder_layer(key, i, sizes, dtype)))
        to_fp8 = jax.jit(W.round_to_fp8)
        embed = jax.jit(lambda key: W.decoder_embed(key, sizes, dtype))
        ends = jax.jit(lambda key: _f32(W.decoder_head(key, sizes, dtype)))

        @jax.jit
        def project_out(head, hidden):
            return _rms_norm(head["ln_out"]["scale"], hidden, eps) @ \
                head["lm_head"]["w"]

        def logits_of(sample, lower: bool):
            row = list(sample["prompt"]) + list(sample["served"])[:-1]
            blocks, streams = programs.embed(embed(key)["table"], row)
            for index in range(count):
                layer = make(key, jnp.int32(index))
                if lower:
                    layer = to_fp8(layer)
                blocks = programs.layer(layer, blocks, streams)
                del layer
            head = ends(key)
            # the logits that chose served[j] sit at the position before it
            positions = len(sample["prompt"]) - 1 + \
                np.arange(len(sample["served"]))
            return project_out(to_fp8(head) if lower else head,
                               jnp.concatenate(blocks)[positions])

        gaps, control_gaps, tokens = [], [], 0
        means, control_means, sums = [], [], [0.0, 0.0]
        for sample in samples:
            served = jnp.asarray(np.asarray(sample["served"], np.int32))
            gap, control_gap = logit_gaps(
                logits_of(sample, False), served,
                logits_of(sample, True) if control else None)
            gaps.append(float(jnp.max(gap)))
            means.append(float(jnp.mean(gap)))
            sums[0] += float(jnp.sum(gap))
            tokens += len(sample["served"])
            if control:
                control_gaps.append(float(jnp.max(control_gap)))
                control_means.append(float(jnp.mean(control_gap)))
                sums[1] += float(jnp.sum(control_gap))
    say(f"reference: {len(samples)} sequences of up to {longest} tokens "
        f"through {count} layers, one at a time, in blocks of "
        f"{programs.block} against a window of {programs.window}")
    say(f"served token gaps, widest a sample {gaps}, mean a sample {means}"
        + (f"; the control's {control_gaps} and {control_means}"
           if control else ""))
    return {"positions": tokens,
            "numbers": {"served_token_gap_std": gaps,
                        "served_token_gap_mean_std": [sums[0] / tokens]},
            "control": {"served_token_gap_std": control_gaps,
                        "served_token_gap_mean_std": [sums[1] / tokens]}
            if control else None}
