"""Plain reference for the hybrid decoder: KDA linear-attention layers
(Kimi Linear, arXiv:2510.26692) beside sparse latent attention (DeepSeek
Sparse Attention over MLA without rotary), dense or routed feed-forward,
every sublayer inside a manifold-constrained hyper-connection
(arXiv:2512.24880).

float32 throughout, `jax.default_matmul_precision("highest")`, no kernels,
no cache, no batching, nothing of the program: KDA as the ONE-TOKEN
recurrence under `lax.scan` (never a chunked form), the sparse layer as
index scores, the best groups, and one softmax over the chosen positions
with EXPANDED keys and values (never the absorbed form), the held experts
as a loop over all tokens, the streams as written.  One sequence at a
time, one layer at a time, tokens in blocks of BLOCK handed one after
another to a few small programs whose shapes do not follow the sequence's
length (the sparse layer's keys are laid out to the served window and
masked), heads in groups, so that 31k positions and one layer's float32
weights (4.3 GB) fit and nothing compiles a second time.  Own weights
from the seed (benchmark/weights_hybrid_sparse.py).

  streams  X [n, dim].  x~ = rms(vec X) (eps hc_eps);  pre = sigmoid(a0 x~
           Phi_pre + b), post = 2 sigmoid(a1 x~ Phi_post + b), res = exp(a2
           x~ Phi_res + b) [n, n] normalised by rows then columns,
           hc_sinkhorn_iters times;  u = pre . X;  X <- res X + post^T
           F(rms(u)).  Embedding copied to the n streams, summed at the end.
  KDA      q, k, v = silu(causal conv of width 4 of x W_q, x W_k, x W_v);
           q, k unit length a head, q x D^-0.5;  g = lower_bound x sigmoid(
           exp(A_log) (x W_f1 W_f2 + dt_bias));  beta = sigmoid(x W_b);
           S <- diag(exp g) S;  S <- S - beta k (k^T S);  S <- S + beta k v^T;
           o = S^T q;  out = W_o (rms_head(o) * sigmoid(x W_g1 W_g2)).
  sparse   c_q = rms(x W_qa), q = c_q W_qb;  c_kv = rms(x W_kva);  k, v =
           c_kv W_kvb;  scale qk_dim^-0.5;  no rotary.  Indexer: q_I = c_q
           W_qI, k_I = layernorm(x W_kI), w = x W_w, rotary (pairs 2i, 2i+1)
           on the leading lanes;  keys mean-pooled over aligned groups of
           index_kpool;  score(t, g) = sum_j w_tj relu(q_I,tj . pooled_g) x
           (heads x 128)^-0.5 over complete groups before t's own;  t
           attends its own open group and the best index_topk / index_kpool
           - 1 complete groups.
  ffn      W_d (silu(min(W_g x, limit)) * clip(W_u x, -limit, limit));  or
           scores = sigmoid(x W_r), the 8 largest of scores + bias, weights
           the chosen scores over their sum x 2.5, the experts HELD and the
           shared expert.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_hybrid_sparse as W
from benchmark.reference.decoder_lm import logit_gaps

BLOCK = 512          # tokens handled at once
HEAD_GROUP = 8       # heads whose expanded keys and values exist at once


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


# -- streams ---------------------------------------------------------------------

def stream_maps(hc, streams, sizes: dict):
    """streams [B, n, dim] -> pre [B, n], post [B, n], res [B, n, n]."""
    n = sizes["hc_mult"]
    flat = streams.reshape(streams.shape[0], -1)
    raw = _rms_norm(hc["norm"]["scale"], flat, sizes["hc_eps"]) @ hc["phi"]
    a, b = hc["alpha"], hc["bias"]
    pre = jax.nn.sigmoid(a[0] * raw[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * raw[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(a[2] * raw[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    for _ in range(sizes["hc_sinkhorn_iters"]):
        res = res / res.sum(axis=2, keepdims=True)
        res = res / res.sum(axis=1, keepdims=True)
    return pre, post, res


def enter(hc, norm, streams, *, sizes: dict):
    """A block of streams [B, n, dim] -> (the sublayer's input [B, dim],
    post, res)."""
    pre, post, res = stream_maps(hc, streams, sizes)
    mixed = jnp.einsum("bn,bnd->bd", pre, streams)
    return _rms_norm(norm["scale"], mixed, sizes["rms_norm_eps"]), post, res


def leave(streams, post, res, out):
    """X <- res X + post^T F over a block."""
    return jnp.einsum("bij,bjd->bid", res, streams) + \
        post[:, :, None] * out[:, None, :]


# -- KDA -------------------------------------------------------------------------

def kda_start(sizes: dict) -> tuple:
    heads, d, taps, _ = W.kda_sizes(sizes)
    return (jnp.zeros((heads, d, d), jnp.float32),
            jnp.zeros((taps - 1, 3 * heads * d), jnp.float32))


def kda_block(p, carry, x, *, sizes: dict):
    """One block of tokens x [B, dim] through a KDA layer from the state
    and the convolution's tail `carry`, a token at a time: -> (the carry
    after it, [B, dim])."""
    heads, d, taps, _ = W.kda_sizes(sizes)
    lower = sizes["linear_attn_config"]["gate_lower_bound"]
    wide = heads * d
    state, tail = carry

    def unit(z):
        return z / jnp.sqrt((z * z).sum(axis=-1, keepdims=True) + 1e-6)

    before = jnp.concatenate(
        [x @ p["q"]["w"], x @ p["k"]["w"], x @ p["v"]["w"]], axis=-1)
    full = jnp.concatenate([tail, before], axis=0)
    rows = x.shape[0]
    mixed = jax.nn.silu(sum(full[i:i + rows] * p["conv"]["w"][i]
                            for i in range(taps)))
    q = unit(mixed[:, :wide].reshape(rows, heads, d)) * d ** -0.5
    k = unit(mixed[:, wide:2 * wide].reshape(rows, heads, d))
    v = mixed[:, 2 * wide:].reshape(rows, heads, d)
    rate = (x @ p["f_a"]["w"] @ p["f_b"]["w"] + p["dt_bias"]).reshape(
        rows, heads, d) * jnp.exp(p["a_log"])[None, :, None]
    g = lower * jax.nn.sigmoid(rate)
    beta = jax.nn.sigmoid(x @ p["b"]["w"])                    # [rows, heads]

    def token(state, xs):
        q, k, v, g, beta = xs
        state = state * jnp.exp(g)[:, :, None]
        state = state - beta[:, None, None] * k[:, :, None] * \
            jnp.einsum("hd,hdv->hv", k, state)[:, None, :]
        state = state + beta[:, None, None] * k[:, :, None] * v[:, None, :]
        return state, jnp.einsum("hd,hdv->hv", q, state)

    state, out = jax.lax.scan(token, state, (q, k, v, g, beta))
    out = _rms_norm(p["o_norm"]["scale"], out,
                    sizes["rms_norm_eps"]).reshape(rows, wide)
    gate = jax.nn.sigmoid(x @ p["g_a"]["w"] @ p["g_b"]["w"])
    return (state, full[rows:]), (out * gate) @ p["o"]["w"]


# -- sparse latent attention -----------------------------------------------------

def _rotary(x, first, sizes: dict):
    """x [T, heads, D] at positions first + [0, T): lanes (2i, 2i+1) of
    the leading rope lanes turned by position x theta^(-2i / rope lanes)."""
    lanes = sizes["assumed_sizes"]["index_rope_head_dim"]
    theta = float(sizes["assumed_sizes"]["index_rope_theta"])
    inverse = 1.0 / theta ** (np.arange(0, lanes, 2, dtype=np.float64)
                              / lanes)
    at = (first + jnp.arange(x.shape[0])).astype(jnp.float32)
    angles = at[:, None] * jnp.asarray(inverse, jnp.float32)[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0:lanes:2], x[..., 1:lanes:2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1).reshape(x.shape[:-1] + (lanes,))
    return jnp.concatenate([turned, x[..., lanes:]], axis=-1)


def sparse_project(layer, h, first, *, sizes: dict):
    """A block h [B, dim] at positions first + [0, B) -> queries [B, H,
    qk], latent rows [B, rank], indexer queries [B, J, 128], keys [B, 128]
    and weights [B, J]."""
    attn, indexer = layer["attn"], layer["indexer"]
    rows, eps = h.shape[0], sizes["rms_norm_eps"]
    index_heads, index_dim = sizes["index_n_heads"], sizes["index_head_dim"]
    c_q = _rms_norm(attn["q_norm"]["scale"], h @ attn["q_a"]["w"], eps)
    q = (c_q @ attn["q_b"]["w"]).reshape(rows, sizes["num_attention_heads"],
                                         sizes["qk_nope_head_dim"])
    c_kv = _rms_norm(attn["kv_norm"]["scale"], h @ attn["kv_a"]["w"], eps)
    q_i = _rotary((c_q @ indexer["q"]["w"]).reshape(rows, index_heads,
                                                    index_dim), first, sizes)
    k_i = h @ indexer["k"]["w"]
    mean = k_i.mean(axis=-1, keepdims=True)
    k_i = (k_i - mean) * jax.lax.rsqrt(
        ((k_i - mean) ** 2).mean(axis=-1, keepdims=True) + eps) * \
        indexer["k_norm"]["scale"] + indexer["k_norm"]["bias"]
    k_i = _rotary(k_i[:, None, :], first, sizes)[:, 0]
    weights = (h @ indexer["w"]["w"]) * (index_heads * index_dim) ** -0.5
    return q, c_kv, q_i, k_i, weights


def chosen_groups(scores, first_row, sizes: dict):
    """scores [B, G] of queries at positions first_row + [0, B) over the
    pooled keys of all G groups -> which groups each query attends beside
    its own open one [B, G]: complete groups before its own, the best
    index_topk / index_kpool - 1 of them."""
    pool = sizes["index_kpool"]
    most = sizes["index_topk"] // pool - 1
    rows, groups = scores.shape
    own = (first_row + jnp.arange(rows)) // pool
    whole = jnp.arange(groups)[None, :] < own[:, None]
    scores = jnp.where(whole, scores, -jnp.inf)
    if groups > most:
        floor = jnp.sort(scores, axis=-1)[:, -most][:, None]
        return whole & (scores >= floor)
    return whole


def visible_positions(q_i, weights, pooled, first, *, sizes: dict):
    """Which of the window's positions [B, T] the queries of a block at
    positions first + [0, B) attend: their own open group, and the groups
    chosen by the index scores over the pooled keys [T / pool, 128]."""
    pool = sizes["index_kpool"]
    dots = jnp.einsum("qjd,gd->qjg", q_i, pooled)
    scores = jnp.einsum("qj,qjg->qg", weights, jax.nn.relu(dots))
    groups = chosen_groups(scores, first, sizes)
    at = first + jnp.arange(q_i.shape[0])
    keys = jnp.arange(pooled.shape[0] * pool)
    return (jnp.repeat(groups, pool, axis=1) |
            (keys[None, :] // pool == at[:, None] // pool)) & \
        (keys[None, :] <= at[:, None])


def expand(c_kv, kv_b):
    """Latent rows [T, rank] x a group of heads' W_kvb [rank, G, qk + v]
    -> their keys and values [T, G, qk + v]."""
    return jnp.einsum("tc,chd->thd", c_kv, kv_b)


def attend(q, kv, visible, *, sizes: dict):
    """A block's queries of one group of heads [B, G, qk] over the
    window's expanded keys and values [T, G, qk + v] where `visible`
    [B, T]: one softmax a query and head."""
    nope = sizes["qk_nope_head_dim"]
    scores = jnp.einsum("qhd,khd->hqk", q, kv[..., :nope]) * \
        sizes["qk_head_dim"] ** -0.5
    scores = jnp.where(visible[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                      kv[..., nope:])


# -- feed-forward ----------------------------------------------------------------

def _swiglu(ffn, h, limit):
    return (jax.nn.silu(jnp.minimum(h @ ffn["gate"]["w"], limit)) *
            jnp.clip(h @ ffn["up"]["w"], -limit, limit)) @ ffn["down"]["w"]


def select(scores, bias, top_k: int, routed_scale: float):
    """`noaux_tc`: the top_k largest of score + bias; the weights are the
    chosen SCORES over their sum, times the scale -> [T, E]."""
    shifted = scores + bias
    kth = jnp.sort(shifted, axis=-1)[:, -top_k][:, None]
    kept = jnp.where(shifted >= kth, scores, 0.0)
    return kept / kept.sum(axis=-1, keepdims=True) * routed_scale


def feed_forward(layer, h, *, sizes: dict):
    """A block h [B, dim]: the dense MLP, or the shared expert and the
    experts HELD, one after another over every token."""
    limit = float(sizes["swiglu_limit"])
    if "experts" not in layer:
        return _swiglu(layer, h, limit)
    first = W.experts_first(sizes)
    weights = select(jax.nn.sigmoid(h @ layer["router"]["w"]),
                     layer["router"]["bias"], sizes["num_experts_per_tok"],
                     sizes["routed_scaling_factor"])

    def expert(out, e):
        one = jax.tree.map(lambda w: w[e], layer["experts"])
        gain = jax.lax.dynamic_index_in_dim(weights, first + e, axis=1)
        return out + gain * _swiglu(one, h, limit), None

    out, _ = jax.lax.scan(expert, _swiglu(layer["shared"], h, limit),
                          jnp.arange(sizes["n_routed_experts"]))
    return out


# -- whole passes ----------------------------------------------------------------

class Programs:
    """The block programs, compiled once each whatever a sequence's
    length; `window` is the length the sparse layer's keys are laid out
    to (the served window, or a test's own sequence)."""

    def __init__(self, sizes: dict, window: int):
        self.sizes, self.window = sizes, window
        self.block = min(BLOCK, window)
        bound = functools.partial
        self.enter = jax.jit(bound(enter, sizes=sizes))
        self.leave = jax.jit(leave, donate_argnums=(0,))
        self.kda_block = jax.jit(bound(kda_block, sizes=sizes))
        self.sparse_project = jax.jit(bound(sparse_project, sizes=sizes))
        self.visible = jax.jit(bound(visible_positions, sizes=sizes))
        self.expand = jax.jit(expand)
        self.attend = jax.jit(bound(attend, sizes=sizes))
        self.feed_forward = jax.jit(bound(feed_forward, sizes=sizes))
        self.project_out = jax.jit(lambda w, x: x @ w)

    def sublayer(self, hc, norm, blocks: list, mixing) -> list:
        entered = [self.enter(hc, norm, block) for block in blocks]
        outs = mixing([mixed for mixed, _, _ in entered])
        return [self.leave(block, post, res, out)
                for block, (_, post, res), out in zip(blocks, entered, outs)]

    def kda(self, layer, inputs: list) -> list:
        carry, outs = kda_start(self.sizes), []
        for h in inputs:
            carry, out = self.kda_block(layer["kda"], carry, h)
            outs.append(out)
        return outs

    def sparse(self, layer, inputs: list, share: bool = False) -> list:
        """`share`: also leave the share of causal pairs attended in
        `attended_share` (a host sync: for the tests)."""
        sizes, block, window = self.sizes, self.block, self.window
        pool = sizes["index_kpool"]
        heads, v_dim = sizes["num_attention_heads"], sizes["v_head_dim"]
        parts = [self.sparse_project(layer, h, jnp.int32(i * block))
                 for i, h in enumerate(inputs)]
        pad = window - len(inputs) * block

        def whole(index):
            rows = jnp.concatenate([part[index] for part in parts])
            return jnp.pad(rows, ((0, pad), (0, 0)))

        c_kv, k_i = whole(1), whole(3)
        pooled = k_i.reshape(window // pool, pool, -1).mean(axis=1)
        visible = [self.visible(part[2], part[4], pooled,
                                jnp.int32(i * block))
                   for i, part in enumerate(parts)]
        kv_b = layer["attn"]["kv_b"]["w"].reshape(c_kv.shape[1], heads, -1)
        attended = [[] for _ in parts]
        for first in range(0, heads, HEAD_GROUP):
            kv = self.expand(c_kv, kv_b[:, first:first + HEAD_GROUP])
            for i, part in enumerate(parts):
                attended[i].append(self.attend(
                    part[0][:, first:first + HEAD_GROUP], kv, visible[i]))
        if share:
            self.attended_share = float(sum(v.sum() for v in visible)) / (
                (len(inputs) * block) * (len(inputs) * block + 1) // 2)
        return [self.project_out(
            layer["attn"]["o"]["w"],
            jnp.concatenate(groups, axis=1).reshape(block, heads * v_dim))
            for groups in attended]

    def layer(self, layer, blocks: list) -> list:
        mixing = self.kda if "kda" in layer else self.sparse
        blocks = self.sublayer(layer["hc_attn"], layer["ln_attn"], blocks,
                               functools.partial(mixing, layer))
        return self.sublayer(
            layer["hc_mlp"], layer["ln_mlp"], blocks,
            lambda inputs: [self.feed_forward(layer, h) for h in inputs])

    def embed(self, table, row) -> list:
        """A sequence's tokens -> its blocks of streams [B, n, dim]."""
        block = self.block
        padded = np.zeros((-(-len(row) // block) * block,), np.int32)
        padded[:len(row)] = row
        x = table[padded].astype(jnp.float32)
        streams = jnp.broadcast_to(
            x[:, None, :], (x.shape[0], self.sizes["hc_mult"], x.shape[1]))
        return [streams[i:i + block] for i in range(0, len(padded), block)]


def _f32(tree):
    return jax.tree.map(lambda leaf: leaf.astype(jnp.float32), tree)


def _window(sizes: dict, longest: int) -> int:
    """The served window where the file names one that holds the
    sequence, else the sequence's own length in whole groups (or blocks)."""
    window = sizes.get("serving", {}).get("max_seq", 0)
    if window >= longest:
        return window
    unit = sizes["index_kpool"] if longest <= BLOCK else BLOCK
    return -(-longest // unit) * unit


def forward_logits(tokens, sizes: dict, seed: int, dtype, transform=None):
    """Logits [T, vocab] of one sequence, for the tests."""
    key = W.key_for(seed)
    transform = transform or (lambda tree: tree)
    with jax.default_matmul_precision("highest"):
        programs = Programs(sizes, _window(sizes, len(tokens)))
        blocks = programs.embed(W.decoder_embed(key, sizes, dtype)["table"],
                                tokens)
        for index in range(sizes["num_hidden_layers"]):
            layer = transform(_f32(W.decoder_layer(
                key, index, sizes, dtype, W.layer_kind(sizes, index))))
            blocks = programs.layer(layer, blocks)
        head = transform(_f32(W.decoder_head(key, sizes, dtype)))
        hidden = _rms_norm(head["ln_out"]["scale"],
                           jnp.concatenate(blocks).sum(axis=1),
                           sizes["rms_norm_eps"])
        return (hidden @ head["lm_head"]["w"])[:len(tokens)]


def sparse_attention(layer, h, sizes: dict):
    """One sparse layer over one sequence h [T, dim], T whole blocks (or
    one): -> ([T, dim], the share of causal pairs attended); for the
    tests."""
    programs = Programs(sizes, _window(sizes, h.shape[0]))
    block = programs.block
    outs = programs.sparse(layer, [h[i:i + block]
                                   for i in range(0, h.shape[0], block)],
                           share=True)
    return jnp.concatenate(outs), programs.attended_share


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
    kinds = [W.layer_kind(sizes, i) for i in range(count)]
    longest = max(len(s["prompt"]) + len(s["served"]) for s in samples)
    with jax.default_matmul_precision("highest"):
        programs = Programs(sizes, _window(sizes, longest))
        # the key is an argument: closed over, every seed would compile
        make = {kind: jax.jit(lambda key, i, kind=kind: _f32(
            W.decoder_layer(key, i, sizes, dtype, kind)))
            for kind in set(kinds)}
        to_fp8 = jax.jit(W.round_to_fp8)
        embed = jax.jit(lambda key: W.decoder_embed(key, sizes, dtype))
        ends = jax.jit(lambda key: _f32(W.decoder_head(key, sizes, dtype)))

        @jax.jit
        def project(head, streams):
            hidden = _rms_norm(head["ln_out"]["scale"], streams.sum(axis=1),
                               eps)
            return hidden @ head["lm_head"]["w"]

        def logits_of(sample, lower: bool):
            row = list(sample["prompt"]) + list(sample["served"])[:-1]
            blocks = programs.embed(embed(key)["table"], row)
            for index in range(count):
                layer = make[kinds[index]](key, jnp.int32(index))
                if lower:
                    layer = to_fp8(layer)
                blocks = programs.layer(layer, blocks)
                del layer
            head = ends(key)
            # the logits that chose served[j] sit at the position before it
            positions = len(sample["prompt"]) - 1 + \
                np.arange(len(sample["served"]))
            return project(to_fp8(head) if lower else head,
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
