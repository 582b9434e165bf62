"""Plain reference for the Gated-DeltaNet hybrid decoder (Olmo-Hybrid-7B,
`model_type` olmo_hybrid): recurrent layers of the gated delta rule with one
gate a head (Gated DeltaNet, arXiv:2412.06464; the keys are
flash-linear-attention's) beside full softmax attention, three to one.

float32 throughout, `jax.default_matmul_precision("highest")`, no kernels,
no cache, no chunks, nothing of the program: the recurrence is the ONE-TOKEN
rule under `lax.scan`, the attention a plain causal softmax over the whole
sequence.  One layer at a time (a layer's float32 weights, 0.86 GB, are all
that is resident of the model) over the sample's sequences in blocks of ROWS
rows, so that it fits beside whatever else the chip holds.  Own weights from
the seed (benchmark/weights_gated_delta.py).

  gdn    u the block's input.  q~, k~, v~ = u W_q, u W_k, u W_v; every
         channel c of the three through a causal convolution of 4 taps,
         y_t[c] = sum_i w[i, c] x_{t-3+i}[c] (w[3] meets the token itself,
         zeros before the sequence, no bias), then SiLU; a head has q, k of
         96 and v of 192;  q = q~/|q~| x 96^-0.5, k = k~/|k~|;  beta = 2
         sigmoid(u W_b);  g = -exp(A_log) softplus(u W_a + dt_bias), a
         number a head;  S [96, 192] a head from zeros:  S <- exp(g) S;
         S <- S + k (beta (v - S^T k))^T;  o = S^T q;  y = (rms_192(o) x
         scale * silu(u W_g)) W_o.
  full   q, k, v = u W_q, u W_k, u W_v; q and k through a learned RMSNorm
         over their WHOLE width, then 30 heads of 128; no rotary; causal
         softmax at 128^-0.5; W_o.
  block  h = x + rms(mix(x));  out = h + rms((silu(h W_gate) * h W_up)
         W_down);  a final RMSNorm and an untied head.

Departures from the published description, each forced by what is
published: the configuration's file lists them under `assumed` (the block
order and the QK-norm are Olmo 2/3's convention, `rope_theta: null` is read
as no rotary, the convolution has no bias, the L2 norm's epsilon 1e-6 lies
under the root; `linear_allow_neg_eigval` false would make beta a plain
sigmoid).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_gated_delta as W
from benchmark.reference.decoder_lm import logit_gaps

ROWS = 5             # sequences a block


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def gdn_mix(p, u, *, sizes: dict):
    """The recurrent layer's token mixing over one sequence u [T, dim],
    token by token from a state of zeros."""
    heads, dk, dv, taps = W.gdn_sizes(sizes)
    t = u.shape[0]
    pre = jnp.concatenate([u @ p[name]["w"] for name in "qkv"], axis=-1)
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, pre.shape[1]), pre.dtype), pre])
    mixed = jax.nn.silu(sum(padded[i:i + t] * p["conv"]["w"][i]
                            for i in range(taps)))
    q = mixed[:, :heads * dk].reshape(t, heads, dk)
    k = mixed[:, heads * dk:2 * heads * dk].reshape(t, heads, dk)
    v = mixed[:, 2 * heads * dk:].reshape(t, heads, dv)

    def unit(z):
        return z / jnp.sqrt((z * z).sum(axis=-1, keepdims=True) + 1e-6)

    q, k = unit(q) * dk ** -0.5, unit(k)
    beta = jax.nn.sigmoid(u @ p["b"]["w"]) * (
        2.0 if sizes["linear_allow_neg_eigval"] else 1.0)
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(u @ p["a"]["w"] + p["dt_bias"])

    def token(state, xs):
        q, k, v, g, beta = xs                           # a head: [H, ..]
        state = state * jnp.exp(g)[:, None, None]
        seen = jnp.einsum("hd,hdv->hv", k, state)
        state = state + k[:, :, None] * (beta[:, None] * (v - seen))[:, None]
        return state, jnp.einsum("hd,hdv->hv", q, state)

    _, out = jax.lax.scan(token, jnp.zeros((heads, dk, dv), jnp.float32),
                          (q, k, v, g, beta))
    out = _rms_norm(p["o_norm"]["scale"], out, sizes["rms_norm_eps"])
    return (out.reshape(t, heads * dv) *
            jax.nn.silu(u @ p["g"]["w"])) @ p["o"]["w"]


def full_mix(p, u, *, sizes: dict):
    """The full layer's attention over one sequence u [T, dim], causal."""
    heads, eps = sizes["num_attention_heads"], sizes["rms_norm_eps"]
    t = u.shape[0]
    q = _rms_norm(p["q_norm"]["scale"], u @ p["q"]["w"], eps)
    k = _rms_norm(p["k_norm"]["scale"], u @ p["k"]["w"], eps)
    q, k, v = (z.reshape(t, heads, -1) for z in (q, k, u @ p["v"]["w"]))
    scores = jnp.einsum("qhd,khd->hqk", q, k) * q.shape[-1] ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attended = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return attended.reshape(t, -1) @ p["o"]["w"]


def layer_forward(layer, x, *, sizes: dict):
    """One block over one sequence x [T, dim]: the norms AFTER the mixing
    and after the feed-forward."""
    eps = sizes["rms_norm_eps"]
    mixed = gdn_mix(layer["gdn"], x, sizes=sizes) if "gdn" in layer \
        else full_mix(layer["attn"], x, sizes=sizes)
    h = x + _rms_norm(layer["ln_attn"]["scale"], mixed, eps)
    fed = (jax.nn.silu(h @ layer["gate"]["w"]) * (h @ layer["up"]["w"])) \
        @ layer["down"]["w"]
    return h + _rms_norm(layer["ln_mlp"]["scale"], fed, eps)


def _f32(tree):
    return jax.tree.map(lambda leaf: leaf.astype(jnp.float32), tree)


class Programs:
    """The few programs a check compiles, whatever the seed and however
    many sequences: a layer of each kind over a block of ROWS sequences,
    the layers' weights, the ends."""

    def __init__(self, sizes: dict, dtype):
        self.sizes = sizes
        self.make = {kind: jax.jit(lambda key, i, kind=kind: _f32(
            W.decoder_layer(key, i, sizes, dtype, kind)))
            for kind in ("gdn", "full")}
        self.forward = jax.jit(jax.vmap(
            functools.partial(layer_forward, sizes=sizes), in_axes=(None, 0)))
        self.to_fp8 = jax.jit(W.round_to_fp8)
        self.embed = jax.jit(lambda key: W.decoder_embed(key, sizes, dtype))
        self.ends = jax.jit(lambda key: _f32(
            W.decoder_head(key, sizes, dtype)))

    def hidden(self, key, tokens, lower: bool = False):
        """tokens [R, T] (numpy) -> the residual after the last layer, a
        list of blocks [ROWS, T, dim]; `lower` rounds every matrix to
        float8 first (the control)."""
        table = self.embed(key)["table"]
        rows = -(-len(tokens) // ROWS) * ROWS
        padded = np.zeros((rows, tokens.shape[1]), np.int32)
        padded[:len(tokens)] = tokens
        blocks = [table[padded[i:i + ROWS]].astype(jnp.float32)
                  for i in range(0, rows, ROWS)]
        del table
        for index, kind in enumerate(W.kinds(self.sizes)):
            layer = self.make[kind](key, jnp.int32(index))
            if lower:
                layer = self.to_fp8(layer)
            blocks = [self.forward(layer, block) for block in blocks]
            del layer
        return blocks


def forward_logits(tokens, sizes: dict, seed: int, dtype):
    """Teacher-forced logits [R, T, vocab] of tokens [R, T] on the
    benchmark's weights for `seed`."""
    tokens = np.asarray(tokens, np.int32)
    key = W.key_for(seed)
    with jax.default_matmul_precision("highest"):
        programs = Programs(sizes, dtype)
        hidden = jnp.concatenate(programs.hidden(key, tokens))[:len(tokens)]
        head = programs.ends(key)
        return _rms_norm(head["ln_out"]["scale"], hidden,
                         sizes["rms_norm_eps"]) @ head["lm_head"]["w"]


def check(samples: list, sizes: dict, seed: int, dtype, control: bool = False,
          say=lambda message: None) -> dict:
    """samples: [{"prompt": [...], "served": [...]}].  Returns what
    latent_moe_lm.check does: `served_token_gap_std`, the widest gap of a
    sample's served tokens below the reference's best in standard
    deviations of that position's logits (a value a sample), and
    `served_token_gap_mean_std`, the mean over ALL the samples' served
    tokens (one value a run); for the control the same of the token that
    float8 weights put first."""
    key = W.key_for(seed)
    eps = sizes["rms_norm_eps"]
    rows = [list(s["prompt"]) + list(s["served"])[:-1] for s in samples]
    longest = max(len(row) for row in rows)
    width = -(-longest // 128) * 128
    tokens = np.zeros((len(rows), width), np.int32)
    for i, row in enumerate(rows):
        tokens[i, :len(row)] = row
    with jax.default_matmul_precision("highest"):
        programs = Programs(sizes, dtype)

        @jax.jit
        def project(head, hidden, positions):
            return _rms_norm(head["ln_out"]["scale"], hidden[positions],
                             eps) @ head["lm_head"]["w"]

        def logits_of(lower: bool) -> list:
            blocks = programs.hidden(key, tokens, lower)
            head = programs.ends(key)
            if lower:
                head = programs.to_fp8(head)
            out = []
            for i, sample in enumerate(samples):
                # the logits that chose served[j] sit at the position
                # before it
                positions = len(sample["prompt"]) - 1 + \
                    np.arange(len(sample["served"]))
                out.append(project(head, blocks[i // ROWS][i % ROWS],
                                   positions))
            return out

        sound = logits_of(False)
        lowered = logits_of(True) if control else [None] * len(samples)
        gaps, control_gaps, tokens_seen = [], [], 0
        means, control_means, sums = [], [], [0.0, 0.0]
        for sample, logits, control_logits in zip(samples, sound, lowered):
            served = jnp.asarray(np.asarray(sample["served"], np.int32))
            gap, control_gap = logit_gaps(logits, served, control_logits)
            gaps.append(float(jnp.max(gap)))
            means.append(float(jnp.mean(gap)))
            sums[0] += float(jnp.sum(gap))
            tokens_seen += len(sample["served"])
            if control:
                control_gaps.append(float(jnp.max(control_gap)))
                control_means.append(float(jnp.mean(control_gap)))
                sums[1] += float(jnp.sum(control_gap))
    say(f"reference: {len(samples)} sequences of up to {longest} tokens "
        f"through {sizes['num_hidden_layers']} layers, one at a time, in "
        f"blocks of {ROWS} rows of {width}")
    say(f"served token gaps, widest a sample {gaps}, mean a sample {means}"
        + (f"; the control's {control_gaps} and {control_means}"
           if control else ""))
    return {"positions": tokens_seen,
            "numbers": {"served_token_gap_std": gaps,
                        "served_token_gap_mean_std": [sums[0] / tokens_seen]},
            "control": {"served_token_gap_std": control_gaps,
                        "served_token_gap_mean_std": [sums[1] / tokens_seen]}
            if control else None}
