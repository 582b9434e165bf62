"""Plain reference for the Mamba-2 hybrid decoder (granite-4.0-h-micro,
`model_type` granitemoehybrid with no expert: state-space layers of Mamba-2,
arXiv:2405.21060, whose B and C every head shares, beside grouped-query
attention without positions, nine to one), written from the published keys
and checked line by line against `transformers`'
`models/granitemoehybrid/modeling_granitemoehybrid.py` (tests/
test_ssm_hybrid_layers.py holds it to that implementation where it imports).

float32 throughout, `jax.default_matmul_precision("highest")`, no kernels,
no cache, no chunks, nothing of the program: the recurrence is the ONE-TOKEN
rule under `lax.scan` from zeros, the convolution four shifted products, the
attention a plain causal softmax over the whole sequence with K and V
repeated over their groups.  One layer at a time (a layer's float32 weights,
0.15 GB, are all that is resident of the model beside the embedding) over
the sample's sequences in blocks of ROWS rows, so that it fits beside
whatever else the chip holds and compiles a few programs whatever the
sample.  Own weights from the seed (benchmark/weights_ssm_hybrid.py).

  x_0    = embedding_multiplier E[token]
  block  h = x + r mix(rms(x));  out = h + r W_down (silu(u W_gate) *
         u W_up), u = rms(h), r = residual_multiplier; both norms learned,
         eps `rms_norm_eps`
  mamba  [z | xBC | dt~] = u W_in (widths 4,096 | 4,352 | 64);  every
         channel c of xBC through a causal convolution of 4 taps with a
         bias, y_t[c] = b[c] + sum_i w[i, c] x_{t-3+i}[c] (w[3] meets the
         token itself, zeros before the sequence), then SiLU;  x (64 heads
         of 64) | B (128) | C (128);  dt = softplus(dt~ + dt_bias), A =
         -exp(A_log), a head;  S [64, 128] a head from zeros:
             S <- exp(dt A) S + dt x B^T;   y = S C + D x
         out = (w * rmsnorm_4096(y * silu(z))) W_out: the gate first, ONE
         norm over all 4,096 channels (`mamba_n_groups` 1)
  attn   q, k, v = u W_q, u W_k, u W_v, 32 / 8 / 8 heads of 64, no rotary,
         causal softmax of attention_multiplier q . k, W_o
  logits = rms(x_40) E^T / logits_scaling (the head is the embedding)
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_ssm_hybrid as W
from benchmark.reference.decoder_lm import logit_gaps

ROWS = 3             # sequences a block


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def mamba_mix(p, u, *, sizes: dict):
    """The Mamba-2 layer's token mixing over one sequence u [T, dim], token
    by token from a state of zeros."""
    heads, width, n, taps = W.mamba_sizes(sizes)
    inner = heads * width
    t = u.shape[0]
    projected = u @ p["in"]["w"]
    z, pre, rate = (projected[:, :inner],
                    projected[:, inner:2 * inner + 2 * n],
                    projected[:, 2 * inner + 2 * n:])
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, pre.shape[1]), pre.dtype), pre])
    mixed = jax.nn.silu(p["conv"]["b"] + sum(
        padded[i:i + t] * p["conv"]["w"][i] for i in range(taps)))
    x = mixed[:, :inner].reshape(t, heads, width)
    b, c = mixed[:, inner:inner + n], mixed[:, inner + n:]
    dt = jax.nn.softplus(rate + p["dt_bias"])                    # [T, H]
    a = -jnp.exp(p["a_log"])

    def token(state, xs):
        x, b, c, dt = xs                     # [H, P], [N], [N], [H]
        state = state * jnp.exp(dt * a)[:, None, None] + \
            (dt[:, None] * x)[:, :, None] * b[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c) + \
            p["d"][:, None] * x

    _, out = jax.lax.scan(token, jnp.zeros((heads, width, n), jnp.float32),
                          (x, b, c, dt))
    gated = out.reshape(t, inner) * jax.nn.silu(z)
    return _rms_norm(p["norm"]["scale"], gated,
                     sizes["rms_norm_eps"]) @ p["out"]["w"]


def attention_mix(p, u, *, sizes: dict):
    """The attention layer over one sequence u [T, dim], causal, K and V
    repeated over their groups."""
    heads, kv_heads = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"]
    t = u.shape[0]
    q = (u @ p["q"]["w"]).reshape(t, heads, -1)
    k, v = ((u @ p[name]["w"]).reshape(t, kv_heads, -1) for name in "kv")
    k, v = (jnp.repeat(z, heads // kv_heads, axis=1) for z in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) * sizes["attention_multiplier"]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attended = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return attended.reshape(t, -1) @ p["o"]["w"]


def layer_forward(layer, x, *, sizes: dict):
    """One pre-norm block over one sequence x [T, dim]."""
    eps, r = sizes["rms_norm_eps"], sizes["residual_multiplier"]
    u = _rms_norm(layer["ln_attn"]["scale"], x, eps)
    mixed = mamba_mix(layer["mamba"], u, sizes=sizes) if "mamba" in layer \
        else attention_mix(layer["attn"], u, sizes=sizes)
    h = x + r * mixed
    u = _rms_norm(layer["ln_mlp"]["scale"], h, eps)
    fed = (jax.nn.silu(u @ layer["gate"]["w"]) * (u @ layer["up"]["w"])) \
        @ layer["down"]["w"]
    return h + r * fed


def _f32(tree):
    return jax.tree.map(lambda leaf: leaf.astype(jnp.float32), tree)


class Programs:
    """The few programs a check compiles, whatever the seed and however
    many sequences: a layer of each kind over a block of ROWS sequences,
    the layers' weights, the ends.  `programs_for` keeps them for the next
    check of the same sizes."""

    def __init__(self, sizes: dict, dtype):
        self.sizes = sizes
        self.make = {kind: jax.jit(lambda key, i, kind=kind: _f32(
            W.decoder_layer(key, i, sizes, dtype, kind))) for kind in W.KINDS}
        self.forward = jax.jit(jax.vmap(
            functools.partial(layer_forward, sizes=sizes), in_axes=(None, 0)))
        self.to_fp8 = jax.jit(W.round_to_fp8)
        self.embed = jax.jit(lambda key: W.decoder_embed(key, sizes, dtype))
        self.ends = jax.jit(lambda key: _f32(
            W.decoder_head(key, sizes, dtype) | {
                "embed": W.decoder_embed(key, sizes, dtype)}))
        self.project = jax.jit(lambda head, hidden, positions: _project(
            head, hidden[positions], sizes))

    def hidden(self, key, tokens, lower: bool = False):
        """tokens [R, T] (numpy) -> the residual after the last layer, a
        list of blocks [ROWS, T, dim]; `lower` rounds every matrix to
        float8 first (the control)."""
        table = self.embed(key)
        if lower:
            table = self.to_fp8(table)
        table = table["table"]
        rows = -(-len(tokens) // ROWS) * ROWS
        padded = np.zeros((rows, tokens.shape[1]), np.int32)
        padded[:len(tokens)] = tokens
        blocks = [table[padded[i:i + ROWS]].astype(jnp.float32) *
                  self.sizes["embedding_multiplier"]
                  for i in range(0, rows, ROWS)]
        del table
        for index, kind in enumerate(W.kinds(self.sizes)):
            layer = self.make[kind](key, jnp.int32(index))
            if lower:
                layer = self.to_fp8(layer)
            blocks = [self.forward(layer, block) for block in blocks]
            del layer
        return blocks

    def head(self, key, lower: bool = False):
        """The final norm's scale and the embedding that is the head."""
        ends = self.ends(key)
        return self.to_fp8(ends) if lower else ends


@functools.lru_cache(maxsize=4)
def _programs(sizes_json: str, dtype) -> Programs:
    return Programs(json.loads(sizes_json), dtype)


def programs_for(sizes: dict, dtype) -> Programs:
    return _programs(json.dumps(sizes, sort_keys=True, default=str),
                     str(jnp.dtype(dtype)))


def _project(head, hidden, sizes: dict):
    return _rms_norm(head["ln_out"]["scale"], hidden,
                     sizes["rms_norm_eps"]) @ head["embed"]["table"].T / \
        sizes["logits_scaling"]


def forward_logits(tokens, sizes: dict, seed: int, dtype):
    """Teacher-forced logits [R, T, vocab] of tokens [R, T] on the
    benchmark's weights for `seed`."""
    tokens = np.asarray(tokens, np.int32)
    key = W.key_for(seed)
    with jax.default_matmul_precision("highest"):
        programs = programs_for(sizes, dtype)
        hidden = jnp.concatenate(programs.hidden(key, tokens))[:len(tokens)]
        return _project(programs.head(key), hidden, sizes)


def check(samples: list, sizes: dict, seed: int, dtype, control: bool = False,
          say=lambda message: None) -> dict:
    """samples: [{"prompt": [...], "served": [...]}].  Returns what
    gated_delta_lm.check does: `served_token_gap_std`, the widest gap of a
    sample's served tokens below the reference's best in standard
    deviations of that position's logits (a value a sample), and
    `served_token_gap_mean_std`, the mean over ALL the samples' served
    tokens (one value a run); for the control the same of the token that
    float8 weights put first."""
    key = W.key_for(seed)
    rows = [list(s["prompt"]) + list(s["served"])[:-1] for s in samples]
    longest = max(len(row) for row in rows)
    width = -(-longest // 128) * 128
    tokens = np.zeros((len(rows), width), np.int32)
    for i, row in enumerate(rows):
        tokens[i, :len(row)] = row
    with jax.default_matmul_precision("highest"):
        programs = programs_for(sizes, dtype)

        def logits_of(lower: bool) -> list:
            blocks = programs.hidden(key, tokens, lower)
            head = programs.head(key, lower)
            out = []
            for i, sample in enumerate(samples):
                # the logits that chose served[j] sit at the position
                # before it
                positions = len(sample["prompt"]) - 1 + \
                    np.arange(len(sample["served"]))
                out.append(programs.project(
                    head, blocks[i // ROWS][i % ROWS], positions))
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
    distinct = [len(set(s["served"])) / len(s["served"]) for s in samples]
    say(f"reference: {len(samples)} sequences of up to {longest} tokens "
        f"through {sizes['num_hidden_layers']} layers, one at a time, in "
        f"blocks of {ROWS} rows of {width}; distinct tokens a served token, "
        f"a sample: {[round(d, 2) for d in distinct]}")
    say(f"served token gaps, widest a sample {gaps}, mean a sample {means}"
        + (f"; the control's {control_gaps} and {control_means}"
           if control else ""))
    return {"positions": tokens_seen,
            "numbers": {"served_token_gap_std": gaps,
                        "served_token_gap_mean_std": [sums[0] / tokens_seen]},
            "control": {"served_token_gap_std": control_gaps,
                        "served_token_gap_mean_std": [sums[1] / tokens_seen]}
            if control else None}
