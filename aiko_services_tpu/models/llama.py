# Llama-style decoder-only transformer, TPU-native.
#
# Parity target: BASELINE.md config 5 ("xgo_robot vision+ASR+Llama-3-8B
# agent sharded over v5e-16") — the reference only reaches an LLM through
# an HTTP hop (reference: examples/speech/speech_elements.py:155-172); here
# the model is native so the agent element shards over the mesh (TP on
# heads/ffn via logical axes, GQA KV heads, RoPE, RMSNorm, SwiGLU).

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import layers as L

__all__ = ["LlamaConfig", "llama_init", "llama_axes", "llama_forward",
           "llama_forward_sp", "llama_decode_step", "llama_greedy_decode",
           "llama_ffn", "init_llama_caches", "LLAMA_PRESETS",
           "SCOPE_KV_VIEW", "SCOPE_ATTN_PROJ", "SCOPE_ATTN_CORE",
           "SCOPE_MLP", "SCOPE_HEAD", "SCOPE_KV_MERGE"]


# jax.named_scope regions of the serving programs (ISSUE 24): HLO
# metadata only, never the computation.  A device trace charges each
# operation to the region its root carries (PERF.md, "How a trace names
# things"); the benchmark's region metrics read these names, so they
# are part of its yardstick.  Nothing finer than these six.
SCOPE_KV_VIEW = "aiko.kv_view"       # slot-major K and V views of the pool
SCOPE_ATTN_PROJ = "aiko.attn_proj"   # attention's norm, q/k/v/o, rotary
SCOPE_ATTN_CORE = "aiko.attn_core"   # scores, mask, softmax, weights x V
SCOPE_MLP = "aiko.mlp"               # the MLP and its norm
SCOPE_HEAD = "aiko.head"             # final norm, output head, argmax
SCOPE_KV_MERGE = "aiko.kv_merge"     # the round's K and V into the pool


@dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 128256
    dim: int = 4096
    ffn_dim: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    dtype: object = jnp.float32
    # num_experts > 0 swaps the dense SwiGLU FFN for a top-k
    # mixture-of-experts layer (models/moe.py) — the Mixtral-style
    # geometry.  Every path (prefill, SP forward, ContinuousDecoder)
    # routes through llama_ffn, so the MoE variant serves identically.
    num_experts: int = 0
    top_k: int = 2

    @property
    def head_dim(self):
        return self.dim // self.num_heads

    @property
    def cache_leaves(self) -> tuple:
        """(heads, lanes) of each leaf a layer keeps of a token in the
        paged pool: a K and a V leaf (serving_paged.BlockPool)."""
        return ((self.num_kv_heads, self.head_dim),) * 2

    def paged_model(self):
        """The layer functions the paged decoder serves this model
        through (serving_paged.PagedModel)."""
        from ..serving_paged import GQA_PAGED_MODEL
        return GQA_PAGED_MODEL

    def moe_config(self):
        from .moe import MoeConfig
        return MoeConfig(dim=self.dim, ffn_dim=self.ffn_dim,
                         num_experts=self.num_experts,
                         top_k=self.top_k, dtype=self.dtype)


LLAMA_PRESETS = {
    # llama-3-8b geometry (the BASELINE agent config)
    "8b": LlamaConfig(),
    # scaled-down variants for tests / CI / single-chip smoke
    "tiny": LlamaConfig(vocab=256, dim=64, ffn_dim=128, num_layers=2,
                        num_heads=4, num_kv_heads=2, max_seq_len=128),
    "1b": LlamaConfig(vocab=128256, dim=2048, ffn_dim=8192, num_layers=16,
                      num_heads=32, num_kv_heads=8),
    # MoE variants (Mixtral-style FFN): tiny for tests/dryrun, 8x1b as
    # the serving-scale geometry
    "tiny_moe": LlamaConfig(vocab=256, dim=64, ffn_dim=128, num_layers=2,
                            num_heads=4, num_kv_heads=2, max_seq_len=128,
                            num_experts=4, top_k=2),
    "8x1b": LlamaConfig(vocab=128256, dim=2048, ffn_dim=8192,
                        num_layers=16, num_heads=32, num_kv_heads=8,
                        num_experts=8, top_k=2),
}


def _layer_init(key, config: LlamaConfig):
    keys = jax.random.split(key, 4)
    dim, dtype = config.dim, config.dtype
    layer = {
        "ln_attn": L.rms_norm_init(dim, dtype),
        "attn": L.mha_init(keys[0], dim, config.num_heads,
                           config.num_kv_heads, bias=False, dtype=dtype),
        "ln_mlp": L.rms_norm_init(dim, dtype),
    }
    if config.num_experts:
        from .moe import moe_init
        layer["moe"] = moe_init(keys[1], config.moe_config())
    else:
        layer |= {
            "gate": L.linear_init(keys[1], dim, config.ffn_dim,
                                  bias=False, dtype=dtype),
            "up": L.linear_init(keys[2], dim, config.ffn_dim,
                                bias=False, dtype=dtype),
            "down": L.linear_init(keys[3], config.ffn_dim, dim,
                                  bias=False, dtype=dtype),
        }
    return layer


def _layer_axes(config: LlamaConfig | None = None):
    axes = {
        "ln_attn": L.rms_norm_axes(),
        "attn": L.mha_axes(bias=False),
        "ln_mlp": L.rms_norm_axes(),
    }
    if config is not None and config.num_experts:
        from .moe import moe_axes
        axes["moe"] = moe_axes()
    else:
        axes |= {
            "gate": L.linear_axes("embed", "ffn", bias=False),
            "up": L.linear_axes("embed", "ffn", bias=False),
            "down": L.linear_axes("ffn", "embed", bias=False),
        }
    return axes


def llama_init(key, config: LlamaConfig):
    keys = jax.random.split(key, config.num_layers + 2)
    return {
        "embed": L.embedding_init(keys[0], config.vocab, config.dim,
                                  config.dtype),
        "layers": [_layer_init(keys[i + 1], config)
                   for i in range(config.num_layers)],
        "ln_out": L.rms_norm_init(config.dim, config.dtype),
        "lm_head": L.linear_init(keys[-1], config.dim, config.vocab,
                                 bias=False, dtype=config.dtype),
    }


def llama_axes(config: LlamaConfig):
    return {
        "embed": L.embedding_axes(),
        "layers": [_layer_axes(config)] * config.num_layers,
        "ln_out": L.rms_norm_axes(),
        "lm_head": L.linear_axes("embed", "vocab", bias=False),
    }


def init_llama_caches(config: LlamaConfig, batch: int,
                      max_len: int | None = None):
    return [L.init_kv_cache(batch, max_len or config.max_seq_len,
                            config.num_kv_heads, config.head_dim,
                            config.dtype)
            for _ in range(config.num_layers)]


def _attention(layer, config: LlamaConfig, x, cos, sin, cache,
               position_offset, mask):
    """RoPE attention with GQA + KV cache: layers.mha with the rotation
    injected via qk_transform, so cached keys are stored
    already-positioned."""
    def rope(q, k):
        return (L.apply_rope(q, cos, sin, position_offset),
                L.apply_rope(k, cos, sin, position_offset))

    return L.mha(layer["attn"], x, mask=mask, cache=cache,
                 num_heads=config.num_heads,
                 num_kv_heads=config.num_kv_heads, qk_transform=rope)


def _swiglu(layer, x):
    return L.linear(layer["down"],
                    jax.nn.silu(L.linear(layer["gate"], x)) *
                    L.linear(layer["up"], x))


def llama_ffn(layer, config: LlamaConfig, x):
    """The per-layer FFN: dense SwiGLU, or top-k MoE when the config
    says so.  Single seam shared by prefill, SP forward, and the
    continuous-batching decode step — an MoE checkpoint serves through
    the same machinery as a dense one."""
    if config.num_experts:
        from .moe import moe_forward
        y, _ = moe_forward(layer["moe"], config.moe_config(), x)
        return y
    return _swiglu(layer, x)


def llama_hidden(params, config: LlamaConfig, tokens, caches,
                 position_offset=0):
    """tokens: [B, T] → (final hidden states [B, T, dim], new_caches).
    T=1 for incremental decode; T>1 prefills with an in-step causal
    mask.  Split from the lm_head so prefill callers can select the
    position(s) they need BEFORE the vocab projection — full-sequence
    prefill logits are [B, T, vocab] (gigabytes at serving widths)."""
    cos, sin = L.rope_frequencies(config.head_dim, config.max_seq_len,
                                  config.rope_theta)
    x = L.embedding(params["embed"], tokens).astype(config.dtype)
    t = tokens.shape[1]

    mask = None
    if t > 1:
        q_pos = position_offset + jnp.arange(t)[:, None]
        k_pos = jnp.arange(caches[0]["k"].shape[2])[None, :]
        mask = (k_pos <= q_pos)[None, None]

    new_caches = []
    # attention goes through layers.mha, which Whisper shares: it is not
    # split into SCOPE_ATTN_PROJ and SCOPE_ATTN_CORE here
    for layer, cache in zip(params["layers"], caches):
        attn_out, cache = _attention(
            layer, config, L.rms_norm(layer["ln_attn"], x), cos, sin,
            cache, position_offset, mask)
        x = x + attn_out
        with jax.named_scope(SCOPE_MLP):
            x = x + llama_ffn(layer, config,
                              L.rms_norm(layer["ln_mlp"], x))
        new_caches.append(cache)
    with jax.named_scope(SCOPE_HEAD):
        return L.rms_norm(params["ln_out"], x), new_caches


def llama_decode_step(params, config: LlamaConfig, tokens, caches,
                      position_offset=0):
    """tokens: [B, T] → (logits [B, T, vocab], new_caches)."""
    x, new_caches = llama_hidden(params, config, tokens, caches,
                                 position_offset)
    logits = L.linear(params["lm_head"], x.astype(jnp.float32))
    return logits, new_caches


def llama_forward(params, config: LlamaConfig, tokens):
    """Teacher-forced full-sequence forward: tokens [B, S] → logits."""
    caches = init_llama_caches(config, tokens.shape[0], tokens.shape[1])
    logits, _ = llama_decode_step(params, config, tokens, caches)
    return logits


def llama_forward_sp(params, config: LlamaConfig, tokens, mesh,
                     axis_name: str = "seq", batch_axis: str = "data"):
    """Sequence-parallel long-context forward (prefill): activations
    sharded over the sequence axis; exact causal attention via ring
    attention (K/V blocks rotate over ICI, online softmax — SURVEY §5.7).

    tokens: [B, S] with S divisible by the `axis_name` mesh size.
    Returns logits [B, S, vocab] sharded the same way.  This is how a
    prompt too long for one chip's memory prefills: each device holds
    S/n of the sequence and never materializes the S×S score matrix."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..parallel.ring_attention import ring_attention_sharded

    def body(params, tokens_local):
        cos, sin = L.rope_frequencies(config.head_dim, config.max_seq_len,
                                      config.rope_theta)
        s_local = tokens_local.shape[1]
        offset = lax.axis_index(axis_name) * s_local
        x = L.embedding(params["embed"],
                        tokens_local).astype(config.dtype)
        for layer in params["layers"]:
            normed = L.rms_norm(layer["ln_attn"], x)
            q = L._split_heads(L.linear(layer["attn"]["q"], normed),
                               config.num_heads)
            k = L._split_heads(L.linear(layer["attn"]["k"], normed),
                               config.num_kv_heads)
            v = L._split_heads(L.linear(layer["attn"]["v"], normed),
                               config.num_kv_heads)
            q = L.apply_rope(q, cos, sin, offset)
            k = L.apply_rope(k, cos, sin, offset)
            # K/V stay at num_kv_heads: the ring rotates the small
            # blocks and expands per-block (GQA-aware ring attention)
            attn = ring_attention_sharded(q, k, v, axis_name=axis_name,
                                          causal=True)
            x = x + L.linear(layer["attn"]["o"], L._merge_heads(attn))
            normed = L.rms_norm(layer["ln_mlp"], x)
            x = x + llama_ffn(layer, config, normed)
        x = L.rms_norm(params["ln_out"], x)
        return L.linear(params["lm_head"], x.astype(jnp.float32))

    batch = batch_axis if batch_axis in mesh.axis_names else None
    token_spec = P(batch, axis_name)
    param_specs = jax.tree.map(lambda _: P(), params)   # replicated
    return jax.shard_map(
        body, mesh=mesh, in_specs=(param_specs, token_spec),
        out_specs=P(batch, axis_name, None))(params, tokens)


def llama_greedy_decode(params, config: LlamaConfig, prompt,
                        max_tokens: int = 32, eos_token: int | None = None):
    """prompt: [B, S] → generated tokens [B, max_tokens].  One lax.scan,
    static shapes, caches threaded through the carry."""
    batch, prompt_len = prompt.shape
    caches = init_llama_caches(config, batch, prompt_len + max_tokens)
    logits, caches = llama_decode_step(params, config, prompt, caches)
    first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    eos = eos_token if eos_token is not None else -1

    def step(carry, position):
        token, caches, done = carry
        logits, caches = llama_decode_step(
            params, config, token[:, None], caches,
            position_offset=position)
        next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        next_token = jnp.where(done, eos, next_token)
        done = done | (next_token == eos)
        return (next_token, caches, done), token

    positions = prompt_len + jnp.arange(max_tokens)
    (_, _, _), tokens = jax.lax.scan(
        step, (first, caches, first == eos), positions)
    return jnp.moveaxis(tokens, 0, 1)
