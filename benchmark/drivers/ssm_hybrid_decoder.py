"""Drives `serving.ContinuousDecoder` over the Mamba-2 hybrid decoder
(`models/ssm_hybrid.py`: state-space layers whose state is a slot's, beside
grouped-query attention layers whose one pool leaf the shared paged kernel
walks): the serving loop, the warm-up, the stamps and the sampling are
`continuous_decoder.Session`'s; what differs is the model's configuration,
its weights, its slot state and the counters of its recurrence beside the
decoder's (the shape of `gated_delta_decoder.py`, whose constructor names
its own model and weights).
"""

from __future__ import annotations

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_ssm_hybrid as W

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:          # run.py loads drivers by path, not package
    sys.path.insert(0, HERE)
import continuous_decoder as base  # noqa: E402

SPAN_PUMP, SPAN_SUBMIT = base.SPAN_PUMP, base.SPAN_SUBMIT


def model_config(sizes: dict, max_seq: int, dtype):
    """The program's configuration from the file's published keys."""
    from aiko_services_tpu.models.ssm_hybrid import SsmHybridConfig
    if sizes["attention_bias"] or sizes["hidden_act"] != "silu" \
            or not sizes["tie_word_embeddings"] \
            or sizes["position_embedding_type"] != "nope" \
            or sizes["num_local_experts"] or sizes["mamba_proj_bias"] \
            or not sizes["mamba_conv_bias"] \
            or sizes["normalization_function"] != "rmsnorm":
        raise ValueError(
            "the program computes attention without bias or rotary, SiLU "
            "gates, RMSNorm, a biased convolution, no expert and a tied "
            "head")
    heads, width, state, taps = W.mamba_sizes(sizes)
    return SsmHybridConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        layer_types=tuple(W.kinds(sizes)),
        ffn_dim=sizes["shared_intermediate_size"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=W.head_dim(sizes), ssm_heads=heads, ssm_head_dim=width,
        ssm_state=state, conv_width=taps, norm_eps=sizes["rms_norm_eps"],
        embedding_multiplier=float(sizes["embedding_multiplier"]),
        attention_multiplier=float(sizes["attention_multiplier"]),
        residual_multiplier=float(sizes["residual_multiplier"]),
        logits_scaling=float(sizes["logits_scaling"]),
        max_seq_len=max_seq, dtype=dtype)


class Session(base.Session):
    def __init__(self, config: dict, traffic: dict, plan: dict, seed: int,
                 say, lower_precision: bool = False):
        from aiko_services_tpu import serving

        sizes, serve = config, config["serving"]
        self.sizes, self.serve, self.say = sizes, serve, say
        self.seed, self.plan = seed, plan
        self.dtype = jnp.dtype(config["dtype"])
        model = model_config(sizes, serve["max_seq"], self.dtype)
        start = time.perf_counter()
        params = W.decoder_weights(
            W.key_for(seed), sizes, self.dtype,
            # the control: see PERF.md, correctness
            transform=W.round_to_fp8 if lower_precision else None)
        jax.block_until_ready(params)
        say(f"weights: {sum(l.nbytes for l in jax.tree.leaves(params)) / 1e9:.2f}"
            f" GB made on the device in {time.perf_counter() - start:.1f} s")
        self.decoder = serving.ContinuousDecoder(
            params, model, paged_kv=True, kv_block=serve["kv_block"],
            max_slots=serve["max_slots"], max_seq=serve["max_seq"],
            t_block=serve["t_block"],
            prefill_buckets=tuple(serve["prefill_buckets"]),
            prefill_chunk=serve["prefill_chunk"],
            prefill_budget=serve["prefill_budget"],
            steps_per_sync=serve["steps_per_sync"], name="bench")
        say(f"pool {self.decoder.pool.nbytes() / 1e9:.2f} GB "
            f"({self.decoder.pool.block_nbytes / serve['kv_block']:.0f} B a "
            f"token), slot state {self.decoder.slot_state.nbytes() / 1e9:.2f}"
            f" GB; step kernel {self.decoder.step_kernel}, walks live "
            f"blocks {self.decoder._walks_live}")
        self.window = None
        self.served: dict = {}
        self.slot_of: dict = {}     # the slot each request was served in
        self.break_token = None     # a test's seam: alters a served token
        deliver = self.decoder._deliver

        @functools.wraps(deliver)
        def stamped(slot, token, now):
            request = self.decoder._slots[slot]
            if self.window is not None and \
                    request.request_id in self.window.records:
                self.window.token(request.request_id)
                self.slot_of[request.request_id] = slot
            if self.break_token is not None:
                token = self.break_token(request.request_id, token)
            return deliver(slot, token, now)

        self.decoder._deliver = stamped
        rng = np.random.default_rng([int(seed), 11])
        self.prompts = {
            r["id"]: rng.integers(1, sizes["vocab_size"],
                                  size=r["prompt_tokens"]).tolist()
            for r in plan["requests"]}

    def counters(self) -> dict:
        """The decoder's counters, and what its Mamba layers counted."""
        stats = self.decoder.stats
        return super().counters() | {key: stats[key] for key in (
            "ssm_states_moved", "ssm_states_held", "slot_states_zeroed")}

    def close(self) -> None:
        self.decoder.slot_state = None
        super().close()
