"""Drives `serving.ContinuousDecoder` over the latent-attention,
routed-expert decoder (`models/latent_moe.py`): the serving loop, the
warm-up, the stamps and the sampling are `continuous_decoder.Session`'s;
what differs is the model's configuration, its weights, and the expert
layers' counters beside the decoder's.
"""

from __future__ import annotations

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_latent_moe as W

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:          # run.py loads drivers by path, not package
    sys.path.insert(0, HERE)
import continuous_decoder as base  # noqa: E402

SPAN_PUMP, SPAN_SUBMIT = base.SPAN_PUMP, base.SPAN_SUBMIT


def model_config(sizes: dict, max_seq: int, dtype):
    """The program's configuration from the file's published keys."""
    from aiko_services_tpu.models.latent_moe import LatentMoeConfig
    scaling = sizes["rope_scaling"]
    if scaling["type"] != "yarn" or sizes["scoring_func"] != "sigmoid" \
            or not sizes["norm_topk_prob"] or sizes["moe_layer_freq"] != 1:
        raise ValueError("the program computes YaRN rotary, a sigmoid router "
                         "with renormalised weights, experts in every layer")
    return LatentMoeConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        q_rank=sizes["q_lora_rank"], kv_rank=sizes["kv_lora_rank"],
        nope_dim=sizes["qk_nope_head_dim"],
        rope_dim=sizes["qk_rope_head_dim"], v_dim=sizes["v_head_dim"],
        dense_ffn_dim=sizes["intermediate_size"],
        dense_layers=sizes["first_k_dense_replace"],
        expert_ffn_dim=sizes["moe_intermediate_size"],
        shared_experts=sizes["n_shared_experts"],
        num_experts=W.router_width(sizes),
        top_k=sizes["num_experts_per_tok"],
        routed_scale=sizes["routed_scaling_factor"],
        experts_first=W.experts_first(sizes),
        experts_held=sizes["n_routed_experts"],
        # the rotary table is built for the served window only: its values
        # are those of the published 131,072-position table
        max_seq_len=max_seq, rope_theta=float(sizes["rope_theta"]),
        yarn_factor=float(scaling["factor"]),
        yarn_original=scaling["original_max_position_embeddings"],
        yarn_beta_fast=float(scaling["beta_fast"]),
        yarn_beta_slow=float(scaling["beta_slow"]),
        yarn_mscale=float(scaling["mscale"]),
        yarn_mscale_all_dim=float(scaling["mscale_all_dim"]), dtype=dtype)


class Session(base.Session):
    def __init__(self, config: dict, traffic: dict, plan: dict, seed: int,
                 say, lower_precision: bool = False):
        from aiko_services_tpu import serving

        sizes, serve = config, config["serving"]
        if sizes["rms_norm_eps"] != 1e-6:
            raise ValueError("models/layers.rms_norm computes with 1e-6")
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
        """The decoder's counters, and what its expert layers counted."""
        stats = self.decoder.stats
        return super().counters() | {
            key: stats[key] for key in (
                "moe_layer_steps", "moe_experts_hit", "moe_pairs_here",
                "moe_pairs_routed")} | {
            "moe_experts_held": self.sizes["n_routed_experts"]}
