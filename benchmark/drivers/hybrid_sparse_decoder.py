"""Drives `serving.ContinuousDecoder` over the hybrid decoder
(`models/hybrid_sparse.py`: KDA slot state beside a sparse-selected latent
pool, hyper-connected streams): the serving loop, the warm-up, the stamps
and the sampling are `continuous_decoder.Session`'s; what differs is the
model's configuration, its weights, and the counters of its expert and
sparse-attention layers beside the decoder's.
"""

from __future__ import annotations

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_hybrid_sparse as W

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:          # run.py loads drivers by path, not package
    sys.path.insert(0, HERE)
import continuous_decoder as base  # noqa: E402

SPAN_PUMP, SPAN_SUBMIT = base.SPAN_PUMP, base.SPAN_SUBMIT


def model_config(sizes: dict, max_seq: int, dtype):
    """The program's configuration from the file's published keys."""
    from aiko_services_tpu.models.hybrid_sparse import HybridSparseConfig
    count = sizes["num_hidden_layers"]
    if sizes["scoring_func"] != "sigmoid" or not sizes["norm_topk_prob"] \
            or sizes["topk_method"] != "noaux_tc" or sizes["n_group"] != 1 \
            or sizes["qk_rope_head_dim"] or not sizes["mhc"] \
            or not sizes["index_kpool_compress"] \
            or not sizes["index_kpool_always_select_tail"] \
            or not sizes["indexer_rope_interleave"]:
        raise ValueError(
            "the program computes a sigmoid router with a correction bias "
            "and renormalised weights over one group, MLA without rotary, "
            "pooled indexer keys with the open group always attended, "
            "hyper-connected streams")
    if len(sizes["layer_types"]) != count or \
            len(sizes["mlp_layer_types"]) != count:
        raise ValueError("layer_types and mlp_layer_types name every layer")
    heads, head_dim, taps, gate_rank = W.kda_sizes(sizes)
    return HybridSparseConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        layer_types=tuple(W.KINDS[kind] for kind in sizes["layer_types"]),
        mlp_types=tuple(sizes["mlp_layer_types"]),
        kda_heads=heads, kda_head_dim=head_dim, conv_width=taps,
        gate_rank=gate_rank,
        gate_lower_bound=float(
            sizes["linear_attn_config"]["gate_lower_bound"]),
        num_heads=sizes["num_attention_heads"],
        q_rank=sizes["q_lora_rank"], kv_rank=sizes["kv_lora_rank"],
        nope_dim=sizes["qk_nope_head_dim"], v_dim=sizes["v_head_dim"],
        index_heads=sizes["index_n_heads"],
        index_dim=sizes["index_head_dim"],
        index_rope_dim=sizes["assumed_sizes"]["index_rope_head_dim"],
        index_topk=sizes["index_topk"], index_pool=sizes["index_kpool"],
        rope_theta=float(sizes["assumed_sizes"]["index_rope_theta"]),
        dense_ffn_dim=sizes["intermediate_size"],
        expert_ffn_dim=sizes["moe_intermediate_size"],
        shared_experts=sizes["n_shared_experts"],
        num_experts=W.router_width(sizes),
        top_k=sizes["num_experts_per_tok"],
        routed_scale=sizes["routed_scaling_factor"],
        experts_first=W.experts_first(sizes),
        experts_held=sizes["n_routed_experts"],
        swiglu_limit=float(sizes["swiglu_limit"]),
        hc_mult=sizes["hc_mult"],
        hc_sinkhorn_iters=sizes["hc_sinkhorn_iters"],
        hc_eps=sizes["hc_eps"], norm_eps=sizes["rms_norm_eps"],
        # the rotary table is built for the served window only: its values
        # are those of a longer table's leading rows
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
            f" GB")
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
        """The decoder's counters, and what its expert and sparse-attention
        layers counted."""
        stats = self.decoder.stats
        return super().counters() | {
            key: stats[key] for key in (
                "moe_layer_steps", "moe_experts_hit", "moe_pairs_here",
                "moe_pairs_routed", "dsa_positions_live",
                "dsa_positions_attended", "slot_states_zeroed")} | {
            "moe_experts_held": self.sizes["n_routed_experts"]}

    def close(self) -> None:
        self.decoder.slot_state = None
        super().close()
