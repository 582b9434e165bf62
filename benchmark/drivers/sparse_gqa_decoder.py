"""Drives `serving.ContinuousDecoder` over the sparse grouped-query decoder
(`models/sparse_gqa.py`: K, V and an indexer key a token, keys chosen a
query by a lightning indexer, softmax-routed held experts): the serving
loop, the warm-up, the stamps and the sampling are
`continuous_decoder.Session`'s; what differs is the model's configuration,
its weights, and the counters of its expert layers and its selection
beside the decoder's.
"""

from __future__ import annotations

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_sparse_gqa as W

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:          # run.py loads drivers by path, not package
    sys.path.insert(0, HERE)
import continuous_decoder as base  # noqa: E402

SPAN_PUMP, SPAN_SUBMIT = base.SPAN_PUMP, base.SPAN_SUBMIT


def model_config(sizes: dict, max_seq: int, dtype):
    """The program's configuration from the file's published keys."""
    from aiko_services_tpu.models.sparse_gqa import SparseGqaConfig
    scaling = sizes["rope_scaling"]
    if sizes["attention_bias"] or sizes["hidden_act"] != "silu" \
            or not sizes["norm_topk_prob"] or sizes["mlp_only_layers"] \
            or sizes["decoder_sparse_step"] != 1 \
            or sizes["use_sliding_window"] or sizes["tie_word_embeddings"] \
            or scaling["rope_type"] != "default":
        raise ValueError(
            "the program computes attention without bias over the whole "
            "context, default (sectioned) rotary, SiLU experts in every "
            "layer with renormalised softmax weights, an untied head")
    index_heads, index_dim = W.indexer_sizes(sizes)
    return SparseGqaConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], rope_theta=float(sizes["rope_theta"]),
        mrope_section=tuple(scaling["mrope_section"]),
        index_heads=index_heads, index_dim=index_dim,
        index_rope_dim=sizes["assumed_sizes"]["index_rope_head_dim"],
        index_rope_theta=float(sizes["assumed_sizes"]["index_rope_theta"]),
        index_topk=sizes["sa_config"]["topk"],
        expert_ffn_dim=sizes["moe_intermediate_size"],
        num_experts=W.router_width(sizes),
        top_k=sizes["num_experts_per_tok"],
        experts_first=W.experts_first(sizes),
        experts_held=sizes["num_experts"],
        norm_eps=sizes["rms_norm_eps"],
        # the rotary tables are built for the served window only: their
        # values are those of a longer table's leading rows
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
            f"token)")
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
        """The decoder's counters, and what its expert layers and its
        selection counted."""
        stats = self.decoder.stats
        return super().counters() | {
            key: stats[key] for key in (
                "moe_layer_steps", "moe_experts_hit", "moe_pairs_here",
                "moe_pairs_routed", "dsa_positions_live",
                "dsa_positions_attended", "dsa_rows_fetched",
                "dsa_slot_steps_dense")} | {
            "moe_experts_held": self.sizes["num_experts"]}
