"""Drives `serving.ContinuousDecoder` over the Gated-DeltaNet hybrid decoder
(`models/gated_delta.py`: recurrent layers whose state is a slot's, beside
full-attention layers whose K and V the shared paged kernel walks): the
serving loop, the warm-up, the stamps and the sampling are
`continuous_decoder.Session`'s; what differs is the model's configuration,
its weights, its slot state and the counters of its recurrence beside the
decoder's.
"""

from __future__ import annotations

import functools
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights_gated_delta as W

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:          # run.py loads drivers by path, not package
    sys.path.insert(0, HERE)
import continuous_decoder as base  # noqa: E402

SPAN_PUMP, SPAN_SUBMIT = base.SPAN_PUMP, base.SPAN_SUBMIT


def model_config(sizes: dict, max_seq: int, dtype):
    """The program's configuration from the file's published keys."""
    from aiko_services_tpu.models.gated_delta import GatedDeltaConfig
    if sizes["attention_bias"] or sizes["hidden_act"] != "silu" \
            or sizes["tie_word_embeddings"] \
            or sizes["rope_parameters"]["rope_theta"] is not None \
            or sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise ValueError(
            "the program computes attention without bias, grouping or "
            "rotary, SiLU gates and an untied head")
    heads, key_dim, value_dim, taps = W.gdn_sizes(sizes)
    return GatedDeltaConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        layer_types=tuple(W.kinds(sizes)),
        ffn_dim=sizes["intermediate_size"],
        num_heads=sizes["num_attention_heads"],
        head_dim=W.full_head_dim(sizes), gdn_heads=heads, key_dim=key_dim,
        value_dim=value_dim, conv_width=taps,
        neg_eigval=sizes["linear_allow_neg_eigval"],
        norm_eps=sizes["rms_norm_eps"], max_seq_len=max_seq, dtype=dtype)


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
        """The decoder's counters, and what its recurrent layers counted."""
        stats = self.decoder.stats
        return super().counters() | {key: stats[key] for key in (
            "gdn_states_moved", "gdn_states_held", "slot_states_zeroed")}

    def close(self) -> None:
        self.decoder.slot_state = None
        super().close()
