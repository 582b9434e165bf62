"""Drives `serving.ContinuousDecoder` the way a serving loop does: submit
what is due, pump, repeat.

The program hands back tokens only when a request ends, so the times of
the first and the last token are stamped from outside, by wrapping the
decoder's `_deliver` (the one private name this file leans on; a public
per-token hook is the tracing issue's).  Spans are the benchmark's own
`jax.profiler.TraceAnnotation`s around the calls into the program.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as W

SPAN_PUMP, SPAN_SUBMIT = "bench.pump", "bench.generator"


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class Session:
    def __init__(self, config: dict, traffic: dict, plan: dict, seed: int,
                 say, lower_precision: bool = False):
        from aiko_services_tpu import serving
        from aiko_services_tpu.models.llama import LlamaConfig

        sizes, serve = config, config["serving"]
        self.sizes, self.serve, self.say = sizes, serve, say
        self.seed, self.plan = seed, plan
        self.dtype = jnp.dtype(config["dtype"])
        model = LlamaConfig(
            vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
            ffn_dim=sizes["intermediate_size"],
            num_layers=sizes["num_hidden_layers"],
            num_heads=sizes["num_attention_heads"],
            num_kv_heads=sizes["num_key_value_heads"],
            # the rotary table is built for the served window only: its
            # values are those of the published 32,768-position table
            max_seq_len=serve["max_seq"],
            rope_theta=sizes["rope_theta"], dtype=self.dtype)
        if model.head_dim != sizes["head_dim"]:
            raise ValueError("the program derives head_dim as hidden/heads")
        make = lambda key: W.decoder_weights(key, sizes, self.dtype)
        if lower_precision:       # the control: see PERF.md, correctness
            make = lambda key, make=make: W.round_to_fp8(make(key))
        start = time.perf_counter()
        params = jax.jit(make)(W.key_for(seed))
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

    # -- set-up ---------------------------------------------------------------
    def _serve_all(self, batch: list) -> None:
        """Submit a warm-up batch and pump until every request is done."""
        pending = set()
        for i, (length, new_tokens) in enumerate(batch):
            rid = f"warm{i}"
            pending.add(rid)
            ok = self.decoder.submit(rid, [1 + i % 7] * length, new_tokens,
                                     lambda rid, _t: pending.discard(rid))
            if not ok:
                raise RuntimeError("warm-up request refused")
        deadline = time.perf_counter() + 900.0
        while pending:
            if time.perf_counter() > deadline:
                raise RuntimeError("warm-up did not finish")
            self.decoder.pump()

    def warm_up(self) -> None:
        """Every program the window can reach, and no other: an admit for
        each (bucket, width) that `prefill_budget` lets one round form,
        the chunk extend, and the step at 1, 2 and 4 iterations."""
        serve = self.serve
        budget, buckets = serve["prefill_budget"], serve["prefill_buckets"]
        for bucket in buckets:
            most = serve["max_slots"] if budget is None \
                else max(1, budget // bucket)
            width = 1
            while width <= _next_pow2(most):
                rows = min(width, most, serve["max_slots"])
                self._serve_all([(bucket, 1)] * rows)
                width *= 2
        longest = max(r["prompt_tokens"] for r in self.plan["requests"])
        if longest > buckets[-1]:
            self._serve_all([(longest, 1)])
        steps = 1
        while steps <= serve["steps_per_sync"]:
            self._serve_all([(buckets[0], steps + 1)])
            steps *= 2
        self.say(f"warm-up: programs {sorted(map(str, self.decoder._prefill_fns))}")

    # -- the window -----------------------------------------------------------
    def run(self, window) -> None:
        decoder, self.window = self.decoder, window
        annotate = jax.profiler.TraceAnnotation

        def finished(rid, tokens):
            self.served[rid] = [int(t) for t in tokens]
            window.done(rid)

        window.start()
        while not window.finished():
            due = window.due()
            if due:
                with annotate(SPAN_SUBMIT):
                    for request in due:
                        rid = request["id"]
                        if decoder.submit(rid, self.prompts[rid],
                                          request["output_tokens"], finished):
                            window.sent(rid)
                        else:
                            window.refused(rid)
            if decoder.idle:
                wait = window.next_due()
                time.sleep(min(0.001, max(0.0, wait or 0.0)))
                continue
            with annotate(SPAN_PUMP):
                decoder.pump()
        self.window = None

    def counters(self) -> dict:
        stats = self.decoder.stats
        return {key: stats[key] for key in (
            "steps", "rounds", "useful_steps", "wasted_steps",
            "tokens_decode", "tokens_prefill", "prefill_s", "decode_s",
            "prefill_chunks", "prefills", "completed")} | {
            "max_slots": self.serve["max_slots"]}

    def samples(self, records: dict, count: int, rng) -> list:
        """What the reference is given: the longest finished request, then
        one request drawn from the seed out of each slot that served any,
        slot after slot and round again, until there are `count`: a fault
        confined to one slot is then in the sample once `count` reaches the
        number of slots."""
        done = sorted((rid for rid, r in records.items()
                       if rid in self.served and r["done"] is not None),
                      key=lambda rid: -len(self.prompts[rid]))
        if not done:
            return []
        chosen, by_slot = [done[0]], {}
        for rid in done[1:]:
            by_slot.setdefault(self.slot_of.get(rid, -1), []).append(rid)
        queues = [[by_slot[slot][i] for i in rng.permutation(
            len(by_slot[slot]))] for slot in sorted(by_slot)]
        while len(chosen) < count and any(queues):
            for queue in queues:
                if queue and len(chosen) < count:
                    chosen.append(queue.pop())
        self.say(f"sampled for the reference: {len(chosen)} requests from "
                 f"slots {sorted({self.slot_of.get(r, -1) for r in chosen})}")
        return [{"id": rid, "prompt": self.prompts[rid],
                 "served": self.served[rid]} for rid in chosen]

    def reference_sizes(self) -> dict:
        return self.sizes

    def close(self) -> None:
        """Free the program's state, so that the reference has the chip."""
        self.decoder.params = None
        self.decoder.pool = None
        self.decoder = None
