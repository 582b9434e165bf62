# Disaggregated prefill/decode serving tests (ISSUE 14): the
# KV-transfer envelope must carry the int8 {"q","s"} layout BIT-EXACT,
# disaggregated greedy output must be bit-identical to colocated,
# chaos on the transfer path must recover via retry then the
# local-prefill fallback ladder (never a dropped request), deadline
# routing must send short-budget prompts to the least-loaded prefill
# runtime, the two pools must autoscale on their OWN signals, and the
# in-flight prefix dedup window must share a same-batch duplicate's
# prefill.

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models.llama import (LLAMA_PRESETS,
                                            llama_greedy_decode,
                                            llama_init)
from aiko_services_tpu.transport import wire

CONFIG = dataclasses.replace(LLAMA_PRESETS["tiny"], max_seq_len=128)
PROMPT = [(i * 13) % 50 + 1 for i in range(40)]


@pytest.fixture(scope="module")
def params():
    return llama_init(jax.random.PRNGKey(0), CONFIG)


def oracle(params, prompt, max_new):
    out = llama_greedy_decode(params, CONFIG,
                              jnp.asarray([prompt], jnp.int32),
                              max_tokens=max_new)
    return [int(t) for t in np.asarray(out)[0]]


def make_harness(params, disagg=True, **kwargs):
    from aiko_services_tpu.serving_disagg import DisaggHarness
    kwargs.setdefault("block_tokens", 8)
    kwargs.setdefault("max_slots", 4)
    kwargs.setdefault("prefill_slots", 2)
    kwargs.setdefault("prefill_chunk", 16)
    kwargs.setdefault("prefill_buckets", (64,))
    return DisaggHarness(params, CONFIG, disagg=disagg, **kwargs)


def run_one(harness, rid, prompt, max_new, timeout=120.0, **kwargs):
    done = {}
    harness.submit(rid, prompt, max_new,
                   lambda r, t: done.update({r: t}), **kwargs)
    assert harness.run_until(lambda: rid in done, timeout=timeout), \
        f"request {rid} never completed"
    return done[rid]


# -- KV-transfer envelope ---------------------------------------------------

class TestKVTransferWire:
    def test_int8_layout_roundtrip_bit_exact(self):
        rng = np.random.default_rng(3)
        blocks = []
        for _ in range(2):              # 2 blocks x 2 layers
            layers = []
            for _ in range(2):
                layers.append({
                    "k": {"q": rng.integers(-127, 127, (2, 8, 16),
                                            dtype=np.int8),
                          "s": rng.random((2, 8), np.float32)},
                    "v": {"q": rng.integers(-127, 127, (2, 8, 16),
                                            dtype=np.int8),
                          "s": rng.random((2, 8), np.float32)}})
            blocks.append(layers)
        payload = wire.encode_kv_transfer(
            "t1", "team.a", list(range(20)), 1, 8,
            ("2", "2", "16", "bfloat16", "True", "8", "2"), blocks,
            first_token=42)
        out = wire.decode_kv_transfer(payload)
        assert out["transfer_id"] == "t1"
        assert out["tenant"] == "team.a"
        assert out["start_block"] == 1
        assert out["block_tokens"] == 8
        assert out["first_token"] == 42
        assert out["layout"] == ("2", "2", "16", "bfloat16", "True",
                                 "8", "2")
        np.testing.assert_array_equal(out["tokens"],
                                      np.arange(20, dtype=np.int32))
        for b in range(2):
            for layer in range(2):
                for side in ("k", "v"):
                    sent = blocks[b][layer][side]
                    got = out["blocks"][b][layer][side]
                    np.testing.assert_array_equal(got["q"], sent["q"])
                    np.testing.assert_array_equal(got["s"], sent["s"])

    def test_native_bf16_roundtrip_bit_exact(self):
        import ml_dtypes
        rows = np.arange(2 * 8 * 4, dtype=np.float32).reshape(
            2, 8, 4).astype(ml_dtypes.bfloat16)
        payload = wire.encode_kv_transfer(
            "t2", "", list(range(8)), 0, 8, ("l",),
            [[{"k": rows, "v": rows}]])
        out = wire.decode_kv_transfer(payload)
        got = out["blocks"][0][0]["k"]
        np.testing.assert_array_equal(got.view(np.uint16),
                                      rows.view(np.uint16))

    def test_truncation_raises_wire_error(self):
        rows = np.zeros((2, 8, 4), np.float32)
        payload = wire.encode_kv_transfer(
            "t3", "", list(range(8)), 0, 8, (),
            [[{"k": rows, "v": rows}]])
        for cut in (len(payload) // 3, len(payload) - 7):
            with pytest.raises(wire.WireError):
                wire.decode_kv_transfer(payload[:cut])

    def test_illegal_dtype_refused_at_encode(self):
        bad = np.zeros((2, 8, 4), np.float64)
        good = np.zeros((2, 8, 4), np.float32)
        with pytest.raises(wire.WireError):
            wire.encode_kv_transfer("t", "", [1], 0, 8, (),
                                    [[{"k": bad, "v": good}]])

    def test_wrong_block_length_refused_at_decode(self):
        rows = np.zeros((2, 6, 4), np.float32)      # 6 != block 8
        payload = wire.encode_kv_transfer(
            "t", "", list(range(8)), 0, 8, (),
            [[{"k": rows, "v": rows}]])
        with pytest.raises(wire.WireError):
            wire.decode_kv_transfer(payload)

    def test_foreign_command_refused(self):
        payload = wire.encode_envelope("process_frame", ["s", {}])
        with pytest.raises(wire.WireError):
            wire.decode_kv_transfer(payload)


class TestWireSchemaCheck:
    def test_declared_schema_is_sound(self):
        from aiko_services_tpu.analysis.graph_check import \
            check_wire_schemas
        assert check_wire_schemas() == []

    def test_drifted_schema_is_an_error(self):
        from aiko_services_tpu.analysis.graph_check import \
            check_wire_schemas
        findings = check_wire_schemas(
            schema={"kv": "f64[*,*,*]", "tokens": "i32[*]"},
            dtypes=dict(wire.KV_TRANSFER_DTYPES),
            ranks=dict(wire.KV_TRANSFER_RANK))
        rules = {f.rule for f in findings}
        assert rules == {"wire-kv-schema"}
        # f64 disagrees with the runtime table AND kv_q/kv_s are
        # enforced but undeclared
        assert len(findings) >= 3

    def test_unparseable_contract_is_an_error(self):
        from aiko_services_tpu.analysis.graph_check import \
            check_wire_schemas
        findings = check_wire_schemas(
            schema={"kv": "no-such-dtype[*,*"},
            dtypes={"kv": ("float32",)}, ranks={"kv": 3})
        assert any("does not parse" in f.message for f in findings)


# -- deadline routing -------------------------------------------------------

class TestDeadlineRouter:
    def test_urgent_goes_least_loaded(self):
        from aiko_services_tpu.ops.admission import DeadlineRouter
        router = DeadlineRouter(urgent_budget_s=1.0, name="t1")
        loads = {"a": 3, "b": 0, "c": 1}
        assert router.route(loads, remaining=0.5) == "b"
        loads["b"] = 9
        assert router.route(loads, remaining=0.2) == "c"

    def test_relaxed_round_robins(self):
        from aiko_services_tpu.ops.admission import DeadlineRouter
        router = DeadlineRouter(urgent_budget_s=1.0, name="t2")
        loads = {"a": 5, "b": 0}
        picks = [router.route(loads, remaining=None)
                 for _ in range(4)]
        assert picks == ["a", "b", "a", "b"]
        picks = [router.route(loads, remaining=30.0)
                 for _ in range(2)]
        assert picks == ["a", "b"]

    def test_empty_pool_returns_none(self):
        from aiko_services_tpu.ops.admission import DeadlineRouter
        assert DeadlineRouter(name="t3").route({}, 0.1) is None


# -- end-to-end parity ------------------------------------------------------

class TestDisaggParity:
    def test_disagg_greedy_bit_identical_and_suffix_only(self, params):
        """Remote-prefilled output is bit-identical to the oracle, and
        the decode decoder only prefilled the ragged suffix."""
        harness = make_harness(params, disagg=True)
        try:
            assert harness.wait_discovered(15.0)
            tokens = run_one(harness, "r1", PROMPT, 10)
            assert tokens == oracle(params, PROMPT, 10)
            stats = harness.client.stats
            assert stats["transfers"] == 1
            assert stats["installs"] == 1
            assert stats["local_fallbacks"] == 0
            assert harness.decoder.stats["prefix_admits"] == 1
            # 40-token prompt, block 8: 5 blocks shipped; the decode
            # side prefills only the 8-token anchored suffix
            assert stats["installed_blocks"] == 5
            assert harness.decoder.stats["tokens_prefill"] <= 16
            # TTFT landed in the "remote" population (ISSUE 14)
            remote = harness.decoder.slo_sketch_stats(prefill="remote")
            assert remote["ttft_p50_ms"] is not None
            cold = harness.decoder.slo_sketch_stats(prefill="cold")
            assert cold["ttft_p50_ms"] is None
        finally:
            harness.stop()

    def test_second_turn_ships_handles_and_repeat_stays_local(
            self, params):
        """A conversation's second turn ships its shared prefix as
        HANDLES (indices, no bytes); an identical repeat skips the
        remote hop entirely (the decode side holds the whole chain)."""
        harness = make_harness(params, disagg=True)
        try:
            assert harness.wait_discovered(15.0)
            run_one(harness, "r1", PROMPT, 10)
            turn2 = PROMPT + [7, 9, 3, 5, 2, 8, 6, 1]
            tokens = run_one(harness, "r2", turn2, 10)
            assert tokens == oracle(params, turn2, 10)
            stats = harness.client.stats
            assert stats["handle_blocks"] >= 5
            assert harness.client.handle_hit_rate() > 0
            run_one(harness, "r3", PROMPT, 10)
            assert stats["local_cached"] == 1
            assert stats["transfers"] == 2      # r3 never went remote
        finally:
            harness.stop()

    @pytest.mark.slow
    def test_int8_kv_ships_quantized_layout_bit_faithful(self, params):
        """int8 decoders ship {"q","s"} blocks: the disaggregated
        output matches a colocated int8 decoder's output exactly (the
        transfer carries the donor's stored bytes — no re-rounding)."""
        opts = {"decoder_opts": {"kv_cache_dtype": "int8"}}
        coloc = make_harness(params, disagg=False, **opts)
        try:
            expect = run_one(coloc, "c1", PROMPT, 10)
        finally:
            coloc.stop()
        harness = make_harness(params, disagg=True, **opts)
        try:
            assert harness.wait_discovered(15.0)
            tokens = run_one(harness, "r1", PROMPT, 10)
            assert tokens == expect
            assert harness.client.stats["transfers"] == 1
        finally:
            harness.stop()

    def test_no_pool_prefills_locally(self, params):
        """Colocated harness (no prefill pool): same tokens, zero
        transfers — and a disagg client with an empty candidate set
        falls straight to local prefill, counted."""
        harness = make_harness(params, disagg=False)
        try:
            tokens = run_one(harness, "r1", PROMPT, 10)
            assert tokens == oracle(params, PROMPT, 10)
        finally:
            harness.stop()


# -- chaos on the transfer path ---------------------------------------------

class TestTransferChaos:
    def test_dropped_transfers_retry_then_fall_back_local(self, params):
        """Every KV-transfer reply dropped on the peer channel: the
        client times out, retries, times out again, and prefills
        locally — output still bit-identical, zero lost."""
        from aiko_services_tpu.transport.chaos import FaultPlan
        plan = FaultPlan(seed=11)
        plan.drop(payload_match="kv_transfer")
        harness = make_harness(params, disagg=True, fault_plan=plan,
                               transfer_timeout=0.3, retries=1)
        try:
            assert harness.wait_discovered(15.0)
            tokens = run_one(harness, "r1", PROMPT, 10, timeout=120.0)
            assert tokens == oracle(params, PROMPT, 10)
            stats = harness.client.stats
            assert stats["transfer_timeouts"] >= 2
            assert stats["retries"] >= 1
            assert stats["local_fallbacks"] == 1
            assert harness.client.pending_count() == 0
        finally:
            harness.stop()

    def test_truncated_transfer_detected_then_recovered(self, params):
        """A truncated transfer payload is rejected by the schema
        check (WireError, counted corrupt) — never scattered into the
        cache — and the ladder still completes the request."""
        from aiko_services_tpu.transport.chaos import FaultPlan
        plan = FaultPlan(seed=7)
        plan.truncate(payload_match="kv_transfer", truncate_to=64,
                      count=2)
        harness = make_harness(params, disagg=True, fault_plan=plan,
                               transfer_timeout=0.4, retries=1)
        try:
            assert harness.wait_discovered(15.0)
            tokens = run_one(harness, "r1", PROMPT, 10, timeout=120.0)
            assert tokens == oracle(params, PROMPT, 10)
            stats = harness.client.stats
            assert stats["transfer_corrupt"] >= 1
            # recovery = retry (both copies truncated -> local ladder)
            assert stats["local_fallbacks"] + stats["installs"] >= 1
            assert harness.client.pending_count() == 0
        finally:
            harness.stop()

    def test_prefill_kill_mid_transfer_loses_nothing(self, params):
        """The seeded chaos scenario: the prefill runtime dies with
        transfers in flight.  Every request rides the fallback ladder
        to a local prefill — counted, none dropped, parity intact."""
        harness = make_harness(params, disagg=True,
                               transfer_timeout=0.5, retries=1)
        try:
            assert harness.wait_discovered(15.0)
            done = {}
            prompts = {f"r{i}": [p + i for p in PROMPT]
                       for i in range(3)}
            for rid, prompt in prompts.items():
                harness.submit(rid, prompt, 8,
                               lambda r, t: done.update({r: t}))
            # kill while the transfers are pending (nothing has had a
            # chance to complete: the kill happens before any engine
            # step runs)
            assert harness.client.pending_count() >= 1
            harness.kill_prefill()
            assert harness.run_until(
                lambda: len(done) == len(prompts), timeout=120.0)
            for rid, prompt in prompts.items():
                assert done[rid] == oracle(params, prompt, 8), rid
            assert harness.client.stats["local_fallbacks"] >= 1
            assert harness.client.pending_count() == 0
        finally:
            harness.stop()


# -- pipelined chunk streaming (ISSUE 17) -----------------------------------

class TestChunkStreaming:
    # chunked extends (and so chunk streaming) engage only past the
    # largest prefill bucket (64): 80 tokens = five 16-token chunks
    LONG = (PROMPT * 3)[:80]

    def test_chunks_stream_during_prefill(self, params):
        """With chunked prefill on, every finished chunk's blocks ship
        IMMEDIATELY: the client installs them while the donor is still
        prefilling (transfer_overlap_s > 0), the final envelope ships
        only the remainder, and greedy output is unchanged."""
        harness = make_harness(params, disagg=True)
        try:
            assert harness.wait_discovered(15.0)
            tokens = run_one(harness, "r1", self.LONG, 10)
            assert tokens == oracle(params, self.LONG, 10)
            rstats = harness.prefill.stats
            cstats = harness.client.stats
            # 80-token prompt, chunk 16, block 8: four mid-prefill
            # chunks of two blocks each stream ahead of the final
            assert rstats["chunks_shipped"] == 4
            assert rstats["chunk_blocks"] == 8
            assert cstats["chunk_installs"] == 4
            assert cstats["chunk_blocks"] == 8
            assert cstats["chunk_dropped"] == 0
            assert cstats["chunk_streamed"] == 1
            assert cstats["transfer_overlap_s"] > 0.0
            assert cstats["installs"] == 1      # final still settles
            assert cstats["local_fallbacks"] == 0
            assert harness.client.pending_count() == 0
        finally:
            harness.stop()

    def test_chunk_stream_off_matches(self, params):
        """chunk_stream=False is the A/B: identical tokens, all
        blocks ride the single final envelope."""
        harness = make_harness(params, disagg=True, chunk_stream=False)
        try:
            assert harness.wait_discovered(15.0)
            tokens = run_one(harness, "r1", self.LONG, 10)
            assert tokens == oracle(params, self.LONG, 10)
            assert harness.prefill.stats["chunks_shipped"] == 0
            cstats = harness.client.stats
            assert cstats["chunk_installs"] == 0
            assert cstats["chunk_streamed"] == 0
            assert cstats["transfer_overlap_s"] == 0.0
            assert cstats["installs"] == 1
        finally:
            harness.stop()

    def test_corrupt_chunk_recovers_zero_lost(self, params):
        """The FIRST streamed chunk truncated in flight: the schema
        check drops it (counted corrupt, never installed), later
        members and the fallback ladder still complete the request
        bit-identically — a lost chunk costs bytes, never answers."""
        from aiko_services_tpu.transport.chaos import FaultPlan
        plan = FaultPlan(seed=5)
        plan.truncate(payload_match="kv_transfer", truncate_to=64,
                      count=1)
        harness = make_harness(params, disagg=True, fault_plan=plan,
                               transfer_timeout=0.5, retries=1)
        try:
            assert harness.wait_discovered(15.0)
            tokens = run_one(harness, "r1", self.LONG, 10,
                             timeout=120.0)
            assert tokens == oracle(params, self.LONG, 10)
            cstats = harness.client.stats
            assert cstats["transfer_corrupt"] >= 1
            assert cstats["installs"] + cstats["local_fallbacks"] >= 1
            assert harness.client.pending_count() == 0
        finally:
            harness.stop()


# -- in-flight prefix dedup window (PR 13 residue d) -------------------------

class TestDedupWindow:
    def make_decoder(self, params, **kwargs):
        from aiko_services_tpu.serving import (ContinuousDecoder,
                                               PrefixKVCache)
        cache = PrefixKVCache(block_tokens=8,
                              max_bytes=kwargs.pop("max_bytes",
                                                   64 << 20),
                              name=f"dedup{id(self)}")
        decoder = ContinuousDecoder(
            params, CONFIG, max_slots=4, prefill_buckets=(64,),
            steps_per_sync=4, prefill_chunk=16, prefix_cache=cache,
            **kwargs)
        return decoder, cache

    def run(self, decoder, requests, rounds=500):
        done = {}
        for rid, (prompt, max_new) in requests.items():
            decoder.submit(rid, prompt, max_new,
                           lambda r, t: done.update({r: t}))
        for _ in range(rounds):
            decoder.pump()
            if len(done) == len(requests):
                break
        assert len(done) == len(requests)
        return done

    def test_same_batch_duplicates_share_prefill(self, params):
        """Two identical prompts submitted TOGETHER: the follower
        defers behind the leader's in-flight prefill, the leader's
        prompt harvests at its first token, and the follower admits as
        a prefix hit — output bit-identical, prefill paid once."""
        decoder, cache = self.make_decoder(params)
        done = self.run(decoder, {"a": (PROMPT, 10),
                                  "b": (PROMPT, 10)})
        expect = oracle(params, PROMPT, 10)
        assert done["a"] == expect and done["b"] == expect
        assert decoder.stats["dedup_deferred"] >= 1
        assert decoder.stats["dedup_shared"] >= 1
        assert decoder.stats["prefix_admits"] == 1
        # the follower prefilled only its suffix: well under 2 prompts
        assert decoder.stats["tokens_prefill"] <= len(PROMPT) + 16
        # no pins leak, no inflight registrations leak
        assert all(n.refs == 0 for n in cache._nodes.values())
        assert decoder._inflight_chains == {}

    def test_leader_budget_refusal_releases_follower(self, params):
        """A leader whose harvest the byte budget refuses must not
        strand its follower: the follower goes cold and still
        completes with identical output."""
        decoder, _ = self.make_decoder(params, max_bytes=1)
        done = self.run(decoder, {"a": (PROMPT, 10),
                                  "b": (PROMPT, 10)})
        expect = oracle(params, PROMPT, 10)
        assert done["a"] == expect and done["b"] == expect
        assert decoder._inflight_chains == {}

    def test_distinct_prompts_do_not_defer(self, params):
        decoder, _ = self.make_decoder(params)
        other = [(i * 7) % 50 + 3 for i in range(40)]
        done = self.run(decoder, {"a": (PROMPT, 8), "b": (other, 8)})
        assert done["a"] == oracle(params, PROMPT, 8)
        assert done["b"] == oracle(params, other, 8)
        assert decoder.stats["dedup_deferred"] == 0


# -- two-pool autoscaling ----------------------------------------------------

class TestTwoPoolAutoscaling:
    def test_pools_scale_on_their_own_signals(self):
        """The prefill-pool autoscaler scales up on prefill queue
        depth while the decode pool holds; the decode pool scales up
        on fleet-merged ITL p95 while the prefill pool holds."""
        import json as _json

        from aiko_services_tpu import (EventEngine, ProcessRuntime,
                                       VirtualClock)
        from aiko_services_tpu.event import settle_virtual
        from aiko_services_tpu.observe.sketch import Sketch
        from aiko_services_tpu.serving_disagg import \
            two_pool_autoscalers
        from tests.test_autoscaler import StubManager

        engine = EventEngine(VirtualClock())
        rt = ProcessRuntime(name="tp", engine=engine).initialize()
        prefill_mgr, decode_mgr = StubManager(1), StubManager(1)
        prefill_as, decode_as = two_pool_autoscalers(
            rt, prefill_mgr, decode_mgr, interval=1.0)

        def publish(process, prefill_depth=None, itl_values=()):
            snapshot = {}
            if prefill_depth is not None:
                snapshot["prefill_queue_depth"] = {
                    "type": "gauge",
                    "series": [{"labels": {}, "value": prefill_depth}]}
            if itl_values:
                sketch = Sketch()
                for value in itl_values:
                    sketch.observe(value)
                snapshot["serving_itl_seconds"] = {
                    "type": "sketch",
                    "series": [{"labels": {}, **sketch.to_dict()}]}
            topic_path = f"{rt.namespace}/host/{process}"
            rt.publish(f"{topic_path}/0/metrics", _json.dumps(
                {"topic_path": topic_path, "snapshot": snapshot}))

        # phase 1: prefill backlog only
        for _ in range(8):
            publish("prefill0", prefill_depth=32.0)
            settle_virtual(engine, 1.0)
        assert len(prefill_mgr.clients) > 1, \
            "prefill pool should grow on its queue backlog"
        assert len(decode_mgr.clients) == 1, \
            "decode pool must not scale on prefill backlog"

        # phase 2: quiet prefill, decode ITL blows past its threshold
        decode_before = len(decode_mgr.clients)
        total = 0
        for round_i in range(10):
            publish("decode0",
                    itl_values=[0.2] * (total + 40))
            total += 40
            settle_virtual(engine, 1.0)
        assert len(decode_mgr.clients) > decode_before, \
            "decode pool should grow on fleet-merged ITL p95"
        prefill_as.stop()
        decode_as.stop()
        rt.terminate()


# -- role tags ---------------------------------------------------------------

class TestRoleTags:
    def test_prefill_runtime_advertises_role_tag(self, params):
        harness = make_harness(params, disagg=True)
        try:
            assert harness.wait_discovered(15.0)
            fields = None
            for fields_i in harness._services_cache.services:
                if "role=prefill" in fields_i.tags:
                    fields = fields_i
            assert fields is not None, \
                "prefill runtime's record must carry role=prefill"
        finally:
            harness.stop()

    def test_pipeline_placeholder_captures_roles(self):
        from aiko_services_tpu.pipeline import (
            _RemoteElementPlaceholder, PipelineElementDefinition)
        placeholder = _RemoteElementPlaceholder(
            PipelineElementDefinition(name="x"))
        assert placeholder.roles == {}


# -- PE_LlamaAgent integration ----------------------------------------------

def test_llama_agent_disagg_routes_through_prefill_pool(make_runtime,
                                                        engine):
    """PE_LlamaAgent with disagg=true: the agent's prompt rides a
    PrefillClient to a discovered role=prefill runtime, the shipped
    chain installs into the agent decoder's cache, and the request
    admits as a prefix hit in the `remote` population — the whole
    split through the ordinary pipeline serving plane."""
    from aiko_services_tpu.compute import ComputeRuntime
    from aiko_services_tpu.pipeline import (Pipeline,
                                            parse_pipeline_definition)
    from aiko_services_tpu.registrar import Registrar
    from aiko_services_tpu.serving_disagg import PrefillRuntime
    from aiko_services_tpu.share import ServicesCache

    reg_rt = make_runtime("dz_reg").initialize()
    Registrar(reg_rt)
    engine.clock.advance(2.1)           # primary promotion
    for _ in range(300):
        engine.step()

    tiny = LLAMA_PRESETS["tiny"]
    prefill_rt = make_runtime("dz_prefill").initialize()
    prefill = PrefillRuntime(
        prefill_rt, "dz_prefill",
        params=llama_init(jax.random.PRNGKey(0), tiny), config=tiny,
        block_tokens=8, max_slots=2, prefill_buckets=(16,),
        prefill_chunk=16)

    host = make_runtime("dz_host").initialize()
    ComputeRuntime(host, "compute")
    definition = parse_pipeline_definition({
        "version": 0, "name": "p_dz", "runtime": "jax",
        "graph": ["(PE_LlamaAgent)"],
        "parameters": {
            "PE_LlamaAgent.preset": "tiny",
            "PE_LlamaAgent.max_tokens": 6,
            "PE_LlamaAgent.prompt_length": 16,
            "PE_LlamaAgent.mode": "continuous",
            "PE_LlamaAgent.max_batch": 2,
            "PE_LlamaAgent.steps_per_sync": 2,
            "PE_LlamaAgent.prefix_block": 8,
            "PE_LlamaAgent.prefill_chunk": 16,
            "PE_LlamaAgent.role": "decode",
            "PE_LlamaAgent.disagg": True,
        },
        "elements": [{
            "name": "PE_LlamaAgent",
            "input": [{"name": "text"}],
            "output": [{"name": "response"},
                       {"name": "response_tokens"}],
            "parameters": {},
        }],
    })
    pipeline = Pipeline(host, definition,
                        services_cache=ServicesCache(host),
                        stream_lease_time=0)
    done = []
    pipeline.add_frame_handler(done.append)
    pipeline.create_stream("s1", lease_time=0)
    agent = next(node.element for node in pipeline.graph.nodes()
                 if node.name == "PE_LlamaAgent")
    # let discovery settle: the client registers candidates as the
    # services-cache sync lands (a frame racing discovery would ride
    # the counted local_no_pool fallback instead — correct, but not
    # what this test measures)
    for _ in range(400):
        engine.step()
    assert agent._prefill_client.loads, "prefill pool not discovered"
    pipeline.post("process_frame", "s1",
                  {"text": "hello there prefill pool"})
    for _ in range(8000):
        if done:
            break
        engine.clock.advance(0.002)
        engine.step()
    assert done, "agent frame never completed"
    assert done[0].swag["response"]
    client = agent._prefill_client
    assert client is not None
    assert client.stats["transfers"] == 1
    assert client.stats["installs"] == 1
    assert client.stats["local_fallbacks"] == 0
    assert prefill.stats["computed"] == 1
    assert agent.decoder.stats["prefix_admits"] == 1
    remote = agent.decoder.slo_sketch_stats(prefill="remote")
    assert remote["ttft_p50_ms"] is not None
    # the pipeline's discovery record carries the decode role tag
    assert "role=decode" in pipeline.tags
    pipeline.destroy_stream("s1")
    pipeline.stop()
    prefill.stop()


# -- review-fix regressions --------------------------------------------------

class TestPagedDisagg:
    """ISSUE 15: the disaggregated plane over a PAGED decode pool —
    shipped KV lands ONCE (wire -> pool scatter), the admit is a table
    edit, bursts coalesce into batch envelopes, and a cacheless pool
    installs by direct slot-table aliasing."""

    def test_paged_install_lands_once_bit_identical(self, params):
        harness = make_harness(params, disagg=True,
                               decoder_opts={"paged_kv": True})
        try:
            assert harness.wait_discovered(15.0)
            tokens = run_one(harness, "r1", PROMPT, 10)
            assert tokens == oracle(params, PROMPT, 10)
            stats = harness.client.stats
            assert stats["installs"] == 1
            assert stats["local_fallbacks"] == 0
            assert harness.decoder.stats["prefix_admits"] == 1
            # the whole point: the admit moved ZERO KV bytes — the
            # transfer's pool write was the only landing
            assert harness.decoder.stats["prefix_copy_bytes"] == 0
            assert harness.decoder.pool.stats["install_blocks"] == 5
        finally:
            harness.stop()

    def test_burst_coalesces_into_batch_envelopes(self, params):
        """Same-destination transfers inside the batch window ride ONE
        kv_transfer_batch envelope (PR 14 residue b).  The client
        waits for a transfer as long as the test waits for the burst:
        on a loaded machine the prefill side's first compile outlasts
        the default 5 s twice, and a transfer that gives up is
        prefilled locally and never installed."""
        harness = make_harness(params, disagg=True, max_slots=8,
                               prefill_slots=4, batch_window=0.05,
                               transfer_timeout=300.0,
                               decoder_opts={"paged_kv": True})
        try:
            assert harness.wait_discovered(15.0)
            rng = np.random.default_rng(3)
            done = {}
            for i in range(6):
                prompt = rng.integers(1, CONFIG.vocab,
                                      size=40).tolist()
                harness.submit(f"b{i}", prompt, 4,
                               lambda r, t: done.update({r: t}))
            assert harness.run_until(lambda: len(done) == 6,
                                     timeout=300.0)
            pstats = harness.prefill.stats
            assert pstats["batched_envelopes"] >= 1
            assert pstats["envelopes"] < 6        # burst amortized
            assert harness.client.stats["batched_replies"] >= 1
            assert harness.client.stats["installs"] == 6
            assert harness.client.stats["local_fallbacks"] == 0
            from aiko_services_tpu.observe.metrics import \
                default_registry
            assert default_registry().value(
                "disagg_transfer_batched_total",
                {"runtime": "disagg_prefill"}) >= 2
        finally:
            harness.stop()

    def test_cacheless_decode_pool_direct_install(self, params):
        """A paged decoder WITHOUT a prefix cache still rides the
        split: shipped blocks land in its pool and alias into the
        request's slot table (ISSUE 15 satellite — PR 14 residue d)."""
        from aiko_services_tpu.serving import ContinuousDecoder
        from aiko_services_tpu.serving_disagg import PrefillClient
        harness = make_harness(params, disagg=True,
                               decoder_opts={"paged_kv": True})
        try:
            assert harness.wait_discovered(15.0)
            cacheless = ContinuousDecoder(
                params, CONFIG, max_slots=4, prefill_buckets=(64,),
                steps_per_sync=4, prefill_chunk=16, paged_kv=True,
                kv_block=8, name="cacheless")
            harness.engine.add_flatout_handler(cacheless.pump)
            client = PrefillClient(harness.decode_rt, cacheless,
                                   name="cacheless",
                                   transfer_timeout=60.0)
            client.add_candidate(harness.prefill.topic_path)
            done = {}
            client.submit("c1", PROMPT, 10,
                          lambda r, t: done.update({r: t}))
            assert harness.run_until(lambda: "c1" in done,
                                     timeout=300.0)
            assert done["c1"] == oracle(params, PROMPT, 10)
            assert client.stats["direct_installs"] == 1
            assert client.stats["local_fallbacks"] == 0
            assert cacheless.stats["prefix_admits"] == 1
            # cacheless: nothing survives the request — full drain
            assert harness.run_until(lambda: cacheless.idle,
                                     timeout=60.0)
            assert cacheless.pool.used_blocks() == 0
            client.stop()
            harness.engine.remove_flatout_handler(cacheless.pump)
        finally:
            harness.stop()

    def test_corrupt_batch_member_fails_alone(self, params):
        """One truncated member of a batch envelope rides the corrupt
        rung; its siblings still install."""
        good = wire.encode_kv_transfer(
            "g1", "", list(range(16)), 0, 8,
            ("2", "2", "16", "float32", "False", "8", "4"),
            [[{"k": np.zeros((2, 8, 16), np.float32),
               "v": np.zeros((2, 8, 16), np.float32)}
              for _ in range(2)]])
        batch = wire.encode_kv_batch([good[:40], good])
        members = wire.decode_kv_batch(batch)
        assert len(members) == 2
        with pytest.raises(wire.WireError):
            wire.decode_kv_transfer(members[0])
        out = wire.decode_kv_transfer(members[1])
        assert out["transfer_id"] == "g1"
        with pytest.raises(wire.WireError):
            wire.decode_kv_batch(good)      # foreign command refused
        with pytest.raises(wire.WireError):
            wire.encode_kv_batch([])


class TestReviewFixes:
    def test_non_array_leaves_raise_wire_error_not_attribute_error(
            self):
        """A version-drifted kv_transfer whose leaves decoded as
        strings must fail as WireError (the recovery ladder's catch),
        never AttributeError out of the message handler."""
        tokens = np.arange(8, dtype=np.int32)
        garbage = wire.encode_envelope(
            "kv_transfer",
            ["t", "", "0", "8", "", [], {"tokens": tokens},
             [[{"k": "garbage", "v": "garbage"}]]])
        with pytest.raises(wire.WireError):
            wire.decode_kv_transfer(garbage)
        bad_q = wire.encode_envelope(
            "kv_transfer",
            ["t", "", "0", "8", "", [], {"tokens": tokens},
             [[{"k": {"q": "x", "s": "y"}, "v": "z"}]]])
        with pytest.raises(wire.WireError):
            wire.decode_kv_transfer(bad_q)
        bad_tokens = wire.encode_envelope(
            "kv_transfer",
            ["t", "", "0", "8", "", [], {"tokens": "nope"}, []])
        with pytest.raises(wire.WireError):
            wire.decode_kv_transfer(bad_tokens)

    def test_late_follower_shares_without_waiting_out_generation(
            self, params):
        """A duplicate prompt arriving AFTER the leader's first token
        must not wait out the leader's whole generation: the leader's
        prompt harvests at the follower's admit check, and the
        follower admits as a prefix hit while the leader is still
        decoding."""
        from aiko_services_tpu.serving import (ContinuousDecoder,
                                               PrefixKVCache)
        cache = PrefixKVCache(block_tokens=8, max_bytes=64 << 20,
                              name="late_dedup")
        decoder = ContinuousDecoder(
            params, CONFIG, max_slots=4, prefill_buckets=(64,),
            steps_per_sync=2, prefill_chunk=16, prefix_cache=cache)
        done = {}
        decoder.submit("leader", PROMPT, 40,
                       lambda r, t: done.update({r: t}))
        # pump until the leader is PAST its first token but far from
        # retiring
        for _ in range(200):
            decoder.pump()
            leader = next((r for r in decoder._slots
                           if r is not None), None)
            if leader is not None and leader.generated:
                break
        assert leader is not None and leader.generated
        assert len(leader.generated) < 30
        decoder.submit("dup", PROMPT, 8,
                       lambda r, t: done.update({r: t}))
        for _ in range(400):
            decoder.pump()
            if "dup" in done:
                break
        assert "dup" in done
        # the follower shared the leader's prompt via the late
        # harvest: prefix admit, no re-prefill of the prompt
        assert decoder.stats["prefix_admits"] == 1
        assert decoder.stats["dedup_shared"] >= 1
        assert done["dup"] == oracle(params, PROMPT, 8)
        while "leader" not in done:
            decoder.pump()
        assert done["leader"] == oracle(params, PROMPT, 40)
        assert decoder._inflight_chains == {}

    def test_sync_shed_signals_exactly_once(self, params):
        """A synchronous local-rung shed returns False WITHOUT also
        firing on_refused (one refusal, one signal)."""
        from aiko_services_tpu.serving import (ContinuousDecoder,
                                               PrefixKVCache)
        from aiko_services_tpu.serving_disagg import PrefillClient
        from aiko_services_tpu.event import EventEngine
        from aiko_services_tpu.process import ProcessRuntime
        rt = ProcessRuntime(name="shed_rt",
                            engine=EventEngine()).initialize()
        cache = PrefixKVCache(block_tokens=8, name="shed_cache")
        decoder = ContinuousDecoder(params, CONFIG, max_slots=2,
                                    prefill_buckets=(64,),
                                    prefix_cache=cache)
        client = PrefillClient(rt, decoder, name="shed")
        refused = []
        # force a synchronous refusal: a measured round EWMA plus an
        # already-passed deadline makes estimated_admit_wait shed
        decoder._round_ewma = 10.0
        import time as _time
        ok = client.submit("r1", [1, 2, 3], 4,
                           lambda *_: None,
                           deadline=_time.monotonic() - 1.0,
                           on_refused=refused.append)
        assert ok is False          # short prompt -> sync local rung
        assert refused == []        # ...and NOT signalled twice
        assert client.stats["install_shed"] == 1
        client.stop()
        rt.terminate()

    def test_geometry_wrong_blocks_refused_before_any_row_lands(
            self, params):
        """Schema-legal but geometry-wrong blocks (wrong layer count /
        head extents) must be refused at install — a poisoned chain
        would wedge the decode pump at its next hit."""
        from aiko_services_tpu.serving import (ContinuousDecoder,
                                               PrefixKVCache)
        cache = PrefixKVCache(block_tokens=8, name="geom")
        ContinuousDecoder(params, CONFIG, max_slots=2,
                          prefill_buckets=(64,), prefix_cache=cache)
        good_leaf = np.zeros(
            (CONFIG.num_kv_heads, 8, CONFIG.head_dim),
            np.float32).astype(jnp.bfloat16)
        # wrong layer count
        with pytest.raises(ValueError):
            cache.install_chain("t", list(range(8)), 0,
                                [{"k": [good_leaf], "v": [good_leaf]}]
                                if CONFIG.num_layers != 1 else
                                [{"k": [], "v": []}])
        # wrong head extent
        bad_leaf = np.zeros((CONFIG.num_kv_heads + 1, 8,
                             CONFIG.head_dim), np.float32)
        with pytest.raises(ValueError):
            cache.install_chain("t", list(range(8)), 0, [{
                "k": [bad_leaf] * CONFIG.num_layers,
                "v": [bad_leaf] * CONFIG.num_layers}])
        assert len(cache) == 0, "no row may land from a refused block"

    def test_role_aware_rotation_stays_within_role(self):
        """A mixed-role candidate set must rotate a decode hop onto
        the other DECODE candidate, not the prefill runtime."""
        from aiko_services_tpu.pipeline import (
            _RemoteElementPlaceholder, PipelineElementDefinition)

        class StubPipeline:
            _remote: dict = {}
            activated = []

            def _activate_remote(self, node, topic, failover=False):
                self.activated.append(topic)

        from aiko_services_tpu.pipeline import Pipeline
        stub = StubPipeline()
        placeholder = _RemoteElementPlaceholder(
            PipelineElementDefinition(name="x"))
        placeholder.topic_path = "ns/h/1/1"
        placeholder.candidates = {"ns/h/1/1": None, "ns/h/2/1": None,
                                  "ns/h/3/1": None}
        placeholder.roles = {"ns/h/1/1": "decode",
                             "ns/h/2/1": "prefill",
                             "ns/h/3/1": "decode"}
        stub._remote = {"x": placeholder}
        Pipeline._rotate_candidate(stub, "x")
        assert stub.activated == ["ns/h/3/1"], \
            "rotation must skip the prefill-role candidate"

    def test_long_prompt_past_bucket_still_ships_blocks(self, params):
        """A PrefillRuntime built WITHOUT an explicit prefill_chunk
        must still compute and ship chains for prompts longer than
        its largest bucket (chunked prefill is forced on; the old
        default truncated the prompt so _ship matched nothing).
        Since past-bucket prompts take the chunked path, chunk
        streaming engages by default: every chain block must cross
        exactly once across the chunk envelopes plus the final."""
        from aiko_services_tpu.event import EventEngine
        from aiko_services_tpu.process import ProcessRuntime
        from aiko_services_tpu.serving_disagg import PrefillRuntime
        rt = ProcessRuntime(name="long_pf",
                            engine=EventEngine()).initialize()
        prefill = PrefillRuntime(rt, "long_pf", params=params,
                                 config=CONFIG, block_tokens=8,
                                 max_slots=2, prefill_buckets=(16,),
                                 pump_period=0)
        got = []
        reply_topic = f"{rt.topic_path}/0/reply"
        rt.add_message_handler(lambda t, p: got.append(p),
                               reply_topic, binary=True)
        long_prompt = [(i * 7) % 90 + 1 for i in range(40)]  # > bucket
        prefill.prefill("t1", reply_topic, "", "0",
                        {"tokens": np.asarray(long_prompt, np.int32)})
        assert rt.event.run_until(
            lambda: got and wire.decode_kv_transfer(got[-1])["final"],
            timeout=60.0)
        outs = [wire.decode_kv_transfer(p) for p in got]
        final = outs[-1]
        assert all(not o["final"] for o in outs[:-1])
        assert sum(len(o["blocks"]) for o in outs) == 5   # 40 tok / 8
        assert [int(t) for t in final["tokens"]] == long_prompt
        assert prefill.stats["empty_ships"] == 0
        assert prefill.stats["chunks_shipped"] >= 1
        prefill.stop()
        rt.terminate()

    def test_role_tagged_pipeline_is_not_a_prefill_candidate(
            self, params):
        """A pipeline record tagged role=prefill (the PE `role`
        parameter tags its whole pipeline) must NOT be routed
        transfers — it has no `prefill` RPC.  Discovery filters on
        the prefill PROTOCOL too."""
        from aiko_services_tpu.service import Service
        harness = make_harness(params, disagg=True)
        try:
            assert harness.wait_discovered(15.0)
            real = set(harness.client.loads)
            decoy = Service(harness.decode_rt, "decoy",
                            "pipeline", tags=["role=prefill"])
            harness.decode_rt._register_service(decoy)
            harness.run_until(lambda: False, timeout=0.5)
            assert decoy.topic_path not in harness.client.loads
            assert set(harness.client.loads) == real
        finally:
            harness.stop()

    def test_client_stop_unregisters_its_reply_topic(self, params):
        """A stopped client's uuid reply topic must leave the peer
        negotiation record — later redials must not re-pin dead
        topics forever."""
        harness = make_harness(params, disagg=True)
        try:
            assert harness.wait_discovered(15.0)
            run_one(harness, "r1", PROMPT, 8)   # channel negotiated
            host = harness.decode_rt.peer
            topic = harness.client.reply_topic
            assert any(topic in r.get("reply_topics", ())
                       for r in host._negotiations.values())
            harness.client.stop()
            assert not any(topic in r.get("reply_topics", ())
                           for r in host._negotiations.values())
            assert not any(k[1] == topic for k in host._attached)
            harness.client = None       # stop() already ran
        finally:
            harness.stop()
