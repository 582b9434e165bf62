# The serving round seen from inside (ISSUE 24): the ring of round
# records that PhaseProfiler keeps beside its sums, round_log() as the
# way to it, the slow-round warning, the public per-token hook, the
# profiler session's spans, and the names of the compiled programs
# that the benchmark's configuration finds them by.

import dataclasses
import gc
import json
import logging
import os

import jax
import pytest

from aiko_services_tpu.models.llama import LLAMA_PRESETS, llama_init
from aiko_services_tpu.observe import profiler as P
from aiko_services_tpu.serving import ContinuousDecoder

CONFIG = dataclasses.replace(LLAMA_PRESETS["tiny"], max_seq_len=96)
PROMPT = [(i * 13) % 50 + 1 for i in range(40)]
F = {name: index for index, name in enumerate(P.ROUND_FIELDS)}


@pytest.fixture(scope="module")
def params():
    return llama_init(jax.random.PRNGKey(0), CONFIG)


def paged(params, name, **kwargs):
    kwargs.setdefault("max_slots", 4)
    kwargs.setdefault("prefill_buckets", (16, 64))
    kwargs.setdefault("steps_per_sync", 4)
    return ContinuousDecoder(params, CONFIG, paged_kv=True, kv_block=8,
                             name=name, **kwargs)


class Heard(logging.Handler):
    """The decoder's logger does not propagate: listen on it directly."""

    def __init__(self, decoder, level):
        super().__init__(level)
        self.messages, self.logger = [], decoder.logger

    def emit(self, record):
        self.messages.append(record.getMessage())

    def __enter__(self):
        self.logger.addHandler(self)
        return self.messages

    def __exit__(self, *_):
        self.logger.removeHandler(self)


def serve(decoder, requests, pumps=200):
    """Submit, pump until all are done; returns {request: tokens}."""
    done = {}
    for rid, (prompt, new) in requests.items():
        assert decoder.submit(rid, prompt, new,
                              lambda rid, t: done.update({rid: t}))
    for _ in range(pumps):
        decoder.pump()
        if len(done) == len(requests):
            return done
    raise AssertionError(f"{len(done)}/{len(requests)} completed")


class TestRing:
    def test_one_record_a_committed_round_none_for_an_abandoned_tick(
            self, params):
        decoder = paged(params, "ring_a")
        assert P.round_log("ring_a") == []
        decoder.pump()                          # nothing to do: abandoned
        assert P.round_log("ring_a") == []
        serve(decoder, {"a": (PROMPT[:12], 9), "b": (PROMPT[:30], 5)})
        log = P.round_log("ring_a")
        assert len(log) == decoder.profiler.rounds >= 3
        assert [r[F["seq"]] for r in log] == list(range(1, len(log) + 1))
        before = len(log)
        decoder.pump()                          # drained: abandoned again
        assert len(P.round_log("ring_a")) == before

    def test_fields_of_a_record(self, params):
        decoder = paged(params, "ring_b")
        counted = []
        commit = decoder.profiler.commit_round

        def spy(*args):
            counted.append(decoder.stats["rounds"])
            return commit(*args)

        decoder.profiler.commit_round = spy
        serve(decoder, {"a": (PROMPT[:12], 9), "b": (PROMPT[:30], 5),
                        "c": (PROMPT[:7], 3)})
        log = P.round_log("ring_b")
        assert all(len(r) == len(P.ROUND_RECORD) for r in log)
        assert P.ROUND_RECORD[:len(P.ROUND_FIELDS)] == P.ROUND_FIELDS
        # `rounds` is the decoder's counter when the round was committed
        assert [r[F["rounds"]] for r in log] == counted
        assert log[-1][F["rounds"]] == decoder.stats["rounds"]
        for r in log:
            phases = r[F[P.PHASES[0]]:F[P.PHASES[-1]] + 1]
            assert len(phases) == len(P.PHASES)
            assert sum(phases) == pytest.approx(r[F["wall_s"]], rel=1e-9)
            assert r[F["num_steps"]] in (0, 1, 2, 4)
            assert 0 <= r[F["slots"]] <= 4
            assert (r[F["num_steps"]] == 0) == (r[F["slots"]] == 0)
        assert sum(r[F["prefill_tokens"]] for r in log) == 16 + 64 + 16
        assert max(r[F["pending"]] for r in log) == 0    # four slots, three
        # a round begins after the one before it ended: the gap is that
        for earlier, later in zip(log, log[1:]):
            assert later[F["gap_s"]] == pytest.approx(
                later[F["t0"]] - earlier[F["t0"]] - earlier[F["wall_s"]],
                abs=1e-9)
            assert later[F["gap_s"]] >= 0.0
        # and the sums are the ring's columns
        stats = decoder.profiler.phase_stats()
        assert stats["wall_s"] == pytest.approx(
            sum(r[F["wall_s"]] for r in log))
        assert stats["phases"]["host_sync"]["s"] == pytest.approx(
            sum(r[F["host_sync"]] for r in log))

    def test_pending_depth_is_recorded(self, params):
        decoder = paged(params, "ring_c", max_slots=2)
        serve(decoder, {f"r{i}": (PROMPT[:10], 6) for i in range(5)})
        assert max(r[F["pending"]] for r in P.round_log("ring_c")) >= 1

    def test_idle_before_after_a_drained_decoder(self, params):
        decoder = paged(params, "ring_d")
        serve(decoder, {"a": (PROMPT[:12], 6)})
        first = len(P.round_log("ring_d"))
        serve(decoder, {"b": (PROMPT[:12], 6)})
        log = P.round_log("ring_d")
        flags = [r[F["idle_before"]] for r in log]
        # the first round ever and the first after the drain, no other
        assert flags[0] is True and flags[first] is True
        assert flags.count(True) == 2
        assert log[0][F["gap_s"]] == 0.0

    def test_the_ring_is_bounded(self):
        profiler = P.PhaseProfiler("ring_e")
        assert profiler.ring.maxlen == P.RING_ROUNDS == 8192
        for _ in range(P.RING_ROUNDS + 10):
            profiler.begin_round()
            profiler.commit_round()
        log = P.round_log("ring_e")
        assert len(log) == P.RING_ROUNDS
        assert log[0][F["seq"]] == 11 and log[-1][F["seq"]] == 8202
        profiler.reset()                  # the sums go, the records stay
        assert profiler.rounds == 0 and len(profiler.ring) == P.RING_ROUNDS

    def test_round_log_finds_a_decoder_by_name_and_forgets_it(self, params):
        decoder = paged(params, "ring_f")
        serve(decoder, {"a": (PROMPT[:12], 3)})
        assert len(P.round_log("ring_f")) >= 1
        other = P.PhaseProfiler("ring_f_other")
        with pytest.raises(LookupError):
            P.round_log()                 # several profilers: name one
        del decoder, other
        gc.collect()
        with pytest.raises(LookupError):
            P.round_log("ring_f")
        with pytest.raises(LookupError):
            P.round_log("ring_f_other")

    def test_round_log_without_a_name_takes_the_only_profiler(
            self, monkeypatch):
        import weakref
        monkeypatch.setattr(P, "_profilers", weakref.WeakValueDictionary())
        with pytest.raises(LookupError):
            P.round_log()
        profiler = P.PhaseProfiler("only")
        profiler.begin_round()
        profiler.commit_round(7, 4, 3, 64, 2, 1024)
        (record,) = P.round_log()
        # the fields behind `pending` default to 0 past the ones given
        assert record[F["rounds"]] == 7 and \
            record[F["num_steps"]:] == (4, 3, 64, 2, 1024, 0, 0, 0)


class TestWhatStoodAhead:
    """ISSUE 36: behind `attend_width` a round's record says what prefill
    stood ahead of its step and how deep its own pieces read, and the
    profiler hands out the number of the round that is open."""

    def served(self, params, name):
        """Three requests of different lengths, the first in chunks, each
        submitted a few rounds after the one before: (decoder, the offsets
        of every extend row, the programs `pump` dispatched)."""
        decoder = paged(params, name, prefill_buckets=(16,),
                        prefill_chunk=16)
        offsets, programs = [], []
        extend, admit = decoder._extend_group, decoder._admit_group

        def extend_spy(chunk, width, batch):
            offsets.extend(offset for _, _, offset, _ in batch)
            programs.append("extend")
            return extend(chunk, width, batch)

        def admit_spy(*args):
            programs.append("admit")
            return admit(*args)

        decoder._extend_group, decoder._admit_group = extend_spy, admit_spy
        done = {}
        for rid, prompt, new, pumps in (("b", PROMPT[:40], 5, 2),
                                        ("a", PROMPT[:12], 9, 1),
                                        ("c", PROMPT[:7], 3, 60)):
            assert decoder.submit(rid, prompt, new,
                                  lambda rid, t: done.update({rid: t}))
            for _ in range(pumps):
                decoder.pump()
        assert set(done) == {"a", "b", "c"}
        return decoder, offsets, programs

    def test_the_record_is_pr_24s_fields_then_four(self, params):
        assert P.ROUND_RECORD == P.ROUND_FIELDS + (
            "attend_width", "prefill_ahead", "prefill_pieces",
            "prefill_prefix_tokens")
        assert len(P.ROUND_FIELDS) == 19
        self.served(params, "ahead_a")
        log = P.round_log("ahead_a")
        assert log and all(len(r) == len(P.ROUND_RECORD) for r in log)

    def test_prefill_ahead_is_what_was_dispatched_since_the_step_before(
            self, params):
        self.served(params, "ahead_b")
        log = P.round_log("ahead_b")
        ahead_at = P.ROUND_RECORD.index("prefill_ahead")
        # the chunked prompt opens the ring with rounds that only prefill
        assert log[0][F["num_steps"]] == 0 == log[1][F["num_steps"]]
        assert log[0][F["prefill_tokens"]] == log[1][F["prefill_tokens"]] == 16
        waiting, checked = 0, 0
        for r in log:
            if r[F["num_steps"]]:
                assert r[ahead_at] == waiting
                checked += r[ahead_at] > 0
                waiting = 0
            else:
                assert r[ahead_at] == 0
            waiting += r[F["prefill_tokens"]]
        assert checked >= 2
        first_step = next(r for r in log if r[F["num_steps"]])
        assert first_step[ahead_at] >= 32       # across the rounds before it
        assert sum(r[ahead_at] for r in log) + waiting == \
            sum(r[F["prefill_tokens"]] for r in log) == 16 * 3 + 16 + 16

    def test_pieces_are_programs_and_depth_is_the_extend_rows_offsets(
            self, params):
        _, offsets, programs = self.served(params, "ahead_c")
        log = P.round_log("ahead_c")
        pieces_at = P.ROUND_RECORD.index("prefill_pieces")
        depth_at = P.ROUND_RECORD.index("prefill_prefix_tokens")
        assert sorted(offsets) == [0, 16, 24]     # the last chunk slid back
        assert sum(r[depth_at] for r in log) == sum(offsets) == 40
        assert sum(r[pieces_at] for r in log) == len(programs) == 5
        assert programs.count("extend") == 3
        for r in log:
            assert (r[pieces_at] > 0) == (r[F["prefill_tokens"]] > 0)
            assert r[depth_at] == 0 or r[pieces_at] > 0

    def test_one_admit_of_several_rows_is_one_piece(self, params):
        decoder = paged(params, "ahead_d")
        serve(decoder, {f"r{i}": (PROMPT[:10], 3) for i in range(4)})
        first = P.round_log("ahead_d")[0]
        assert first[F["prefill_tokens"]] == 4 * 16
        assert first[P.ROUND_RECORD.index("prefill_pieces")] == 1

    def test_seq_is_what_the_open_round_will_commit(self, params):
        profiler = P.PhaseProfiler("ahead_e")
        profiler.begin_round()
        assert profiler.seq == 1
        assert profiler.commit_round()[F["seq"]] == 1
        profiler.begin_round()
        assert profiler.seq == 2
        profiler.abandon_round()                 # an idle tick took no number
        profiler.begin_round()
        assert profiler.seq == 2 == profiler.commit_round()[F["seq"]]
        profiler.begin_round()                   # a round that raised half way
        profiler.begin_round()                   # and the next one
        assert profiler.seq == 3 == profiler.commit_round()[F["seq"]]

    def test_the_rule_of_the_round_after_and_the_field_agree(self, params):
        """`benchmark/program_rounds.prefill_classes` takes the round AFTER
        a round with `prefill_tokens` over 0 as the one that pays; the
        program now says it.  The rule implies the field; the field sees
        more only where a round that ran no step stands between."""
        self.served(params, "ahead_f")
        log = P.round_log("ahead_f")
        ahead_at = P.ROUND_RECORD.index("prefill_ahead")
        both = differ = 0
        for before, this in zip(log, log[1:]):
            if not this[F["num_steps"]]:
                continue
            rule, field = before[F["prefill_tokens"]] > 0, this[ahead_at] > 0
            assert field or not rule
            both += rule and field
            if field and not rule:
                differ += 1
                assert before[F["num_steps"]] == 0
        assert both >= 2


class TestSlowRound:
    def test_the_verdict(self):
        def record(gap_s, wall_s, idle_before=False):
            return (5, 9, 100.0, gap_s, idle_before, wall_s,
                    *([wall_s / len(P.PHASES)] * len(P.PHASES)), 4, 2, 0, 0)

        assert P.slow_round(record(0.0, 0.4), 0.01) is None     # the floor
        assert P.slow_round(record(0.0, 0.6), 0.2) is None      # the factor
        assert P.slow_round(record(0.0, 0.6), None) is None     # no mean yet
        assert P.slow_round(record(3.0, 0.1, idle_before=True), 0.1) is None
        line = P.slow_round(record(0.5, 0.1), 0.1)              # the gap counts
        assert "seq=5" in line and "600.0 ms" in line
        for phase in P.PHASES:
            assert f"{phase}=" in line

    def test_a_round_made_slow_by_a_patched_clock_warns_once(
            self, params, monkeypatch):
        decoder = paged(params, "slow_a")
        serve(decoder, {"warm": (PROMPT[:12], 12)})      # programs compiled
        decoder._round_ewma = 0.01       # whatever the compiles left there
        done = {}
        decoder.submit("a", PROMPT[:12], 24,
                       lambda rid, t: done.update({rid: t}))
        real, skew, stall_at = P.time.perf_counter, [0.0], [None]

        class Clock:
            @staticmethod
            def perf_counter():
                return real() + skew[0]

        monkeypatch.setattr(P, "time", Clock)
        enter = decoder.profiler.enter

        def stalling_enter(phase):
            # the fourth round from here stands still for 2 s in its sync
            if phase == "host_sync" and stall_at[0] == decoder.profiler._seq:
                skew[0] += 2.0
            enter(phase)

        decoder.profiler.enter = stalling_enter
        stall_at[0] = decoder.profiler._seq + 3
        with Heard(decoder, logging.WARNING) as heard:
            for _ in range(40):
                decoder.pump()
                if done:
                    break
        assert done
        (message,) = [m for m in heard if "slow round" in m]
        assert f"seq={stall_at[0] + 1}:" in message
        assert "extend_dispatch=200" in message, message
        stalled = P.round_log("slow_a")[stall_at[0]]
        assert stalled[F["wall_s"]] > 2.0

    def test_an_idle_gap_never_warns(self, params, monkeypatch):
        decoder = paged(params, "slow_b")
        serve(decoder, {"warm": (PROMPT[:12], 12)})
        decoder._round_ewma = 0.01
        real = P.time.perf_counter

        class Clock:
            @staticmethod
            def perf_counter():
                return real() + 60.0     # a minute passed while idle

        monkeypatch.setattr(P, "time", Clock)
        with Heard(decoder, logging.WARNING) as heard:
            serve(decoder, {"a": (PROMPT[:12], 12)})
        assert not [m for m in heard if "slow round" in m]
        after_idle = [r for r in P.round_log("slow_b") if r[F["idle_before"]]]
        assert after_idle[-1][F["gap_s"]] > 50.0


class TestOnToken:
    def test_every_token_before_retirement_and_a_raising_hook_survives(
            self, params):
        decoder = paged(params, "hook_a")
        assert decoder.on_token is None
        seen, live = [], []

        def on_token(request_id, slot, token, now):
            seen.append((request_id, slot, token))
            # before retirement: the request still holds its slot
            live.append(decoder._slots[slot].request_id == request_id)
            if len(seen) == 2:
                raise RuntimeError("a hook that raises")

        decoder.on_token = on_token
        with Heard(decoder, logging.ERROR) as heard:
            done = serve(decoder, {"a": (PROMPT[:12], 7),
                                   "b": (PROMPT[:20], 4)})
        assert all(live) and len(seen) == 11
        for rid, tokens in done.items():
            assert [t for r, _, t in seen if r == rid] == list(tokens)
            assert len({s for r, s, _ in seen if r == rid}) == 1
        assert [m for m in heard if "on_token failed" in m]


class TestSpans:
    def test_one_set_of_boundaries_drives_sums_ring_and_spans(
            self, monkeypatch):
        opened, closed = [], []

        class Span:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                opened.append(self.name)

            def __exit__(self, *_):
                closed.append(self.name)

        monkeypatch.setattr(P, "_annotation", Span)
        profiler = P.PhaseProfiler("spans_a")
        profiler.begin_round()
        for phase in ("spec_verify", "admit_dispatch", "extend_dispatch",
                      "host_sync", "wave_resolve", "deliver"):
            profiler.enter(phase)
        record = profiler.commit_round()
        assert opened == [P.SPAN_ROUND, "aiko.decoder.plan",
                          "aiko.decoder.dispatch_step",
                          "aiko.decoder.dispatch_prefill",
                          "aiko.decoder.sync", "aiko.decoder.deliver"]
        assert closed == opened[1:] + [P.SPAN_ROUND]
        assert record[F["scan_dispatch"]] == 0.0 < record[F["spec_verify"]]
        del opened[:], closed[:]
        profiler.begin_round()
        profiler.enter("host_sync")
        profiler.abandon_round()          # an idle tick closes what it opened
        assert sorted(opened) == sorted(closed) and len(opened) == 3
        profiler.begin_round()            # a round that raised half way
        profiler.begin_round()
        profiler.commit_round()
        assert sorted(opened) == sorted(closed)
        assert set(P.SPANS) == set(P.PHASES) - {"other"}

    def test_a_profiler_session_records_the_spans(self, params, tmp_path):
        from jax.profiler import ProfileData
        decoder = paged(params, "spans_b")
        serve(decoder, {"warm": (PROMPT[:12], 6)})
        jax.profiler.start_trace(str(tmp_path))
        serve(decoder, {"a": (PROMPT[:12], 6), "b": (PROMPT[:30], 6)})
        jax.profiler.stop_trace()
        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        names = {}
        for plane in ProfileData.from_file(str(path)).planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    for event in line.events:
                        if event.name.startswith("aiko.decoder."):
                            names[event.name] = names.get(event.name, 0) + 1
        rounds = names.pop(P.SPAN_ROUND)
        assert rounds >= 3
        assert set(names) == set(P.SPANS.values())
        assert names["aiko.decoder.plan"] == rounds
        assert names["aiko.decoder.sync"] == rounds

    def test_observe_imports_no_jax(self):
        import subprocess
        import sys
        code = ("import sys, importlib.util as u\n"
                "import aiko_services_tpu.observe.profiler as p\n"
                "p.PhaseProfiler('x')\n"
                "assert 'jax' not in sys.modules, 'jax came with observe'\n")
        # the package's own __init__ may import jax: load observe alone
        code = ("import sys, types, os\n"
                "root = os.path.join(os.getcwd(), 'aiko_services_tpu')\n"
                "pkg = types.ModuleType('aiko_services_tpu')\n"
                "pkg.__path__ = [root]\n"
                "sys.modules['aiko_services_tpu'] = pkg\n" + code)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        subprocess.run([sys.executable, "-c", code], check=True, cwd=root)


class TestProgramNames:
    def test_the_paged_programs_keep_the_names_the_benchmark_finds(
            self, params):
        """benchmark/configs/*.json lists the compiled programs by the
        names an `XLA Modules` event carries, `jit_<python function>`:
        a rename of step, admit or extend blinds every trace metric."""
        from aiko_services_tpu import serving_paged
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs",
                               "mistral-7b-v0.3-d16.json")) as f:
            programs = json.load(f)["trace"]["programs"]
        decoder = paged(params, "names_a", prefill_buckets=(16,),
                        prefill_chunk=16)
        serve(decoder, {"a": (PROMPT[:12], 6), "b": (PROMPT[:40], 6)})
        step = serving_paged._paged_step_for(CONFIG, False)
        assert decoder._step is step
        fns = decoder._prefill_fns
        extends = {fn.__name__ for key, fn in fns.items()
                   if key[0] == "extend"}
        admits = {fn.__name__ for key, fn in fns.items()
                  if key[0] != "extend"}
        assert extends and admits, sorted(map(str, fns))
        names = {"decode_step": {"jit_" + step.__name__},
                 "prefill": {"jit_" + name for name in admits | extends}}
        for role, listed in programs.items():
            assert names[role] == set(listed), role
        # and the lowered module is named so, whatever jax does to it
        pool = decoder.pool
        lowered = step.lower(
            decoder.params, decoder._tokens, decoder._lengths,
            jax.numpy.ones((4,), bool), jax.numpy.ones((4,), "int32"),
            pool.k_pools, pool.v_pools,
            jax.numpy.asarray(decoder._tables_np),
            num_steps=2, eos=-1, t_cap=decoder._cache_t)
        text = lowered.as_text()
        assert "module @jit_step" in text
        # the regions of ISSUE 24 are in its metadata and nowhere else
        located = lowered.as_text(debug_info=True)
        for scope in ("aiko.kv_view", "aiko.attn_proj", "aiko.attn_core",
                      "aiko.mlp", "aiko.head", "aiko.kv_merge"):
            assert scope in located, scope
            assert scope not in text
