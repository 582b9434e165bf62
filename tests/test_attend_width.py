# The width a paged round's step attends at (ISSUE 28): a short ladder of
# widths the decoder owns (the cap, half, a quarter, none under a floor),
# the smallest that covers the round chosen in pump, every width of a step
# count compiled ahead of time at the count's first dispatch.  At a size a
# test holds: max_seq 96 in blocks of 8, the floor patched down to 24, so
# the ladder is 24 / 48 / 96 and a context of a few dozen tokens crosses it.

import dataclasses

import jax
import jax.monitoring
import pytest

import aiko_services_tpu.serving as serving
from aiko_services_tpu.models.llama import LLAMA_PRESETS, llama_init
from aiko_services_tpu.observe import profiler as P
from aiko_services_tpu.serving import ContinuousDecoder

CONFIG = dataclasses.replace(LLAMA_PRESETS["tiny"], max_seq_len=96)
PROMPT = [(i * 13) % 50 + 1 for i in range(60)]
FLOOR, LADDER = 24, (24, 48, 96)
WIDTH = P.ROUND_RECORD.index("attend_width")
STEPS = P.ROUND_RECORD.index("num_steps")


@pytest.fixture(scope="module")
def params():
    return llama_init(jax.random.PRNGKey(0), CONFIG)


_SEQ = [0]


def decoder_of(params, **kwargs):
    _SEQ[0] += 1
    return ContinuousDecoder(
        params, CONFIG, paged_kv=True, kv_block=8, max_slots=4,
        prefill_buckets=(16,), prefill_chunk=16, steps_per_sync=4,
        name=f"width{_SEQ[0]}", **kwargs)


def serve(decoder, requests, rounds=400, each_round=None):
    done = {}
    for rid, (prompt, max_new) in requests.items():
        assert decoder.submit(rid, prompt, max_new,
                              lambda rid, t: done.update({rid: t}))
    for _ in range(rounds):
        decoder.pump()
        if each_round is not None:
            each_round()
        if len(done) == len(requests):
            return done
    raise AssertionError(f"{len(done)}/{len(requests)} completed")


@pytest.mark.parametrize("max_seq, block, floor, expected", [
    (2048, 32, 512, (512, 1024, 2048)),     # the benchmark's decoder
    (1280, 32, 512, (640, 1280)),           # chip_smoke's: a quarter is under
    (1024, 32, 512, (512, 1024)),
    (512, 32, 512, (512,)),                 # at the floor: one width
    (96, 8, 512, (96,)),                    # every test geometry: one width
    (96, 8, 24, LADDER),
    (100, 8, 24, (32, 56, 100)),            # rounded UP to whole blocks
    (40, 8, 24, (24, 40)),                  # half of 40 is 20 -> 24 blocks up
])
def test_the_ladder(monkeypatch, max_seq, block, floor, expected):
    monkeypatch.setattr(serving, "_ATTEND_FLOOR", floor)
    assert serving._attend_ladder(max_seq, block) == expected


@pytest.mark.parametrize("required_t, width", [
    (1, 24), (24, 24), (25, 48), (48, 48), (49, 96), (96, 96),
    (101, 96),          # within a round of max_seq: the cap, merge headroom
])
def test_the_smallest_width_that_covers_the_round(monkeypatch, params,
                                                  required_t, width):
    monkeypatch.setattr(serving, "_ATTEND_FLOOR", FLOOR)
    decoder = decoder_of(params)
    assert decoder._attend_widths == LADDER
    assert decoder._attend_width(required_t) == width


def test_t_block_sets_no_paged_width(params):
    decoders = [decoder_of(params, t_block=t_block)
                for t_block in (8, 32, 96, 256)]
    for decoder in decoders:
        assert decoder._attend_widths == (96,) and decoder._cache_t == 96
        assert decoder._table_blocks == 13          # 96 + 4 of headroom
    # ... but the pool still starts at what t_block asks for
    assert [d.pool.num_blocks - 1 for d in decoders] == [4, 16, 48, 48]


@pytest.mark.parametrize("speculate_k", [0, 2], ids=["plain", "speculative"])
@pytest.mark.parametrize("kv", [None, "int8"], ids=["native", "int8"])
def test_same_tokens_as_the_cap_across_two_widths(monkeypatch, params, kv,
                                                  speculate_k):
    """A short prompt grows from 6 through 24 and 48 positions while a long
    one is still in its chunks (a slot that is not scanned does not hold
    the width up), then both decode at the cap."""
    requests = {"grows": (PROMPT[:6], 70), "long": (PROMPT, 30),
                "late": (PROMPT[:20], 9)}
    kwargs = dict(kv_cache_dtype=kv, speculate_k=speculate_k)
    pinned = decoder_of(params, **kwargs)
    assert pinned._attend_widths == (96,)
    expected = serve(pinned, requests)
    monkeypatch.setattr(serving, "_ATTEND_FLOOR", FLOOR)
    laddered = decoder_of(params, **kwargs)
    assert serve(laddered, requests) == expected
    widths = [r[WIDTH] for r in laddered.profiler.ring if r[STEPS]]
    assert set(widths) == set(LADDER), widths
    assert widths[0] == 24 and widths[-1] == 96
    assert {r[WIDTH] for r in pinned.profiler.ring if r[STEPS]} == {96}
    # a round that ran no step attended at nothing
    assert all(r[WIDTH] == 0 for r in laddered.profiler.ring if not r[STEPS])
    assert laddered.pool.used_blocks() == 0


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_decoders_of_one_config_share_one_step(params, paged):
    """The builder caches are keyed by the config alone (and, paged, by
    the kernel toggle): a second decoder finds the first one's jit
    object, and with it every executable already compiled."""
    from aiko_services_tpu import serving_paged
    kwargs = dict(max_slots=2, prefill_buckets=(16,))
    one = ContinuousDecoder(params, CONFIG, paged_kv=paged, **kwargs)
    other = ContinuousDecoder(params, dataclasses.replace(CONFIG),
                              paged_kv=paged, steps_per_sync=2, **kwargs)
    shared = serving_paged._paged_step_for(CONFIG, False) if paged \
        else serving._step_for(CONFIG)
    assert one._step is other._step is shared


class Compiles:
    """jax's own backend-compile events, as benchmark/run.py's CompileClock
    counts them (a hit of the persistent cache fires one too)."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@pytest.mark.parametrize("speculate_k", [0, 2], ids=["plain", "speculative"])
def test_nothing_compiles_after_a_step_counts_first_round(
        monkeypatch, params, speculate_k):
    monkeypatch.setattr(serving, "_ATTEND_FLOOR", FLOOR)
    # a geometry of its own (three slots): no other test's executables
    decoder = ContinuousDecoder(
        params, CONFIG, paged_kv=True, kv_block=8, max_slots=3,
        prefill_buckets=(16,), steps_per_sync=4, speculate_k=speculate_k,
        name=f"compiles{speculate_k}")
    compiles, seen = Compiles(), []

    def each_round():
        record = decoder.profiler.ring[-1]
        seen.append((record[STEPS], record[WIDTH], compiles.count))

    # 77 tokens after the first: nineteen rounds of four and one of one
    serve(decoder, {"grows": (PROMPT[:6], 78)}, each_round=each_round)
    stepped = [s for s in seen if s[0]]
    assert {width for _, width, _ in stepped} == set(LADDER)
    assert len({steps for steps, _, _ in stepped}) > 1
    # the plain step has ONE program a width, the longest round's, and a
    # shorter round is cut by its budgets; the speculative step has one a
    # step count: either way every round reads the compile count that
    # the first round through its program left
    program_of = (lambda steps: steps) if speculate_k else (lambda steps: 4)
    first = {}
    for steps, width, count in stepped:
        assert count == first.setdefault(program_of(steps), count), \
            (steps, width, seen)
    assert {key[:2] for key in decoder._step_programs} == \
        {(steps, width) for steps in first for width in LADDER}
    assert compiles.count > 0           # the listener heard the first rounds


@pytest.mark.parametrize("new_tokens", [2, 3, 4, 6, 7])
def test_a_shorter_round_is_cut_by_its_budgets(params, new_tokens):
    """Rounds of one, two and four steps through the one program of four:
    the same tokens as the dense decoder, whose step has a program a
    length, and no step counted that the round did not plan."""
    requests = {"a": (PROMPT[:9], new_tokens), "b": (PROMPT[:5], 9)}
    dense = ContinuousDecoder(params, CONFIG, max_slots=4,
                              prefill_buckets=(16,), steps_per_sync=4)
    paged = decoder_of(params)
    assert serve(paged, requests) == serve(dense, requests)
    assert {key[0] for key in paged._step_programs} == {4}
    assert paged.stats["steps"] == dense.stats["steps"]
    assert paged.stats["useful_steps"] == dense.stats["useful_steps"]
    assert [r[STEPS] for r in paged.profiler.ring] == \
        [r[STEPS] for r in dense.profiler.ring]


def test_a_change_of_width_uploads_no_table(monkeypatch, params):
    """The step takes the tables whole and cuts them itself: a prompt of 7
    reaches 20 and 44 positions, where the width changes and no slot takes
    a new block, so both rounds get the array the round before them got."""
    monkeypatch.setattr(serving, "_ATTEND_FLOOR", FLOOR)
    decoder = decoder_of(params)
    rounds = []

    def each_round():
        record = decoder.profiler.ring[-1]
        if record[STEPS]:
            rounds.append((record[WIDTH], decoder._tables_dev))

    serve(decoder, {"grows": (PROMPT[:7], 60)}, each_round=each_round)
    assert decoder._tables_dev.shape == (4, decoder._table_blocks)
    crossings = [(a, b) for a, b in zip(rounds, rounds[1:]) if a[0] != b[0]]
    assert [(a[0], b[0]) for a, b in crossings] == [(24, 48), (48, 96)]
    assert all(a[1] is b[1] for a, b in crossings)
