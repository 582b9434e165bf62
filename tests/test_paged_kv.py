# Paged KV block pool tests (ISSUE 15): the paged decoder's greedy
# output must be BIT-IDENTICAL to the dense slot cache across every
# serving composition (native/int8 x chunked x speculation x
# mid-stream admits x disaggregated install), prefix hits must move
# ZERO KV bytes (aliasing, not copying), harvest must be
# refcount-only, copy-on-extend must protect shared blocks, and the
# pool's refcounts must drain to zero live blocks after every retire.
#
# ISSUE 16 adds the fused pallas decode kernel: TestPagedKernelParity
# proves the kernel path (interpret mode on CPU — the same kernel code
# that compiles on TPU) emits greedy tokens identical to the gather
# oracle across the same matrix, and that its traced step contains no
# _gather_views materialization.

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import aiko_services_tpu.serving as serving
from aiko_services_tpu.models.llama import (LLAMA_PRESETS,
                                            llama_greedy_decode,
                                            llama_init)
from aiko_services_tpu.serving import ContinuousDecoder, PrefixKVCache

CONFIG = dataclasses.replace(LLAMA_PRESETS["tiny"], max_seq_len=96)
PROMPT = [(i * 13) % 50 + 1 for i in range(40)]


@pytest.fixture(scope="module")
def params():
    return llama_init(jax.random.PRNGKey(0), CONFIG)


def oracle(params, prompt, max_new):
    out = llama_greedy_decode(params, CONFIG,
                              jnp.asarray([prompt], jnp.int32),
                              max_tokens=max_new)
    return [int(t) for t in np.asarray(out)[0]]


def run(decoder, requests, rounds=400, midstream=None):
    """Drive requests to completion; `midstream` requests are
    submitted after the second pump round (the mid-stream admit leg of
    the parity matrix)."""
    done = {}
    for rid, (prompt, max_new) in requests.items():
        decoder.submit(rid, prompt, max_new,
                       lambda rid, t: done.update({rid: t}))
    total = len(requests) + len(midstream or {})
    for i in range(rounds):
        decoder.pump()
        if i == 1 and midstream:
            for rid, (prompt, max_new) in midstream.items():
                decoder.submit(rid, prompt, max_new,
                               lambda rid, t: done.update({rid: t}))
            midstream = None
        if len(done) == total:
            break
    assert len(done) == total, f"{len(done)}/{total} completed"
    return done


_SEQ = [0]


def pair(params, block=8, cache=False, **kwargs):
    """(dense decoder, paged decoder[, caches]) at one geometry."""
    kwargs.setdefault("max_slots", 4)
    kwargs.setdefault("prefill_buckets", (64,))
    kwargs.setdefault("steps_per_sync", 4)
    if not cache:
        dense = ContinuousDecoder(params, CONFIG, **kwargs)
        paged = ContinuousDecoder(params, CONFIG, paged_kv=True,
                                  kv_block=block, **kwargs)
        return dense, paged
    _SEQ[0] += 1
    dense_cache = PrefixKVCache(block_tokens=block, max_bytes=64 << 20,
                                name=f"pd{_SEQ[0]}")
    paged_cache = PrefixKVCache(block_tokens=block, max_bytes=64 << 20,
                                name=f"pp{_SEQ[0]}")
    dense = ContinuousDecoder(params, CONFIG,
                              prefix_cache=dense_cache, **kwargs)
    paged = ContinuousDecoder(params, CONFIG, paged_kv=True,
                              prefix_cache=paged_cache, **kwargs)
    return dense, paged, dense_cache, paged_cache


REQUESTS = {"a": (PROMPT, 10), "b": (PROMPT[:17] + [3, 4], 8)}
MIDSTREAM = {"mid": (PROMPT[:9] + [7], 6)}


def paged_at(params, impl, block=8, cache=None, **kwargs):
    """One paged decoder with the decode-attention toggle latched to
    `impl` at construction (the only moment serving reads it)."""
    kwargs.setdefault("max_slots", 4)
    kwargs.setdefault("prefill_buckets", (64,))
    kwargs.setdefault("steps_per_sync", 4)
    before = serving.ATTENTION_IMPL
    serving.ATTENTION_IMPL = impl
    try:
        return ContinuousDecoder(params, CONFIG, paged_kv=True,
                                 kv_block=block, prefix_cache=cache,
                                 **kwargs)
    finally:
        serving.ATTENTION_IMPL = before


def kernel_pair(params, block=8, cache=False, **kwargs):
    """(gather-oracle paged decoder, pallas-kernel paged decoder)."""
    if not cache:
        return (paged_at(params, "two_pass", block, **kwargs),
                paged_at(params, "paged_kernel", block, **kwargs))
    _SEQ[0] += 1
    caches = [PrefixKVCache(block_tokens=block, max_bytes=64 << 20,
                            name=f"kp{_SEQ[0]}{tag}")
              for tag in ("o", "k")]
    return (paged_at(params, "two_pass", block, caches[0], **kwargs),
            paged_at(params, "paged_kernel", block, caches[1],
                     **kwargs), caches[0], caches[1])


# -- parity matrix ----------------------------------------------------------

class TestPagedParity:
    def test_native_with_midstream_admit(self, params, assert_ledger_clean):
        dense, paged = pair(params)
        out_d = run(dense, REQUESTS, midstream=MIDSTREAM)
        out_p = run(paged, REQUESTS, midstream=MIDSTREAM)
        assert out_d == out_p
        assert out_p["a"] == oracle(params, PROMPT, 10)
        assert_ledger_clean(pool=paged.pool)      # drain audit

    def test_int8(self, params, assert_ledger_clean):
        dense, paged = pair(params, kv_cache_dtype="int8")
        assert run(dense, REQUESTS) == run(paged, REQUESTS)
        assert_ledger_clean(pool=paged.pool)

    def test_chunked_prefill(self, params, assert_ledger_clean):
        dense, paged = pair(params, prefill_chunk=16)
        long = {"long": ((PROMPT * 3)[:80], 8)} | REQUESTS
        assert run(dense, long) == run(paged, long)
        assert_ledger_clean(pool=paged.pool)

    @pytest.mark.slow
    def test_spec_int8_chunked_midstream(self, params, assert_ledger_clean):
        dense, paged = pair(params, speculate_k=2,
                            kv_cache_dtype="int8", prefill_chunk=16)
        out_d = run(dense, REQUESTS, midstream=MIDSTREAM)
        out_p = run(paged, REQUESTS, midstream=MIDSTREAM)
        assert out_d == out_p
        assert_ledger_clean(pool=paged.pool)

    def test_speculative(self, params, assert_ledger_clean):
        dense, paged = pair(params, speculate_k=2)
        assert run(dense, REQUESTS) == run(paged, REQUESTS)
        assert_ledger_clean(pool=paged.pool)

    def test_eos_retire_inside_round(self, params, assert_ledger_clean):
        # a slot retiring mid-round (EOS) must release its blocks and
        # not corrupt its neighbours' tables
        dense, paged = pair(params, eos_token=3)
        reqs = {"a": (PROMPT, 30), "b": (PROMPT[:11], 30)}
        assert run(dense, reqs) == run(paged, reqs)
        assert_ledger_clean(pool=paged.pool)


# -- fused pallas kernel vs gather oracle (ISSUE 16) ------------------------

class TestPagedKernelParity:
    """Greedy TOKEN identity between the pallas kernel (interpret mode
    on CPU) and the XLA gather oracle — the acceptance matrix: int8 x
    chunked prefill x speculation x mid-stream admits x block sizes.
    Float bit-equality is NOT the claim (the kernel's blockwise dots
    associate differently); emitted-token identity per combination is."""

    def test_native_with_midstream_admit(self, params):
        oracle_d, kernel_d = kernel_pair(params)
        assert kernel_d.paged_kernel and not oracle_d.paged_kernel
        out_o = run(oracle_d, REQUESTS, midstream=MIDSTREAM)
        out_k = run(kernel_d, REQUESTS, midstream=MIDSTREAM)
        assert out_o == out_k
        assert out_k["a"] == oracle(params, PROMPT, 10)
        assert kernel_d.pool.used_blocks() == 0   # drain audit

    def test_int8(self, params):
        oracle_d, kernel_d = kernel_pair(params, kv_cache_dtype="int8")
        assert run(oracle_d, REQUESTS) == run(kernel_d, REQUESTS)
        assert kernel_d.pool.used_blocks() == 0

    def test_speculative(self, params):
        # the (1+k)-token verify widens INSIDE the kernel (W = 1+k):
        # same kernel, no second variant
        oracle_d, kernel_d = kernel_pair(params, speculate_k=2)
        assert run(oracle_d, REQUESTS) == run(kernel_d, REQUESTS)
        assert kernel_d.pool.used_blocks() == 0

    @pytest.mark.parametrize("block", [32, 64])
    def test_block_sizes(self, params, block):
        oracle_d, kernel_d = kernel_pair(params, block=block)
        assert run(oracle_d, REQUESTS) == run(kernel_d, REQUESTS)
        assert kernel_d.pool.used_blocks() == 0

    def test_int8_chunked_prefill(self, params):
        # the delicate leg: the extend oracle DEQUANTIZES then dots
        # (fold_scales=False in the kernel), and any drift compounds
        # through the stored chunk KV
        oracle_d, kernel_d = kernel_pair(params, kv_cache_dtype="int8",
                                         prefill_chunk=16)
        long = {"long": ((PROMPT * 3)[:80], 8)} | REQUESTS
        assert run(oracle_d, long) == run(kernel_d, long)
        assert kernel_d.pool.used_blocks() == 0

    @pytest.mark.slow
    def test_spec_int8_chunked(self, params):
        oracle_d, kernel_d = kernel_pair(params, speculate_k=2,
                                         kv_cache_dtype="int8",
                                         prefill_chunk=16)
        out_o = run(oracle_d, REQUESTS, midstream=MIDSTREAM)
        out_k = run(kernel_d, REQUESTS, midstream=MIDSTREAM)
        assert out_o == out_k
        assert kernel_d.pool.used_blocks() == 0

    def test_copy_on_extend_shared_blocks(self, params):
        # the PR 13 slide-back shape over SHARED blocks, kernel mode:
        # a cached chain is hit, the final chunk slides back into it,
        # copy-on-extend must fire and the kernel must read the copied
        # block — warm output stays identical to the oracle's cold run
        long_prompt = [(i * 7) % 50 + 1 for i in range(95)]
        oracle_d, kernel_d, _, kcache = kernel_pair(params, cache=True,
                                                    prefill_chunk=16)
        cold = run(oracle_d, {"cold": (long_prompt, 1)})["cold"]
        for probe in ("w1", "w2", "w3"):
            warm = run(kernel_d, {probe: (long_prompt, 1)})[probe]
            assert warm == cold, probe
        assert kernel_d.pool.stats["cow_copies"] >= 1
        assert kernel_d.pool.used_blocks() == len(kcache)

    def test_disagg_installed_chain(self, params):
        # blocks shipped from a dense donor land via
        # install_shipped_blocks and the kernel reads the installed
        # chain through its table — TestDirectInstall with kernel on
        donor_cache = PrefixKVCache(block_tokens=8,
                                    max_bytes=64 << 20, name="kdd")
        donor = ContinuousDecoder(params, CONFIG,
                                  prefix_cache=donor_cache,
                                  max_slots=4, prefill_buckets=(64,),
                                  steps_per_sync=4)
        run(donor, {"donor": (PROMPT, 1)})
        kernel_d = paged_at(params, "paged_kernel", prefill_chunk=16)
        keys, hit = donor_cache.match("", PROMPT)
        blocks = []
        for node in donor_cache.nodes(keys):
            k_rows, v_rows = donor_cache.block_rows(node)
            blocks.append({"k": [np.asarray(r) for r in k_rows],
                           "v": [np.asarray(r) for r in v_rows]})
        covered, ids = kernel_d.install_shipped_blocks(PROMPT, 0,
                                                       blocks)
        assert covered == hit == len(ids) * 8
        done = {}
        assert kernel_d.submit("direct", PROMPT, 10,
                               lambda r, t: done.update({r: t}),
                               kv_blocks=(covered, ids))
        for _ in range(400):
            kernel_d.pump()
            if "direct" in done:
                break
        assert done["direct"] == oracle(params, PROMPT, 10)
        assert kernel_d.stats["prefix_copy_bytes"] == 0
        assert kernel_d.pool.used_blocks() == 0

    def test_traced_step_has_no_gather(self, params, monkeypatch):
        # the acceptance clause "no [S,H,T,D] gather in the kernel
        # path's traced step", checked at the trace itself: lower both
        # fresh-built steps and count _gather_views calls
        from aiko_services_tpu import serving_paged
        calls = []
        real = serving_paged._gather_views
        monkeypatch.setattr(
            serving_paged, "_gather_views",
            lambda *a, **k: calls.append(1) or real(*a, **k))
        pools = [jnp.zeros((9, CONFIG.num_kv_heads, 8,
                            CONFIG.head_dim), CONFIG.dtype)
                 for _ in range(CONFIG.num_layers)]
        arrays = (jnp.ones((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
                  jnp.ones((2,), bool), jnp.full((2,), 8, jnp.int32),
                  pools, pools,
                  jnp.zeros((2, 4), jnp.int32))
        serving_paged._build_paged_step(CONFIG, kernel=True).lower(
            params, *arrays, num_steps=4, eos=-1, t_cap=32)
        assert calls == []                   # kernel path: gather-free
        serving_paged._build_paged_step(CONFIG, kernel=False).lower(
            params, *arrays, num_steps=4, eos=-1, t_cap=32)
        assert calls                         # oracle still gathers


# -- the kernel's ragged walk (ISSUE 30) ------------------------------------
# A slot walks its OWN live blocks, a chunk of them a time, and reads no
# dead cell into a result.  At a size a test holds: blocks of 8, a chunk
# patched down to 32 positions (4 blocks), a table of 12 blocks (3 chunks).

WALK_BLOCK, WALK_CHUNK, WALK_TABLE = 8, 32, 12
WALK_CASES = {              # slot -> (entry length, active at round entry)
    "inactive": (50, False), "empty": (0, True), "one": (1, True),
    "one-short-of-a-block": (7, True), "a-block": (8, True),
    "mid-chunk": (45, True), "a-chunk": (32, True),
    "a-chunk-and-one": (33, True), "cap": (96, True)}


@pytest.fixture
def small_chunks(monkeypatch):
    """The walk's chunk at WALK_CHUNK positions.  The jitted step
    builders are shared process-wide and would hand back a program
    traced at another chunk: they are dropped around the test."""
    from aiko_services_tpu import serving_paged
    from aiko_services_tpu.ops import paged_attention
    monkeypatch.setattr(paged_attention, "_CHUNK", WALK_CHUNK)
    serving_paged._paged_step_for.cache_clear()
    yield
    serving_paged._paged_step_for.cache_clear()


@pytest.fixture(scope="module")
def walked():
    """One layer's attention for a slot of every WALK_CASES length:
    (kernel over a POISONED pool, gather oracle over a clean one).
    Poisoned: every dead cell of a last live block and every block past
    it is NaN, table entries past the live ones point at such blocks;
    the clean pool holds zeros there (the oracle's exact-zero weights
    times NaN would be NaN)."""
    from aiko_services_tpu import serving_paged
    from aiko_services_tpu.models import layers as L
    from aiko_services_tpu.models.llama import _layer_init
    from aiko_services_tpu.ops import paged_attention
    slots, block, nb = len(WALK_CASES), WALK_BLOCK, WALK_TABLE
    keys = jax.random.split(jax.random.PRNGKey(30), 6)
    layer = _layer_init(keys[0], CONFIG)
    # one position past max_seq: the new token of the slot at the cap
    cos, sin = L.rope_frequencies(CONFIG.head_dim, CONFIG.max_seq_len + 1,
                                  CONFIG.rope_theta)
    entry = jnp.asarray([case[0] for case in WALK_CASES.values()],
                        jnp.int32)
    active = jnp.asarray([case[1] for case in WALK_CASES.values()])
    shape = (slots * nb + 1, CONFIG.num_kv_heads, block, CONFIG.head_dim)
    tables = 1 + jnp.arange(slots * nb, dtype=jnp.int32).reshape(slots, nb)
    # position of every pool cell in its slot (the null block: dead)
    position = (jnp.arange(nb * block)[None] <
                jnp.where(active, entry, 0)[:, None])
    live = jnp.concatenate([
        jnp.zeros((1, block), bool), position.reshape(slots * nb, block)]
    )[:, None, :, None]
    pools = [jax.random.normal(key, shape, jnp.float32)
             for key in keys[1:3]]
    clean = [jnp.where(live, pool, 0.0) for pool in pools]
    poisoned = [jnp.where(live, pool, jnp.nan) for pool in pools]
    x = jax.random.normal(keys[3], (slots, 1, CONFIG.dim), jnp.float32)
    sides = jnp.zeros((slots, CONFIG.num_kv_heads, 4, CONFIG.head_dim),
                      jnp.float32)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(paged_attention, "_CHUNK", WALK_CHUNK)
        kernel = jax.jit(
            lambda k_pool, v_pool:
            serving_paged._kernel_attention_block(
                tables, layer, CONFIG, x, cos, sin, k_pool, v_pool, sides,
                sides, entry, entry, 0, active)[0])(*poisoned)
    views = [serving_paged._gather_views([pool], tables, nb * block)[0]
             for pool in clean]
    gathered = serving._slot_attention_block(
        layer, CONFIG, x, cos, sin, views[0], views[1], sides, sides,
        jnp.where(active, entry, 0), entry, 0)[0]
    return np.asarray(kernel), np.asarray(gathered)


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_ragged_walk_reads_no_dead_cell(walked, case):
    kernel, gathered = walked
    slot = list(WALK_CASES).index(case)
    assert np.isfinite(kernel[slot]).all()
    np.testing.assert_allclose(kernel[slot], gathered[slot],
                               rtol=2e-5, atol=2e-6)


def test_walk_positions_are_whole_live_blocks():
    from aiko_services_tpu.ops.paged_attention import (walk_positions,
                                                       walks_live_blocks)
    assert walk_positions(np.array([0, 1, 31, 32, 33, 450]), 32).tolist() \
        == [0, 32, 32, 32, 64, 480]
    # what mosaic lets a kernel slice out of HBM by hand; the
    # interpreter has no lanes
    assert walks_live_blocks(128, False) and walks_live_blocks(256, False)
    assert not walks_live_blocks(64, False)
    assert not walks_live_blocks(128, True)
    assert walks_live_blocks(16, False, interpret=True)
    assert not walks_live_blocks(16, True, interpret=True)


# The walk under a sparse selection (ISSUE 39): `chosen` masks positions of
# the blocks a slot walks anyway.  Same sizes as above; a slot a case.

MASKED_CASES = {            # slot -> (entry length, which positions chosen)
    "empty": (0, "some"), "a-block": (8, "some"), "mid-chunk": (45, "some"),
    "cap": (96, "some"), "everything": (45, "all"),
    "nothing-of-the-pool": (45, "none"),
    "without-its-last-chunk": (70, "not-from-64-on"),
    "chosen-past-the-length": (45, "all-the-table")}


@pytest.fixture(scope="module")
def masked_walk():
    """-> (the walk with `chosen`, the walk without, a float64 softmax over
    the chosen live positions and the valid side rows), a slot a case of
    MASKED_CASES, the side rows partly valid (another part a slot).  Every
    dead cell of K and V, chosen or not, holds NaN."""
    from aiko_services_tpu.ops import paged_attention
    slots, block, nb = len(MASKED_CASES), WALK_BLOCK, WALK_TABLE
    heads, groups, dim, side = 2, 2, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(39), 6)
    rng = np.random.default_rng(39)
    entry = np.asarray([case[0] for case in MASKED_CASES.values()], np.int32)
    at = np.arange(nb * block)[None]
    chosen = np.stack([
        {"some": rng.random(nb * block) < 0.3, "all": at[0] < length,
         "none": at[0] < 0, "not-from-64-on": at[0] < 64,
         "all-the-table": at[0] >= 0}[kind]
        for length, kind in MASKED_CASES.values()])
    tables = 1 + rng.permutation(slots * nb).astype(np.int32).reshape(
        slots, nb)
    live = np.zeros((slots * nb + 1, block), bool)
    live[tables] = (at < entry[:, None]).reshape(slots, nb, block)
    pools = [np.where(live[:, None, :, None], np.asarray(jax.random.normal(
        key, (slots * nb + 1, heads, block, dim))), np.nan)
        for key in keys[:2]]
    q = np.asarray(jax.random.normal(keys[2], (slots, heads, groups, dim)))
    sides = [np.asarray(jax.random.normal(key, (slots, heads, side, dim)))
             for key in keys[3:5]]
    valid = rng.random((slots, 1, side)) < 0.5
    valid[:, 0, 0] = True               # a token always attends itself
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(paged_attention, "_CHUNK", WALK_CHUNK)
        masked, plain = (np.asarray(paged_attention.paged_decode_attention(
            *map(jnp.asarray, (q, *pools, tables, *sides, valid, entry)),
            groups=groups, interpret=True, chosen=mask))
            for mask in (jnp.asarray(chosen), None))
    theirs = np.zeros(q.shape)
    for s in range(slots):
        keep = np.concatenate([chosen[s] & (at[0] < entry[s]), valid[s, 0]])
        rows = [np.concatenate([
            pool[tables[s]].transpose(1, 0, 2, 3).reshape(heads, -1, dim),
            side_rows[s]], axis=1)[:, keep].astype(np.float64)
            for pool, side_rows in zip(pools, sides)]
        scores = np.einsum("hgd,hpd->hgp", q[s], rows[0]) / np.sqrt(dim)
        weights = np.exp(scores - scores.max(-1, keepdims=True))
        theirs[s] = np.einsum("hgp,hpd->hgd",
                              weights / weights.sum(-1, keepdims=True),
                              rows[1])
    return masked, plain, theirs


@pytest.mark.parametrize("case", list(MASKED_CASES))
def test_masked_walk_attends_the_chosen_live_positions_alone(masked_walk,
                                                             case):
    masked, plain, theirs = masked_walk
    slot = list(MASKED_CASES).index(case)
    assert np.isfinite(masked[slot]).all()
    np.testing.assert_allclose(masked[slot], theirs[slot], rtol=2e-5,
                               atol=2e-6)
    # a mask of everything live, or of the whole table, is no mask: the
    # same bits as the call that has none; any other is another result
    same = (masked[slot] == plain[slot]).all()
    assert same == (MASKED_CASES[case][1] in ("all", "all-the-table")
                    or MASKED_CASES[case][0] == 0)


def test_the_table_body_refuses_a_mask_by_name():
    """A head of 64 compiled for a chip, or an int8 pool anywhere, goes
    through the body that follows the table: it takes no `chosen`."""
    from aiko_services_tpu.ops.paged_attention import paged_decode_attention
    pool = jnp.zeros((5, 2, WALK_BLOCK, 64))
    side = jnp.zeros((1, 2, 4, 64))
    with pytest.raises(ValueError, match="`chosen`.*the table body"):
        paged_decode_attention(
            jnp.zeros((1, 2, 2, 64)), pool, pool, jnp.ones((1, 4), jnp.int32),
            side, side, jnp.ones((1, 1, 4), bool), jnp.asarray([9]),
            groups=2, interpret=False, chosen=jnp.ones((1, 32), bool))
    with pytest.raises(ValueError, match="names 40 positions"):
        paged_decode_attention(
            jnp.zeros((1, 2, 2, 64)), pool, pool, jnp.ones((1, 4), jnp.int32),
            side, side, jnp.ones((1, 1, 4), bool), jnp.asarray([9]),
            groups=2, interpret=True, chosen=jnp.ones((1, 40), bool))


@pytest.mark.parametrize("kwargs", [{}, {"prefill_chunk": 16}],
                         ids=["bucketed", "chunked"])
def test_ragged_lengths_in_one_round_keep_token_identity(
        params, small_chunks, kwargs):
    """A whole run, kernel against oracle, with slots of very different
    lengths in every round: 3 to 70 tokens of prompt, so one slot walks
    three chunks while its neighbour walks one block."""
    requests = {"tiny": (PROMPT[:3], 12), "short": (PROMPT[:9], 20),
                "block": (PROMPT[:16], 9),
                "long": ((PROMPT * 2)[:70], 20)}
    oracle_d, kernel_d = kernel_pair(params, **kwargs)
    assert kernel_d.step_kernel and kernel_d._walks_live
    assert not oracle_d.step_kernel
    assert run(oracle_d, requests) == run(kernel_d, requests)
    assert kernel_d.pool.used_blocks() == 0


# -- zero-copy prefix hits --------------------------------------------------

class TestPagedPrefixReuse:
    def test_hit_aliases_with_zero_copy_bytes(self, params):
        dense, paged, _, paged_cache = pair(params, cache=True,
                                            prefill_chunk=16)
        donor = {"donor": (PROMPT, 10)}
        probes = {"full": (PROMPT, 10),
                  "part": (PROMPT[:24] + [7, 9, 3], 8)}
        d1, d2 = run(dense, donor), run(dense, probes)
        p1, p2 = run(paged, donor), run(paged, probes)
        assert d1 == p1 and d2 == p2
        assert paged.stats["prefix_admits"] == \
            dense.stats["prefix_admits"] == 2
        # the acceptance number: dense copies the whole chain per hit,
        # paged aliases — zero KV bytes move on admit AND harvest
        assert dense.stats["prefix_copy_bytes"] > 0
        assert dense.stats["harvest_copy_bytes"] > 0
        assert paged.stats["prefix_copy_bytes"] == 0
        assert paged.stats["harvest_copy_bytes"] == 0
        # live pool blocks after drain == cache-resident blocks
        assert paged.pool.used_blocks() == len(paged_cache)
        assert all(node.pool_id is not None
                   for node in paged_cache._nodes.values())

    def test_eviction_releases_pool_blocks(self, params):
        _, paged, _, cache = pair(params, cache=True,
                                  prefill_chunk=16)
        run(paged, {"donor": (PROMPT, 10)})
        resident = paged.pool.used_blocks()
        assert resident == len(cache) > 0
        # evict everything (no pins remain after drain)
        cache.max_bytes = 1
        cache._evict_to_budget("default")
        assert len(cache) == 0
        assert paged.pool.used_blocks() == 0      # zero live blocks

    def test_shared_chain_across_two_slots(self, params):
        # two concurrent hits alias the SAME pool blocks; each slot
        # extends into its own fresh blocks and the chain survives
        # both retires (ISSUE 15 satellite: copy-on-extend correctness
        # when two slots share a block)
        _, paged, _, cache = pair(params, cache=True,
                                  prefill_chunk=16)
        run(paged, {"donor": (PROMPT, 10)})
        chain_ids = [node.pool_id for node in cache._nodes.values()]
        refs_before = [paged.pool.refs(i) for i in chain_ids]
        out = run(paged, {"s1": (PROMPT, 10), "s2": (PROMPT, 10)})
        assert out["s1"] == out["s2"] == oracle(params, PROMPT, 10)
        # after both retires every shared block is back to its cache
        # ref alone (or re-harvested children extended the chain)
        for block_id, before in zip(chain_ids, refs_before):
            assert paged.pool.refs(block_id) == before == 1

    def test_two_decoders_share_cache_and_pool(self, params):
        # the dense idiom of several decoders sharing one cache must
        # stay constructible in paged mode: the second decoder ADOPTS
        # the cache's pool, and a chain harvested by the first is a
        # zero-copy hit on the second
        _SEQ[0] += 1
        cache = PrefixKVCache(block_tokens=8, max_bytes=64 << 20,
                              name=f"share{_SEQ[0]}")
        common = dict(max_slots=4, prefill_buckets=(64,),
                      steps_per_sync=4, prefill_chunk=16)
        d1 = ContinuousDecoder(params, CONFIG, paged_kv=True,
                               kv_block=8, prefix_cache=cache,
                               **common)
        d2 = ContinuousDecoder(params, CONFIG, paged_kv=True,
                               kv_block=8, prefix_cache=cache,
                               **common)
        assert d1.pool is d2.pool is cache.pool
        run(d1, {"donor": (PROMPT, 10)})
        out = run(d2, {"probe": (PROMPT, 10)})
        assert out["probe"] == oracle(params, PROMPT, 10)
        assert d2.stats["prefix_admits"] == 1
        assert d2.stats["prefix_copy_bytes"] == 0
        assert d1.pool.used_blocks() == len(cache)

    def test_speculative_hit_seeds_context(self, params):
        dense, paged, *_ = pair(params, cache=True, speculate_k=2,
                                prefill_chunk=16)
        donor = {"donor": (PROMPT, 10)}
        probe = {"full": (PROMPT, 10)}
        assert run(dense, donor) == run(paged, donor)
        assert run(dense, probe) == run(paged, probe)
        assert paged.stats["prefix_admits"] == 1


# -- copy-on-extend ---------------------------------------------------------

class TestCopyOnExtend:
    def test_seq_cap_slide_back_copies_shared_block(self, params):
        """The PR 13 seq-cap regression shape: a 95-token prompt at
        max_seq 96 forces the final chunk to slide BACK into the
        cached region.  Dense rewrites in place (idempotent); paged
        must copy the shared block first so the cached chain keeps its
        rows — and a later hit must still be bit-identical."""
        long_prompt = [(i * 7) % 50 + 1 for i in range(95)]
        dense, paged, _, cache = pair(params, cache=True,
                                      prefill_chunk=16)
        cold = run(dense, {"cold": (long_prompt, 1)})["cold"]
        for probe in ("w1", "w2"):
            warm = run(paged, {probe: (long_prompt, 1)})[probe]
            assert warm == cold, probe
        # w1 harvested the chain; w2 hit it and slid back into it —
        # the shared block was copied, not mutated
        assert paged.stats["prefix_admits"] >= 1
        assert paged.pool.stats["cow_copies"] >= 1
        # a third hit still matches: the cache's rows were never
        # overwritten by w2's recompute
        assert run(paged, {"w3": (long_prompt, 1)})["w3"] == cold
        assert paged.pool.used_blocks() == len(cache)

    def test_slide_back_leaves_the_cached_blocks_bit_equal(self, params):
        """The chunk goes to the pool by whole blocks (PR 32): the final
        chunk of the 95-token prompt starts at 79, in the MIDDLE of a
        block of 8 that the cached chain shares, and that block is read
        whole and written back.  _copy_on_write makes every block of
        [offset, offset + chunk) the slot's own first, the partial
        first one too, so what the write-back touches is the copy: every
        block the cache held before the hit holds the same bits after."""
        long_prompt = [(i * 7) % 50 + 1 for i in range(95)]
        _, paged, _, cache = pair(params, cache=True, prefill_chunk=16)
        run(paged, {"w1": (long_prompt, 1)})
        pool = paged.pool
        held = np.flatnonzero(pool._refs > 0)
        assert len(held) == len(cache) > 0
        before = [np.asarray(leaf[held])
                  for leaf in pool.k_pools + pool.v_pools]
        run(paged, {"w2": (long_prompt, 1)})
        assert pool.stats["cow_copies"] >= 1
        for leaf, was in zip(pool.k_pools + pool.v_pools, before):
            np.testing.assert_array_equal(np.asarray(leaf[held]), was)

    def test_no_copies_on_ordinary_hits(self, params):
        _, paged, *_ = pair(params, cache=True, prefill_chunk=16)
        run(paged, {"donor": (PROMPT, 10)})
        run(paged, {"probe": (PROMPT, 10)})
        assert paged.pool.stats["cow_copies"] == 0


# -- pool accounting --------------------------------------------------------

class TestBlockPool:
    def test_alloc_release_and_growth(self, params):
        from aiko_services_tpu.serving_paged import BlockPool
        pool = BlockPool(CONFIG, 8, False, initial_blocks=4,
                         grow_blocks=4, name="t")
        ids = pool.alloc_blocks(6)           # forces one growth
        assert len(set(ids)) == 6 and 0 not in ids
        assert pool.stats["grows"] == 1
        assert pool.used_blocks() == 6
        pool.retain(ids[:2])
        pool.release_blocks(ids)
        assert pool.used_blocks() == 2       # retained pair survives
        assert pool._used == pool.used_blocks()  # gauge twin is exact
        pool.release_blocks(ids[:2])
        assert pool.used_blocks() == 0
        assert pool._used == 0
        with pytest.raises(ValueError):
            pool.release_blocks([ids[0]])    # double free is loud

    def test_idle_watermark_shrink_after_drain(self, params):
        # ISSUE 16 satellite: a burst grows the pool; after the tenant
        # drains, maybe_shrink returns the free tail so steady-state
        # HBM stays honest — but never below the construction floor,
        # never while occupied, and only past the geometric hysteresis
        from aiko_services_tpu.serving_paged import BlockPool
        pool = BlockPool(CONFIG, 8, False, initial_blocks=4,
                         grow_blocks=4, name="shrink")
        floor = pool.num_blocks
        ids = pool.alloc_blocks(40)          # burst: forces growth
        grown = pool.num_blocks
        assert grown > floor
        assert pool.maybe_shrink() == 0      # occupied: watermark says no
        assert pool.num_blocks == grown
        pool.release_blocks(ids)
        released = pool.maybe_shrink()       # drained: tail goes back
        assert released > 0
        assert pool.num_blocks == grown - released == floor
        assert pool.stats["shrinks"] == 1
        assert pool.used_blocks() == 0 and pool._used == 0
        assert pool.occupancy() == 0.0
        # the shrunk pool still serves: realloc regrows cleanly
        again = pool.alloc_blocks(6)
        assert len(set(again)) == 6 and 0 not in again
        pool.release_blocks(again)
        # hysteresis: a trivial free tail (< half the pool) is kept
        small = pool.alloc_blocks(2)
        pool.release_blocks(small)
        assert pool.maybe_shrink() == 0 or \
            pool.num_blocks >= floor         # never below the floor

    def test_shrink_respects_retained_tail(self, params):
        # a cache-retained block in the tail stops the scan: shrink
        # releases only the free run ABOVE the highest live block
        from aiko_services_tpu.serving_paged import BlockPool
        pool = BlockPool(CONFIG, 8, False, initial_blocks=4,
                         grow_blocks=4, name="shrink2")
        ids = pool.alloc_blocks(40)
        keep = max(ids)                      # pin the tail block
        pool.retain([keep])
        pool.release_blocks(ids)
        assert pool.maybe_shrink() == 0      # tail pinned: nothing moves
        assert pool.refs(keep) == 1
        pool.release_blocks([keep])
        assert pool.maybe_shrink() > 0
        assert pool.used_blocks() == 0

    def test_kv_cache_bytes_models_pool(self, params):
        _, paged = pair(params)
        assert paged.kv_cache_bytes() == \
            paged.pool.nbytes() + paged._tables_np.nbytes
        # same geometry, same initial coverage: pool models comparable
        # bytes to the dense allocation (within one block of padding)
        assert paged.pool.nbytes() > 0

    def test_int8_pool_layout(self, params):
        _, paged = pair(params, kv_cache_dtype="int8")
        leaf = paged.pool.k_pools[0]
        assert set(leaf) == {"q", "s"}
        assert leaf["q"].dtype == jnp.int8
        assert leaf["s"].shape == leaf["q"].shape[:3]


# -- direct slot-table install (cacheless disagg landing) -------------------

class TestDirectInstall:
    def _blocks_for(self, donor_cache, tokens):
        """Ship-shaped host blocks for `tokens` harvested from a
        throwaway dense donor cache."""
        keys, hit = donor_cache.match("", tokens)
        nodes = donor_cache.nodes(keys)
        out = []
        for node in nodes:
            k_rows, v_rows = donor_cache.block_rows(node)
            out.append({"k": [np.asarray(r) for r in k_rows],
                        "v": [np.asarray(r) for r in v_rows]})
        return out, hit

    def test_install_and_alias_parity(self, params):
        donor_cache = PrefixKVCache(block_tokens=8,
                                    max_bytes=64 << 20, name="dd1")
        donor = ContinuousDecoder(params, CONFIG,
                                  prefix_cache=donor_cache,
                                  max_slots=4, prefill_buckets=(64,),
                                  steps_per_sync=4)
        run(donor, {"donor": (PROMPT, 1)})
        cacheless = ContinuousDecoder(params, CONFIG, paged_kv=True,
                                      kv_block=8, max_slots=4,
                                      prefill_buckets=(64,),
                                      steps_per_sync=4,
                                      prefill_chunk=16)
        blocks, hit = self._blocks_for(donor_cache, PROMPT)
        covered, ids = cacheless.install_shipped_blocks(PROMPT, 0,
                                                        blocks)
        assert covered == hit == len(ids) * 8
        done = {}
        assert cacheless.submit("direct", PROMPT, 10,
                                lambda r, t: done.update({r: t}),
                                kv_blocks=(covered, ids))
        for _ in range(400):
            cacheless.pump()
            if "direct" in done:
                break
        assert done["direct"] == oracle(params, PROMPT, 10)
        # the install skipped the covered prefill work entirely
        assert cacheless.stats["prefix_admits"] == 1
        assert cacheless.stats["prefix_copy_bytes"] == 0
        assert cacheless.pool.used_blocks() == 0   # drain audit

    def test_refused_submit_leaves_ids_with_caller(self, params):
        cacheless = ContinuousDecoder(params, CONFIG, paged_kv=True,
                                      kv_block=8, max_slots=4,
                                      prefill_buckets=(64,),
                                      steps_per_sync=4,
                                      prefill_chunk=16)
        # prime the round EWMA so deadline admission is live
        run(cacheless, {"warm": (PROMPT[:9], 2)})
        ids = cacheless.pool.alloc_blocks(3)
        import time
        accepted = cacheless.submit(
            "late", PROMPT, 4, lambda r, t: None,
            deadline=time.monotonic() - 1.0,
            kv_blocks=(24, ids))
        assert not accepted
        # ownership never transferred: the caller's release drains
        cacheless.pool.release_blocks(ids)
        assert cacheless.pool.used_blocks() == 0

    def test_truncated_prompt_drops_install_to_cold(self, params):
        # a prompt over the admit cap tail-truncates inside submit, so
        # pre-installed ids would alias KV for the tokens that were
        # just cut off — the install must drop to a cold prefill (and
        # release the ids), never silently emit wrong tokens
        cacheless = ContinuousDecoder(params, CONFIG, paged_kv=True,
                                      kv_block=8, max_slots=4,
                                      prefill_buckets=(32,),
                                      steps_per_sync=4)
        long_prompt = [(i * 7) % 50 + 1 for i in range(40)]  # cap 32
        ids = cacheless.pool.alloc_blocks(4)     # zero-filled garbage
        done = {}
        assert cacheless.submit("over", long_prompt, 6,
                                lambda r, t: done.update({r: t}),
                                kv_blocks=(32, ids))
        for _ in range(400):
            cacheless.pump()
            if "over" in done:
                break
        assert cacheless.stats["install_misaligned"] == 1
        assert done["over"] == oracle(params, long_prompt[-32:], 6)
        assert cacheless.pool.used_blocks() == 0  # ids were released

    def test_dense_then_paged_share_refused(self, params):
        # the order-independent twin of the dense-decoder-refuses-
        # paged-cache check: a dense decoder binding FIRST poisons the
        # cache for any later paged attach (its insert()ed nodes have
        # no pool id), so construction must refuse loudly
        _SEQ[0] += 1
        cache = PrefixKVCache(block_tokens=8, max_bytes=64 << 20,
                              name=f"mix{_SEQ[0]}")
        ContinuousDecoder(params, CONFIG, prefix_cache=cache,
                          max_slots=4, prefill_buckets=(64,),
                          steps_per_sync=4)
        with pytest.raises(ValueError, match="dense"):
            ContinuousDecoder(params, CONFIG, paged_kv=True,
                              kv_block=8, prefix_cache=cache,
                              max_slots=4, prefill_buckets=(64,),
                              steps_per_sync=4)

    def test_geometry_mismatch_refused_before_landing(self, params):
        cacheless = ContinuousDecoder(params, CONFIG, paged_kv=True,
                                      kv_block=8, max_slots=4,
                                      prefill_buckets=(64,),
                                      steps_per_sync=4)
        bad = [{"k": [np.zeros((2, 8, 16), np.float32)],   # 1 layer
                "v": [np.zeros((2, 8, 16), np.float32)]}]
        with pytest.raises(ValueError):
            cacheless.install_shipped_blocks(PROMPT, 0, bad)
        assert cacheless.pool.used_blocks() == 0
