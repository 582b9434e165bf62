# What the tests of a paged model share (ISSUE 43), once: a model's tiny
# suite (`PagedModelCases`: configuration, seeded weights, the reference's
# logits, a decoder of the suite's geometry, serving through it, each
# served token's gap to the reference) and a benchmark configuration at its
# cell's sizes as shapes on a described chip (`DescribedCell`).  pytest
# does not collect this module; README.md "Test-suite wall-time budget" says
# how a new model's tests use it.
#
# A program compiles once a process for one GEOMETRY (slots, max_seq, block,
# buckets, chunk, steps a round).  So `decoder_for` has ONE default
# geometry, a case departs from it only where what it checks needs another,
# and `serve` reads a decoder's counters as differences, so cases that
# differ in their requests alone serve through one decoder.

import contextlib
import functools
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "benchmark", "drivers")):
    if path not in sys.path:
        sys.path.insert(0, path)

from aiko_services_tpu import serving_paged  # noqa: E402
from aiko_services_tpu.serving import ContinuousDecoder  # noqa: E402


# what every suite's decoders hold of a slot, and so the longest sequence
# the reference is asked for
MAX_SEQ = 128


class PagedModelCases:
    """One model at a size a test holds: `driver` names the benchmark
    driver whose `model_config` reads `sizes` (the published file's keys),
    `weights` is its benchmark/weights_* module, `reference_forward(tokens
    [T], sizes, seed) -> logits [T, vocab]` its plain reference."""

    def __init__(self, driver, weights, reference_forward, sizes, seed):
        self.driver, self.weights = driver, weights
        self.reference_forward = reference_forward
        self.sizes, self.seed = sizes, seed

    def model_config(self, sizes=None, dtype=jnp.float32, max_seq=MAX_SEQ):
        return importlib.import_module(self.driver).model_config(
            self.sizes if sizes is None else sizes, max_seq, dtype)

    @functools.cached_property
    def params(self):
        return self.weights.decoder_weights(
            self.weights.key_for(self.seed), self.sizes, jnp.float32)

    def has_the_layout_of(self, init):
        """The seeded weights are, leaf for leaf, what the program's own
        `init(key, config)` makes."""
        ours = jax.eval_shape(
            lambda: init(jax.random.PRNGKey(0), self.model_config()))
        assert jax.tree.structure(ours) == jax.tree.structure(self.params)
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(ours),
                jax.tree_util.tree_leaves_with_path(self.params)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype), \
                jax.tree_util.keystr(path)

    def reference_logits(self, tokens, sizes=None):
        """The reference's logits at every position of `tokens`, from ONE
        forward over them padded to `MAX_SEQ`: the reference is
        causal (what follows a position moves nothing at it), and it runs
        operation by operation, so a sequence of another length is some
        hundred small programs compiled anew, ten seconds where the
        forward itself takes one."""
        tokens = np.asarray(tokens)
        pad = np.ones(max(0, MAX_SEQ - len(tokens)), tokens.dtype)
        return np.asarray(self.reference_forward(
            np.concatenate([tokens, pad]),
            self.sizes if sizes is None else sizes, self.seed))[:len(tokens)]

    def forward_gap(self, forward, tokens, dtype=jnp.float32):
        """How far the program's own full forward (`forward(params, config,
        tokens [1, T])`, as ONE program) with matrices and activations in
        `dtype` lies from the reference's logits: -> (the widest gap, the
        spread of the reference's logits)."""
        params = jax.tree.map(
            lambda leaf: leaf.astype(dtype) if leaf.ndim > 1 else leaf,
            self.params)
        ours = jax.jit(forward, static_argnums=1)(
            params, self.model_config(dtype=dtype),
            jnp.asarray(tokens)[None])[0]
        theirs = self.reference_logits(tokens)
        return float(np.abs(np.asarray(ours) - theirs).max()), \
            float(theirs.std())

    def decoder_for(self, name, buckets=(8, 32), chunk=32, slots=4,
                    **kwargs):
        return ContinuousDecoder(self.params, self.model_config(), **({
            "paged_kv": True, "kv_block": 8, "max_slots": slots,
            "max_seq": MAX_SEQ, "prefill_buckets": buckets,
            "prefill_chunk": chunk, "prefill_budget": chunk,
            "steps_per_sync": 4, "name": name} | kwargs))

    @staticmethod
    def serve(decoder, requests, rounds=400):
        """Submit {rid: (prompt, new tokens)} and pump until all are
        served: -> ({rid: tokens}, what the decoder's counters grew by)."""
        before, served = dict(decoder.stats), {}
        for rid, (prompt, new) in requests.items():
            assert decoder.submit(rid, prompt, new, lambda rid, tokens:
                                  served.__setitem__(rid, list(tokens)))
        for _ in range(rounds):
            if len(served) == len(requests):
                break
            decoder.pump()
        assert len(served) == len(requests)
        return served, {
            key: value - before.get(key, 0)
            for key, value in decoder.stats.items()
            if isinstance(value, (int, float))}

    def served_gaps(self, requests, served):
        """Per request, how far each served token's logit lies below the
        reference's best at its position (one full teacher-forced
        forward), in standard deviations of that position's logits."""
        out = {}
        for rid, (prompt, _) in requests.items():
            tokens = served[rid]
            logits = self.reference_logits(prompt + tokens[:-1])
            at = logits[len(prompt) - 1:]
            out[rid] = float(
                ((at.max(-1) - at[np.arange(len(tokens)), tokens])
                 / at.std(-1)).max())
        return out

    def altered_token_gap(self, decoder):
        """`served_gaps` of a request one of whose served tokens was
        altered: the comparison sees a wrong token."""
        rng = np.random.default_rng(8)
        requests = {"a": (rng.integers(1, 256, size=12).tolist(), 6)}
        served, _ = self.serve(decoder, requests)
        served["a"][2] = (served["a"][2] + 1) % 256
        return self.served_gaps(requests, served)["a"]

    # -- the serving paths a pool that is not K and V alone is not carried
    # through refuse, by name ---------------------------------------------

    def refuses_to_build(self, kwargs, named, params=None):
        kwargs = dict(paged_kv=True, kv_block=8, max_slots=2, max_seq=64,
                      prefill_chunk=32) | kwargs
        if kwargs.get("prefix_cache"):
            from aiko_services_tpu.serving import PrefixKVCache
            kwargs["prefix_cache"] = PrefixKVCache(block_tokens=8)
        with pytest.raises(ValueError, match=named):
            ContinuousDecoder(params or self.params,
                              self.model_config(max_seq=64), **kwargs)

    def refuses_tensor_parallel_weights(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
        sharded = dict(self.params)
        sharded["lm_head"] = {"w": jax.device_put(
            self.params["lm_head"]["w"],
            NamedSharding(mesh, P(None, "model")))}
        self.refuses_to_build({}, "tensor-parallel", sharded)

    @staticmethod
    def refuses(decoder, path):
        """Drain, the KV wire's layout, an install of shipped blocks, a
        disaggregated client: each refuses on entry, so any decoder of
        the model will do."""
        with pytest.raises(ValueError, match="not carried"):
            if path == "drain":
                decoder.drain()
            elif path == "wire-layout":
                decoder.kv_wire_layout()
            elif path == "install":
                decoder.install_shipped_blocks([1] * 16, 0, [{}])
            else:
                from aiko_services_tpu.serving_disagg import PrefillClient
                PrefillClient(None, decoder)


@contextlib.contextmanager
def scan_kernel_interpreted(model, scan=True, name="delta_chunk_scan"):
    """`scan`: a prompt's pieces take the chunk kernel that `model` holds
    under `name` (ops/delta_chunk's in models/hybrid_sparse and
    models/gated_delta, ops/ssm_chunk's `ssm_chunk_scan` in
    models/ssm_hybrid), in the interpreter.  The
    model takes it unasked on a chip alone, and the choice is made where
    the admit and the extend are TRACED: so the builders' caches, which
    know nothing of it, are emptied around, and the decoder that serves
    must not have traced its admits yet.  A case that uses this comes LAST
    in its file: what it evicts nobody compiles again."""
    if not scan:
        yield
        return
    builders = (serving_paged._paged_admit_fn_for,
                serving_paged._paged_extend_fn_for)
    for builder in builders:
        builder.cache_clear()
    traced, kernel = [], getattr(model, name)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(model, "_scan_kernel", lambda config, interpret: True)
        patch.setattr(model, name,
                      lambda *args: traced.append(1) or kernel(*args))
        yield
    for builder in builders:
        builder.cache_clear()
    assert traced, "no admit or extend was traced through the chunk kernel"


# what every model whose pool is not K and V alone refuses at construction:
# a case's id -> (what is asked for, what the refusal names)
NOT_CARRIED = {
    "dense": (dict(paged_kv=False), "dense slot cache"),
    "int8-kv": (dict(kv_cache_dtype="int8"), "int8 KV cache"),
    "speculation": (dict(speculate_k=2), "speculative decoding"),
    "prefix-cache": (dict(prefix_cache=True), "prefix cache"),
    "weight-quant": (dict(weight_quant=True), "weight-only int8")}


def share_layer(layer, first, held):
    """What a chip holding experts [first, first + held) keeps of a whole
    layer: everything, and those experts' rows."""
    return layer | {"experts": jax.tree.map(
        lambda w: w[first:first + held], layer["experts"])}


def shaped(chip, shape, kind):
    return jax.ShapeDtypeStruct(tuple(shape), kind, sharding=chip)


class DescribedCell:
    """benchmark/configs/`file` as its cell serves it, as shapes on `chip`
    (conftest's described v5e): `config` from the file's keys through
    `model_config(sizes, max_seq, dtype)`, `serve` the file's serving
    block, `params` from `init(key, config)`, a full pool laid out as
    BlockPool lays it out from `serving_paged.layer_leaves`, the slot state
    the configuration declares (or none), and a table of `table` blocks: a
    slot's whole length and the round's headroom."""

    def __init__(self, chip, file, init, model_config):
        with open(os.path.join(ROOT, "benchmark", "configs", file)) as f:
            sizes = json.load(f)
        self.chip, self.serve = chip, sizes["serving"]
        self.config = config = model_config(sizes, self.serve["max_seq"],
                                            jnp.bfloat16)
        self.params = jax.tree.map(
            lambda leaf: self.shaped(leaf.shape, leaf.dtype),
            jax.eval_shape(lambda: init(jax.random.PRNGKey(0), config)))
        self.slots, block = self.serve["max_slots"], self.serve["kv_block"]
        self.blocks = self.slots * self.serve["max_seq"] // block + 1
        leaves = serving_paged.layer_leaves(config)
        self.leaf_shapes = [
            [(self.blocks, layer[side][0], block // layer[side][2],
              layer[side][1]) if len(layer) > side else None
             for layer in leaves]
            for side in range(max(len(layer) for layer in leaves))]
        self.k_pools, self.v_pools = serving_paged._join_sides([
            [shape and self.shaped(shape, jnp.bfloat16) for shape in side]
            for side in self.leaf_shapes])
        # the slot state is the programs' LAST argument, where a model
        # declares any
        self.state = [[tuple(self.shaped((self.slots,) + tuple(shape), kind)
                             for shape, kind in layer)
                       for layer in config.slot_state]] \
            if getattr(config, "slot_state", ()) else []
        self.table = -(-(self.serve["max_seq"] +
                         self.serve["steps_per_sync"]) // block)

    def shaped(self, shape, kind=jnp.int32):
        return shaped(self.chip, shape, kind)

    def lower_step(self, kernel, t_cap=None):
        """`jit_step` x `steps_per_sync` over every slot."""
        vector = self.shaped((self.slots,))
        return serving_paged._paged_step_for(self.config, kernel).lower(
            self.params, vector, vector, self.shaped((self.slots,), bool),
            vector, self.k_pools, self.v_pools,
            self.shaped((self.slots, self.table)), *self.state,
            num_steps=self.serve["steps_per_sync"], eos=-1,
            t_cap=t_cap or self.serve["max_seq"])

    def _prompts(self, tokens, width, context):
        vector = self.shaped((self.slots,))
        row = self.shaped((width,))
        return (self.params, self.k_pools, self.v_pools, vector, vector,
                self.shaped(context), self.shaped((width, tokens)), row,
                row, self.shaped((width,), bool))

    def lower_admit(self, tokens, width):
        """`jit_admit` of `width` prompts padded to a bucket of `tokens`."""
        block = self.serve["kv_block"]
        return serving_paged._paged_admit_fn_for(
            self.config, tokens, width, False, False).lower(
            *self._prompts(tokens, width, (1, 1)),
            self.shaped((width, -(-tokens // block))), *self.state)

    def lower_extend(self, tokens, width, context=(1, 1), table=None):
        """`jit_extend` of `width` chunks of `tokens` (the gather path)."""
        table = table or self.serve["max_seq"] // self.serve["kv_block"]
        return serving_paged._paged_extend_fn_for(
            self.config, tokens, width, False, False, False).lower(
            *self._prompts(tokens, width, context),
            self.shaped((width,), bool), self.shaped((width,)),
            self.shaped((width, table)), *self.state,
            t_cap=self.serve["max_seq"])


def no_copy_of(compiled, *leaves, temporaries=None):
    """No `copy` in the optimized HLO has a result of a pool leaf's shape
    (in whatever layout); the temporaries under a bound: -> the text."""
    text = compiled.as_text()
    for leaf in leaves:
        result = re.escape("[" + ",".join(map(str, leaf)) + "]")
        assert re.findall(rf"= \w+{result}\S* copy\(.*", text) == []
    if temporaries is not None:
        assert compiled.memory_analysis().temp_size_in_bytes < temporaries
    return text


def block_windows(text, leaf_shape, dtype_name):
    """(how many, of what shape) the whole-block gather reads of the pool
    leaf and the scatter writes back to it, from the optimized HLO."""
    # (the compiler drops a unit axis: a latent leaf's one head)
    leaf_shape = [n for n in leaf_shape if n != 1]
    leaf = re.escape("[" + ",".join(map(str, leaf_shape)) + "]")
    window = ",".join(map(str, leaf_shape[1:]))
    reads = re.findall(
        rf"= {dtype_name}\[([\d,]+),{window}\]\S* gather\(.*"
        rf"slice_sizes=\{{1,{window}\}}", text)
    writes = re.findall(
        rf"= {dtype_name}{leaf}\S* scatter\(.*inserted_window_dims=\{{0\}}, "
        rf"scatter_dims_to_operand_dims=\{{0\}}", text)
    return reads, writes


# what hands an array on in optimized HLO without making it
HLO_CARRIES = {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
               "call", "conditional"}


def made_whole(text, leaf):
    """The lines of optimized HLO whose operation MAKES an array of the
    type and shape `leaf` (as "f32[32,64,128,128]"), beside a tuple or
    alone, and each one's kind: what a state or pool leaf must not be
    computed into anew."""
    made = [line.strip() for line in text.splitlines()
            if re.search(r"= \(?[^=]*%s\S* (\S+)\(" % re.escape(leaf), line)]
    kinds = [re.search(r"\S* ([a-z\-]+)\(", line.split(" = ", 1)[1]).group(1)
             for line in made]
    return made, kinds
