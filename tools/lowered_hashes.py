#!/usr/bin/env python3
# a CLI whose output IS its result, like the converters beside it
# graft: disable-file=lint-print
"""Whether a change left a configuration's serving programs alone: lower the
decode step, an admit and an extend of each of the benchmark's decoder
configurations at the cell's sizes for a DESCRIBED v5e, here on the CPU, and
print a hash of each program's StableHLO (asked for since PR 31; CHANGES.md,
PR 38 and PR 39, did it from scratch scripts).

    JAX_PLATFORMS=cpu python3 tools/lowered_hashes.py [config ...] [--compile]

Run it on two trees and compare the lines.  The step is lowered as a decoder
on the chip builds it (`jax.default_backend` says "tpu" around the lowering:
the Pallas kernels, not the interpreter).  A kernel's Mosaic module rides in
the text as serialized bytecode that carries the file and line of every
Python frame, so ANY edit above a call site would change it: each `body` is
replaced by the hash of its assembly without debug info before the text is
hashed.  What is left differs only where the computation does.

--compile also compiles each program for the described chip and prints what
it needs of the chip's memory (a configuration that does not fit is refused
here and costs no chip time).  Nothing runs and nothing is timed.
"""

from __future__ import annotations

import base64
import hashlib
import importlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "benchmark", "drivers")):
    if path not in sys.path:
        sys.path.insert(0, path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _model_of(sizes: dict, dtype):
    """(the program's configuration, the benchmark's weights module) of a
    configuration file, as its driver makes them."""
    driver = importlib.import_module(sizes["driver"])
    max_seq = sizes["serving"]["max_seq"]
    if hasattr(driver, "model_config"):
        return driver.model_config(sizes, max_seq, dtype), driver.W
    from aiko_services_tpu.models.llama import LlamaConfig
    return LlamaConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        ffn_dim=sizes["intermediate_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"], max_seq_len=max_seq,
        rope_theta=sizes["rope_theta"], dtype=dtype), driver.W


def _stripped(text: str) -> str:
    """`text` with every Mosaic body replaced by the hash of its assembly
    without source locations."""
    from jax._src.lib.mlir import ir

    def assembly(found) -> str:
        context = ir.Context()
        context.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(found.group(1)), context)
        asm = module.operation.get_asm(enable_debug_info=False)
        return '\\22body\\22: \\22%s\\22' % hashlib.sha256(
            asm.encode()).hexdigest()

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', assembly, text)


def programs(sizes: dict, chip):
    """(name, lowered) of the step, an admit and an extend at the cell's
    sizes, arguments as shapes on `chip`."""
    from aiko_services_tpu import serving_paged
    serve = sizes["serving"]
    dtype = jnp.dtype(sizes["dtype"])
    config, weights = _model_of(sizes, dtype)
    model = config.paged_model()

    def shaped(shape, kind):
        return jax.ShapeDtypeStruct(tuple(shape), kind, sharding=chip)

    params = jax.tree.map(
        lambda leaf: shaped(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: weights.decoder_weights(
            weights.key_for(0), sizes, dtype)))
    slots, block = serve["max_slots"], serve["kv_block"]
    max_seq = serve["max_seq"]
    blocks = slots * -(-min(serve["t_block"], max_seq) // block) + 1
    leaves = serving_paged.layer_leaves(config)

    def side(n):
        return [shaped((blocks, layer[n][0], block // layer[n][2],
                        layer[n][1]), dtype) if len(layer) > n else None
                for layer in leaves]

    k_pools = side(0)
    v_pools = [pool for n in range(1, max(map(len, leaves)))
               for pool in side(n)]
    state = ([tuple(shaped((slots,) + tuple(shape), kind)
                    for shape, kind in layer)
              for layer in config.slot_state],) \
        if getattr(config, "slot_state", ()) else ()
    vector = shaped((slots,), jnp.int32)
    context = shaped((1, 1), jnp.int32)
    table = -(-(max_seq + serve["steps_per_sync"]) // block)
    # the decoder's own rule on a TPU (ContinuousDecoder's constructor)
    kernel = model.walks(config, False, False) == "kernel" or bool(
        model.step_kernel is not None and model.step_kernel(config, False))
    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield "step", serving_paged._paged_step_for(config, kernel).lower(
            params, vector, vector, shaped((slots,), bool), vector, k_pools,
            v_pools, shaped((slots, table), jnp.int32), *state,
            num_steps=serve["steps_per_sync"], eos=-1, t_cap=max_seq)
        bucket = serve["prefill_buckets"][-1]
        one, flag = shaped((1,), jnp.int32), shaped((1,), bool)
        yield "admit", serving_paged._paged_admit_fn_for(
            config, bucket, 1, False, False).lower(
            params, k_pools, v_pools, vector, vector, context,
            shaped((1, bucket), jnp.int32), one, one, flag,
            shaped((1, -(-bucket // block)), jnp.int32), *state)
        chunk = serve.get("prefill_chunk")
        if chunk:
            yield "extend", serving_paged._paged_extend_fn_for(
                config, chunk, 1, False, False, False).lower(
                params, k_pools, v_pools, vector, vector, context,
                shaped((1, chunk), jnp.int32), one, one, flag, flag, one,
                shaped((1, table), jnp.int32), *state, t_cap=max_seq)
    finally:
        jax.default_backend = backend


def main(argv: list) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    compile_too = "--compile" in argv
    names = [name for name in argv if not name.startswith("--")]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        files = {entry["name"]: entry["file"]
                 for entry in json.load(f)["configs"]}
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    for name in names or sorted(files):
        with open(os.path.join(ROOT, files[name])) as f:
            sizes = json.load(f)
        for program, lowered in programs(sizes, chip):
            digest = hashlib.sha256(
                _stripped(lowered.as_text()).encode()).hexdigest()[:16]
            line = f"{name} {program} {digest}"
            if compile_too:
                memory = lowered.compile().memory_analysis()
                live = (memory.argument_size_in_bytes
                        + memory.temp_size_in_bytes
                        + memory.output_size_in_bytes
                        - memory.alias_size_in_bytes)
                line += (f" arguments {memory.argument_size_in_bytes / 1e9:.2f}"
                         f" GB temporaries {memory.temp_size_in_bytes / 1e9:.2f}"
                         f" GB live {live / 1e9:.2f} GB")
            print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
