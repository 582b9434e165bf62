#!/usr/bin/env python3
"""Compile a decoder configuration's programs at their real size for a
DESCRIBED v5e, here on the CPU, and print what each needs of the chip's
memory: a later PR that adds a decoder cell can size its pool without
chip time (guide on-chip-measurement, section 2, rehearsal 3).

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py <config name> [step|admit|extend ...]

Nothing runs and nothing is timed.  A compile that passes is not a chip
run; one that is refused costs no chip time.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(argv: list) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from aiko_services_tpu import serving_paged
    from aiko_services_tpu.models.llama import LlamaConfig
    from benchmark import weights as W

    with open(os.path.join(ROOT, "benchmark", "configs",
                           argv[0] + ".json")) as f:
        sizes = json.load(f)
    serve = sizes["serving"]
    wanted = argv[1:] or ["step", "admit", "extend"]
    dtype = jnp.dtype(sizes["dtype"])
    config = LlamaConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        ffn_dim=sizes["intermediate_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        max_seq_len=serve["max_seq"], rope_theta=sizes["rope_theta"],
        dtype=dtype)
    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def shaped(shape, kind):
        return jax.ShapeDtypeStruct(shape, kind, sharding=chip)

    params = jax.tree.map(
        lambda leaf: shaped(leaf.shape, leaf.dtype),
        jax.eval_shape(lambda: W.decoder_weights(W.key_for(0), sizes, dtype)))
    slots, block = serve["max_slots"], serve["kv_block"]
    t_cap = min(serve["t_block"], serve["max_seq"])
    table_width = -(-t_cap // block)
    pool = [shaped((slots * table_width + 1, config.num_kv_heads, block,
                    config.head_dim), dtype)
            for _ in range(config.num_layers)]
    per_slot = [shaped((slots,), jnp.int32), shaped((slots,), jnp.int32)]
    context = shaped((1, 1), jnp.int32)
    held = (sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
            + 2 * sum(l.size * l.dtype.itemsize for l in pool))
    print(f"weights + pool: {held / 1e9:.2f} GB "
          f"({slots} slots x {t_cap} positions)")

    def report(label, lowered):
        memory = lowered.compile().memory_analysis()
        total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                 + memory.output_size_in_bytes - memory.alias_size_in_bytes)
        print(f"{label}: arguments {memory.argument_size_in_bytes / 1e9:.2f} "
              f"GB, temporaries {memory.temp_size_in_bytes / 1e9:.2f} GB, "
              f"outputs {memory.output_size_in_bytes / 1e9:.2f} GB, aliased "
              f"{memory.alias_size_in_bytes / 1e9:.2f} GB -> "
              f"{total / 1e9:.2f} GB live, code "
              f"{memory.generated_code_size_in_bytes / 1e6:.1f} MB")

    if "step" in wanted:
        step = serving_paged._paged_step_for(config, False)
        for steps in (serve["steps_per_sync"],):
            report(f"step x{steps}", step.lower(
                params, *per_slot, shaped((slots,), bool),
                shaped((slots,), jnp.int32), pool, pool,
                shaped((slots, table_width), jnp.int32),
                num_steps=steps, eos=-1, t_cap=t_cap))
    if "admit" in wanted:
        bucket, width = serve["prefill_buckets"][-1], 1
        admit = serving_paged._paged_admit_fn_for(config, bucket, width,
                                                  False, False)
        report(f"admit {bucket} x{width}", admit.lower(
            params, pool, pool, *per_slot, context,
            shaped((width, bucket), jnp.int32), shaped((width,), jnp.int32),
            shaped((width,), jnp.int32), shaped((width,), bool),
            shaped((width, -(-bucket // block)), jnp.int32)))
    if "extend" in wanted and serve.get("prefill_chunk"):
        chunk, width = serve["prefill_chunk"], 1
        extend = serving_paged._paged_extend_fn_for(config, chunk, width,
                                                    False, False, False)
        vector = shaped((width,), jnp.int32)
        report(f"extend {chunk} x{width}", extend.lower(
            params, pool, pool, *per_slot, context,
            shaped((width, chunk), jnp.int32), vector, vector,
            shaped((width,), bool), shaped((width,), bool), vector,
            shaped((width, table_width), jnp.int32), t_cap=t_cap))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
