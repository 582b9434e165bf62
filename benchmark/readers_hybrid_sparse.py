"""What the hybrid decoder's metric files share: the counters its driver
adds and the work of its kernels from them.  A run whose driver keeps no
such counter (another program) reads as None.
"""

from __future__ import annotations

from benchmark import ops_bytes_hybrid_sparse as ops
from benchmark import readers
from benchmark.readers_latent_moe import (per_step,  # noqa: F401
                                          roofline_share, sizes_of)
from benchmark.trace import regions


def step_region_ms(run: dict, scope: str) -> float | None:
    """Device ms a decode step of THIS program spends under `scope`; None
    for a program whose driver keeps no sparse-attention counter (another
    configuration's, or the parent's under this benchmark)."""
    if "dsa_positions_live" not in run["counters"]["after"]:
        return None
    return regions.step_region_ms(run, scope)


def sparse_core_work(run: dict) -> dict | None:
    attended = per_step(run, "dsa_positions_attended")
    live = per_step(run, "dsa_positions_live")
    if attended is None or live is None:
        return None
    return ops.sparse_core(*sizes_of(run), attended, live)


def sparse_core_ms(run: dict) -> float | None:
    """Device ms a decode step spends choosing and attending: the regions
    `aiko.dsa_index` and `aiko.attn_core` together, and the copy of the
    leaf that the gather waits for (`aiko.dsa_relayout`, which lies inside
    `aiko.attn_core` and is read apart)."""
    parts = [step_region_ms(run, scope)
             for scope in ("aiko.dsa_index", "aiko.attn_core",
                           "aiko.dsa_relayout")]
    return None if None in parts else sum(parts)


def step_work(run: dict) -> dict | None:
    numbers = [per_step(run, key) for key in (
        "useful_steps", "dsa_positions_attended", "dsa_positions_live",
        "moe_experts_hit", "moe_pairs_here")]
    if None in numbers:
        return None
    return ops.decode_step(*sizes_of(run), *numbers)


def extend_region_ms(run: dict, scope: str) -> float | None:
    """Device ms a prefill chunk spends under `scope`: the region's time
    inside `jit_extend` over the traced span, over the chunks the decoder
    dispatched in it.  None where no operation carries the scope."""
    trace, _ = regions.of_run(run)
    chunks = readers.delta(run, "prefill_chunks", "trace_counters")
    if trace is None or not chunks:
        return None
    found = regions.region_seconds(trace, ["jit_extend"])
    if not found or scope not in found["seconds"]:
        return None
    return 1e3 * found["seconds"][scope] / chunks
