"""What the sparse grouped-query decoder's metric files share: the
counters its driver adds and the work of its kernels from them (run.py
reads a metric in the cells its `workloads` names; a counter that a run's
driver does not keep reads as None).
"""

from __future__ import annotations

from benchmark import ops_bytes_sparse_gqa as ops
from benchmark.readers_latent_moe import (per_step,  # noqa: F401
                                          roofline_share, sizes_of)
from benchmark.trace import regions


def index_select_work(run: dict) -> dict | None:
    live = per_step(run, "dsa_positions_live")
    if live is None:
        return None
    return ops.index_select(*sizes_of(run), live)


def index_select_ms(run: dict) -> float | None:
    parts = [regions.step_region_ms(run, scope)
             for scope in ("aiko.dsa_index", "aiko.dsa_select")]
    return None if None in parts else sum(parts)


def sparse_attention_work(run: dict) -> dict | None:
    attended = per_step(run, "dsa_positions_attended")
    if attended is None:
        return None
    return ops.sparse_attention(*sizes_of(run), attended)


def step_work(run: dict) -> dict | None:
    numbers = [per_step(run, key) for key in (
        "useful_steps", "dsa_positions_attended", "dsa_positions_live",
        "moe_experts_hit", "moe_pairs_here")]
    if None in numbers:
        return None
    return ops.decode_step(*sizes_of(run), *numbers)
