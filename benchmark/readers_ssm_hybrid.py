"""What the Mamba-2 hybrid decoder's metric files share: the counters its
driver adds and the work of its kernels from them (run.py reads a metric in
the cells its `workloads` names; a run whose driver keeps no such counter,
another program's or the parent's under this benchmark, reads as None).
"""

from __future__ import annotations

from benchmark import ops_bytes_ssm_hybrid as ops
from benchmark.readers_latent_moe import per_step, roofline_share  # noqa: F401
from benchmark.trace import regions


def ours(run: dict) -> bool:
    return "ssm_states_moved" in run["counters"]["after"]


def step_region_ms(run: dict, scope: str) -> float | None:
    """Device ms a decode step of THIS program spends under `scope`."""
    return regions.step_region_ms(run, scope) if ours(run) else None


def state_work(run: dict) -> dict | None:
    moved = per_step(run, "ssm_states_moved")
    return None if moved is None else ops.state_step(run["config"], moved)
