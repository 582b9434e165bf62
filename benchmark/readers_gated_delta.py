"""What the Gated-DeltaNet hybrid decoder's metric files share: the counters
its driver adds and the work of its kernels from them (run.py reads a metric
in the cells its `workloads` names; a run whose driver keeps no such counter,
another program's or the parent's under this benchmark, reads as None).
"""

from __future__ import annotations

from benchmark import ops_bytes_gated_delta as ops
from benchmark import readers
from benchmark.readers_latent_moe import (held_tokens, per_step,  # noqa: F401
                                          roofline_share, sizes_of)
from benchmark.trace import regions

PREFILL = ["jit_admit", "jit_extend"]


def step_region_ms(run: dict, scope: str) -> float | None:
    """Device ms a decode step of THIS program spends under `scope`."""
    if "gdn_states_moved" not in run["counters"]["after"]:
        return None
    return regions.step_region_ms(run, scope)


def traced_share(run: dict, part: str, whole: str, scale: float = 1.0):
    """100 * part / (whole * scale) over the TRACED span, the window's last
    seconds, from the driver's counters.  The counters around the window
    are snapshot before the pre-roll, whose ramp (64 rounds with 1 to 64
    slots live) would read as 7-11% of the slots idle."""
    value = readers.ratio(readers.delta(run, part, "trace_counters"),
                          (readers.delta(run, whole, "trace_counters") or 0)
                          * scale)
    return None if value is None else 100.0 * value


def state_work(run: dict) -> dict | None:
    moved = per_step(run, "gdn_states_moved")
    return None if moved is None else ops.state_step(run["config"], moved)


def step_work(run: dict) -> dict | None:
    numbers = [per_step(run, "useful_steps"), held_tokens(run),
               per_step(run, "gdn_states_moved")]
    if None in numbers:
        return None
    return ops.decode_step(*sizes_of(run), *numbers)


def pieces(run: dict) -> float | None:
    """Prompts' pieces prefilled in the traced span: a request admitted
    whole is one, a chunk of a longer prompt one."""
    found = [readers.delta(run, key, "trace_counters")
             for key in ("prefills", "prefill_chunks")]
    return None if None in found else sum(found)


def scan_seconds(run: dict) -> float | None:
    """Device seconds under `aiko.gdn_scan` inside the admit and extend
    programs over the traced span; None where no operation carries it."""
    trace, _ = regions.of_run(run)
    if trace is None or "gdn_states_moved" not in run["counters"]["after"]:
        return None
    found = regions.region_seconds(trace, PREFILL)
    if not found or "aiko.gdn_scan" not in found["seconds"]:
        return None
    return found["seconds"]["aiko.gdn_scan"]


def scan_work(run: dict) -> dict | None:
    tokens = readers.delta(run, "tokens_prefill", "trace_counters")
    count = pieces(run)
    if not tokens or not count:
        return None
    return ops.scan(run["config"], tokens, count)
