"""What the latent-attention, routed-expert cell's metric files share:
the counters its driver adds and the work of its kernels from them.  A run
whose driver keeps no such counter (another program) reads as None.
"""

from __future__ import annotations

from benchmark import ops_bytes_latent_moe as ops
from benchmark import readers


def per_step(run: dict, key: str) -> float | None:
    """A counter's growth over the traced span, a decode step."""
    return readers.ratio(readers.delta(run, key, "trace_counters"),
                         readers.delta(run, "steps", "trace_counters"))


def share(run: dict, part: str, whole: str, scale: float = 1.0):
    """100 * part / (whole * scale) over the window, from the driver's
    counters."""
    value = readers.ratio(readers.delta(run, part),
                          (readers.delta(run, whole) or 0) * scale)
    return None if value is None else 100.0 * value


def held_tokens(run: dict) -> float | None:
    """Mean tokens of context the running requests held over the traced
    span (the benchmark's own stamps)."""
    trace = run["trace"]
    if not trace or not trace.get("window_s"):
        return None
    end = run["seconds"]
    return readers.live_tokens(run, end - trace["window_s"], end)


def roofline_share(run: dict, work: dict | None, ms: float | None):
    if not work or not ms or not run.get("peaks"):
        return None
    return 100.0 * ops.roofline_seconds(work, run["peaks"])["seconds"] \
        / (ms / 1e3)


def sizes_of(run: dict) -> tuple:
    config = run["config"]
    return config, ops.ITEMSIZE[config["dtype"]]


def attention_work(run: dict) -> dict | None:
    held = held_tokens(run)
    if held is None:
        return None
    return ops.latent_attention(*sizes_of(run), held)


def experts_work(run: dict) -> dict | None:
    hit, pairs = per_step(run, "moe_experts_hit"), per_step(run, "moe_pairs_here")
    if hit is None or pairs is None:
        return None
    return ops.routed_experts(*sizes_of(run), hit, pairs)


def step_work(run: dict) -> dict | None:
    held = held_tokens(run)
    hit, pairs = per_step(run, "moe_experts_hit"), per_step(run, "moe_pairs_here")
    slots = per_step(run, "useful_steps")
    if None in (held, hit, pairs, slots):
        return None
    return ops.decode_step(*sizes_of(run), slots, held, hit, pairs)
