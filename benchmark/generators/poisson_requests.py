"""Open loop: independent arrivals at a fixed mean rate.

Parameters: rate_per_s; preroll_s (arrivals begin that long before the
window so that it opens on a system in its steady state; they are served
and not counted); fields (see benchmark/draws.py) for each request's
sizes; order_draw, which fixes the ORDER of sizes and gaps too, for a mix of so
few requests that the order alone moves its tail (chat_open_loop: the same
seed twice read within 2%, six seeds spread by 12%; PERF.md, findings):
the seed then changes the prompts' tokens and the weights, nothing else.
"""

import numpy as np

from benchmark import draws


def generate(params: dict, seed: int, seconds: float) -> dict:
    order = params.get("order_draw")
    rng = np.random.default_rng([int(seed), 1] if order is None
                                else [int(order), 4])
    preroll = float(params.get("preroll_s", 0.0))
    count = max(1, round(params["rate_per_s"] * (seconds + preroll)))
    gaps = draws.permuted(draws.quantiles(
        {"dist": "exponential", "mean": 1.0 / params["rate_per_s"]}, count),
        rng)
    due = np.cumsum(gaps) - preroll
    sizes = draws.fields(params.get("fields", {}), count, rng)
    requests = [{"id": f"r{i}", "due": float(due[i]), **sizes[i]}
                for i in range(count)]
    return {"requests": requests, "max_outstanding": None,
            "preroll_s": preroll}
