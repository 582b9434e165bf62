"""Closed backlog: everything is offered at once and the queue never
empties.

Parameters: max_outstanding (requests kept queued or running: the next is
handed over as one finishes); supply_per_s (an upper bound on what can
complete, which sizes the supply); preroll_s (the ramp to full occupancy,
not counted); fields (see benchmark/draws.py).
"""

import math

import numpy as np

from benchmark import draws


def generate(params: dict, seed: int, seconds: float) -> dict:
    rng = np.random.default_rng([int(seed), 3])
    preroll = float(params.get("preroll_s", 0.0))
    count = int(params["max_outstanding"]) + math.ceil(
        params["supply_per_s"] * (seconds + preroll))
    sizes = draws.fields(params.get("fields", {}), count, rng)
    return {"requests": [{"id": f"r{i}", "due": -preroll, **sizes[i]}
                         for i in range(count)],
            "max_outstanding": int(params["max_outstanding"]),
            "preroll_s": preroll}
