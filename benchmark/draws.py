"""Draws shared by the traffic generators.

Every seed gets the SAME multiset of sizes and of gaps between arrivals,
in another order: the values are the distribution's quantiles at evenly
spaced probabilities, and the seed only permutes them.  Runs then differ
by the order of the work and not by its amount, so two seeds spread no
more than two runs of one seed.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantiles(spec: dict, count: int) -> np.ndarray:
    """`count` values of the distribution `spec` describes, ascending.

    {"dist": "fixed", "value": v}
    {"dist": "uniform", "min": a, "max": b}
    {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
    {"dist": "exponential", "mean": m}
    Whole numbers unless "float" is true; lognormal values are clipped."""
    p = (np.arange(count) + 0.5) / count
    kind = spec["dist"]
    if kind == "fixed":
        values = np.full(count, float(spec["value"]))
    elif kind == "uniform":
        values = spec["min"] + (spec["max"] - spec["min"]) * p
    elif kind == "lognormal":
        normal = NormalDist()
        values = np.array([spec["median"] * math.exp(
            spec["sigma"] * normal.inv_cdf(q)) for q in p])
        values = np.clip(values, spec["min"], spec["max"])
    elif kind == "exponential":
        values = -spec["mean"] * np.log1p(-p)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return values if spec.get("float") or kind == "exponential" \
        else np.rint(values).astype(np.int64)


def permuted(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    return values[rng.permutation(len(values))]


def fields(specs: dict, count: int, rng: np.random.Generator) -> list:
    """One dict per request, each field permuted on its own."""
    columns = {name: permuted(quantiles(spec, count), rng)
               for name, spec in sorted(specs.items())}
    return [{name: column[i].item() for name, column in columns.items()}
            for i in range(count)]
