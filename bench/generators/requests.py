"""General request generator: lengths from a distribution, arrivals open
or closed, greedy decoding, every request drawn from the seed.

A traffic file (`bench/traffic/<mix>.json`) names this generator and
gives its parameters:

    "arrivals": {"kind": "poisson", "rate_per_s": 6.0}   # open loop
    "arrivals": {"kind": "closed", "clients": 8, "pool": 16}  # closed loop
    "prompt":   {"dist": "lognormal", "median": 512, "sigma": 1.0,
                 "min": 32, "max": 2048}
    "output":   {"dist": "uniform", "min": 64, "max": 256}

Every seed gets the same work in another order. The lengths are the
quantiles of their distribution at (i + 0.5) / n, and the open loop's
gaps between arrivals the quantiles of the exponential distribution, so
the open loop offers exactly rate x seconds requests in the window. The
seed draws an independent uniform permutation of each list, and the
token ids: gaps and lengths are exchangeable as in a Poisson stream
with independent lengths, so short gaps bunch into bursts and long
prompts can land together, but no seed gets more or longer requests
than another. The closed loop's clients take the pool's requests in
turn, from the start again once it is used up.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Plan:
    """What the window sends. ``due`` holds the open loop's send times
    in seconds from the window's start; ``clients`` the closed loop's
    client count (each sends the next request of ``prompts`` when its
    previous one finishes)."""
    prompts: List[np.ndarray]
    max_new: List[int]
    due: Optional[np.ndarray] = None
    clients: Optional[int] = None


def _quantiles(n: int, d: dict) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    if d["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = d["median"] * np.exp(d["sigma"] * z)
    elif d["dist"] == "uniform":
        v = d["min"] + u * (d["max"] - d["min"])
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return np.clip(np.rint(v), d["min"], d["max"]).astype(np.int64)


def build(params: dict, seed: int, seconds: float, vocab: int) -> Plan:
    rng = np.random.default_rng(seed % 2**64)
    arr = params["arrivals"]
    if arr["kind"] == "poisson":
        n = max(1, round(arr["rate_per_s"] * seconds))
    elif arr["kind"] == "closed":
        n = int(arr["pool"])
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    plens = rng.permutation(_quantiles(n, params["prompt"]))
    olens = rng.permutation(_quantiles(n, params["output"]))
    prompts = [rng.integers(0, vocab, int(p), dtype=np.int32)
               for p in plens]
    plan = Plan(prompts=prompts, max_new=[int(o) for o in olens])
    if arr["kind"] == "poisson":
        u = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-u) / arr["rate_per_s"])
        plan.due = np.cumsum(gaps) - gaps[0]
    else:
        plan.clients = int(arr["clients"])
    return plan
