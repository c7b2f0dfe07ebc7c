"""``open_loop`` (independent users): requests due at fixed times after the
start of traffic, whatever the server is doing.

Parameters (``bench/traffic/<mix>.json``): ``rate_per_s`` (Poisson
arrivals), ``backlog`` (requests all due at time 0, before them),
``ramp_s`` (traffic before the window opens), ``block`` (the run of
requests that carries one length from each stratum) and ``prompt`` and
``output``: lognormal lengths by ``median`` and ``sigma``, clipped to
``[min, max]``.

Lengths and gaps are the same set for every seed (stratified quantiles,
``bench/generator.py``). The lengths are ordered by ``balanced`` and the
gaps shuffled: two seeds put the same work into the same time, and each
stretch of the schedule carries about the same work, so runs differ by
the order of the work, not its amount.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np

from bench.generator import (Req, balanced, exponential_quantiles,
                             lognormal_quantiles)


def request_count(traffic: Dict[str, Any], seconds: float) -> int:
    """Requests due before the window closes, a tenth more for slack,
    rounded up to whole blocks."""
    horizon = traffic.get("ramp_s", 0.0) + seconds
    block = traffic["block"]
    n = traffic.get("backlog", 0) + math.ceil(
        1.1 * traffic["rate_per_s"] * horizon) + 1
    return block * math.ceil(n / block)


def schedule(traffic: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Req]:
    n = request_count(traffic, seconds)
    p, o, block = traffic["prompt"], traffic["output"], traffic["block"]
    rng = np.random.default_rng(seed)
    plens = balanced(lognormal_quantiles(
        n, p["median"], p["sigma"], p["min"], p["max"]), block, rng)
    outs = balanced(lognormal_quantiles(
        n, o["median"], o["sigma"], o["min"], o["max"]), block, rng)
    backlog = traffic.get("backlog", 0)
    gaps = rng.permutation(exponential_quantiles(n - backlog,
                                                 traffic["rate_per_s"]))
    due = np.concatenate([np.zeros(backlog), np.cumsum(gaps)])
    return [Req(float(due[k]),
                rng.integers(0, vocab, int(plens[k])).astype(np.int32),
                int(outs[k]))
            for k in range(n)]
