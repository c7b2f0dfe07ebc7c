"""``sessions`` (an app's users): every request opens with one of the
app's own system prompts, then carries the user's part of the
conversation, and is due at a fixed time whatever the server is doing.

Parameters (``bench/traffic/<mix>.json``), besides ``open_loop``'s
``rate_per_s``, ``backlog``, ``ramp_s``, ``block`` and ``output``:

* ``system``: ``count`` system prompts, their lengths evenly spaced over
  ``[min, max]``, and ``shared``: whether a request's system part is one
  of those prompts (``true``) or drawn fresh for the request (``false``,
  the control in which nothing repeats);
* ``own``: the request's own part, lognormal by ``median`` and
  ``sigma``, clipped to ``[min, max]``;
* ``prompt``: ``min`` and ``max``, bounds of the totals, which the
  warm-up reads: the shortest system prompt plus ``own``'s ``min``, and
  the longest plus ``own``'s ``max``.

The own parts are stratified quantiles in ``block`` strata, and stratum
``s`` always goes with system prompt ``s % count``, so every block of
the schedule holds each system prompt ``block / count`` times and the
totals are the same set for every seed. The seed orders the requests
(``bench/generator.py``'s ``balanced``), shuffles the gaps and draws
every token. Lengths and gaps are drawn before any token, so the shared
and the unshared mix give one seed the same lengths at the same times.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from bench.generator import (Req, balanced, exponential_quantiles,
                             lognormal_quantiles)
from bench.generators.open_loop import request_count


def system_lengths(system: Dict[str, Any]) -> np.ndarray:
    """The system prompts' lengths, evenly spaced over [min, max]."""
    return np.rint(np.linspace(system["min"], system["max"],
                               system["count"])).astype(int)


def schedule(traffic: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Req]:
    n = request_count(traffic, seconds)
    sysp, own, o = traffic["system"], traffic["own"], traffic["output"]
    block, count = traffic["block"], sysp["count"]
    if block % count:
        raise ValueError(f"block {block} does not hold each of {count} "
                         f"system prompts equally often")
    rng = np.random.default_rng(seed)
    own_sorted = np.sort(lognormal_quantiles(
        n, own["median"], own["sigma"], own["min"], own["max"]))
    idx = balanced(np.arange(n), block, rng)      # index into own_sorted
    which = (idx // (n // block)) % count         # stratum -> system prompt
    outs = balanced(lognormal_quantiles(
        n, o["median"], o["sigma"], o["min"], o["max"]), block, rng)
    backlog = traffic.get("backlog", 0)
    gaps = rng.permutation(exponential_quantiles(n - backlog,
                                                 traffic["rate_per_s"]))
    due = np.concatenate([np.zeros(backlog), np.cumsum(gaps)])
    lens = system_lengths(sysp)
    shared = [rng.integers(0, vocab, int(k)).astype(np.int32) for k in lens]
    reqs = []
    for k in range(n):
        head = (shared[which[k]] if sysp["shared"] else
                rng.integers(0, vocab, int(lens[which[k]])).astype(np.int32))
        tail = rng.integers(0, vocab, int(own_sorted[idx[k]])).astype(np.int32)
        reqs.append(Req(float(due[k]), np.concatenate([head, tail]),
                        int(outs[k])))
    return reqs
