"""What every traffic generator shares, and the one entry that finds a
generator by the name its traffic file gives.

A traffic file ``bench/traffic/<mix>.json`` names its generator under
``generator``; the generator is ``bench/generators/<generator>.py``, whose
``schedule(traffic, seed, seconds, vocab)`` returns the requests. A new
shape of traffic is a new file there, and no file here changes.

The helpers keep the work the same for every seed: lengths and gaps are
stratified quantiles of their distributions, and ``balanced`` orders
them so that every block of consecutive requests holds one value from
each stratum. The seed only chooses the order and the prompt tokens.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import math
import pathlib
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

GENERATORS = pathlib.Path(__file__).resolve().parent / "generators"


@dataclasses.dataclass(frozen=True)
class Req:
    due_s: float                 # after the start of traffic
    prompt: np.ndarray           # int32 token ids
    max_new_tokens: int


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """``n`` stratified lognormal lengths, rounded and clipped."""
    z = np.array([NormalDist().inv_cdf((k + 0.5) / n) for k in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def exponential_quantiles(n: int, rate: float) -> np.ndarray:
    return np.array([-math.log(1.0 - (k + 0.5) / n) / rate for k in range(n)])


def balanced(values: np.ndarray, block: int,
             rng: np.random.Generator) -> np.ndarray:
    """``values`` (a multiple of ``block`` of them) in an order drawn from
    ``rng`` in which each run of ``block`` consecutive entries holds one
    value from each of ``block`` strata (the sorted values cut into
    ``block`` equal parts). So any stretch of the schedule carries about
    the same work, whatever the seed."""
    v = np.sort(np.asarray(values))
    if v.size % block:
        raise ValueError(f"{v.size} values do not fill blocks of {block}")
    strata = v.reshape(block, -1)
    rows = np.stack([rng.permutation(s) for s in strata], axis=1)
    return np.stack([rng.permutation(r) for r in rows]).reshape(-1)


def schedule(traffic: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Req]:
    """The requests of one run: ``bench/generators/<generator>.py``."""
    path = GENERATORS / f"{traffic['generator']}.py"
    if not path.is_file():
        raise KeyError(f"no generator {traffic['generator']!r}: known "
                       f"{sorted(p.stem for p in GENERATORS.glob('*.py'))}")
    spec = importlib.util.spec_from_file_location(
        f"bench_generator_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.schedule(traffic, seed, seconds, vocab)
