"""Kernel cells: the paper's dot product, ``repro.kernels.ops.dot``, as a
user calls it, in a closed loop of one caller.

Set-up makes the two float32 vectors of length ``n`` on the device from
the seed (they stay resident) and compiles ``ops.dot`` under the
traffic's ``scheme``, jitted once. The window calls it back to back;
each call's scalar is read to the host before the next call is made.

End-to-end (host clock): ``dot_gb_s`` = 2 n x 4 bytes x calls completed
in the window / window seconds (1 GB = 1e9 B).

``correct``: once the window has closed, every answer is compared with
the exact sum of the products (``bench/reference/dot.py``, float64 on
the host). The number compared is the largest error over the calls,
relative to the sum of |a_i b_i|: unlike an error relative to the sum,
which lies near zero on some seeds, it is steady from seed to seed.
"""

from __future__ import annotations

import contextlib
import functools
import time

import numpy as np

from bench.harness import Cell, Outcome, log, memory_peak
from bench.reference import dot as reference


def vectors(n: int, seed: int):
    """Two standard-normal float32 vectors, made on the device in one
    jitted call from any whole-number seed."""
    import jax

    words = np.random.SeedSequence(seed).generate_state(2)
    key = jax.random.fold_in(jax.random.key(int(words[0] >> 1)),
                             int(words[1] >> 1))

    @functools.partial(jax.jit, static_argnums=0)
    def make(n, key):
        ka, kb = jax.random.split(key)
        return (jax.random.normal(ka, (n,), jax.numpy.float32),
                jax.random.normal(kb, (n,), jax.numpy.float32))

    return make(n, key)


def dot_call(scheme: str, compute_dtype=None):
    """The jitted call a user makes: ``ops.dot(a, b, scheme=...)``."""
    import jax

    from repro.kernels import ops

    def dot(a, b):
        return ops.dot(a, b, scheme=scheme, compute_dtype=compute_dtype)

    return jax.jit(dot)


def run(cell: Cell) -> Outcome:
    """One run; with ``cell.control`` the program's own bfloat16-accumulate
    path, the precision below the configuration's, is the one timed and
    compared."""
    import jax

    conf, tr = cell.config, cell.traffic
    n = conf["n"]
    a, b = vectors(n, cell.seed)
    fn = dot_call(tr["scheme"], "bfloat16" if cell.control else None)
    float(fn(a, b))                       # compile (or load) and warm up
    programs, misses = cell.compiles.reset() if cell.compiles else (0, 0)
    setup_s = time.perf_counter() - cell.t0
    log(f"setup_s={setup_s:.3f} programs={programs} cache_misses={misses}")
    answers, stamps = [], []
    start = time.perf_counter()
    end = start + cell.seconds
    trace_to = start + min(cell.seconds, tr["trace_seconds"])
    with contextlib.ExitStack() as tracing:
        tracing.enter_context(cell.traced())
        while True:
            with cell.span("bench.call"):
                out = fn(a, b)
            with cell.span("bench.readback"):
                answers.append(float(out))
            now = time.perf_counter()
            stamps.append(now)
            if now >= trace_to:
                tracing.close()
            if now >= end:
                break
    window = now - start
    in_window = cell.compiles.reset() if cell.compiles else (0, 0)
    calls = len(answers)
    peak = memory_peak(jax.devices()[:cell.chips])
    a_h, b_h = np.asarray(a), np.asarray(b)
    del a, b
    t = time.perf_counter()
    exact, scale = reference.exact_dot(a_h, b_h)
    err = max(abs(x - exact) for x in set(answers)) / scale
    e2e = {"dot_gb_s": 2 * n * 4 * calls / window / 1e9, "setup_s": setup_s}
    took = np.diff([start] + stamps)
    slow = took[took > 2 * np.median(took)]
    notes = {"calls": calls, "window_s": window,
             "us_per_call": 1e6 * window / calls,
             "median_call_us": 1e6 * float(np.median(took)),
             "slowest_call_ms": 1e3 * float(took.max()),
             "calls_over_twice_median": int(slow.size),
             "ms_in_those_calls": 1e3 * float(slow.sum()),
             "distinct_answers": len(set(answers)),
             "programs_in_window": in_window[0],
             "compiles_in_window": in_window[1],
             "exact": exact, "sum_abs_products": scale,
             "check_s": time.perf_counter() - t, "memory_peak_bytes": peak}
    return Outcome(e2e=e2e,
                   checks={"max_rel_err": (err, cell.limits["max_rel_err"])},
                   attempted=calls, failed=0,
                   records={"n": n},
                   memory_peak_bytes=peak, notes=notes)
