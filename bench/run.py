"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. One process sets up (weights and inputs
from ``--seed``, compilation from the persistent cache at
``<checkout>/.jax_cache``, warm-up of every shape the cell's traffic
uses), measures for ``--seconds``, checks what the timed path produced
against the plain reference, and prints one JSON object as the last line
of standard output. With no TPU, or fewer chips than the cell asks for,
it exits 2 and prints no result.
"""

import time

T0 = time.perf_counter()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]

if __name__ == "__main__":
    # the cache directory is part of each entry's key, so it is fixed
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # in place of this script's own directory, which would shadow names
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    sys.exit(harness.main(t0=T0))
