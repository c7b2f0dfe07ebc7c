"""Find a serving cell's knee: the highest offered rate the engine
sustains without a growing backlog.

    python bench/sweep.py --workload <cell> --rates 0.5 1 1.5 ... [--seconds s]

One process builds the cell's engine once. For each rate it serves the
cell's traffic at that rate on a fresh engine over the same weights (the
ramp first, then ``--seconds``) and prints one JSON line: requests due
and finished in the window, tokens per second, the requests waiting at
the window's start and end, and the slope of the queue over the window.
The knee is the last rate whose queue does not grow; the traffic file
records it and the fixed rate derived from it. Benchmark runs never run
this.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def point(cell, engine_args, rate: float) -> dict:
    import numpy as np

    from bench import generator
    from bench.kinds import serve
    from repro.serve import InferenceEngine

    cfg, ec, model, params = engine_args
    engine = InferenceEngine(cfg, ec, model=model, params=params)
    # the slots filled at the start and no queue: the queue's slope is
    # what this rate adds to it
    cell.traffic = dict(cell.traffic, rate_per_s=rate,
                        backlog=ec.max_slots)
    reqs = generator.schedule(cell.traffic, cell.seed, cell.seconds,
                              cell.config["model"]["vocab_size"])
    recs, steps, _, (start, ws, we), late = serve.drive(cell, engine, reqs)
    e2e, due_in = serve.end_to_end(recs, ws, we)
    inside = [s for s in steps if s["t0"] >= ws]
    t = np.array([s["t1"] - ws for s in inside])
    q = np.array([s["queued"] for s in inside], float)
    slope = float(np.polyfit(t, q, 1)[0]) if len(t) > 2 else float("nan")
    finished = sum(r["handle"].done and ws <= r["tokens"][-1] <= we
                   for r in recs.values())
    del engine
    return {"rate_per_s": rate, "due_in_window": due_in,
            "finished_in_window": finished,
            "finished_per_s": finished / (we - ws),
            "queued_at_start": int(q[0]) if len(q) else 0,
            "queued_at_end": int(q[-1]) if len(q) else 0,
            "queue_slope_per_s": slope,
            "mean_occupancy": float(np.mean([s["occupancy"] for s in inside])),
            "generator_late_ms": 1e3 * late, **e2e}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    from bench import harness
    from bench.kinds import serve

    harness.enable_cache()
    cell = harness.make_cell(args.workload, args.seed, args.seconds, False,
                             time.perf_counter())
    harness.devices(cell.chips)
    engine, _ = serve.build(cell.config, cell.seed)
    serve.warm_up(engine, cell.traffic)
    engine_args = (engine.cfg, engine.ec, engine.model, engine.params)
    del engine
    for rate in args.rates:
        print(json.dumps(point(copy.copy(cell), engine_args, rate)),
              flush=True)
    return 0


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
