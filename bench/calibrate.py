"""Readings that the limits in ``bench/configs/*.json`` are set from.

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ... [--seconds s]

For each seed, in this one process, it runs the cell as a benchmark run
does (the program's numbers: the lower readings) and its control, the
same comparison with the precision below the configuration's (the upper
readings), through the harness with ``cell.control`` set, so that the
control's numbers meet the cell's limits as a run's would and the line
says whether it came out correct (it must not):

* serving cells: the reference's forward with float8 operands in the
  program's place, read at each position of the same sampled prompts
  and served tokens (the gap of the token it puts first, and its norm);
* dot cells: the program's own bfloat16-accumulate path.

One JSON line per seed goes to standard output. The benchmark's own runs
never run this. It needs the chip, as ``bench/run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def readings(workload: str, seed: int, seconds: float) -> dict:
    from bench import harness

    spec = harness.load_spec()

    def once(control: bool, seconds: float):
        cell = harness.make_cell(workload, seed, seconds, False,
                                 time.perf_counter(), spec)
        cell.control = control
        line, outcome = harness.measure(cell, spec)
        return line, outcome, {k: v for k, (v, _) in outcome.checks.items()}

    if harness.make_cell(workload, seed, seconds, False, 0.0,
                         spec).config["kind"] == "serve":
        # one run: the control is read on the program's own sample
        line, outcome, control = once(True, seconds)
        program = {k[len("program_"):]: v for k, v in outcome.notes.items()
                   if k.startswith("program_")}
    else:
        _, _, program = once(False, seconds)
        line, _, control = once(True, min(seconds, 1.0))
    return {"workload": workload, "seed": seed, "program": program,
            "control": control, "control_correct": line["correct"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window of each run (default: run_seconds)")
    args = ap.parse_args()
    from bench import harness

    seconds = args.seconds or harness.load_spec()["run_seconds"]
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed, seconds)), flush=True)
    return 0


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
