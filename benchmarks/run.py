"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus commented context
blocks). Mapping to the paper:

  bench_accuracy        motivation (why compensate): error vs condition
                        — registry-driven: sweeps EVERY scheme in
                        repro.kernels.schemes (+ a-priori bounds)
  bench_dot_variants    Fig. 2 — per-variant cycles across the
                        hierarchy (variant list = the scheme registry
                        via ecm.registry_tpu_blocks)
  bench_model_error     model honesty: ECM-predicted vs measured
                        us/call per scheme from the dot-grid rows
                        (ecm_model_error_<scheme> rows)
  bench_batched         batched engine: one (batch, steps) grid vs a
                        per-call loop (the 2016 follow-up's saturation
                        claim, in batched-serving form)
  bench_matmul_batched  batched matmul engine: one (batch, mb, nb, ks)
                        grid vs a per-call loop + the vmap dispatch row
  bench_serve           continuous-batching engine: tokens/s vs decode-
                        slot occupancy for every registered scheme (the
                        saturation claim in request-level serving form)
  bench_scaling         Fig. 3 — multicore/multichip scaling + saturation
  bench_architectures   Table 2 / Fig. 4 — cross-generation comparison
  bench_flash_attention the §Perf-identified fix: fused attention with
                        compensated online softmax
  bench_e2e             system-level step cost, Kahan on/off
  bench_roofline        §Roofline table from the dry-run artifacts

Accumulator contract (every compensated row above): reductions carry an
``(s, c)`` pair with ``total = s + c``; partial grids merge through the
deterministic two-sum tree in ``repro.kernels.engine.merge_accumulators``
— cross-lane, cross-batch (vmap), and cross-device (collectives) alike.

CLI::

    python -m benchmarks.run                  # full sweep, CSV to stdout
    python -m benchmarks.run --smoke          # tiny shapes (CI stage 3)
    python -m benchmarks.run --json OUT.json  # also write the rows as a
                                              # BENCH_*.json artifact
"""

import argparse
import json


def _benchmarks():
    """(name, module, full_kwargs, smoke_kwargs) in run order. The smoke
    kwargs shrink the parameterizable sweeps to CI-budget shapes; no-arg
    modules are already smoke-sized (CPU interpret mode)."""
    from benchmarks import (
        bench_accuracy,
        bench_architectures,
        bench_batched,
        bench_dot_variants,
        bench_e2e,
        bench_flash_attention,
        bench_matmul_batched,
        bench_model_error,
        bench_roofline,
        bench_scaling,
        bench_serve,
    )

    return [
        ("bench_accuracy", bench_accuracy, {}, {"n": 1 << 11}),
        ("bench_dot_variants", bench_dot_variants, {}, {"n": 1 << 14}),
        # reads the dot_<scheme> rows bench_dot_variants just captured,
        # so the n here must match its n
        ("bench_model_error", bench_model_error, {}, {"n": 1 << 14}),
        ("bench_batched", bench_batched, {},
         {"batch": 2, "n": 8 * 128 * 4}),
        ("bench_matmul_batched", bench_matmul_batched, {},
         {"batch": 2, "m": 32, "k": 512, "n": 128}),
        ("bench_serve", bench_serve, {},
         {"max_slots": 2, "prompt_len": 8, "new_tokens": 4,
          "prefill_len": 64, "prefill_widths": (16, 64)}),
        ("bench_scaling", bench_scaling, {}, {}),
        ("bench_architectures", bench_architectures, {}, {}),
        ("bench_flash_attention", bench_flash_attention, {}, {}),
        ("bench_e2e", bench_e2e, {}, {}),
        ("bench_roofline", bench_roofline, {}, {}),
    ]


def main(smoke: bool = False, json_path: str = "") -> None:
    from benchmarks import common

    common.reset_rows()
    print("name,us_per_call,derived")
    for name, mod, full_kw, smoke_kw in _benchmarks():
        print(f"# ===== {name} =====")
        mod.main(**(smoke_kw if smoke else full_kw))
    if json_path:
        with open(json_path, "w") as f:
            json.dump({"smoke": smoke, "rows": common.ROWS}, f, indent=1)
        print(f"# wrote {len(common.ROWS)} rows to {json_path}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI benchmarks smoke stage)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write captured rows to a BENCH_*.json artifact")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main(smoke=args.smoke, json_path=args.json)
