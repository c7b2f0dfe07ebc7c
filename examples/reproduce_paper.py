"""Reproduce the paper's tables from the ECM implementation.

Prints (a) the §3 IvyBridge walk-through (naive/scalar/SSE/AVX predictions,
saturation points, Eq. 2), (b) Table 2 across SNB/IVB/HSW/BDW, and (c) the
TPU transplants. Every x86 number here is pinned against the published
values by tests/test_ecm.py.

    PYTHONPATH=src python examples/reproduce_paper.py
"""

from repro.core import ecm


def main():
    print("=" * 72)
    print("(a) Paper §3: IvyBridge, single precision")
    print("=" * 72)
    rows = [
        ("naive (AVX, compiler)", ecm.NAIVE_SP),
        ("Kahan scalar", ecm.KAHAN_SCALAR_SP),
        ("Kahan SSE", ecm.KAHAN_SSE_SP),
        ("Kahan AVX", ecm.KAHAN_AVX_SP),
        ("Kahan scalar (DP)", ecm.KAHAN_SCALAR_DP),
    ]
    for name, kern in rows:
        r = ecm.ecm_x86(ecm.IVB, kern)
        print(f"{name:22s} ECM {r.shorthand():34s} -> {r.pred_shorthand():26s}"
              f" P={r.perf_gups} GUP/s  n_s={r.n_s}")
    print("\npaper Eq. 2: P = {8.80 | 4.40 | 2.93 | 1.68} GUP/s "
          "(naive, IVB) — matches row 1")

    print()
    print("=" * 72)
    print("(b) Paper Table 2: optimal AVX Kahan across four Xeon generations")
    print("=" * 72)
    for m in (ecm.SNB, ecm.IVB, ecm.HSW, ecm.BDW):
        r = ecm.ecm_x86(m, ecm.KAHAN_AVX_SP)
        print(f"{m.name}: ECM {r.shorthand():36s} pred {r.pred_shorthand():26s}"
              f" perf {r.perf_gups} GUP/s")

    print()
    print("=" * 72)
    print("(c) TPU transplant: v4 / v5e / v5p")
    print("=" * 72)
    for m in (ecm.TPU_V4, ecm.TPU_V5E, ecm.TPU_V5P):
        for kern in (ecm.NAIVE_DOT_TPU, ecm.KAHAN_DOT_TPU,
                     ecm.KAHAN_DOT_SEQ_TPU):
            r = ecm.ecm_tpu(m, kern)
            print(f"{m.name} {kern.name:15s} {r.shorthand():44s}"
                  f" P={r.perf_db_gups:8.2f} GUP/s ({r.bound})")
        print("-> 'Kahan comes for free' holds whenever the vectorized "
              "kernel stays bandwidth-bound;")
        print("   the sequential variant is instruction-bound everywhere — "
              "the paper's scalar result.\n")


if __name__ == "__main__":
    main()
