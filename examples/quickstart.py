"""Quickstart: the paper's kernel as a library call.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import numerics
from repro.core.kahan import kahan_dot, kahan_sum, naive_dot
from repro.kernels import ops


def main():
    # 1. An ill-conditioned dot product (cond ~ 1e6): naive fp32 loses
    #    digits; the Kahan kernel recovers most; dot2 recovers all.
    a, b, exact, cond = numerics.gen_dot(8192, 1e4, seed=0)
    print(f"condition number: {cond:.2e}; exact value: {exact:.9e}")
    for name, val in [
        ("naive (sequential)", float(naive_dot(jnp.asarray(a), jnp.asarray(b)))),
        ("kahan (pure jax)", float(kahan_dot(jnp.asarray(a), jnp.asarray(b)))),
        ("kahan (pallas kernel)", float(ops.dot(jnp.asarray(a), jnp.asarray(b), scheme="kahan"))),
        ("dot2  (pallas kernel)", float(ops.dot(jnp.asarray(a), jnp.asarray(b), scheme="dot2"))),
    ]:
        print(f"  {name:24s} {val:.9e}  relerr={numerics.relative_error(val, exact):.2e}")

    # 2. Compensated summation: 1.0 added to 1e8, 4096 times, in fp32.
    x = np.concatenate([[1e8], np.ones(4096)]).astype(np.float32)
    print("\nsum of 1e8 + 4096 ones (fp32):")
    print(f"  naive jnp.sum : {float(jnp.sum(jnp.asarray(x))):.1f}")
    print(f"  kahan_sum     : {float(kahan_sum(jnp.asarray(x))):.1f}"
          "   (exact: 100004096)")

    # 3. One Policy selects scheme x unroll x blocks x ACCUMULATE DTYPE
    #    for every kernel. compute_dtype="float64" (needs x64) turns the
    #    engine into its own verification oracle: the f64-accumulated
    #    batched matmul is the reference the fp32 run is judged against.
    from repro.kernels import use_policy

    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.standard_normal((2, 16, 2048)) * 10, jnp.float32)
    B = jnp.asarray(rng.standard_normal((2, 2048, 128)) * 10, jnp.float32)
    c32 = {}
    for scheme in ("naive", "kahan"):
        with use_policy(scheme=scheme, blocks=(16, 128, 256)):
            c32[scheme] = np.asarray(ops.batched_matmul(A, B), np.float64)
    with jax.enable_x64(True):
        with use_policy(scheme="kahan", compute_dtype="float64",
                        blocks=(16, 128, 256)):
            c64 = np.asarray(ops.batched_matmul(A, B))
    print("\nbatched_matmul [2,16,2048]@[2,2048,128], fp32 vs f64-verify:")
    for scheme, c in c32.items():
        err = np.abs(c - c64).max() / np.abs(c64).max()
        print(f"  {scheme:6s} fp32 accumulate: max relerr vs f64 {err:.2e}")

    # 4. Request-level serving in five lines: the continuous-batching
    #    engine admits each request into a decode slot, samples with its
    #    own fold_in stream, and guarantees its tokens + compensated
    #    telemetry are bitwise identical solo or under traffic.
    from repro.configs import get_smoke
    from repro.serve import EngineConfig, InferenceEngine, Request, SamplingParams

    engine = InferenceEngine(get_smoke("olmo-1b"),
                             EngineConfig(max_slots=2, max_len=16,
                                          track_stats=True))
    handles = engine.run([Request(prompt=[3, 1, 4, 1, 5],
                                  sampling=SamplingParams(max_new_tokens=4)),
                          Request(prompt=[2, 7],
                                  sampling=SamplingParams(max_new_tokens=2))])
    print("\nserved:", {rid: h.tokens for rid, h in sorted(handles.items())})

    # 5. The ECM model: why Kahan is free on TPU when vectorized.
    #    Variant descriptions derive from the scheme registry.
    from repro.core import ecm
    for k in (ecm.NAIVE_DOT_TPU, ecm.KAHAN_DOT_TPU, ecm.KAHAN_DOT_SEQ_TPU):
        r = ecm.ecm_tpu(ecm.TPU_V5E, k)
        print(f"\nECM v5e {k.name}: {r.shorthand()}"
              f"\n  -> {r.perf_db_gups} GUP/s ({r.bound}-bound)")


if __name__ == "__main__":
    main()
