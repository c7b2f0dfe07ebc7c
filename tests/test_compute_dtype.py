"""``Policy.compute_dtype`` threading: Policy -> engine -> kernel bodies
-> oracles -> ECM tables.

Acceptance bar (ISSUE 3): with ``compute_dtype="float64"`` the GenDot
accuracy ladder strictly improves over fp32 for ``naive``, while
``kahan``/``dot2`` stay within their a-priori ``error_bound`` evaluated
at the f64 unit roundoff. bf16 accumulate is the other end of the trade
space; the kernel-vs-oracle bitwise contract holds along the whole axis.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ecm, numerics
from repro.kernels import ops, ref, schemes
from repro.kernels.engine import CompensatedReduction
from repro.kernels.schemes import Policy, use_policy

N = 8192


@pytest.fixture(scope="module")
def gendot():
    a, b, exact, cond = numerics.gen_dot(N, 1e8, seed=8)
    return jnp.asarray(a), jnp.asarray(b), exact, cond


def test_f64_ladder_strictly_improves_naive(gendot):
    a, b, exact, cond = gendot
    err32 = numerics.relative_error(
        float(ops.dot(a, b, scheme="naive", unroll=1)), exact)
    with jax.enable_x64(True):
        err64 = numerics.relative_error(
            float(ops.dot(a, b, scheme="naive", unroll=1,
                          compute_dtype="float64")), exact)
    assert err64 < err32, (err64, err32)


@pytest.mark.parametrize("name", ["kahan", "dot2"])
def test_f64_compensated_within_apriori_bound(gendot, name):
    a, b, exact, cond = gendot
    with jax.enable_x64(True):
        got = float(ops.dot(a, b, scheme=name, unroll=1,
                            compute_dtype="float64"))
    err = numerics.relative_error(got, exact)
    bound = schemes.get(name).error_bound(N, cond, eps=schemes.EPS64)
    assert err <= bound, (name, err, bound)


@pytest.mark.parametrize("name", ["naive", "kahan", "dot2"])
def test_f64_kernel_matches_oracle_bitwise(gendot, name):
    a, b, _, _ = gendot
    with jax.enable_x64(True):
        got = float(ops.dot(a, b, scheme=name, unroll=1,
                            compute_dtype="float64"))
        want = float(ref.dot_ref(a, b, scheme=name, rows=8,
                                 compute_dtype="float64"))
    assert got == want, name


def test_bf16_accumulate_kernel_matches_oracle_bitwise():
    rng = np.random.default_rng(9)
    a = jnp.asarray(rng.standard_normal(8 * 128 * 3 + 41), jnp.float32)
    b = jnp.asarray(rng.standard_normal(a.shape[0]), jnp.float32)
    for name in ("naive", "kahan"):
        got = ops.dot(a, b, scheme=name, unroll=1,
                      compute_dtype="bfloat16")
        assert got.dtype == jnp.bfloat16
        want = ref.dot_ref(a, b, scheme=name, rows=8,
                           compute_dtype="bfloat16")
        assert float(got) == float(want), name


def test_bf16_kahan_recovers_dropped_bits_on_long_sum():
    """The bf16-accumulate trade space (the precision-vs-compensation
    axis the follow-up papers motivate): summing 512 exact ones per lane,
    a naive bf16 accumulator STALLS at 256 (256 + 1 rounds back to 256
    with an 8-bit mantissa) and loses half the total; the Kahan pair
    carries the dropped units in ``c`` and recovers the sum. Inputs are
    exactly bf16-representable, so the gap is pure accumulation error."""
    n = 8 * 128 * 512  # 512 sequential adds per accumulator lane
    x = jnp.ones((n,), jnp.float32)
    errs = {
        name: abs(float(ops.asum(x, scheme=name, unroll=1,
                                 compute_dtype="bfloat16")) - n) / n
        for name in ("naive", "kahan")}
    assert errs["naive"] > 0.25, errs      # the stall really happened
    assert errs["kahan"] < 0.01, errs      # compensation recovered it


def test_policy_threads_compute_dtype_through_use_policy():
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.standard_normal(4096), jnp.float32)
    with use_policy(scheme="kahan", unroll=2, compute_dtype="bfloat16"):
        out = ops.asum(a)
    assert out.dtype == jnp.bfloat16
    explicit = ops.asum(a, scheme="kahan", unroll=2,
                        compute_dtype="bfloat16")
    assert float(out) == float(explicit)
    # engine resolves the ambient policy's dtype too
    with use_policy(compute_dtype="bfloat16"):
        eng = CompensatedReduction(scheme="kahan")
    assert eng.compute_dtype == jnp.dtype("bfloat16")


def test_matmul_and_flash_accept_compute_dtype():
    rng = np.random.default_rng(12)
    a = jnp.asarray(rng.standard_normal((16, 512)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((512, 128)), jnp.float32)
    mm = ops.matmul(a, b, scheme="kahan", block_m=16, block_n=128,
                    block_k=256, compute_dtype="bfloat16")
    assert mm.dtype == jnp.bfloat16
    q = jnp.asarray(rng.standard_normal((1, 128, 64)), jnp.float32)
    eng = CompensatedReduction(scheme="kahan", compute_dtype="bfloat16")
    out = eng.flash_attention(q, q, q, block_q=128, block_k=128)
    assert out.dtype == jnp.bfloat16


def test_compute_dtype_fails_fast_with_menu_everywhere():
    """Satellite: the fail-fast enumerates the supported dtypes and fires
    at the API boundary (Policy construction, engine construction, ops
    kwarg) — never inside a trace."""
    a = jnp.zeros((8,), jnp.float32)
    for call in (lambda: Policy(compute_dtype="float16"),
                 lambda: CompensatedReduction(compute_dtype="float16"),
                 lambda: ops.dot(a, a, compute_dtype="float16"),
                 lambda: ops.matmul(jnp.zeros((8, 8)), jnp.zeros((8, 8)),
                                    compute_dtype="int8")):
        with pytest.raises(ValueError) as ei:
            call()
        msg = str(ei.value)
        assert "bfloat16" in msg and "float32" in msg and "float64" in msg


def test_f64_without_x64_fails_fast():
    if jax.config.jax_enable_x64:
        pytest.skip("x64 globally enabled")
    with pytest.raises(ValueError, match="x64"):
        Policy(compute_dtype="float64")


def test_f64_on_tpu_backend_fails_fast_with_menu(monkeypatch):
    """A TPU backend has no 64-bit float in Mosaic: f64 accumulate is
    refused at the boundary (even with x64 on), naming what can run."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.enable_x64(True):
        for call in (lambda: Policy(compute_dtype="float64"),
                     lambda: CompensatedReduction(compute_dtype="float64")):
            with pytest.raises(ValueError, match="TPU") as ei:
                call()
            assert "float32 | bfloat16" in str(ei.value)
        # the supported dtypes still resolve on that backend
        assert Policy(compute_dtype="bfloat16").compute_dtype == \
            jnp.dtype("bfloat16")


def test_ecm_tables_model_the_dtype_axis():
    assert ecm.elem_bytes_for_dtype("bfloat16") == 2
    assert ecm.elem_bytes_for_dtype(jnp.dtype("float64")) == 8
    with pytest.raises(ValueError, match="float16"):
        ecm.elem_bytes_for_dtype("float16")
    blocks16 = ecm.registry_tpu_blocks(compute_dtype="bfloat16")
    blocks64 = ecm.registry_tpu_blocks(compute_dtype="float64")
    assert blocks16["kahan"].elem_bytes == 2
    assert blocks64["kahan"].elem_bytes == 8
    # halved element width halves the HBM bytes per block -> the
    # bandwidth roofline moves while the instruction mix stays fixed
    r16 = ecm.ecm_tpu(ecm.TPU_V5E, blocks16["kahan"])
    r64 = ecm.ecm_tpu(ecm.TPU_V5E, blocks64["kahan"])
    assert r16.t_hbm_cy < r64.t_hbm_cy
    k32 = ecm.dot_kernel_for_scheme("kahan", compute_dtype="float32")
    assert k32.elem_bytes == 4
