"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles.

The compensated kernels must match their oracles BITWISE (same rounding
sequence executed by the interpret-mode kernel body); the matmul kernel is
compared with a tight tolerance (XLA CPU reassociates within-tile dots
differently for different shapes).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.costmodel import find_pallas_call
from repro.core import numerics
from repro.kernels import kahan_dot, ops, ref
from repro.kernels.engine import CompensatedReduction


SIZES = [8 * 128, 8 * 128 * 4 + 17, 50_000]
DTYPES = [np.float32, np.bfloat16] if hasattr(np, "bfloat16") else [np.float32]


def _data(n, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(n).astype(np.float32).astype(dtype),
            rng.standard_normal(n).astype(np.float32).astype(dtype))


#: lengths that make a grid step fold several accumulator tiles: 512
#: tiles at unroll 1 (256 per step under a 1 MiB cap), and 257 tiles, a
#: prime count, which keeps one tile per step at unroll 1
DOT_SIZES = SIZES + [8 * 128 * 512, 8 * 128 * 257]


@pytest.mark.parametrize("n", DOT_SIZES)
@pytest.mark.parametrize("scheme", ["naive", "kahan", "pairwise", "dot2"])
@pytest.mark.parametrize("unroll", [1, 4])
def test_dot_kernel_matches_oracle(n, scheme, unroll):
    a, b = _data(n, seed=n)
    got = ops.dot(jnp.asarray(a), jnp.asarray(b), scheme=scheme, unroll=unroll)
    want = ref.dot_ref(jnp.asarray(a), jnp.asarray(b), scheme=scheme,
                       rows=8 * unroll)
    assert float(got) == float(want), f"{scheme} unroll={unroll} not bitwise"


V5E_SCOPED_VMEM = 16 << 20  # v5e's default scoped VMEM limit


@pytest.mark.parametrize("n, unroll", [(1 << 27, 8), (1 << 24, 8),
                                       (8 * 128 * 512, 1), (8 * 128 * 257, 1),
                                       (50_000, 4), (1, 8)])
def test_dot_tiles_per_step(n, unroll):
    """T, the count of accumulator tiles a grid step streams, is the
    largest divisor of the tile count within the block cap; the grid
    covers the engine's padded length exactly, and the double-buffered
    blocks fit v5e's default scoped VMEM."""
    tile = 8 * unroll * 128
    tile_bytes = tile * 4
    steps = -(-n // tile)
    t = kahan_dot._tiles_per_step(steps, tile_bytes)
    assert steps % t == 0
    assert t == 1 or t * tile_bytes <= kahan_dot._BLOCK_BYTES
    assert not any(steps % d == 0 and d * tile_bytes <= kahan_dot._BLOCK_BYTES
                   for d in range(t + 1, steps + 1))
    # operands and outputs double-buffered, plus the (s, c) scratch
    assert 2 * 2 * t * tile_bytes + 6 * tile_bytes < V5E_SCOPED_VMEM

    x = jax.ShapeDtypeStruct((n,), jnp.float32)
    call = find_pallas_call(jax.make_jaxpr(
        lambda a, b: ops.dot(a, b, scheme="kahan", unroll=unroll))(x, x))
    (grid,) = call.params["grid_mapping"].grid
    padded = CompensatedReduction(unroll=unroll).block * steps
    assert grid * t == steps
    assert [v.aval.size for v in call.invars] == [padded, padded]
    if n == 1 << 27:
        assert grid <= 1024


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("scheme", ["naive", "kahan"])
def test_sum_kernel_matches_oracle(n, scheme):
    a, _ = _data(n, seed=n + 1)
    got = ops.asum(jnp.asarray(a), scheme=scheme, unroll=2)
    want = ref.sum_ref(jnp.asarray(a), scheme=scheme, rows=16)
    assert float(got) == float(want)


def test_dot_kernel_bf16_inputs():
    rng = np.random.default_rng(3)
    a = rng.standard_normal(4096).astype(np.float32)
    b = rng.standard_normal(4096).astype(np.float32)
    a16 = jnp.asarray(a).astype(jnp.bfloat16)
    b16 = jnp.asarray(b).astype(jnp.bfloat16)
    got = ops.dot(a16, b16, scheme="kahan")
    want = ref.dot_ref(a16, b16, scheme="kahan", rows=64)
    assert float(got) == float(want)
    # and it should be close to the fp32 result (inputs quantized to bf16)
    exact = numerics.exact_dot(np.asarray(a16, np.float32),
                               np.asarray(b16, np.float32))
    assert numerics.relative_error(float(got), exact) < 1e-5


@pytest.mark.parametrize("shape", [(32, 256, 64), (100, 700, 130),
                                   (8, 1024, 128)])
@pytest.mark.parametrize("scheme", ["naive", "kahan"])
def test_matmul_kernel_matches_oracle(shape, scheme):
    m, k, n = shape
    rng = np.random.default_rng(m + k)
    a = rng.standard_normal((m, k)).astype(np.float32)
    b = rng.standard_normal((k, n)).astype(np.float32)
    got = ops.matmul(jnp.asarray(a), jnp.asarray(b), block_m=32,
                     block_n=128, block_k=256, scheme=scheme)
    want = ref.matmul_ref(jnp.asarray(a), jnp.asarray(b), bk=256, scheme=scheme)
    exact = ref.matmul_exact_f64(a, b)
    scale = np.abs(exact).max()
    assert np.abs(np.asarray(got) - np.asarray(want)).max() / scale < 2e-6
    assert np.abs(np.asarray(got, np.float64) - exact).max() / scale < 2e-5


def test_kahan_matmul_beats_naive_on_long_k():
    """Long-K contraction (many tiles): compensated inter-tile accumulation
    must beat naive fp32 accumulation vs the fp64 reference."""
    rng = np.random.default_rng(9)
    m, k, n = 8, 1 << 15, 128
    a = (rng.standard_normal((m, k)) * 10).astype(np.float32)
    b = (rng.standard_normal((k, n)) * 10).astype(np.float32)
    exact = ref.matmul_exact_f64(a, b)
    kah = ops.matmul(jnp.asarray(a), jnp.asarray(b), block_m=8,
                     block_n=128, block_k=128, scheme="kahan")
    nai = ops.matmul(jnp.asarray(a), jnp.asarray(b), block_m=8,
                     block_n=128, block_k=128, scheme="naive")
    err_k = np.abs(np.asarray(kah, np.float64) - exact).max()
    err_n = np.abs(np.asarray(nai, np.float64) - exact).max()
    assert err_k <= err_n


def test_accuracy_ordering_ill_conditioned():
    a, b, exact, cond = numerics.gen_dot(8192, 1e6, seed=11)
    errs = {}
    for scheme in ("naive", "kahan", "dot2"):
        got = ops.dot(jnp.asarray(a), jnp.asarray(b), scheme=scheme, unroll=1)
        errs[scheme] = numerics.relative_error(float(got), exact)
    assert errs["dot2"] <= errs["kahan"] * 1.01 + 1e-12
    assert errs["dot2"] < 1e-4
