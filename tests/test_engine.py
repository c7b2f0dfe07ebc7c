"""CompensatedReduction engine tests.

The acceptance bar for the engine: the batched (batch, steps) Pallas grid
must be BITWISE-equal to a Python loop of single kernel calls (per mode),
and the sharded (s, c) merge must equal the single-device
``merge_accumulators`` tree on identical data.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import collectives as coll
from repro.kernels import engine, ops
from repro.kernels.engine import (
    Accumulator,
    CompensatedReduction,
    merge_accumulators,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ragged (pad-requiring) size — the block-aligned case is a strict subset
# (padding becomes a no-op) and is covered by the bf16 test at 4096
SIZES = [8 * 128 * 3 + 41]
# ragged, 256 tiles of (16, 128) at unroll 2: the dot kernel's grid steps
# each fold 128 of them
MULTI_TILE = 16 * 128 * 255 + 41


def _batch(b, n, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((b, n)).astype(dtype)),
            jnp.asarray(rng.standard_normal((b, n)).astype(dtype)))


# --- batched grid == per-call loop, bitwise ---------------------------------

@pytest.mark.parametrize("n", SIZES + [MULTI_TILE])
@pytest.mark.parametrize("scheme", ["naive", "kahan", "dot2"])
def test_batched_dot_bitwise_matches_loop(n, scheme):
    a, b = _batch(5, n, seed=n)
    got = ops.batched_dot(a, b, scheme=scheme, unroll=2)
    want = jnp.stack([ops.dot(a[i], b[i], scheme=scheme, unroll=2)
                      for i in range(a.shape[0])])
    assert np.array_equal(np.asarray(got), np.asarray(want)), scheme


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("scheme", ["naive", "kahan"])
def test_batched_asum_bitwise_matches_loop(n, scheme):
    x, _ = _batch(4, n, seed=n + 7)
    got = ops.batched_asum(x, scheme=scheme, unroll=2)
    want = jnp.stack([ops.asum(x[i], scheme=scheme, unroll=2)
                      for i in range(x.shape[0])])
    assert np.array_equal(np.asarray(got), np.asarray(want)), scheme


def test_batched_bf16_promotion_bitwise():
    """Promotion to the engine's COMPUTE_DTYPE happens once, before
    padding; batched and per-call paths promote identically."""
    a, b = _batch(3, 4096, seed=3)
    a16, b16 = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    got = ops.batched_dot(a16, b16, scheme="kahan", unroll=2)
    assert got.dtype == engine.COMPUTE_DTYPE
    want = jnp.stack([ops.dot(a16[i], b16[i], scheme="kahan", unroll=2)
                      for i in range(3)])
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [8 * 128 * 2 + 9, MULTI_TILE])
def test_vmap_dispatches_to_batched_grid(n):
    """jax.vmap of the scalar entry points must produce the batched-grid
    result (custom_vmap rule), bitwise-equal to the per-call loop."""
    a, b = _batch(4, n, seed=11)
    vd = jax.vmap(lambda x, y: ops.dot(x, y, scheme="kahan", unroll=2))(a, b)
    ld = jnp.stack([ops.dot(a[i], b[i], scheme="kahan", unroll=2)
                    for i in range(4)])
    assert np.array_equal(np.asarray(vd), np.asarray(ld))
    vs = jax.vmap(lambda x: ops.asum(x, scheme="kahan", unroll=2))(a)
    ls = jnp.stack([ops.asum(a[i], scheme="kahan", unroll=2) for i in range(4)])
    assert np.array_equal(np.asarray(vs), np.asarray(ls))


# --- accumulator pytree ------------------------------------------------------

def test_accumulator_pytree_and_combine():
    eng = CompensatedReduction(scheme="kahan", unroll=1)
    a, b = _batch(1, 4096, seed=5)
    acc1 = eng.dot_accumulators(a[0, :2048], b[0, :2048])
    acc2 = eng.dot_accumulators(a[0, 2048:], b[0, 2048:])
    assert isinstance(acc1, Accumulator)
    leaves = jax.tree.leaves(acc1)
    assert len(leaves) == 2  # (s, c) — first-class pytree
    merged = acc1.combine(acc2)
    # merged total approximates the full dot at fp32 fidelity
    full = float(eng.dot(a[0], b[0]))
    assert abs(float(merged.total()) - full) <= 1e-5 * max(abs(full), 1.0)


def test_accumulator_total_batched_is_vmap_of_tree():
    eng = CompensatedReduction(scheme="kahan", unroll=2)
    x, _ = _batch(3, 8 * 128 * 4, seed=9)
    acc = eng.batched_sum_accumulators(x)
    got = acc.total()
    want = jax.vmap(merge_accumulators)(acc.s, acc.c)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# --- interpret=None resolution ----------------------------------------------

def test_interpret_default_resolves_identically(monkeypatch):
    """interpret=None must resolve through the single engine authority for
    all three reductions (no per-wrapper re-implementation)."""
    calls = []
    real = engine.resolve_interpret

    def spy(v):
        calls.append(v)
        return real(v)

    monkeypatch.setattr(engine, "resolve_interpret", spy)
    a, b = _batch(1, 2048, seed=13)
    m = jnp.ones((16, 128), jnp.float32)
    ops.dot(a[0], b[0], interpret=None)
    ops.asum(a[0], interpret=None)
    ops.matmul(m, m.T, block_m=16, block_n=128, block_k=128, interpret=None)
    assert len(calls) >= 3 and all(v is None for v in calls)
    # and the resolved value is the documented policy
    assert real(None) == (jax.default_backend() != "tpu")
    assert real(True) is True and real(False) is False


def test_interpret_none_matches_explicit_on_cpu():
    a, b = _batch(1, 2048, seed=17)
    expect = jax.default_backend() != "tpu"
    for fn in (lambda i: ops.dot(a[0], b[0], interpret=i),
               lambda i: ops.asum(a[0], interpret=i)):
        assert float(fn(None)) == float(fn(expect))


# --- sharded merge vs single-device tree ------------------------------------

def test_merge_sharded_equals_single_device_tree():
    """Function-level contract: the gather-side fold IS the single-device
    two-sum tree on the stacked per-device grids."""
    eng = CompensatedReduction(scheme="kahan", unroll=2)
    x, _ = _batch(4, 8 * 128 * 2 * 3, seed=21)
    accs = [eng.sum_accumulators(x[i]) for i in range(4)]
    ss = jnp.stack([a.s for a in accs])
    cs = jnp.stack([a.c for a in accs])
    got = coll.merge_sharded_accumulators(ss, cs)
    want = merge_accumulators(ss, cs)
    assert float(got) == float(want)


@pytest.mark.slow  # subsumed by the 2-device subprocess test below
def test_sharded_asum_single_device_mesh():
    mesh = jax.make_mesh((1,), ("data",))
    x, _ = _batch(1, 8 * 128 * 4 + 13, seed=23)
    got = coll.sharded_asum(mesh, x[0], scheme="kahan", unroll=2)
    want = CompensatedReduction(scheme="kahan", unroll=2).asum(x[0])
    assert float(got) == float(want)


@pytest.mark.slow  # subsumed by the 2-device subprocess test below
def test_sharded_dot_single_device_mesh():
    mesh = jax.make_mesh((1,), ("data",))
    a, b = _batch(1, 5000, seed=29)
    got = coll.sharded_dot(mesh, a[0], b[0], unroll=2)
    want = CompensatedReduction(unroll=2).dot(a[0], b[0])
    assert float(got) == float(want)


_MULTIDEV_SCRIPT = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.distributed import collectives as coll
    from repro.kernels.engine import CompensatedReduction, merge_accumulators

    assert len(jax.devices()) == 2
    mesh = jax.make_mesh((2,), ("data",))
    rng = np.random.default_rng(2)
    n = 2 * (8 * 128 * 2 * 3)
    x = jnp.asarray(rng.standard_normal(n) * 1e3, jnp.float32)
    got = coll.sharded_asum(mesh, x, scheme="kahan", unroll=2)

    eng = CompensatedReduction(scheme="kahan", unroll=2)
    shards = x.reshape(2, n // 2)
    accs = [eng.sum_accumulators(shards[i]) for i in range(2)]
    ss = jnp.stack([a.s for a in accs])
    cs = jnp.stack([a.c for a in accs])
    want = merge_accumulators(ss, cs)
    assert float(got) == float(want), (float(got), float(want))
    print("OK")
""")


def test_sharded_merge_matches_single_device_on_2_devices():
    """The real cross-device check: 2 forced host devices in a subprocess
    (the device-count flag must not leak into this process). The gathered
    (s, c) grids fold to the same bits as the single-device tree; wider
    merges of stacked grids are covered at function level above."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=2").strip()
    res = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout


def test_local_process_sees_one_device():
    """The XLA_FLAGS device-count override must NOT be global."""
    import jax

    assert len(jax.devices()) == 1


# --- matmul on the engine contract -------------------------------------------

def _mm_batch(b, m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((b, m, k)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, k, n)), jnp.float32))

_MM_BLOCKS = dict(block_m=16, block_n=128, block_k=256)


def test_batched_matmul_bitwise_matches_loop_every_scheme():
    """Acceptance bar: ops.batched_matmul is bitwise-equal to a Python
    loop of ops.matmul calls for EVERY registered scheme (ragged shapes —
    the engine pads/clamps identically on both paths)."""
    from repro.kernels import schemes

    a, b = _mm_batch(3, 24, 700, 130, seed=31)
    for name in schemes.names():
        got = ops.batched_matmul(a, b, scheme=name, **_MM_BLOCKS)
        want = jnp.stack([ops.matmul(a[i], b[i], scheme=name, **_MM_BLOCKS)
                          for i in range(3)])
        assert np.array_equal(np.asarray(got), np.asarray(want)), name


def test_vmap_matmul_dispatches_to_batched_grid():
    a, b = _mm_batch(3, 24, 700, 130, seed=37)
    vm = jax.vmap(lambda x, y: ops.matmul(x, y, scheme="kahan",
                                          **_MM_BLOCKS))(a, b)
    lp = jnp.stack([ops.matmul(a[i], b[i], scheme="kahan", **_MM_BLOCKS)
                    for i in range(3)])
    assert np.array_equal(np.asarray(vm), np.asarray(lp))


def test_matmul_grad_flows_through_engine():
    """ops.matmul is differentiable (custom VJP): the backward matmuls
    run the same compensated kernel, and the result matches the plain
    fp32 matmul cotangents tightly."""
    a, b = _mm_batch(1, 16, 512, 128, seed=41)
    a, b = a[0], b[0]

    def loss(x, y):
        return jnp.sum(ops.matmul(x, y, scheme="kahan", **_MM_BLOCKS))

    da, db = jax.grad(loss, argnums=(0, 1))(a, b)
    da_ref = jnp.ones((16, 128)) @ b.T
    db_ref = a.T @ jnp.ones((16, 128))
    np.testing.assert_allclose(np.asarray(da), np.asarray(da_ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(db), np.asarray(db_ref),
                               rtol=1e-5, atol=1e-4)


def test_matmul_accumulators_are_engine_accumulators():
    """The matmul kernel emits raw (s, c) grids under the shared
    total = finalize(s, c) contract; the collapsed entry point equals
    finalize-then-slice of the producer's output."""
    a, b = _mm_batch(1, 24, 700, 130, seed=43)
    a, b = a[0], b[0]
    eng = CompensatedReduction(scheme="dot2", blocks=(16, 128, 256))
    acc = eng.matmul_accumulators(a, b)
    assert isinstance(acc, Accumulator)
    want = eng.scheme.finalize(acc.s, acc.c)[:24, :130]
    got = eng.matmul(a, b)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_sharded_matmul_single_device_matches_merge():
    """Gather-side contract: sharded_matmul == merge_accumulator_grids of
    the stacked per-device (s, c) grids (1-device mesh; the 2-device
    run is pinned by the slow-tier subprocess test below)."""
    from repro.kernels.engine import merge_accumulator_grids

    mesh = jax.make_mesh((1,), ("data",))
    a, b = _mm_batch(1, 24, 512, 130, seed=47)
    a, b = a[0], b[0]
    got = coll.sharded_matmul(mesh, a, b, scheme="kahan", **_MM_BLOCKS)
    eng = CompensatedReduction(scheme="kahan", blocks=(16, 128, 256))
    acc = eng.matmul_accumulators(a, b)
    want = merge_accumulator_grids(acc.s[None], acc.c[None])[:24, :130]
    assert np.array_equal(np.asarray(got), np.asarray(want))


_MULTIDEV_MATMUL_SCRIPT = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.distributed import collectives as coll
    from repro.kernels.engine import (CompensatedReduction,
                                      merge_accumulator_grids)

    assert len(jax.devices()) == 2
    mesh = jax.make_mesh((2,), ("data",))
    rng = np.random.default_rng(5)
    m, k, n = 24, 1024, 130
    a = jnp.asarray(rng.standard_normal((m, k)) * 1e2, jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)) * 1e2, jnp.float32)
    got = coll.sharded_matmul(mesh, a, b, scheme="kahan", block_m=16,
                              block_n=128, block_k=256)

    eng = CompensatedReduction(scheme="kahan", blocks=(16, 128, 256))
    accs = [eng.matmul_accumulators(a[:, i*(k//2):(i+1)*(k//2)],
                                    b[i*(k//2):(i+1)*(k//2)])
            for i in range(2)]
    ss = jnp.stack([acc.s for acc in accs])
    cs = jnp.stack([acc.c for acc in accs])
    want = merge_accumulator_grids(ss, cs)[:m, :n]
    assert np.array_equal(np.asarray(got), np.asarray(want))
    print("OK")
""")


@pytest.mark.slow
def test_sharded_matmul_matches_device_major_merge_on_2_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=2").strip()
    res = subprocess.run([sys.executable, "-c", _MULTIDEV_MATMUL_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout
