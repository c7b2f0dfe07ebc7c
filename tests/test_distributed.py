"""Distribution-layer tests: sharding rule mapping (shape-aware
degradation), compression codec + error feedback, compensated
cross-device reductions."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.distributed import compression as comp


# --- sharding rules ---------------------------------------------------------

def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_physical_spec_basic():
    mesh = _mesh11()
    spec = shd.physical_spec(mesh, shd.TRAIN_RULES, P("embed", "mlp"),
                             (128, 256))
    # axis size 1 -> mapping dropped (replicated is equivalent)
    assert spec == P()


def test_physical_spec_divisibility_degrades():
    mesh = jax.make_mesh((1,), ("model",))
    rules = shd.Rules("t", {"heads": "model"})
    # heads=5 on 1-way axis -> fine but size 1 -> dropped
    assert shd.physical_spec(mesh, rules, P("heads"), (5,)) == P()


def test_physical_spec_absent_axis_dropped():
    mesh = _mesh11()  # no "pod" axis
    spec = shd.physical_spec(mesh, shd.TRAIN_RULES, P("batch", None),
                             (8, 16))
    assert spec == P()  # ("pod","data") -> ("data",) -> size 1 -> dropped


def test_physical_spec_no_axis_reuse():
    import types

    rules = shd.Rules("t", {"a": "model", "b": "model"})
    mesh = types.SimpleNamespace(shape={"model": 2})  # duck-typed 2-way mesh

    spec = shd.physical_spec(mesh, rules, P("a", "b"), (4, 4))
    # second use of "model" must be dropped
    assert spec in (P("model"), P("model", None))


def test_physical_spec_divisibility_with_real_axis():
    import types

    mesh = types.SimpleNamespace(shape={"model": 16})
    rules = shd.Rules("t", {"heads": "model", "kv_seq": "model"})
    # 25 heads don't divide 16 -> replicated
    assert shd.physical_spec(mesh, rules, P("heads"), (25,)) == P()
    # 32768 kv positions do
    assert shd.physical_spec(mesh, rules, P(None, "kv_seq"),
                             (4, 32768)) == P(None, "model")


def test_constrain_is_noop_outside_context():
    x = jnp.ones((4, 4))
    y = shd.constrain(x, "batch", None)
    assert y is x


# --- compression ------------------------------------------------------------

def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(1024), jnp.float32)
    scale = jnp.max(jnp.abs(g))
    q = comp.quantize(g, scale)
    deq = comp.dequantize(q, scale)
    assert q.dtype == jnp.int8
    assert float(jnp.max(jnp.abs(deq - g))) <= float(scale) / 127.0 + 1e-6


def test_error_feedback_accumulates_residual():
    rng = np.random.default_rng(1)
    grads = {"w": jnp.asarray(rng.standard_normal(512), jnp.float32)}
    errors = jax.tree.map(jnp.zeros_like, grads)
    qt, errors = comp.ef_step(grads, errors)
    (q, scale) = qt["w"]
    deq = comp.dequantize(q, scale)
    np.testing.assert_allclose(np.asarray(deq + errors["w"]),
                               np.asarray(grads["w"]), rtol=0, atol=1e-6)


def test_error_feedback_converges_where_plain_quant_stalls():
    """SGD on a quadratic with tiny gradients: int8 quantization alone
    rounds small grads to zero; error feedback accumulates them."""
    target = jnp.asarray(np.linspace(-1, 1, 64), jnp.float32)
    w_ef = jnp.zeros((64,))
    w_pq = jnp.zeros((64,))
    err = jnp.zeros((64,))
    big = jnp.zeros((64,)).at[0].set(100.0)  # one huge coordinate
    lr = 0.05
    for _ in range(400):
        g_ef = (w_ef - target) + big * 0  # plain quadratic grads
        g_pq = (w_pq - target)
        # shared scale dominated by an artificial large component
        scale = jnp.float32(50.0)
        corrected = g_ef + err
        q = comp.dequantize(comp.quantize(corrected, scale), scale)
        err = corrected - q
        w_ef = w_ef - lr * q
        w_pq = w_pq - lr * comp.dequantize(comp.quantize(g_pq, scale), scale)
    assert float(jnp.mean(jnp.abs(w_ef - target))) < 0.05
    assert float(jnp.mean(jnp.abs(w_pq - target))) > \
        float(jnp.mean(jnp.abs(w_ef - target)))


def test_compressed_psum_single_device():
    mesh = jax.make_mesh((1,), ("data",))

    @jax.jit
    def run(x):
        return jax.shard_map(
            lambda v: comp.compressed_psum(v, "data"),
            mesh=mesh, in_specs=P(), out_specs=P())(x)

    x = jnp.asarray([1.0, -2.0, 3.0], jnp.float32)
    np.testing.assert_allclose(np.asarray(run(x)), np.asarray(x), atol=0.05)


def test_deterministic_mean_single_device():
    from repro.distributed.collectives import deterministic_mean

    mesh = jax.make_mesh((1,), ("data",))
    v = jnp.asarray([3.5], jnp.float32)
    out = deterministic_mean(mesh, v, axis="data")
    assert float(out) == 3.5


def test_compensated_psum_scalar_single_device():
    from repro.core.kahan import compensated_psum_scalar

    mesh = jax.make_mesh((1,), ("data",))

    @jax.jit
    def run(s, c):
        return jax.shard_map(
            lambda a, b: compensated_psum_scalar(a[0], b[0], "data"),
            mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)(  # fold result is replicated by construction
                s[None], c[None])

    s, c = run(jnp.float32(1e8), jnp.float32(1.0))
    assert float(s) + float(c) == 1e8 + 1.0
