"""Ahead-of-time compiles of the main path for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described, not present. These tests hand the
kernels of the main path (and one full-width olmo-1b decode tick) to it
with ``interpret=False``, so a kernel that Mosaic would refuse fails
here, on the CPU, instead of on the chip. Nothing runs: a pass says the
compiler accepts the program, nothing about results or times.

The topology is described inside a module-scoped fixture (never at
import time): only one process at a time may load the TPU library, and
a worker that describes it keeps the library until it exits.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import ops, schemes
from repro.kernels.engine import CompensatedReduction
from repro.kernels.schemes import Policy
from repro.models import build_model
from repro.serve import EngineConfig, InferenceEngine

N_VEC = 1 << 24                    # 64 MiB per f32 operand: HBM-resident
BATCH, WIDTH = 4, 1 << 20          # batched dot: 4 grid steps of 32 tiles a row
MM = 2048                          # matmul M = N = K
BH, S, DH = 16, 2048, 128          # flash: olmo-1b heads x context x dh


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(text, what):
    assert "tpu_custom_call" in text, f"{what}: no Mosaic kernel in the HLO"


@pytest.mark.parametrize("name", ["naive", "kahan"])
def test_dot_and_asum_compile_for_v5e(one_chip, name):
    x = _sds(one_chip, (N_VEC,))
    dot = functools.partial(ops.dot, scheme=name, interpret=False)
    asum = functools.partial(ops.asum, scheme=name, interpret=False)
    _assert_mosaic(_compile(dot, x, x), f"dot/{name}")
    _assert_mosaic(_compile(asum, x), f"asum/{name}")
    rows = _sds(one_chip, (BATCH, WIDTH))
    batched = functools.partial(ops.batched_dot, scheme=name, interpret=False)
    _assert_mosaic(_compile(batched, rows, rows), f"batched_dot/{name}")


def test_matmul_compiles_for_v5e(one_chip):
    a = _sds(one_chip, (MM, MM))
    mm = functools.partial(ops.matmul, scheme="kahan", interpret=False)
    _assert_mosaic(_compile(mm, a, a), "matmul/kahan")


@pytest.mark.parametrize("name", ["naive", "kahan"])
def test_flash_and_flash_chunk_compile_for_v5e(one_chip, name):
    """Both flash grids at olmo-1b attention shapes. They trace the
    shared block body unpinned (``pin=interpret``): Mosaic has no
    lowering for ``optimization_barrier``, so a regression that pins the
    compiled kernel fails this test."""
    eng = CompensatedReduction(scheme=name, interpret=False)
    q = _sds(one_chip, (BH, S, DH))
    _assert_mosaic(_compile(eng.flash_attention, q, q, q), f"flash/{name}")

    chunk = _sds(one_chip, (BH, 64, DH))
    off = _sds(one_chip, (), jnp.int32)

    def chunk_fn(q, k, v, q_off):
        return eng.flash_chunk_attention(q, k, v, q_off=q_off)

    _assert_mosaic(_compile(chunk_fn, chunk, q, q, off),
                   f"flash_chunk/{name}")


def test_flash_body_is_barrier_free_only_when_compiled():
    """The Mosaic-safe body: pinned for interpret mode and the oracle,
    unpinned for the compiled kernel."""
    run, args = fa.flash_block_probe("kahan", block_q=8, block_k=128, dh=128,
                                     kv_len=128)
    pinned = str(jax.make_jaxpr(run)(*args))
    assert "optimization_barrier" in pinned

    sch = schemes.get("kahan")

    def unpinned(*a):
        q, k, v, m, ls, lc, as_, ac, qb, kb, step = a
        return fa.flash_block_update(
            sch, q, k, v, m, ls, lc, as_, ac, qb=qb, kb=kb, step=step,
            block_q=8, block_k=128, kv_len=128, causal=True,
            scale=128 ** -0.5, pin=False)

    assert "optimization_barrier" not in str(jax.make_jaxpr(unpinned)(*args))


def test_olmo_1b_decode_tick_compiles_for_v5e(one_chip):
    """One full-width olmo-1b decode tick with ``track_stats`` (the
    telemetry norm runs the batched Pallas asum). Weights are abstract:
    only their shapes reach the compiler."""
    cfg = get_config("olmo-1b")
    model = build_model(cfg)
    params = jax.eval_shape(lambda k: model.init(k)[0], jax.random.key(0))
    engine = InferenceEngine(
        cfg, EngineConfig(max_slots=4, max_len=256, track_stats=True,
                          policy=Policy(scheme="kahan", interpret=False)),
        model=model, params=params)
    tick, args = engine.trace_tick()
    shapes = jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), args)
    text = tick.lower(*shapes).compile().as_text()
    _assert_mosaic(text, "olmo-1b decode tick")
