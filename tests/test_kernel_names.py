"""Every Pallas kernel carries an explicit, stable name.

The name is the Mosaic kernel's ``kernel_name`` and the HLO instruction's
name, so a device trace shows ``%dot_accumulators.N`` and so on whatever
the kernel body's Python function is called; the benchmark's readers
match on it (``dot_roofline`` reads ``^%dot_accumulators``). Lowering
for the TPU platform needs no chip and no TPU library: it runs on the
CPU.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.engine import CompensatedReduction

F32 = jnp.float32
VEC = jax.ShapeDtypeStruct((1 << 14,), F32)
ROWS = jax.ShapeDtypeStruct((4, 1 << 14), F32)
MAT = jax.ShapeDtypeStruct((256, 256), F32)
MATS = jax.ShapeDtypeStruct((2, 256, 256), F32)
QKV = jax.ShapeDtypeStruct((4, 256, 128), F32)
CHUNK = jax.ShapeDtypeStruct((4, 64, 128), F32)
OFF = jax.ShapeDtypeStruct((), jnp.int32)


def _flash(q, k, v):
    return CompensatedReduction(scheme="kahan", interpret=False) \
        .flash_attention(q, k, v)


def _flash_chunk(q, k, v, q_off):
    return CompensatedReduction(scheme="kahan", interpret=False) \
        .flash_chunk_attention(q, k, v, q_off=q_off)


def _entry(fn, **kw):
    return functools.partial(fn, scheme="kahan", interpret=False, **kw)


CASES = {
    "dot_accumulators": (_entry(ops.dot), (VEC, VEC)),
    "dot_accumulators_batched": (_entry(ops.batched_dot), (ROWS, ROWS)),
    "sum_accumulators": (_entry(ops.asum), (VEC,)),
    "sum_accumulators_batched": (_entry(ops.batched_asum), (ROWS,)),
    "matmul_accumulators": (_entry(ops.matmul), (MAT, MAT)),
    "matmul_accumulators_batched": (_entry(ops.batched_matmul), (MATS, MATS)),
    "flash_accumulators": (_flash, (QKV, QKV, QKV)),
    "flash_chunk_accumulators": (_flash_chunk, (CHUNK, QKV, QKV, OFF)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_lowered_kernel_carries_its_name(name):
    fn, shapes = CASES[name]
    text = jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()
    assert re.findall(r'kernel_name = "([^"]*)"', text) == [name]
