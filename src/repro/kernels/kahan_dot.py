"""Pallas TPU kernel for the compensated dot product — paper Fig. 1b.

TPU adaptation of the paper's SIMD kernels (DESIGN.md §2):

* The SIMD lane structure is the VPU's native (8, 128) tile; the paper's
  *unroll factor* U becomes the number of independent (8, 128) accumulator
  groups — the block processed per grid step is ``(8*U, 128)`` and every
  accumulator cell carries its own compensation term, exactly like the
  partial-sum registers in the paper's unrolled AVX loop.
* One *unit of work* = one VMEM block (the cache-line analog). HBM→VMEM
  transfers are double-buffered by the Pallas pipeline — the ECM overlap
  inversion described in DESIGN.md §7.
* The accumulation step is NOT hardcoded: the kernel body is one
  parameterized loop that calls ``scheme.mul_update`` from the
  compensation-scheme registry (``repro.kernels.schemes``) — naive,
  kahan, pairwise, dot2, and any scheme registered later, with no kernel
  edits. The final cross-lane merge uses the engine's two-sum tree.

The kernel returns the full (s, c) accumulator grids; the engine performs
the deterministic compensated merge (cheap: one (8*U, 128) tree fold per
*array*, not per block).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.schemes import CompensationScheme

LANES = 128
SUBLANES = 8


def _dot_kernel(a_ref, b_ref, s_out, c_out, s_acc, c_acc, *,
                scheme: CompensationScheme, grid_steps: int,
                compute_dtype=jnp.float32, step_dim: int = 0):
    """Shared body for the single grid (steps,) and the batched grid
    (batch, steps). Batched block refs carry a leading length-1 batch dim;
    the reshape to the scratch shape strips/restores it. ``step_dim``
    selects which grid axis is the sequential reduction."""
    g = pl.program_id(step_dim)

    @pl.when(g == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        c_acc[...] = jnp.zeros_like(c_acc)

    a = a_ref[...].reshape(s_acc.shape).astype(compute_dtype)
    b = b_ref[...].reshape(s_acc.shape).astype(compute_dtype)
    s, c = scheme.mul_update(s_acc[...], c_acc[...], a, b, g)
    s_acc[...] = s
    c_acc[...] = c

    @pl.when(g == grid_steps - 1)
    def _emit():
        s_out[...] = s_acc[...].reshape(s_out.shape)
        c_out[...] = c_acc[...].reshape(c_out.shape)


@functools.partial(jax.jit, static_argnames=("scheme", "unroll", "interpret",
                                             "compute_dtype"))
def dot_accumulators(a: jax.Array, b: jax.Array, *,
                     scheme: CompensationScheme, unroll: int = 8,
                     interpret: bool = True,
                     compute_dtype=jnp.float32,
                     ) -> Tuple[jax.Array, jax.Array]:
    """Run the blocked dot kernel; returns (s, c) accumulator grids.

    ``a``/``b`` must already be 1-D of equal length, padded by the caller to
    a multiple of ``8 * unroll * 128``. ``scheme`` is a (hashable, static)
    ``CompensationScheme`` — callers resolve names through the registry.
    ``compute_dtype`` is the accumulate dtype (engine-validated).
    """
    rows = SUBLANES * unroll
    n = a.shape[0]
    assert n % (rows * LANES) == 0, "caller must pad"
    steps = n // (rows * LANES)
    a2 = a.reshape(steps * rows, LANES)
    b2 = b.reshape(steps * rows, LANES)

    kernel = functools.partial(_dot_kernel, scheme=scheme, grid_steps=steps,
                               compute_dtype=compute_dtype)
    s, c = pl.pallas_call(
        kernel,
        name="dot_accumulators",
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda g: (g, 0)),
            pl.BlockSpec((rows, LANES), lambda g: (g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda g: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda g: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), compute_dtype),
            jax.ShapeDtypeStruct((rows, LANES), compute_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), compute_dtype),
            pltpu.VMEM((rows, LANES), compute_dtype),
        ],
        interpret=interpret,
    )(a2, b2)
    return s, c


@functools.partial(jax.jit, static_argnames=("scheme", "unroll", "interpret",
                                             "compute_dtype"))
def dot_accumulators_batched(a: jax.Array, b: jax.Array, *,
                             scheme: CompensationScheme, unroll: int = 8,
                             interpret: bool = True,
                             compute_dtype=jnp.float32,
                             ) -> Tuple[jax.Array, jax.Array]:
    """Batched dot kernel: one (batch, steps) Pallas grid.

    ``a``/``b``: [batch, n], padded by the caller to n % (8*unroll*128)
    == 0. Returns [batch, rows, LANES] (s, c) grids. The steps axis is the
    inner (sequential) grid dimension, so the VMEM scratch accumulators
    are re-initialized at step 0 of each batch row and each row executes
    the exact rounding sequence of a single ``dot_accumulators`` call —
    bitwise-equal to a Python loop of kernel calls, minus the per-call
    dispatch and pipeline drain.
    """
    rows = SUBLANES * unroll
    batch, n = a.shape
    assert n % (rows * LANES) == 0, "caller must pad"
    steps = n // (rows * LANES)
    a3 = a.reshape(batch, steps * rows, LANES)
    b3 = b.reshape(batch, steps * rows, LANES)

    kernel = functools.partial(_dot_kernel, scheme=scheme, grid_steps=steps,
                               compute_dtype=compute_dtype, step_dim=1)
    s, c = pl.pallas_call(
        kernel,
        name="dot_accumulators_batched",
        grid=(batch, steps),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda bi, g: (bi, g, 0)),
            pl.BlockSpec((1, rows, LANES), lambda bi, g: (bi, g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, LANES), lambda bi, g: (bi, 0, 0)),
            pl.BlockSpec((1, rows, LANES), lambda bi, g: (bi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, rows, LANES), compute_dtype),
            jax.ShapeDtypeStruct((batch, rows, LANES), compute_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), compute_dtype),
            pltpu.VMEM((rows, LANES), compute_dtype),
        ],
        interpret=interpret,
    )(a3, b3)
    return s, c
