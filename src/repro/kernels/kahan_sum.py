"""Pallas TPU kernel for compensated array summation (single-stream dot).

Same accumulator structure as ``kahan_dot`` with one input stream; the
accumulation step is ``scheme.update`` from the compensation-scheme
registry, so every registered scheme (naive / kahan / pairwise / dot2 /
custom) works here with no kernel edits. Used for loss/metric
accumulation and as the building block of the compensated cross-entropy.
See kahan_dot.py for the design notes; unlike the dot, a grid step here
still streams a single (8*U, 128) tile.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.kahan_dot import LANES, SUBLANES
from repro.kernels.schemes import CompensationScheme


def _sum_kernel(x_ref, s_out, c_out, s_acc, c_acc, *,
                scheme: CompensationScheme, grid_steps: int,
                compute_dtype=jnp.float32, step_dim: int = 0):
    """Shared body for the single (steps,) and batched (batch, steps)
    grids — see ``kahan_dot._dot_kernel`` for the reshape convention."""
    g = pl.program_id(step_dim)

    @pl.when(g == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        c_acc[...] = jnp.zeros_like(c_acc)

    x = x_ref[...].reshape(s_acc.shape).astype(compute_dtype)
    s, c = scheme.update(s_acc[...], c_acc[...], x, g)
    s_acc[...] = s
    c_acc[...] = c

    @pl.when(g == grid_steps - 1)
    def _emit():
        s_out[...] = s_acc[...].reshape(s_out.shape)
        c_out[...] = c_acc[...].reshape(c_out.shape)


@functools.partial(jax.jit, static_argnames=("scheme", "unroll", "interpret",
                                             "compute_dtype"))
def sum_accumulators(x: jax.Array, *, scheme: CompensationScheme,
                     unroll: int = 8, interpret: bool = True,
                     compute_dtype=jnp.float32,
                     ) -> Tuple[jax.Array, jax.Array]:
    """Run the blocked sum kernel; returns (s, c) accumulator grids."""
    rows = SUBLANES * unroll
    n = x.shape[0]
    assert n % (rows * LANES) == 0, "caller must pad"
    steps = n // (rows * LANES)
    x2 = x.reshape(steps * rows, LANES)

    kernel = functools.partial(_sum_kernel, scheme=scheme, grid_steps=steps,
                               compute_dtype=compute_dtype)
    s, c = pl.pallas_call(
        kernel,
        name="sum_accumulators",
        grid=(steps,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda g: (g, 0))],
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
    )(x2)
    return s, c


@functools.partial(jax.jit, static_argnames=("scheme", "unroll", "interpret",
                                             "compute_dtype"))
def sum_accumulators_batched(x: jax.Array, *, scheme: CompensationScheme,
                             unroll: int = 8, interpret: bool = True,
                             compute_dtype=jnp.float32,
                             ) -> Tuple[jax.Array, jax.Array]:
    """Batched sum kernel: one (batch, steps) Pallas grid.

    ``x``: [batch, n] padded to n % (8*unroll*128) == 0. Returns
    [batch, rows, LANES] (s, c) grids; each batch row executes the exact
    rounding sequence of a single ``sum_accumulators`` call (see
    ``kahan_dot.dot_accumulators_batched``).
    """
    rows = SUBLANES * unroll
    batch, n = x.shape
    assert n % (rows * LANES) == 0, "caller must pad"
    steps = n // (rows * LANES)
    x3 = x.reshape(batch, steps * rows, LANES)

    kernel = functools.partial(_sum_kernel, scheme=scheme, grid_steps=steps,
                               compute_dtype=compute_dtype, step_dim=1)
    s, c = pl.pallas_call(
        kernel,
        name="sum_accumulators_batched",
        grid=(batch, steps),
        in_specs=[pl.BlockSpec((1, rows, LANES), lambda bi, g: (bi, g, 0))],
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
    )(x3)
    return s, c
