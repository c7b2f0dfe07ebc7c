"""Pallas TPU kernel for the compensated dot product — paper Fig. 1b.

TPU adaptation of the paper's SIMD kernels (DESIGN.md §2):

* The SIMD lane structure is the VPU's native (8, 128) tile; the paper's
  *unroll factor* U becomes the number of independent (8, 128) accumulator
  groups. The ``(8*U, 128)`` accumulator tile is the unit of the
  rounding sequence and of the caller's padding: every accumulator cell
  carries its own compensation term, exactly like the partial-sum
  registers in the paper's unrolled AVX loop.
* A grid step streams ``T`` consecutive such tiles, a ``(T*8*U, 128)``
  block per operand, and folds them in order with the accumulators held
  in registers. ``T`` follows from the shape alone (``_tiles_per_step``):
  the largest divisor of the tile count whose block stays within
  ``_BLOCK_BYTES``, so a large block amortizes the fixed cost of a grid
  step while the tile sequence, and so the rounding, is that of one tile
  per step. HBM→VMEM transfers are double-buffered by the Pallas
  pipeline — the ECM overlap inversion described in DESIGN.md §7.
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

#: Most bytes of one operand that a grid step streams. On a v5e the dot
#: at 2^27 reads 91.3-91.4% of HBM bandwidth from 512 KiB to 2 MiB (90%
#: at 256 KiB); two operands, double-buffered, take 4x this in VMEM, and
#: 4 MiB overflows the default scoped 16 MiB.
_BLOCK_BYTES = 1 << 20


def _tiles_per_step(steps: int, tile_bytes: int) -> int:
    """T: the largest divisor of ``steps`` (the count of accumulator
    tiles) whose block of T tiles takes at most ``_BLOCK_BYTES``; 1 when
    even one tile is larger. A divisor, so the grid covers the padded
    input exactly and no input is padded further."""
    cap = max(1, _BLOCK_BYTES // tile_bytes)
    return max(t for t in range(1, min(steps, cap) + 1) if steps % t == 0)


def _dot_kernel(a_ref, b_ref, s_out, c_out, s_acc, c_acc, *,
                scheme: CompensationScheme, grid_steps: int, tiles: int,
                compute_dtype=jnp.float32, step_dim: int = 0):
    """Shared body for the single grid (steps,) and the batched grid
    (batch, steps). Batched block refs carry a leading length-1 batch dim;
    the reshape to the scratch shape strips/restores it. ``step_dim``
    selects which grid axis is the sequential reduction.

    A step folds its ``tiles`` accumulator tiles in order, tile k of grid
    step g under the step index ``g * tiles + k``: the update sequence of
    a grid of one tile per step, with (s, c) loaded and stored once."""
    g = pl.program_id(step_dim)
    rows = s_acc.shape[0]

    @pl.when(g == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        c_acc[...] = jnp.zeros_like(c_acc)

    def fold(k, sc):
        r = pl.ds(pl.multiple_of(k * rows, rows), rows)
        a = a_ref[..., r, :].reshape(s_acc.shape).astype(compute_dtype)
        b = b_ref[..., r, :].reshape(s_acc.shape).astype(compute_dtype)
        return scheme.mul_update(*sc, a, b, g * tiles + k)

    # int32 bounds keep the step index int32, as program_id is, under x64
    s, c = jax.lax.fori_loop(jnp.int32(0), jnp.int32(tiles), fold,
                             (s_acc[...], c_acc[...]))
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
    tiles = _tiles_per_step(steps, rows * LANES * a.dtype.itemsize)
    a2 = a.reshape(steps * rows, LANES)
    b2 = b.reshape(steps * rows, LANES)

    kernel = functools.partial(_dot_kernel, scheme=scheme,
                               grid_steps=steps // tiles, tiles=tiles,
                               compute_dtype=compute_dtype)
    s, c = pl.pallas_call(
        kernel,
        name="dot_accumulators",
        grid=(steps // tiles,),
        in_specs=[
            pl.BlockSpec((tiles * rows, LANES), lambda g: (g, 0)),
            pl.BlockSpec((tiles * rows, LANES), lambda g: (g, 0)),
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
    tiles = _tiles_per_step(steps, rows * LANES * a.dtype.itemsize)
    a3 = a.reshape(batch, steps * rows, LANES)
    b3 = b.reshape(batch, steps * rows, LANES)

    kernel = functools.partial(_dot_kernel, scheme=scheme,
                               grid_steps=steps // tiles, tiles=tiles,
                               compute_dtype=compute_dtype, step_dim=1)
    s, c = pl.pallas_call(
        kernel,
        name="dot_accumulators_batched",
        grid=(batch, steps // tiles),
        in_specs=[
            pl.BlockSpec((1, tiles * rows, LANES), lambda bi, g: (bi, g, 0)),
            pl.BlockSpec((1, tiles * rows, LANES), lambda bi, g: (bi, g, 0)),
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
