"""Pallas TPU kernel: matmul with compensated inter-tile accumulation.

This is the TPU analog of the paper's "FMA with unit multiplicand" trick
(§4): the MXU performs the per-tile multiply-(fp32-)accumulate — error-free
enough *within* a (bm, bk)x(bk, bn) tile thanks to fp32 accumulation — and
the VPU applies the registered scheme's update when folding successive
K-tiles into the output accumulator. The long K-dimension reduction is
where fp32 accumulation error grows with K; compensation bounds it
independent of K (O(eps) instead of O(K*eps)).

Use case in the framework: long-context attention score@V contractions and
the vocab-dim logit matmul accumulate over K = seq_len or K = d_model
tiles; the engine's ``matmul`` is the drop-in used by the compensated
serving path and (via ``ArchConfig.kahan_matmul``) the model projections.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics — sequential),
M/N parallel; the batched variant prepends a leading batch grid dimension
(batch, M/bm, N/bn, K/bk), so per batch index the kernel executes the
identical rounding sequence as a single call — bitwise-equal to a Python
loop. Accumulators (s, c) live in VMEM scratch, one pair per (bm, bn)
output tile; they are re-initialized whenever k == 0. The per-K-tile fold
is ``scheme.update`` from the compensation-scheme registry (any registered
scheme works; the tile *product* is always the MXU's dot in the engine's
compute dtype, so ``mul_update`` does not apply here).

Engine contract: the kernels EMIT the raw ``(s, c)`` accumulator grids —
finalization (``scheme.finalize``, i.e. ``s + c``) happens in the engine,
which also owns padding, compute-dtype promotion, and block clamping
(``CompensatedReduction.matmul`` / ``batched_matmul`` /
``matmul_accumulators``). Callers go through the engine (or ``ops.*``),
not this module directly. Keeping the pair un-collapsed at the kernel
boundary is what lets ``distributed.collectives.sharded_matmul``
all-gather per-device grids and fold them device-major with the two-sum
tree instead of a ``psum``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.schemes import CompensationScheme


def _matmul_kernel(a_ref, b_ref, s_out, c_out, s_acc, c_acc, *,
                   scheme: CompensationScheme, k_steps: int,
                   compute_dtype=jnp.float32, step_dim: int = 2):
    """Shared body for the single (Mb, Nb, Kb) and batched
    (batch, Mb, Nb, Kb) grids. Batched block refs carry a leading
    length-1 batch dim; the reshape to the scratch shape strips/restores
    it. ``step_dim`` selects the sequential K grid axis."""
    k = pl.program_id(step_dim)

    @pl.when(k == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        c_acc[...] = jnp.zeros_like(c_acc)

    a = a_ref[...].reshape(s_acc.shape[0], -1).astype(compute_dtype)
    b = b_ref[...].reshape(-1, s_acc.shape[1]).astype(compute_dtype)
    # HIGHEST: on a TPU the default contraction precision rounds f32
    # operands to one bf16 pass, far outside the compute dtype's bound
    prod = jnp.dot(a, b, preferred_element_type=compute_dtype,  # contract: allow-no-uncompensated-reduction(block inner product; the scheme.update fold below carries the compensation)
                   precision=jax.lax.Precision.HIGHEST)
    s, c = scheme.update(s_acc[...], c_acc[...], prod, k)
    s_acc[...] = s
    c_acc[...] = c

    @pl.when(k == k_steps - 1)
    def _emit():
        s_out[...] = s_acc[...].reshape(s_out.shape)
        c_out[...] = c_acc[...].reshape(c_out.shape)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "scheme", "interpret",
                     "compute_dtype"))
def matmul_accumulators(a: jax.Array, b: jax.Array, *,
                        scheme: CompensationScheme,
                        block_m: int = 256, block_n: int = 256,
                        block_k: int = 512, interpret: bool = True,
                        compute_dtype=jnp.float32,
                        ) -> Tuple[jax.Array, jax.Array]:
    """Blocked matmul kernel; returns the full (s, c) grids, each [M, N].

    Caller (the engine) must pad M, N, K to multiples of the block sizes
    (zero padding is exact for every scheme) and pass a resolved
    ``CompensationScheme``. ``finalize(s, c) = s + c`` is the caller's job.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    grid = (m // block_m, n // block_n, k // block_k)

    kernel = functools.partial(_matmul_kernel, scheme=scheme,
                               k_steps=grid[2], compute_dtype=compute_dtype)
    s, c = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
            pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), compute_dtype),
            jax.ShapeDtypeStruct((m, n), compute_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), compute_dtype),
            pltpu.VMEM((block_m, block_n), compute_dtype),
        ],
        interpret=interpret,
    )(a, b)
    return s, c


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "scheme", "interpret",
                     "compute_dtype"))
def matmul_accumulators_batched(a: jax.Array, b: jax.Array, *,
                                scheme: CompensationScheme,
                                block_m: int = 256, block_n: int = 256,
                                block_k: int = 512, interpret: bool = True,
                                compute_dtype=jnp.float32,
                                ) -> Tuple[jax.Array, jax.Array]:
    """Batched matmul kernel: ONE (batch, Mb, Nb, Kb) Pallas grid.

    ``a``: [batch, M, K]; ``b``: [batch, K, N], padded like the single
    kernel. Returns [batch, M, N] (s, c) grids. K stays the innermost
    (sequential) grid dimension, so the scratch accumulators re-initialize
    at k == 0 of every (batch, i, j) tile and each batch index executes
    the exact rounding sequence of a single ``matmul_accumulators`` call —
    bitwise-equal to a Python loop of kernel calls.
    """
    batch, m, k = a.shape
    b2, k2, n = b.shape
    assert batch == b2 and k == k2
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    grid = (batch, m // block_m, n // block_n, k // block_k)

    kernel = functools.partial(_matmul_kernel, scheme=scheme,
                               k_steps=grid[3], compute_dtype=compute_dtype,
                               step_dim=3)
    s, c = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k),
                         lambda bi, i, j, kk: (bi, i, kk)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda bi, i, j, kk: (bi, kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, block_n),
                         lambda bi, i, j, kk: (bi, i, j)),
            pl.BlockSpec((1, block_m, block_n),
                         lambda bi, i, j, kk: (bi, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, m, n), compute_dtype),
            jax.ShapeDtypeStruct((batch, m, n), compute_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), compute_dtype),
            pltpu.VMEM((block_m, block_n), compute_dtype),
        ],
        interpret=interpret,
    )(a, b)
    return s, c
