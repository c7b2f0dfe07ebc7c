"""Collective-layer utilities.

Most collectives in this framework are implicit — pjit + GSPMD inserts
all-gather/reduce-scatter/all-to-all from the sharding specs, and the XLA
latency-hiding scheduler overlaps them with compute (enabled via the flags
in launch/train.py). What lives here is the *explicitly managed* layer:

* ``sharded_asum`` / ``sharded_dot`` — the engine's sharded path: each
  device runs the compensated Pallas kernel over its local shard, the
  per-device ``(s, c)`` accumulator grids are all-gathered, and ONE
  deterministic two-sum tree (``engine.merge_accumulators``, device-major
  order) collapses them — never a plain ``psum``, whose reduction order
  the backend may re-associate run to run.
* ``sharded_matmul`` — the grid-shaped member of that family: the K
  (contraction) axis is sharded, each device runs the engine's matmul
  kernel over its K-slice and emits per-device ``(s, c)`` OUTPUT-TILE
  grids, which are all-gathered and folded device-major through the same
  two-sum tree (``engine.merge_accumulator_grids`` — elementwise over
  the [M, N] tile) — again, never a ``psum``.
* ``merge_sharded_accumulators`` — that gather-side fold, exposed
  separately so tests can check it against the single-device merge on
  identical data.
* ``deterministic_mean`` — shard_map wrapper around the core compensated
  scalar reduction (bitwise run-to-run reproducible metrics regardless of
  reduction order).
* ``reduce_scatter_grads`` — spec helper: gradients of FSDP-sharded params
  should be produced reduce-scattered, not all-reduced; under pjit this is
  expressed through the output shardings (grads inherit param specs), so
  the helper just documents/validates that wiring.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.kahan import compensated_psum_scalar, kahan_step
from repro.kernels.engine import (
    Accumulator,
    CompensatedReduction,
    SchemeSpec,
    merge_accumulator_grids,
    merge_accumulators,
)


# ---------------------------------------------------------------------------
# Sharded compensated reductions (the engine's cross-device path)
# ---------------------------------------------------------------------------

def merge_sharded_accumulators(s_gathered: jax.Array, c_gathered: jax.Array,
                               ) -> jax.Array:
    """Collapse all-gathered per-device accumulator grids to one scalar.

    ``s_gathered``/``c_gathered``: [n_dev, rows, lanes] in device-major
    order (the order ``all_gather`` fixes). The fold IS the single-device
    two-sum tree on the stacked grids — so the sharded result equals
    ``merge_accumulators`` run on the same data on one device, and is
    independent of any backend reduction-order choice.
    """
    return merge_accumulators(s_gathered, c_gathered)


def _sharded_reduce(axis: str, local_accumulate):
    """shard_map body shared by sharded_asum / sharded_dot: run the
    local kernel, all-gather the (s, c) grids, tree-fold in device order."""

    def reduce(*shards):
        acc: Accumulator = local_accumulate(*shards)
        ss = jax.lax.all_gather(acc.s, axis)   # [n_dev, rows, lanes]
        cs = jax.lax.all_gather(acc.c, axis)
        return merge_sharded_accumulators(ss, cs)

    return reduce


def sharded_asum(mesh: Mesh, x: jax.Array, *, axis: str = "data",
                 scheme: SchemeSpec = None, unroll: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 compute_dtype=None) -> jax.Array:
    """Compensated sum of an array sharded over one mesh axis.

    Per-device: the engine's Pallas sum kernel over the local shard.
    Cross-device: all-gather of the (s, c) grids + the deterministic
    two-sum tree — NOT a psum. Returns a replicated compute-dtype scalar
    that is bitwise reproducible for a fixed mesh size. ``scheme`` is any
    registered compensation scheme / a Policy (None -> ambient policy);
    ``compute_dtype`` overrides the policy's accumulate dtype.
    """
    eng = CompensatedReduction(scheme=scheme, unroll=unroll,
                               interpret=interpret,
                               compute_dtype=compute_dtype)
    reduce = _sharded_reduce(axis, eng.sum_accumulators)
    return jax.shard_map(reduce, mesh=mesh, in_specs=P(axis),
                         out_specs=P(), check_vma=False)(x)


def sharded_dot(mesh: Mesh, a: jax.Array, b: jax.Array, *,
                axis: str = "data", scheme: SchemeSpec = None,
                unroll: Optional[int] = None,
                interpret: Optional[bool] = None,
                compute_dtype=None) -> jax.Array:
    """Compensated dot of two identically-sharded 1-D arrays (see
    ``sharded_asum`` for the merge and scheme-resolution semantics)."""
    eng = CompensatedReduction(scheme=scheme, unroll=unroll,
                               interpret=interpret,
                               compute_dtype=compute_dtype)
    reduce = _sharded_reduce(axis, eng.dot_accumulators)
    return jax.shard_map(reduce, mesh=mesh, in_specs=(P(axis), P(axis)),
                         out_specs=P(), check_vma=False)(a, b)


def sharded_matmul(mesh: Mesh, a: jax.Array, b: jax.Array, *,
                   axis: str = "data", scheme: SchemeSpec = None,
                   block_m: Optional[int] = None,
                   block_n: Optional[int] = None,
                   block_k: Optional[int] = None,
                   interpret: Optional[bool] = None,
                   compute_dtype=None) -> jax.Array:
    """C = A @ B with the K (contraction) axis sharded over ``axis``.

    ``a``: [M, K] sharded on its second dim; ``b``: [K, N] sharded on its
    first dim (K must divide by the axis size). Per-device: the engine's
    matmul kernel over the local K-slice, emitting the raw per-output-tile
    ``(s, c)`` accumulator grids. Cross-device: all-gather of those grids
    and a device-major elementwise two-sum tree
    (``engine.merge_accumulator_grids``) — NEVER a ``psum``, so the
    result is bitwise reproducible for a fixed mesh size. Returns the
    replicated [M, N] product in the compute dtype.
    """
    eng = CompensatedReduction(scheme=scheme, interpret=interpret,
                               compute_dtype=compute_dtype)
    m, n = a.shape[0], b.shape[1]

    def reduce(a_shard, b_shard):
        acc: Accumulator = eng.matmul_accumulators(
            a_shard, b_shard, block_m=block_m, block_n=block_n,
            block_k=block_k)
        ss = jax.lax.all_gather(acc.s, axis)   # [n_dev, M_pad, N_pad]
        cs = jax.lax.all_gather(acc.c, axis)
        return merge_accumulator_grids(ss, cs)[:m, :n]

    return jax.shard_map(reduce, mesh=mesh,
                         in_specs=(P(None, axis), P(axis, None)),
                         out_specs=P(), check_vma=False)(a, b)


# ---------------------------------------------------------------------------
# Scalar metric reductions
# ---------------------------------------------------------------------------

def deterministic_mean(mesh: Mesh, values: jax.Array, axis: str = "data",
                       ) -> jax.Array:
    """Bitwise-deterministic mean of per-device scalars over one mesh axis.

    Gathers the (value, comp) pairs and folds them in device order with
    two-sum — the distributed form of the paper's compensated reduction.
    """
    @jax.shard_map(mesh=mesh, in_specs=P(axis), out_specs=P(),
                   check_vma=False)  # fold result replicated by construction
    def reduce(v):
        s, c = kahan_step(jnp.zeros(()), jnp.zeros(()), v[0])
        rs, rc = compensated_psum_scalar(s, c, axis)
        return (rs + rc) / mesh.shape[axis]

    return reduce(values)


def expected_grad_spec(param_spec: P) -> P:
    """Gradients share their parameter's sharding (ZeRO: the reduce-scatter
    is implied by emitting grads in the param's FSDP-sharded spec)."""
    return param_spec
