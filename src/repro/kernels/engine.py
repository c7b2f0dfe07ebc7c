"""Unified compensated-reduction engine.

One accumulator contract for every compensated reduction in the repo
(dot / asum / matmul, single, batched, and sharded):

    total = s + c            (the ``kahan_step`` sign convention)
    merge = two-sum tree     (``merge_accumulators``: pairwise fold in a
                              fixed order — deterministic, associativity-
                              free, robust to magnitude inversion)

The *variant axis* (which accumulation scheme runs per block) is owned by
the ``repro.kernels.schemes`` registry: ``CompensatedReduction`` resolves
a scheme name / ``CompensationScheme`` / ``Policy`` ONCE at construction
(unknown names fail fast with the registered menu) and hands the resolved
scheme object to the kernels as a static argument. (The legacy ``mode``
alias was removed — see the migration note in ``repro.kernels.schemes``.)

``CompensatedReduction`` owns the three policies the kernel wrappers used
to re-implement independently:

* **promotion** — inputs are promoted to ``COMPUTE_DTYPE`` (fp32) exactly
  once, *before* padding, so fp16/bf16 inputs don't allocate an extra
  low-precision padded copy and the compute dtype is stated in one place.
  Results are always fp32; the kernels' per-block ``astype`` is a no-op.
* **padding / blocking** — 1-D streams are zero-padded (exact: adding
  0.0 is error-free for finite accumulators) to the kernel block
  ``SUBLANES * unroll * LANES``; matmul pads M/N/K to block multiples.
* **merge** — accumulator grids collapse through the same two-sum tree
  everywhere: cross-lane (here), cross-batch-element (``vmap`` of the
  same tree), cross-device (``repro.distributed.collectives`` gathers
  per-device ``(s, c)`` grids and folds them through this very function).

Unset knobs (scheme/unroll/blocks/interpret = None) resolve from the
ambient ``schemes.use_policy`` default. ``interpret=None`` resolution
(interpret mode off only on a real TPU backend) is hoisted here too —
``resolve_interpret`` is the single authority for dot, asum, and matmul.

Batched variants (``batched_dot`` / ``batched_asum`` / ``batched_matmul``)
lay a ``[batch, ...]`` problem out as ONE Pallas grid with a leading batch
dimension instead of a Python loop of kernel calls; per batch row the
kernel executes the identical rounding sequence, so results are
bitwise-equal to the per-call loop. ``jax.vmap`` of the scalar entry
points (and of ``matmul``) dispatches to the batched grid through a
``jax.custom_batching.custom_vmap`` rule.

``Policy.compute_dtype`` threads through here: the engine resolves it
once (fp32 default; f64 needs x64; bf16 is the bf16-accumulate axis),
promotes inputs to it, and hands it to every kernel body and oracle as a
static argument — one accumulate-dtype authority for dot / asum / matmul
/ flash attention.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import tree_util

from repro.core import kahan as K
from repro.kernels import flash_attention as _fa
from repro.kernels import kahan_dot as _kd
from repro.kernels import kahan_matmul as _km
from repro.kernels import kahan_sum as _ks
from repro.kernels import schemes as _schemes
from repro.kernels.schemes import CompensationScheme, Policy

#: default accumulate dtype (the resolved per-engine value may differ —
#: ``CompensatedReduction.compute_dtype`` is the per-call authority).
COMPUTE_DTYPE = jnp.float32

LANES = _kd.LANES
SUBLANES = _kd.SUBLANES

SchemeSpec = Union[str, CompensationScheme, Policy, None]


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Single authority for ``interpret=None``: Mosaic only on a real TPU
    backend, interpret mode everywhere else. Shared by dot/asum/matmul."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# ---------------------------------------------------------------------------
# Accumulator pytree
# ---------------------------------------------------------------------------

@tree_util.register_pytree_node_class
@dataclasses.dataclass
class Accumulator:
    """A compensated accumulator grid: ``total = s + c`` elementwise.

    Shapes: ``[rows, lanes]`` for single reductions, ``[batch, rows,
    lanes]`` for batched ones. First-class pytree so it can cross jit /
    scan / shard_map boundaries and be all-gathered per device. NOTE:
    ``total()`` treats a 3-D grid as *batched* (one total per leading
    index); for device-gathered ``[n_dev, rows, lanes]`` grids that must
    collapse to ONE scalar, use ``merge_accumulators`` directly (or
    ``distributed.collectives.merge_sharded_accumulators``).
    """

    s: jax.Array
    c: jax.Array

    def tree_flatten(self):
        return (self.s, self.c), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    def combine(self, other: "Accumulator") -> "Accumulator":
        """Elementwise two-sum merge of two grids (same shape)."""
        s, c = K.kahan_combine(self.s, self.c, other.s, other.c)
        return Accumulator(s, c)

    def total(self) -> jax.Array:
        """Collapse through the two-sum tree: scalar for ``[rows, lanes]``
        grids, ``[batch]`` for batched grids (vmap of the same tree —
        identical rounding sequence per row)."""
        if self.s.ndim == 3:
            return jax.vmap(merge_accumulators)(self.s, self.c)
        return merge_accumulators(self.s, self.c)


def merge_accumulators(s: jax.Array, c: jax.Array) -> jax.Array:
    """Deterministic compensated merge of an accumulator grid -> scalar.

    THE merge policy: flatten, pad to a power of two with exact zeros,
    fold halves pairwise with two-sum (log2 depth), collapse to s + c.
    Every consumer (kernel wrappers, batched vmap rule, cross-device
    collectives) folds through this same order. Scalar case of
    ``merge_accumulator_grids`` (one tree implementation, not two copies
    to keep in lockstep).
    """
    return merge_accumulator_grids(s.reshape(-1), c.reshape(-1))


def merge_accumulator_grids(s: jax.Array, c: jax.Array) -> jax.Array:
    """Deterministic compensated merge ALONG THE LEADING AXIS only.

    ``s``/``c``: [n, *grid] stacked accumulator grids (e.g. per-device
    matmul (s, c) tiles in device-major all-gather order). The leading
    axis folds through the same power-of-two two-sum tree as
    ``merge_accumulators`` — elementwise over the trailing grid — and the
    result collapses to ``s + c`` per cell. This is the cross-device
    merge for grid-shaped reductions (``collectives.sharded_matmul``),
    where the output is a [M, N] tile, not a scalar.
    """
    n = s.shape[0]
    p2 = 1 << (n - 1).bit_length()
    if p2 != n:
        pad = ((0, p2 - n),) + ((0, 0),) * (s.ndim - 1)
        s = jnp.pad(s, pad)
        c = jnp.pad(c, pad)
    while s.shape[0] > 1:
        half = s.shape[0] // 2
        s, c = K.kahan_combine(s[:half], c[:half], s[half:], c[half:])
    return s[0] + c[0]


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompensatedReduction:
    """Shared padding / promotion / blocking / merge policy for the
    compensated reductions.

    scheme        registered scheme name, CompensationScheme, or a Policy
                  (None -> the ambient ``schemes.use_policy`` default)
    unroll        accumulator-group count U: the (8*U, 128) accumulator
                  tile and the padding unit; a dot grid step streams T
                  such tiles, T from the shape (None -> policy)
    interpret     None -> ``resolve_interpret`` (Mosaic only on TPU)
    blocks        matmul (block_m, block_n, block_k) defaults (None -> policy)
    compute_dtype accumulate dtype for every kernel body (None -> policy;
                  fp32 | f64 (x64 required) | bf16 — anything else fails
                  fast here, at construction)

    Unknown scheme names raise ``ValueError`` (listing the registered
    menu) here — at construction — never inside a kernel trace.
    """

    scheme: SchemeSpec = None
    unroll: Optional[int] = None
    interpret: Optional[bool] = None
    blocks: Optional[Tuple[int, int, int]] = None
    compute_dtype: Any = None

    def __post_init__(self):
        spec = self.scheme
        if isinstance(spec, Policy):
            pol = spec
            spec = pol.scheme
        else:
            pol = _schemes.current_policy()
            if spec is None:
                spec = pol.scheme
        object.__setattr__(self, "scheme", _schemes.resolve_scheme(spec))
        if self.unroll is None:
            object.__setattr__(self, "unroll", pol.unroll)
        if self.interpret is None:
            object.__setattr__(self, "interpret", pol.interpret)
        if self.blocks is None:
            object.__setattr__(self, "blocks", pol.blocks)
        object.__setattr__(
            self, "compute_dtype",
            pol.compute_dtype if self.compute_dtype is None
            else _schemes.resolve_compute_dtype(self.compute_dtype))

    @property
    def block(self) -> int:
        return SUBLANES * self.unroll * LANES

    def _interpret(self) -> bool:
        return resolve_interpret(self.interpret)

    # -- promotion + padding (the one place) --------------------------------
    def _prep1d(self, x: jax.Array) -> jax.Array:
        """Ravel, promote to the compute dtype, zero-pad to the kernel
        block.

        Promotion happens BEFORE padding: narrower inputs are widened
        once and the pad allocates the compute dtype directly (no
        low-precision intermediate copy); zero padding is exact in either
        order.
        """
        x = jnp.ravel(x).astype(self.compute_dtype)
        pad = (-x.shape[0]) % self.block
        if pad or x.shape[0] == 0:
            pad = pad or self.block  # empty input -> one zero block (sum 0.0)
            x = jnp.concatenate([x, jnp.zeros((pad,), self.compute_dtype)])
        return x

    def _prep2d(self, x: jax.Array) -> jax.Array:
        """[batch, ...] -> [batch, n_padded] in the compute dtype (same
        policy, one pad shared by every batch row)."""
        x = x.reshape(x.shape[0], -1).astype(self.compute_dtype)
        pad = (-x.shape[1]) % self.block
        if pad or x.shape[1] == 0:
            pad = pad or self.block  # empty rows -> one zero block (sum 0.0)
            x = jnp.concatenate(
                [x, jnp.zeros((x.shape[0], pad), self.compute_dtype)], axis=1)
        return x

    # -- accumulator producers ----------------------------------------------
    def dot_accumulators(self, a: jax.Array, b: jax.Array) -> Accumulator:
        if a.size != b.size:
            raise ValueError(
                f"dot operands must have equal size: {a.shape} vs {b.shape}")
        a, b = self._prep1d(a), self._prep1d(b)
        s, c = _kd.dot_accumulators(a, b, scheme=self.scheme,
                                    unroll=self.unroll,
                                    interpret=self._interpret(),
                                    compute_dtype=self.compute_dtype)
        return Accumulator(s, c)

    def sum_accumulators(self, x: jax.Array) -> Accumulator:
        x = self._prep1d(x)
        s, c = _ks.sum_accumulators(x, scheme=self.scheme,
                                    unroll=self.unroll,
                                    interpret=self._interpret(),
                                    compute_dtype=self.compute_dtype)
        return Accumulator(s, c)

    def batched_dot_accumulators(self, a: jax.Array, b: jax.Array,
                                 ) -> Accumulator:
        if a.shape != b.shape:
            raise ValueError(
                f"batched_dot operands must match: {a.shape} vs {b.shape}")
        a, b = self._prep2d(a), self._prep2d(b)
        s, c = _kd.dot_accumulators_batched(
            a, b, scheme=self.scheme, unroll=self.unroll,
            interpret=self._interpret(), compute_dtype=self.compute_dtype)
        return Accumulator(s, c)

    def batched_sum_accumulators(self, x: jax.Array) -> Accumulator:
        x = self._prep2d(x)
        s, c = _ks.sum_accumulators_batched(
            x, scheme=self.scheme, unroll=self.unroll,
            interpret=self._interpret(), compute_dtype=self.compute_dtype)
        return Accumulator(s, c)

    # -- collapsed results ---------------------------------------------------
    def dot(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """Compensated dot of two arrays (raveled). Compute-dtype scalar.
        ``jax.vmap`` dispatches to the batched grid (custom_vmap rule)."""
        return _vmappable_dot(self.scheme, self.unroll, self.interpret,
                              self.compute_dtype)(a, b)

    def asum(self, x: jax.Array) -> jax.Array:
        """Compensated sum of an array (raveled). Compute-dtype scalar.
        ``jax.vmap`` dispatches to the batched grid (custom_vmap rule)."""
        return _vmappable_asum(self.scheme, self.unroll, self.interpret,
                               self.compute_dtype)(x)

    def batched_dot(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """[batch, n] x [batch, n] -> [batch], one Pallas grid
        (batch, steps). Bitwise-equal to a Python loop of ``dot`` calls."""
        return self.batched_dot_accumulators(a, b).total()

    def batched_asum(self, x: jax.Array) -> jax.Array:
        """[batch, n] -> [batch], one Pallas grid (batch, steps).
        Bitwise-equal to a Python loop of ``asum`` calls."""
        return self.batched_sum_accumulators(x).total()

    # -- matmul --------------------------------------------------------------
    def _matmul_blocks(self, m: int, n: int, k: int,
                       block_m: Optional[int], block_n: Optional[int],
                       block_k: Optional[int]) -> Tuple[int, int, int]:
        """Resolve + clamp block sizes for an (m, k) x (k, n) problem —
        the ONE blocking policy (shared by single / batched / sharded)."""
        bm, bn, bk = self.blocks
        block_m = bm if block_m is None else block_m
        block_n = bn if block_n is None else block_n
        block_k = bk if block_k is None else block_k
        return (min(block_m, _round_up(m, 8)),
                min(block_n, _round_up(n, 128)),
                min(block_k, _round_up(k, 128)))

    def _prep_matmul(self, a: jax.Array, b: jax.Array,
                     blocks: Tuple[int, int, int],
                     ) -> Tuple[jax.Array, jax.Array]:
        """Promote both operands to the compute dtype, then zero-pad
        M/N/K to block multiples (padding is exact; promotion first so
        the pad allocates the compute dtype directly). Works for 2-D and
        leading-batch-dim 3-D operands."""
        block_m, block_n, block_k = blocks
        m, k = a.shape[-2:]
        n = b.shape[-1]
        a = a.astype(self.compute_dtype)
        b = b.astype(self.compute_dtype)
        pm, pn, pk = (-m) % block_m, (-n) % block_n, (-k) % block_k
        lead = ((0, 0),) * (a.ndim - 2)
        if pm or pk:
            a = jnp.pad(a, lead + ((0, pm), (0, pk)))
        if pk or pn:
            b = jnp.pad(b, lead + ((0, pk), (0, pn)))
        return a, b

    def matmul_accumulators(self, a: jax.Array, b: jax.Array, *,
                            block_m: Optional[int] = None,
                            block_n: Optional[int] = None,
                            block_k: Optional[int] = None) -> Accumulator:
        """(s, c) accumulator grids for C = A @ B, each [M_pad, N_pad]
        (padded to block multiples — callers slice after finalizing).
        This is the producer the sharded path all-gathers per device."""
        m, k = a.shape
        k2, n = b.shape
        assert k == k2, f"contraction mismatch {k} vs {k2}"
        blocks = self._matmul_blocks(m, n, k, block_m, block_n, block_k)
        a, b = self._prep_matmul(a, b, blocks)
        s, c = _km.matmul_accumulators(
            a, b, scheme=self.scheme, block_m=blocks[0], block_n=blocks[1],
            block_k=blocks[2], interpret=self._interpret(),
            compute_dtype=self.compute_dtype)
        return Accumulator(s, c)

    def batched_matmul_accumulators(self, a: jax.Array, b: jax.Array, *,
                                    block_m: Optional[int] = None,
                                    block_n: Optional[int] = None,
                                    block_k: Optional[int] = None,
                                    ) -> Accumulator:
        """(s, c) grids [batch, M_pad, N_pad] from ONE
        (batch, m_blocks, n_blocks, k_steps) Pallas grid."""
        batch, m, k = a.shape
        b2, k2, n = b.shape
        assert batch == b2 and k == k2, (
            f"batched_matmul operands mismatch: {a.shape} vs {b.shape}")
        blocks = self._matmul_blocks(m, n, k, block_m, block_n, block_k)
        a, b = self._prep_matmul(a, b, blocks)
        s, c = _km.matmul_accumulators_batched(
            a, b, scheme=self.scheme, block_m=blocks[0], block_n=blocks[1],
            block_k=blocks[2], interpret=self._interpret(),
            compute_dtype=self.compute_dtype)
        return Accumulator(s, c)

    def matmul(self, a: jax.Array, b: jax.Array, *,
               block_m: Optional[int] = None, block_n: Optional[int] = None,
               block_k: Optional[int] = None) -> jax.Array:
        """C = A @ B, compensated inter-K-tile accumulation, compute-dtype
        output.

        Same promotion policy (inputs widened to the compute dtype before
        padding); the kernel emits the (s, c) grids and the engine
        finalizes them (``scheme.finalize``, the shared ``s + c``
        contract). Unset block sizes come from the resolved policy's
        ``blocks``. ``jax.vmap`` dispatches to the batched
        (batch, m_blocks, n_blocks, k_steps) grid via a custom_vmap rule;
        gradients flow through a custom VJP whose backward matmuls reuse
        this same compensated kernel.
        """
        m, k = a.shape
        n = b.shape[1]
        blocks = self._matmul_blocks(m, n, k, block_m, block_n, block_k)
        return _vmappable_matmul(self.scheme, self.interpret,
                                 self.compute_dtype, blocks)(a, b)

    def batched_matmul(self, a: jax.Array, b: jax.Array, *,
                       block_m: Optional[int] = None,
                       block_n: Optional[int] = None,
                       block_k: Optional[int] = None) -> jax.Array:
        """[batch, M, K] x [batch, K, N] -> [batch, M, N], one Pallas grid
        (batch, m_blocks, n_blocks, k_steps). Bitwise-equal to a Python
        loop of ``matmul`` calls."""
        m, n = a.shape[1], b.shape[2]
        acc = self.batched_matmul_accumulators(
            a, b, block_m=block_m, block_n=block_n, block_k=block_k)
        return self.scheme.finalize(acc.s, acc.c)[:, :m, :n]

    # -- flash attention -----------------------------------------------------
    def flash_attention(self, q: jax.Array, k: jax.Array, v: jax.Array, *,
                        block_q: int = 256, block_k: int = 256,
                        causal: bool = True,
                        q_groups: int = 1) -> jax.Array:
        """Fused attention with compensated online-softmax accumulators.

        q: [BH, Sq, dh]; k/v: [BH // q_groups, Skv, dh]. The engine
        promotes to the compute dtype, pads Sq/Skv to block multiples
        (padded keys are masked in-kernel via ``kv_len``), launches the
        flash grid, and finalizes the kernel-emitted (l, acc) accumulator
        pairs with the shared ``s + c`` contract. Returns [BH, Sq, dh] in
        the compute dtype.

        ``q_groups``: GQA group factor G — each k/v head serves G
        consecutive query heads through the kernel's k/v BlockSpec index
        map (``bh // G``), so grouped k/v are never materialized G times.
        """
        l_acc, o_acc, sq = self.flash_attention_accumulators(
            q, k, v, block_q=block_q, block_k=block_k, causal=causal,
            q_groups=q_groups)
        l_tot = self.scheme.finalize(l_acc.s, l_acc.c)
        o_tot = self.scheme.finalize(o_acc.s, o_acc.c)
        out = o_tot / jnp.maximum(l_tot, 1e-30)
        return out[:, :sq, :]

    def flash_attention_accumulators(self, q: jax.Array, k: jax.Array,
                                     v: jax.Array, *, block_q: int = 256,
                                     block_k: int = 256, causal: bool = True,
                                     q_groups: int = 1,
                                     ) -> Tuple[Accumulator, Accumulator, int]:
        """Raw (l, acc) accumulator pairs from the flash grid.

        Returns (l_acc [BH, Sq_pad, 1], o_acc [BH, Sq_pad, dh], sq) —
        ``sq`` is the un-padded query count for the caller's final slice.
        With ``q_groups=G``, k/v carry [BH // G, Skv, dh] and the kernel
        index map shares each k/v head across its G query heads.
        """
        bh, sq, dh = q.shape
        if bh != k.shape[0] * q_groups:
            raise ValueError(
                f"flash_attention: q has {bh} head-rows but k/v carry "
                f"{k.shape[0]} with q_groups={q_groups} "
                f"(expected BH == BH_kv * q_groups)")
        skv = k.shape[1]
        block_q = min(block_q, _round_up(sq, 8))
        block_k = min(block_k, _round_up(skv, 128))
        q = q.astype(self.compute_dtype)
        k = k.astype(self.compute_dtype)
        v = v.astype(self.compute_dtype)
        pq, pk = (-sq) % block_q, (-skv) % block_k
        if pq:
            q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
        if pk:
            k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
        l_s, l_c, o_s, o_c = _fa.flash_accumulators(
            q, k, v, block_q=block_q, block_k=block_k, scheme=self.scheme,
            causal=causal, kv_len=skv, interpret=self._interpret(),
            q_groups=q_groups, compute_dtype=self.compute_dtype)
        return Accumulator(l_s, l_c), Accumulator(o_s, o_c), sq

    def flash_chunk_attention(self, q: jax.Array, k: jax.Array,
                              v: jax.Array, *, q_off: jax.Array,
                              block_q: int = 256, block_k: int = 256,
                              q_groups: int = 1) -> jax.Array:
        """Chunked-prefill fused attention: a chunk of queries at TRACED
        absolute offset ``q_off`` attends the full KV cache.

        q: [BH, W, dh] (the chunk — query row i lives at sequence
        position ``q_off + i``); k/v: [BH // q_groups, Skv, dh] — the
        whole per-slot cache with the chunk's K/V already written at
        ``q_off``. Masking is always causal on absolute positions (which
        is also what excludes unwritten cache rows); ``kv_len`` masks
        only engine padding, so ONE compiled program serves every chunk
        of width W. Same padding / promotion / finalization policy — and
        the same shared block body — as ``flash_attention``, so output
        rows whose absolute positions coincide with a full-sequence
        call's are bitwise equal. Returns [BH, W, dh] compute-dtype.
        """
        l_acc, o_acc, w = self.flash_chunk_attention_accumulators(
            q, k, v, q_off=q_off, block_q=block_q, block_k=block_k,
            q_groups=q_groups)
        l_tot = self.scheme.finalize(l_acc.s, l_acc.c)
        o_tot = self.scheme.finalize(o_acc.s, o_acc.c)
        out = o_tot / jnp.maximum(l_tot, 1e-30)
        return out[:, :w, :]

    def flash_chunk_attention_accumulators(self, q: jax.Array, k: jax.Array,
                                           v: jax.Array, *, q_off: jax.Array,
                                           block_q: int = 256,
                                           block_k: int = 256,
                                           q_groups: int = 1,
                                           ) -> Tuple[Accumulator,
                                                      Accumulator, int]:
        """Raw (l, acc) pairs from the chunked-prefill flash grid.

        Padded query rows (W -> block multiple) run at absolute
        positions past the chunk and produce garbage the caller slices
        off — exactly the engine's Sq-padding policy on the full grid.
        """
        bh, w, dh = q.shape
        if bh != k.shape[0] * q_groups:
            raise ValueError(
                f"flash_chunk_attention: q has {bh} head-rows but k/v "
                f"carry {k.shape[0]} with q_groups={q_groups} "
                f"(expected BH == BH_kv * q_groups)")
        skv = k.shape[1]
        block_q = min(block_q, _round_up(w, 8))
        block_k = min(block_k, _round_up(skv, 128))
        q = q.astype(self.compute_dtype)
        k = k.astype(self.compute_dtype)
        v = v.astype(self.compute_dtype)
        pq, pk = (-w) % block_q, (-skv) % block_k
        if pq:
            q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
        if pk:
            k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
        l_s, l_c, o_s, o_c = _fa.flash_chunk_accumulators(
            q, k, v, q_off, block_q=block_q, block_k=block_k,
            scheme=self.scheme, kv_len=skv, interpret=self._interpret(),
            q_groups=q_groups, compute_dtype=self.compute_dtype)
        return Accumulator(l_s, l_c), Accumulator(o_s, o_c), w


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# vmap dispatch: scalar entry points batch onto the (batch, steps) grid
# ---------------------------------------------------------------------------

def _flatten_batch(x: jax.Array, axis_size: int) -> jax.Array:
    """Batched operand [axis_size, *rest] -> [axis_size, prod(rest)]."""
    assert x.shape[0] == axis_size
    return x.reshape(axis_size, -1)


@functools.lru_cache(maxsize=None)
def _vmappable_dot(scheme: CompensationScheme, unroll: int,
                   interpret: Optional[bool], compute_dtype):
    eng = CompensatedReduction(scheme=scheme, unroll=unroll,
                               interpret=interpret,
                               compute_dtype=compute_dtype)

    @jax.custom_batching.custom_vmap
    def _dot(a, b):
        return eng.dot_accumulators(a, b).total()

    @_dot.def_vmap
    def _dot_vmap(axis_size, in_batched, a, b):
        a_b, b_b = in_batched
        if not a_b:
            a = jnp.broadcast_to(a[None], (axis_size,) + a.shape)
        if not b_b:
            b = jnp.broadcast_to(b[None], (axis_size,) + b.shape)
        out = eng.batched_dot(_flatten_batch(a, axis_size),
                              _flatten_batch(b, axis_size))
        return out, True

    return _dot


@functools.lru_cache(maxsize=None)
def _vmappable_asum(scheme: CompensationScheme, unroll: int,
                    interpret: Optional[bool], compute_dtype):
    eng = CompensatedReduction(scheme=scheme, unroll=unroll,
                               interpret=interpret,
                               compute_dtype=compute_dtype)

    @jax.custom_batching.custom_vmap
    def _asum(x):
        return eng.sum_accumulators(x).total()

    @_asum.def_vmap
    def _asum_vmap(axis_size, in_batched, x):
        if not in_batched[0]:
            x = jnp.broadcast_to(x[None], (axis_size,) + x.shape)
        return eng.batched_asum(_flatten_batch(x, axis_size)), True

    return _asum


@functools.lru_cache(maxsize=None)
def _vmappable_matmul(scheme: CompensationScheme,
                      interpret: Optional[bool], compute_dtype,
                      blocks: Tuple[int, int, int]):
    """Matmul entry point with BOTH transform rules attached:

    * ``custom_vmap`` — ``jax.vmap`` lands on the batched
      (batch, m_blocks, n_blocks, k_steps) grid instead of a per-element
      fallback loop;
    * ``custom_vjp`` — Pallas kernels have no automatic transpose; the
      backward matmuls (dA = g @ B^T, dB = A^T @ g) route through the
      SAME compensated kernel, so training through ``ops.matmul`` keeps
      the engine contract end to end.
    """
    eng = CompensatedReduction(scheme=scheme, interpret=interpret,
                               compute_dtype=compute_dtype, blocks=blocks)

    # custom_vmap INSIDE, custom_vjp OUTSIDE: jax.grad must intercept at
    # the outer custom_vjp before ever tracing through the custom_vmap
    # wrapper (which has no JVP rule); jax.vmap batches the custom_vjp
    # call by vmapping its underlying function, which lands on the inner
    # custom_vmap's rule — so both transforms reach their intended path.
    @jax.custom_batching.custom_vmap
    def _mm_vmappable(a, b):
        m, n = a.shape[0], b.shape[1]
        acc = eng.matmul_accumulators(a, b)
        return eng.scheme.finalize(acc.s, acc.c)[:m, :n]

    @_mm_vmappable.def_vmap
    def _mm_vmap(axis_size, in_batched, a, b):
        a_b, b_b = in_batched
        if not a_b:
            a = jnp.broadcast_to(a[None], (axis_size,) + a.shape)
        if not b_b:
            b = jnp.broadcast_to(b[None], (axis_size,) + b.shape)
        return eng.batched_matmul(a, b), True

    @jax.custom_vjp
    def mm(a, b):
        return _mm_vmappable(a, b)

    def _mm_fwd(a, b):
        return mm(a, b), (a, b)

    def _mm_bwd(res, g):
        a, b = res
        da = mm(g, b.T).astype(a.dtype)
        db = mm(a.T, g).astype(b.dtype)
        return da, db

    mm.defvjp(_mm_fwd, _mm_bwd)
    return mm
