"""Kahan / compensated-summation primitives.

This module is the numerical core of the reproduction: the paper's kernel
(Fig. 1b) is the compensated accumulation

    prod = a[i] * b[i]
    y    = prod - c
    t    = s + y
    c    = (t - s) - y
    s    = t

We provide it in composable JAX form:

* ``two_sum`` / ``fast_two_sum`` — error-free transformations (EFTs).
* ``kahan_step`` — one compensated accumulation step (the paper's loop body).
* ``kahan_sum`` / ``kahan_dot`` — vectorized reductions with lane-parallel
  partial accumulators (the SIMD adaptation) and a compensated cross-lane
  merge.
* ``KahanAccumulator`` — a pytree carrying ``(value, comp)`` pairs, used for
  compensated gradient accumulation and the Kahan optimizer.
* tree utilities (``tree_kahan_add`` etc.) for whole-pytree compensated
  updates.

Numerical notes
---------------
``two_sum`` (Knuth) is branch-free and valid for any ordering of |a|, |b|;
``fast_two_sum`` (Dekker) requires |a| >= |b| and costs 3 flops instead of 6.
The paper's Kahan step is cheaper than a full two-sum accumulation but only
tracks the *local* error; we use the classic Kahan step inside kernels (to
mirror the paper's instruction mix: 1 MUL + 4 ADD per update) and full
two-sum folds where accumulators are merged (cross-lane, cross-device,
cross-microbatch), where robustness to magnitude inversion matters.

FMA-contraction hazard: ``(t - s) - y`` must be evaluated with exactly the
rounded intermediate ``t - s``. XLA does not reassociate floating point and
does not contract these adds into FMAs, so plain jnp is safe; the Pallas
kernels inherit the same semantics. ``tests/test_kahan_core.py`` pins this.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import tree_util


Array = jax.Array


# ---------------------------------------------------------------------------
# Error-free transformations
# ---------------------------------------------------------------------------

def two_sum(a: Array, b: Array) -> Tuple[Array, Array]:
    """Knuth two-sum: returns (s, e) with s = fl(a+b) and a+b = s+e exactly.

    6 flops, branch-free, no magnitude precondition. Exact for any IEEE
    inputs barring overflow.
    """
    s = a + b
    bp = s - a
    ap = s - bp
    eb = b - bp
    ea = a - ap
    return s, ea + eb


def fast_two_sum(a: Array, b: Array) -> Tuple[Array, Array]:
    """Dekker fast-two-sum: requires |a| >= |b| (elementwise). 3 flops."""
    s = a + b
    e = b - (s - a)
    return s, e


def two_prod(a: Array, b: Array) -> Tuple[Array, Array]:
    """Error-free product via FMA-style splitting.

    Uses the Dekker/Veltkamp split (no hardware FMA assumption — on TPU the
    MXU accumulates in fp32 and jnp has no fused ``fma`` primitive exposed,
    so we split). Returns (p, e) with p = fl(a*b), a*b = p + e exactly for
    fp32/fp64 (not for bf16 inputs — upcast first).
    """
    # Veltkamp splitting constant: 2^ceil(m/2)+1 where m = mantissa bits.
    dtype = jnp.result_type(a, b)
    if dtype == jnp.float64:
        c = jnp.asarray(134217729.0, dtype)  # 2^27 + 1
    else:
        c = jnp.asarray(4097.0, dtype)  # 2^12 + 1 for fp32
    p = a * b
    a_big = c * a
    a_hi = a_big - (a_big - a)
    a_lo = a - a_hi
    b_big = c * b
    b_hi = b_big - (b_big - b)
    b_lo = b - b_hi
    e = ((a_hi * b_hi - p) + a_hi * b_lo + a_lo * b_hi) + a_lo * b_lo
    return p, e


# ---------------------------------------------------------------------------
# The paper's Kahan step
# ---------------------------------------------------------------------------

def kahan_step(s: Array, c: Array, x: Array) -> Tuple[Array, Array]:
    """One Kahan accumulation step: add ``x`` into (s, c).

    The paper's Fig. 1b loop body (minus the multiply): 4 adds. We use the
    sign-flipped compensation convention ``total = s + c`` (the paper's
    original ``y = x - c; c = (t - s) - y`` stores the *negative* error,
    ``total = s - c``). Same instruction count and rounding behavior, but a
    single convention composes cleanly with the two-sum merges used for
    cross-lane / cross-device / cross-microbatch folds.
    """
    y = x + c
    t = s + y
    c = y - (t - s)
    return t, c


def kahan_combine(s1: Array, c1: Array, s2: Array, c2: Array) -> Tuple[Array, Array]:
    """Merge two compensated accumulators into one.

    Used when reducing lane-parallel partials (the paper's horizontal SIMD
    reduction after the main loop) and when merging per-device partials.
    two-sum based: robust to arbitrary relative magnitudes. Both inputs and
    the output use the ``total = s + c`` convention.
    """
    s, e = two_sum(s1, s2)
    # accumulated compensations are small; their sum attaches to the error term
    return s, e + c1 + c2


# ---------------------------------------------------------------------------
# Vectorized compensated reductions (pure-JAX reference implementations;
# the Pallas kernels in repro.kernels mirror these block-for-block)
# ---------------------------------------------------------------------------

def _lane_partials_sum(x: Array, lanes: int) -> Tuple[Array, Array]:
    """Fold ``x`` (1-D) into ``lanes`` compensated partial accumulators.

    This is the SIMD structure from the paper: lane j accumulates elements
    j, j+lanes, j+2*lanes, ... with its own (s_j, c_j) pair. Implemented as
    a scan over rows of the (n//lanes, lanes) reshape; remainder handled by
    zero-padding (exact: adding 0.0 is error-free for finite s).
    """
    n = x.shape[0]
    rows = -(-n // lanes)
    pad = rows * lanes - n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    xm = x.reshape(rows, lanes)

    def body(carry, row):
        s, c = carry
        s, c = kahan_step(s, c, row)
        return (s, c), None

    init = (jnp.zeros((lanes,), x.dtype), jnp.zeros((lanes,), x.dtype))
    (s, c), _ = jax.lax.scan(body, init, xm)
    return s, c


def _merge_lanes(s: Array, c: Array) -> Tuple[Array, Array]:
    """Tree-reduce lane partials with compensated merges (log2 depth)."""
    lanes = s.shape[0]
    while lanes > 1:
        half = lanes // 2
        if lanes % 2:  # odd: fold the last lane into lane 0 first
            s0, c0 = kahan_combine(s[0], c[0], s[-1], c[-1])
            s = s.at[0].set(s0)
            c = c.at[0].set(c0)
            s, c = s[: lanes - 1], c[: lanes - 1]
            lanes -= 1
            half = lanes // 2
        s_new, c_new = kahan_combine(s[:half], c[:half], s[half:], c[half:])
        s, c = s_new, c_new
        lanes = half
    return s[0], c[0]


def kahan_sum(x: Array, lanes: int = 128) -> Array:
    """Compensated sum of a 1-D array with lane-parallel partials.

    ``lanes`` is the SIMD-width analog (TPU lane count by default). Returns
    the compensated total ``s + c`` in x.dtype.
    """
    x = jnp.ravel(x)
    s, c = _lane_partials_sum(x, min(lanes, max(x.shape[0], 1)))
    s, c = _merge_lanes(s, c)
    return s + c


def kahan_dot(a: Array, b: Array, lanes: int = 128) -> Array:
    """Compensated dot product — the paper's kernel, pure-JAX form.

    1 MUL + 4 ADD per element, lane-parallel partial accumulators, two-sum
    lane merge. Matches the Pallas kernel in repro/kernels/kahan_dot.py.
    """
    a = jnp.ravel(a)
    b = jnp.ravel(b)
    n = a.shape[0]
    lanes = min(lanes, max(n, 1))
    rows = -(-n // lanes)
    pad = rows * lanes - n
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad,), a.dtype)])
        b = jnp.concatenate([b, jnp.zeros((pad,), b.dtype)])
    am = a.reshape(rows, lanes)
    bm = b.reshape(rows, lanes)

    def body(carry, ab):
        s, c = carry
        ar, br = ab
        s, c = kahan_step(s, c, ar * br)
        return (s, c), None

    init = (jnp.zeros((lanes,), a.dtype), jnp.zeros((lanes,), a.dtype))
    (s, c), _ = jax.lax.scan(body, init, (am, bm))
    s, c = _merge_lanes(s, c)
    return s + c


def kahan_dot2(a: Array, b: Array, lanes: int = 128) -> Array:
    """Dot2-style compensated dot: two_prod + two_sum (Ogita/Rump/Oishi).

    Twice-working-precision result; more expensive than the paper's Kahan
    (≈ 17 flops/element) but the accuracy ceiling for the benchmark tables.
    """
    a = jnp.ravel(a)
    b = jnp.ravel(b)
    n = a.shape[0]
    lanes = min(lanes, max(n, 1))
    rows = -(-n // lanes)
    pad = rows * lanes - n
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad,), a.dtype)])
        b = jnp.concatenate([b, jnp.zeros((pad,), b.dtype)])
    am = a.reshape(rows, lanes)
    bm = b.reshape(rows, lanes)

    def body(carry, ab):
        s, c = carry
        ar, br = ab
        p, ep = two_prod(ar, br)
        s, es = two_sum(s, p)
        return (s, c + (ep + es)), None

    init = (jnp.zeros((lanes,), a.dtype), jnp.zeros((lanes,), a.dtype))
    (s, c), _ = jax.lax.scan(body, init, (am, bm))
    s, c = _merge_lanes(s, c)
    return s + c


def naive_sum(x: Array) -> Array:
    """Strictly-sequential naive sum (the accuracy baseline, NOT jnp.sum —
    jnp.sum already uses pairwise/tree reduction which is far more accurate
    than the scalar C loop the paper compares against)."""
    x = jnp.ravel(x)

    def body(carry, xi):
        return carry + xi, None

    s, _ = jax.lax.scan(body, jnp.zeros((), x.dtype), x)
    return s


def naive_dot(a: Array, b: Array) -> Array:
    """Strictly-sequential naive dot (paper Fig. 1a semantics)."""
    a = jnp.ravel(a)
    b = jnp.ravel(b)

    def body(carry, ab):
        ai, bi = ab
        return carry + ai * bi, None

    s, _ = jax.lax.scan(body, jnp.zeros((), a.dtype), (a, b))
    return s


# ---------------------------------------------------------------------------
# Compensated accumulator pytree — grad accumulation / optimizer substrate
# ---------------------------------------------------------------------------

@tree_util.register_pytree_node_class
@dataclasses.dataclass
class KahanAccumulator:
    """A compensated running value: ``total ≈ value + comp`` with ``comp``
    holding the rounding residue of every ``add`` so far.

    Works elementwise over arrays of any shape; used as the microbatch
    gradient accumulator and inside KahanAdamW for bf16 parameter updates.
    """

    value: Any
    comp: Any

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        return (self.value, self.comp), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    # -- API ----------------------------------------------------------------
    @classmethod
    def zeros_like(cls, tree: Any) -> "KahanAccumulator":
        return cls(
            value=jax.tree.map(jnp.zeros_like, tree),
            comp=jax.tree.map(jnp.zeros_like, tree),
        )

    @classmethod
    def init(cls, tree: Any) -> "KahanAccumulator":
        """Start from an existing value with zero compensation."""
        return cls(value=tree, comp=jax.tree.map(jnp.zeros_like, tree))

    def add(self, delta: Any) -> "KahanAccumulator":
        """Compensated ``self += delta`` (elementwise Kahan step per leaf)."""
        def leaf(s, c, x):
            s2, c2 = kahan_step(s, c, x.astype(s.dtype))
            return s2, c2

        pairs = jax.tree.map(leaf, self.value, self.comp, delta)
        value = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda p: isinstance(p, tuple))
        comp = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda p: isinstance(p, tuple))
        return KahanAccumulator(value, comp)

    def merge(self, other: "KahanAccumulator") -> "KahanAccumulator":
        """Compensated merge of two accumulators (two-sum based)."""
        def leaf(s1, c1, s2, c2):
            return kahan_combine(s1, c1, s2, c2)

        pairs = jax.tree.map(leaf, self.value, self.comp, other.value, other.comp)
        value = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda p: isinstance(p, tuple))
        comp = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda p: isinstance(p, tuple))
        return KahanAccumulator(value, comp)

    def total(self) -> Any:
        """Collapse to the best single-value estimate (value + comp)."""
        return jax.tree.map(lambda s, c: s + c, self.value, self.comp)

    def scale(self, factor) -> "KahanAccumulator":
        """Exact-ish scaling: scaling both members commutes with compensation
        up to one rounding each (used for 1/num_microbatches averaging)."""
        return KahanAccumulator(
            value=jax.tree.map(lambda s: s * factor, self.value),
            comp=jax.tree.map(lambda c: c * factor, self.comp),
        )


# ---------------------------------------------------------------------------
# Whole-tree helpers
# ---------------------------------------------------------------------------

def tree_kahan_add(value: Any, comp: Any, delta: Any) -> Tuple[Any, Any]:
    """Compensated ``value += delta`` over matching pytrees.

    Returns (new_value, new_comp). The workhorse of KahanAdamW: ``value`` may
    be bf16; the compensation recovers the bits bf16 drops on small updates.
    """
    def leaf(s, c, x):
        return kahan_step(s, c, x.astype(s.dtype))

    pairs = jax.tree.map(leaf, value, comp, delta)
    new_value = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda p: isinstance(p, tuple))
    new_comp = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda p: isinstance(p, tuple))
    return new_value, new_comp


def tree_kahan_sq_norm(tree: Any) -> Array:
    """Compensated global squared L2 norm of a pytree (fp32 accumulate).

    SHARDING-PRESERVING by construction: each leaf is Kahan-accumulated by
    scanning its LEADING axis (the compensation vector keeps the trailing
    shape — and therefore the trailing sharding — of the leaf; no
    ravel/reshape that would force GSPMD to all-gather a sharded
    gradient). The first llama4 dry-run caught the naive version
    all-gathering 3 x 480 GiB of fp32 expert gradients for exactly this
    reason. Leaf partials fold with two-sum in flatten order —
    reproducible for a fixed tree structure.
    """
    leaves = tree_util.tree_leaves(tree)
    s = jnp.zeros((), jnp.float32)
    c = jnp.zeros((), jnp.float32)
    for leaf in leaves:
        g = leaf.astype(jnp.float32)
        if g.ndim >= 2 and g.shape[0] > 1:
            def body(carry, row):
                cs, cc = carry
                cs, cc = kahan_step(cs, cc, row * row)
                return (cs, cc), None

            init = (jnp.zeros(g.shape[1:], jnp.float32),
                    jnp.zeros(g.shape[1:], jnp.float32))
            (acc_s, acc_c), _ = jax.lax.scan(body, init, g)
            part_s = jnp.sum(acc_s)
            part_c = jnp.sum(acc_c)
            s, c = kahan_combine(s, c, part_s, part_c)
        else:
            part = jnp.sum(g * g)
            s, c = kahan_step(s, c, part)
    return s + c


@partial(jax.jit, static_argnames=("axis_name",))
def compensated_psum_scalar(s: Array, c: Array, axis_name: str) -> Tuple[Array, Array]:
    """Deterministic compensated cross-device scalar reduction.

    all_gather the (s, c) partials and fold them in device order with
    two-sum. Unlike ``psum``, the result is independent of the reduction
    order the backend picks — bitwise reproducible for a fixed mesh size.
    For scalars/metrics only (gathers 2 floats/device).
    """
    ss = jax.lax.all_gather(s, axis_name).astype(jnp.float32)  # [n_dev]
    cs = jax.lax.all_gather(c, axis_name).astype(jnp.float32)

    def body(carry, sc):
        acc_s, acc_c = carry
        si, ci = sc
        acc_s, acc_c = kahan_combine(acc_s, acc_c, si, ci)
        return (jnp.asarray(acc_s, jnp.float32),
                jnp.asarray(acc_c, jnp.float32)), None

    init = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    # under shard_map the gathered xs are "varying" over axis_name; the
    # carry must match that manual-axes type
    init = jax.tree.map(
        lambda t: jax.lax.pcast(t, (axis_name,), to="varying"), init)
    (rs, rc), _ = jax.lax.scan(body, init, (ss, cs))
    return rs, rc
