"""Pure-jnp oracles for the Pallas kernels — registry-generic.

Each oracle mirrors the *exact accumulation semantics* of its kernel so
that interpret-mode kernel output can be compared with tight tolerances
(bitwise for the 1-D reductions and flash attention). There is ONE oracle
body per kernel shape, parameterized by the same ``CompensationScheme``
callables the kernel body traces — the per-mode ``if/elif`` chains are
gone, and any scheme registered in ``repro.kernels.schemes`` gets its
oracle for free, bitwise-matching by construction.

``compute_dtype`` threads through every oracle exactly as it does through
the kernels (None resolves the ambient policy — fp32 by default), so the
bitwise contract holds along the whole fp32 / f64 / bf16-accumulate axis.

The accumulator merge policy is owned by ``repro.kernels.engine``;
``merge_accumulators`` is re-exported here for back-compat. (The legacy
``mode`` alias was removed — see the migration note in
``repro.kernels.schemes``.)
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.kernels import schemes as _schemes
from repro.kernels.engine import merge_accumulators  # noqa: F401  (re-export)
from repro.kernels.schemes import CompensationScheme

SchemeSpec = Union[str, CompensationScheme, None]


def _pad_to(x: jax.Array, multiple: int) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % multiple
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _resolve(scheme: SchemeSpec) -> CompensationScheme:
    return _schemes.resolve_scheme(scheme)


def dot_ref(a: jax.Array, b: jax.Array, scheme: SchemeSpec = None,
            rows: int = 8, lanes: int = 128, *, compute_dtype=None) -> jax.Array:
    """Oracle for the dot kernels.

    Accumulation layout matches the kernel: data is viewed as
    ``[steps, rows, lanes]``; a (rows, lanes) grid of accumulators is
    updated once per step via ``scheme.mul_update`` (the same callable
    the kernel body traces — bitwise by construction); accumulators are
    then merged with two-sum in the same tree order as the engine.
    """
    sch = _resolve(scheme)
    cdt = _schemes.resolve_compute_dtype(compute_dtype)
    a = _pad_to(jnp.ravel(a).astype(cdt), rows * lanes)
    b = _pad_to(jnp.ravel(b).astype(cdt), rows * lanes)
    am = a.reshape(-1, rows, lanes)
    bm = b.reshape(-1, rows, lanes)
    steps = jnp.arange(am.shape[0], dtype=jnp.int32)

    def body(carry, xs):
        s, c = carry
        x, y, g = xs
        return sch.mul_update(s, c, x, y, g), None

    init = (jnp.zeros((rows, lanes), cdt), jnp.zeros((rows, lanes), cdt))
    (s, c), _ = jax.lax.scan(body, init, (am, bm, steps))
    return merge_accumulators(s, c)


def sum_ref(x: jax.Array, scheme: SchemeSpec = None,
            rows: int = 8, lanes: int = 128, *, compute_dtype=None) -> jax.Array:
    """Oracle for the sum kernels (single-stream dot with b == 1)."""
    sch = _resolve(scheme)
    cdt = _schemes.resolve_compute_dtype(compute_dtype)
    x = _pad_to(jnp.ravel(x).astype(cdt), rows * lanes)
    xm = x.reshape(-1, rows, lanes)
    steps = jnp.arange(xm.shape[0], dtype=jnp.int32)

    def body(carry, xs):
        s, c = carry
        row, g = xs
        return sch.update(s, c, row, g), None

    init = (jnp.zeros((rows, lanes), cdt), jnp.zeros((rows, lanes), cdt))
    (s, c), _ = jax.lax.scan(body, init, (xm, steps))
    return merge_accumulators(s, c)


def batched_dot_ref(a: jax.Array, b: jax.Array, scheme: SchemeSpec = None,
                    rows: int = 8, lanes: int = 128, *, compute_dtype=None) -> jax.Array:
    """Oracle for the batched dot grid: vmap of the single oracle over the
    leading batch axis — per row, the identical rounding sequence."""
    sch = _resolve(scheme)
    fn = functools.partial(dot_ref, scheme=sch, rows=rows, lanes=lanes,
                           compute_dtype=compute_dtype)
    return jax.vmap(fn)(a, b)


def batched_sum_ref(x: jax.Array, scheme: SchemeSpec = None,
                    rows: int = 8, lanes: int = 128, *, compute_dtype=None) -> jax.Array:
    """Oracle for the batched sum grid (see ``batched_dot_ref``)."""
    sch = _resolve(scheme)
    fn = functools.partial(sum_ref, scheme=sch, rows=rows, lanes=lanes,
                           compute_dtype=compute_dtype)
    return jax.vmap(fn)(x)


def matmul_ref(a: jax.Array, b: jax.Array, bk: int = 512,
               scheme: SchemeSpec = None, *, compute_dtype=None) -> jax.Array:
    """Oracle for the matmul kernel: per-tile dot products folded across K
    tiles with ``scheme.update``, finalized with the shared ``s + c``.

    a: [M, K], b: [K, N] (any float dtype; accumulate in compute_dtype).
    """
    sch = _resolve(scheme)
    cdt = _schemes.resolve_compute_dtype(compute_dtype)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    pad = (-k) % bk
    if pad:
        a = jnp.concatenate([a, jnp.zeros((m, pad), a.dtype)], axis=1)
        b = jnp.concatenate([b, jnp.zeros((pad, n), b.dtype)], axis=0)
    kt = a.shape[1] // bk
    a3 = a.reshape(m, kt, bk).transpose(1, 0, 2)  # [kt, M, bk]
    b3 = b.reshape(kt, bk, n)                      # [kt, bk, N]
    steps = jnp.arange(kt, dtype=jnp.int32)

    def body(carry, xs):
        s, c = carry
        at, bt, g = xs
        prod = jnp.dot(at.astype(cdt), bt.astype(cdt),  # contract: allow-no-uncompensated-reduction(oracle block product; scheme.update carries the compensation, mirrors the kernel)
                       preferred_element_type=cdt,
                       precision=jax.lax.Precision.HIGHEST)
        return sch.update(s, c, prod, g), None

    init = (jnp.zeros((m, n), cdt), jnp.zeros((m, n), cdt))
    (s, c), _ = jax.lax.scan(body, init, (a3, b3, steps))
    return sch.finalize(s, c)


def batched_matmul_ref(a: jax.Array, b: jax.Array, bk: int = 512,
                       scheme: SchemeSpec = None, *, compute_dtype=None) -> jax.Array:
    """Oracle for the batched matmul grid: vmap of ``matmul_ref`` over the
    leading batch axis — per index, the identical rounding sequence."""
    sch = _resolve(scheme)
    fn = functools.partial(matmul_ref, bk=bk, scheme=sch,
                           compute_dtype=compute_dtype)
    return jax.vmap(fn)(a, b)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        scheme: SchemeSpec = None, *, block_q: int = 256,
                        block_k: int = 256, causal: bool = True,
                        q_groups: int = 1,
                        compute_dtype=None) -> jax.Array:
    """BITWISE oracle for the flash-attention kernel under the engine
    contract.

    Replays the engine's padding/clamping policy and the kernel's exact
    per-k-block op sequence (same ``scheme.update`` callables, same
    masking, same online-softmax rescale — including the shared
    ``rowsum_tree`` — and the same out-of-kernel finalize) with Python
    loops over (bh, q-block), TRACED UNDER JIT like the kernel itself is
    (eager per-op execution fuses elementwise chains differently and
    drifts by ~1 ulp) — so interpret-mode kernel output matches to the
    bit for every registered scheme. q: [BH, Sq, dh]; k/v: [BH, Skv, dh];
    returns [BH, Sq, dh] in the compute dtype.

    ``q_groups``: GQA group factor G — k/v carry [BH // G, Skv, dh] and
    the kernel's k/v BlockSpec index map reads block ``bh // G``; the
    oracle replays that sharing by repeating each k/v head G times
    (pure data movement, so the bitwise contract is untouched).
    """
    from repro.kernels import flash_attention as _flash
    from repro.kernels.flash_attention import NEG_INF

    sch = _resolve(scheme)
    cdt = _schemes.resolve_compute_dtype(compute_dtype)
    bh, sq, dh = q.shape
    if q_groups > 1:
        assert k.shape[0] * q_groups == bh, (q.shape, k.shape, q_groups)
        k = jnp.repeat(k, q_groups, axis=0)
        v = jnp.repeat(v, q_groups, axis=0)
    skv = k.shape[1]
    block_q = min(block_q, _round_up(sq, 8))
    block_k = min(block_k, _round_up(skv, 128))
    scale = dh ** -0.5

    def _run(q, k, v, qb_idx, kb_idx):
        q = q.astype(cdt)
        k = k.astype(cdt)
        v = v.astype(cdt)
        pq, pk = (-sq) % block_q, (-skv) % block_k
        if pq:
            q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
        if pk:
            k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
        n_qb = q.shape[1] // block_q
        n_kb = k.shape[1] // block_k

        outs = []
        for b in range(bh):
            kblks = k[b].reshape(n_kb, block_k, dh)
            vblks = v[b].reshape(n_kb, block_k, dh)
            rows = []
            for qb in range(n_qb):
                qblk = q[b, qb * block_q:(qb + 1) * block_q]      # [bq, dh]
                # block indices come in as TRACED values (qb_idx/kb_idx
                # arrays), matching the kernel's pl.program_id — a Python
                # int would constant-fold the iota masks and change the
                # compiled program (and with it the rounding of
                # fusion-sensitive ops). The k loop is a lax.scan like
                # the kernel's sequential grid axis (and the dot/sum
                # oracles).
                qb_t = qb_idx[qb]

                def body(carry, xs, _qb=qb_t):
                    m, l_s, l_c, a_s, a_c = carry
                    kblk, vblk, kb_t = xs
                    # the SAME shared block body the kernel traces
                    out = _flash.flash_block_update(
                        sch, qblk, kblk, vblk, m, l_s, l_c, a_s, a_c,
                        qb=_qb, kb=kb_t, step=kb_t, block_q=block_q,
                        block_k=block_k, kv_len=skv, causal=causal,
                        scale=scale, compute_dtype=cdt)
                    return out, None

                init = (jnp.full((block_q, 1), NEG_INF, cdt),
                        jnp.zeros((block_q, 1), cdt),
                        jnp.zeros((block_q, 1), cdt),
                        jnp.zeros((block_q, dh), cdt),
                        jnp.zeros((block_q, dh), cdt))
                (m, l_s, l_c, a_s, a_c), _ = jax.lax.scan(
                    body, init, (kblks, vblks, kb_idx))
                row = sch.finalize(a_s, a_c) / jnp.maximum(
                    sch.finalize(l_s, l_c), 1e-30)
                rows.append(row)
            outs.append(jnp.concatenate(rows, axis=0)[:sq])
        return jnp.stack(outs)

    n_qb = _round_up(sq, block_q) // block_q
    n_kb = _round_up(skv, block_k) // block_k
    return jax.jit(_run)(q, k, v, jnp.arange(n_qb, dtype=jnp.int32),
                         jnp.arange(n_kb, dtype=jnp.int32))


def matmul_exact_f64(a: jax.Array, b: jax.Array) -> jax.Array:
    """High-precision reference (numpy float64) for accuracy comparisons."""
    import numpy as np

    return np.asarray(a, np.float64) @ np.asarray(b, np.float64)
