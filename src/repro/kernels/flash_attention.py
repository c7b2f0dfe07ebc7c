"""Pallas TPU flash-attention with compensated online softmax.

Motivation (EXPERIMENTS.md §Perf): the dominant residual roofline term in
every train/prefill cell is the materialized fp32 score/softmax buffer
traffic — the textbook fix is a fused flash kernel (scores never leave
VMEM). This kernel is that fix, with the paper's technique applied where
it belongs inside it: the ONLINE-SOFTMAX ACCUMULATORS.

Flash attention folds k-blocks into running statistics

    m   <- max(m, rowmax(s))                 (stabilizer)
    l   <- l * exp(m_old - m) + rowsum(p)    (denominator)
    acc <- acc * exp(m_old - m) + p @ v      (numerator)

``l`` and ``acc`` are *long sequential accumulations* (one add per
k-block: 4096 blocks at 512k context) — exactly the error pattern the
paper compensates in the scalar product. Both carry the engine's (value,
comp) pair and fold each k-block through ``scheme.update`` from the
compensation-scheme registry (naive / kahan / pairwise / dot2 / custom —
same menu as the dot kernels); the rescaling by exp(m_old - m) scales
value AND comp (scaling commutes with compensation up to one rounding).

Engine contract: the kernel EMITS the raw ``(l_s, l_c, acc_s, acc_c)``
accumulator grids — finalization (``scheme.finalize`` on both pairs, then
the ``acc / l`` division) happens in ``CompensatedReduction``, which also
owns Sq/Skv padding, compute-dtype promotion, and interpret resolution.
The public ``flash_attention`` below is a thin policy-resolving veneer
over the engine; ``kernels.ref.flash_attention_ref`` traces the SAME
scheme callables block-for-block, so kernel-vs-oracle equality is bitwise
in interpret mode (on a TPU it is a tolerance; see ``flash_block_update``).

Layout: inputs [BH, S, dh] (batch*heads flattened by the caller); grid
(BH, q_blocks, k_blocks), k innermost ("arbitrary"); per-(bh, q-block)
scratch in VMEM: m, l, l_c, acc, acc_c. Causal masking from block
coordinates; ``kv_len`` masks engine-padded key positions (so non-causal
inputs may be padded too). Rows whose blocks are entirely masked still
execute but contribute exp(-inf)=0 — acceptable for the validation
kernel; a production variant would prune the grid.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.schemes import CompensationScheme

NEG_INF = -1e30


def rowsum_tree(p: jax.Array) -> jax.Array:
    """Deterministic row-sum: [bq, bk] -> [bq, 1] by a power-of-two
    pairwise tree of ELEMENTWISE adds.

    ``jnp.sum`` (and even a dot-against-ones, which XLA's simplifier
    rewrites back into a reduce) may fuse/vectorize with a different
    association order depending on the surrounding computation, breaking
    the kernel-vs-oracle bitwise contract. Slice-and-add is elementwise
    only, so every tracing context executes the identical rounding
    sequence. Shared by ``_flash_kernel`` and ``ref.flash_attention_ref``.
    """
    n = p.shape[-1]
    p2 = 1 << (n - 1).bit_length()
    if p2 != n:
        p = jnp.pad(p, ((0, 0), (0, p2 - n)))
    while p.shape[-1] > 1:
        half = p.shape[-1] // 2
        p = p[:, :half] + p[:, half:]
    return p


def flash_block_update(scheme: CompensationScheme, q, k, v, m_old,
                       l_s, l_c, a_s, a_c, *, qb, kb, step, block_q: int,
                       block_k: int, kv_len: int, causal: bool,
                       scale: float, compute_dtype=jnp.float32,
                       q_off=None, pin: bool = True):
    """ONE k-block fold of the online-softmax state — the shared body.

    Traced by BOTH the Pallas kernel (block refs) and the jnp oracle
    (array slices), exactly like the scheme callables are shared by the
    dot kernels and their oracles — kernel-vs-oracle bitwise equality by
    construction. With ``pin`` (the default) every fusion-sensitive op
    (dot, mul, reduce, exp, select) is pinned behind
    ``lax.optimization_barrier``: XLA CPU contracts mul+add chains into
    FMAs, inlines exp into consumer loops with a different rounding
    path, and rematerializes producers across fusion boundaries — all
    decisions that vary with the surrounding program and would otherwise
    let the same math round differently in the kernel and the oracle.

    The Pallas kernels pass ``pin=interpret``: interpret mode and the
    oracle both run on XLA and keep their barriers, so there the
    equality stays bitwise. Mosaic has no lowering for
    ``optimization_barrier``, so a compiled TPU kernel traces the body
    unpinned. Kernel (Mosaic) and oracle (XLA) are then different
    compilers, and on a TPU the kernel agrees with
    ``ref.flash_attention_ref`` to a tolerance (``chip_smoke.py`` holds
    both to ``FLASH_TOL``), not bitwise.

    Inputs are one block each: q [bq, dh]; k/v [bk, dh]; running stats
    m_old/l/l_c [bq, 1], a/a_c [bq, dh]. Returns the updated
    (m, l_s, l_c, a_s, a_c).

    ``q_off`` (optional, traced i32 scalar): absolute position of query
    row 0 of the WHOLE q operand — the chunked-prefill entry point
    (``flash_chunk_accumulators``) attends a chunk of queries that live
    at positions ``q_off + i`` of the sequence against the full KV
    cache. Shifting ``q_pos`` is integer arithmetic (exact), so when a
    chunk's absolute positions coincide with a full-sequence call's,
    the per-block float op sequence — and therefore the output bits —
    is identical. ``None`` (the default) keeps the traced program of
    the non-offset paths byte-for-byte unchanged.
    """
    barrier = jax.lax.optimization_barrier if pin else (lambda x: x)
    # HIGHEST on both products: a TPU's default contraction precision
    # rounds f32 operands to one bf16 pass
    s = barrier(jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),  # contract: allow-no-uncompensated-reduction(flash scores; compute_dtype over head_dim terms, block-local)
                                    precision=jax.lax.Precision.HIGHEST,
                                    preferred_element_type=compute_dtype))
    s = barrier(s * scale)
    q_pos = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    if q_off is not None:
        q_pos = q_off + q_pos
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = k_pos < kv_len                       # engine-padded keys
    if causal:
        valid = valid & (q_pos >= k_pos)
    s = barrier(jnp.where(valid, s, NEG_INF))
    m_new = barrier(jnp.maximum(m_old, barrier(
        jnp.max(s, axis=-1, keepdims=True))))
    corr = barrier(jnp.exp(barrier(m_old - m_new)))   # [bq, 1]
    p = barrier(jnp.exp(barrier(s - m_new)))          # [bq, bk]
    p_sum = barrier(rowsum_tree(p))
    pv = barrier(jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),  # contract: allow-no-uncompensated-reduction(flash PV block product; the scheme accumulator fold below carries the compensation)
                                     precision=jax.lax.Precision.HIGHEST,
                                     preferred_element_type=compute_dtype))
    # rescale value AND comp, then fold this k-block's contribution
    # through the scheme's accumulator update.
    ls_r = barrier(l_s * corr)
    lc_r = barrier(l_c * corr)
    as_r = barrier(a_s * corr)
    ac_r = barrier(a_c * corr)
    l_s, l_c = scheme.update(ls_r, lc_r, p_sum, step)
    a_s, a_c = scheme.update(as_r, ac_r, pv, step)
    return m_new, l_s, l_c, a_s, a_c


def flash_block_probe(scheme=None, *, block_q: int = 8, block_k: int = 8,
                      dh: int = 8, kv_len: int = 8, causal: bool = True,
                      compute_dtype=None, with_offset: bool = False):
    """(callable, abstract args) for tracing ONE block body standalone.

    The trace auditor (``repro.analysis.trace``) traces this and asserts
    the resulting primitive sequence appears contiguously in BOTH the
    Pallas kernel's and the jnp oracle's jaxprs — the compiled-truth form
    of the shared-block-body discipline documented on
    ``flash_block_update``. Abstract ``ShapeDtypeStruct`` args (never
    weak-typed literals) so the standalone trace is equation-for-equation
    the one the kernel and oracle embed.

    ``with_offset``: probe the chunked-prefill variant of the body —
    one extra traced i32 scalar (``q_off``) appended to the args, fed to
    ``flash_block_update(..., q_off=...)`` exactly as the chunk kernel
    does, so the flash-prefill trace targets can pin THAT primitive
    sequence.
    """
    from repro.kernels import schemes as _schemes

    sch = _schemes.resolve_scheme(scheme)
    cdt = _schemes.resolve_compute_dtype(compute_dtype)
    s = jax.ShapeDtypeStruct
    i32 = jnp.int32
    args = (s((block_q, dh), cdt), s((block_k, dh), cdt),
            s((block_k, dh), cdt), s((block_q, 1), cdt),
            s((block_q, 1), cdt), s((block_q, 1), cdt),
            s((block_q, dh), cdt), s((block_q, dh), cdt),
            s((), i32), s((), i32), s((), i32))
    if with_offset:
        args = args + (s((), i32),)

        def run(q, k, v, m_old, l_s, l_c, a_s, a_c, qb, kb, step, q_off):
            return flash_block_update(
                sch, q, k, v, m_old, l_s, l_c, a_s, a_c, qb=qb, kb=kb,
                step=step, block_q=block_q, block_k=block_k, kv_len=kv_len,
                causal=causal, scale=dh ** -0.5, compute_dtype=cdt,
                q_off=q_off)

        return run, args

    def run(q, k, v, m_old, l_s, l_c, a_s, a_c, qb, kb, step):
        return flash_block_update(
            sch, q, k, v, m_old, l_s, l_c, a_s, a_c, qb=qb, kb=kb,
            step=step, block_q=block_q, block_k=block_k, kv_len=kv_len,
            causal=causal, scale=dh ** -0.5, compute_dtype=cdt)

    return run, args


def _flash_kernel(q_ref, k_ref, v_ref, ls_out, lc_out, as_out, ac_out,
                  m_scr, l_scr, lc_scr, acc_scr, accc_scr, *,
                  scheme: CompensationScheme, causal: bool, block_q: int,
                  block_k: int, k_steps: int, kv_len: int, scale: float,
                  compute_dtype=jnp.float32, interpret: bool):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        lc_scr[...] = jnp.zeros_like(lc_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        accc_scr[...] = jnp.zeros_like(accc_scr)

    q = q_ref[0].astype(compute_dtype)          # [bq, dh]
    k = k_ref[0].astype(compute_dtype)          # [bk, dh]
    v = v_ref[0].astype(compute_dtype)

    m_new, l_s, l_c, a_s, a_c = flash_block_update(
        scheme, q, k, v, m_scr[...], l_scr[...], lc_scr[...],
        acc_scr[...], accc_scr[...], qb=pl.program_id(1), kb=kb, step=kb,
        block_q=block_q, block_k=block_k, kv_len=kv_len, causal=causal,
        scale=scale, compute_dtype=compute_dtype, pin=interpret)
    l_scr[...] = l_s
    lc_scr[...] = l_c
    acc_scr[...] = a_s
    accc_scr[...] = a_c
    m_scr[...] = m_new

    @pl.when(kb == k_steps - 1)
    def _emit():
        ls_out[0] = l_scr[...]
        lc_out[0] = lc_scr[...]
        as_out[0] = acc_scr[...]
        ac_out[0] = accc_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "scheme", "causal", "kv_len",
                     "interpret", "q_groups", "compute_dtype"))
def flash_accumulators(q, k, v, *, block_q, block_k,
                       scheme: CompensationScheme, causal, kv_len,
                       interpret, q_groups: int = 1,
                       compute_dtype=jnp.float32,
                       ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Run the flash grid; returns the raw (l_s, l_c, acc_s, acc_c) grids.

    ``q``: [BH, Sq, dh]; ``k``/``v``: [BH // q_groups, Skv, dh], already
    promoted to ``compute_dtype`` and padded to block multiples by the
    engine. ``kv_len`` is the un-padded key count (padded keys are
    masked). l grids are [BH, Sq, 1]; acc grids [BH, Sq, dh].

    ``q_groups``: the GQA group factor G. Query head-rows are laid out
    [..., kv_head, group] (G consecutive q rows per kv head), so the k/v
    BlockSpec index map fetches block ``bh // G`` — each k/v head is
    read once per group straight from its single copy; the duplication
    never leaves the index map (no broadcast materialization).
    """
    bh, sq, dh = q.shape
    _, skv, _ = k.shape
    assert sq % block_q == 0 and skv % block_k == 0
    assert bh == k.shape[0] * q_groups, (q.shape, k.shape, q_groups)
    grid = (bh, sq // block_q, skv // block_k)
    scale = dh ** -0.5

    kernel = functools.partial(
        _flash_kernel, scheme=scheme, causal=causal, block_q=block_q,
        block_k=block_k, k_steps=grid[2], kv_len=kv_len, scale=scale,
        compute_dtype=compute_dtype, interpret=interpret)
    return pl.pallas_call(
        kernel,
        name="flash_accumulators",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda b, i, j: (b // q_groups, j, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda b, i, j: (b // q_groups, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, 1), compute_dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), compute_dtype),
            jax.ShapeDtypeStruct((bh, sq, dh), compute_dtype),
            jax.ShapeDtypeStruct((bh, sq, dh), compute_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), compute_dtype),    # m
            pltpu.VMEM((block_q, 1), compute_dtype),    # l
            pltpu.VMEM((block_q, 1), compute_dtype),    # l comp
            pltpu.VMEM((block_q, dh), compute_dtype),   # acc
            pltpu.VMEM((block_q, dh), compute_dtype),   # acc comp
        ],
        interpret=interpret,
    )(q, k, v)


def _flash_chunk_kernel(off_ref, q_ref, k_ref, v_ref, ls_out, lc_out,
                        as_out, ac_out, m_scr, l_scr, lc_scr, acc_scr,
                        accc_scr, *, scheme: CompensationScheme,
                        block_q: int, block_k: int, k_steps: int,
                        kv_len: int, scale: float,
                        compute_dtype=jnp.float32, interpret: bool):
    """Chunked-prefill grid body: ``_flash_kernel`` plus a traced query
    offset read from SMEM. Queries live at absolute positions
    ``q_off + i``; masking is always causal on those absolute positions,
    which is also what excludes cache rows not yet written (a causal
    query at position p never reads keys past p)."""
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        lc_scr[...] = jnp.zeros_like(lc_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        accc_scr[...] = jnp.zeros_like(accc_scr)

    q = q_ref[0].astype(compute_dtype)          # [bq, dh]
    k = k_ref[0].astype(compute_dtype)          # [bk, dh]
    v = v_ref[0].astype(compute_dtype)

    m_new, l_s, l_c, a_s, a_c = flash_block_update(
        scheme, q, k, v, m_scr[...], l_scr[...], lc_scr[...],
        acc_scr[...], accc_scr[...], qb=pl.program_id(1), kb=kb, step=kb,
        block_q=block_q, block_k=block_k, kv_len=kv_len, causal=True,
        scale=scale, compute_dtype=compute_dtype, q_off=off_ref[0, 0],
        pin=interpret)
    l_scr[...] = l_s
    lc_scr[...] = l_c
    acc_scr[...] = a_s
    accc_scr[...] = a_c
    m_scr[...] = m_new

    @pl.when(kb == k_steps - 1)
    def _emit():
        ls_out[0] = l_scr[...]
        lc_out[0] = lc_scr[...]
        as_out[0] = acc_scr[...]
        ac_out[0] = accc_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "scheme", "kv_len", "interpret",
                     "q_groups", "compute_dtype"))
def flash_chunk_accumulators(q, k, v, q_off, *, block_q, block_k,
                             scheme: CompensationScheme, kv_len,
                             interpret, q_groups: int = 1,
                             compute_dtype=jnp.float32,
                             ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                        jax.Array]:
    """Chunked-prefill flash grid: a chunk of queries at a TRACED offset
    attends the full KV cache. Returns raw (l_s, l_c, acc_s, acc_c).

    ``q``: [BH, W, dh] — the chunk's queries, at absolute sequence
    positions ``q_off + i``. ``k``/``v``: [BH // q_groups, Skv, dh] —
    the slot's whole cache (the chunk's own K/V already written at
    ``q_off``), padded to block multiples by the engine. ``q_off`` is a
    traced i32 scalar fed through SMEM, so one compiled program serves
    every chunk of the same width — the serving engine's O(#buckets)
    program-set bound survives the flash path. Masking is always causal
    on absolute positions (which subsumes excluding cache rows past the
    chunk: a causal query never reads keys beyond itself); ``kv_len``
    is static and masks only engine padding. Same block body
    (``flash_block_update``) as the full grid, so rows whose absolute
    positions coincide with a full-sequence call's are bitwise equal.
    """
    bh, w, dh = q.shape
    _, skv, _ = k.shape
    assert w % block_q == 0 and skv % block_k == 0
    assert bh == k.shape[0] * q_groups, (q.shape, k.shape, q_groups)
    grid = (bh, w // block_q, skv // block_k)
    scale = dh ** -0.5
    off = jnp.asarray(q_off, jnp.int32).reshape(1, 1)

    kernel = functools.partial(
        _flash_chunk_kernel, scheme=scheme, block_q=block_q,
        block_k=block_k, k_steps=grid[2], kv_len=kv_len, scale=scale,
        compute_dtype=compute_dtype, interpret=interpret)
    return pl.pallas_call(
        kernel,
        name="flash_chunk_accumulators",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda b, i, j: (b // q_groups, j, 0)),
            pl.BlockSpec((1, block_k, dh),
                         lambda b, i, j: (b // q_groups, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, w, 1), compute_dtype),
            jax.ShapeDtypeStruct((bh, w, 1), compute_dtype),
            jax.ShapeDtypeStruct((bh, w, dh), compute_dtype),
            jax.ShapeDtypeStruct((bh, w, dh), compute_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), compute_dtype),    # m
            pltpu.VMEM((block_q, 1), compute_dtype),    # l
            pltpu.VMEM((block_q, 1), compute_dtype),    # l comp
            pltpu.VMEM((block_q, dh), compute_dtype),   # acc
            pltpu.VMEM((block_q, dh), compute_dtype),   # acc comp
        ],
        interpret=interpret,
    )(off, q, k, v)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    block_q: int = 256, block_k: int = 256,
                    scheme: Union[str, CompensationScheme, None] = None,
                    causal: bool = True, interpret: Optional[bool] = None,
                    q_groups: int = 1) -> jax.Array:
    """q: [BH, Sq, dh]; k/v: [BH // q_groups, Skv, dh]. Returns
    [BH, Sq, dh] in the engine's compute dtype.

    Thin veneer over ``CompensatedReduction.flash_attention``: the engine
    owns padding (Sq/Skv to block multiples; padded keys masked),
    compute-dtype promotion, interpret resolution, and finalization of the
    (l, acc) accumulator pairs. ``scheme``: registered scheme name /
    CompensationScheme / Policy / None (None resolves the ambient
    ``use_policy`` default). ``q_groups``: GQA group factor — grouped k/v
    heads are shared through the kernel's BlockSpec index map
    (``bh // G``), never broadcast-materialized.
    """
    from repro.kernels.engine import CompensatedReduction

    eng = CompensatedReduction(scheme=scheme, interpret=interpret)
    return eng.flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                               causal=causal, q_groups=q_groups)


def flash_chunk_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          q_off: jax.Array, block_q: int = 256,
                          block_k: int = 256,
                          scheme: Union[str, CompensationScheme, None] = None,
                          interpret: Optional[bool] = None,
                          q_groups: int = 1) -> jax.Array:
    """Chunked-prefill veneer: q [BH, W, dh] at traced absolute offset
    ``q_off`` attends the full cached k/v [BH // q_groups, Skv, dh].
    Always causal on absolute positions. Engine owns padding / promotion
    / finalization exactly as in ``flash_attention``; see
    ``CompensatedReduction.flash_chunk_attention``."""
    from repro.kernels.engine import CompensatedReduction

    eng = CompensatedReduction(scheme=scheme, interpret=interpret)
    return eng.flash_chunk_attention(q, k, v, q_off=q_off, block_q=block_q,
                                     block_k=block_k, q_groups=q_groups)
