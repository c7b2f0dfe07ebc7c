"""Request-level continuous-batching inference engine.

``InferenceEngine`` replaces the lock-step batch decoder with a
request-level API::

    engine = InferenceEngine(cfg, EngineConfig(max_slots=8, max_len=512))
    handle = engine.submit(Request(prompt=[3, 1, 4], sampling=SamplingParams(
        temperature=0.7, max_new_tokens=32)))
    while not handle.done:
        engine.step()                 # one engine tick
    print(handle.tokens, handle.telemetry)

Scheduling model: a fixed decode batch of ``max_slots`` per-slot caches
(``repro.serve.slots``). Each ``step()`` first admits queued requests
into free slots (they enter the PREFILLING lifecycle state and own the
slot's pristine cache row), then runs CHUNKED PREFILL — at most
``EngineConfig.prefill_budget`` fixed-size prompt chunks across the
prefilling requests, oldest first — and finally ONE decode tick over the
slots whose requests are RUNNING. Finished requests free their slot
mid-flight for the next step's admissions.

CHUNKED PREFILL (why): the one-shot admit of PR 4 compiled one XLA
program per DISTINCT PROMPT LENGTH (a mixed-length trace recompiled on
nearly every admission) and ran a whole prompt's prefill inside one
step() (a single long prompt stalled every occupied decode slot for its
full prefill — head-of-line blocking). Now a prompt is split into
fixed-size chunks of ``EngineConfig.prefill_chunk`` tokens; the last
partial chunk is zero-padded up to a small power-of-two BUCKET (padded
steps are computed and exactly discarded), so the compiled prefill
program set is O(#buckets) ≈ log2(prefill_chunk), not O(#distinct
prompt lengths); and with a chunk budget set, the time-to-next-decode-
token of already-running requests is bounded by ``prefill_budget``
chunks instead of a whole prompt. ``prefill_chunk=None`` keeps the
legacy one-shot admit (whole prompt in one per-length program) as the
baseline the tests and benchmarks compare against.

THE NUMERICS CONTRACT (the serving-layer analogue of the engine's
batched-vs-loop guarantee): a request's emitted tokens and its
compensated logit-norm telemetry are BITWISE IDENTICAL (a) whether it
runs alone or interleaved with arbitrary other traffic, AND (b) whether
its prompt is prefilled one-shot or in chunks of any size — for every
registered compensation scheme. Four mechanisms carry it:

* ALL prefill — one-shot and every chunk width — scans ONE shared
  per-position traced body (``models.common.prefill_chunk_scan`` over
  the family's ``decode_step``) with ``lax.optimization_barrier``
  pinning the body boundary and TRACED offset/position/validity
  operands. Programs differ only in scan trip count and discarded pad
  steps, so every prompt position executes the identical rounding
  sequence whatever program computes it — the same shared-traced-body
  discipline as the kernels' block-body/oracle equality. The chunk
  schedule is a pure function of (prompt_len, prefill_chunk): scheduler
  choices (budget, interleaving, slot placement) cannot leak into a
  request's bits;
* the decode tick runs ONE pinned batched decode body over the fixed
  ``max_slots`` slot axis: the single-request ``decode_one`` mapped over
  the slots with ``jax.vmap``, with ``lax.optimization_barrier`` pins on
  the whole batched call's inputs and outputs. The width never changes
  with occupancy, so a solo request runs the same program as an
  interleaved one and only its row index differs; and nothing in the
  body reduces across the slot axis (matmuls, norms, attention and
  sampling are all row-local), so a row's bits cannot depend on its
  neighbours. That every row of a batched op rounds alike, whatever its
  index, is the backend's part: the serving tests check it on the CPU
  and ``chip_smoke.py`` at 4 and 16 slots on the TPU. Every slot shares
  one read of the weights per tick. The
  tick updates ONLY the rows of RUNNING slots — free and PREFILLING
  rows keep their bits through an exact post-body select, which is what
  lets a partially prefilled row live in the slot cache while its
  neighbours decode;
* prefill chunk programs operate on the request's own batch-1 row
  (gathered from / scattered back to its slot in-trace), so the
  program depends only on the request's own prompt;
* sampling keys fold from per-request state only
  (``fold_in(fold_in(engine_key, request.seed), emit_index)``), and the
  per-request telemetry reduction runs on the engine's batched
  ``(batch, steps)`` grid with the deterministic two-sum merge, which is
  row-wise bitwise-equal to a per-request loop (PR 1's contract).

ONE ``repro.kernels.Policy`` (``EngineConfig.policy``) selects the
compensation scheme / unroll / accumulate dtype for everything the
engine computes — the telemetry norms here, and the model's own
projections when ``ArchConfig.kahan_matmul`` routes them through the
kernels.

PARALLEL (FLASH) PREFILL (``EngineConfig.prefill_mode = "flash"``): the
per-position scan body above is decode-speed — a w-token chunk costs w
sequential steps. The flash mode swaps in the families'
``prefill_chunk_parallel``: ONE forward pass over the whole chunk, with
attention running through the engine's chunk flash kernel
(``CompensatedReduction.flash_chunk_attention`` — compensated online
softmax against the slot's full KV cache at a TRACED offset, causal on
absolute positions) and the projections through ``ops.matmul`` when
``ArchConfig.kahan_matmul`` — so ``kahan_attention``'s kernel now
serves traffic and prefill tokens/s scales with chunk width (the
paper's "compensation is free once you vectorize", in serving form).
Contract under flash mode: solo-vs-interleaved stays BITWISE (chunk
programs are keyed by (width, runs_begin) only and operate on the
request's own gathered row); chunked-vs-one-shot compares EXACT tokens
with a pinned, documented telemetry tolerance — XLA vectorizes the
fused softmax/projection ops shape-dependently across widths, so
cross-width equality is allclose-at-~1-ulp, not bitwise. The
per-position scan body REMAINS the oracle (and the default). Families
whose recurrence forces per-position stepping — hybrid (ring-buffer
window KV + SSM state) and xLSTM (recurrent cell state) — and configs
the parallel body cannot serve (MLA, MoE capacity routing, sliding
window) fall back to the scan body; ``engine.prefill_body`` reports
the resolved choice.

PAGED KV LAYOUT (``EngineConfig.kv_layout = "paged"``): the dense
``SlotKVCache`` pins ``max_slots * max_len`` positions per KV leaf
whether or not anyone lives there. The paged layout re-homes every
PAGEABLE leaf (position-addressed KV history — ``repro.serve.paging``)
into a fixed pool of ``num_pages`` pages of ``page_size`` positions,
addressed per request through a traced page-table operand, so live KV
memory scales with live tokens and one compiled program serves every
page placement. THE DENSE LAYOUT REMAINS THE DEFAULT AND THE BITWISE
ORACLE: a request's tokens and telemetry are bitwise identical under
either layout, and identical whether its pages are contiguous or
scattered — carried by the same ``optimization_barrier``-pinned
batched decode body and prefill chunk body in BOTH layouts (identical
pinned interiors; only the exact-data-movement gather/scatter differs)
plus the zero-fill gather / zero-reset-on-free pristine-bits guarantee.
Page reservation is whole-request at admission (never in a trace,
never mid-decode; exhaustion blocks admission FIFO — the ALLOCATING
state), and ``EngineConfig.prefix_cache`` adds a refcounted radix tree
(``repro.serve.prefix``) over prefilled prompts so shared prefixes
admit by reference and resume prefill at the shared page boundary.
Recurrent-only families (SSM/xLSTM, all-window hybrids) have no
pageable leaf and fall back to dense; ``engine.kv_layout`` reports the
resolved layout, ``engine.page_stats()`` the pool accounting.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Deque, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.base import ArchConfig
from repro.kernels import schemes as _schemes
from repro.kernels.schemes import Policy, use_policy
from repro.models import build_model
from repro.serve.paging import (
    PageAllocator,
    PagedKVCache,
    paged_gather_row,
    paged_gather_rows,
    paged_scatter_decode,
    paged_scatter_row,
    pages_for,
)
from repro.serve.prefix import PrefixNode, RadixPrefixTree
from repro.serve.scheduler import (
    ALLOCATING,
    QUEUED,
    Request,
    RequestHandle,
    SamplingParams,
    SlotScheduler,
)
from repro.serve.slots import (
    SlotKVCache,
    _donate,
    gather_row,
    scatter_row,
    select_live,
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine-level (not per-request) serving configuration.

    max_slots      decode batch width: concurrent requests served per tick
    max_len        per-slot cache capacity (prompt + generated tokens)
    track_stats    record the compensated squared logit norm per emitted
                   token (the per-request telemetry trace)
    policy         ONE Policy for every compensated reduction the engine
                   runs; None captures the ambient ``use_policy`` default
                   at engine construction
    sample_seed    seed of the engine-level sampling key; per-request
                   streams fold their ``SamplingParams.seed`` into it
    prefill_chunk  prompt-chunk width for chunked prefill (the compiled
                   prefill program set is {prefill_chunk} plus power-of-
                   two tail buckets below it). None = legacy one-shot
                   admit: the whole prompt in ONE program per distinct
                   prompt length — bitwise-identical to the chunked path
                   but O(#lengths) compiles and unbounded admit stalls
    prefill_budget max prefill chunks run per ``step()`` across all
                   PREFILLING requests (oldest first); None = unbounded
                   (every admitted request finishes its prefill within
                   the admitting step — one-shot-era step timing). Set
                   to 1 to bound already-running requests' time-to-next-
                   token by a single chunk of prefill work
    max_finished   retain at most this many FINISHED handles in
                   ``engine.handles`` (oldest-finished evicted first);
                   None = retain all (callers can still drain with
                   ``pop_finished()``)
    prefill_mode   which traced body advances a prefill chunk: "scan"
                   (default — the per-position ``lax.scan`` of the
                   family's decode body; carries the cross-width bitwise
                   contract and stays the oracle) or "flash" (the
                   parallel multi-token chunk body: ONE forward pass per
                   chunk through the engine's chunk flash kernel /
                   ``ops.matmul`` — prefill becomes MXU work and tokens/s
                   scales with chunk width). Families whose recurrence
                   forces per-position stepping (hybrid ring/SSM, xLSTM)
                   — and configs the parallel body cannot serve (MLA,
                   MoE capacity routing, sliding window) — fall back to
                   the scan body under "flash"; see
                   ``InferenceEngine.prefill_body``
    kv_layout      how pageable cache leaves are stored: "dense"
                   (default AND the bitwise oracle — ``SlotKVCache``
                   rows of max_slots x max_len) or "paged" (a fixed
                   page pool with per-request page tables,
                   ``repro.serve.paging`` — live memory scales with
                   live tokens). Families with no pageable leaf
                   (SSM/xLSTM recurrence, all-window hybrids) fall back
                   to dense; ``InferenceEngine.kv_layout`` reports the
                   resolved layout
    page_size      positions per page (power of two; max_len must be a
                   multiple). Smaller pages track live tokens tighter
                   and share prefixes at finer grain; larger pages cut
                   table length and gather/scatter op count
    num_pages      pool capacity in pages; None = dense parity
                   (max_slots * max_len / page_size). Admission blocks
                   (deterministic FIFO) when the pool runs short;
                   requests that could never fit fail fast at submit
    prefix_cache   keep requests' full prompt pages, from the end of
                   their prefill, in a refcounted radix tree
                   (``repro.serve.prefix``) so a request with a resident
                   prompt prefix admits by reference and resumes
                   prefill at the shared offset. Paged layout only
    """

    max_slots: int = 4
    max_len: int = 512
    track_stats: bool = False
    policy: Optional[Policy] = None
    sample_seed: int = 0
    prefill_chunk: Optional[int] = 64
    prefill_budget: Optional[int] = None
    max_finished: Optional[int] = None
    prefill_mode: str = "scan"
    kv_layout: str = "dense"
    page_size: int = 16
    num_pages: Optional[int] = None
    prefix_cache: bool = False

    def __post_init__(self):
        if self.prefill_mode not in ("scan", "flash"):
            raise ValueError(
                f"prefill_mode must be 'scan' or 'flash', "
                f"got {self.prefill_mode!r}")
        if self.kv_layout not in ("dense", "paged"):
            raise ValueError(
                f"kv_layout must be 'dense' or 'paged', "
                f"got {self.kv_layout!r}")
        if self.max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {self.max_len}")
        if self.kv_layout == "paged":
            ps = self.page_size
            if ps < 1 or (ps & (ps - 1)):
                raise ValueError(
                    f"page_size must be a power of two >= 1, got {ps}")
            if self.max_len % ps:
                raise ValueError(
                    f"max_len={self.max_len} must be a multiple of "
                    f"page_size={ps}")
            if self.num_pages is not None and self.num_pages < 1:
                raise ValueError(
                    f"num_pages must be >= 1 (or None for dense parity), "
                    f"got {self.num_pages}")
        if self.prefix_cache and self.kv_layout != "paged":
            raise ValueError(
                "prefix_cache=True requires kv_layout='paged' (prefix "
                "sharing is page-granular)")
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 (or None for one-shot "
                f"prefill), got {self.prefill_chunk}")
        if self.prefill_budget is not None and self.prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be >= 1 (or None for unbounded), "
                f"got {self.prefill_budget}")
        if self.max_finished is not None and self.max_finished < 0:
            raise ValueError(
                f"max_finished must be >= 0 (or None to retain all), "
                f"got {self.max_finished}")


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One emitted token, as surfaced by ``step()`` / ``stream()``."""

    request_id: int
    token: int
    norm: Optional[float]    # compensated |logits|^2 (None if not tracked)
    done: bool


def _bucket(n: int, chunk: int) -> int:
    """Smallest power-of-two >= n, capped at the chunk width — the
    static widths a partial tail chunk may compile to."""
    b = 1
    while b < n:
        b *= 2
    return min(b, chunk)


def _next_chunk(prompt_len: int, offset: int, chunk: Optional[int],
                ) -> Tuple[int, int]:
    """(width, nvalid) of the next prefill chunk at ``offset``.

    A pure function of the request's own prompt length and the engine's
    static chunk width — scheduler state cannot influence it, which is
    half of the chunked bitwise contract."""
    remaining = prompt_len - offset
    if chunk is None:                       # one-shot: whole prompt
        return prompt_len, prompt_len
    if remaining > chunk:
        return chunk, chunk
    return _bucket(remaining, chunk), remaining


def prefill_program_family(max_len: int, chunk: Optional[int],
                           needs_begin: bool,
                           ) -> frozenset:
    """Every (width, runs_begin) prefill-program key ANY traffic can need.

    A pure sweep of the ``_next_chunk`` schedule over all prompt lengths
    1..max_len — the exhaustive program set a given engine config can
    compile, which the trace auditor's ``trace-program-count`` rule
    bounds against ``prefill_program_bound``. ``chunk=None`` (one-shot
    admit) yields one width per distinct prompt length, the O(#lengths)
    behaviour the chunked path exists to avoid.
    """
    keys = set()
    for plen in range(1, max_len + 1):
        offset = 0
        first = needs_begin
        while offset < plen:
            width, nvalid = _next_chunk(plen, offset, chunk)
            keys.add((width, first))
            offset += nvalid
            first = False
    return frozenset(keys)


def prefill_program_bound(chunk: int, needs_begin: bool) -> int:
    """The O(#buckets) cap on the compiled prefill program set.

    Widths are the power-of-two tail buckets up to ``chunk`` plus
    ``chunk`` itself; each width compiles at most once per
    ``runs_begin`` flavour (twice only for families with a one-time
    ``prefill_begin``). One-shot engines (``chunk=None``) have no such
    bound — that IS the contract violation — so this fails fast on None.
    """
    if chunk is None:
        raise ValueError(
            "one-shot admit (prefill_chunk=None) has no O(#buckets) "
            "program bound — its program set is O(#distinct prompt "
            "lengths)")
    widths = {chunk}
    b = 1
    while b <= chunk:
        widths.add(b)
        b *= 2
    return len(widths) * (2 if needs_begin else 1)


class _ServePrograms:
    """The engine's compiled callables: one decode ``tick`` plus
    lazily-built prefill chunk programs keyed by (width, runs_begin) —
    the ONLY shape parameters a chunk program has, which is what makes
    the compiled prefill program set O(#buckets). ``prefill_body``
    records which chunk body the programs trace ("scan" or "flash" —
    the RESOLVED body, after any family fallback)."""

    def __init__(self, tick, prefill_factory, prefill_body: str = "scan"):
        self.tick = tick
        self.prefill_body = prefill_body
        self._factory = prefill_factory
        self._prefill: Dict[Tuple[int, bool], Any] = {}

    def prefill(self, width: int, first: bool):
        key = (width, first)
        if key not in self._prefill:
            self._prefill[key] = self._factory(width, first)
        return self._prefill[key]


def _compiled_fns(model, cfg: ArchConfig, ec: EngineConfig, policy: Policy,
                  batch_axes, page_axes=None) -> _ServePrograms:
    """Build (or fetch) the engine's jitted callables.

    Cached ON the model object keyed by the engine signature, so several
    engines over the same model instance (e.g. a solo-replay or one-shot
    reference engine next to the serving engine in the determinism
    tests) share compiled code — widths shared between a chunked and a
    one-shot engine resolve to the SAME program.

    ``page_axes`` non-None selects the PAGED program family (the
    engine's RESOLVED layout, after the no-pageable-leaf fallback): the
    tick and the prefill chunk programs take each request's page table
    as a traced operand and assemble/write its logical row through
    ``repro.serve.paging`` — one compiled program for ANY page
    placement. The compute between gather and scatter is the same
    barrier-pinned batched decode body / chunk body the dense programs
    run.
    """
    # Resolve the chunk body ONCE: "flash" engines over a family whose
    # recurrence forces per-position stepping (hybrid ring/SSM, xLSTM —
    # no ``prefill_chunk_parallel``) or whose config the parallel body
    # cannot serve (``parallel_prefill_ok`` False: MLA, MoE, sliding
    # window) fall back to the scan body. The cache key carries the
    # RESOLVED body, so a flash engine over a fallback family shares its
    # programs with the scan engine.
    prefill_body = "scan"
    if (ec.prefill_mode == "flash"
            and getattr(model, "parallel_prefill_ok", False)
            and hasattr(model, "prefill_chunk_parallel")):
        prefill_body = "flash"
    layout = "dense" if page_axes is None else ("paged", ec.page_size)
    key = ("serve", ec.max_slots, ec.max_len, ec.track_stats,
           ec.sample_seed, policy, prefill_body, layout)
    cache = model.__dict__.setdefault("_serve_compiled", {})
    if key in cache:
        return cache[key]

    vocab = cfg.vocab_size
    base_key = jax.random.key(ec.sample_seed)  # contract: allow-no-raw-prngkey(the engine IS the key boundary — requests fold_in from this root)

    def sample_row(logits_row, key, temp):
        """Per-request sampling: greedy at temp<=0, categorical above.
        Purely row-local (one key, one logit row) — both branches are
        computed and selected so the traced program is temp-agnostic."""
        greedy = jnp.argmax(logits_row).astype(jnp.int32)
        safe_t = jnp.where(temp > 0, temp, jnp.float32(1.0))
        samp = jax.random.categorical(
            key, logits_row.astype(jnp.float32) / safe_t).astype(jnp.int32)
        return jnp.where(temp > 0, samp, greedy)

    def _norms(logits):
        """[B, V_pad] -> [B] compensated squared logit norms on the
        engine's batched (batch, steps) grid. Valid-vocab slice only:
        the padded region carries a -1e30 mask bias whose square
        overflows fp32."""
        from repro.models.layers import activation_sq_norm

        return activation_sq_norm(logits[:, :vocab], scheme=policy)

    def decode_one(params, cache_row, token, pos, seed, eidx, temp):
        """ONE request's decode step — the row the batched body maps over
        the slots. Re-inserts the request axis (size 1) per cache leaf,
        runs the model's own decode_step, samples with the request's
        folded key."""
        cache1 = jax.tree.map(lambda x, a: jnp.expand_dims(x, a),
                              cache_row, batch_axes)
        logits, new_cache = model.decode_step(params, cache1, token[None],
                                              pos)
        new_row = jax.tree.map(lambda x, a: jnp.squeeze(x, a),
                               new_cache, batch_axes)
        k = jax.random.fold_in(jax.random.fold_in(base_key, seed), eidx)
        tok = sample_row(logits[0], k, temp)
        return logits[0], new_row, tok

    decode_rows = jax.vmap(decode_one,
                           in_axes=(None, batch_axes, 0, 0, 0, 0, 0),
                           out_axes=(0, batch_axes, 0))

    def decode_slots(params, rows, tokens, pos, seeds, eidx, temps):
        """The pinned batched decode body: ``decode_one`` over all
        ``max_slots`` rows at once, so the weights are read once a tick.
        The width is fixed and no op reduces across rows, so a row's
        bits depend on its own inputs only. The pins sit around the
        whole batched call (``optimization_barrier`` has no batching
        rule): the dense tick feeds the cache as it is, the paged tick
        rows gathered through page tables, and the pins keep XLA from
        fusing either data movement INTO the arithmetic — both layouts
        execute this identical interior, which is the paged-vs-dense
        half of the serving bitwise contract (module docstring)."""
        args = jax.lax.optimization_barrier(
            (rows, tokens, pos, seeds, eidx, temps))
        return jax.lax.optimization_barrier(decode_rows(params, *args))

    def _norms_or_zeros(logits):
        return (_norms(logits) if ec.track_stats
                else jnp.zeros((ec.max_slots,), jnp.float32))

    if page_axes is not None:
        # Paged tick: gather every slot's logical row through its table
        # in one batched gather (dense leaves are already rows), run the
        # SAME pinned body, then write back exactly ONE pool page per
        # slot — the one containing ``pos`` — in one scatter (dead slots
        # write the NULL page; dense leaves keep dead slots' old bits).
        @functools.partial(jax.jit, donate_argnums=tuple(
            1 + i for i in _donate()))
        def tick(params, cache, tokens, pos, seeds, eidx, temps, live,
                 tables, nres):
            with use_policy(policy):
                rows = paged_gather_rows(cache, batch_axes, page_axes,
                                         tables, nres)
                logits, new_rows, next_tok = decode_slots(
                    params, rows, tokens, pos, seeds, eidx, temps)
                new_cache = paged_scatter_decode(
                    cache, new_rows, batch_axes, page_axes, tables, pos,
                    live)
                norms = _norms_or_zeros(logits)
            return new_cache, next_tok, norms
    else:
        @functools.partial(jax.jit, donate_argnums=tuple(
            1 + i for i in _donate()))
        def tick(params, cache, tokens, pos, seeds, eidx, temps, live):
            with use_policy(policy):
                logits, new_cache, next_tok = decode_slots(
                    params, cache, tokens, pos, seeds, eidx, temps)
                # ONLY running slots advance: free and PREFILLING rows
                # keep their bits (a partially prefilled row must not be
                # stomped by the garbage compute of its own tick lane).
                # The select is exact and applied OUTSIDE the pinned
                # body, so live rows' bits are untouched.
                new_cache = jax.tree.map(
                    lambda n, o, a: select_live(n, o, a, live),
                    new_cache, cache, batch_axes)
                norms = _norms_or_zeros(logits)
            return new_cache, next_tok, norms

    begin = getattr(model, "prefill_begin", None)
    chunk_fn = (model.prefill_chunk_parallel if prefill_body == "flash"
                else model.prefill_chunk)

    def _advance(params, batch, row, offset, nvalid, first):
        """The shared chunk interior: optional pinned ``prefill_begin``
        plus the resolved chunk body, with the body boundary
        ``optimization_barrier``-pinned on BOTH sides — the dense
        program slices the row out of its slot, the paged program
        assembles it through a page table, and the pin keeps either
        layout's data movement out of the chunk arithmetic (the
        paged-vs-dense bitwise contract, prefill half)."""
        if first and begin is not None:
            # pinned like the scan body: the setup's bits must not
            # depend on which width the first chunk has
            row = jax.lax.optimization_barrier(begin(params, batch, row))
        row = jax.lax.optimization_barrier(row)
        logits, row = chunk_fn(params, batch, row, offset, nvalid)
        return jax.lax.optimization_barrier((logits, row))

    def _finish_chunk(logits, seed, temp):
        """Emit-0 sampling + telemetry from the last-valid-position
        logits (used only when this was the request's final chunk)."""
        k = jax.random.fold_in(jax.random.fold_in(base_key, seed),
                               jnp.int32(0))
        tok = sample_row(logits[0], k, temp)
        norm = (_norms(logits)[0] if ec.track_stats
                else jnp.float32(0.0))
        return tok, norm

    def prefill_factory(width: int, first: bool):
        """One jitted prefill-chunk program for a static chunk width.

        Gathers the request's batch-1 row from its slot (dense: sliced;
        paged: assembled through its page table), (optionally) runs the
        family's one-time ``prefill_begin`` setup, advances the row by
        the chunk through the resolved body — the per-position scan, or
        (``prefill_mode="flash"``) the family's parallel multi-token
        pass — scatters the row back, and samples emit 0 + its
        telemetry norm from the last-valid-position logits (the engine
        uses them only when this was the request's final chunk)."""
        if page_axes is not None:
            pgsz = ec.page_size

            @functools.partial(jax.jit, donate_argnums=tuple(
                1 + i for i in _donate()))
            def prefill(params, cache, slot, batch, offset, nvalid, seed,
                        temp, table, nres):
                with use_policy(policy):
                    row = paged_gather_row(cache, batch_axes, page_axes,
                                           slot, table, nres)
                    logits, row = _advance(params, batch, row, offset,
                                           nvalid, first)
                    # write back ONLY the chunk's pages: everything below
                    # ``offset`` (shared prefix pages included) is
                    # redirected to the NULL page — strict copy-on-write
                    first_pg = offset // pgsz
                    end_pg = (offset + nvalid - 1) // pgsz + 1
                    new_cache = paged_scatter_row(
                        cache, row, batch_axes, page_axes, slot, table,
                        first_pg, end_pg)
                    tok, norm = _finish_chunk(logits, seed, temp)
                return new_cache, tok, norm

            return prefill

        @functools.partial(jax.jit, donate_argnums=tuple(
            1 + i for i in _donate()))
        def prefill(params, cache, slot, batch, offset, nvalid, seed, temp):
            with use_policy(policy):
                row = gather_row(cache, batch_axes, slot)
                logits, row = _advance(params, batch, row, offset, nvalid,
                                       first)
                new_cache = scatter_row(cache, row, batch_axes, slot)
                tok, norm = _finish_chunk(logits, seed, temp)
            return new_cache, tok, norm

        return prefill

    fns = _ServePrograms(tick, prefill_factory, prefill_body)
    cache[key] = fns
    return fns


@dataclasses.dataclass
class _PageLease:
    """One admitted request's page reservation (paged layout only).

    table    [max_pages] i32 page table — shared prefix pages first,
             then the request's own pages, NULL (0) beyond ``n_pages``
    n_pages  reserved pages total (every page the request can touch —
             fixed at admission, so decode never allocates)
    shared   prefix-tree nodes the request holds a reference on until
             it finishes: its matched path, then the nodes that adopt
             its own prompt pages when its prefill completes
    own      engine-owned pages, zero-reset and freed at finish (pages
             the prefix tree adopts leave this list)
    resume   prefill resume offset: positions [0, resume) came in by
             reference (+ at most one copy-on-write page) and are never
             re-prefilled
    """

    table: np.ndarray
    n_pages: int
    shared: List[PrefixNode]
    own: List[int]
    resume: int


class InferenceEngine:
    """Continuous-batching serving engine over the model-zoo API.

    ``model`` / ``params`` may be passed in to share one set of weights
    across engines (the determinism tests replay requests solo against
    the same weights the loaded engine serves).
    """

    def __init__(self, cfg: ArchConfig, ec: EngineConfig = EngineConfig(),
                 seed: int = 0, model=None, params=None):
        self.cfg = cfg
        self.ec = ec
        # capture ONE policy at construction; later ambient changes
        # don't silently renumber the engine.
        self.policy = (ec.policy if ec.policy is not None
                       else _schemes.current_policy())
        self.model = model if model is not None else build_model(cfg)
        if params is None:
            params, _ = self.model.init(jax.random.key(seed))  # contract: allow-no-raw-prngkey(engine-owned init root from the config seed — the serving boundary)
        self.params = params
        # resolve the KV layout: "paged" needs at least one pageable
        # leaf — recurrent-only families (SSM/xLSTM, all-window hybrids)
        # fall back to dense; ``engine.kv_layout`` reports the result
        # (mirroring the flash -> scan prefill_body fallback).
        self.pages: Optional[PageAllocator] = None
        self.prefix: Optional[RadixPrefixTree] = None
        self.num_pages = 0
        if (ec.kv_layout == "paged"
                and PagedKVCache.pageable(self.model, ec.max_len)):
            self.num_pages = (
                ec.num_pages if ec.num_pages is not None
                else ec.max_slots * ec.max_len // ec.page_size)
            self.slots = PagedKVCache(self.model, ec.max_slots, ec.max_len,
                                      ec.page_size, self.num_pages)
            self.pages = PageAllocator(self.num_pages)
            if ec.prefix_cache:
                self.prefix = RadixPrefixTree(ec.page_size)
        else:
            self.slots = SlotKVCache(self.model, ec.max_slots, ec.max_len)
        self.scheduler = SlotScheduler(ec.max_slots)
        self._fns = _compiled_fns(
            self.model, cfg, ec, self.policy, self.slots.batch_axes,
            getattr(self.slots, "page_axes", None))
        self._needs_begin = getattr(self.model, "prefill_begin", None) is not None
        # paged bookkeeping: request_id -> its page lease, plus the
        # launcher-facing counters ``page_stats()`` surfaces
        self._leases: Dict[int, _PageLease] = {}
        self.prefix_hit_tokens = 0
        self.page_stalls = 0
        # (width, runs_begin) of every prefill program THIS engine's
        # traffic has needed (the jitted programs themselves are shared
        # model-wide, so a solo-replay engine reuses the loaded engine's)
        self._used_prefill: set = set()
        self._next_id = 0
        # (request_id, width, body) of every prefill chunk the MOST
        # RECENT step() ran — the launcher's per-chunk logging surface
        self.last_chunks: List[Tuple[int, int, str]] = []
        self.t = 0                       # engine step counter
        self.handles: Dict[int, RequestHandle] = {}
        self._finished: Deque[int] = collections.deque()
        # per-request extras, converted to device arrays ONCE at the
        # first chunk (multi-chunk prompts would otherwise re-upload the
        # full vision/frame embedding tensor every chunk); dropped when
        # the prefill completes
        self._extras_dev: Dict[int, Dict[str, jax.Array]] = {}

    # ------------------------------------------------------------ submission
    def submit(self, request: Request) -> RequestHandle:
        """Queue a request; returns its live handle immediately. Under a
        profiler session the span ``serve.submit`` carries its
        ``request_id`` and ``prompt_len``, the id that the request's
        ``serve.prefill`` spans carry too."""
        with TraceAnnotation("serve.submit") as span:
            handle = self._queue(request)
            span.set_metadata(request_id=handle.request_id,
                              prompt_len=handle.prompt_len)
        return handle

    def _queue(self, request: Request) -> RequestHandle:
        rid = request.request_id
        if rid is None:
            rid = self._next_id
        if rid in self.handles:
            raise ValueError(f"request_id {rid} already submitted")
        self._next_id = max(self._next_id, rid) + 1
        if request.sampling.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        prompt = np.asarray(request.prompt)
        if prompt.ndim != 1 or prompt.shape[0] == 0:
            # validated here, at the API boundary — an empty or
            # mis-shaped prompt would otherwise surface as an opaque
            # shape error deep inside the prefill trace
            raise ValueError(
                f"request {rid}: prompt must be a non-empty 1-D token "
                f"sequence, got shape {tuple(prompt.shape)}")
        prompt_len = int(prompt.shape[0])
        if prompt_len + request.sampling.max_new_tokens - 1 > self.ec.max_len:
            raise ValueError(
                f"request {rid}: prompt_len={prompt_len} + "
                f"max_new_tokens={request.sampling.max_new_tokens} exceeds "
                f"the engine's max_len={self.ec.max_len}")
        if self.pages is not None:
            need = pages_for(
                prompt_len + request.sampling.max_new_tokens - 1,
                self.ec.page_size)
            if need > self.num_pages:
                # fail fast at the API boundary: this request could never
                # be admitted even with the whole pool free — waiting in
                # the FIFO queue would starve everything behind it forever
                raise ValueError(
                    f"request {rid}: needs {need} pages but the pool has "
                    f"only {self.num_pages} — raise num_pages or shrink "
                    f"the request")
        handle = RequestHandle(request_id=rid, request=request,
                               prompt_len=prompt_len)
        self.handles[rid] = handle
        self.scheduler.submit(handle)
        return handle

    def _chunk_batch(self, rid: int, request: Request, offset: int,
                     width: int, nvalid: int) -> Dict[str, jax.Array]:
        """Model inputs for one prefill chunk: the [1, width] token
        window (zero-padded past nvalid — those scan steps are exactly
        discarded) plus the request's extras, whose shapes are
        config-static (vision patch / frame counts), every chunk —
        converted to device arrays once and reused across chunks."""
        prompt = np.asarray(request.prompt)
        toks = np.zeros((1, width), np.int32)
        toks[0, :nvalid] = prompt[offset:offset + nvalid]
        batch = {"tokens": jnp.asarray(toks)}
        if request.extras:
            if rid not in self._extras_dev:
                self._extras_dev[rid] = {k: jnp.asarray(v)[None]
                                         for k, v in request.extras.items()}
            batch.update(self._extras_dev[rid])
        return batch

    # ------------------------------------------------------------------ step
    def step(self) -> List[TokenEvent]:
        """One engine tick: admit queued requests into free slots, run up
        to ``prefill_budget`` prefill chunks (oldest request first; a
        request whose last chunk lands emits its first token and joins
        the decode batch), then one decode tick over the running slots.
        Returns the tokens emitted this step, prefill completions first.

        Under a ``jax.profiler`` session the step leaves host spans on
        the profiler's clock (``serve.step`` with ``step_num``, and
        ``serve.admit``, ``serve.prefill*``, ``serve.tick.*`` and
        ``serve.record`` inside it); with no session each costs one
        check of whether a session is active.
        """
        events: List[TokenEvent] = []
        self.last_chunks = []
        with StepTraceAnnotation("serve.step", step_num=self.t):
            admitted, chunks = self._admit_and_prefill(events)
            running = self.scheduler.running
            if running:
                self._tick(running, admitted, chunks, events)
        self.t += 1
        return events

    def _admit_and_prefill(self, events: List[TokenEvent]) -> Tuple[int, int]:
        """Admissions and budgeted chunked prefill; returns (requests
        admitted, chunks run)."""
        sch = self.scheduler
        budget = self.ec.prefill_budget
        admitted = spent = 0
        while True:
            if sch.can_admit():
                with TraceAnnotation("serve.admit"):
                    while sch.can_admit():
                        if self.pages is not None and not self._reserve_pages(
                                sch.peek()):
                            # page exhaustion: the head blocks IN THE QUEUE
                            # (strict FIFO — nothing jumps a starved head)
                            # until finishing requests release pages
                            self.page_stalls += 1
                            break
                        sch.admit_next()
                        admitted += 1
            if budget is not None and spent >= budget:
                break
            prefilling = sch.prefilling
            if not prefilling:
                break
            # oldest admitted request first: FIFO prefill, deterministic
            slot, h = next(iter(prefilling.items()))
            self._run_chunk(slot, h, events)
            spent += 1
        return admitted, spent

    def _tick(self, running: Dict[int, RequestHandle], admitted: int,
              chunks: int, events: List[TokenEvent]) -> None:
        """One decode tick over the running slots. The dispatch span's
        arguments count the step: slots in the tick (``live``), requests
        still queued, prefill chunks run and requests admitted before
        it."""
        b = self.ec.max_slots
        with TraceAnnotation("serve.tick.inputs"):
            tokens = np.zeros((b,), np.int32)
            pos = np.zeros((b,), np.int32)
            seeds = np.zeros((b,), np.int32)
            eidx = np.zeros((b,), np.int32)
            temps = np.zeros((b,), np.float32)
            live = np.zeros((b,), bool)
            for slot, h in running.items():
                tokens[slot] = h.tokens[-1]
                pos[slot] = h.pos
                seeds[slot] = h.seed
                eidx[slot] = h.emitted
                temps[slot] = h.request.sampling.temperature
                live[slot] = True
            args = tuple(jnp.asarray(a) for a in
                         (tokens, pos, seeds, eidx, temps, live))
            if self.pages is not None:
                tables = np.zeros((b, self.slots.max_pages), np.int32)
                nres = np.zeros((b,), np.int32)
                for slot, h in running.items():
                    lease = self._leases[h.request_id]
                    tables[slot] = lease.table
                    nres[slot] = lease.n_pages
                args += (jnp.asarray(tables), jnp.asarray(nres))
        with TraceAnnotation("serve.tick.dispatch", live=len(running),
                             queued=self.scheduler.queued, chunks=chunks,
                             admitted=admitted):
            new_cache, next_tok, norms = self._fns.tick(
                self.params, self.slots.cache, *args)
        self.slots.cache = new_cache
        with TraceAnnotation("serve.tick.readback"):
            toks = np.asarray(next_tok)
            norms = np.asarray(norms)
        with TraceAnnotation("serve.record") as span:
            first = len(events)
            for slot, h in running.items():
                h.pos += 1
                self._record(h, int(toks[slot]), norms[slot], events)
            span.set_metadata(finished=sum(e.done for e in events[first:]))

    def _run_chunk(self, slot: int, h: RequestHandle,
                   events: List[TokenEvent]) -> None:
        """Advance one PREFILLING request by one chunk; on the final
        chunk, record emit 0 and move the request into the decode batch.
        Its span's ``new_program`` is 1 when this engine had not run the
        chunk's program before (so the call compiled or loaded it).
        """
        offset = h.prefill_pos
        width, nvalid = _next_chunk(h.prompt_len, offset,
                                    self.ec.prefill_chunk)
        lease = (self._leases[h.request_id] if self.pages is not None
                 else None)
        resume = lease.resume if lease is not None else 0
        # a prefix-resumed request's FIRST chunk is the one at its resume
        # offset — ``prefill_begin`` (dense, per-slot leaves) must still
        # run for it
        first = offset == resume and self._needs_begin
        new = (width, first) not in self._used_prefill
        self._used_prefill.add((width, first))
        self.last_chunks.append((h.request_id, width, self.prefill_body))
        with TraceAnnotation("serve.prefill", request_id=h.request_id,
                             width=width, offset=offset, new_program=int(new)):
            extra = ()
            if lease is not None:
                extra = (jnp.asarray(lease.table),
                         jnp.asarray(lease.n_pages, jnp.int32))
            fn = self._fns.prefill(width, first)
            sp = h.request.sampling
            args = (jnp.asarray(slot, jnp.int32),
                    self._chunk_batch(h.request_id, h.request, offset,
                                      width, nvalid),
                    jnp.asarray(offset, jnp.int32),
                    jnp.asarray(nvalid, jnp.int32),
                    jnp.asarray(h.seed, jnp.int32),
                    jnp.asarray(sp.temperature, jnp.float32)) + extra
            with TraceAnnotation("serve.prefill.dispatch"):
                new_cache, tok, norm = fn(self.params, self.slots.cache,
                                          *args)
            self.slots.cache = new_cache
            h.prefill_pos = offset + nvalid
            if h.prefill_pos == h.prompt_len:
                with TraceAnnotation("serve.prefill.readback"):
                    tok = int(tok)
                    if self.ec.track_stats:
                        norm = np.float32(norm)
                self._extras_dev.pop(h.request_id, None)
                self.scheduler.mark_running(h)
                h.pos = h.prompt_len
                if lease is not None:
                    self._donate_prompt(h, lease)
                self._record(h, tok, norm, events)

    def _record(self, h: RequestHandle, token: int, norm,
                events: List[TokenEvent]) -> None:
        h.tokens.append(token)
        h.emitted += 1
        nval = None
        if self.ec.track_stats:
            # float() of an fp32 is exact — the telemetry trace keeps
            # its bits for the solo-vs-batched comparison.
            nval = float(np.float32(norm))
            h.telemetry.append(nval)
        done = h.remaining == 0
        if done:
            slot = self.scheduler.release(h)
            self.slots.reset(slot)      # eviction hook: no stale state
            if self.pages is not None:
                self._release_pages(h)
            self._finished.append(h.request_id)
            if self.ec.max_finished is not None:
                while len(self._finished) > self.ec.max_finished:
                    self.handles.pop(self._finished.popleft(), None)
        events.append(TokenEvent(h.request_id, token, nval, done))

    # ------------------------------------------------------ page admission
    def _sharable(self, h: RequestHandle) -> bool:
        """May this request share prompt pages through the prefix tree?
        Sharing needs cache bits that are a function of the TOKEN PREFIX
        only: extras-bearing requests (multimodal / encoder inputs feed
        every cached position) are excluded, as are ``prefill_begin``
        families (begin-derived state conditions the pageable leaves,
        and those families take extras anyway), and a flash chunk body
        without a chunk width has no alignable resume offset."""
        return (self.prefix is not None and not h.request.extras
                and not self._needs_begin
                and (self.prefill_body == "scan"
                     or self.ec.prefill_chunk is not None))

    def _reserve_pages(self, h: RequestHandle) -> bool:
        """Reserve EVERY page the queue head can touch (the ALLOCATING
        admission window); False = pool exhausted even after prefix-
        cache eviction — the head goes back to QUEUED and admission
        stalls, strict FIFO. All allocation happens here, on the host:
        never inside a trace, and never mid-decode.

        With the prefix cache on, the prompt is matched against the
        radix tree first: matched full pages are taken BY REFERENCE
        (refcounted, never written — the prefill scatter masks every
        page below the resume offset to the NULL page), and under the
        scan chunk body one partially-matching page may be duplicated
        copy-on-write. The resume offset is capped so at least one
        prompt position is always re-prefilled (the final chunk's
        logits emit token 0) and — under the flash body — aligned to
        both the page size and the chunk width, so a resumed request
        runs EXACTLY the chunk programs its private prefill would have
        run from that offset (cross-width flash equality is allclose,
        not bitwise; alignment keeps shared-vs-private bitwise).

        Under a profiler session the span ``serve.reserve`` (inside
        ``serve.admit``) carries ``request_id``, ``prompt_len``,
        ``hit_tokens`` (the resume offset), ``shared_pages``,
        ``new_pages``, ``evicted_pages`` and ``admitted`` (0 when the
        head stalls): over the admitted spans ``hit_tokens`` sums to
        ``prefix_hit_tokens``, and the stalls count ``page_stalls``.
        """
        with TraceAnnotation("serve.reserve") as span:
            lease, evicted = self._lease(h)
            ok = lease is not None
            span.set_metadata(
                request_id=h.request_id, prompt_len=h.prompt_len,
                hit_tokens=lease.resume if ok else 0,
                shared_pages=len(lease.shared) if ok else 0,
                new_pages=len(lease.own) if ok else 0,
                evicted_pages=evicted, admitted=int(ok))
        return ok

    def _lease(self, h: RequestHandle) -> Tuple[Optional[_PageLease], int]:
        """``_reserve_pages``' work: (the lease, or None when the pool
        is short, and the cached prefix pages evicted for it)."""
        ec = self.ec
        ps = ec.page_size
        h.status = ALLOCATING
        total = pages_for(
            h.prompt_len + h.request.sampling.max_new_tokens - 1, ps)
        prompt = [int(t) for t in np.asarray(h.request.prompt)]
        sharable = self._sharable(h)
        path: List[PrefixNode] = []
        resume = 0
        if sharable:
            path = self.prefix.match(prompt)
            r = min(len(path) * ps, h.prompt_len - 1)
            if self.prefill_body == "flash":
                c = ec.prefill_chunk
                r = min(r, c * ((h.prompt_len - 1) // c))
                align = max(ps, c)
                r = (r // align) * align
            else:
                r = (r // ps) * ps
            path = path[:r // ps]
            resume = r
        shared = len(path)
        need = total - shared
        freed: List[int] = []
        if self.prefix is not None:
            self.prefix.acquire(path)
            if self.pages.free_count < need:
                # reclaim refs-0 cached prefix pages, oldest first (the
                # path we just acquired is pinned by its refs)
                freed = self.prefix.evict(need - self.pages.free_count)
                if freed:
                    self.slots.reset_pages(freed)  # pristine before reuse
                    self.pages.free(freed)
        if self.pages.free_count < need:
            if self.prefix is not None:
                self.prefix.release(path)
            h.status = QUEUED
            return None, len(freed)
        own = self.pages.alloc(need)
        if sharable and self.prefill_body == "scan":
            # copy-on-write at the first divergent page (scan body only —
            # flash resume must stay chunk-aligned): duplicate the child
            # sharing the longest token prefix of the next page into the
            # request's own first page, then resume AFTER the overlap.
            # Chosen after eviction, so the donor is still resident.
            donor, t = self.prefix.partial_child(path, prompt)
            t = min(t, h.prompt_len - 1 - resume)
            if donor is not None and t > 0:
                self.slots.copy_page(donor.page, own[0])
                resume += t
        table = np.zeros((self.slots.max_pages,), np.int32)
        for j, node in enumerate(path):
            table[j] = node.page
        table[shared:shared + need] = own
        lease = _PageLease(table=table, n_pages=total, shared=path,
                           own=own, resume=resume)
        self._leases[h.request_id] = lease
        h.prefill_pos = resume
        self.prefix_hit_tokens += resume
        return lease, len(freed)

    def _donate_prompt(self, h: RequestHandle, lease: _PageLease) -> None:
        """Prefill-completion hook (prefix cache on): offer the request's
        full prompt pages to the prefix tree as soon as no later write
        can touch them — decode writes only at positions >= prompt_len —
        so requests admitted while the donor still decodes share them.
        First insert of a page run wins (the bitwise contract makes any
        two requests' bits for identical full-page prompt runs
        identical, so which donor wins is unobservable). The request
        holds a reference on every node that adopted one of its pages
        until it finishes, so eviction cannot reclaim a page it reads."""
        if self.prefix is None or not self._sharable(h):
            return
        ps = self.ec.page_size
        if self.prefill_body == "flash":
            # only positions computed in FULL chunk-width programs are
            # donor-eligible under flash (tail buckets are width-
            # dependent): insert pages fully inside that region
            c = self.ec.prefill_chunk
            n_ins = (c * ((h.prompt_len - 1) // c)) // ps
        else:
            n_ins = h.prompt_len // ps
        if not n_ins:
            return
        prompt = [int(t) for t in np.asarray(h.request.prompt)]
        adopted, _ = self.prefix.insert(prompt, n_ins, lease.table[:n_ins])
        if adopted:
            taken = set(adopted)
            nodes = [n for n in self.prefix.match(prompt)[:n_ins]
                     if n.page in taken]
            self.prefix.acquire(nodes)
            lease.shared = lease.shared + nodes
            lease.own = [p for p in lease.own if p not in taken]

    def _release_pages(self, h: RequestHandle) -> None:
        """Finish hook (runs right after the slot is released): drop the
        request's prefix references (its matched path and the nodes that
        adopted its prompt pages) and zero-reset + free its own pages.
        The leak invariant: after a drained trace, free pages +
        tree-owned pages == num_pages."""
        lease = self._leases.pop(h.request_id)
        if self.prefix is not None:
            self.prefix.release(lease.shared)
        if lease.own:
            self.slots.reset_pages(lease.own)  # pristine before reuse
            self.pages.free(lease.own)

    @property
    def kv_layout(self) -> str:
        """The RESOLVED cache layout: "paged" only when
        ``EngineConfig.kv_layout == "paged"`` AND the family has at
        least one pageable leaf (recurrent-only families fall back to
        dense — mirroring the flash -> scan ``prefill_body``
        fallback)."""
        return "paged" if self.pages is not None else "dense"

    def page_stats(self) -> Dict[str, int]:
        """Pool / prefix accounting snapshot (paged layout only) — the
        launcher's per-step log line and the footprint tests read this.

        ``pages_in_use`` counts every non-free page: request-reserved
        plus tree-owned (shared live + retained cache).
        ``kv_bytes_in_use`` is that count times the per-page byte
        footprint across every pool leaf — the live-memory figure that
        scales with live tokens where the dense layout pins
        ``max_slots * max_len``."""
        if self.pages is None:
            raise RuntimeError(
                "page_stats: this engine resolved to the dense layout "
                "(kv_layout='dense', or the family has no pageable leaf)")
        in_use = self.num_pages - self.pages.free_count
        return {
            "num_pages": self.num_pages,
            "free_pages": self.pages.free_count,
            "pages_in_use": in_use,
            "prefix_pages": (self.prefix.total_pages
                             if self.prefix is not None else 0),
            "prefix_cached_pages": (self.prefix.cached_pages
                                    if self.prefix is not None else 0),
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "page_stalls": self.page_stalls,
            "kv_bytes_in_use": in_use * self.slots.page_bytes,
        }

    # ------------------------------------------------------- handle hygiene
    def pop_finished(self) -> Dict[int, RequestHandle]:
        """Drain the retained FINISHED handles (request_id -> handle) and
        drop them from ``engine.handles`` — the eviction valve that keeps
        a long-lived engine's handle table bounded under sustained
        traffic (see also ``EngineConfig.max_finished``)."""
        out = {}
        while self._finished:
            rid = self._finished.popleft()
            h = self.handles.pop(rid, None)
            if h is not None:
                out[rid] = h
        return out

    # ------------------------------------------------------- audit surface
    def trace_tick(self) -> Tuple[Any, Tuple]:
        """(decode-tick callable, representative args) for the trace
        auditor — the jitted tick itself plus abstract-shaped operands,
        so ``jax.make_jaxpr(fn)(*args)`` yields the IR XLA compiles.
        The supported registration surface of ``repro.analysis.targets``
        (reaching into ``_fns`` from outside would pin internals)."""
        b = self.ec.max_slots
        z = functools.partial(jax.ShapeDtypeStruct, (b,))
        args = (self.params, self.slots.cache, z(jnp.int32), z(jnp.int32),
                z(jnp.int32), z(jnp.int32), z(jnp.float32), z(jnp.bool_))
        if self.pages is not None:
            args += (jax.ShapeDtypeStruct((b, self.slots.max_pages),
                                          jnp.int32), z(jnp.int32))
        return self._fns.tick, args

    def trace_prefill(self, width: int, first: bool = False,
                      ) -> Tuple[Any, Tuple]:
        """(prefill-chunk program, representative args) for one static
        chunk width — the trace auditor's view of a bucket program."""
        s = jax.ShapeDtypeStruct
        batch = {"tokens": s((1, width), jnp.int32)}
        args = (self.params, self.slots.cache, s((), jnp.int32), batch,
                s((), jnp.int32), s((), jnp.int32), s((), jnp.int32),
                s((), jnp.float32))
        if self.pages is not None:
            args += (s((self.slots.max_pages,), jnp.int32),
                     s((), jnp.int32))
        return self._fns.prefill(width, first), args

    @property
    def prefill_body(self) -> str:
        """The RESOLVED chunk body this engine's prefill programs trace:
        "flash" only when ``EngineConfig.prefill_mode == "flash"`` AND
        the family can take the parallel path (hybrid/xlstm recurrence
        and MLA / MoE / sliding-window configs fall back to "scan")."""
        return self._fns.prefill_body

    @property
    def prefill_programs(self) -> Tuple[Tuple[int, bool], ...]:
        """(chunk_width, runs_begin) key of every prefill program THIS
        engine's traffic has needed — the quantity the compile-count
        regression guard bounds: O(#buckets) when chunked, O(#distinct
        prompt lengths) under one-shot admit."""
        return tuple(sorted(self._used_prefill))

    # ------------------------------------------------------------ driving
    def stream(self, requests: Sequence[Request] = (),
               arrivals: Optional[Sequence[int]] = None,
               _sink: Optional[Dict[int, RequestHandle]] = None,
               ) -> Iterator[Tuple[int, List[TokenEvent]]]:
        """Drive a trace to completion, yielding ``(step, events)`` per
        tick. ``arrivals[i]`` is the engine step at which ``requests[i]``
        arrives (default: all at step 0) — the staggered-arrival replay
        surface the trace driver and the determinism tests build on."""
        arr = [0] * len(requests) if arrivals is None else list(arrivals)
        if len(arr) != len(requests):
            raise ValueError("arrivals must match requests")
        pending = sorted(range(len(requests)), key=lambda i: (arr[i], i))
        while pending or self.scheduler.busy:
            while pending and arr[pending[0]] <= self.t:
                h = self.submit(requests[pending.pop(0)])
                if _sink is not None:
                    _sink[h.request_id] = h
            yield self.t, self.step()

    def run(self, requests: Sequence[Request] = (),
            arrivals: Optional[Sequence[int]] = None,
            ) -> Dict[int, RequestHandle]:
        """Submit ``requests`` (staggered by ``arrivals``, in engine
        steps) plus anything already queued, and step until drained.
        Returns ``request_id -> handle`` for the trace THIS call drove
        (not every handle the engine ever retained — handle references
        are captured at submission, so they survive ``max_finished``
        eviction)."""
        driven = {rid: h for rid, h in self.handles.items() if not h.done}
        for _ in self.stream(requests, arrivals, _sink=driven):
            pass
        return driven
