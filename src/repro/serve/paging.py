"""Paged KV cache: a fixed-size page pool + per-request page tables.

The dense ``SlotKVCache`` holds ``max_slots x max_len`` positions per KV
leaf whether or not anyone lives there; the compensated kernels are
data-traffic bound (the paper's ECM result), so the serving memory
footprint should scale with LIVE tokens instead. This module provides
the paged layout (``EngineConfig.kv_layout="paged"``): every PAGEABLE
cache leaf — position-addressed KV history, identified by
``models.common.cache_page_axes`` — is re-homed into a pool of
``num_pages`` fixed-size pages of ``page_size`` positions each, and a
request's logical row is assembled THROUGH ITS PAGE TABLE (a traced i32
index array) on the way into the same decode/prefill bodies the dense
engine runs. Non-pageable leaves (ring-buffer windows, recurrent
SSM/xLSTM state, one-shot cross-attention K/V — the ``pageable=False``
spec split documented on ``cache_page_axes``) keep their dense
``max_slots`` rows inside the same cache pytree.

THE DENSE ORACLE. ``SlotKVCache`` stays the default and the bitwise
oracle: a request's emitted tokens AND compensated telemetry are
identical under either layout, and identical whether its pages happen to
be contiguous or scattered. Three mechanisms carry it:

* gather/scatter is EXACT DATA MOVEMENT at traced page indices
  (``jnp.take`` over the page axis, ``dynamic_update_slice`` /
  ``.at[].set`` writes) — one compiled program serves every page
  placement, so "scattered vs contiguous" cannot even reach the
  arithmetic;
* the gathered row is BITWISE the dense row: pages are zero-reset when
  freed (and the pool starts pristine), table entries past the live page
  count are masked to exact zeros on gather, so unwritten positions
  carry the same pristine bits the dense slot row would;
* the compute between gather and scatter is the SAME barrier-pinned
  decode/chunk body the dense programs run (``repro.serve.engine`` pins
  the body boundary in both layouts), so XLA cannot fuse the paged data
  movement into the arithmetic differently than the dense slicing.

THE NULL PAGE. Page 0 is reserved and never allocated: masked scatter
lanes (dead decode slots, pages below a prefill chunk's first written
page — e.g. shared prefix pages, which are strictly copy-on-write) are
redirected there, and gather masks every non-live table entry to zeros
before use, so nothing ever reads it. Allocatable pages are 1..num_pages.

THE ALLOCATOR is plain deterministic Python (``PageAllocator``:
lowest-numbered page first, sorted free list — the scheduler's
lowest-free-slot policy, for pages). The engine reserves EVERY page a
request can touch (``ceil((prompt_len + max_new_tokens - 1)/page_size)``
minus shared prefix pages) at admission, so allocation never happens
inside a trace and decode can never hit page exhaustion mid-request;
admission blocks (FIFO head-of-line, deterministic) when the pool is
short. Impossible requests fail fast at ``submit``.
"""

from __future__ import annotations

import bisect
import functools
from typing import Any, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import cache_batch_axes, cache_page_axes
from repro.serve.slots import _donate, _take_leaf, _update_leaf, select_live

#: the reserved never-allocated page: masked scatters land here, gather
#: masks every read of it to exact zeros.
NULL_PAGE = 0


def pages_for(n_positions: int, page_size: int) -> int:
    """Pages covering positions [0, n_positions) — ceil division."""
    return -(-n_positions // page_size)


# ---------------------------------------------------------------------------
# Traced per-leaf page ops (pure; composed in-trace by the engine programs)
# ---------------------------------------------------------------------------

def _to_positions(leaf_row: jax.Array, b: int, s: int) -> jax.Array:
    """Leaf-layout batch-1 row -> canonical [max_len, *rest] layout."""
    return jnp.moveaxis(leaf_row, (b, s), (0, 1))[0]


def _from_positions(x: jax.Array, b: int, s: int) -> jax.Array:
    """Canonical [max_len, *rest] -> leaf-layout batch-1 row."""
    return jnp.moveaxis(x[None], (0, 1), (b, s))


def gather_pages(pool: jax.Array, table: jax.Array, n_live,
                 b: int, s: int) -> jax.Array:
    """Assemble a request's logical leaf row through its page table.

    ``pool``: [num_pages+1, page_size, *rest]; ``table``: [max_pages]
    traced i32 (one compiled program for ANY placement); ``n_live``:
    traced count of live pages. Rows at positions >= n_live*page_size
    are masked to EXACT zeros — together with zero-reset on free, the
    assembled row is bitwise the dense slot row (pristine bits where
    nothing was written), which is half of the paged-vs-dense oracle
    equality.
    """
    mp, ps = table.shape[0], pool.shape[1]
    pages = jnp.take(pool, table, axis=0)          # [mp, ps, *rest]
    row = pages.reshape((mp * ps,) + pool.shape[2:])
    idx = jnp.arange(mp * ps).reshape((mp * ps,) + (1,) * (row.ndim - 1))
    row = jnp.where(idx < n_live * ps, row, jnp.zeros_like(row))
    return _from_positions(row, b, s)


def scatter_pages(pool: jax.Array, leaf_row: jax.Array, table: jax.Array,
                  first_page, n_live, b: int, s: int) -> jax.Array:
    """Write a row's pages [first_page, n_live) back through its table.

    Pages outside the written range are redirected to the NULL page
    (never read), which keeps shared prefix pages strictly copy-on-write
    — a prefill chunk at offset >= the shared boundary can never touch a
    donor page.
    """
    mp, ps = table.shape[0], pool.shape[1]
    row = _to_positions(leaf_row, b, s)
    pages = row.reshape((mp, ps) + row.shape[1:]).astype(pool.dtype)
    j = jnp.arange(mp, dtype=jnp.int32)
    dst = jnp.where((j >= first_page) & (j < n_live), table, NULL_PAGE)
    return pool.at[dst].set(pages)


def scatter_decode_pages(pool: jax.Array, rows: jax.Array, tables: jax.Array,
                        pos: jax.Array, live: jax.Array,
                        b: int, s: int) -> jax.Array:
    """Write back ONLY the page containing each slot's decode position.

    ``rows``: every slot's leaf row (slot axis at ``b``); ``tables``
    [max_slots, max_pages], ``pos`` and ``live`` [max_slots]. A decode
    step writes exactly one position per slot, so the tick extracts one
    page per slot and lands them in ONE scatter (O(max_slots x
    page_size), not O(max_len) traffic). Dead slots (``live=False``)
    are redirected to the NULL page, so their rows' contents never
    reach the pool.
    """
    ps = pool.shape[1]
    # each slot's page read in place, in the rows' own layout: a vmapped
    # per-slot slice would become a gather that transposes every row
    idx = ((pos // ps) * ps)[:, None] + jnp.arange(ps, dtype=pos.dtype)
    shape = [1] * rows.ndim
    shape[b], shape[s] = idx.shape
    idx = (idx if b < s else idx.T).reshape(shape)
    pages = jnp.moveaxis(jnp.take_along_axis(rows, idx, axis=s),
                         (b, s), (0, 1))
    own = jnp.take_along_axis(tables, (pos // ps)[:, None], axis=1)[:, 0]
    dst = jnp.where(live, own, jnp.int32(NULL_PAGE))
    return pool.at[dst].set(pages.astype(pool.dtype))


# ---------------------------------------------------------------------------
# Row-level (whole cache pytree) ops
# ---------------------------------------------------------------------------

def paged_gather_row(cache: Any, batch_axes: Any, page_axes: Any,
                     slot, table, n_live) -> Any:
    """Batch-1 row of a mixed dense/paged cache: dense leaves slice at
    the traced slot, pool leaves assemble through the page table."""
    def one(leaf, b, s):
        if s < 0:
            return _take_leaf(leaf, b, slot)
        return gather_pages(leaf, table, n_live, b, s)

    return jax.tree.map(one, cache, batch_axes, page_axes)


def paged_scatter_row(cache: Any, row: Any, batch_axes: Any, page_axes: Any,
                      slot, table, first_page, n_live) -> Any:
    """Install a row back: dense leaves at the slot, pool leaves through
    the table (pages [first_page, n_live) only — prefill granularity)."""
    def one(leaf, r, b, s):
        if s < 0:
            return _update_leaf(leaf, r, b, slot)
        return scatter_pages(leaf, r, table, first_page, n_live, b, s)

    return jax.tree.map(one, cache, row, batch_axes, page_axes)


def gather_rows(pool: jax.Array, tables: jax.Array, n_live: jax.Array,
                b: int, s: int) -> jax.Array:
    """Every slot's logical leaf row through its page table, the slot
    axis at ``b`` (``tables`` [max_slots, max_pages], ``n_live``
    [max_slots]).

    A leaf whose axis 0 is a stacked layer axis (neither ``b`` nor
    ``s``) is gathered one layer at a time, each layer's pages landing
    directly in that layer's slice of the result. One gather of all
    layers would land the rows in the pool's page-major order and need
    a transpose of the whole gathered cache into the layer-major order
    the decode body reads: on a TPU v5e that took longer (olmo-1b, 16
    slots x 2048: 104.5 against 97.8 ms a tick).
    """
    if 0 not in (b, s):
        def layer(i):
            pool_i = jax.lax.dynamic_index_in_dim(pool, i, axis=2,
                                                  keepdims=False)
            return gather_rows(pool_i, tables, n_live, b - 1, s - 1)

        return jax.lax.map(layer, jnp.arange(pool.shape[2]))

    def row(table, n):
        return jnp.squeeze(gather_pages(pool, table, n, b, s), b)

    return jax.vmap(row, out_axes=b)(tables, n_live)


def paged_gather_rows(cache: Any, batch_axes: Any, page_axes: Any,
                      tables, n_live) -> Any:
    """Every slot's row at once, in the dense cache's own layout (the
    slot axis at each leaf's batch axis): dense leaves already are and
    pass through; pool leaves assemble all rows through their tables
    (:func:`gather_rows`)."""
    def one(leaf, b, s):
        if s < 0:
            return leaf
        return gather_rows(leaf, tables, n_live, b, s)

    return jax.tree.map(one, cache, batch_axes, page_axes)


def paged_scatter_decode(cache: Any, rows: Any, batch_axes: Any,
                         page_axes: Any, tables, pos, live) -> Any:
    """Decode-tick write-back of every slot: dense leaves take the new
    rows of live slots and keep their old bits in dead ones (exact
    select), pool leaves write each slot's ONE page containing its
    ``pos`` (dead slots -> NULL page)."""
    def one(leaf, r, b, s):
        if s < 0:
            return select_live(r, leaf, b, live)
        return scatter_decode_pages(leaf, r, tables, pos, live, b, s)

    return jax.tree.map(one, cache, rows, batch_axes, page_axes)


# ---------------------------------------------------------------------------
# Deterministic free-list allocator (plain Python — never inside a trace)
# ---------------------------------------------------------------------------

class PageAllocator:
    """Lowest-numbered-page-first free list over pages 1..num_pages.

    Deterministic (sorted free list, like the scheduler's lowest-free-
    slot policy) so a replayed trace allocates identically — and page
    placement could not change a request's bits even if it didn't,
    because the gather/scatter programs take the table as a traced
    operand. Page 0 (``NULL_PAGE``) is reserved and never enters the
    free list.
    """

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        self.num_pages = num_pages
        self._free: List[int] = list(range(1, num_pages + 1))

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Take the ``n`` lowest free pages; raises on exhaustion (the
        engine checks ``free_count`` first — running out here means a
        bookkeeping bug, not backpressure)."""
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: need {n}, have {len(self._free)} "
                f"free of {self.num_pages}")
        taken, self._free = self._free[:n], self._free[n:]
        return taken

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            if p == NULL_PAGE or p > self.num_pages:
                raise ValueError(f"cannot free page {p}")
            if p in self._free:
                raise ValueError(f"double free of page {p}")
            bisect.insort(self._free, p)


# ---------------------------------------------------------------------------
# The pool-backed cache
# ---------------------------------------------------------------------------

class PagedKVCache:
    """Mixed dense/paged slot cache over a model-zoo cache pytree.

    Pageable leaves (``models.common.cache_page_axes``) live as pools of
    shape ``[num_pages+1, page_size, *rest]`` (page 0 = NULL); every
    other leaf keeps its dense ``max_slots`` row exactly as
    ``SlotKVCache`` holds it. The jitted mutators are cached on the
    model (the same pool as the engine's compiled programs), so sibling
    engines over one model share compiled code.
    """

    def __init__(self, model, max_slots: int, max_len: int,
                 page_size: int, num_pages: int):
        if max_len % page_size:
            raise ValueError(
                f"max_len={max_len} must be a multiple of "
                f"page_size={page_size}")
        self.model = model
        self.max_slots = max_slots
        self.max_len = max_len
        self.page_size = page_size
        self.num_pages = num_pages
        #: pages per logical row — the static page-table width.
        self.max_pages = max_len // page_size

        row, self.specs = model.init_cache(1, max_len)
        self.batch_axes = cache_batch_axes(self.specs)
        self.page_axes = cache_page_axes(row, self.specs, max_len)

        # The gather zero-fill contract requires pristine == all-zeros
        # for every pageable leaf; checked once, on host, at
        # construction (never inside a trace).
        for leaf, s in zip(jax.tree.leaves(row),
                           jax.tree.leaves(self.page_axes)):
            if s >= 0 and np.asarray(leaf).any():
                raise ValueError(
                    "pageable cache leaf has a non-zero pristine state — "
                    "the paged layout's zero-fill gather cannot represent "
                    "it (keep the leaf dense via the kv_ring spec flag)")

        # Dense leaves keep their full max_slots allocation; pageable
        # leaves are replaced by pristine-zero pools. Where every leaf
        # is pageable only the shapes of the full cache are taken, so
        # set-up never holds a dense-size cache beside the pool (where
        # some are dense, the transient full-size arrays are dropped
        # right here, before first use).
        flat_s = jax.tree.leaves(self.page_axes)
        if all(s >= 0 for s in flat_s):
            full = jax.eval_shape(
                lambda: model.init_cache(max_slots, max_len)[0])
        else:
            full, _ = model.init_cache(max_slots, max_len)
        flat_full = jax.tree.leaves(full)
        flat_row = jax.tree.leaves(row)
        flat_b = jax.tree.leaves(self.batch_axes)
        flat = []
        for lf, lr, b, s in zip(flat_full, flat_row, flat_b, flat_s):
            if s < 0:
                flat.append(lf)
            else:
                canon = _to_positions(lr, b, s)
                flat.append(jnp.zeros(
                    (num_pages + 1, page_size) + canon.shape[1:], lf.dtype))
        self.cache = jax.tree.unflatten(jax.tree.structure(full), flat)

        key = ("paged", max_slots, max_len, page_size, num_pages)
        pool = model.__dict__.setdefault("_serve_compiled", {})
        if key not in pool:
            b_axes, s_axes = self.batch_axes, self.page_axes

            @functools.partial(jax.jit, donate_argnums=_donate())
            def _reset_dense(cache, slot):
                prow, _ = model.init_cache(1, max_len)

                def one(leaf, r, b, s):
                    if s < 0:
                        return _update_leaf(leaf, r, b, slot)
                    return leaf            # pool leaves: page-level reset

                return jax.tree.map(one, cache, prow, b_axes, s_axes)

            @functools.partial(jax.jit, donate_argnums=_donate())
            def _reset_page(cache, pid):
                def one(leaf, s):
                    if s < 0:
                        return leaf
                    zero = jnp.zeros((1,) + leaf.shape[1:], leaf.dtype)
                    starts = (pid,) + (jnp.int32(0),) * (leaf.ndim - 1)
                    return jax.lax.dynamic_update_slice(leaf, zero, starts)

                return jax.tree.map(one, cache, s_axes)

            @functools.partial(jax.jit, donate_argnums=_donate())
            def _copy_page(cache, src, dst):
                def one(leaf, s):
                    if s < 0:
                        return leaf
                    page = jax.lax.dynamic_index_in_dim(leaf, src, axis=0)
                    starts = (dst,) + (jnp.int32(0),) * (leaf.ndim - 1)
                    return jax.lax.dynamic_update_slice(leaf, page, starts)

                return jax.tree.map(one, cache, s_axes)

            @jax.jit
            def _read_row(cache, slot, table, n_live):
                return paged_gather_row(cache, b_axes, s_axes, slot, table,
                                        n_live)

            pool[key] = (_reset_dense, _reset_page, _copy_page, _read_row)
        (self._reset_dense, self._reset_page, self._copy_page,
         self._read_row) = pool[key]

    @staticmethod
    def pageable(model, max_len: int) -> bool:
        """True when the family has at least one pageable leaf (the
        engine falls back to the dense layout otherwise — SSM/xLSTM
        recurrent state, all-window hybrids)."""
        row, specs = model.init_cache(1, max_len)
        axes = cache_page_axes(row, specs, max_len)
        return any(s >= 0 for s in jax.tree.leaves(axes))

    # ------------------------------------------------------------- mutators
    def read(self, slot: int, table: np.ndarray, n_live: int) -> Any:
        """Dense-equivalent batch-1 row of a request (introspection /
        tests): dense leaves from its slot, pool leaves through its
        table with live-page zero-fill."""
        return self._read_row(self.cache, jnp.asarray(slot, jnp.int32),
                              jnp.asarray(table, jnp.int32),
                              jnp.asarray(n_live, jnp.int32))

    def reset(self, slot: int) -> None:
        """Return a freed slot's DENSE leaves to the pristine init row
        (the eviction hook ``SlotKVCache.reset`` provides, minus the
        pool leaves — their hygiene is page-granular, see
        ``reset_pages``)."""
        self.cache = self._reset_dense(self.cache,
                                       jnp.asarray(slot, jnp.int32))

    def reset_pages(self, pages: Sequence[int]) -> None:
        """Zero freed pages before they re-enter the free list — the
        page-granular pristine-bits guarantee the gather zero-fill (and
        the eviction-hygiene test) relies on."""
        for pid in pages:
            self.cache = self._reset_page(self.cache,
                                          jnp.asarray(pid, jnp.int32))

    def copy_page(self, src: int, dst: int) -> None:
        """Device-side page copy (copy-on-write at the first divergent
        prefix page) — pure data movement, so the copied bits are the
        donor's bits."""
        self.cache = self._copy_page(self.cache, jnp.asarray(src, jnp.int32),
                                     jnp.asarray(dst, jnp.int32))

    # ----------------------------------------------------------- accounting
    @property
    def page_bytes(self) -> int:
        """Bytes of ONE page across every pool leaf — the unit of the
        engine's live-memory accounting."""
        total = 0
        for leaf, s in zip(jax.tree.leaves(self.cache),
                           jax.tree.leaves(self.page_axes)):
            if s >= 0:
                n = 1
                for d in leaf.shape[1:]:
                    n *= d
                total += n * leaf.dtype.itemsize
        return total
