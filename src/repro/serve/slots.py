"""Slot-addressed KV/recurrent cache for the continuous-batching engine.

One cache pytree with a fixed request axis of ``max_slots`` rows, built
by the model's own ``init_cache`` — KV tensors for attention layers,
ring buffers for sliding-window layers, SSM / xLSTM recurrent state for
the subquadratic families. Which dimension of each leaf is the request
axis comes from the model's cache specs via
``repro.models.cache_batch_axes`` — the models' slot-addressing hook —
so this module needs no per-family knowledge.

The per-leaf row operations are exposed two ways:

* pure traceable helpers ``gather_row`` / ``scatter_row`` — the engine's
  chunked-prefill programs compose them IN-TRACE (extract the occupied
  slot's batch-1 row, advance it by one prompt chunk at an offset,
  scatter it back — one fused jit program per chunk width, donated);
* jitted ``SlotKVCache`` methods — ``reset`` (restore a slot to the
  model's pristine init row, run on eviction so a freed slot never
  carries stale state) and ``read`` (fetch a slot's row — the
  introspection hook the eviction-hygiene test audits reset with).

All slot indices are traced (``dynamic_slice`` / ``dynamic_update_slice``
at a traced start), so operating on slot 0 and slot 7 share one compiled
program. Mutating methods donate the big cache, so slot writes are
in-place buffer updates, not O(max_slots) copies.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.common import cache_batch_axes


def _update_leaf(big: jax.Array, row: jax.Array, axis: int, slot) -> jax.Array:
    starts = [jnp.int32(0)] * big.ndim
    starts[axis] = slot
    return jax.lax.dynamic_update_slice(big, row.astype(big.dtype),
                                        tuple(starts))


def _take_leaf(big: jax.Array, axis: int, slot) -> jax.Array:
    starts = [jnp.int32(0)] * big.ndim
    starts[axis] = slot
    sizes = list(big.shape)
    sizes[axis] = 1
    return jax.lax.dynamic_slice(big, tuple(starts), sizes)


def select_live(new: jax.Array, old: jax.Array, axis: int, live) -> jax.Array:
    """``new`` in the rows whose ``live`` flag is set, ``old`` elsewhere:
    an exact select along the request axis ``axis`` (``live`` [max_slots])."""
    shape = [1] * new.ndim
    shape[axis] = live.shape[0]
    return jnp.where(live.reshape(shape), new, old)


def gather_row(cache: Any, axes: Any, slot) -> Any:
    """Extract slot ``slot`` as a batch-1 row cache (pure; traceable)."""
    return jax.tree.map(lambda big, a: _take_leaf(big, a, slot), cache, axes)


def scatter_row(cache: Any, row: Any, axes: Any, slot) -> Any:
    """Install a batch-1 row cache at slot ``slot`` (pure; traceable)."""
    return jax.tree.map(lambda big, r, a: _update_leaf(big, r, a, slot),
                        cache, row, axes)


def _donate():
    # buffer donation is a no-op (plus a warning) on CPU; only request it
    # where the runtime honors it.
    return (0,) if jax.default_backend() != "cpu" else ()


class SlotKVCache:
    """Fixed-batch slot cache over a model-zoo cache pytree."""

    def __init__(self, model, max_slots: int, max_len: int):
        self.model = model
        self.max_slots = max_slots
        self.max_len = max_len
        self.cache, self.specs = model.init_cache(max_slots, max_len)
        #: pytree of ints (cache structure): the request axis per leaf —
        #: slice/scatter axis here, vmap in/out axes in the engine tick.
        self.batch_axes = cache_batch_axes(self.specs)

        # the jitted mutators are cached ON the model (same pool as the
        # engine's compiled programs), so every engine over one model
        # instance — solo replays, one-shot references, benchmark reruns
        # — shares ONE compiled write/reset/read instead of recompiling
        # per SlotKVCache
        key = ("slots", max_slots, max_len)
        pool = model.__dict__.setdefault("_serve_compiled", {})
        if key not in pool:
            axes = self.batch_axes

            @functools.partial(jax.jit, donate_argnums=_donate())
            def _reset(cache, slot):
                row, _ = model.init_cache(1, max_len)
                return scatter_row(cache, row, axes, slot)

            @jax.jit
            def _read(cache, slot):
                return gather_row(cache, axes, slot)

            pool[key] = (_reset, _read)
        self._reset, self._read = pool[key]

    def read(self, slot: int) -> Any:
        """Fetch ``slot``'s batch-1 row cache (introspection / tests)."""
        return self._read(self.cache, jnp.asarray(slot, jnp.int32))

    def reset(self, slot: int) -> None:
        """Return ``slot`` to the model's pristine init state (eviction
        hook — freed slots never leak a previous request's state)."""
        self.cache = self._reset(self.cache, jnp.asarray(slot, jnp.int32))
