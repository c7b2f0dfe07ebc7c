"""Shared model-assembly machinery.

* ``stack_init`` — vmap a per-layer init over L keys -> params stacked with a
  leading "layers" axis (never sharded), ready for ``lax.scan`` over layers
  (keeps the HLO size O(1) in depth, so compile time does not grow with
  the layer count).
* ``chunked_ce_loss`` — the vocab matmul + cross-entropy evaluated in
  sequence chunks under ``jax.checkpoint`` with KAHAN-COMPENSATED chunk
  accumulation (paper technique, applied to the longest fp32 reduction in
  training: the per-token loss sum over ~1M tokens).
* ``prefill_chunk_scan`` / ``decode_prefill_chunk`` — the model-zoo half
  of the serving engine's chunked prefill: advance a batch-1 decode cache
  by a fixed-width token chunk starting at an arbitrary offset, one
  position at a time through ONE barrier-pinned traced body (the
  families' ``prefill_chunk`` methods delegate here).
  ``prefill_chunk_body`` is that body, exported standalone so the trace
  auditor (``repro.analysis.trace``) can verify every compiled chunk
  program carries its exact primitive sequence.
* ``parallel_chunk_logits`` — the parallel (flash) chunk body's
  last-valid-position logits: families that can run a whole chunk in
  ONE forward pass (``prefill_chunk_parallel``, engine
  ``prefill_mode="flash"``) share it to sample the request's first
  token; the per-position scan above stays the oracle.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.kahan import kahan_step
from repro.models.layers import _dtype

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Stacked (scan-over-layers) parameter trees
# ---------------------------------------------------------------------------

def stack_init(key, n: int, init_fn: Callable) -> Tuple[Params, Params]:
    """Run ``init_fn(key_i)`` for n layer keys, stacking results on axis 0."""
    keys = jax.random.split(key, n)
    params = jax.vmap(lambda k: init_fn(k)[0])(keys)
    _, spec = init_fn(keys[0])  # structure only
    spec = stack_specs(spec)
    return params, spec


def stack_specs(spec_tree: Params) -> Params:
    """Prepend an unsharded "layers" axis to every PartitionSpec leaf."""
    return jax.tree.map(lambda s: P(None, *s),
                        spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


def cache_batch_axes(cache_specs: Params) -> Params:
    """Per-leaf index of the request ("batch") axis in a decode cache.

    Every model's ``init_cache`` returns ``(cache, specs)`` with a
    matching tree of logical PartitionSpecs, and every cache leaf marks
    its request dimension with the logical axis name ``"batch"`` —
    stacked (scan-over-layers) leaves carry it one position deeper, ring
    buffers and recurrent SSM/xLSTM states wherever their layout puts
    it. This helper turns those specs into a pytree of ints (same
    structure as the cache), which is the slot-addressing contract the
    serving engine builds on: ``repro.serve.slots`` uses it both as the
    scatter axis for per-slot cache writes/resets and as the ``vmap``
    in/out axes for the batched decode tick. Leaves that do not mark a
    batch axis fail fast here, at engine construction — never inside a
    trace.
    """
    def one(sp: P) -> int:
        for i, name in enumerate(sp):
            if name == "batch" or (isinstance(name, tuple) and "batch" in name):
                return i
        raise ValueError(
            f"cache spec {sp} does not mark a 'batch' axis; every cache "
            "leaf must be slot-addressable for request-level serving")

    return jax.tree.map(one, cache_specs,
                        is_leaf=lambda s: isinstance(s, P))


def cache_page_axes(cache: Any, cache_specs: Params, max_len: int) -> Any:
    """Per-leaf index of the PAGEABLE sequence axis (-1 = dense per-slot).

    The page-aware counterpart of :func:`cache_batch_axes`: a cache leaf
    is pageable — its positions may live scattered across a fixed-size
    page pool (``repro.serve.paging``) — exactly when its spec names a
    ``"kv_seq"`` axis and the leaf allocates the full ``max_len``
    positions along it. ``"kv_seq"`` is reserved for POSITION-ADDRESSED
    KV history (decode writes position ``pos`` at index ``pos``), which
    is what makes page-granular gather/scatter pure data movement.

    Everything else stays dense per-slot, and the spec axis name IS the
    documented ``pageable=False`` flag:

    * ring-buffer window caches mark their length axis ``"kv_ring"``
      (see ``models.hybrid``) — their addressing is modular
      (``pos % window``), so a page does not correspond to a contiguous
      position range;
    * recurrent SSM / xLSTM state and one-shot cross-attention K/V
      (``encdec``'s ``xk``/``xv``) carry no ``"kv_seq"`` axis at all.

    Defensive depth: a ``"kv_seq"`` leaf allocated shorter than
    ``max_len`` (a ring buffer that kept the wrong name) fails fast
    here, at engine construction — never inside a trace.
    """
    def one(leaf, sp: P) -> int:
        for i, name in enumerate(sp):
            if name == "kv_seq" or (isinstance(name, tuple)
                                    and "kv_seq" in name):
                if leaf.shape[i] != max_len:
                    raise ValueError(
                        f"cache leaf {leaf.shape} marks axis {i} as "
                        f"'kv_seq' but allocates {leaf.shape[i]} != "
                        f"max_len={max_len} positions — ring-buffer "
                        f"caches must use the 'kv_ring' axis name "
                        f"(the pageable=False spec flag)")
                return i
        return -1

    spec_leaf = lambda s: isinstance(s, P)  # noqa: E731
    return jax.tree.map(
        one, cache,
        jax.tree.unflatten(jax.tree.structure(cache),
                           jax.tree.leaves(cache_specs, is_leaf=spec_leaf)))


# ---------------------------------------------------------------------------
# Chunked (resume-from-offset) prefill
# ---------------------------------------------------------------------------

def prefill_chunk_body(step_fn: Callable, offset: jax.Array,
                       nvalid: jax.Array) -> Callable:
    """The ONE barrier-pinned per-position scan body of chunked prefill.

    Exported standalone (rather than living as a closure inside
    ``prefill_chunk_scan``) so the trace auditor
    (``repro.analysis.trace``) can trace it in isolation and assert that
    every compiled chunk-width program contains exactly this primitive
    sequence — the registration hook of the ``trace-barrier-pinned``
    rule, mirroring how ``kernels.flash_attention.flash_block_update`` is
    shared by kernel and oracle. The barriers pin the body boundary so
    XLA cannot fuse or vectorize it differently per chunk width.
    """

    def body(carry, inp):
        cache, last = carry
        tok, i = inp
        cache = jax.lax.optimization_barrier(cache)
        logits, new_cache = step_fn(cache, tok, offset + i)
        logits, new_cache = jax.lax.optimization_barrier((logits, new_cache))
        valid = i < nvalid
        cache = jax.tree.map(lambda n, o: jnp.where(valid, n, o),
                             new_cache, cache)
        last = jnp.where(valid, logits[0], last)
        return (cache, last), None

    return body


def prefill_chunk_scan(step_fn: Callable, tokens: jax.Array, cache: Any,
                       offset: jax.Array, nvalid: jax.Array, v_pad: int,
                       ) -> Tuple[jax.Array, Any]:
    """Advance a batch-1 decode cache by one fixed-width prompt chunk.

    ``tokens``: [1, w] int32 — the chunk, zero-padded past ``nvalid``
    (bucket padding: the serving engine rounds a partial tail chunk up
    to a small power-of-two bucket so the compiled program set stays
    O(#buckets), not O(#distinct prompt lengths)). ``offset`` / ``nvalid``
    are TRACED scalars: position ``offset + i`` is fed to the body per
    step, so resuming at any offset reuses one compiled program.
    ``step_fn(cache, token, pos) -> (logits [1, v_pad], cache)`` is the
    model's single-position decode body. Returns ``(logits of the last
    VALID position [1, v_pad], advanced cache)``.

    THE BITWISE DISCIPLINE (the serving analogue of the kernel/oracle
    shared-block-body technique): every prompt position is computed by
    this ONE traced body via ``lax.scan``, whatever chunk width the
    program around it has — one-shot admit (w = prompt_len), full chunks
    (w = prefill_chunk) and padded tail buckets all execute the identical
    per-position rounding sequence. ``lax.optimization_barrier`` pins the
    body boundary so XLA cannot fuse or vectorize it differently per
    chunk width (measured on XLA CPU: unpinned cross-width programs
    drift by an ulp, the same failure mode as vmap's batch
    vectorization). Steps past ``nvalid`` run on the pad token and are
    DISCARDED by an exact elementwise select, so bucket padding never
    touches the cache or the returned logits.
    """
    w = tokens.shape[-1]
    body = prefill_chunk_body(step_fn, offset, nvalid)
    last0 = jnp.zeros((v_pad,), jnp.float32)
    (cache, last), _ = jax.lax.scan(
        body, (cache, last0), (tokens[0], jnp.arange(w)))
    return last[None], cache


def parallel_chunk_logits(x: jax.Array, params: Params, cfg: ArchConfig,
                          nvalid: jax.Array) -> jax.Array:
    """Last-VALID-position logits of a parallel prefill chunk.

    ``x``: [1, w, D] — the chunk's final hidden states from ONE
    multi-token forward pass (``prefill_chunk_parallel``); ``nvalid`` is
    the traced count of real (non-bucket-padding) positions, >= 1 for
    every scheduled chunk. The serving engine samples a request's first
    token from these logits when the chunk completes the prompt, so this
    is the parallel body's analogue of ``prefill_chunk_scan``'s
    last-valid select — implemented as a dynamic slice of the HIDDEN
    state (one [1, 1, D] row) so only one position pays the vocab
    projection.
    """
    idx = jnp.clip(nvalid - 1, 0, x.shape[1] - 1)
    x_last = jax.lax.dynamic_slice_in_dim(x, idx, 1, axis=1)
    return decode_logits(x_last, params, cfg)


def decode_prefill_chunk(model, params: Params, batch: Dict[str, jax.Array],
                         cache: Any, offset: jax.Array, nvalid: jax.Array,
                         ) -> Tuple[jax.Array, Any]:
    """Default family ``prefill_chunk``: the per-position body IS the
    model's own ``decode_step``, so chunked prefill shares its update
    semantics (cache writes at traced positions, ring wrap, recurrent
    state) with decode by construction."""

    def step(cache, tok, pos):
        return model.decode_step(params, cache, tok[None], pos)

    return prefill_chunk_scan(step, batch["tokens"], cache, offset, nvalid,
                              model.cfg.padded_vocab)


# ---------------------------------------------------------------------------
# Compensated chunked cross-entropy
# ---------------------------------------------------------------------------

def chunked_ce_loss(x: jax.Array, head_w: jax.Array, labels: jax.Array,
                    mask: jax.Array, cfg: ArchConfig,
                    ) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy over a vocab-sharded head, chunked over sequence.

    x: [B,S,D] final hidden states; head_w: [D, V_padded]; labels [B,S]
    int32; mask [B,S] {0,1}. Returns (sum_loss, sum_count) — caller divides
    (the division is deferred so microbatch accumulation stays compensated).

    Each chunk's logits ([B,chunk,V]) exist only inside a jax.checkpoint
    region — the backward pass recomputes them, bounding live memory at
    O(B*chunk*V / n_model_shards). Chunk partial losses fold into a Kahan
    accumulator when cfg.kahan_loss (the paper's kernel, applied at the
    loss level).
    """
    b, s, d = x.shape
    chunk = min(cfg.loss_chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nch = x.shape[1] // chunk

    xs = x.reshape(b, nch, chunk, d).swapaxes(0, 1)          # [nch,B,c,D]
    ls = labels.reshape(b, nch, chunk).swapaxes(0, 1)
    ms = mask.reshape(b, nch, chunk).swapaxes(0, 1)

    v_pad = head_w.shape[-1]
    vocab_bias = jnp.where(jnp.arange(v_pad) < cfg.vocab_size, 0.0,
                           -1e30).astype(jnp.float32)

    @jax.checkpoint
    def chunk_loss(xc, lc, mc):
        # contract: allow-no-uncompensated-reduction(logit projection; fp32 preferred_element_type, d_model terms)
        logits = jax.lax.dot_general(
            xc, head_w.astype(xc.dtype),
            dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [B,c,V] fp32
        logits = logits + vocab_bias
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None],
                                   axis=-1)[..., 0]
        mcf = mc.astype(jnp.float32)
        return jnp.sum((lse - gold) * mcf), jnp.sum(mcf)  # contract: allow-no-uncompensated-reduction(chunk-local partial; the scan carry is the kahan_loss-compensated fold)

    def body(carry, inp):
        s_acc, c_acc, cnt = carry
        xc, lc, mc = inp
        part, n = chunk_loss(xc, lc, mc)
        if cfg.kahan_loss:
            s_acc, c_acc = kahan_step(s_acc, c_acc, part)
        else:
            s_acc = s_acc + part
        return (s_acc, c_acc, cnt + n), None

    init = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32))
    (s_acc, c_acc, cnt), _ = jax.lax.scan(body, init, (xs, ls, ms))
    return s_acc + c_acc, cnt


# ---------------------------------------------------------------------------
# Embedding / head helpers
# ---------------------------------------------------------------------------

def lm_head_weight(params: Params, cfg: ArchConfig) -> jax.Array:
    """[D, V_padded] head weight (transposed embed table when tied)."""
    if cfg.tie_embeddings:
        return params["embed"]["table"].T
    return params["head"]["w"]


def decode_logits(x_last: jax.Array, params: Params, cfg: ArchConfig,
                  ) -> jax.Array:
    """Logits for a single-position hidden state [B,1,D] -> [B,V_padded]."""
    w = lm_head_weight(params, cfg)
    # contract: allow-no-uncompensated-reduction(decode logit projection; fp32 preferred_element_type, d_model terms)
    logits = jax.lax.dot_general(
        x_last[:, 0, :], w.astype(x_last.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    v_pad = w.shape[-1]
    return logits + jnp.where(jnp.arange(v_pad) < cfg.vocab_size, 0.0, -1e30)


def init_embed_and_head(key, cfg: ArchConfig) -> Tuple[Params, Params]:
    from repro.models.layers import embed_init, dense_init, norm_init

    k1, k2, k3 = jax.random.split(key, 3)
    dt = _dtype(cfg.param_dtype)
    params: Params = {}
    specs: Params = {}
    pe, se = embed_init(k1, cfg.padded_vocab, cfg.d_model, dt)
    params["embed"], specs["embed"] = pe, se
    pn, sn = norm_init(cfg.d_model, cfg.norm, dt)
    params["final_norm"], specs["final_norm"] = pn, sn
    if not cfg.tie_embeddings:
        ph, sh = dense_init(k2, cfg.d_model, cfg.padded_vocab, dtype=dt,
                            spec_in="embed", spec_out="vocab", scale=0.02)
        params["head"], specs["head"] = ph, sh
    return params, specs
