"""Functional model layers (no flax): init functions return a ``(params,
specs)`` pair — ``params`` is a nested dict of arrays, ``specs`` a matching
nested dict of *logical* PartitionSpecs (tuples of logical axis names).
``repro.distributed.sharding`` maps logical names onto mesh axes.

Logical axis vocabulary:
  embed      d_model dims of weights (FSDP axis in train rules)
  heads      flattened attention-head dim (TP axis when divisible)
  kv_heads   KV head dim
  mlp        FFN hidden
  vocab      (padded) vocabulary
  expert     MoE expert dim
  kv_lora    MLA latent dim
  xl_inner   xLSTM inner dim
  layers     stacked-scan leading axis (never sharded)

Dtype policy: parameters are created in ``cfg.param_dtype``; matmuls run in
``cfg.compute_dtype``; softmax / norm statistics / losses in fp32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig

Params = Dict[str, Any]


def _dtype(name: str):
    return jnp.dtype(name)


def _init_normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Linear / norms / embeddings
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out, *, dtype, bias: bool = False,
               spec_in: str = "embed", spec_out=None,
               scale: Optional[float] = None) -> Tuple[Params, Params]:
    """General dense layer. ``d_out``/``spec_out`` may be tuples for fused
    multi-dim outputs (e.g. (H, dh))."""
    d_out_t = d_out if isinstance(d_out, tuple) else (d_out,)
    spec_out_t = spec_out if isinstance(spec_out, tuple) else (spec_out,)
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": _init_normal(key, (d_in, *d_out_t), scale, dtype)}
    s = {"w": P(spec_in, *spec_out_t)}
    if bias:
        p["b"] = jnp.zeros(d_out_t, dtype)
        s["b"] = P(*spec_out_t)
    return p, s


def dense(p: Params, x: jax.Array, compute_dtype, *,
          compensated: bool = False) -> jax.Array:
    """Dense projection. With ``compensated=True`` (ArchConfig
    ``kahan_matmul``) the contraction routes through the engine's
    compensated matmul (``ops.matmul`` — custom-VJP, so training
    gradients also accumulate compensated); scheme / blocks / accumulate
    dtype come from the ambient ``repro.kernels`` Policy."""
    w = p["w"].astype(compute_dtype)
    if compensated:
        from repro.kernels import ops as _ops

        lead = x.shape[:-1]
        out_dims = w.shape[1:]
        x2 = x.astype(compute_dtype).reshape(-1, x.shape[-1])
        w2 = w.reshape(w.shape[0], -1)
        y = _ops.matmul(x2, w2).astype(compute_dtype)
        y = y.reshape(*lead, *out_dims)
    else:
        # contract: allow-no-uncompensated-reduction(Policy-selected fast path; compensated branch above is the default)
        y = jax.lax.dot_general(
            x.astype(compute_dtype), w,
            dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())))
    if "b" in p:
        y = y + p["b"].astype(compute_dtype)
    return y


def norm_init(d: int, kind: str, dtype) -> Tuple[Params, Params]:
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}, {"scale": P(None)}
    if kind == "layernorm":
        return ({"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
                {"scale": P(None), "bias": P(None)})
    if kind == "layernorm_np":  # OLMo non-parametric LN
        return {}, {}
    raise ValueError(kind)


def norm_apply(p: Params, x: jax.Array, kind: str) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)  # contract: allow-no-uncompensated-reduction(rmsnorm variance; d_model fp32 terms feeding an rsqrt)
        y = xf * jax.lax.rsqrt(var + 1e-6) * p["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)  # contract: allow-no-uncompensated-reduction(layernorm mean; d_model fp32 terms)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + 1e-5)
        if kind == "layernorm":
            y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def embed_init(key, vocab: int, d: int, dtype) -> Tuple[Params, Params]:
    return ({"table": _init_normal(key, (vocab, d), 0.02, dtype)},
            {"table": P("vocab", "embed")})


def embed_lookup(p: Params, tokens: jax.Array, compute_dtype) -> jax.Array:
    return p["table"].astype(compute_dtype)[tokens]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_apply(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, dh] (dh even); pos: broadcastable to [..., S]."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., :, None, :]  # [..., S, 1, half]
    sin = jnp.sin(ang)[..., :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, sliding window, cache, cross-attention)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AttnStatic:
    """Static attention wiring derived from the ArchConfig."""

    n_heads: int
    n_kv: int
    d_head: int
    theta: float
    qkv_bias: bool
    compute_dtype: Any
    # engine-kernel routing (ArchConfig.kahan_matmul / kahan_attention)
    kahan_matmul: bool = False
    kahan_attention: bool = False


def attn_init(key, cfg: ArchConfig, *, cross: bool = False) -> Tuple[Params, Params]:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    # shard heads over "heads" only when the production TP=16 divides them;
    # otherwise replicate (hymba 25H, whisper 20H).
    hspec = "heads" if h % 16 == 0 else None
    kvspec = "kv_heads" if kv % 16 == 0 else None
    pq, sq = dense_init(ks[0], d, (h, dh), dtype=dt, bias=cfg.qkv_bias,
                        spec_in="embed", spec_out=(hspec, None))
    pk, sk = dense_init(ks[1], d, (kv, dh), dtype=dt, bias=cfg.qkv_bias,
                        spec_in="embed", spec_out=(kvspec, None))
    pv, sv = dense_init(ks[2], d, (kv, dh), dtype=dt, bias=cfg.qkv_bias,
                        spec_in="embed", spec_out=(kvspec, None))
    po, so = dense_init(ks[3], h * dh, d, dtype=dt,
                        spec_in="heads", spec_out="embed",
                        scale=(h * dh) ** -0.5 / (2 * cfg.n_layers) ** 0.5)
    return ({"q": pq, "k": pk, "v": pv, "o": po},
            {"q": sq, "k": sk, "v": sv, "o": so})


def _mask_bias(q_pos, k_pos, *, causal: bool, window: int,
               k_len_valid=None) -> jax.Array:
    """[Sq, Sk] additive fp32 bias from position vectors. window<=0: full."""
    diff = q_pos[:, None] - k_pos[None, :]
    ok = jnp.ones(diff.shape, bool)
    if causal:
        ok = ok & (diff >= 0)
    if window > 0:
        ok = ok & (diff < window)
    if k_len_valid is not None:  # decode: only the filled prefix is valid
        ok = ok & (k_pos[None, :] < k_len_valid)
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


# default q-chunk for the flash-style attention core; bounds the transient
# fp32 score tensor to [B, H, Q_CHUNK, S_kv] per scan step.
ATTN_Q_CHUNK = 512


def _flash_core(qg: jax.Array, k: jax.Array, v: jax.Array,
                compute_dtype) -> jax.Array:
    """Grouped-query attention through the engine's fused flash kernel.

    qg: [B, Sq, KV, G, dh]; k/v: [B, Skv, KV, dh]. Query head-rows
    flatten [batch, kv_head, group]-major into the kernel's leading BH
    grid dimension; k/v flatten [batch, kv_head]-major ONCE and each k/v
    head is shared across its G query groups by the kernel's BlockSpec
    index map (``bh // G``) — the group duplication never leaves the
    index map, so prefill KV traffic stays at 1/G of the broadcast form.
    The engine owns padding / promotion / the compensated online-softmax
    accumulators (ambient Policy selects scheme + accumulate dtype).
    Causal, full-window only — callers guard.
    """
    from repro.kernels.flash_attention import flash_attention as _flash

    b, sq, kvh, g, dh = qg.shape
    skv = k.shape[1]
    qf = qg.transpose(0, 2, 3, 1, 4).reshape(b * kvh * g, sq, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, dh)
    out = _flash(qf, kf, vf, causal=True, q_groups=g)
    out = out.reshape(b, kvh, g, sq, dh).transpose(0, 3, 1, 2, 4)
    return out.astype(compute_dtype)


def _flash_chunk_core(qg: jax.Array, k: jax.Array, v: jax.Array,
                      q_off: jax.Array, compute_dtype) -> jax.Array:
    """Chunked-prefill GQA through the engine's chunk flash kernel.

    qg: [B, W, KV, G, dh] — one prefill chunk's queries, living at
    absolute positions ``q_off + i``; k/v: [B, Skv, KV, dh] — the slot's
    FULL cache (the chunk's K/V already written at ``q_off``). Same
    [batch, kv_head, group]-major flattening and BlockSpec-index-map KV
    sharing as ``_flash_core``; ``q_off`` is traced, so one compiled
    program serves every chunk of width W regardless of where in the
    prompt it lands.
    """
    from repro.kernels.flash_attention import (
        flash_chunk_attention as _flash_chunk)

    b, w, kvh, g, dh = qg.shape
    skv = k.shape[1]
    qf = qg.transpose(0, 2, 3, 1, 4).reshape(b * kvh * g, w, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, dh)
    out = _flash_chunk(qf, kf, vf, q_off=q_off, q_groups=g)
    out = out.reshape(b, kvh, g, w, dh).transpose(0, 3, 1, 2, 4)
    return out.astype(compute_dtype)


def _attn_core(q: jax.Array, k: jax.Array, v: jax.Array, q_pos: jax.Array,
               k_pos: jax.Array, *, causal: bool, window: int,
               compute_dtype, chunked: bool = True) -> jax.Array:
    """Grouped-query attention, optionally q-chunked.

    q: [B,Sq,KV,G,dh]; k/v: [B,Skv,KV,dh]; positions give the masking.
    Scores for one q-chunk against the FULL k are materialized in fp32 —
    [B,KV,G,qc,Skv] — then softmaxed locally (no online rescaling needed
    because k is not chunked). Returns [B,Sq,KV,G,dh] in compute dtype.

    ``chunked=False`` (the TRAINING path): under sequence-parallel sharding
    the score slab is already bounded by S/n_model_shards per device, and
    a q-chunk scan is actively harmful — GSPMD re-gathers the (loop-
    invariant) K/V inside the scan body every iteration (measured on
    qwen2.5-3b: ~200 GB/device/step of repeated all-gathers). Prefill
    (serve rules, batch-sharded only) keeps the chunked path for memory.
    """
    b, sq, kvh, g, dh = q.shape
    scale = dh ** -0.5

    def one_chunk(qc, qp):
        # contract: allow-no-uncompensated-reduction(attention scores; fp32 over head_dim terms, flash path owns the compensated variant)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qc.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        bias = _mask_bias(qp, k_pos, causal=causal, window=window)
        scores = scores + bias
        # guard fully-masked rows (ring slots before they fill)
        m = jnp.max(scores, axis=-1, keepdims=True)
        m = jnp.maximum(m, -1e30)
        p = jnp.exp(scores - m)
        l = jnp.sum(p, axis=-1, keepdims=True)  # contract: allow-no-uncompensated-reduction(softmax normalizer; fp32, bounded by seq chunk)
        p = (p / jnp.maximum(l, 1e-30)).astype(compute_dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v)  # contract: allow-no-uncompensated-reduction(prob-weighted value mix; probs sum to 1)

    chunk = min(ATTN_Q_CHUNK, sq)
    if sq <= chunk or not chunked:
        return one_chunk(q, q_pos)
    pad = (-sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad))
    nch = q.shape[1] // chunk
    qs = q.reshape(b, nch, chunk, kvh, g, dh).swapaxes(0, 1)
    qps = q_pos.reshape(nch, chunk)

    def body(_, inp):
        qc, qp = inp
        return None, one_chunk(qc, qp)

    _, outs = jax.lax.scan(body, None, (qs, qps))
    out = outs.swapaxes(0, 1).reshape(b, nch * chunk, kvh, g, dh)
    return out[:, :sq]


def attention(p: Params, st: AttnStatic, x: jax.Array, *,
              q_pos: jax.Array,
              causal: bool = True,
              window: int = 0,
              cache: Optional[Tuple[jax.Array, jax.Array]] = None,
              cache_index: Optional[jax.Array] = None,
              cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
              chunk_valid: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """Unified attention.

    Modes:
      train/prefill: cache=None or zero-filled cache to populate; x=[B,S,D].
      decode: cache=(k,v) [B,Skv,KV,dh], cache_index = current position;
              x=[B,1,D]; q_pos = [cache_index].
      cross: cross_kv supplied (whisper); no cache/causality.
      chunk prefill: ``chunk_valid`` supplied with s > 1 and a cache —
              x is one prefill CHUNK whose tokens live at absolute
              positions ``cache_index + i`` (``q_pos`` must carry
              exactly those); only the first ``chunk_valid`` positions
              are real (the rest is bucket padding). The chunk's K/V are
              written into the cache at the traced offset by an EXACT
              positional select — rows outside [cache_index,
              cache_index + chunk_valid) keep their previous bits — and
              every query attends the FULL cache, causally on absolute
              positions (which also excludes not-yet-written rows).
              Routed through the engine's chunk flash kernel
              (``_flash_chunk_core``, compensated online softmax) when
              ``st.kahan_attention``, else the materialized parallel
              core. Ring buffers are NOT supported here (window layers'
              families fall back to the per-position scan body).

    Sliding-window layers may allocate the cache as a RING BUFFER of length
    ``window`` (< full sequence): slot ``t % window`` holds step ``t``; the
    absolute position of slot ``j`` is reconstructed for masking.

    Returns (out [B,S,D], new_cache or None).
    """
    cd = st.compute_dtype
    b, s, _ = x.shape
    cmp = st.kahan_matmul                          # engine-matmul routing
    q = dense(p["q"], x, cd, compensated=cmp)      # [B,S,H,dh] fused proj
    if cross_kv is None:
        k = dense(p["k"], x, cd, compensated=cmp)  # [B,S,KV,dh]
        v = dense(p["v"], x, cd, compensated=cmp)
        q = rope_apply(q, q_pos, st.theta)
        k = rope_apply(k, q_pos, st.theta)
    else:
        k, v = cross_kv                            # precomputed [B,F,KV,dh]

    new_cache = None
    ring = False
    chunk_prefill = chunk_valid is not None and cache is not None and s > 1
    if cache is not None and cross_kv is None:
        ck, cv = cache
        s_alloc = ck.shape[1]
        ring = window > 0 and s_alloc == window
        if chunk_prefill:
            if ring:
                raise ValueError(
                    "chunk-parallel prefill does not support ring-buffer "
                    "caches; window layers' families must fall back to "
                    "the per-position scan body")
            # Write the chunk's K/V at the traced offset with an EXACT
            # positional select (no dynamic_update_slice: its clamping
            # near the cache end would silently shift rows). Rows outside
            # [cache_index, cache_index + chunk_valid) keep their
            # previous bits, so bucket padding never touches the cache.
            rows = jnp.arange(s_alloc)
            rel = rows - cache_index
            keep = ((rel >= 0) & (rel < chunk_valid))[None, :, None, None]
            src = jnp.clip(rel, 0, s - 1)
            ck = jnp.where(keep, jnp.take(k, src, axis=1).astype(ck.dtype),
                           ck)
            cv = jnp.where(keep, jnp.take(v, src, axis=1).astype(cv.dtype),
                           cv)
        elif s == 1:  # decode: insert at cache_index (mod window when ring)
            slot = cache_index % s_alloc if ring else cache_index
            ck = jax.lax.dynamic_update_slice(
                ck, k.astype(ck.dtype), (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cv, v.astype(cv.dtype), (0, slot, 0, 0))
        elif ring:  # prefill into ring: keep the last `window` positions
            s_in = k.shape[1]
            j = jnp.arange(s_alloc)
            src = (s_in - 1) - ((s_in - 1 - j) % s_alloc)  # may be < 0 early
            src_c = jnp.clip(src, 0)
            ck = jnp.where((src >= 0)[None, :, None, None],
                           jnp.take(k, src_c, axis=1).astype(ck.dtype), 0)
            cv = jnp.where((src >= 0)[None, :, None, None],
                           jnp.take(v, src_c, axis=1).astype(cv.dtype), 0)
        else:       # prefill: fill the prefix
            ck = jax.lax.dynamic_update_slice(
                ck, k.astype(ck.dtype), (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cv, v.astype(cv.dtype), (0, 0, 0, 0))
        new_cache = (ck, cv)
        if s == 1 or chunk_prefill:  # decode / chunk attend the cache
            k, v = ck.astype(cd), cv.astype(cd)
        # prefill attends against the in-flight k/v (full positions)

    s_kv = k.shape[1]
    kv_heads = k.shape[2]
    groups = q.shape[2] // kv_heads
    qg = q.reshape(b, s, kv_heads, groups, q.shape[-1])

    if cross_kv is not None:
        k_pos = jnp.arange(s_kv)
        out = _attn_core(qg, k, v, q_pos, k_pos, causal=False, window=0,
                         compute_dtype=cd, chunked=cache is not None)
    elif cache is not None and s == 1:
        if ring:
            j = jnp.arange(s_kv)
            k_pos = cache_index - ((cache_index - j) % s_kv)
            # negative k_pos (unfilled ring slots) fail the causal test
            # only when also > q_pos; mask them via a large positive pos
            k_pos = jnp.where(k_pos >= 0, k_pos, jnp.iinfo(jnp.int32).max)
            out = _attn_core(qg, k, v, q_pos, k_pos, causal=True,
                             window=window, compute_dtype=cd)
        else:
            # decode against the valid prefix: positions beyond cache_index
            # get an out-of-causal-range position
            k_pos = jnp.arange(s_kv)
            k_pos = jnp.where(k_pos <= cache_index, k_pos,
                              jnp.iinfo(jnp.int32).max)
            out = _attn_core(qg, k, v, q_pos, k_pos, causal=True,
                             window=window, compute_dtype=cd)
    elif chunk_prefill:
        # one prefill CHUNK against the full cache at a traced offset:
        # causal masking on absolute positions subsumes excluding rows
        # past the chunk (a query at position p never reads keys > p, and
        # every key <= p is already written — earlier chunks filled the
        # prefix, the select above wrote this chunk's valid rows).
        if st.kahan_attention:
            out = _flash_chunk_core(qg, k, v, cache_index, cd)
        else:
            out = _attn_core(qg, k, v, q_pos, jnp.arange(s_kv), causal=True,
                             window=0, compute_dtype=cd, chunked=True)
    else:
        # cache present -> prefill (chunked); cache None -> training (SP
        # bounds the score slab; see _attn_core docstring)
        k_pos = jnp.arange(s_kv)
        if (st.kahan_attention and cache is not None and causal
                and window <= 0 and not ring and s == s_kv):
            # PREFILL through the engine's fused flash kernel with
            # compensated online-softmax accumulators. Training stays on
            # _attn_core (the Pallas kernel has no transpose rule — its
            # backward would need a flash-bwd kernel).
            out = _flash_core(qg, k, v, cd)
        else:
            out = _attn_core(qg, k, v, q_pos, k_pos, causal=causal,
                             window=window, compute_dtype=cd,
                             chunked=cache is not None)

    out = out.reshape(b, s, -1)
    out = dense(p["o"], out, cd, compensated=cmp)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ArchConfig) -> Tuple[Params, Params]:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    hspec = "heads" if h % 16 == 0 else None
    p_q, s_q = dense_init(ks[0], d, (h, m.qk_nope_dim + m.qk_rope_dim),
                          dtype=dt, spec_in="embed", spec_out=(hspec, None))
    p_dkv, s_dkv = dense_init(ks[1], d, m.kv_lora_rank, dtype=dt,
                              spec_in="embed", spec_out="kv_lora")
    p_kr, s_kr = dense_init(ks[2], d, m.qk_rope_dim, dtype=dt,
                            spec_in="embed", spec_out=None)
    p_uk, s_uk = dense_init(ks[3], m.kv_lora_rank, (h, m.qk_nope_dim),
                            dtype=dt, spec_in="kv_lora", spec_out=(hspec, None))
    p_uv, s_uv = dense_init(ks[4], m.kv_lora_rank, (h, m.v_head_dim),
                            dtype=dt, spec_in="kv_lora", spec_out=(hspec, None))
    p_o, s_o = dense_init(ks[5], h * m.v_head_dim, d, dtype=dt,
                          spec_in="heads", spec_out="embed",
                          scale=(h * m.v_head_dim) ** -0.5 / (2 * cfg.n_layers) ** 0.5)
    return ({"q": p_q, "dkv": p_dkv, "kr": p_kr, "uk": p_uk, "uv": p_uv,
             "o": p_o, "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), dt)}},
            {"q": s_q, "dkv": s_dkv, "kr": s_kr, "uk": s_uk, "uv": s_uv,
             "o": s_o, "kv_norm": {"scale": P(None)}})


def mla_attention(p: Params, cfg: ArchConfig, x: jax.Array, *,
                  q_pos: jax.Array,
                  cache: Optional[Tuple[jax.Array, jax.Array]] = None,
                  cache_index: Optional[jax.Array] = None,
                  ) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """MLA with the cache holding (c_kv [B,S,r], k_rope [B,S,dr]).

    Decode uses the weight-absorbed form (q-side absorption of W_uk and
    output-side absorption of W_uv) — the published serving optimization:
    per-step cost is O(S * (r + dr)) per head instead of re-expanding K/V.
    """
    m = cfg.mla
    cd = _dtype(cfg.compute_dtype)
    b, s, _ = x.shape
    h = cfg.n_heads
    scale_dim = m.qk_nope_dim + m.qk_rope_dim
    cmp = cfg.kahan_matmul                         # engine-matmul routing

    q = dense(p["q"], x, cd, compensated=cmp)                 # [B,S,H,nope+rope]
    q_nope = q[..., :m.qk_nope_dim]
    q_rope = rope_apply(q[..., m.qk_nope_dim:], q_pos, cfg.rope_theta)

    c_kv = dense(p["dkv"], x, cd, compensated=cmp)            # [B,S,r]
    c_kv = norm_apply(p["kv_norm"], c_kv, "rmsnorm")
    k_rope = dense(p["kr"], x, cd, compensated=cmp)[:, :, None, :]  # [B,S,1,dr]
    k_rope = rope_apply(k_rope, q_pos, cfg.rope_theta)[:, :, 0, :]

    decode = cache is not None and s == 1
    if cache is not None:
        cc, cr = cache
        if decode:
            cc = jax.lax.dynamic_update_slice(cc, c_kv.astype(cc.dtype),
                                              (0, cache_index, 0))
            cr = jax.lax.dynamic_update_slice(cr, k_rope.astype(cr.dtype),
                                              (0, cache_index, 0))
        else:
            cc = jax.lax.dynamic_update_slice(cc, c_kv.astype(cc.dtype), (0, 0, 0))
            cr = jax.lax.dynamic_update_slice(cr, k_rope.astype(cr.dtype), (0, 0, 0))
        cache = (cc, cr)
        c_all, r_all = cc.astype(cd), cr.astype(cd)
    else:
        c_all, r_all = c_kv, k_rope

    s_kv = c_all.shape[1]
    k_pos = jnp.arange(s_kv)
    w_uk = p["uk"]["w"].astype(cd)                            # [r,H,nope]
    w_uv = p["uv"]["w"].astype(cd)                            # [r,H,v]
    scale = scale_dim ** -0.5

    if decode:
        k_pos_m = jnp.where(k_pos <= cache_index, k_pos,
                            jnp.iinfo(jnp.int32).max)
        bias = _mask_bias(q_pos, k_pos_m, causal=True, window=0)
        # absorbed: q_c = q_nope @ W_uk^T -> [B,1,H,r]
        q_c = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)  # contract: allow-no-uncompensated-reduction(MLA absorbed projection; nope_dim terms in fp32)
        sc_nope = jnp.einsum("bqhr,bsr->bhqs", q_c.astype(jnp.float32),  # contract: allow-no-uncompensated-reduction(MLA latent scores; fp32 over rank r terms)
                             c_all.astype(jnp.float32))
        sc_rope = jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32),  # contract: allow-no-uncompensated-reduction(MLA rope scores; fp32 over rope_dim terms)
                             r_all.astype(jnp.float32))
        scores = (sc_nope + sc_rope) * scale + bias
        probs = jax.nn.softmax(scores, axis=-1).astype(cd)
        # [B,1,H,r]  contract: allow-no-uncompensated-reduction(prob-weighted latent mix; probs sum to 1)
        ctx_c = jnp.einsum("bhqs,bsr->bqhr", probs, c_all)
        ctx = jnp.einsum("bqhr,rhv->bqhv", ctx_c, w_uv)  # contract: allow-no-uncompensated-reduction(MLA value up-projection; rank r terms in fp32)
    else:
        # train/prefill: expand latent K/V once, q-chunk the scores
        k_nope = jnp.einsum("bsr,rhn->bshn", c_all, w_uk)  # contract: allow-no-uncompensated-reduction(MLA K expansion; rank r terms in fp32)
        v = jnp.einsum("bsr,rhv->bshv", c_all, w_uv)  # contract: allow-no-uncompensated-reduction(MLA V expansion; rank r terms in fp32)

        def one_chunk(qn_c, qr_c, qp):
            sc = (jnp.einsum("bqhn,bshn->bhqs", qn_c.astype(jnp.float32),  # contract: allow-no-uncompensated-reduction(MLA nope scores; fp32 over nope_dim terms)
                             k_nope.astype(jnp.float32))
                  + jnp.einsum("bqhd,bsd->bhqs", qr_c.astype(jnp.float32),  # contract: allow-no-uncompensated-reduction(MLA rope scores; fp32 over rope_dim terms)
                               r_all.astype(jnp.float32))) * scale
            sc = sc + _mask_bias(qp, k_pos, causal=True, window=0)
            pr = jax.nn.softmax(sc, axis=-1).astype(cd)
            return jnp.einsum("bhqs,bshv->bqhv", pr, v)  # contract: allow-no-uncompensated-reduction(prob-weighted value mix; probs sum to 1)

        chunk = min(ATTN_Q_CHUNK, s)
        if s <= chunk or cache is None:
            # training path: single block (SP bounds the slab; chunk scans
            # trigger repeated loop-invariant gathers — see _attn_core)
            ctx = one_chunk(q_nope, q_rope, q_pos)
        else:
            pad = (-s) % chunk
            qn, qr, qp = q_nope, q_rope, q_pos
            if pad:
                qn = jnp.pad(qn, ((0, 0), (0, pad), (0, 0), (0, 0)))
                qr = jnp.pad(qr, ((0, 0), (0, pad), (0, 0), (0, 0)))
                qp = jnp.pad(qp, (0, pad))
            nch = qn.shape[1] // chunk

            def split(t):
                return t.reshape(b, nch, chunk, *t.shape[2:]).swapaxes(0, 1)

            def body(_, inp):
                qn_c, qr_c, qp_c = inp
                return None, one_chunk(qn_c, qr_c, qp_c)

            _, outs = jax.lax.scan(
                body, None, (split(qn), split(qr), qp.reshape(nch, chunk)))
            ctx = outs.swapaxes(0, 1).reshape(b, nch * chunk, h,
                                              m.v_head_dim)[:, :s]

    out = dense(p["o"], ctx.reshape(b, s, h * m.v_head_dim), cd,
                compensated=cmp)
    return out, cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ArchConfig, d_ff: Optional[int] = None,
             spec_hidden: str = "mlp") -> Tuple[Params, Params]:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    if cfg.mlp == "swiglu":
        pg, sg = dense_init(ks[0], d, f, dtype=dt, spec_in="embed",
                            spec_out=spec_hidden)
        pu, su = dense_init(ks[1], d, f, dtype=dt, spec_in="embed",
                            spec_out=spec_hidden)
        pd, sd = dense_init(ks[2], f, d, dtype=dt, spec_in=spec_hidden,
                            spec_out="embed",
                            scale=f ** -0.5 / (2 * cfg.n_layers) ** 0.5)
        return ({"gate": pg, "up": pu, "down": pd},
                {"gate": sg, "up": su, "down": sd})
    pu, su = dense_init(ks[0], d, f, dtype=dt, spec_in="embed",
                        spec_out=spec_hidden)
    pd, sd = dense_init(ks[1], f, d, dtype=dt, spec_in=spec_hidden,
                        spec_out="embed",
                        scale=f ** -0.5 / (2 * cfg.n_layers) ** 0.5)
    return {"up": pu, "down": pd}, {"up": su, "down": sd}


def mlp_apply(p: Params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    cd = _dtype(cfg.compute_dtype)
    cmp = cfg.kahan_matmul                         # engine-matmul routing
    if cfg.mlp == "swiglu":
        g = jax.nn.silu(dense(p["gate"], x, cd, compensated=cmp)
                        .astype(jnp.float32)).astype(cd)
        u = dense(p["up"], x, cd, compensated=cmp)
        return dense(p["down"], g * u, cd, compensated=cmp)
    h = jax.nn.gelu(dense(p["up"], x, cd, compensated=cmp)
                    .astype(jnp.float32)).astype(cd)
    return dense(p["down"], h, cd, compensated=cmp)


# ---------------------------------------------------------------------------
# Compensated activation telemetry (engine-backed)
# ---------------------------------------------------------------------------

def activation_sq_norm(x: jax.Array, *, scheme=None, mesh=None,
                       axis: str = "data",
                       interpret: Optional[bool] = None) -> jax.Array:
    """Per-request compensated squared L2 norm of an activation tensor.

    ``x``: [B, ...] (logits, hidden states). Returns [B] fp32 via the
    engine's batched (batch, steps) Pallas grid — one kernel launch for
    the whole batch, bitwise-equal to a per-request loop. This is the
    serving/training telemetry hook: drift in these norms is the cheapest
    early signal of numerical divergence between precision configs.

    ``scheme``: registered compensation-scheme name / CompensationScheme
    / Policy; None resolves the ambient ``schemes.use_policy`` default.

    With ``mesh``/``axis`` given, ``x`` is treated as batch-sharded over
    that mesh axis and each device reduces only its local requests; the
    result stays sharded like the batch (no cross-device fold is needed —
    the norm is per-request). For *scalar* cross-device reductions use
    ``repro.distributed.collectives.sharded_asum``, which all-gathers the
    (s, c) grids and applies the deterministic two-sum tree.
    """
    from repro.kernels.engine import CompensatedReduction

    eng = CompensatedReduction(scheme=scheme, interpret=interpret)
    flat = x.reshape(x.shape[0], -1).astype(jnp.float32)
    sq = flat * flat
    if mesh is not None:
        return jax.shard_map(
            eng.batched_asum, mesh=mesh, in_specs=P(axis),
            out_specs=P(axis), check_vma=False)(sq)
    return eng.batched_asum(sq)
