"""Decoder-only transformer LMs: dense, VLM-splice, and MoE variants.

The model is assembled from SEGMENTS — (kind, n_layers, scan?) descriptors —
so non-uniform stacks (deepseek-v2's leading dense layer, llama4's
dense+MoE superblocks) still lower as a small number of ``lax.scan`` bodies:
HLO size stays O(#segments), not O(#layers).

Steps exposed (shape table: train_4k -> loss/train, prefill_32k -> prefill,
decode_* -> decode_step):

    loss(params, batch)                       -> (scalar, metrics)
    init_cache(batch)                         -> cache pytree (+specs)
    prefill(params, batch, cache)             -> (last-pos logits, cache)
    decode_step(params, cache, tokens, pos)   -> (logits, cache)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import moe as moe_lib
from repro.models.common import (
    Params,
    chunked_ce_loss,
    decode_logits,
    init_embed_and_head,
    lm_head_weight,
    parallel_chunk_logits,
    prefill_chunk_scan,
    stack_init,
)
from repro.models.layers import (
    AttnStatic,
    _dtype,
    attention,
    attn_init,
    embed_lookup,
    mla_attention,
    mla_init,
    mlp_apply,
    mlp_init,
    norm_apply,
    norm_init,
)


@dataclasses.dataclass(frozen=True)
class Segment:
    name: str
    kind: str        # 'dense' | 'moe' | 'super' (dense+moe pair)
    n_layers: int    # number of scan steps (superblock counts as one)
    scan: bool = True


def plan_segments(cfg: ArchConfig) -> List[Segment]:
    if cfg.moe is None:
        return [Segment("blocks", "dense", cfg.n_layers)]
    mo = cfg.moe
    segs: List[Segment] = []
    if mo.first_k_dense:
        segs.append(Segment("dense_prefix", "dense", mo.first_k_dense,
                            scan=False))
    remaining = cfg.n_layers - mo.first_k_dense
    if mo.interleave == 1:
        segs.append(Segment("moe_blocks", "moe", remaining))
    elif mo.interleave == 2:
        assert remaining % 2 == 0
        segs.append(Segment("super_blocks", "super", remaining // 2))
    else:
        raise NotImplementedError(f"interleave={mo.interleave}")
    return segs


class TransformerLM:
    """Dense / MoE / VLM decoder-only LM."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.st = AttnStatic(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                             cfg.rope_theta, cfg.qkv_bias,
                             _dtype(cfg.compute_dtype),
                             kahan_matmul=cfg.kahan_matmul,
                             kahan_attention=cfg.kahan_attention)
        self.segments = plan_segments(cfg)
        # Parallel (multi-token) chunk prefill works where one forward
        # pass over the chunk is semantically position-independent: MLA
        # has no chunk-at-offset attention form, sliding-window layers
        # may allocate ring caches, and MoE capacity routing would let
        # bucket-padding tokens steal expert capacity from real ones
        # (chunk-width-dependent results). Those configs keep the
        # per-position scan body.
        self.parallel_prefill_ok = (cfg.mla is None and cfg.moe is None
                                    and cfg.sliding_window <= 0)

    # ------------------------------------------------------------------ init
    def _block_init(self, kind: str):
        cfg = self.cfg

        def init_one(key):
            ks = jax.random.split(key, 4)
            p: Params = {}
            s: Params = {}
            p["ln1"], s["ln1"] = norm_init(cfg.d_model, cfg.norm,
                                           _dtype(cfg.param_dtype))
            if cfg.mla is not None:
                p["attn"], s["attn"] = mla_init(ks[0], cfg)
            else:
                p["attn"], s["attn"] = attn_init(ks[0], cfg)
            p["ln2"], s["ln2"] = norm_init(cfg.d_model, cfg.norm,
                                           _dtype(cfg.param_dtype))
            if kind == "moe":
                p["ffn"], s["ffn"] = moe_lib.moe_init(ks[1], cfg)
            else:
                p["ffn"], s["ffn"] = mlp_init(ks[1], cfg)
            return p, s

        if kind == "super":
            dense_init_fn = self._block_init("dense")
            moe_init_fn = self._block_init("moe")

            def init_super(key):
                k1, k2 = jax.random.split(key)
                pa, sa = dense_init_fn(k1)
                pb, sb = moe_init_fn(k2)
                return {"a": pa, "b": pb}, {"a": sa, "b": sb}

            return init_super
        return init_one

    def init(self, key) -> Tuple[Params, Params]:
        cfg = self.cfg
        keys = jax.random.split(key, 1 + len(self.segments))
        params, specs = init_embed_and_head(keys[0], cfg)
        for i, seg in enumerate(self.segments):
            init_fn = self._block_init(seg.kind)
            if seg.scan:
                p, s = stack_init(keys[1 + i], seg.n_layers, init_fn)
            else:
                assert seg.n_layers == 1
                p, s = init_fn(keys[1 + i])
            params[seg.name] = p
            specs[seg.name] = s
        return params, specs

    # --------------------------------------------------------------- forward
    def _apply_block(self, kind: str, p: Params, x: jax.Array, *,
                     q_pos, cache=None, cache_index=None, chunk_valid=None):
        """Returns (x, new_cache, aux_loss_sum, dropped)."""
        cfg = self.cfg

        def one(kind_one, p_one, x, cache_one):
            a_in = norm_apply(p_one["ln1"], x, cfg.norm)
            if cfg.mla is not None:
                attn_out, new_cache = mla_attention(
                    p_one["attn"], cfg, a_in, q_pos=q_pos, cache=cache_one,
                    cache_index=cache_index)
            else:
                attn_out, new_cache = attention(
                    p_one["attn"], self.st, a_in, q_pos=q_pos,
                    window=cfg.sliding_window, cache=cache_one,
                    cache_index=cache_index, chunk_valid=chunk_valid)
            # named for the remat policy: saving the (small) per-layer
            # attention output lets the backward pass recompute the fp32
            # score/softmax chain ONCE instead of twice
            from jax.ad_checkpoint import checkpoint_name
            attn_out = checkpoint_name(attn_out, "attn_out")
            x = x + attn_out
            m_in = norm_apply(p_one["ln2"], x, cfg.norm)
            if kind_one == "moe":
                y, metrics = moe_lib.moe_apply(p_one["ffn"], cfg, m_in)
                return x + y, new_cache, metrics["aux_loss"], metrics["dropped_frac"]
            return x + mlp_apply(p_one["ffn"], cfg, m_in), new_cache, 0.0, 0.0

        if kind == "super":
            ca, cb = cache if cache is not None else (None, None)
            x, nca, aux_a, dr_a = one("dense", p["a"], x, ca)
            x, ncb, aux_b, dr_b = one("moe", p["b"], x, cb)
            nc = (nca, ncb) if cache is not None else None
            return x, nc, aux_a + aux_b, dr_a + dr_b
        return one(kind, p, x, cache)

    def _run_segments(self, params: Params, x: jax.Array, *, q_pos,
                      caches: Optional[Dict[str, Any]] = None,
                      cache_index=None, remat: bool = False,
                      chunk_valid=None):
        new_caches: Dict[str, Any] = {}
        aux_total = jnp.zeros((), jnp.float32)
        drop_total = jnp.zeros((), jnp.float32)
        for seg in self.segments:
            p_seg = params[seg.name]
            c_seg = caches.get(seg.name) if caches is not None else None

            def apply_one(p_l, x, c_l, _kind=seg.kind):
                return self._apply_block(_kind, p_l, x, q_pos=q_pos,
                                         cache=c_l, cache_index=cache_index,
                                         chunk_valid=chunk_valid)

            if remat:
                # plain full-recompute remat. Measured: saving
                # attn_out via save_only_these_names gives no byte-model
                # win (the bwd-proper score chain is recomputed either
                # way) while costing save memory — policy reverted.
                apply_one = jax.checkpoint(apply_one)

            if seg.scan:
                def body(carry, inp):
                    x, aux, drop = carry
                    p_l, c_l = inp
                    x, nc, a, d_ = apply_one(p_l, x, c_l)
                    return (x, aux + a, drop + d_), nc

                (x, aux_total, drop_total), nc = jax.lax.scan(
                    body, (x, aux_total, drop_total), (p_seg, c_seg))
            else:
                x, nc, a, d_ = apply_one(p_seg, x, c_seg)
                aux_total = aux_total + a
                drop_total = drop_total + d_
            if caches is not None:
                new_caches[seg.name] = nc
        return x, new_caches, aux_total, drop_total

    def _embed(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        cfg = self.cfg
        cd = _dtype(cfg.compute_dtype)
        x = embed_lookup(params["embed"], batch["tokens"], cd)
        if cfg.vision is not None and "vision_embeds" in batch:
            npch = cfg.vision.n_patches
            vis = batch["vision_embeds"].astype(cd)
            x = jnp.concatenate([vis, x[:, npch:, :]], axis=1)
        from repro.distributed.sharding import constrain
        return constrain(x, "batch", "seq", None)

    # ------------------------------------------------------------------ loss
    def loss(self, params: Params, batch: Dict[str, jax.Array],
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        x = self._embed(params, batch)
        s = x.shape[1]
        q_pos = jnp.arange(s)
        x, _, aux, drop = self._run_segments(params, x, q_pos=q_pos,
                                             remat=True)
        x = norm_apply(params["final_norm"], x, cfg.norm)
        sum_loss, cnt = chunked_ce_loss(x, lm_head_weight(params, cfg),
                                        batch["labels"], batch["loss_mask"],
                                        cfg)
        loss = sum_loss / jnp.maximum(cnt, 1.0)
        n_moe = sum(seg.n_layers for seg in self.segments
                    if seg.kind in ("moe", "super"))
        if cfg.moe is not None and n_moe:
            loss = loss + cfg.moe.router_aux_coef * aux / n_moe
        metrics = {"ce_loss": sum_loss / jnp.maximum(cnt, 1.0),
                   "aux_loss": aux, "dropped_frac": drop,
                   "tokens": cnt}
        return loss, metrics

    # ----------------------------------------------------------------- cache
    def _cache_one(self, batch_size: int, max_len: int):
        cfg = self.cfg
        cd = _dtype(cfg.compute_dtype)
        if cfg.mla is not None:
            m = cfg.mla
            c = (jnp.zeros((batch_size, max_len, m.kv_lora_rank), cd),
                 jnp.zeros((batch_size, max_len, m.qk_rope_dim), cd))
            s = (P("batch", "kv_seq", None), P("batch", "kv_seq", None))
            return c, s
        kvspec = "kv_heads" if cfg.n_kv_heads % 16 == 0 else None
        shape = (batch_size, max_len, cfg.n_kv_heads, cfg.head_dim)
        c = (jnp.zeros(shape, cd), jnp.zeros(shape, cd))
        s = (P("batch", "kv_seq", kvspec, None),) * 2
        return c, s

    def init_cache(self, batch_size: int, max_len: int,
                   ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        caches: Dict[str, Any] = {}
        specs: Dict[str, Any] = {}

        def stack(c, s, n):
            cs = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n, *a.shape)), c)
            ss = jax.tree.map(lambda sp: P(None, *sp), s,
                              is_leaf=lambda sp: isinstance(sp, P))
            return cs, ss

        for seg in self.segments:
            c, s = self._cache_one(batch_size, max_len)
            if seg.kind == "super":
                c, s = (c, c), (s, s)
            if seg.scan:
                c, s = stack(c, s, seg.n_layers)
            caches[seg.name] = c
            specs[seg.name] = s
        return caches, specs

    # --------------------------------------------------------------- serving
    def prefill(self, params: Params, batch: Dict[str, jax.Array],
                caches: Dict[str, Any],
                ) -> Tuple[jax.Array, Dict[str, Any]]:
        cfg = self.cfg
        x = self._embed(params, batch)
        s = x.shape[1]
        q_pos = jnp.arange(s)
        x, new_caches, _, _ = self._run_segments(params, x, q_pos=q_pos,
                                                 caches=caches)
        x = norm_apply(params["final_norm"], x, cfg.norm)
        logits = decode_logits(x[:, -1:, :], params, cfg)
        return logits, new_caches

    def _decode_x(self, params: Params, caches: Dict[str, Any],
                  x: jax.Array, pos: jax.Array,
                  ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Single-position decode from an already-embedded [B,1,D] input
        (shared by ``decode_step`` and the chunked-prefill body, which
        embeds per position so it can splice vision embeddings)."""
        cfg = self.cfg
        q_pos = pos[None]
        x, new_caches, _, _ = self._run_segments(
            params, x, q_pos=q_pos, caches=caches, cache_index=pos)
        x = norm_apply(params["final_norm"], x, cfg.norm)
        logits = decode_logits(x, params, cfg)
        return logits, new_caches

    def decode_step(self, params: Params, caches: Dict[str, Any],
                    tokens: jax.Array, pos: jax.Array,
                    ) -> Tuple[jax.Array, Dict[str, Any]]:
        cfg = self.cfg
        cd = _dtype(cfg.compute_dtype)
        x = embed_lookup(params["embed"], tokens[:, None], cd)
        return self._decode_x(params, caches, x, pos)

    def prefill_chunk(self, params: Params, batch: Dict[str, jax.Array],
                      cache: Dict[str, Any], offset: jax.Array,
                      nvalid: jax.Array) -> Tuple[jax.Array, Dict[str, Any]]:
        """Resume-from-offset prefill (the serving engine's chunking
        hook): advance a batch-1 cache by ``batch["tokens"]`` at
        positions ``offset + i``. VLM prompts splice
        ``batch["vision_embeds"]`` at positions < n_patches, mirroring
        ``_embed``'s whole-prompt splice per position."""
        cfg = self.cfg
        cd = _dtype(cfg.compute_dtype)
        vis = None
        if cfg.vision is not None and "vision_embeds" in batch:
            vis = batch["vision_embeds"].astype(cd)       # [1, n_patches, D]

        def step(cache, tok, pos):
            x = embed_lookup(params["embed"], tok[None, None], cd)  # [1,1,D]
            if vis is not None:
                npch = cfg.vision.n_patches
                v = jax.lax.dynamic_slice_in_dim(
                    vis, jnp.clip(pos, 0, npch - 1), 1, axis=1)
                x = jnp.where(pos < npch, v, x)
            return self._decode_x(params, cache, x, pos)

        return prefill_chunk_scan(step, batch["tokens"], cache, offset,
                                  nvalid, cfg.padded_vocab)

    def prefill_chunk_parallel(self, params: Params,
                               batch: Dict[str, jax.Array],
                               cache: Dict[str, Any], offset: jax.Array,
                               nvalid: jax.Array,
                               ) -> Tuple[jax.Array, Dict[str, Any]]:
        """Multi-token chunk prefill: ONE forward pass over the whole
        chunk (same ``(logits, cache)`` contract as ``prefill_chunk``,
        which remains the per-position oracle).

        The chunk's tokens live at absolute positions ``offset + i``;
        attention writes the chunk's K/V into the cache at the traced
        offset and attends the FULL cache through the engine's chunk
        flash kernel (``layers.attention`` chunk-prefill mode) — one MXU
        pass instead of ``w`` sequential decode-speed steps. VLM prompts
        splice ``batch["vision_embeds"]`` at the same traced positions
        as the scan body (exact gather + select). Bucket-padding
        positions past ``nvalid`` run but their cache writes are
        discarded by the exact positional select and the returned logits
        come from the last VALID position. Configs the parallel body
        cannot serve (``parallel_prefill_ok`` False: MLA, MoE, sliding
        window) delegate to the per-position scan.
        """
        cfg = self.cfg
        if not self.parallel_prefill_ok:
            return self.prefill_chunk(params, batch, cache, offset, nvalid)
        cd = _dtype(cfg.compute_dtype)
        tokens = batch["tokens"]                      # [1, w]
        w = tokens.shape[-1]
        pos = offset + jnp.arange(w)
        x = embed_lookup(params["embed"], tokens, cd)  # [1, w, D]
        if cfg.vision is not None and "vision_embeds" in batch:
            npch = cfg.vision.n_patches
            vis = batch["vision_embeds"].astype(cd)   # [1, n_patches, D]
            v = jnp.take(vis, jnp.clip(pos, 0, npch - 1), axis=1)
            x = jnp.where((pos < npch)[None, :, None], v, x)
        x, new_caches, _, _ = self._run_segments(
            params, x, q_pos=pos, caches=cache, cache_index=offset,
            chunk_valid=nvalid)
        x = norm_apply(params["final_norm"], x, cfg.norm)
        return parallel_chunk_logits(x, params, cfg, nvalid), new_caches
