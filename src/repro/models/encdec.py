"""Whisper-style encoder-decoder backbone (audio frontend stubbed).

``input_specs`` provides precomputed frame embeddings [B, F, D] (the output
of the conv frontend). The encoder is a full-attention non-causal stack;
the decoder interleaves causal self-attention (KV-cached for serving) and
cross-attention to the encoder memory (cross-K/V cached at prefill). RoPE
stands in for learned absolute positions — immaterial for the backbone
shapes.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.common import (
    Params,
    chunked_ce_loss,
    decode_logits,
    decode_prefill_chunk,
    init_embed_and_head,
    lm_head_weight,
    parallel_chunk_logits,
    stack_init,
)
from repro.models.layers import (
    AttnStatic,
    _dtype,
    attention,
    attn_init,
    dense,
    embed_lookup,
    mlp_apply,
    mlp_init,
    norm_apply,
    norm_init,
)


class EncDecLM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.st = AttnStatic(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                             cfg.rope_theta, cfg.qkv_bias,
                             _dtype(cfg.compute_dtype),
                             kahan_matmul=cfg.kahan_matmul,
                             kahan_attention=cfg.kahan_attention)
        # The decoder is plain GQA self-attention + cached cross-attention
        # — both take multi-token chunks, so the parallel prefill body
        # always applies (``prefill_begin`` still runs once, inside the
        # first chunk program).
        self.parallel_prefill_ok = True

    # ------------------------------------------------------------------ init
    def _enc_block_init(self):
        cfg = self.cfg
        dt = _dtype(cfg.param_dtype)

        def init_one(key):
            ks = jax.random.split(key, 2)
            p, s = {}, {}
            p["ln1"], s["ln1"] = norm_init(cfg.d_model, cfg.norm, dt)
            p["attn"], s["attn"] = attn_init(ks[0], cfg)
            p["ln2"], s["ln2"] = norm_init(cfg.d_model, cfg.norm, dt)
            p["mlp"], s["mlp"] = mlp_init(ks[1], cfg)
            return p, s

        return init_one

    def _dec_block_init(self):
        cfg = self.cfg
        dt = _dtype(cfg.param_dtype)

        def init_one(key):
            ks = jax.random.split(key, 3)
            p, s = {}, {}
            p["ln1"], s["ln1"] = norm_init(cfg.d_model, cfg.norm, dt)
            p["attn"], s["attn"] = attn_init(ks[0], cfg)
            p["ln_x"], s["ln_x"] = norm_init(cfg.d_model, cfg.norm, dt)
            p["xattn"], s["xattn"] = attn_init(ks[1], cfg)
            p["ln2"], s["ln2"] = norm_init(cfg.d_model, cfg.norm, dt)
            p["mlp"], s["mlp"] = mlp_init(ks[2], cfg)
            return p, s

        return init_one

    def init(self, key) -> Tuple[Params, Params]:
        cfg = self.cfg
        k0, k1, k2 = jax.random.split(key, 3)
        params, specs = init_embed_and_head(k0, cfg)
        params["encoder"], specs["encoder"] = stack_init(
            k1, cfg.encoder.n_layers, self._enc_block_init())
        params["decoder"], specs["decoder"] = stack_init(
            k2, cfg.n_layers, self._dec_block_init())
        return params, specs

    # --------------------------------------------------------------- encoder
    def encode(self, params: Params, frames: jax.Array) -> jax.Array:
        cfg = self.cfg
        cd = _dtype(cfg.compute_dtype)
        x = frames.astype(cd)
        f_pos = jnp.arange(x.shape[1])

        def body(x, p_l):
            a_in = norm_apply(p_l["ln1"], x, cfg.norm)
            a, _ = attention(p_l["attn"], self.st, a_in, q_pos=f_pos,
                             causal=False)
            x = x + a
            m_in = norm_apply(p_l["ln2"], x, cfg.norm)
            return x + mlp_apply(p_l["mlp"], cfg, m_in), None

        x, _ = jax.lax.scan(body, x, params["encoder"])
        return x

    # --------------------------------------------------------------- decoder
    def _dec_run(self, params, x, enc_out, *, q_pos, caches=None,
                 cache_index=None, remat=False, chunk_valid=None):
        cfg = self.cfg
        cd = _dtype(cfg.compute_dtype)
        f_pos = None if enc_out is None else jnp.arange(enc_out.shape[1])

        def apply_one(p_l, x, c_l):
            kv_c = c_l["kv"] if c_l is not None else None
            a_in = norm_apply(p_l["ln1"], x, cfg.norm)
            a, new_kv = attention(p_l["attn"], self.st, a_in, q_pos=q_pos,
                                  cache=kv_c, cache_index=cache_index,
                                  chunk_valid=chunk_valid)
            x = x + a
            xa_in = norm_apply(p_l["ln_x"], x, cfg.norm)
            if c_l is not None and "xk" in c_l:      # serving: cached cross
                xk, xv = c_l["xk"], c_l["xv"]
            else:                                     # training: from enc_out
                xk = dense(p_l["xattn"]["k"], enc_out, cd)
                xv = dense(p_l["xattn"]["v"], enc_out, cd)
            xa, _ = attention(p_l["xattn"], self.st, xa_in, q_pos=q_pos,
                              cross_kv=(xk, xv))
            x = x + xa
            m_in = norm_apply(p_l["ln2"], x, cfg.norm)
            x = x + mlp_apply(p_l["mlp"], cfg, m_in)
            new_c = None
            if c_l is not None:
                new_c = dict(c_l)
                new_c["kv"] = new_kv
                if enc_out is not None and "xk" in c_l:
                    pass  # cross cache already filled
            return x, new_c

        if remat:
            apply_one = jax.checkpoint(apply_one)

        def body(x, inp):
            p_l, c_l = inp
            x, nc = apply_one(p_l, x, c_l)
            return x, nc

        x, new_caches = jax.lax.scan(body, x, (params["decoder"], caches))
        return x, new_caches

    # ----------------------------------------------------------------- steps
    def loss(self, params, batch):
        cfg = self.cfg
        cd = _dtype(cfg.compute_dtype)
        from repro.distributed.sharding import constrain
        enc_out = self.encode(params, batch["frames"])
        x = embed_lookup(params["embed"], batch["tokens"], cd)
        x = constrain(x, "batch", "seq", None)
        q_pos = jnp.arange(x.shape[1])
        x, _ = self._dec_run(params, x, enc_out, q_pos=q_pos, remat=True)
        x = norm_apply(params["final_norm"], x, cfg.norm)
        sum_loss, cnt = chunked_ce_loss(x, lm_head_weight(params, cfg),
                                        batch["labels"], batch["loss_mask"],
                                        cfg)
        loss = sum_loss / jnp.maximum(cnt, 1.0)
        return loss, {"ce_loss": loss, "tokens": cnt}

    def init_cache(self, batch_size: int, max_len: int):
        cfg = self.cfg
        cd = _dtype(cfg.compute_dtype)
        kvspec = "kv_heads" if cfg.n_kv_heads % 16 == 0 else None
        l = cfg.n_layers
        f = cfg.encoder.n_frames
        kv_shape = (l, batch_size, max_len, cfg.n_kv_heads, cfg.head_dim)
        x_shape = (l, batch_size, f, cfg.n_kv_heads, cfg.head_dim)
        caches = {
            "kv": (jnp.zeros(kv_shape, cd), jnp.zeros(kv_shape, cd)),
            "xk": jnp.zeros(x_shape, cd),
            "xv": jnp.zeros(x_shape, cd),
        }
        specs = {
            "kv": (P(None, "batch", "kv_seq", kvspec, None),) * 2,
            "xk": P(None, "batch", None, kvspec, None),
            "xv": P(None, "batch", None, kvspec, None),
        }
        return caches, specs

    def prefill(self, params, batch, caches):
        cfg = self.cfg
        cd = _dtype(cfg.compute_dtype)
        # ONE encode + cross-K/V fill, shared verbatim with the chunked
        # path; the decoder then reads the cached memory (enc_out=None),
        # exactly as decode_step does
        caches = self.prefill_begin(params, batch, caches)

        x = embed_lookup(params["embed"], batch["tokens"], cd)
        q_pos = jnp.arange(x.shape[1])
        x, new_caches = self._dec_run(params, x, None, q_pos=q_pos,
                                      caches=caches)
        x = norm_apply(params["final_norm"], x, cfg.norm)
        return decode_logits(x[:, -1:, :], params, cfg), new_caches

    def decode_step(self, params, caches, tokens, pos):
        cfg = self.cfg
        cd = _dtype(cfg.compute_dtype)
        x = embed_lookup(params["embed"], tokens[:, None], cd)
        x, new_caches = self._dec_run(params, x, None, q_pos=pos[None],
                                      caches=caches, cache_index=pos)
        x = norm_apply(params["final_norm"], x, cfg.norm)
        return decode_logits(x, params, cfg), new_caches

    def prefill_begin(self, params, batch, caches):
        """One-time prefill setup (the serving engine runs it inside the
        FIRST chunk program only): encode the frames and fill the
        per-layer cross-attention K/V caches, so later chunks and decode
        steps read the cached memory instead of re-encoding."""
        cd = _dtype(self.cfg.compute_dtype)
        enc_out = self.encode(params, batch["frames"])

        def fill(_, p_l):
            xk = dense(p_l["xattn"]["k"], enc_out, cd)
            xv = dense(p_l["xattn"]["v"], enc_out, cd)
            return None, (xk, xv)

        _, (xks, xvs) = jax.lax.scan(fill, None, params["decoder"])
        caches = dict(caches)
        caches["xk"], caches["xv"] = xks, xvs
        return caches

    def prefill_chunk(self, params, batch, cache, offset, nvalid):
        """Resume-from-offset prefill over the decoder; cross-attention
        reads the ``prefill_begin``-cached K/V (the per-position body is
        ``decode_step``)."""
        return decode_prefill_chunk(self, params, batch, cache, offset,
                                    nvalid)

    def prefill_chunk_parallel(self, params, batch, cache, offset, nvalid):
        """Multi-token chunk prefill over the decoder: ONE forward pass
        per chunk (self-attention through the engine chunk flash kernel
        at the traced offset; cross-attention reads the
        ``prefill_begin``-cached K/V, which already serves any query
        width). Same contract as ``prefill_chunk`` — the per-position
        scan stays the oracle."""
        cfg = self.cfg
        cd = _dtype(cfg.compute_dtype)
        tokens = batch["tokens"]                      # [1, w]
        pos = offset + jnp.arange(tokens.shape[-1])
        x = embed_lookup(params["embed"], tokens, cd)
        x, new_caches = self._dec_run(params, x, None, q_pos=pos,
                                      caches=cache, cache_index=offset,
                                      chunk_valid=nvalid)
        x = norm_apply(params["final_norm"], x, cfg.norm)
        return parallel_chunk_logits(x, params, cfg, nvalid), new_caches
