"""xLSTM blocks (Beck et al., arXiv:2405.04517): mLSTM + sLSTM.

mLSTM (matrix memory, parallelizable): trained with the standard
*chunkwise-parallel* form — intra-chunk attention-like term with
exponential-gate decays + inter-chunk recurrent state (C, n, m), all
stabilized in log space; decode uses the O(1) recurrent step. Chunk length
= cfg.xlstm.chunk.

sLSTM (scalar memory, exponential gating, recurrent R matrices): inherently
sequential over time (the R h_{t-1} term defeats parallelization — the
xLSTM paper says as much), implemented as a lax.scan over steps with
max-stabilized exponential gates.

Block layout follows the paper's residual stack: one sLSTM block per
``slstm_every`` blocks (7:1 for the 1.3B config), the rest mLSTM. The
model-level scan iterates groups of ``slstm_every`` blocks (params stacked
[G, ...]) — one group = 7 stacked mLSTM (inner scan) + 1 sLSTM.

Compensated-accumulation touchpoint (the paper-technique tie-in): chunk
boundary folds of (C, n) use plain adds in fp32 — the compensated variant
is exercised at the loss/optimizer level, not inside the recurrences (the
stabilized exponentials dominate the error budget here).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import _dtype, _init_normal, norm_apply

Params = Dict[str, Any]

MLSTM_CACHE = Tuple[jax.Array, jax.Array, jax.Array, jax.Array]
# (C [B,H,dqk,dv], n [B,H,dqk], m [B,H], conv_buf [B,k-1,dI])
SLSTM_CACHE = Tuple[jax.Array, jax.Array, jax.Array, jax.Array]
# (c, n, m, h) each [B, d] fp32 (m,c,n per hidden unit)


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def mlstm_init(key, cfg: ArchConfig) -> Tuple[Params, Params]:
    xl = cfg.xlstm
    d = cfg.d_model
    d_in = int(xl.mlstm_proj_factor * d)
    d_qk = int(xl.mlstm_qk_factor * d_in)
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    p = {
        "norm": {"scale": jnp.ones((d,), dt)},
        "up_u": {"w": _init_normal(ks[0], (d, d_in), d ** -0.5, dt)},
        "up_z": {"w": _init_normal(ks[1], (d, d_in), d ** -0.5, dt)},
        "conv_w": _init_normal(ks[2], (xl.conv_kernel, d_in), 0.5, dt),
        "conv_b": jnp.zeros((d_in,), dt),
        "wq": {"w": _init_normal(ks[3], (d_in, d_qk), d_in ** -0.5, dt)},
        "wk": {"w": _init_normal(ks[4], (d_in, d_qk), d_in ** -0.5, dt)},
        "wv": {"w": _init_normal(ks[5], (d_in, d_in), d_in ** -0.5, dt)},
        "w_if": {"w": _init_normal(ks[6], (d_in, 2 * cfg.n_heads),
                                   d_in ** -0.5, jnp.float32),
                 "b": jnp.concatenate([
                     jnp.zeros((cfg.n_heads,), jnp.float32),          # i
                     jnp.linspace(3.0, 6.0, cfg.n_heads)])},          # f
        "out_norm": {"scale": jnp.ones((d_in,), dt)},
        "down": {"w": _init_normal(ks[7], (d_in, d),
                                   d_in ** -0.5 / (2 * cfg.n_layers) ** 0.5,
                                   dt)},
    }
    s = {
        "norm": {"scale": P(None)},
        "up_u": {"w": P("embed", "xl_inner")},
        "up_z": {"w": P("embed", "xl_inner")},
        "conv_w": P(None, "xl_inner"),
        "conv_b": P("xl_inner"),
        "wq": {"w": P("xl_inner", None)},
        "wk": {"w": P("xl_inner", None)},
        "wv": {"w": P("xl_inner", "xl_inner")},
        "w_if": {"w": P("xl_inner", None), "b": P(None)},
        "out_norm": {"scale": P("xl_inner")},
        "down": {"w": P("xl_inner", "embed")},
    }
    return p, s


def _conv_causal(x, w, b):
    k = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + pad[:, i:i + x.shape[1], :] * w[i]
    return out + b


def _mlstm_chunk(state, inp):
    """Chunkwise-parallel mLSTM step (all fp32).

    state: (C [B,H,K,V], n [B,H,K], m [B,H])
    inp: q,k,v: [B,H,L,*]; i_raw,f_raw: [B,H,L]
    """
    c_in, n_in, m_in = state
    q, k, v, i_raw, f_raw = inp
    scale = q.shape[-1] ** -0.5
    lf = jax.nn.log_sigmoid(f_raw)                    # [B,H,L]
    b_cum = jnp.cumsum(lf, axis=-1)                   # [B,H,L]  # contract: allow-no-uncompensated-reduction(log-domain forget-gate prefix; chunk-length fp32 terms defining the decay, not a sum estimate)
    total_g = b_cum[..., -1:]

    # intra-chunk decay matrix logD[j,t] = i[t] + b[j] - b[t], t <= j
    logd = (i_raw[:, :, None, :] + b_cum[:, :, :, None]
            - b_cum[:, :, None, :])
    l = q.shape[2]
    tri = jnp.tril(jnp.ones((l, l), bool))
    logd = jnp.where(tri, logd, -jnp.inf)
    m_intra = jnp.max(logd, axis=-1)                  # [B,H,L]
    m_inter = m_in[..., None] + b_cum                 # [B,H,L]
    m_new = jnp.maximum(m_intra, m_inter)
    m_new = jnp.maximum(m_new, -1e30)                 # all -inf guard

    d_mat = jnp.exp(logd - m_new[..., None])          # [B,H,L,L]
    s_mat = jnp.einsum("bhld,bhtd->bhlt", q, k) * scale * d_mat  # contract: allow-no-uncompensated-reduction(mLSTM intra-chunk scores; fp32 over head_dim terms)
    h_intra = jnp.einsum("bhlt,bhtv->bhlv", s_mat, v)  # contract: allow-no-uncompensated-reduction(mLSTM intra-chunk mix; fp32, chunk-bounded terms)
    inter_scale = jnp.exp(m_inter - m_new)            # [B,H,L]
    # contract: allow-no-uncompensated-reduction(mLSTM state readout; fp32 over head_dim terms)
    h_inter = jnp.einsum("bhld,bhdv->bhlv", q, c_in) * scale \
        * inter_scale[..., None]
    num = h_intra + h_inter

    # contract: allow-no-uncompensated-reduction(mLSTM normalizer; fp32, chunk-bounded terms)
    n_intra = jnp.sum(s_mat, axis=-1)                 # [B,H,L]
    n_inter = jnp.einsum("bhld,bhd->bhl", q, n_in) * scale * inter_scale  # contract: allow-no-uncompensated-reduction(mLSTM normalizer readout; fp32 over head_dim terms)
    denom = jnp.maximum(jnp.abs(n_intra + n_inter), jnp.exp(-m_new))
    h = num / denom[..., None]                        # [B,H,L,V]

    # state carry-out
    m_out = jnp.maximum(m_in + total_g[..., 0],
                        jnp.max(i_raw + total_g - b_cum, axis=-1))
    w_t = jnp.exp(i_raw + total_g - b_cum - m_out[..., None])   # [B,H,L]
    c_out = (jnp.exp(m_in + total_g[..., 0] - m_out)[..., None, None] * c_in
             + jnp.einsum("bhl,bhld,bhlv->bhdv", w_t, k, v))  # contract: allow-no-uncompensated-reduction(mLSTM state update; fp32, chunk-bounded terms)
    n_out = (jnp.exp(m_in + total_g[..., 0] - m_out)[..., None] * n_in
             + jnp.einsum("bhl,bhld->bhd", w_t, k))  # contract: allow-no-uncompensated-reduction(mLSTM normalizer update; fp32, chunk-bounded terms)
    return (c_out, n_out, m_out), h


def mlstm_apply(p: Params, cfg: ArchConfig, x: jax.Array, *,
                cache: Optional[MLSTM_CACHE] = None,
                ) -> Tuple[jax.Array, Optional[MLSTM_CACHE]]:
    """One mLSTM block (pre-norm, residual added by caller)."""
    xl = cfg.xlstm
    cd = _dtype(cfg.compute_dtype)
    b, s, d = x.shape
    h_heads = cfg.n_heads
    d_in = int(xl.mlstm_proj_factor * d)
    d_qk = int(xl.mlstm_qk_factor * d_in)
    kq = d_qk // h_heads
    kv = d_in // h_heads

    xn = norm_apply(p["norm"], x, "rmsnorm").astype(cd)
    u = jnp.einsum("bsd,di->bsi", xn, p["up_u"]["w"].astype(cd))  # contract: allow-no-uncompensated-reduction(mLSTM up-projection; cd accumulate, d_model terms)
    z = jnp.einsum("bsd,di->bsi", xn, p["up_z"]["w"].astype(cd))  # contract: allow-no-uncompensated-reduction(mLSTM up-projection; cd accumulate, d_model terms)

    decode = cache is not None and s == 1
    if decode:
        c_st, n_st, m_st, conv_buf = cache
        win = jnp.concatenate([conv_buf, u], axis=1)
        cu = jnp.einsum("bki,ki->bi", win.astype(jnp.float32),  # contract: allow-no-uncompensated-reduction(depthwise conv window; fp32, kernel-width terms)
                        p["conv_w"].astype(jnp.float32)) \
            + p["conv_b"].astype(jnp.float32)
        cu = jax.nn.silu(cu)[:, None, :].astype(cd)
        new_conv_buf = win[:, 1:]
    else:
        cu = jax.nn.silu(_conv_causal(u, p["conv_w"].astype(cd),
                                      p["conv_b"].astype(cd))
                         .astype(jnp.float32)).astype(cd)

    q = jnp.einsum("bsi,ik->bsk", cu, p["wq"]["w"].astype(cd))  # contract: allow-no-uncompensated-reduction(QKV projection; cd accumulate, d_in terms)
    k = jnp.einsum("bsi,ik->bsk", cu, p["wk"]["w"].astype(cd))  # contract: allow-no-uncompensated-reduction(QKV projection; cd accumulate, d_in terms)
    v = jnp.einsum("bsi,ik->bsk", u, p["wv"]["w"].astype(cd))  # contract: allow-no-uncompensated-reduction(QKV projection; cd accumulate, d_in terms)
    gates = jnp.einsum("bsi,ig->bsg", cu.astype(jnp.float32),  # contract: allow-no-uncompensated-reduction(gate pre-activations; fp32 over d_in terms)
                       p["w_if"]["w"]) + p["w_if"]["b"]
    i_raw = gates[..., :h_heads].transpose(0, 2, 1)   # [B,H,S]
    f_raw = gates[..., h_heads:].transpose(0, 2, 1)

    def heads(t, dh):
        return t.reshape(b, s, h_heads, dh).transpose(0, 2, 1, 3).astype(jnp.float32)

    qh, kh, vh = heads(q, kq), heads(k, kq), heads(v, kv)

    if decode:
        state = (c_st, n_st, m_st)
        (c_st, n_st, m_st), hh = _mlstm_chunk(
            state, (qh, kh, vh, i_raw, f_raw))
        new_cache = (c_st, n_st, m_st, new_conv_buf)
    else:
        chunk = min(xl.chunk, s)
        pad = (-s) % chunk
        if pad:
            qh = jnp.pad(qh, ((0, 0), (0, 0), (0, pad), (0, 0)))
            kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0)))
            vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0)))
            i_raw = jnp.pad(i_raw, ((0, 0), (0, 0), (0, pad)),
                            constant_values=-1e30)
            f_raw = jnp.pad(f_raw, ((0, 0), (0, 0), (0, pad)),
                            constant_values=30.0)
        nch = qh.shape[2] // chunk

        def split(t):
            return t.reshape(*t.shape[:2], nch, chunk,
                             *t.shape[3:]).transpose(2, 0, 1, 3,
                                                     *range(4, t.ndim + 1))

        init = (jnp.zeros((b, h_heads, kq, kv), jnp.float32),
                jnp.zeros((b, h_heads, kq), jnp.float32),
                jnp.full((b, h_heads), -1e30, jnp.float32))
        if cache is not None:
            init = (cache[0], cache[1], cache[2])
        (c_st, n_st, m_st), hs = jax.lax.scan(
            _mlstm_chunk, init,
            (split(qh), split(kh), split(vh), split(i_raw), split(f_raw)))
        hh = hs.transpose(1, 2, 0, 3, 4).reshape(b, h_heads, nch * chunk, kv)
        hh = hh[:, :, :s]
        new_cache = None
        if cache is not None:
            kk = xl.conv_kernel
            new_cache = (c_st, n_st, m_st, u[:, -(kk - 1):, :])

    h_flat = hh.transpose(0, 2, 1, 3).reshape(b, s, d_in).astype(cd)
    h_flat = norm_apply(p["out_norm"], h_flat, "rmsnorm")
    h_gated = h_flat * jax.nn.silu(z.astype(jnp.float32)).astype(cd)
    out = jnp.einsum("bsi,id->bsd", h_gated, p["down"]["w"].astype(cd))  # contract: allow-no-uncompensated-reduction(mLSTM down-projection; cd accumulate, d_in terms)
    return out, new_cache


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def slstm_init(key, cfg: ArchConfig) -> Tuple[Params, Params]:
    xl = cfg.xlstm
    d = cfg.d_model
    h = cfg.n_heads
    dh = d // h
    f = int(xl.slstm_proj_factor * d)
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p = {
        "norm": {"scale": jnp.ones((d,), dt)},
        "w": {"w": _init_normal(ks[0], (d, 4 * d), d ** -0.5, dt),
              "b": jnp.concatenate([
                  jnp.zeros((d,), jnp.float32),                 # z
                  jnp.zeros((d,), jnp.float32),                 # i
                  jnp.broadcast_to(jnp.linspace(3.0, 6.0, h)[:, None],
                                   (h, dh)).reshape(d),         # f
                  jnp.zeros((d,), jnp.float32)]).astype(jnp.float32)},  # o
        # block-diagonal recurrent matrices, one per head
        "r": _init_normal(ks[1], (h, dh, 4 * dh), dh ** -0.5, jnp.float32),
        "up_g": {"w": _init_normal(ks[2], (d, f), d ** -0.5, dt)},
        "up_u": {"w": _init_normal(ks[3], (d, f), d ** -0.5, dt)},
        "down": {"w": _init_normal(ks[4], (f, d),
                                   f ** -0.5 / (2 * cfg.n_layers) ** 0.5, dt)},
    }
    s = {
        "norm": {"scale": P(None)},
        "w": {"w": P("embed", None), "b": P(None)},
        "r": P(None, None, None),
        "up_g": {"w": P("embed", "mlp")},
        "up_u": {"w": P("embed", "mlp")},
        "down": {"w": P("mlp", "embed")},
    }
    return p, s


def _slstm_step(p, cfg, carry, wx_t):
    """One sLSTM time step. carry: (c, n, m, h) [B,d] fp32; wx_t: [B,4d]."""
    c, n, m, h = carry
    b = h.shape[0]
    heads = cfg.n_heads
    dh = h.shape[1] // heads
    rh = jnp.einsum("bhd,hdg->bhg", h.reshape(b, heads, dh), p["r"])  # contract: allow-no-uncompensated-reduction(sLSTM recurrent product; fp32 over head_dim terms)
    rh = rh.reshape(b, heads, 4, dh).transpose(0, 2, 1, 3).reshape(b, 4 * heads * dh)
    # gate layout after transpose: [z | i | f | o] each [B,d]
    pre = wx_t + rh
    d = h.shape[1]
    z_t = jnp.tanh(pre[:, :d])
    i_t = pre[:, d:2 * d]
    f_t = jax.nn.log_sigmoid(pre[:, 2 * d:3 * d])
    o_t = jax.nn.sigmoid(pre[:, 3 * d:])
    m_new = jnp.maximum(f_t + m, i_t)
    decay = jnp.exp(f_t + m - m_new)
    inject = jnp.exp(i_t - m_new)
    c_new = decay * c + inject * z_t
    n_new = decay * n + inject
    h_new = o_t * c_new / jnp.maximum(n_new, 1e-6)
    return (c_new, n_new, m_new, h_new), h_new


def slstm_apply(p: Params, cfg: ArchConfig, x: jax.Array, *,
                cache: Optional[SLSTM_CACHE] = None,
                ) -> Tuple[jax.Array, Optional[SLSTM_CACHE]]:
    """One sLSTM block (pre-norm + recurrence + gated FFN)."""
    cd = _dtype(cfg.compute_dtype)
    b, s, d = x.shape
    xn = norm_apply(p["norm"], x, "rmsnorm").astype(cd)
    wx = jnp.einsum("bsd,dg->bsg", xn, p["w"]["w"].astype(cd))  # contract: allow-no-uncompensated-reduction(sLSTM input projection; cd accumulate, d_model terms)
    wx = wx.astype(jnp.float32) + p["w"]["b"]
    # reorder [z|i|f|o] interleaved per head for the recurrent add: keep
    # canonical [z|i|f|o] over full d — r-product is transposed to match.

    if cache is None:
        init = (jnp.zeros((b, d), jnp.float32), jnp.zeros((b, d), jnp.float32),
                jnp.full((b, d), -1e30, jnp.float32),
                jnp.zeros((b, d), jnp.float32))
    else:
        init = cache

    def step(carry, wx_t):
        return _slstm_step(p, cfg, carry, wx_t)

    carry, hs = jax.lax.scan(step, init, wx.swapaxes(0, 1))
    h_seq = hs.swapaxes(0, 1).astype(cd)                     # [B,S,d]
    new_cache = carry if cache is not None else None

    # gated FFN (proj factor 4/3)
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", h_seq,  # contract: allow-no-uncompensated-reduction(gated FFN up-projection; cd accumulate, d_model terms)
                               p["up_g"]["w"].astype(cd))
                    .astype(jnp.float32)).astype(cd)
    u = jnp.einsum("bsd,df->bsf", h_seq, p["up_u"]["w"].astype(cd))  # contract: allow-no-uncompensated-reduction(gated FFN up-projection; cd accumulate, d_model terms)
    out = jnp.einsum("bsf,fd->bsd", g * u, p["down"]["w"].astype(cd))  # contract: allow-no-uncompensated-reduction(gated FFN down-projection; cd accumulate, d_ff terms)
    return out, new_cache
