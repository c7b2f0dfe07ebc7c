"""Mixture-of-Experts layer with GROUP-LOCAL sort-based dispatch.

Scalability notes:

* The classic one-hot dispatch einsum materializes [T, E, C] — at llama4
  scale (T=1M, E=128, C≈10k) that is O(10^12) elements. Unusable.
* A single GLOBAL sort-based dispatch keeps shapes linear but its
  data-dependent gathers/scatters defeat GSPMD sharding inference — the
  10 GB permuted-token tensors get replicated per device (observed in the
  first dry-run: 1.9 TiB/device of temps).
* Fix: HIERARCHICAL (group-local) dispatch, the MaxText pattern. Tokens are
  reshaped to [G, T/G, D] with G sharded over the data axes; each group
  routes/sorts/scatters LOCALLY (batched ops — no cross-group traffic);
  the expert einsum contracts the [G, E, C, D] buffer (G -> data,
  E -> model) against expert weights (E -> model), and the single
  cross-device movement is the all-to-all GSPMD inserts to reshard between
  the token and expert layouts. All shapes static; overflow beyond the
  per-group capacity is deterministically DROPPED (capacity_factor).

Router statistics and the load-balance auxiliary loss accumulate in fp32.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain
from repro.models.layers import _dtype, _init_normal, dense_init, mlp_init

Params = Dict[str, Any]


def moe_init(key, cfg: ArchConfig) -> Tuple[Params, Params]:
    mo = cfg.moe
    d = cfg.d_model
    f = mo.d_ff_expert
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)

    p_router, s_router = dense_init(ks[0], d, mo.n_experts, dtype=jnp.float32,
                                    spec_in="embed", spec_out=None)
    scale_in = d ** -0.5
    scale_out = f ** -0.5 / (2 * cfg.n_layers) ** 0.5
    p = {
        "router": p_router,
        "gate": _init_normal(ks[1], (mo.n_experts, d, f), scale_in, dt),
        "up": _init_normal(ks[2], (mo.n_experts, d, f), scale_in, dt),
        "down": _init_normal(ks[3], (mo.n_experts, f, d), scale_out, dt),
    }
    s = {
        "router": s_router,
        "gate": P("expert", "embed", "mlp"),
        "up": P("expert", "embed", "mlp"),
        "down": P("expert", "mlp", "embed"),
    }
    if mo.n_shared:
        ps, ss = mlp_init(ks[4], cfg, d_ff=mo.n_shared * (mo.d_ff_shared or f))
        p["shared"] = ps
        s["shared"] = ss
    return p, s


def _positions_in_segment(sorted_ids: jax.Array) -> jax.Array:
    """Rank within contiguous equal-id runs; batched over leading dims."""
    n = sorted_ids.shape[-1]
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), sorted_ids.shape)
    is_start = jnp.concatenate(
        [jnp.ones((*sorted_ids.shape[:-1], 1), bool),
         sorted_ids[..., 1:] != sorted_ids[..., :-1]], axis=-1)
    seg_start = jnp.where(is_start, idx, 0)
    seg_start = jax.lax.associative_scan(jnp.maximum, seg_start, axis=-1)
    return idx - seg_start


def _n_groups(cfg: ArchConfig, tokens: int, batch: int) -> int:
    """Groups = min(32, batch) constrained to divide both (static)."""
    g = 32
    while g > 1 and (batch % g or tokens % g):
        g //= 2
    return max(g, 1)


def moe_apply(p: Params, cfg: ArchConfig, x: jax.Array,
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: [B, S, D] -> (y [B, S, D], metrics {aux_loss, dropped_frac})."""
    mo = cfg.moe
    cd = _dtype(cfg.compute_dtype)
    b, s, d = x.shape
    t = b * s
    g = _n_groups(cfg, t, b)
    tg = t // g
    xg = x.reshape(g, tg, d)
    xg = constrain(xg, "moe_group", None, None)

    # --- routing (fp32, group-batched) -------------------------------------
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),  # contract: allow-no-uncompensated-reduction(router logits; fp32 over d_model terms, only ordering matters)
                        p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, mo.top_k)       # [G,Tg,k]
    if mo.top_k > 1:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)  # contract: allow-no-uncompensated-reduction(gate renormalizer; top_k<=8 fp32 terms)

    # --- load-balance auxiliary loss (Switch-style, global statistics) -----
    top1 = expert_idx[..., 0].reshape(-1)
    counts = jnp.zeros((mo.n_experts,), jnp.float32).at[top1].add(1.0)
    frac_tokens = counts / t
    frac_probs = jnp.mean(probs, axis=(0, 1))  # contract: allow-no-uncompensated-reduction(router load statistic; feeds the diagnostic aux loss only)
    aux = mo.n_experts * jnp.sum(frac_tokens * frac_probs)  # contract: allow-no-uncompensated-reduction(aux-loss statistic; n_experts fp32 terms, diagnostic only)

    # --- group-local sort-based dispatch ------------------------------------
    tk = tg * mo.top_k
    e_flat = expert_idx.reshape(g, tk)
    g_flat = gates.reshape(g, tk)
    t_flat = jnp.broadcast_to(
        jnp.repeat(jnp.arange(tg, dtype=jnp.int32), mo.top_k), (g, tk))

    order = jnp.argsort(e_flat, axis=-1, stable=True)        # [G,Tk]
    se = jnp.take_along_axis(e_flat, order, axis=-1)
    st_tok = jnp.take_along_axis(t_flat, order, axis=-1)
    sg = jnp.take_along_axis(g_flat, order, axis=-1)
    pos = _positions_in_segment(se)

    if s == 1:
        # decode: DROPLESS (capacity = all slots) — a dropped token at
        # decode corrupts generation, and the buffer is tiny (tk tokens)
        capacity = tk
    else:
        capacity = int(max(1, round(tk / mo.n_experts * mo.capacity_factor)))
    keep = pos < capacity
    pos_c = jnp.where(keep, pos, capacity)  # OOB -> dropped by 'drop' mode

    # NOTE on indexing style: take_along_axis / .at[gidx, se, pos] would
    # broadcast u32 index tensors across the feature dim (observed: 20 GiB
    # index buffers at llama4 scale); the vmap'd row-gathers below keep D a
    # slice dimension (indices stay [Tk]-sized).
    gathered = jax.vmap(lambda mat, idx: mat[idx])(xg, st_tok)  # [G,Tk,D]
    gathered = jnp.where(keep[..., None], gathered, 0)

    def scatter_one(e_ids, c_ids, upd):
        b0 = jnp.zeros((mo.n_experts, capacity, d), cd)
        return b0.at[e_ids, c_ids].set(upd, mode="drop")

    buf = jax.vmap(scatter_one)(se, pos_c, gathered.astype(cd))
    # two-stage resharding: the scatter stays GROUP-LOCAL (E replicated per
    # group shard -> no collective in the scatter itself); the subsequent
    # constraint to the expert-parallel layout is a pure local slice.
    buf = constrain(buf, "moe_group", None, None, None)
    buf = constrain(buf, "moe_group", "expert", None, None)

    # --- expert FFN (contracted over the shared expert weights) -------------
    if cfg.mlp == "swiglu":
        gt = jnp.einsum("gecd,edf->gecf", buf, p["gate"].astype(cd))  # contract: allow-no-uncompensated-reduction(expert FFN contraction; cd accumulate, d_model terms)
        up = jnp.einsum("gecd,edf->gecf", buf, p["up"].astype(cd))  # contract: allow-no-uncompensated-reduction(expert FFN contraction; cd accumulate, d_model terms)
        h = (jax.nn.silu(gt.astype(jnp.float32)).astype(cd)) * up
    else:
        up = jnp.einsum("gecd,edf->gecf", buf, p["up"].astype(cd))  # contract: allow-no-uncompensated-reduction(expert FFN contraction; cd accumulate, d_model terms)
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(cd)
    out = jnp.einsum("gecf,efd->gecd", h, p["down"].astype(cd))  # contract: allow-no-uncompensated-reduction(expert FFN down-projection; cd accumulate, d_ff terms)
    out = constrain(out, "moe_group", "expert", None, None)

    # --- group-local combine -------------------------------------------------
    # all-gather the (small) expert outputs back to group-local layout so
    # the gather/scatter-add stay collective-free
    out = constrain(out, "moe_group", None, None, None)
    picked = jax.vmap(lambda o, e, c: o[e, c])(out, se, pos_c)  # [G,Tk,D]
    contrib = picked * (sg * keep).astype(cd)[..., None]
    y = jax.vmap(lambda t_ids, u: jnp.zeros((tg, d), cd).at[t_ids].add(u))(
        st_tok, contrib)
    y = constrain(y, "moe_group", None, None)

    if mo.n_shared:
        from repro.models.layers import mlp_apply
        y = y + mlp_apply(p["shared"], cfg, xg.astype(cd))

    metrics = {
        "aux_loss": aux,
        "dropped_frac": 1.0 - jnp.mean(keep.astype(jnp.float32)),  # contract: allow-no-uncompensated-reduction(capacity-drop diagnostic; fraction of a {0,1} mask)
    }
    return y.reshape(b, s, d), metrics
