"""AdamW and Kahan-compensated AdamW (the paper's technique applied to the
optimizer's long-horizon parameter accumulation).

Motivation: with bf16 parameters, per-step updates are
typically ~1e-3 of the parameter magnitude — far below bf16's 2^-8 relative
resolution — so naive ``p += update`` silently drops most steps ("stale
weights"). The classical fixes are fp32 master weights (+4 bytes/param).
The Kahan fix keeps a bf16 compensation buffer (+2 bytes/param) that
carries the dropped bits across steps: mathematically the same compensated
accumulation the paper applies to the dot product, applied over *time*
instead of over a vector.

States:
  AdamW      : m, v (fp32), params fp32 or bf16(+master)
  KahanAdamW : m, v (fp32 or bf16), params bf16 + comp bf16

Both share the same update math (bias-corrected Adam + decoupled weight
decay); only the parameter application differs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.kahan import kahan_step, tree_kahan_sq_norm
from repro.kernels.engine import CompensatedReduction, merge_accumulators


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    kahan: bool = True               # compensated bf16 parameter updates
    moment_dtype: str = "float32"    # bf16 moments are viable under kahan
    kahan_norm: bool = True          # compensated global-norm computation


class OptState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any
    comp: Optional[Any]              # Kahan compensation buffer (or None)


def init(cfg: AdamWConfig, params: Any) -> OptState:
    mdt = jnp.dtype(cfg.moment_dtype)
    zeros = lambda dt: jax.tree.map(lambda p: jnp.zeros(p.shape, dt), params)
    comp = None
    if cfg.kahan:
        comp = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)
    return OptState(step=jnp.zeros((), jnp.int32), m=zeros(mdt), v=zeros(mdt),
                    comp=comp)


def opt_state_specs(params_specs: Any, cfg: AdamWConfig) -> OptState:
    """Sharding specs matching init() — moments/comp shard like params."""
    from jax.sharding import PartitionSpec as P

    comp_spec = params_specs if cfg.kahan else None
    return OptState(step=P(), m=params_specs, v=params_specs, comp=comp_spec)


def engine_sq_norm(grads: Any) -> jax.Array:
    """Sum of squares of every leaf through the engine's compensated fold.

    Each leaf's squares go through ``sum_accumulators`` (the same kernel
    path as ``ops.asum``), the per-leaf ``(s, c)`` grids concatenate, and
    ONE ``merge_accumulators`` tree collapses them — so the cross-leaf
    fold shares the deterministic merge order used everywhere else in the
    engine instead of Python's left-to-right ``sum()``.
    """
    eng = CompensatedReduction()
    accs = [eng.sum_accumulators(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads)]
    s = jnp.concatenate([a.s.reshape(-1) for a in accs])
    c = jnp.concatenate([a.c.reshape(-1) for a in accs])
    return merge_accumulators(s, c)


def global_norm(cfg: AdamWConfig, grads: Any) -> jax.Array:
    if cfg.kahan_norm:
        return jnp.sqrt(tree_kahan_sq_norm(grads))
    return jnp.sqrt(engine_sq_norm(grads))


def global_norm_ref(grads: Any) -> jax.Array:
    """Uncompensated oracle for the engine-folded global norm (kept for
    the tolerance test in tests/test_optim.py)."""
    leaves = jax.tree.leaves(grads)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))  # contract: allow-no-uncompensated-reduction(reference oracle for engine_sq_norm; not a hot path)
                        for g in leaves))


def apply_update(cfg: AdamWConfig, params: Any, grads: Any, state: OptState,
                 lr_scale: jax.Array | float = 1.0,
                 ) -> Tuple[Any, OptState, dict]:
    """One optimizer step. grads may be any float dtype (upcast to fp32 for
    the moment math). Returns (params, state, metrics)."""
    step = state.step + 1
    gnorm = global_norm(cfg, grads)
    clip_scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-12)) \
        if cfg.grad_clip > 0 else 1.0

    b1, b2 = cfg.b1, cfg.b2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)
    lr = cfg.lr * lr_scale
    mdt = jnp.dtype(cfg.moment_dtype)

    def leaf_update(p, g, m, v):
        gf = g.astype(jnp.float32) * clip_scale
        m32 = m.astype(jnp.float32) * b1 + gf * (1 - b1)
        v32 = v.astype(jnp.float32) * b2 + jnp.square(gf) * (1 - b2)
        mhat = m32 / c1
        vhat = v32 / c2
        delta = -lr * (mhat / (jnp.sqrt(vhat) + cfg.eps)
                       + cfg.weight_decay * p.astype(jnp.float32))
        return delta, m32.astype(mdt), v32.astype(mdt)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)

    deltas, new_m, new_v = [], [], []
    for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
        d, m2, v2 = leaf_update(p, g, m, v)
        deltas.append(d)
        new_m.append(m2)
        new_v.append(v2)

    if cfg.kahan:
        flat_c = treedef.flatten_up_to(state.comp)
        new_p, new_c = [], []
        for p, d, c in zip(flat_p, deltas, flat_c):
            # compensated p += delta in the PARAM dtype (bf16-safe)
            s, c2 = kahan_step(p, c, d.astype(p.dtype))
            new_p.append(s)
            new_c.append(c2)
        params_out = treedef.unflatten(new_p)
        comp_out = treedef.unflatten(new_c)
    else:
        new_p = [(p.astype(jnp.float32) + d).astype(p.dtype)
                 for p, d in zip(flat_p, deltas)]
        params_out = treedef.unflatten(new_p)
        comp_out = None

    new_state = OptState(step=step, m=treedef.unflatten(new_m),
                         v=treedef.unflatten(new_v), comp=comp_out)
    metrics = {"grad_norm": gnorm, "lr": jnp.asarray(lr, jnp.float32)}
    return params_out, new_state, metrics
