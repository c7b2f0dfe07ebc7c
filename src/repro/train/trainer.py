"""Training loop: microbatched train_step with Kahan gradient accumulation,
checkpointing, auto-resume, and failure-tolerant outer loop.

``make_train_step`` builds the jit-able step:

    (params, opt_state, batch) -> (params, opt_state, metrics)

Microbatching: the global batch [B, S] is reshaped to [n_micro, B/n, S] and
scanned; gradients fold into a ``KahanAccumulator`` in ``accum_dtype``
(bf16-safe — the compensation term recovers the bits bf16 drops when a
small microbatch gradient lands on a large partial sum; the paper's kernel
over microbatches instead of vector lanes). The optimizer update runs once
per global step.

PP note: the scan-over-layers structure is stage-sliceable
(a pipeline stage = a contiguous slice of the stacked layer params), but
the assigned production mesh has no stage axis, so PP is not mapped here.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.kahan import KahanAccumulator
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distributed import sharding as shd
from repro.models import build_model
from repro.optim import AdamWConfig, apply_update
from repro.optim import init as opt_init
from repro.optim import schedule as schedules
from repro import checkpoint as ckpt

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    microbatches: int = 1
    accum_dtype: str = "float32"      # bf16 viable thanks to Kahan accum
    kahan_accum: bool = True
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    ckpt_keep: int = 3
    log_every: int = 10
    warmup: int = 20
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)


def make_train_step(model, cfg: ArchConfig, tc: TrainConfig,
                    mesh=None) -> Callable:
    """Build the jit-able train step.

    ``mesh``: optional ``jax.sharding.Mesh``. When it has more than one
    device on the "data" axis (and microbatching is on), the cross-device
    scalar LOSS METRIC folds through ``collectives.sharded_asum`` —
    per-device compensated Pallas kernels, all-gathered (s, c) grids, and
    the deterministic two-sum tree — instead of the local ``kahan_step``
    scan fold, so the reported loss is bitwise reproducible regardless of
    backend reduction order. ``tc.microbatches`` must then divide by the
    data-axis size (validated HERE, not silently skipped). The gradient
    path is unchanged; this is the metric plumbing the ROADMAP left open
    for multi-host training.
    """
    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch)
        return loss, metrics, grads

    n_data = 1 if mesh is None else int(mesh.shape.get("data", 1))
    if n_data > 1 and tc.microbatches > 1 and tc.microbatches % n_data:
        raise ValueError(
            f"microbatches ({tc.microbatches}) must divide by the mesh "
            f"data-axis size ({n_data}) for the sharded loss-metric fold "
            "— refusing to silently fall back to the local fold")

    def train_step(params, opt_state, batch):
        adt = jnp.dtype(tc.accum_dtype)
        if tc.microbatches <= 1:
            loss, metrics, grads = grads_of(params, batch)
        else:
            n = tc.microbatches

            def split(x):
                b = x.shape[0]
                assert b % n == 0, f"batch {b} % microbatches {n}"
                return x.reshape(n, b // n, *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def body(carry, mb):
                acc, loss_s, loss_c = carry
                loss, metrics, grads = grads_of(params, mb)
                grads = jax.tree.map(lambda g: g.astype(adt), grads)
                if tc.kahan_accum:
                    acc = acc.add(grads)
                else:
                    acc = KahanAccumulator(
                        jax.tree.map(jnp.add, acc.value, grads), acc.comp)
                from repro.core.kahan import kahan_step
                loss_s, loss_c = kahan_step(loss_s, loss_c, loss)
                return (acc, loss_s, loss_c), (metrics, loss)

            zero = KahanAccumulator.zeros_like(
                jax.tree.map(lambda p: jnp.zeros(p.shape, adt), params))
            (acc, loss_s, loss_c), (metrics, losses) = jax.lax.scan(
                body, (zero, jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)), micro)
            grads = acc.scale(1.0 / n).total()
            if n_data > 1:
                # engine's sharded path: the [n_micro] loss vector shards
                # over "data", each device reduces its slice with the
                # compensated kernel, grids all-gather + tree-merge.
                # (divisibility validated at build time above)
                from repro.distributed import collectives

                loss = collectives.sharded_asum(
                    mesh, losses.astype(jnp.float32)) / n
            else:
                loss = (loss_s + loss_c) / n
            metrics = jax.tree.map(lambda m: m[-1], metrics)

        lr_scale = schedules.warmup_cosine(opt_state.step, warmup=tc.warmup,
                                           total=max(tc.steps, 1))
        params, opt_state, opt_metrics = apply_update(
            tc.opt, params, grads, opt_state, lr_scale=lr_scale)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


class Trainer:
    """Single-host training driver with checkpoint/auto-resume.

    ``failure_hook(step)`` is called before each step — the FT tests inject
    simulated crashes through it.
    """

    def __init__(self, cfg: ArchConfig, tc: TrainConfig, data: SyntheticLM,
                 failure_hook: Optional[Callable[[int], None]] = None,
                 seed: int = 0, mesh=None):
        self.cfg = cfg
        self.tc = tc
        self.data = data
        self.failure_hook = failure_hook
        self.model = build_model(cfg)
        self.step_fn = jax.jit(make_train_step(self.model, cfg, tc,
                                               mesh=mesh),
                               donate_argnums=(0, 1))
        key = jax.random.key(seed)
        self.params, self.specs = self.model.init(key)
        self.opt_state = opt_init(tc.opt, self.params)
        self.step = 0
        self.metrics_history: list = []
        self._maybe_resume()

    # ----------------------------------------------------------- checkpoint
    def _state_tree(self):
        return {"params": self.params, "opt": self.opt_state}

    def _maybe_resume(self):
        tc = self.tc
        if not tc.ckpt_dir:
            return
        latest = ckpt.latest_step(tc.ckpt_dir)
        if latest is None:
            return
        tree, step, extras = ckpt.restore(tc.ckpt_dir, self._state_tree())
        self.params = tree["params"]
        self.opt_state = tree["opt"]
        self.step = step
        self.data.load_state_dict(extras.get("data", {"step": step}))
        log.info("resumed from step %d", step)

    def _save(self):
        if not self.tc.ckpt_dir:
            return
        ckpt.save(self.tc.ckpt_dir, self.step, self._state_tree(),
                  extras={"data": self.data.state_dict()},
                  keep=self.tc.ckpt_keep)

    # ------------------------------------------------------------------ run
    def run(self) -> Dict[str, float]:
        tc = self.tc
        t0 = time.time()
        while self.step < tc.steps:
            if self.failure_hook is not None:
                self.failure_hook(self.step)
            batch_np = self.data.batch_at(self.step)
            batch = jax.tree.map(jnp.asarray, batch_np)
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
            self.step += 1
            self.data.load_state_dict({"step": self.step})
            if self.step % tc.log_every == 0 or self.step == tc.steps:
                m = {k: float(v) for k, v in metrics.items()
                     if jnp.ndim(v) == 0}
                m["step"] = self.step
                m["wall_s"] = round(time.time() - t0, 2)
                self.metrics_history.append(m)
                log.info("step %d: %s", self.step,
                         {k: round(v, 4) if isinstance(v, float) else v
                          for k, v in m.items()})
            if self.step % tc.ckpt_every == 0 or self.step == tc.steps:
                self._save()
        return self.metrics_history[-1] if self.metrics_history else {}
