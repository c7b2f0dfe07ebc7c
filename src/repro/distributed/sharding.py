"""Logical-axis sharding rules -> concrete NamedShardings.

Models annotate parameters/caches with LOGICAL PartitionSpecs ("embed",
"heads", "mlp", ...). This module maps them onto mesh axes per a rule
table, with shape-aware degradation: a mapping is dropped when the dim is
not divisible by the mesh-axis product (e.g. batch=1, or
kv_heads=5 on a 16-way model axis) — replication instead of a hard error,
mirroring how production frameworks degrade.

Rule table:

TRAIN_RULES — DP over (pod, data) for batch; ZeRO-3/FSDP over data for the
  "embed" weight dim; TP over model for heads/mlp/vocab/expert; sequence-
  parallel activations ("seq" -> model) so archs whose head counts do not
  divide 16 (llama4 40H, hymba 25H, whisper 20H) still shard attention
  compute by q-position.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

AxisMap = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class Rules:
    name: str
    table: Dict[str, AxisMap]

    def lookup(self, logical: Optional[str]) -> AxisMap:
        if logical is None:
            return None
        return self.table.get(logical)


TRAIN_RULES = Rules("train", {
    "batch": ("pod", "data"),
    "seq": "model",            # sequence-parallel activations
    "embed": "data",           # FSDP / ZeRO-3 weight dim
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "moe_group": ("pod", "data"),
    "kv_lora": None,
    "xl_inner": "model",
    "kv_seq": None,
    "kv_ring": None,
    "frames": None,
})


def _axis_size(mesh: Mesh, axes: AxisMap) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        if a not in mesh.shape:
            return 0        # axis absent (e.g. "pod" on single-pod mesh)
        size *= mesh.shape[a]
    return size


def _present_axes(mesh: Mesh, axes: AxisMap) -> AxisMap:
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    kept = tuple(a for a in axes if a in mesh.shape)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def physical_spec(mesh: Mesh, rules: Rules, logical: P,
                  shape: Tuple[int, ...]) -> P:
    """Map a logical PartitionSpec to mesh axes, dropping non-divisible or
    absent mappings (shape-aware degradation)."""
    if len(logical) == 0:
        return P()
    out = []
    used: set = set()
    for dim, name in enumerate(logical):
        axes = _present_axes(mesh, rules.lookup(name))
        size = _axis_size(mesh, axes) if axes is not None else 1
        flat = (axes,) if isinstance(axes, str) else (axes or ())
        if (axes is None or size <= 1 or dim >= len(shape)
                or shape[dim] % size != 0 or any(a in used for a in flat)):
            out.append(None)
        else:
            out.append(axes)
            used.update(flat)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


# ---------------------------------------------------------------------------
# Activation-constraint context (used by models via ``constrain``)
# ---------------------------------------------------------------------------

_CTX: contextvars.ContextVar = contextvars.ContextVar("shard_ctx",
                                                      default=None)


@contextlib.contextmanager
def activation_rules(mesh: Mesh, rules: Rules):
    token = _CTX.set((mesh, rules))
    try:
        yield
    finally:
        _CTX.reset(token)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint against the ambient (mesh, rules); no-op
    outside an ``activation_rules`` context (so tests/CPU paths are
    unaffected)."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = physical_spec(mesh, rules, P(*logical), tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

