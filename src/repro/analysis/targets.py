"""Registry of auditable trace targets.

A *target* bundles everything one entry point needs to be audited at the
IR level: a build callable producing a :class:`TraceArtifact` (jaxprs of
the real traced program, optionally an oracle trace, the standalone
shared-body trace, lazily compiled HLO, or a program-family inventory),
a tag set that scopes which trace rules run on it, and per-target
exemptions (the trace layer's analogue of source pragmas — rule id ->
mandatory reason, audited in the report like any pragma).

The registry mirrors ``repro.analysis.rules``: ``register()`` adds
targets at runtime, everything resolves through ``get``/``select``, and
the built-ins below cover the repo's real numerics surface — the
``ops.*`` engine wrappers, the flash kernel + oracle + shared block
body, the serving engine's decode tick and every prefill-chunk bucket
program, the sharded collectives, and the optimizer's
``engine_sq_norm`` — with tiny interpret-friendly shapes so the whole
audit stays inside the CI stage-0b budget.

Builds are memoized module-wide (the tiny model/engine is shared across
the serve targets) and lazy: importing this module registers targets but
traces nothing until ``trace.audit`` asks.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import (
    Any,
    Callable,
    Dict,
    Iterable,
    List,
    Mapping,
    Optional,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class TraceArtifact:
    """What one build produced — each field feeds specific trace rules.

    jaxpr             the target's main trace (most rules)
    oracle_jaxpr      the bitwise-oracle trace (trace-barrier-pinned)
    body_jaxpr        the standalone shared-block-body trace
                      (trace-barrier-pinned containment reference)
    compute_dtype     resolved accumulate dtype (trace-accum-dtype)
    slot_width        the decode tick's fixed slot width
                      (trace-decode-is-batched)
    hlo               lazy () -> (lowered_hlo_text, optimized_hlo_text)
                      (trace-barrier-survives-fusion)
    program_keys      prefill (width, runs_begin) family
                      (trace-program-count)
    program_bound     the O(#buckets) cap on that family
    """

    jaxpr: Any = None
    oracle_jaxpr: Any = None
    body_jaxpr: Any = None
    compute_dtype: Any = None
    slot_width: Optional[int] = None
    hlo: Optional[Callable[[], Tuple[str, str]]] = None
    program_keys: Optional[frozenset] = None
    program_bound: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class Target:
    """One auditable entry point.

    id       stable identifier (the finding anchor: ``<id>:0:0``)
    build    () -> TraceArtifact (memoize expensive work yourself —
             builders below share one tiny model/engine)
    tags     trace rules run on any tag overlap ("kernel", "sharded",
             "serve", "decode", "prefill", "shared-block", "hlo",
             "program-count")
    doc      one-line description (--trace --list-rules)
    exempt   rule id -> reason; suppresses that rule's findings on this
             target, surfaced in the report exactly like a source pragma
    """

    id: str
    build: Callable[[], TraceArtifact]
    tags: Tuple[str, ...]
    doc: str
    exempt: Mapping[str, str] = dataclasses.field(default_factory=dict)


_REGISTRY: Dict[str, Target] = {}


def register(target: Target, *, override: bool = False) -> Target:
    """Add a target (same registry contract as ``rules.register``)."""
    if not isinstance(target, Target):
        raise TypeError(f"expected Target, got {type(target)!r}")
    if target.id in _REGISTRY and not override:
        raise ValueError(
            f"trace target {target.id!r} already registered "
            f"(pass override=True to replace)")
    _REGISTRY[target.id] = target
    return target


def unregister(target_id: str) -> None:
    """Remove a target (tests / plugin teardown)."""
    _REGISTRY.pop(target_id, None)


def names() -> Tuple[str, ...]:
    """Registered target ids, registration order."""
    return tuple(_REGISTRY)


def registered() -> Dict[str, Target]:
    """Snapshot of the registry."""
    return dict(_REGISTRY)


def get(target_id: str) -> Target:
    """Fail-fast lookup with the registered menu."""
    try:
        return _REGISTRY[target_id]
    except KeyError:
        raise ValueError(
            f"unknown trace target {target_id!r}; registered targets: "
            f"{sorted(_REGISTRY)}") from None


def select(target_ids: Optional[Iterable[str]]) -> List[Target]:
    """All targets, or a validated subset."""
    if target_ids is None:
        return list(_REGISTRY.values())
    return [get(t) for t in target_ids]


# ---------------------------------------------------------------------------
# Shared tiny fixtures (memoized — one model, one engine, reused by every
# serve target and by tests that need a sibling engine on the same weights)
# ---------------------------------------------------------------------------

_F32 = jnp.float32

#: audit shapes: big enough to exercise blocking, small enough that the
#: full audit (every target) stays well under the CI stage-0b minute.
_N = 64
_MM = 8
_FLASH = (2, 8, 8)          # (batch*heads, seq, head_dim)
_FLASH_BLOCK = 8

#: tiny serving config (trace-decode-is-batched keys on slot-major scan
#: operands, not on trip count, so max_slots may equal n_layers)
_SLOTS = 3
_MAX_LEN = 16
_CHUNK = 4


def _sds(shape, dtype=_F32):
    return jax.ShapeDtypeStruct(shape, dtype)


@functools.lru_cache(maxsize=None)
def tiny_arch():
    """The audit's model config (the test suite's tiny dense config)."""
    from repro.configs.base import ArchConfig

    return ArchConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                      param_dtype="float32", compute_dtype="float32",
                      loss_chunk=64)


@functools.lru_cache(maxsize=None)
def tiny_flash_arch():
    """Flash-capable twin of :func:`tiny_arch`: ``kahan_attention=True``
    routes the parallel chunk body through the engine's chunk flash
    kernel, so the flash-prefill targets actually carry the Pallas
    grid (the default tiny config would silently audit the dense
    fallback core instead)."""
    from repro.configs.base import ArchConfig

    return ArchConfig(name="tiny-flash", family="dense", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      vocab_size=128, kahan_attention=True,
                      param_dtype="float32", compute_dtype="float32",
                      loss_chunk=64)


@functools.lru_cache(maxsize=None)
def _tiny_serve():
    """ONE tiny engine shared by every serve target."""
    from repro.serve import EngineConfig, InferenceEngine

    return InferenceEngine(
        tiny_arch(),
        EngineConfig(max_slots=_SLOTS, max_len=_MAX_LEN,
                     prefill_chunk=_CHUNK))


@functools.lru_cache(maxsize=None)
def _tiny_serve_flash():
    """The flash-mode sibling engine (parallel multi-token chunk body)."""
    from repro.serve import EngineConfig, InferenceEngine

    return InferenceEngine(
        tiny_flash_arch(),
        EngineConfig(max_slots=_SLOTS, max_len=_MAX_LEN,
                     prefill_chunk=_CHUNK, prefill_mode="flash"))


@functools.lru_cache(maxsize=None)
def _tiny_serve_paged():
    """The paged-layout sibling engine (page pool + traced page tables).

    Same arch/geometry as :func:`_tiny_serve` so its programs differ
    from the dense ones ONLY in the gather/scatter boundary — exactly
    the surface the paged targets audit."""
    from repro.serve import EngineConfig, InferenceEngine

    return InferenceEngine(
        tiny_arch(),
        EngineConfig(max_slots=_SLOTS, max_len=_MAX_LEN,
                     prefill_chunk=_CHUNK, kv_layout="paged", page_size=4))


@functools.lru_cache(maxsize=None)
def _mesh():
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]), ("data",))


@functools.lru_cache(maxsize=None)
def _engine_compute_dtype():
    from repro.kernels.engine import CompensatedReduction

    return CompensatedReduction().compute_dtype


# ---------------------------------------------------------------------------
# Builders
# ---------------------------------------------------------------------------

def _ops_build(name: str, *avals) -> Callable[[], TraceArtifact]:
    @functools.lru_cache(maxsize=None)
    def build() -> TraceArtifact:
        from repro.kernels import ops

        fn = getattr(ops, name)
        return TraceArtifact(jaxpr=jax.make_jaxpr(fn)(*avals),
                             compute_dtype=_engine_compute_dtype())

    return build


@functools.lru_cache(maxsize=None)
def _flash_build() -> TraceArtifact:
    from repro.kernels import ref as _ref
    from repro.kernels.engine import CompensatedReduction
    from repro.kernels.flash_attention import flash_block_probe

    eng = CompensatedReduction(scheme="kahan")
    q = _sds(_FLASH)
    kernel = jax.make_jaxpr(
        lambda q, k, v: eng.flash_attention(
            q, k, v, block_q=_FLASH_BLOCK, block_k=_FLASH_BLOCK))(q, q, q)
    oracle_fn = functools.partial(
        _ref.flash_attention_ref, scheme="kahan", block_q=_FLASH_BLOCK,
        block_k=_FLASH_BLOCK)
    oracle = jax.make_jaxpr(oracle_fn)(q, q, q)
    body_fn, body_args = flash_block_probe(
        scheme="kahan", block_q=_FLASH_BLOCK, block_k=_FLASH_BLOCK,
        dh=_FLASH[2], kv_len=_FLASH[1])
    body = jax.make_jaxpr(body_fn)(*body_args)

    def hlo() -> Tuple[str, str]:
        # the ORACLE is the pure-XLA barrier-pinned program (the kernel
        # side lowers through the Pallas interpreter on CPU); its
        # lowered module carries the opt-barrier ops and its optimized
        # module must keep the compensation subtracts they pin.
        lowered = jax.jit(oracle_fn).lower(q, q, q)
        return (lowered.compiler_ir("hlo").as_hlo_text(),
                lowered.compile().as_text())

    return TraceArtifact(jaxpr=kernel, oracle_jaxpr=oracle, body_jaxpr=body,
                         compute_dtype=eng.compute_dtype, hlo=hlo)


@functools.lru_cache(maxsize=None)
def _flash_chunk_build() -> TraceArtifact:
    from repro.kernels.engine import CompensatedReduction
    from repro.kernels.flash_attention import flash_block_probe

    eng = CompensatedReduction(scheme="kahan")
    q = _sds(_FLASH)
    off = _sds((), jnp.int32)
    kernel = jax.make_jaxpr(
        lambda q, k, v, off: eng.flash_chunk_attention(
            q, k, v, q_off=off, block_q=_FLASH_BLOCK,
            block_k=_FLASH_BLOCK))(q, q, q, off)
    body_fn, body_args = flash_block_probe(
        scheme="kahan", block_q=_FLASH_BLOCK, block_k=_FLASH_BLOCK,
        dh=_FLASH[2], kv_len=_FLASH[1], with_offset=True)
    body = jax.make_jaxpr(body_fn)(*body_args)
    return TraceArtifact(jaxpr=kernel, body_jaxpr=body,
                         compute_dtype=eng.compute_dtype)


@functools.lru_cache(maxsize=None)
def _sq_norm_build() -> TraceArtifact:
    from repro.optim.adamw import engine_sq_norm

    grads = {"w": _sds((_MM, _MM)), "b": _sds((_MM,))}
    return TraceArtifact(jaxpr=jax.make_jaxpr(engine_sq_norm)(grads),
                         compute_dtype=_engine_compute_dtype())


def _sharded_build(name: str, *avals) -> Callable[[], TraceArtifact]:
    @functools.lru_cache(maxsize=None)
    def build() -> TraceArtifact:
        from repro.distributed import collectives

        fn = getattr(collectives, name)
        closed = jax.make_jaxpr(
            lambda *xs: fn(_mesh(), *xs))(*avals)
        return TraceArtifact(jaxpr=closed)

    return build


@functools.lru_cache(maxsize=None)
def _decode_tick_build() -> TraceArtifact:
    engine = _tiny_serve()
    fn, args = engine.trace_tick()
    return TraceArtifact(jaxpr=jax.make_jaxpr(fn)(*args),
                         slot_width=engine.ec.max_slots)


@functools.lru_cache(maxsize=None)
def _prefill_traces() -> Dict[int, Any]:
    """width -> jaxpr of that bucket program (one shared engine)."""
    engine = _tiny_serve()
    out = {}
    for width in sorted(prefill_widths(), reverse=True):
        fn, args = engine.trace_prefill(width, first=False)
        out[width] = jax.make_jaxpr(fn)(*args)
    return out


@functools.lru_cache(maxsize=None)
def _prefill_body_reference():
    """The barrier-pinned per-position scan body, extracted from the
    WIDEST bucket program — the containment reference every other width
    must reproduce verbatim (widths differ only in scan trip count)."""
    from repro.analysis import trace as _trace

    widest = _prefill_traces()[max(prefill_widths())]
    for eqn, _ in _trace.iter_eqns(widest):
        if eqn.primitive.name == "scan":
            inner = eqn.params["jaxpr"]
            direct = [e.primitive.name for e in inner.jaxpr.eqns]
            if "optimization_barrier" in direct:
                return inner
    raise ValueError(
        "no barrier-pinned scan body in the prefill trace — "
        "prefill_chunk_scan lost its optimization_barrier pins")


def prefill_widths() -> Tuple[int, ...]:
    """The audit engine's static chunk-width family (power-of-two tail
    buckets up to the chunk, plus the chunk itself)."""
    from repro.serve.engine import prefill_program_family

    fam = prefill_program_family(_MAX_LEN, _CHUNK, needs_begin=False)
    return tuple(sorted({w for w, _ in fam}))


def _prefill_build(width: int) -> Callable[[], TraceArtifact]:
    def build() -> TraceArtifact:
        return TraceArtifact(jaxpr=_prefill_traces()[width],
                             body_jaxpr=_prefill_body_reference())

    return build


@functools.lru_cache(maxsize=None)
def _prefill_family_build() -> TraceArtifact:
    from repro.serve.engine import (
        prefill_program_bound,
        prefill_program_family,
    )

    return TraceArtifact(
        program_keys=prefill_program_family(_MAX_LEN, _CHUNK,
                                            needs_begin=False),
        program_bound=prefill_program_bound(_CHUNK, needs_begin=False))


@functools.lru_cache(maxsize=None)
def _paged_decode_tick_build() -> TraceArtifact:
    """The paged decode tick: one batched page-table gather -> the SAME
    pinned batched decode body -> one scatter of a page per slot.
    trace-decode-is-batched pins the body's width; the allocator
    never appears here (reservation is host-side, at admission), so
    trace-no-host-callback doubles as the no-host-sync guard on the
    allocator boundary."""
    engine = _tiny_serve_paged()
    assert engine.kv_layout == "paged", (
        "the audit's paged engine resolved to the dense layout")
    fn, args = engine.trace_tick()
    return TraceArtifact(jaxpr=jax.make_jaxpr(fn)(*args),
                         slot_width=engine.ec.max_slots)


@functools.lru_cache(maxsize=None)
def _paged_prefill_build() -> TraceArtifact:
    """The paged prefix-resume prefill program at the full chunk width:
    gather through the page table, the dense engine's OWN pinned
    per-position scan body (the containment reference is shared —
    paging may only change the data movement around it), then the
    range-masked page scatter."""
    engine = _tiny_serve_paged()
    fn, args = engine.trace_prefill(_CHUNK, first=False)
    return TraceArtifact(jaxpr=jax.make_jaxpr(fn)(*args),
                         body_jaxpr=_prefill_body_reference())


@functools.lru_cache(maxsize=None)
def _paged_prefill_family_build() -> TraceArtifact:
    """Paged prefill keeps the SAME O(#buckets) program family: the
    page table and reserved-page count are traced operands, so page
    placement (and prefix-resume offsets) can never mint programs."""
    from repro.serve.engine import (
        prefill_program_bound,
        prefill_program_family,
    )

    return TraceArtifact(
        program_keys=prefill_program_family(_MAX_LEN, _CHUNK,
                                            needs_begin=False),
        program_bound=prefill_program_bound(_CHUNK, needs_begin=False))


@functools.lru_cache(maxsize=None)
def _prefill_flash_traces() -> Dict[int, Any]:
    """width -> jaxpr of the flash-mode bucket program."""
    engine = _tiny_serve_flash()
    assert engine.prefill_body == "flash", (
        "the audit's flash engine resolved to the scan body — "
        "tiny_flash_arch lost its parallel-prefill eligibility")
    out = {}
    for width in sorted(prefill_widths(), reverse=True):
        fn, args = engine.trace_prefill(width, first=False)
        out[width] = jax.make_jaxpr(fn)(*args)
    return out


@functools.lru_cache(maxsize=None)
def _prefill_flash_body_reference():
    """The chunk flash kernel's block body WITH the traced-offset
    operand, traced standalone at the audit engine's resolved block
    geometry (block_q = the 8-padded chunk width, block_k / kv_len from
    the max_len-16 cache) — trace-barrier-pinned asserts every
    multi-token flash bucket program embeds this sequence verbatim."""
    from repro.kernels.flash_attention import flash_block_probe

    arch = tiny_flash_arch()
    body_fn, body_args = flash_block_probe(
        scheme="kahan", block_q=8, block_k=128, dh=arch.head_dim,
        kv_len=_MAX_LEN, with_offset=True)
    return jax.make_jaxpr(body_fn)(*body_args)


def _prefill_flash_build(width: int) -> Callable[[], TraceArtifact]:
    def build() -> TraceArtifact:
        # width-1 buckets route through the decode branch (a 1-wide
        # chunk IS a decode step) — no flash grid to pin there
        body = _prefill_flash_body_reference() if width > 1 else None
        return TraceArtifact(jaxpr=_prefill_flash_traces()[width],
                             body_jaxpr=body)

    return build


@functools.lru_cache(maxsize=None)
def _prefill_flash_family_build() -> TraceArtifact:
    """Flash mode must keep the SAME O(#buckets) program family: the
    body swap changes what runs inside a bucket program, never how many
    programs the engine compiles."""
    from repro.serve.engine import (
        prefill_program_bound,
        prefill_program_family,
    )

    return TraceArtifact(
        program_keys=prefill_program_family(_MAX_LEN, _CHUNK,
                                            needs_begin=False),
        program_bound=prefill_program_bound(_CHUNK, needs_begin=False))


# ---------------------------------------------------------------------------
# Built-in targets
# ---------------------------------------------------------------------------

for _t in (
    Target(id="ops.dot", build=_ops_build("dot", _sds((_N,)), _sds((_N,))),
           tags=("kernel",),
           doc="compensated dot product (engine wrapper)"),
    Target(id="ops.asum", build=_ops_build("asum", _sds((_N,))),
           tags=("kernel",),
           doc="compensated sum (engine wrapper)"),
    Target(id="ops.batched_dot",
           build=_ops_build("batched_dot", _sds((4, _N)), _sds((4, _N))),
           tags=("kernel",),
           doc="batched compensated dots on the (batch, steps) grid"),
    Target(id="ops.batched_asum",
           build=_ops_build("batched_asum", _sds((4, _N))),
           tags=("kernel",),
           doc="batched compensated sums on the (batch, steps) grid"),
    Target(id="ops.matmul",
           build=_ops_build("matmul", _sds((_MM, _MM)), _sds((_MM, _MM))),
           tags=("kernel",),
           doc="compensated matmul with inter-K-tile accumulation"),
    Target(id="ops.batched_matmul",
           build=_ops_build("batched_matmul", _sds((2, _MM, _MM)),
                            _sds((2, _MM, _MM))),
           tags=("kernel",),
           doc="batched compensated matmuls as one Pallas grid"),
    Target(id="kernels.flash_attention", build=_flash_build,
           tags=("kernel", "shared-block", "hlo"),
           doc="flash kernel vs jnp oracle, sharing flash_block_update"),
    Target(id="kernels.flash_chunk_attention", build=_flash_chunk_build,
           tags=("kernel", "shared-block"),
           doc="chunked-prefill flash grid (queries at a traced offset) "
               "embedding the offset variant of flash_block_update"),
    Target(id="optim.engine_sq_norm", build=_sq_norm_build,
           tags=("kernel", "sharded"),
           doc="optimizer global-norm fold through the engine's merge "
               "tree"),
    Target(id="collectives.sharded_asum",
           build=_sharded_build("sharded_asum", _sds((_N,))),
           tags=("sharded",),
           doc="cross-device compensated sum (all-gather + two-sum tree)"),
    Target(id="collectives.sharded_dot",
           build=_sharded_build("sharded_dot", _sds((_N,)), _sds((_N,))),
           tags=("sharded",),
           doc="cross-device compensated dot (all-gather + two-sum tree)"),
    Target(id="collectives.sharded_matmul",
           build=_sharded_build("sharded_matmul", _sds((_MM, _MM)),
                                _sds((_MM, _MM))),
           tags=("sharded",),
           doc="K-sharded compensated matmul (all-gather + grid merge)"),
    Target(id="collectives.deterministic_mean",
           build=_sharded_build("deterministic_mean", _sds((1,))),
           tags=("sharded",),
           doc="bitwise-deterministic scalar mean over a mesh axis"),
    Target(id="serve.decode_tick", build=_decode_tick_build,
           tags=("serve", "decode"),
           doc="the engine's jitted decode tick over the slot axis"),
    Target(id="serve.prefill_buckets", build=_prefill_family_build,
           tags=("program-count",),
           doc="the prefill (width, runs_begin) program family vs its "
               "O(#buckets) bound"),
    Target(id="serve.prefill_flash_buckets", build=_prefill_flash_family_build,
           tags=("program-count",),
           doc="flash-mode prefill program family — the parallel body "
               "keeps the same O(#buckets) bound"),
    Target(id="serve.paged_decode_tick", build=_paged_decode_tick_build,
           tags=("serve", "decode"),
           doc="paged decode tick: batched page-table gather/scatter "
               "around the pinned batched decode body"),
    Target(id="serve.paged_prefill.w4", build=_paged_prefill_build,
           tags=("serve", "prefill", "shared-block"),
           doc="paged prefix-resume prefill program (table + reserved-"
               "count operands) — must embed the dense engine's pinned "
               "per-position body verbatim"),
    Target(id="serve.paged_prefill_buckets", build=_paged_prefill_family_build,
           tags=("program-count",),
           doc="paged prefill program family — traced page tables keep "
               "placement out of the program key, same O(#buckets) bound"),
):
    register(_t)

for _w in prefill_widths():
    register(Target(
        id=f"serve.prefill.w{_w}", build=_prefill_build(_w),
        tags=("serve", "prefill", "shared-block"),
        doc=f"prefill bucket program at chunk width {_w} (must embed the "
            f"shared per-position body verbatim)"))
    register(Target(
        id=f"serve.prefill_flash.w{_w}", build=_prefill_flash_build(_w),
        tags=(("serve", "prefill", "shared-block") if _w > 1
              else ("serve", "prefill")),
        doc=(f"flash-mode prefill program at chunk width {_w} (must embed "
             f"the offset flash block body verbatim)" if _w > 1 else
             "flash-mode width-1 bucket (routes through the decode "
             "branch — no flash grid)")))
