"""Cost-level contract rules: verify kernel cost against the ECM model.

The paper's method is low-level instruction analysis feeding the ECM
model — count the FLOPs, loads, and stores one loop iteration executes,
and the model predicts when compensation is hidden behind the memory
stream. ``core/ecm.py`` builds its tables from each scheme's *declared*
``instruction_mix``; this module is the third analysis level that checks
the declaration against what the kernels actually trace, so an ECM
prediction can never silently drift from the compiled truth (the
verification substrate the ROADMAP-item-5 Policy autotuner trusts).

Mechanism: :func:`register_cost_targets` registers one cost target per
(kernel kind x registered scheme) into the shared
:mod:`repro.analysis.targets` registry — ``cost.dot.<scheme>``,
``cost.asum.<scheme>``, ``cost.matmul.<scheme>``, ``cost.flash.<scheme>``
plus a ``cost.dot.kahan.bf16`` accumulate-dtype cell. Each build traces
the real ``ops.*`` entry point at audit shapes, locates the embedded
``pallas_call``, and statically derives a :class:`CostArtifact`:
per-element add/mul counts (float ``add``/``sub``/``mul`` equations in
the kernel-body jaxpr, weighted by output element count), MXU
``dot_general`` calls, and bytes loaded/stored per element at the
resolved ``compute_dtype`` (measured at TWO sizes, so load linearity and
accumulator-store constancy are facts, not assumptions). A ``CostRule``
registry mirroring ``rules.py``/``trace.py`` then cross-checks:

=========================  =============================================
cost-instruction-mix       the traced per-element FLOP mix matches the
                           scheme's declared ``InstructionMix``
                           (``traced_dot`` on the dot body, ``traced_sum``
                           on the asum body and the matmul/flash fold
                           sites) for every registered scheme
cost-memory-traffic        traced bytes/element match the
                           ``ecm.elem_bytes_for_dtype``-derived
                           expectation (streams x element width; the
                           accumulator store is n-independent)
cost-no-hidden-copies      no transpose/convert opcode in the optimized
                           HLO of the jitted scheme body — an XLA upgrade
                           (or a careless scheme) that materializes a
                           hidden copy invalidates the traffic model
cost-compensation-ratio    at the MEASURED counts the scheme stays
                           bandwidth-bound, i.e. its ECM time equals
                           naive's — the paper's "Kahan costs ~nothing"
                           claim as a machine-checked invariant
cost-ecm-tables-derived    the ``ecm.tpu_block_for_scheme`` table entry
                           is reproducible from the traced mix (flags
                           canonical-vs-traced drift with the measured
                           counts in the finding)
=========================  =============================================

Findings anchor ``target:0:0`` and share ``LintReport`` with the AST and
trace levels; per-target exemptions (``Target(exempt={...})``) audit
exactly like source pragmas. Run it with
``python -m repro.analysis --cost [--strict] [--target ID]``
(= ``scripts/ci.sh`` stage 0c); ``--cost --list-rules`` lists rules AND
cost targets.

Adding a cost rule mirrors the other levels::

    from repro.analysis import costmodel

    def _check_my_clause(target, art):
        if art.kind == "dot" and art.adds > 100:
            yield costmodel._v(target, "cost-my-clause", "...")

    costmodel.register(costmodel.CostRule(
        id="cost-my-clause", tags=("cost-dot",),
        checker=_check_my_clause, fix_hint="...", doc="..."))
"""

from __future__ import annotations

import dataclasses
import functools
from typing import (
    Any,
    Callable,
    Dict,
    Iterable,
    Iterator,
    List,
    Optional,
    Tuple,
)

import numpy as np

from repro.analysis.core import LintReport, Pragma, Violation
from repro.analysis.trace import iter_eqns


def _float_avals(vars_) -> Iterator[Any]:
    """Float-dtype avals — unlike the trace layer's np-only helper this
    recognizes the extension float dtypes too (bfloat16 is an ml_dtypes
    type numpy does not consider a ``np.floating`` subdtype, and the
    bf16 accumulate cell is exactly the target that must be counted)."""
    import jax.numpy as jnp

    for v in vars_:
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None and jnp.issubdtype(dt, jnp.floating):
            yield aval

CostChecker = Callable[[Any, Any], Iterator[Violation]]

#: audit sizes for the 1-D reductions: _N1 is exactly one kernel block
#: at the default policy (8 rows x unroll 8 x 128 lanes), _N2 is two —
#: measuring at both proves loads scale linearly while the accumulator
#: store stays constant.
_N1 = 8 * 128 * 8
_N2 = 2 * _N1
#: matmul audit cell: (16, 16) inputs on (8, 8, 8) blocks -> a (2, 2, 2)
#: grid whose body folds one MXU tile per K step.
_MM_N = 16
_MM_BLOCK = 8
#: flash audit cell (block_q = block_k = dh = kv_len = 8): the block
#: body folds TWO accumulator sites per K tile — the row-sum l
#: (block_q elems) and the weighted-value acc (block_q x dh elems).
_FLASH_DIM = 8
_FLASH_FOLD_ELEMS = _FLASH_DIM * (1 + _FLASH_DIM)

#: opcodes that must NOT appear in the optimized HLO of a scheme body:
#: a materialized transpose or dtype round-trip is hidden traffic the
#: byte model does not account for. (``copy`` stays allowed — XLA emits
#: a benign tuple-element copy even for the naive body.)
_FORBIDDEN_HLO_OPS = ("transpose", "convert")

#: tolerance for the compensation-ratio check: bandwidth-bound means
#: T_ECM(scheme)/T_ECM(naive) == 1.0 exactly in the model; allow for
#: float division noise only.
_RATIO_TOL = 1e-9


# ---------------------------------------------------------------------------
# Artifact + rule registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CostArtifact:
    """Statically derived cost of one kernel at audit shapes.

    kind                "dot" | "asum" | "matmul" | "flash"
    scheme              registered scheme name
    compute_dtype       resolved accumulate dtype of the traced kernel
    adds / muls         float add(+sub) / mul count: per element for
                        dot/asum, per output-tile element per K step for
                        matmul, raw per-probe for flash
    mxu_calls           ``dot_general`` equations in the kernel body
    load_bytes_per_elem n -> float input-stream bytes per element
    store_bytes         n -> total accumulator-output bytes (the (s, c)
                        grids the kernel emits)
    baseline_adds/muls  the naive scheme's raw flash-probe counts (the
                        differential baseline; flash only)
    fold_elems          accumulator elements folded per flash K tile
    hlo                 lazy () -> optimized HLO text of the jitted body
    """

    kind: str
    scheme: str
    compute_dtype: Any = None
    adds: float = 0.0
    muls: float = 0.0
    mxu_calls: int = 0
    load_bytes_per_elem: Dict[int, float] = dataclasses.field(
        default_factory=dict)
    store_bytes: Dict[int, int] = dataclasses.field(default_factory=dict)
    baseline_adds: float = 0.0
    baseline_muls: float = 0.0
    fold_elems: int = 0
    hlo: Optional[Callable[[], str]] = None


@dataclasses.dataclass(frozen=True)
class CostRule:
    """One cost clause of the performance contract.

    id        exemption-addressable identifier (``Target.exempt`` key)
    tags      a rule runs on every cost target sharing at least one tag
              ("cost-dot" / "cost-asum" / "cost-matmul" / "cost-flash")
    checker   generator over (target, artifact) yielding Violations
    fix_hint  one-line remediation appended to findings
    doc       one-line statement of the clause (--cost --list-rules)
    """

    id: str
    tags: Tuple[str, ...]
    checker: CostChecker
    fix_hint: str
    doc: str

    def applies_to(self, target) -> bool:
        return bool(set(self.tags) & set(target.tags))


_REGISTRY: Dict[str, CostRule] = {}


def register(rule: CostRule, *, override: bool = False) -> CostRule:
    """Add a cost rule (same registry contract as ``rules.register``)."""
    if not isinstance(rule, CostRule):
        raise TypeError(f"expected CostRule, got {type(rule)!r}")
    if rule.id in _REGISTRY and not override:
        raise ValueError(
            f"cost rule {rule.id!r} already registered "
            f"(pass override=True to replace)")
    _REGISTRY[rule.id] = rule
    return rule


def unregister(rule_id: str) -> None:
    """Remove a cost rule (tests / plugin teardown)."""
    _REGISTRY.pop(rule_id, None)


def names() -> Tuple[str, ...]:
    """Registered cost-rule ids, registration order."""
    return tuple(_REGISTRY)


def registered() -> Dict[str, CostRule]:
    """Snapshot of the registry."""
    return dict(_REGISTRY)


def get(rule_id: str) -> CostRule:
    """Fail-fast lookup with the registered menu."""
    try:
        return _REGISTRY[rule_id]
    except KeyError:
        raise ValueError(
            f"unknown cost rule {rule_id!r}; registered cost rules: "
            f"{sorted(_REGISTRY)}") from None


def select(rule_ids: Optional[Iterable[str]]) -> List[CostRule]:
    """All cost rules, or a validated subset."""
    if rule_ids is None:
        return list(_REGISTRY.values())
    return [get(r) for r in rule_ids]


# ---------------------------------------------------------------------------
# Static derivation: count what the kernel-body jaxpr executes
# ---------------------------------------------------------------------------

_ADD_PRIMS = frozenset(("add", "sub", "add_any"))
_MUL_PRIMS = frozenset(("mul",))


def weighted_op_counts(jaxpr) -> Tuple[float, float, int]:
    """(adds, muls, mxu_calls) of a jaxpr, element-weighted.

    Every float ``add``/``sub`` (adds) and ``mul`` (muls) equation
    contributes its output element count — the vector op count a VPU
    actually executes. ``dot_general`` equations are MXU work and are
    counted separately, NOT folded into the flop mix. Predication ops
    (``select_n``, broadcasts, comparisons — pairwise's cascade control)
    are excluded: they occupy no FLOP slot in the paper's accounting.
    """
    adds = muls = 0.0
    mxu = 0
    for eqn, _ in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "dot_general":
            mxu += 1
            continue
        if name not in _ADD_PRIMS and name not in _MUL_PRIMS:
            continue
        for aval in _float_avals(eqn.outvars):
            elems = float(np.prod(aval.shape)) if aval.shape else 1.0
            if name in _ADD_PRIMS:
                adds += elems
            else:
                muls += elems
    return adds, muls, mxu


def find_pallas_call(jaxpr):
    """The single ``pallas_call`` equation inside a traced entry point
    (fail fast if zero or several — the cost accounting assumes the
    engine launches exactly one grid per call)."""
    calls = [eqn for eqn, _ in iter_eqns(jaxpr)
             if eqn.primitive.name == "pallas_call"]
    if len(calls) != 1:
        raise ValueError(
            f"expected exactly one pallas_call in the trace, found "
            f"{len(calls)} — the cost model cannot attribute the work")
    return calls[0]


def pallas_io_bytes(eqn) -> Tuple[int, int]:
    """(load_bytes, store_bytes) of one ``pallas_call`` equation: total
    float bytes streamed in (the HBM read side of the ECM model) and the
    float bytes of the emitted accumulator grids."""
    loads = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in _float_avals(eqn.invars))
    stores = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in _float_avals(eqn.outvars))
    return loads, stores


def _grid_steps(eqn) -> int:
    grid = eqn.params["grid_mapping"].grid
    return int(np.prod(grid)) if grid else 1


def _v(target, rule: str, message: str) -> Violation:
    return Violation(rule=rule, path=target.id, line=0, col=0,
                     message=message)


# ---------------------------------------------------------------------------
# Cost-target builders
# ---------------------------------------------------------------------------

def _resolve_dtype(compute_dtype):
    from repro.kernels import schemes as _schemes

    return _schemes.resolve_compute_dtype(compute_dtype)


def _scheme_body_hlo(scheme_name: str, dtype) -> Callable[[], str]:
    """Lazy optimized-HLO text of the jitted ``mul_update`` body on one
    (8, 128) VREG block — what XLA makes of the scheme's inner loop."""
    def hlo() -> str:
        import jax
        import jax.numpy as jnp

        from repro.kernels import schemes as _schemes

        sch = _schemes.get(scheme_name)
        blk = jax.ShapeDtypeStruct((8, 128), dtype)
        step = jax.ShapeDtypeStruct((), jnp.int32)
        fn = lambda s, c, a, b, g: sch.mul_update(s, c, a, b, g)  # noqa: E731
        return jax.jit(fn).lower(blk, blk, blk, blk, step).compile().as_text()

    return hlo


def _reduction_cost_build(kind: str, scheme_name: str,
                          compute_dtype=None) -> Callable[[], CostArtifact]:
    """Builder for the 1-D reductions (``ops.dot`` / ``ops.asum``):
    trace at _N1 and _N2, count the embedded kernel body, measure the
    pallas_call's streamed bytes at both sizes."""
    def build() -> CostArtifact:
        import jax

        from repro.kernels import ops

        dt = _resolve_dtype(compute_dtype)
        art = CostArtifact(kind=kind, scheme=scheme_name, compute_dtype=dt,
                           hlo=_scheme_body_hlo(scheme_name, dt))
        fn = getattr(ops, kind)
        for n in (_N1, _N2):
            avals = (jax.ShapeDtypeStruct((n,), dt),)
            if kind == "dot":
                avals = avals * 2
            jaxpr = jax.make_jaxpr(functools.partial(
                fn, scheme=scheme_name, compute_dtype=dt))(*avals)
            call = find_pallas_call(jaxpr)
            loads, stores = pallas_io_bytes(call)
            art.load_bytes_per_elem[n] = loads / n
            art.store_bytes[n] = stores
            if n == _N1:
                adds, muls, mxu = weighted_op_counts(call.params["jaxpr"])
                steps = _grid_steps(call)
                art.adds = adds * steps / n
                art.muls = muls * steps / n
                art.mxu_calls = mxu
        return art

    return build


def _matmul_cost_build(scheme_name: str) -> Callable[[], CostArtifact]:
    """Builder for ``ops.matmul``: the kernel body folds ONE MXU tile
    per K step through the scheme's sum path — counts normalize per
    output-tile element (block_m x block_n)."""
    def build() -> CostArtifact:
        import jax

        from repro.kernels import ops

        dt = _resolve_dtype(None)
        a = jax.ShapeDtypeStruct((_MM_N, _MM_N), dt)
        jaxpr = jax.make_jaxpr(functools.partial(
            ops.matmul, scheme=scheme_name, block_m=_MM_BLOCK,
            block_n=_MM_BLOCK, block_k=_MM_BLOCK))(a, a)
        call = find_pallas_call(jaxpr)
        adds, muls, mxu = weighted_op_counts(call.params["jaxpr"])
        tile = _MM_BLOCK * _MM_BLOCK
        return CostArtifact(kind="matmul", scheme=scheme_name,
                            compute_dtype=dt, adds=adds / tile,
                            muls=muls / tile, mxu_calls=mxu)

    return build


@functools.lru_cache(maxsize=None)
def _flash_probe_counts(scheme_name: str) -> Tuple[float, float]:
    """Raw (adds, muls) of the flash block body traced standalone at the
    audit geometry. Memoized per scheme name within a process — the
    naive baseline is re-derived for every differential comparison."""
    import jax

    from repro.kernels.flash_attention import flash_block_probe

    body_fn, body_args = flash_block_probe(
        scheme=scheme_name, block_q=_FLASH_DIM, block_k=_FLASH_DIM,
        dh=_FLASH_DIM, kv_len=_FLASH_DIM)
    jaxpr = jax.make_jaxpr(body_fn)(*body_args)
    adds, muls, _ = weighted_op_counts(jaxpr)
    return adds, muls


def _flash_cost_build(scheme_name: str) -> Callable[[], CostArtifact]:
    """Builder for the flash block body: softmax work is scheme-
    independent, so the scheme's cost is DIFFERENTIAL — extra adds over
    the naive body at the two accumulator fold sites (l and acc),
    ``fold_elems`` accumulator elements per K tile."""
    def build() -> CostArtifact:
        import jax

        from repro.kernels.flash_attention import flash_block_probe

        dt = _resolve_dtype(None)
        adds, muls = _flash_probe_counts(scheme_name)
        base_adds, base_muls = _flash_probe_counts("naive")

        def hlo() -> str:
            body_fn, body_args = flash_block_probe(
                scheme=scheme_name, block_q=_FLASH_DIM, block_k=_FLASH_DIM,
                dh=_FLASH_DIM, kv_len=_FLASH_DIM)
            return jax.jit(body_fn).lower(*body_args).compile().as_text()

        return CostArtifact(kind="flash", scheme=scheme_name,
                            compute_dtype=dt, adds=adds, muls=muls,
                            baseline_adds=base_adds, baseline_muls=base_muls,
                            fold_elems=_FLASH_FOLD_ELEMS, hlo=hlo)

    return build


#: dot2's split-based fp32 body deliberately executes MORE raw VPU ops
#: (25/elem) than its canonical FMA-based Ogita accounting (17/elem, the
#: figure the ECM tables keep for cross-paper comparability). At the raw
#: count dot2 crosses the v5e compute/bandwidth break-even, so the two
#: model-facing rules are exempt WITH the trade documented — the
#: instruction-mix and traffic rules still verify the raw counts against
#: the declared traced_* overrides.
_DOT2_EXEMPT = {
    "cost-compensation-ratio":
        "split-based TwoProd (no FMA on the VPU) costs 25 raw flops/elem "
        "— compute-bound at v5e, unlike the canonical 17-flop accounting; "
        "the accuracy-vs-cost trade is deliberate and benchmarked",
    "cost-ecm-tables-derived":
        "ECM tables keep the canonical FMA-based Ogita count (17 "
        "flops/elem) for cross-paper comparability; the traced split "
        "body executes 25 — declared via InstructionMix.traced_* and "
        "verified by cost-instruction-mix",
}


def register_cost_targets() -> Tuple[str, ...]:
    """(Re-)register one cost target per kernel kind x registered scheme
    into the shared ``analysis.targets`` registry, plus the bf16
    accumulate cell. Idempotent (``override=True``) and registry-driven,
    so schemes registered at runtime are covered by the next audit;
    auto-registered cost targets whose scheme has since been
    UNregistered are pruned (a scheme that is gone cannot — and need
    not — be cost-audited). Returns the registered target ids."""
    from repro.analysis import targets as _targets
    from repro.kernels import schemes as _schemes

    ids = []

    def _add(target):
        _targets.register(target, override=True)
        ids.append(target.id)

    for name in _schemes.names():
        exempt = dict(_DOT2_EXEMPT) if name == "dot2" else {}
        _add(_targets.Target(
            id=f"cost.dot.{name}",
            build=_reduction_cost_build("dot", name),
            tags=("cost", "cost-dot"),
            doc=f"static cost of the {name} dot kernel body vs the ECM "
                f"model (mix, traffic, HLO, ratio, tables)",
            exempt=exempt))
        _add(_targets.Target(
            id=f"cost.asum.{name}",
            build=_reduction_cost_build("asum", name),
            tags=("cost", "cost-asum"),
            doc=f"static cost of the {name} sum kernel body (sum-path "
                f"mix + single-stream traffic)"))
        _add(_targets.Target(
            id=f"cost.matmul.{name}",
            build=_matmul_cost_build(name),
            tags=("cost", "cost-matmul"),
            doc=f"static cost of the {name} matmul body (sum-path fold "
                f"per MXU tile, exactly one dot_general)"))
        _add(_targets.Target(
            id=f"cost.flash.{name}",
            build=_flash_cost_build(name),
            tags=("cost", "cost-flash"),
            doc=f"differential cost of the {name} flash block body over "
                f"the naive baseline at the two fold sites"))
    _add(_targets.Target(
        id="cost.dot.kahan.bf16",
        build=_reduction_cost_build("dot", "kahan",
                                    compute_dtype="bfloat16"),
        tags=("cost", "cost-dot"),
        doc="the kahan dot kernel at bfloat16 accumulate — the halved "
            "element width must reach the traffic model",
        exempt={
            "cost-no-hidden-copies":
                "the CPU/XLA backend legalizes bf16 arithmetic through "
                "convert pairs — platform dtype lowering, not scheme "
                "structure; the fp32 cell covers the structural check",
        }))
    # prune auto-registered cells of schemes that have since been
    # unregistered (plugin/test teardown) — a stale cell would otherwise
    # fail its build on the registry lookup forever after.
    prefixes = tuple(f"cost.{k}." for k in ("dot", "asum", "matmul",
                                            "flash"))
    for tid, target in _targets.registered().items():
        if "cost" in target.tags and tid.startswith(prefixes) \
                and tid not in ids:
            _targets.unregister(tid)
    return tuple(ids)


# ---------------------------------------------------------------------------
# Built-in cost rules
# ---------------------------------------------------------------------------

def _expectation(art):
    from repro.core import ecm

    return ecm.expected_cost(
        art.scheme, compute_dtype=art.compute_dtype,
        streams=2 if art.kind == "dot" else 1)


def _check_instruction_mix(target, art) -> Iterator[Violation]:
    exp = _expectation(art)
    if art.kind in ("dot", "asum"):
        want = ((exp.dot_adds, exp.dot_muls) if art.kind == "dot"
                else (exp.sum_adds, 0))
        got = (art.adds, art.muls)
        if got != (float(want[0]), float(want[1])):
            yield _v(target, "cost-instruction-mix",
                     f"traced {art.kind} body executes "
                     f"{art.adds:g} adds + {art.muls:g} muls per element; "
                     f"the declared instruction_mix says {want[0]} + "
                     f"{want[1]}")
        if art.mxu_calls:
            yield _v(target, "cost-instruction-mix",
                     f"{art.mxu_calls} dot_general equation(s) in the "
                     f"{art.kind} kernel body — the VPU reduction must "
                     f"not route through the MXU")
    elif art.kind == "matmul":
        if (art.adds, art.muls) != (float(exp.sum_adds), 0.0):
            yield _v(target, "cost-instruction-mix",
                     f"matmul body folds {art.adds:g} adds + {art.muls:g} "
                     f"muls per tile element per K step; the scheme's sum "
                     f"path declares {exp.sum_adds} + 0 (products belong "
                     f"to the MXU)")
        if art.mxu_calls != 1:
            yield _v(target, "cost-instruction-mix",
                     f"matmul body contains {art.mxu_calls} dot_general "
                     f"equations — expected exactly one MXU tile "
                     f"contraction per K step")
    elif art.kind == "flash":
        want_delta = (exp.sum_adds - 1) * art.fold_elems
        got_delta = art.adds - art.baseline_adds
        if got_delta != float(want_delta):
            yield _v(target, "cost-instruction-mix",
                     f"flash body costs {got_delta:g} adds over the naive "
                     f"baseline; the scheme's sum path "
                     f"({exp.sum_adds} adds/elem at {art.fold_elems} fold "
                     f"elements per tile) predicts {want_delta}")
        if art.muls != art.baseline_muls:
            yield _v(target, "cost-instruction-mix",
                     f"flash body executes {art.muls:g} muls vs the naive "
                     f"baseline's {art.baseline_muls:g} — the sum-path "
                     f"fold must not add multiplies")


def _check_memory_traffic(target, art) -> Iterator[Violation]:
    exp = _expectation(art)
    for n, got in sorted(art.load_bytes_per_elem.items()):
        if got != float(exp.load_bytes_per_elem):
            yield _v(target, "cost-memory-traffic",
                     f"kernel streams {got:g} load bytes/element at "
                     f"n={n}; {exp.streams} stream(s) x {exp.elem_bytes} B "
                     f"({np.dtype(art.compute_dtype).name}) predicts "
                     f"{exp.load_bytes_per_elem}")
    stores = sorted(art.store_bytes.items())
    if len(stores) >= 2 and len({b for _, b in stores}) != 1:
        yield _v(target, "cost-memory-traffic",
                 f"accumulator store bytes vary with n "
                 f"({dict(stores)}) — the emitted (s, c) grids must be "
                 f"n-independent (fixed rows x 128 x elem_bytes)")


def _check_no_hidden_copies(target, art) -> Iterator[Violation]:
    if art.hlo is None:
        return
    from repro.analysis.hlo import parse_hlo

    counts = parse_hlo(art.hlo()).opcode_counts()
    for op in _FORBIDDEN_HLO_OPS:
        if counts.get(op, 0):
            yield _v(target, "cost-no-hidden-copies",
                     f"optimized HLO of the {art.scheme} body contains "
                     f"{counts[op]} {op} op(s) — hidden data movement the "
                     f"byte model does not account for")


def _check_compensation_ratio(target, art) -> Iterator[Violation]:
    from repro.core import ecm

    exp = _expectation(art)
    block = ecm.TPUKernelBlock(
        name=f"{art.scheme}-measured", elems=_N1, streams=exp.streams,
        flops_per_elem=int(round(art.adds + art.muls)), useful_flops=2,
        elem_bytes=exp.elem_bytes)
    res = ecm.ecm_tpu(ecm.TPU_V5E, block)
    naive = ecm.ecm_tpu(ecm.TPU_V5E, dataclasses.replace(
        block, name="naive-measured", flops_per_elem=2))
    ratio = res.t_db_cy / naive.t_db_cy
    if res.bound != "bandwidth" or ratio > 1.0 + _RATIO_TOL:
        yield _v(target, "cost-compensation-ratio",
                 f"at the MEASURED mix ({art.adds:g} adds + {art.muls:g} "
                 f"muls/elem) the {art.scheme} kernel is {res.bound}-bound "
                 f"with T_ECM {ratio:.2f}x naive — compensation is no "
                 f"longer hidden behind the memory stream")


def _check_ecm_tables_derived(target, art) -> Iterator[Violation]:
    from repro.core import ecm

    table = ecm.tpu_block_for_scheme(art.scheme,
                                     compute_dtype=art.compute_dtype)
    measured = int(round(art.adds + art.muls))
    if table.flops_per_elem != measured:
        yield _v(target, "cost-ecm-tables-derived",
                 f"ecm.tpu_block_for_scheme({art.scheme!r}) models "
                 f"{table.flops_per_elem} flops/elem but the traced body "
                 f"executes {measured} — the ECM table has drifted from "
                 f"the kernel")
    want_bytes = ecm.elem_bytes_for_dtype(art.compute_dtype)
    if table.elem_bytes != want_bytes:
        yield _v(target, "cost-ecm-tables-derived",
                 f"ecm.tpu_block_for_scheme({art.scheme!r}) models "
                 f"{table.elem_bytes} B/elem but the resolved "
                 f"compute_dtype ({np.dtype(art.compute_dtype).name}) is "
                 f"{want_bytes} B")


for _rule in (
    CostRule(
        id="cost-instruction-mix",
        tags=("cost-dot", "cost-asum", "cost-matmul", "cost-flash"),
        checker=_check_instruction_mix,
        fix_hint="fix the kernel body or the scheme's declared "
                 "InstructionMix (traced_* overrides declare a raw count "
                 "that differs from the canonical accounting)",
        doc="the traced per-element FLOP mix of every kernel body matches "
            "the scheme's declared instruction_mix",
    ),
    CostRule(
        id="cost-memory-traffic",
        tags=("cost-dot", "cost-asum"),
        checker=_check_memory_traffic,
        fix_hint="the kernel must stream each input exactly once at the "
                 "resolved compute_dtype and emit fixed-size (s, c) grids",
        doc="traced bytes/element match the elem_bytes_for_dtype-derived "
            "expectation; the accumulator store is n-independent",
    ),
    CostRule(
        id="cost-no-hidden-copies",
        tags=("cost-dot", "cost-flash"),
        checker=_check_no_hidden_copies,
        fix_hint="keep scheme bodies layout-preserving in the accumulate "
                 "dtype (no transposes, no dtype round-trips)",
        doc="no transpose/convert opcode in the optimized HLO of the "
            "jitted scheme body",
    ),
    CostRule(
        id="cost-compensation-ratio",
        tags=("cost-dot",),
        checker=_check_compensation_ratio,
        fix_hint="keep the scheme's per-element flops under the "
                 "bandwidth hide-point (T_comp <= T_hbm on v5e), or "
                 "exempt with the documented accuracy-vs-cost trade",
        doc="at the measured mix the scheme stays bandwidth-bound — "
            "compensation costs ~nothing vs naive (the paper's claim)",
    ),
    CostRule(
        id="cost-ecm-tables-derived",
        tags=("cost-dot",),
        checker=_check_ecm_tables_derived,
        fix_hint="ecm.tpu_block_for_scheme must be reproducible from the "
                 "traced mix; deliberate canonical-vs-traced splits carry "
                 "a documented exemption",
        doc="every ECM table entry is reproducible from a traced "
            "instruction mix (flags model drift with measured counts)",
    ),
):
    register(_rule)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def audit(target_ids: Optional[Iterable[str]] = None,
          rule_ids: Optional[Iterable[str]] = None) -> LintReport:
    """Run cost rules over the cost targets -> a ``LintReport``.

    Shares the AST/trace layers' report type end to end: findings anchor
    ``target:0:0``, ``Target.exempt`` entries surface as ``Pragma``
    rows (``used`` marks whether they suppressed a live finding), and a
    target whose build fails becomes a ``cost-build-error`` violation.
    Cost targets are (re-)registered first, so schemes registered at
    runtime are audited without any wiring.
    """
    from repro.analysis import targets as _targets

    register_cost_targets()
    report = LintReport()
    rules = select(rule_ids)
    if target_ids is None:
        selected = [t for t in _targets.select(None) if "cost" in t.tags]
    else:
        selected = _targets.select(target_ids)
    for target in selected:
        applicable = [r for r in rules if r.applies_to(target)]
        if not applicable:
            continue
        report.files += 1
        try:
            art = target.build()
        except Exception as e:  # noqa: BLE001 — any build failure is a finding
            report.violations.append(Violation(
                rule="cost-build-error", path=target.id, line=0, col=0,
                message=f"cost target build failed: "
                        f"{type(e).__name__}: {e}",
                fix_hint="fix the cost-target build (a kernel that cannot "
                         "trace cannot be cost-audited)"))
            continue
        for rule in applicable:
            found = [dataclasses.replace(v, fix_hint=v.fix_hint
                                         or rule.fix_hint)
                     for v in rule.checker(target, art)]
            if rule.id in target.exempt:
                report.exemptions.append(Pragma(
                    rule=rule.id, reason=target.exempt[rule.id],
                    path=target.id, line=0, comment_line=0,
                    used=bool(found)))
                continue
            report.violations.extend(found)
    report.violations.sort(key=lambda v: (v.path, v.rule))
    return report
