"""Trace-level contract rules: audit jaxprs and HLO, not source text.

The AST linter (``repro.analysis.rules``) checks what the SOURCE says;
this module checks what XLA actually compiles — the same split as the
paper's method, where the ECM model is validated against the generated
instruction stream, not the C code. A *trace rule* runs over the jaxpr
(and, for HLO-tagged targets, the lowered/optimized HLO modules) of a
registered :mod:`repro.analysis.targets` entry and yields the same
``Violation`` objects the AST layer produces, anchored ``target:rule``
instead of ``file:line``.

Walking jaxprs: sub-jaxprs hide inside equation params — ``scan`` holds
a ClosedJaxpr under ``jaxpr``, ``cond`` a list under ``branches``,
``pjit``/``pallas_call``/``custom_vjp_call``/``custom_vmap_call`` their
own spellings. :func:`iter_eqns` ducks all of them (any param value with
``.eqns`` is a Jaxpr, with ``.jaxpr`` a ClosedJaxpr; lists/tuples are
scanned elementwise) and threads an equation-provenance path like
``"scan/pjit"`` into every finding.

Shipped rules (each is a compiled-truth clause of the engine contract;
``python -m repro.analysis --trace --list-rules`` is the live list):

=============================  ==========================================
trace-no-raw-psum              no float psum/psum_scatter primitive
                               anywhere in sharded entry-point traces —
                               catches dynamically constructed reductions
                               the AST rule structurally cannot
trace-barrier-pinned           the registered shared block body traces
                               with its optimization_barrier equations,
                               and its exact primitive sequence appears
                               contiguously in both the kernel and the
                               oracle trace
trace-decode-is-batched        the decode tick traces ONE batched decode
                               body of leading width max_slots, pinned
                               by optimization_barrier on its inputs and
                               outputs (the bitwise slot-placement
                               guarantee's mechanism), and no per-slot
                               scan
trace-accum-dtype              every float-carrying equation in kernel
                               traces uses the resolved
                               Policy.compute_dtype
trace-no-host-callback         no pure/io/debug callback primitives in
                               serving traces
trace-barrier-survives-fusion  opt-barrier ops reach the last HLO that
                               can carry them (XLA's
                               OptimizationBarrierExpander strips the op
                               at the very end of every pipeline) and the
                               compensation arithmetic they pin is not
                               algebraically folded post-fusion
trace-program-count            the prefill program family stays within
                               the O(#buckets) bound
=============================  ==========================================
"""

from __future__ import annotations

import dataclasses
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

TraceChecker = Callable[[Any, Any], Iterator[Violation]]


@dataclasses.dataclass(frozen=True)
class TraceRule:
    """One compiled-truth clause of the engine contract.

    id        exemption-addressable identifier (``Target.exempt`` key)
    tags      a rule runs on every target sharing at least one tag
    checker   generator over (target, artifact) yielding Violations
    fix_hint  one-line remediation appended to findings
    doc       one-line statement of the clause (--trace --list-rules)
    """

    id: str
    tags: Tuple[str, ...]
    checker: TraceChecker
    fix_hint: str
    doc: str

    def applies_to(self, target) -> bool:
        return bool(set(self.tags) & set(target.tags))


_REGISTRY: Dict[str, TraceRule] = {}


def register(rule: TraceRule, *, override: bool = False) -> TraceRule:
    """Add a trace rule (same registry contract as ``rules.register``)."""
    if not isinstance(rule, TraceRule):
        raise TypeError(f"expected TraceRule, got {type(rule)!r}")
    if rule.id in _REGISTRY and not override:
        raise ValueError(
            f"trace rule {rule.id!r} already registered "
            f"(pass override=True to replace)")
    _REGISTRY[rule.id] = rule
    return rule


def unregister(rule_id: str) -> None:
    """Remove a trace rule (tests / plugin teardown)."""
    _REGISTRY.pop(rule_id, None)


def names() -> Tuple[str, ...]:
    """Registered trace-rule ids, registration order."""
    return tuple(_REGISTRY)


def registered() -> Dict[str, TraceRule]:
    """Snapshot of the registry."""
    return dict(_REGISTRY)


def get(rule_id: str) -> TraceRule:
    """Fail-fast lookup with the registered menu."""
    try:
        return _REGISTRY[rule_id]
    except KeyError:
        raise ValueError(
            f"unknown trace rule {rule_id!r}; registered trace rules: "
            f"{sorted(_REGISTRY)}") from None


def select(rule_ids: Optional[Iterable[str]]) -> List[TraceRule]:
    """All trace rules, or a validated subset."""
    if rule_ids is None:
        return list(_REGISTRY.values())
    return [get(r) for r in rule_ids]


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _as_jaxpr(obj):
    return obj.jaxpr if hasattr(obj, "jaxpr") else obj


def _eqn_subjaxprs(eqn) -> Iterator[Any]:
    for val in eqn.params.values():
        items = val if isinstance(val, (list, tuple)) else (val,)
        for item in items:
            if hasattr(item, "eqns"):
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr


def iter_eqns(jaxpr, path: str = "") -> Iterator[Tuple[Any, str]]:
    """Pre-order walk over every equation, recursing through sub-jaxprs.

    Yields ``(eqn, provenance)`` where provenance is the slash-joined
    chain of enclosing higher-order primitives (e.g. ``"scan/pjit"``;
    empty string at top level) — the anchor every trace finding carries.
    """
    for eqn in _as_jaxpr(jaxpr).eqns:
        yield eqn, path
        sub_path = f"{path}/{eqn.primitive.name}" if path \
            else eqn.primitive.name
        for sub in _eqn_subjaxprs(eqn):
            yield from iter_eqns(sub, sub_path)


def primitive_seq(jaxpr) -> List[str]:
    """Flattened (pre-order, recursion inlined) primitive-name sequence —
    the representation the contiguous-containment checks compare."""
    return [eqn.primitive.name for eqn, _ in iter_eqns(jaxpr)]


def contains_subsequence(hay: List[str], needle: List[str]) -> bool:
    """True when ``needle`` appears as a CONTIGUOUS run inside ``hay``."""
    n = len(needle)
    if n == 0:
        return True
    return any(hay[i:i + n] == needle for i in range(len(hay) - n + 1))


def scan_lengths(jaxpr) -> List[int]:
    """Trip counts of every ``scan`` equation anywhere in the trace."""
    return [eqn.params["length"] for eqn, _ in iter_eqns(jaxpr)
            if eqn.primitive.name == "scan"]


def _float_avals(vars_) -> Iterator[Any]:
    for v in vars_:
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None and np.issubdtype(dt, np.floating):
            yield aval


def _v(target, rule: str, message: str) -> Violation:
    return Violation(rule=rule, path=target.id, line=0, col=0,
                     message=message)


# ---------------------------------------------------------------------------
# Built-in trace rules
# ---------------------------------------------------------------------------

# shard_map traces spell the cross-device sum ``psum_invariant``; pmap
# traces spell ``psum``, and ``lax.psum_scatter`` traces as
# ``reduce_scatter``. All are re-associable backend reductions — all are
# off-contract for float payloads.
_PSUM_PRIMS = frozenset(("psum", "psum_invariant", "reduce_scatter"))
# ``jax.debug.print`` traces as ``debug_print``, not ``debug_callback``.
_CALLBACK_PRIMS = frozenset(
    ("pure_callback", "io_callback", "debug_callback", "debug_print"))


def _check_no_raw_psum(target, art) -> Iterator[Violation]:
    if art.jaxpr is None:
        return
    for eqn, path in iter_eqns(art.jaxpr):
        if eqn.primitive.name in _PSUM_PRIMS \
                and any(True for _ in _float_avals(eqn.invars)):
            yield _v(target, "trace-no-raw-psum",
                     f"float {eqn.primitive.name} primitive in the traced "
                     f"program (at {path or 'top level'}) — the backend "
                     f"may re-associate its reduction order")


def _check_barrier_pinned(target, art) -> Iterator[Violation]:
    if art.body_jaxpr is None:
        return
    body = primitive_seq(art.body_jaxpr)
    n_bar = body.count("optimization_barrier")
    if n_bar == 0:
        yield _v(target, "trace-barrier-pinned",
                 "the registered shared block body traces with ZERO "
                 "optimization_barrier equations")
        return
    traces = [("kernel", art.jaxpr)]
    if art.oracle_jaxpr is not None:
        traces.append(("oracle", art.oracle_jaxpr))
    for label, tr in traces:
        if tr is None:
            continue
        seq = primitive_seq(tr)
        if seq.count("optimization_barrier") < n_bar:
            yield _v(target, "trace-barrier-pinned",
                     f"{label} trace retains "
                     f"{seq.count('optimization_barrier')} of the block "
                     f"body's {n_bar} optimization_barrier equations")
        elif not contains_subsequence(seq, body):
            yield _v(target, "trace-barrier-pinned",
                     f"{label} trace does not contain the shared block "
                     f"body's {len(body)}-primitive sequence contiguously "
                     f"— the body traced differently in context")


def _is_slot_pin(eqn, n: int) -> bool:
    """An ``optimization_barrier`` over slot-batched operands: every
    non-scalar operand has an axis of width ``n`` and at least one (a
    per-slot vector: tokens, positions, sampled tokens, logits) leads
    with it."""
    if eqn.primitive.name != "optimization_barrier":
        return False
    shapes = [tuple(v.aval.shape) for v in eqn.invars]
    return (all(n in sh for sh in shapes if sh)
            and any(sh[:1] == (n,) for sh in shapes))


def _is_slot_scan(eqn, n: int) -> bool:
    """A ``scan`` over the slot axis: its scanned operands are slot-major,
    i.e. they hold the per-slot request vectors, an integer one (tokens,
    positions, seeds) and a floating one (temperatures), each of shape
    exactly ``[n]``. The trip count alone never decides: a layer scan or
    a per-layer ``lax.map`` whose length equals ``n`` (olmo-1b: 16
    layers, 16 slots) scans weights, cache leaves or one index vector,
    so it does not match."""
    if eqn.primitive.name != "scan" or eqn.params["length"] != n:
        return False
    first = eqn.params["num_consts"] + eqn.params["num_carry"]
    vecs = [v.aval.dtype for v in eqn.invars[first:]
            if tuple(v.aval.shape) == (n,)]
    return (any(np.issubdtype(d, np.integer) for d in vecs)
            and any(np.issubdtype(d, np.floating) for d in vecs))


def _check_decode_is_batched(target, art) -> Iterator[Violation]:
    if art.jaxpr is None or art.slot_width is None:
        return
    n = art.slot_width
    if any(_is_slot_scan(eqn, n) for eqn, _ in iter_eqns(art.jaxpr)):
        yield _v(target, "trace-decode-is-batched",
                 f"decode tick holds a lax.scan of length {n} over "
                 f"per-slot operands: a per-slot scan of a batch-1 body, "
                 f"which reads every weight once per slot")
    pins = [path for eqn, path in iter_eqns(art.jaxpr)
            if _is_slot_pin(eqn, n)]
    if len(pins) != 2 or any("scan" in p.split("/") for p in pins):
        yield _v(target, "trace-decode-is-batched",
                 f"decode tick traces {len(pins)} optimization_barrier "
                 f"pins over width-{n} slot operands outside any scan, "
                 f"not the 2 (entry and exit) of ONE pinned batched "
                 f"decode body — an unpinned body leaves per-row "
                 f"rounding to XLA's fusion of the data movement around "
                 f"it")


def _check_accum_dtype(target, art) -> Iterator[Violation]:
    if art.jaxpr is None or art.compute_dtype is None:
        return
    expected = np.dtype(art.compute_dtype)
    offending: Dict[Tuple[str, str, str], int] = {}
    for eqn, path in iter_eqns(art.jaxpr):
        for aval in _float_avals(eqn.outvars):
            if np.dtype(aval.dtype) != expected:
                key = (eqn.primitive.name, str(np.dtype(aval.dtype)), path)
                offending[key] = offending.get(key, 0) + 1
    for (prim, dt, path), count in sorted(offending.items()):
        yield _v(target, "trace-accum-dtype",
                 f"{count} {prim} equation(s) at {path or 'top level'} "
                 f"carry float dtype {dt}; the resolved "
                 f"Policy.compute_dtype is {expected}")


def _check_no_host_callback(target, art) -> Iterator[Violation]:
    if art.jaxpr is None:
        return
    for eqn, path in iter_eqns(art.jaxpr):
        if eqn.primitive.name in _CALLBACK_PRIMS:
            yield _v(target, "trace-no-host-callback",
                     f"host callback ({eqn.primitive.name}) in a serving "
                     f"trace (at {path or 'top level'}) — a host "
                     f"round-trip on every execution")


def _check_barrier_survives_fusion(target, art) -> Iterator[Violation]:
    if art.hlo is None:
        return
    from repro.analysis.hlo import parse_hlo

    pre_text, opt_text = art.hlo()
    pre = parse_hlo(pre_text).opcode_counts()
    if pre.get("opt-barrier", 0) == 0:
        yield _v(target, "trace-barrier-survives-fusion",
                 "no opt-barrier op in the lowered HLO module — the "
                 "barriers were lost before XLA's optimization pipeline "
                 "even started")
        return
    opt = parse_hlo(opt_text).opcode_counts()
    pre_sub, opt_sub = pre.get("subtract", 0), opt.get("subtract", 0)
    if opt_sub < pre_sub:
        yield _v(target, "trace-barrier-survives-fusion",
                 f"post-fusion HLO retains {opt_sub} of {pre_sub} "
                 f"subtract ops — XLA algebraically folded compensation "
                 f"arithmetic the barriers were meant to pin")


def _check_program_count(target, art) -> Iterator[Violation]:
    if art.program_keys is None or art.program_bound is None:
        return
    n = len(set(art.program_keys))
    if n > art.program_bound:
        yield _v(target, "trace-program-count",
                 f"prefill program family has {n} (width, runs_begin) "
                 f"keys, exceeding the O(#buckets) bound of "
                 f"{art.program_bound} — per-prompt-length recompiles "
                 f"are back")


for _rule in (
    TraceRule(
        id="trace-no-raw-psum",
        tags=("sharded",),
        checker=_check_no_raw_psum,
        fix_hint="all-gather the (s, c) grids and fold through "
                 "engine.merge_accumulator_grids (distributed.collectives)",
        doc="no float psum primitive anywhere in sharded entry-point "
            "traces — catches dynamically constructed reductions the AST "
            "rule cannot see",
    ),
    TraceRule(
        id="trace-barrier-pinned",
        tags=("shared-block",),
        checker=_check_barrier_pinned,
        fix_hint="route the computation through the registered shared "
                 "body (flash_block_update / prefill_chunk_body) and keep "
                 "its lax.optimization_barrier pins",
        doc="the shared block body keeps its barriers and traces to the "
            "identical contiguous primitive sequence in kernel and oracle",
    ),
    TraceRule(
        id="trace-decode-is-batched",
        tags=("decode",),
        checker=_check_decode_is_batched,
        fix_hint="run decode_one through the engine's decode_slots: one "
                 "vmap over the max_slots axis, pinned on its inputs and "
                 "outputs",
        doc="the decode tick traces exactly ONE barrier-pinned batched "
            "decode body of leading width max_slots, and no per-slot "
            "scan",
    ),
    TraceRule(
        id="trace-accum-dtype",
        tags=("kernel",),
        checker=_check_accum_dtype,
        fix_hint="thread the engine's compute_dtype through (Policy."
                 "compute_dtype is the accumulate-dtype authority)",
        doc="every float-carrying equation in kernel traces uses the "
            "resolved Policy.compute_dtype",
    ),
    TraceRule(
        id="trace-no-host-callback",
        tags=("serve",),
        checker=_check_no_host_callback,
        fix_hint="drop jax.debug.print / callbacks from serving bodies; "
                 "emit at the engine's host-side points instead",
        doc="no pure_callback/io_callback/debug_callback/debug_print "
            "primitives in serving traces",
    ),
    TraceRule(
        id="trace-barrier-survives-fusion",
        tags=("hlo",),
        checker=_check_barrier_survives_fusion,
        fix_hint="keep the lax.optimization_barrier pins on the "
                 "fusion-sensitive ops (see flash_block_update)",
        doc="opt-barrier ops reach the lowered HLO and the compensation "
            "arithmetic they pin survives XLA's fusion/simplification",
    ),
    TraceRule(
        id="trace-program-count",
        tags=("program-count",),
        checker=_check_program_count,
        fix_hint="set a finite EngineConfig.prefill_chunk so tail chunks "
                 "bucket to powers of two",
        doc="the compiled prefill program family stays within the "
            "O(#buckets) bound (serve.engine.prefill_program_bound)",
    ),
):
    register(_rule)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def audit(target_ids: Optional[Iterable[str]] = None,
          rule_ids: Optional[Iterable[str]] = None) -> LintReport:
    """Run trace rules over registered targets -> a ``LintReport``.

    Shares the AST layer's report type: findings anchor ``target:0:0``
    (the target id is the path), per-target exemptions surface as
    ``Pragma`` entries (``used`` marks whether they suppressed a live
    finding — a stale exemption warns exactly like a stale pragma), and a
    target whose build/trace raises becomes a ``trace-build-error``
    violation rather than aborting the audit.
    """
    from repro.analysis import targets as _targets

    report = LintReport()
    rules = select(rule_ids)
    for target in _targets.select(target_ids):
        # a target no selected rule applies to is not built at all —
        # cost-level targets (tags "cost-*", registered into the shared
        # registry by analysis.costmodel) share this registry but only
        # trace under the cost audit.
        applicable = [r for r in rules if r.applies_to(target)]
        if not applicable:
            continue
        report.files += 1
        try:
            art = target.build()
        except Exception as e:  # noqa: BLE001 — any build failure is a finding
            report.violations.append(Violation(
                rule="trace-build-error", path=target.id, line=0, col=0,
                message=f"target build/trace failed: "
                        f"{type(e).__name__}: {e}",
                fix_hint="fix the registered build in analysis/targets.py "
                         "(a target that cannot trace cannot be audited)"))
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
