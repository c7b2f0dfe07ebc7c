"""The contract-rule registry and the built-in rules.

Mirrors ``repro.kernels.schemes``: a rule is a frozen dataclass bundling
everything one contract clause needs (id, scope globs, checker, fix-hint,
doc line), ``register()`` adds more at runtime, and every consumer (the
CLI, the CI gate, pragma validation) resolves rules through the registry
— no parallel hardcoded rule list anywhere.

Each rule encodes one clause of the engine contract (ROADMAP.md,
"Engine contract" / "Contract rules (machine-checked)"):

==========================  =================================================
no-raw-psum                 cross-device reductions fold (s, c) grids through
                            the deterministic two-sum tree, never lax.psum
no-legacy-mode-kwarg        the mode= kwarg was removed in PR 4 (AST-accurate
                            successor to the old ci.sh grep: the .at[...]
                            scatter ``mode="drop"`` resolves as a scatter and
                            needs no special-case exclusion)
no-uncompensated-reduction  jnp.sum/dot/matmul/einsum/mean/cumsum/prod/
                            trace/average/linalg.norm + lax.dot_general
                            in hot-path packages route through ops.* or
                            carry an annotated exemption
no-literal-interpret        interpret=True/False literals bypass
                            engine.resolve_interpret, the single authority
no-hardcoded-accum-dtype    kernel bodies/oracles accumulate in the resolved
                            Policy.compute_dtype, not a hardcoded jnp dtype
no-host-sync-in-trace       .item()/.block_until_ready() anywhere in scope,
                            and float()/int()/np.asarray() inside decode/
                            prefill bodies, force a device sync (and
                            int/float of a tracer is a trace error)
no-raw-prngkey              PRNG keys are created at boundary modules only
                            (train/launch/config); everything else fold_ins
                            from a key it was handed
==========================  =================================================
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Tuple

from repro.analysis.core import FileContext, Violation

Checker = Callable[[FileContext], Iterator[Violation]]


@dataclasses.dataclass(frozen=True)
class Rule:
    """One machine-checked clause of the engine contract.

    id        pragma-addressable identifier (``allow-<id>(reason)``)
    scope     fnmatch globs over package-relative paths the rule runs on
    checker   generator over an annotated AST yielding Violations
    fix_hint  one-line remediation appended to findings
    doc       one-line statement of the contract clause (--list-rules)
    exclude   globs carved OUT of scope (e.g. the resolve_interpret
              authority module for no-literal-interpret)
    """

    id: str
    scope: Tuple[str, ...]
    checker: Checker
    fix_hint: str
    doc: str
    exclude: Tuple[str, ...] = ()

    def applies_to(self, relpath: str) -> bool:
        if any(fnmatch.fnmatch(relpath, g) for g in self.exclude):
            return False
        return any(fnmatch.fnmatch(relpath, g) for g in self.scope)


_REGISTRY: Dict[str, Rule] = {}


def register(rule: Rule, *, override: bool = False) -> Rule:
    """Add a rule (returns it, for decorator-ish use). Same contract as
    ``schemes.register``: duplicate ids fail fast unless override=True."""
    if not isinstance(rule, Rule):
        raise TypeError(f"expected Rule, got {type(rule)!r}")
    if rule.id in _REGISTRY and not override:
        raise ValueError(
            f"rule {rule.id!r} already registered "
            f"(pass override=True to replace)")
    _REGISTRY[rule.id] = rule
    return rule


def unregister(rule_id: str) -> None:
    """Remove a rule (tests / plugin teardown)."""
    _REGISTRY.pop(rule_id, None)


def names() -> Tuple[str, ...]:
    """Registered rule ids, registration order."""
    return tuple(_REGISTRY)


def registered() -> Dict[str, Rule]:
    """Snapshot of the registry (copy — safe to iterate while registering)."""
    return dict(_REGISTRY)


def get(rule_id: str) -> Rule:
    """Fail-fast lookup with the registered menu (the schemes.get shape)."""
    try:
        return _REGISTRY[rule_id]
    except KeyError:
        raise ValueError(
            f"unknown contract rule {rule_id!r}; registered rules: "
            f"{sorted(_REGISTRY)}") from None


def select(rule_ids: Optional[Iterable[str]]) -> List[Rule]:
    """All rules, or a validated subset (unknown ids fail fast)."""
    if rule_ids is None:
        return list(_REGISTRY.values())
    return [get(r) for r in rule_ids]


# ---------------------------------------------------------------------------
# Built-in rules
# ---------------------------------------------------------------------------

#: packages whose reductions are serving/training hot paths — the scope
#: of the core no-uncompensated-reduction clause.
HOT_SCOPE = ("kernels/*", "serve/*", "models/*", "optim/*", "distributed/*")

#: the jnp reduction entry points the contract covers (matmul-shaped
#: contractions, full/axis sums, and the sum-derived reductions mean/
#: cumsum/average, the diagonal sum trace, and the sequential-rounding
#: product prod); lax.dot_general and jnp.linalg.norm are checked too.
JNP_REDUCTIONS = ("sum", "dot", "matmul", "einsum", "vdot", "tensordot",
                  "inner", "mean", "cumsum", "prod", "trace", "average")

_JNP_REDUCTION_NAMES = frozenset(
    f"jax.numpy.{r}" for r in JNP_REDUCTIONS) | frozenset(
    ("jax.numpy.linalg.norm",))
_DOT_GENERAL_NAMES = frozenset(("jax.lax.dot_general",))
_PSUM_NAMES = frozenset(
    ("jax.lax.psum", "jax.lax.pmean", "jax.lax.psum_scatter"))
_KEY_NAMES = frozenset(("jax.random.key", "jax.random.PRNGKey"))


def _check_uncompensated_reduction(ctx: FileContext) -> Iterator[Violation]:
    for call in ctx.calls():
        name = ctx.resolve(call.func)
        if name in _JNP_REDUCTION_NAMES:
            short = name.split("jax.numpy.", 1)[1]
            yield ctx.violation(
                call, "no-uncompensated-reduction",
                f"raw jnp.{short} reduction off the compensated engine")
        elif name in _DOT_GENERAL_NAMES:
            yield ctx.violation(
                call, "no-uncompensated-reduction",
                "raw lax.dot_general contraction off the compensated engine")


def _check_raw_psum(ctx: FileContext) -> Iterator[Violation]:
    for call in ctx.calls():
        name = ctx.resolve(call.func)
        if name in _PSUM_NAMES:
            yield ctx.violation(
                call, "no-raw-psum",
                f"{name.rsplit('.', 1)[1]} is an order-unspecified "
                f"cross-device float reduction")


def _is_at_scatter(func: ast.AST) -> bool:
    """True for ``x.at[idx].set/add/...(..., mode=...)`` — the jnp
    scatter family, whose ``mode=`` kwarg is jnp API, not the removed
    compensation-mode alias."""
    return (isinstance(func, ast.Attribute)
            and isinstance(func.value, ast.Subscript)
            and isinstance(func.value.value, ast.Attribute)
            and func.value.value.attr == "at")


def _check_legacy_mode(ctx: FileContext) -> Iterator[Violation]:
    for call in ctx.calls():
        for kw in call.keywords:
            if kw.arg == "mode" and not _is_at_scatter(call.func):
                yield ctx.violation(
                    call, "no-legacy-mode-kwarg",
                    "mode= kwarg (the legacy compensation-scheme alias "
                    "was removed in PR 4)")
    for fn in ctx.functions():
        args = fn.args
        all_args = (*args.posonlyargs, *args.args, *args.kwonlyargs)
        for a in all_args:
            if a.arg == "mode":
                yield ctx.violation(
                    fn, "no-legacy-mode-kwarg",
                    f"function {fn.name!r} declares a 'mode' parameter")


def _check_literal_interpret(ctx: FileContext) -> Iterator[Violation]:
    for call in ctx.calls():
        for kw in call.keywords:
            if kw.arg == "interpret" and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, bool):
                yield ctx.violation(
                    call, "no-literal-interpret",
                    f"interpret={kw.value.value} literal pins the backend "
                    f"mode at the call site")


_HARDCODED_DTYPES = frozenset(
    ("jax.numpy.float32", "jax.numpy.float64", "jax.numpy.bfloat16"))
_DTYPE_LITERALS = frozenset(("float32", "float64", "bfloat16"))


def _check_hardcoded_accum_dtype(ctx: FileContext) -> Iterator[Violation]:
    for node in ctx.walk():
        if isinstance(node, ast.Attribute):
            name = ctx.resolve(node)
            if name in _HARDCODED_DTYPES and ctx.in_function_body(node) \
                    and not ctx.in_default_arg(node):
                # skip the inner Attribute of a longer resolved chain
                parent = ctx.parent(node)
                if isinstance(parent, ast.Attribute):
                    continue
                yield ctx.violation(
                    node, "no-hardcoded-accum-dtype",
                    f"hardcoded {name.rsplit('.', 1)[1]} accumulate dtype "
                    f"in a kernel body")
        elif isinstance(node, ast.Call) \
                and ctx.resolve(node.func) == "jax.numpy.dtype" \
                and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and node.args[0].value in _DTYPE_LITERALS \
                and ctx.in_function_body(node) \
                and not ctx.in_default_arg(node):
            yield ctx.violation(
                node, "no-hardcoded-accum-dtype",
                f"hardcoded jnp.dtype({node.args[0].value!r}) in a kernel "
                f"body")


_TRACE_BODY_MARKERS = ("decode", "prefill")


def _in_trace_body(ctx: FileContext, node: ast.AST) -> bool:
    return any(m in fn for fn in ctx.enclosing_functions(node)
               for m in _TRACE_BODY_MARKERS)


def _check_host_sync(ctx: FileContext) -> Iterator[Violation]:
    for call in ctx.calls():
        func = call.func
        if isinstance(func, ast.Attribute) and func.attr == "item":
            yield ctx.violation(
                call, "no-host-sync-in-trace",
                ".item() forces a device sync (and fails on tracers)")
        elif isinstance(func, ast.Attribute) \
                and func.attr == "block_until_ready":
            yield ctx.violation(
                call, "no-host-sync-in-trace",
                ".block_until_ready() forces a device sync (and fails "
                "on tracers)")
        elif isinstance(func, ast.Name) and func.id in ("float", "int"):
            if call.args and not isinstance(call.args[0], ast.Constant):
                if _in_trace_body(ctx, call):
                    yield ctx.violation(
                        call, "no-host-sync-in-trace",
                        f"{func.id}() on a non-literal inside a "
                        f"decode/prefill body syncs (or breaks) the trace")
        elif ctx.resolve(func) == "numpy.asarray" \
                and _in_trace_body(ctx, call):
            yield ctx.violation(
                call, "no-host-sync-in-trace",
                "np.asarray() inside a decode/prefill body pulls the "
                "value to host — a device sync per trace entry")


def _check_raw_prngkey(ctx: FileContext) -> Iterator[Violation]:
    for call in ctx.calls():
        name = ctx.resolve(call.func)
        if name in _KEY_NAMES:
            yield ctx.violation(
                call, "no-raw-prngkey",
                "fresh PRNG key outside a boundary module — streams must "
                "fold_in from an owned key")


for _rule in (
    Rule(
        id="no-raw-psum",
        scope=HOT_SCOPE + ("train/*", "core/*"),
        checker=_check_raw_psum,
        fix_hint="all-gather the (s, c) grids and fold through "
                 "engine.merge_accumulator_grids (see "
                 "distributed.collectives)",
        doc="cross-device reductions use the deterministic two-sum merge "
            "tree, never an order-unspecified psum/pmean",
    ),
    Rule(
        id="no-legacy-mode-kwarg",
        scope=("*",),
        checker=_check_legacy_mode,
        fix_hint="write scheme=/Policy (migration note in "
                 "repro.kernels.schemes)",
        doc="the legacy compensation mode= kwarg stays removed "
            "(jnp .at[...] scatter mode= resolves as a scatter and is "
            "allowed)",
    ),
    Rule(
        id="no-uncompensated-reduction",
        scope=HOT_SCOPE,
        checker=_check_uncompensated_reduction,
        fix_hint="route through ops.dot/asum/matmul (or annotate: "
                 "# contract: allow-no-uncompensated-reduction(reason))",
        doc="hot-path reductions run on the engine's (s, c) accumulators "
            "or carry an annotated exemption",
    ),
    Rule(
        id="no-literal-interpret",
        scope=("*",),
        exclude=("kernels/engine.py",),
        checker=_check_literal_interpret,
        fix_hint="pass interpret=None (resolved by "
                 "engine.resolve_interpret) or thread a Policy",
        doc="interpret resolves through engine.resolve_interpret only — "
            "no True/False literals at call sites",
    ),
    Rule(
        id="no-hardcoded-accum-dtype",
        scope=("kernels/kahan_dot.py", "kernels/kahan_sum.py",
               "kernels/kahan_matmul.py", "kernels/flash_attention.py",
               "kernels/ref.py", "kernels/engine.py"),
        checker=_check_hardcoded_accum_dtype,
        fix_hint="use the compute_dtype argument the engine threads in "
                 "(Policy.compute_dtype is the accumulate-dtype authority)",
        doc="kernel bodies and oracles accumulate in the resolved "
            "Policy.compute_dtype (parameter defaults are fine)",
    ),
    Rule(
        id="no-host-sync-in-trace",
        scope=("models/*", "serve/*"),
        checker=_check_host_sync,
        fix_hint="keep the value on device (jnp ops / lax.select); sync "
                 "only at the engine's host-side emit points",
        doc="decode/prefill bodies never .item()/.block_until_ready()/"
            "float()/int()/np.asarray() traced values — recompile + sync "
            "hazard",
    ),
    Rule(
        id="no-raw-prngkey",
        scope=("models/*", "kernels/*", "optim/*", "distributed/*",
               "serve/*", "core/*", "data/*", "ft/*",
               "checkpoint/*"),
        checker=_check_raw_prngkey,
        fix_hint="fold_in from a key handed down by the boundary "
                 "(train/launch/engine config seed)",
        doc="PRNG keys are created at boundary modules only; per-request "
            "streams fold_in from per-request state",
    ),
):
    register(_rule)
