"""Trace-level auditor tests (repro.analysis.trace / .targets).

Every trace rule fires on a bad artifact — including ones the AST layer
structurally CANNOT see (a dynamically constructed psum, a vmap'd decode
tick) — and stays silent on the registered good target; the jaxpr
walkers; both registries roundtrip; target exemptions audit exactly like
source pragmas; build failures become findings; the shared JSON schema;
the --trace CLI exit-code contract; and the tier-1 repo-wide trace
self-audit (every registered target clean under every rule)."""

import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import lint_source, targets, trace
from repro.analysis.__main__ import main as cli_main
from repro.analysis.hlo import parse_hlo
from repro.analysis.report import render_json


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _toy(tags, build=None, exempt=None):
    return targets.Target(
        id="toy.fixture", build=build or (lambda: None),
        tags=tuple(tags), doc="test fixture", exempt=exempt or {})


def _fired(rule_id, tags, art):
    return list(trace.get(rule_id).checker(_toy(tags), art))


# ---------------------------------------------------------------------------
# jaxpr walkers
# ---------------------------------------------------------------------------

def test_iter_eqns_provenance_and_scan_lengths():
    def f(x):
        def body(c, t):
            return c + t, c
        out, _ = jax.lax.scan(body, x, jnp.arange(3, dtype=jnp.float32))
        return out

    jaxpr = jax.make_jaxpr(f)(_sds(()))
    paths = {p for _, p in trace.iter_eqns(jaxpr)}
    assert "" in paths            # top-level equations
    assert "scan" in paths        # the body's equations carry provenance
    assert trace.scan_lengths(jaxpr) == [3]


def test_contains_subsequence_is_contiguous():
    assert trace.contains_subsequence(["a", "b", "c", "d"], ["b", "c"])
    assert trace.contains_subsequence(["a"], [])
    assert not trace.contains_subsequence(["a", "b", "c"], ["a", "c"])
    assert not trace.contains_subsequence(["b", "a"], ["a", "b"])


# ---------------------------------------------------------------------------
# trace-no-raw-psum: catches what the AST rule cannot
# ---------------------------------------------------------------------------

def test_no_raw_psum_fires_on_dynamically_constructed_psum():
    # the AST rule resolves names structurally — a psum assembled at
    # runtime never matches it...
    src = ("import jax\n"
           "def reduce_all(mesh, x):\n"
           "    op = getattr(jax.lax, 'p' + 'sum')\n"
           "    f = jax.shard_map(lambda s: op(s, 'data'), mesh=mesh,\n"
           "                      in_specs=None, out_specs=None)\n"
           "    return f(x)\n")
    ast_report = lint_source(src, "distributed/x.py",
                             rule_ids=["no-raw-psum"])
    assert ast_report.violations == []

    # ...but the primitive is right there in the traced program.
    op = getattr(jax.lax, "p" + "sum")
    f = jax.shard_map(lambda s: op(s, "data"), mesh=targets._mesh(),
                      in_specs=P("data"), out_specs=P())
    art = targets.TraceArtifact(jaxpr=jax.make_jaxpr(f)(_sds((4,))))
    found = _fired("trace-no-raw-psum", ("sharded",), art)
    assert found, "dynamic psum escaped the trace rule"
    assert all(v.rule == "trace-no-raw-psum" and v.path == "toy.fixture"
               for v in found)


def test_no_raw_psum_silent_on_registered_collectives():
    report = trace.audit(
        target_ids=["collectives.sharded_asum",
                    "collectives.deterministic_mean"],
        rule_ids=["trace-no-raw-psum"])
    assert report.violations == [], [v.format() for v in report.violations]


# ---------------------------------------------------------------------------
# trace-barrier-pinned
# ---------------------------------------------------------------------------

def _barrier_body(x):
    y = jax.lax.optimization_barrier(x * 2.0)
    return y - x


def test_barrier_pinned_fires_when_kernel_drops_the_barriers():
    x = _sds((4,))
    body = jax.make_jaxpr(_barrier_body)(x)
    kernel = jax.make_jaxpr(lambda x: x * 2.0 - x)(x)  # barriers gone
    art = targets.TraceArtifact(jaxpr=kernel, body_jaxpr=body)
    found = _fired("trace-barrier-pinned", ("shared-block",), art)
    assert found and "optimization_barrier" in found[0].message


def test_barrier_pinned_fires_when_body_traces_differently():
    x = _sds((4,))
    body = jax.make_jaxpr(_barrier_body)(x)
    # same barrier COUNT, different primitive sequence -> not contained
    kernel = jax.make_jaxpr(
        lambda x: jax.lax.optimization_barrier(x + 1.0) - x)(x)
    found = _fired("trace-barrier-pinned", ("shared-block",),
                   targets.TraceArtifact(jaxpr=kernel, body_jaxpr=body))
    assert found and "contiguously" in found[0].message


def test_barrier_pinned_fires_on_barrierless_body():
    x = _sds((4,))
    body = jax.make_jaxpr(lambda x: x * 2.0)(x)
    found = _fired("trace-barrier-pinned", ("shared-block",),
                   targets.TraceArtifact(jaxpr=body, body_jaxpr=body))
    assert found and "ZERO" in found[0].message


def test_barrier_pinned_silent_when_body_is_embedded():
    x = _sds((4,))
    body = jax.make_jaxpr(_barrier_body)(x)
    kernel = jax.make_jaxpr(lambda x: _barrier_body(x) * 3.0)(x)
    oracle = jax.make_jaxpr(lambda x: 1.0 + _barrier_body(x))(x)
    art = targets.TraceArtifact(jaxpr=kernel, oracle_jaxpr=oracle,
                                body_jaxpr=body)
    assert _fired("trace-barrier-pinned", ("shared-block",), art) == []


# ---------------------------------------------------------------------------
# trace-decode-is-batched: one pinned batched body, no per-slot scan
# ---------------------------------------------------------------------------

def _scan_tick_trace():
    """The per-slot scan tick (the test oracle) over the audit's engine."""
    from scan_tick_oracle import scan_tick

    eng = targets._tiny_serve()
    fn, args = eng.trace_tick()
    return jax.make_jaxpr(scan_tick(eng))(*args), eng.ec.max_slots


def _unpinned_tick_trace():
    """A batched tick with no pins: one vmapped row body, no barrier."""
    rows, toks = _sds((3, 8)), _sds((3,), jnp.int32)
    body = jax.vmap(lambda r, t: (r * 2.0, t + 1))
    return jax.make_jaxpr(body)(rows, toks), 3


@pytest.mark.parametrize("tick, message", [
    (_scan_tick_trace, "lax.scan of length"),
    (_unpinned_tick_trace, "0 optimization_barrier pins"),
])
def test_decode_is_batched_fires_on_scan_or_unpinned_tick(tick, message):
    jaxpr, width = tick()
    art = targets.TraceArtifact(jaxpr=jaxpr, slot_width=width)
    found = _fired("trace-decode-is-batched", ("decode",), art)
    assert found and any(message in f.message for f in found), (
        [f.message for f in found])


def test_decode_is_batched_silent_on_layer_scan_of_slot_width():
    """A pinned batched tick whose layer scan and per-layer ``lax.map``
    have as many steps as there are slots (olmo-1b: 16 and 16) is not a
    per-slot scan."""
    n = 4
    weights = _sds((n, 8))

    def row(r, t, w):
        def layer(x, w_l):
            return x * w_l, None

        x, _ = jax.lax.scan(layer, r, w)
        x = x + jax.lax.map(lambda i: i.astype(jnp.float32),
                            jnp.arange(n)).sum()
        return x, t + 1

    def tick(rows, toks, w):
        rows, toks = jax.lax.optimization_barrier((rows, toks))
        out = jax.vmap(row, in_axes=(0, 0, None))(rows, toks, w)
        return jax.lax.optimization_barrier(out)

    jaxpr = jax.make_jaxpr(tick)(_sds((n, 8)), _sds((n,), jnp.int32),
                                 weights)
    art = targets.TraceArtifact(jaxpr=jaxpr, slot_width=n)
    assert _fired("trace-decode-is-batched", ("decode",), art) == []


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_decode_is_batched_when_layers_equal_slots(layout):
    """An engine with as many layers as slots (olmo-1b: 16 and 16): its
    layer scan, and in the paged layout the per-layer gather, run
    ``max_slots`` steps, and the rule stays silent on its tick. The
    per-slot scan oracle over the same dense engine still fires."""
    from repro.serve import EngineConfig, InferenceEngine
    from scan_tick_oracle import scan_tick

    n = 4
    eng = InferenceEngine(
        targets.tiny_arch().replace(n_layers=n),
        EngineConfig(max_slots=n, max_len=16, prefill_chunk=4,
                     kv_layout=layout, page_size=4))
    assert eng.kv_layout == layout
    fn, args = eng.trace_tick()
    jaxpr = jax.make_jaxpr(fn)(*args)
    assert n in trace.scan_lengths(jaxpr)
    art = targets.TraceArtifact(jaxpr=jaxpr, slot_width=n)
    assert _fired("trace-decode-is-batched", ("decode",), art) == []
    if layout == "dense":
        oracle = targets.TraceArtifact(
            jaxpr=jax.make_jaxpr(scan_tick(eng))(*args), slot_width=n)
        found = _fired("trace-decode-is-batched", ("decode",), oracle)
        assert any("lax.scan of length" in f.message for f in found)


def test_decode_is_batched_silent_on_registered_tick():
    report = trace.audit(
        target_ids=["serve.decode_tick", "serve.paged_decode_tick"],
        rule_ids=["trace-decode-is-batched"])
    assert report.violations == [], [v.format() for v in report.violations]


# ---------------------------------------------------------------------------
# trace-accum-dtype
# ---------------------------------------------------------------------------

def test_accum_dtype_fires_on_half_precision_carry():
    jaxpr = jax.make_jaxpr(
        lambda x: jnp.sum(x.astype(jnp.float16)))(_sds((8,)))
    art = targets.TraceArtifact(jaxpr=jaxpr, compute_dtype="float32")
    found = _fired("trace-accum-dtype", ("kernel",), art)
    assert found and "float16" in found[0].message


def test_accum_dtype_silent_on_registered_ops():
    report = trace.audit(target_ids=["ops.dot", "ops.asum"],
                         rule_ids=["trace-accum-dtype"])
    assert report.violations == [], [v.format() for v in report.violations]


# ---------------------------------------------------------------------------
# trace-no-host-callback
# ---------------------------------------------------------------------------

def test_no_host_callback_fires_on_debug_print():
    def tick(x):
        jax.debug.print("x={x}", x=x)
        return x + 1.0

    art = targets.TraceArtifact(jaxpr=jax.make_jaxpr(tick)(_sds((2,))))
    found = _fired("trace-no-host-callback", ("serve",), art)
    assert found and "callback" in found[0].message


def test_no_host_callback_silent_on_registered_tick():
    report = trace.audit(target_ids=["serve.decode_tick"],
                         rule_ids=["trace-no-host-callback"])
    assert report.violations == [], [v.format() for v in report.violations]


# ---------------------------------------------------------------------------
# trace-barrier-survives-fusion (synthetic HLO — the real flash module is
# covered by the repo-wide self-audit below)
# ---------------------------------------------------------------------------

_PRE_HLO = """\
ENTRY main.1 {
  %p0 = f32[] parameter(0)
  %bar = f32[] opt-barrier(%p0)
  %s1 = f32[] subtract(%bar, %p0)
  ROOT %s2 = f32[] subtract(%s1, %p0)
}
"""

# XLA's OptimizationBarrierExpander strips opt-barrier at the end of
# every pipeline — an optimized module WITHOUT the op but WITH the
# compensation subtracts is the healthy outcome.
_OPT_KEPT = """\
%main.1 (p0: f32[]) -> f32[] {
  %p0 = f32[] parameter(0)
  %s1 = f32[] subtract(%p0, %p0)
  ROOT %s2 = f32[] subtract(%s1, %p0)
}
"""

_OPT_FOLDED = """\
%main.1 (p0: f32[]) -> f32[] {
  %p0 = f32[] parameter(0)
  ROOT %s1 = f32[] subtract(%p0, %p0)
}
"""

_PRE_NO_BARRIER = "\n".join(
    l for l in _PRE_HLO.splitlines() if "opt-barrier" not in l) + "\n"


def _hlo_art(pre, opt):
    return targets.TraceArtifact(hlo=lambda: (pre, opt))


def test_barrier_fusion_silent_when_subtracts_survive():
    art = _hlo_art(_PRE_HLO, _OPT_KEPT)
    assert _fired("trace-barrier-survives-fusion", ("hlo",), art) == []


def test_barrier_fusion_fires_when_barrier_never_lowered():
    found = _fired("trace-barrier-survives-fusion", ("hlo",),
                   _hlo_art(_PRE_NO_BARRIER, _OPT_KEPT))
    assert found and "no opt-barrier" in found[0].message


def test_barrier_fusion_fires_when_compensation_folded():
    found = _fired("trace-barrier-survives-fusion", ("hlo",),
                   _hlo_art(_PRE_HLO, _OPT_FOLDED))
    assert found and "folded" in found[0].message


# ---------------------------------------------------------------------------
# HLO op inventory: the parser both HLO audits count opcodes with
# ---------------------------------------------------------------------------

HLO_SAMPLE = """
HloModule test, is_scheduled=true

%body.1 (p.1: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %p.1 = (s32[], f32[64,64]{1,0}) parameter(0)
  %gte.1 = s32[] get-tuple-element(%p.1), index=0
  %gte.2 = f32[64,64]{1,0} get-tuple-element(%p.1), index=1
  %dot.1 = f32[64,64]{1,0} dot(%gte.2, %gte.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar.1 = f32[64,64]{1,0} all-reduce(%dot.1), replica_groups=[1,4]<=[4], to_apply=%add
  ROOT %tuple.1 = (s32[], f32[64,64]{1,0}) tuple(%gte.1, %ar.1)
}

%cond.1 (p.2: (s32[], f32[64,64])) -> pred[] {
  %p.2 = (s32[], f32[64,64]{1,0}) parameter(0)
  %gte.3 = s32[] get-tuple-element(%p.2), index=0
  %c.1 = s32[] constant(12)
  ROOT %lt.1 = pred[] compare(%gte.3, %c.1), direction=LT
}

ENTRY %main.1 (a: f32[64,64]) -> f32[64,64] {
  %a = f32[64,64]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t0 = (s32[], f32[64,64]{1,0}) tuple(%c0, %a)
  %w.1 = (s32[], f32[64,64]{1,0}) while(%t0), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"12"}}
  ROOT %out = f32[64,64]{1,0} get-tuple-element(%w.1), index=1
}
"""

# ``lowered.compiler_ir("hlo").as_hlo_text()`` of
# ``jnp.sum(optimization_barrier(x) - x)``: bare headers, no % sigils.
_PRE_OPT_SAMPLE = """\
HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[]}

region_0.1 {
  reduce_sum.3 = f32[] parameter(0)
  reduce_sum.4 = f32[] parameter(1)
  ROOT reduce_sum.5 = f32[] add(reduce_sum.3, reduce_sum.4)
}

ENTRY main.2 {
  x.1 = f32[8]{0} parameter(0)
  optimization_barrier.1 = f32[8]{0} opt-barrier(x.1)
  sub.1 = f32[8]{0} subtract(optimization_barrier.1, x.1)
  constant.1 = f32[] constant(0)
  ROOT reduce_sum.7 = f32[] reduce(sub.1, constant.1), dimensions={0}, to_apply=region_0.1
}
"""

# Optimized modules print a /*index=N*/ comment every fifth tuple
# element; the op is only parsed once the comments are stripped.
_INDEX_COMMENT_SAMPLE = """\
ENTRY %main.3 (p0: f32[]) -> (f32[], f32[], f32[], f32[], f32[], /*index=5*/f32[]) {
  %p0 = f32[] parameter(0)
  %n0 = f32[] negate(%p0)
  ROOT %t = (f32[], f32[], f32[], f32[], f32[], /*index=5*/f32[]) tuple(%p0, %n0, %p0, %n0, %p0, /*index=5*/%n0)
}
"""


@pytest.mark.parametrize("text,entry,computations,counts", [
    (HLO_SAMPLE, "main.1", {"body.1", "cond.1", "main.1"},
     {"parameter": 3, "get-tuple-element": 4, "dot": 1, "all-reduce": 1,
      "tuple": 2, "constant": 2, "compare": 1, "while": 1}),
    (_PRE_OPT_SAMPLE, "main.2", {"region_0.1", "main.2"},
     {"parameter": 3, "add": 1, "opt-barrier": 1, "subtract": 1,
      "constant": 1, "reduce": 1}),
    (_INDEX_COMMENT_SAMPLE, "main.3", {"main.3"},
     {"parameter": 1, "negate": 1, "tuple": 1}),
], ids=["optimized-while", "pre-optimization", "index-comments"])
def test_parse_hlo_opcode_counts(text, entry, computations, counts):
    module = parse_hlo(text)
    assert module.entry == entry
    assert set(module.computations) == computations
    assert module.opcode_counts() == counts


# ---------------------------------------------------------------------------
# trace-program-count
# ---------------------------------------------------------------------------

def test_program_count_fires_on_unchunked_family():
    from repro.serve.engine import (prefill_program_bound,
                                    prefill_program_family)

    keys = prefill_program_family(16, None, needs_begin=False)
    bound = prefill_program_bound(4, needs_begin=False)
    assert len(keys) > bound  # one program per prompt length
    art = targets.TraceArtifact(program_keys=keys, program_bound=bound)
    found = _fired("trace-program-count", ("program-count",), art)
    assert found and "O(#buckets)" in found[0].message


def test_program_count_silent_on_registered_family():
    report = trace.audit(target_ids=["serve.prefill_buckets"],
                         rule_ids=["trace-program-count"])
    assert report.violations == [], [v.format() for v in report.violations]


def test_program_bound_rejects_unchunked_config():
    from repro.serve.engine import prefill_program_bound

    assert prefill_program_bound(4, needs_begin=False) == 3  # {1, 2, 4}
    assert prefill_program_bound(4, needs_begin=True) == 6
    with pytest.raises(ValueError):
        prefill_program_bound(None, needs_begin=False)


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------

def test_trace_rule_registry_roundtrip():
    rule = trace.TraceRule(id="test-tmp-rule", tags=("kernel",),
                           checker=lambda t, a: iter(()),
                           fix_hint="x", doc="y")
    trace.register(rule)
    try:
        assert "test-tmp-rule" in trace.names()
        assert trace.get("test-tmp-rule") is rule
        with pytest.raises(ValueError):
            trace.register(rule)  # duplicate id
        trace.register(rule, override=True)
    finally:
        trace.unregister("test-tmp-rule")
    assert "test-tmp-rule" not in trace.names()
    with pytest.raises(ValueError):
        trace.get("test-tmp-rule")
    with pytest.raises(TypeError):
        trace.register(object())


def test_target_registry_roundtrip():
    tgt = _toy(("kernel",))
    targets.register(tgt)
    try:
        assert "toy.fixture" in targets.names()
        assert targets.get("toy.fixture") is tgt
        with pytest.raises(ValueError):
            targets.register(tgt)
        targets.register(tgt, override=True)
    finally:
        targets.unregister("toy.fixture")
    assert "toy.fixture" not in targets.names()
    with pytest.raises(ValueError):
        targets.get("toy.fixture")
    with pytest.raises(TypeError):
        targets.register("not a target")


def test_rule_applies_by_tag_overlap():
    rule = trace.get("trace-no-raw-psum")
    assert rule.applies_to(_toy(("sharded", "serve")))
    assert not rule.applies_to(_toy(("kernel",)))


# ---------------------------------------------------------------------------
# audit driver: exemptions and build failures
# ---------------------------------------------------------------------------

def _bad_dtype_art():
    jaxpr = jax.make_jaxpr(
        lambda x: jnp.sum(x.astype(jnp.float16)))(_sds((8,)))
    return targets.TraceArtifact(jaxpr=jaxpr, compute_dtype="float32")


def test_target_exemption_suppresses_and_is_audited():
    tgt = _toy(("kernel",), build=_bad_dtype_art,
               exempt={"trace-accum-dtype": "toy fixture carries fp16"})
    targets.register(tgt)
    try:
        report = trace.audit(target_ids=["toy.fixture"],
                             rule_ids=["trace-accum-dtype"])
    finally:
        targets.unregister("toy.fixture")
    assert report.violations == []
    (ex,) = report.exemptions
    assert ex.rule == "trace-accum-dtype" and ex.path == "toy.fixture"
    assert ex.used is True and ex.reason == "toy fixture carries fp16"


def test_target_exemption_stale_when_rule_is_silent():
    clean = targets.TraceArtifact(
        jaxpr=jax.make_jaxpr(lambda x: x + 1.0)(_sds((2,))))
    tgt = _toy(("serve",), build=lambda: clean,
               exempt={"trace-no-host-callback": "left over"})
    targets.register(tgt)
    try:
        report = trace.audit(target_ids=["toy.fixture"],
                             rule_ids=["trace-no-host-callback"])
    finally:
        targets.unregister("toy.fixture")
    (ex,) = report.exemptions
    assert ex.used is False  # the stale-exemption warning path


def test_build_failure_becomes_finding_not_crash():
    def boom():
        raise RuntimeError("no such shape")

    targets.register(_toy(("kernel",), build=boom))
    try:
        report = trace.audit(target_ids=["toy.fixture"])
    finally:
        targets.unregister("toy.fixture")
    (v,) = report.violations
    assert v.rule == "trace-build-error"
    assert "RuntimeError" in v.message and "no such shape" in v.message
    assert report.exit_code(strict=False) == 1


# ---------------------------------------------------------------------------
# JSON schema + CLI
# ---------------------------------------------------------------------------

def test_trace_json_schema_shares_ast_schema():
    report = trace.audit(target_ids=["serve.prefill_buckets"])
    payload = json.loads(render_json(report, budget=0,
                                     rules=trace.registered().values()))
    assert set(payload) == {"files", "violations", "exemptions",
                            "pragma_errors", "rules", "budget"}
    assert payload["budget"] == {"limit": 0, "exemptions": 0, "ok": True}
    by_id = {r["id"]: r for r in payload["rules"]}
    assert "trace-no-raw-psum" in by_id
    # trace rules render their tag selectors under the shared "scope" key
    assert by_id["trace-no-raw-psum"]["scope"] == ["sharded"]


def test_trace_cli_exit_codes(capsys):
    assert cli_main(["--trace", "--strict",
                     "--target", "serve.prefill_buckets"]) == 0

    assert cli_main(["--trace", "--target", "no.such.target"]) == 2
    err = capsys.readouterr().err
    assert "unknown trace target" in err

    # --target implies --trace
    assert cli_main(["--target", "no.such.target"]) == 2

    assert cli_main(["--trace", "--rule", "no-such-trace-rule"]) == 2
    # paths are an AST-mode concept
    assert cli_main(["--trace", "src/repro"]) == 2

    assert cli_main(["--trace", "--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "trace-no-raw-psum" in out and "serve.decode_tick" in out


def test_cli_budget_ratchet(capsys):
    argv = ["--trace", "--target", "serve.prefill_buckets", "--json"]
    assert cli_main(argv + ["--budget", "0"]) == 0  # no exemptions used
    payload = json.loads(capsys.readouterr().out)
    assert payload["budget"]["ok"] is True


# ---------------------------------------------------------------------------
# tier-1 repo-wide trace self-audit
# ---------------------------------------------------------------------------

def test_repo_trace_self_audit_clean():
    """THE acceptance gate: every registered target traces and passes
    every applicable trace rule — the same check ci.sh stage 0b runs."""
    assert len(trace.names()) >= 5
    report = trace.audit()
    msgs = "\n".join(v.format() for v in report.violations)
    assert report.violations == [], f"trace contract violations:\n{msgs}"
    assert report.files >= 15  # the registered numerics surface
    assert report.exit_code(strict=True) == 0
