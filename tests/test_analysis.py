"""repro.analysis tests: every rule fires on a bad fixture, stays silent
on the good one and on the pragma'd one; pragma parsing; JSON reporter
schema; the CLI exit-code contract; and the tier-1 repo-wide self-lint
(zero unannotated violations in src/repro)."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import (LintReport, Violation, lint_source,
                            parse_pragmas, rules)
from repro.analysis.__main__ import main as cli_main
from repro.analysis.report import render_json

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src" / "repro"


def _lint(source, relpath, rule=None):
    return lint_source(textwrap.dedent(source), relpath,
                       rule_ids=[rule] if rule else None)


def _rules_fired(report):
    return sorted({v.rule for v in report.violations})


# ---------------------------------------------------------------------------
# per-rule fixtures: (rule, relpath, bad, good)
# the pragma'd variant is derived from `bad` by the shared test below
# ---------------------------------------------------------------------------

FIXTURES = [
    (
        "no-raw-psum", "distributed/x.py",
        """\
        import jax
        def allreduce(x):
            return jax.lax.psum(x, "dp")
        """,
        """\
        from repro.distributed.collectives import merge_sharded_accumulators
        def allreduce(s, c):
            return merge_sharded_accumulators(s, c, "dp")
        """,
    ),
    (
        "no-legacy-mode-kwarg", "models/x.py",
        """\
        from repro.kernels import ops
        def f(a, b):
            return ops.dot(a, b, mode="kahan")
        """,
        """\
        from repro.kernels import ops
        def f(a, b, buf, idx):
            y = ops.dot(a, b, scheme="kahan")
            return buf.at[idx].set(y, mode="drop")
        """,
    ),
    (
        "no-uncompensated-reduction", "models/x.py",
        """\
        import jax.numpy as jnp
        def f(a, b):
            return jnp.sum(a) + jnp.einsum("ij,jk->ik", a, b)
        """,
        """\
        from repro.kernels import ops
        def f(a, b):
            return ops.asum(a) + ops.matmul(a, b)
        """,
    ),
    (
        "no-literal-interpret", "models/x.py",
        """\
        from repro.kernels import ops
        def f(a, b):
            return ops.dot(a, b, interpret=True)
        """,
        """\
        from repro.kernels import ops
        def f(a, b, interp=None):
            return ops.dot(a, b, interpret=interp)
        """,
    ),
    (
        "no-hardcoded-accum-dtype", "kernels/kahan_sum.py",
        """\
        import jax.numpy as jnp
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...].astype(jnp.float32)
        """,
        """\
        import jax.numpy as jnp
        COMPUTE_DTYPE = jnp.float32          # module-level authority: fine
        def kernel(x_ref, o_ref, compute_dtype=jnp.float32):
            o_ref[...] = x_ref[...].astype(compute_dtype)
        """,
    ),
    (
        "no-host-sync-in-trace", "serve/x.py",
        """\
        def decode_step(logits, tok):
            t = float(tok)
            return logits.argmax().item(), t
        """,
        """\
        import jax.numpy as jnp
        def decode_step(logits, tok):
            return jnp.argmax(logits), tok.astype(jnp.int32)
        """,
    ),
    (
        "no-raw-prngkey", "models/x.py",
        """\
        import jax
        def sample(seed):
            return jax.random.PRNGKey(seed)
        """,
        """\
        import jax
        def sample(base_key, request_id):
            return jax.random.fold_in(base_key, request_id)
        """,
    ),
]


@pytest.mark.parametrize("rule,relpath,bad,good",
                         FIXTURES, ids=[f[0] for f in FIXTURES])
def test_rule_fires_on_bad_fixture(rule, relpath, bad, good):
    report = _lint(bad, relpath, rule)
    assert rule in _rules_fired(report), \
        f"{rule} did not fire on its bad fixture"
    for v in report.violations:
        assert v.line > 0 and v.path == relpath
        assert v.fix_hint  # the registry hint is attached


@pytest.mark.parametrize("rule,relpath,bad,good",
                         FIXTURES, ids=[f[0] for f in FIXTURES])
def test_rule_silent_on_good_fixture(rule, relpath, bad, good):
    report = _lint(good, relpath, rule)
    assert report.violations == [], \
        f"{rule} false-positived on its good fixture: {report.violations}"


@pytest.mark.parametrize("rule,relpath,bad,good",
                         FIXTURES, ids=[f[0] for f in FIXTURES])
def test_rule_suppressed_by_pragma(rule, relpath, bad, good):
    """A standalone pragma above each flagged line silences the finding
    and records an audited exemption instead."""
    base = _lint(bad, relpath, rule)
    lines = textwrap.dedent(bad).splitlines()
    for ln in sorted({v.line for v in base.violations}, reverse=True):
        indent = lines[ln - 1][:len(lines[ln - 1]) - len(lines[ln - 1].lstrip())]
        lines.insert(ln - 1, f"{indent}# contract: allow-{rule}(test fixture)")
    annotated = "\n".join(lines)
    report = lint_source(annotated, relpath, rule_ids=[rule])
    assert report.violations == [], \
        f"pragma did not suppress {rule}: {report.violations}"
    assert report.pragma_errors == []
    used = [p for p in report.exemptions if p.used]
    assert len(used) >= 1
    assert all(p.reason == "test fixture" for p in used)


def test_rule_scope_gating():
    """The same raw reduction outside the hot scope is not a finding."""
    src = """\
    import jax.numpy as jnp
    def f(a):
        return jnp.sum(a)
    """
    assert _rules_fired(_lint(src, "models/x.py"))
    assert not _rules_fired(_lint(src, "launch/x.py"))


def test_mode_parameter_declaration_flagged():
    src = """\
    def f(a, mode=None):
        return a
    """
    report = _lint(src, "kernels/x.py", "no-legacy-mode-kwarg")
    assert _rules_fired(report) == ["no-legacy-mode-kwarg"]


def test_reduction_rule_catches_mean_cumsum_norm():
    """mean/cumsum/linalg.norm hide a sum just as surely as jnp.sum."""
    src = """\
    import jax.numpy as jnp
    def stats(x):
        m = jnp.mean(x)
        c = jnp.cumsum(x)
        n = jnp.linalg.norm(x)
        return m, c, n
    """
    report = _lint(src, "models/x.py", "no-uncompensated-reduction")
    assert sorted(v.line for v in report.violations) == [3, 4, 5]


def test_reduction_rule_silent_on_engine_mean():
    src = """\
    from repro.kernels import ops
    def stats(x):
        return ops.asum(x) / x.size
    """
    report = _lint(src, "models/x.py", "no-uncompensated-reduction")
    assert report.violations == []


def test_reduction_rule_catches_prod_trace_average():
    """prod (sequential-rounding product), trace (diagonal sum), and
    average (weighted sum) are reductions too."""
    src = """\
    import jax.numpy as jnp
    def stats(x):
        p = jnp.prod(x)
        t = jnp.trace(x)
        a = jnp.average(x)
        return p, t, a
    """
    report = _lint(src, "models/x.py", "no-uncompensated-reduction")
    assert sorted(v.line for v in report.violations) == [3, 4, 5]
    assert {v.rule for v in report.violations} == \
        {"no-uncompensated-reduction"}


def test_reduction_rule_silent_on_numpy_shape_math():
    """np.prod over a static shape tuple (host-side shape math, no
    accumulation on device data) must not fire — only the jnp spellings
    hide a device-side sum."""
    src = """\
    import math
    import numpy as np
    def nbytes(x):
        return int(np.prod(x.shape)) * 4 + math.prod(x.shape)
    """
    report = _lint(src, "models/x.py", "no-uncompensated-reduction")
    assert report.violations == []


def test_host_sync_rule_catches_asarray_and_block_until_ready():
    src = """\
    import numpy as np
    def decode_step(logits, tok):
        probs = np.asarray(logits)
        logits.block_until_ready()
        return probs, tok
    """
    report = _lint(src, "serve/x.py", "no-host-sync-in-trace")
    assert {3, 4} <= {v.line for v in report.violations}


def test_host_sync_asarray_ok_outside_trace_bodies():
    """np.asarray is only a trace hazard inside decode/prefill bodies —
    the engine's host-side emit points use it legitimately."""
    src = """\
    import numpy as np
    def emit_results(logits):
        return np.asarray(logits)
    """
    report = _lint(src, "serve/x.py", "no-host-sync-in-trace")
    assert report.violations == []


# ---------------------------------------------------------------------------
# pragma parsing
# ---------------------------------------------------------------------------

def test_pragma_trailing_covers_own_line():
    src = 'x = 1  # contract: allow-no-raw-psum(int payload)\n'
    pragmas, errors = parse_pragmas(src, "f.py")
    assert errors == []
    assert len(pragmas) == 1
    assert pragmas[0].rule == "no-raw-psum"
    assert pragmas[0].reason == "int payload"
    assert pragmas[0].line == 1 and pragmas[0].comment_line == 1


def test_pragma_standalone_covers_next_code_line():
    src = ("# contract: allow-no-raw-psum(int payload)\n"
           "# another comment\n"
           "\n"
           "x = 1\n")
    pragmas, _ = parse_pragmas(src, "f.py")
    assert pragmas[0].comment_line == 1
    assert pragmas[0].line == 4


def test_pragma_in_string_is_not_a_pragma():
    src = 's = "# contract: allow-no-raw-psum(nope)"\n'
    pragmas, errors = parse_pragmas(src, "f.py")
    assert pragmas == [] and errors == []


def test_pragma_empty_reason_is_error():
    src = 'x = 1  # contract: allow-no-raw-psum()\n'
    pragmas, errors = parse_pragmas(src, "f.py")
    assert pragmas == []
    assert len(errors) == 1 and "empty reason" in errors[0]


def test_pragma_malformed_is_error():
    src = 'x = 1  # contract: allow no-raw-psum\n'
    _, errors = parse_pragmas(src, "f.py")
    assert len(errors) == 1 and "malformed" in errors[0]


def test_pragma_unknown_rule_is_reported():
    src = 'x = 1  # contract: allow-no-such-rule(whatever)\n'
    report = lint_source(src, "models/x.py")
    assert any("unknown rule" in e for e in report.pragma_errors)
    assert report.exit_code(strict=True) == 1
    assert report.exit_code(strict=False) == 0


def test_pragma_only_suppresses_matching_rule_and_line():
    src = textwrap.dedent("""\
    import jax.numpy as jnp
    def f(a):
        x = jnp.sum(a)  # contract: allow-no-raw-psum(wrong rule)
        return x
    """)
    report = lint_source(src, "models/x.py",
                         rule_ids=["no-uncompensated-reduction"])
    assert _rules_fired(report) == ["no-uncompensated-reduction"]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_register_unregister_roundtrip():
    rule = rules.Rule(id="no-test-rule", scope=("models/*",),
                      checker=lambda ctx: iter(()), fix_hint="n/a",
                      doc="test-only rule")
    rules.register(rule)
    try:
        assert "no-test-rule" in rules.names()
        with pytest.raises(ValueError, match="already registered"):
            rules.register(rule)
        rules.register(rule, override=True)
        assert rules.get("no-test-rule") is rule
    finally:
        rules.unregister("no-test-rule")
    assert "no-test-rule" not in rules.names()
    with pytest.raises(ValueError, match="registered rules"):
        rules.get("no-test-rule")


def test_all_issue_rules_registered():
    expected = {"no-raw-psum", "no-legacy-mode-kwarg",
                "no-uncompensated-reduction", "no-literal-interpret",
                "no-hardcoded-accum-dtype", "no-host-sync-in-trace",
                "no-raw-prngkey"}
    assert expected <= set(rules.names())


# ---------------------------------------------------------------------------
# JSON reporter schema
# ---------------------------------------------------------------------------

def test_json_report_schema():
    src = textwrap.dedent("""\
    import jax.numpy as jnp
    def f(a):
        y = jnp.sum(a)  # contract: allow-no-uncompensated-reduction(fixture)
        return jnp.sum(y)
    """)
    payload = json.loads(render_json(lint_source(src, "models/x.py")))
    assert set(payload) == {"files", "violations", "exemptions",
                            "pragma_errors", "rules", "budget"}
    # no --budget requested: the verdict is present and vacuously ok
    assert payload["budget"] == {"limit": None, "exemptions": 1, "ok": True}
    assert payload["files"] == 1
    (v,) = payload["violations"]
    assert set(v) == {"rule", "path", "line", "col", "message", "fix_hint"}
    assert v["rule"] == "no-uncompensated-reduction" and v["line"] == 4
    (e,) = payload["exemptions"]
    assert set(e) == {"rule", "reason", "path", "line", "comment_line",
                      "used"}
    assert e["used"] is True and e["reason"] == "fixture"
    ids = {r["id"] for r in payload["rules"]}
    assert "no-raw-psum" in ids


def test_sarif_report_schema():
    """Pin the SARIF 2.1.0 surface CI annotators consume: version/$schema
    literals, the driver's rule metadata, result anatomy, and the
    line-0 -> startLine-1 clamp trace/cost findings rely on."""
    from repro.analysis.report import SARIF_SCHEMA, SARIF_VERSION, render_sarif

    src = textwrap.dedent("""\
    import jax.numpy as jnp
    def f(a):
        return jnp.sum(a)
    """)
    payload = json.loads(render_sarif(lint_source(src, "models/x.py")))
    assert payload["version"] == SARIF_VERSION == "2.1.0"
    assert payload["$schema"] == SARIF_SCHEMA
    assert set(payload) == {"$schema", "version", "runs"}
    (run,) = payload["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "repro.analysis"
    rule_ids = {r["id"] for r in driver["rules"]}
    assert "no-uncompensated-reduction" in rule_ids
    for r in driver["rules"]:
        assert set(r) == {"id", "shortDescription", "help"}
    (res,) = run["results"]
    assert set(res) == {"ruleId", "level", "message", "locations"}
    assert res["ruleId"] == "no-uncompensated-reduction"
    assert res["level"] == "error"
    assert "[fix:" in res["message"]["text"]
    region = res["locations"][0]["physicalLocation"]["region"]
    assert region["startLine"] == 3 and region["startColumn"] >= 1
    loc = res["locations"][0]["physicalLocation"]["artifactLocation"]
    assert loc == {"uri": "models/x.py"}

    # a line-0 anchor (trace/cost findings) clamps to the SARIF minimum
    clamped = LintReport(violations=[Violation(
        rule="x", path="cost.dot.kahan", line=0, col=0, message="m")])
    payload = json.loads(render_sarif(clamped, rules=[]))
    region = payload["runs"][0]["results"][0]["locations"][0][
        "physicalLocation"]["region"]
    assert region == {"startLine": 1, "startColumn": 1}


def test_sarif_reports_pragma_errors_as_warnings():
    from repro.analysis.report import render_sarif

    src = textwrap.dedent("""\
    import jax.numpy as jnp
    def f(a):
        return jnp.sum(a)  # contract: allow-no-uncompensated-reduction()
    """)
    payload = json.loads(render_sarif(lint_source(src, "models/x.py")))
    levels = {r["ruleId"]: r["level"] for r in payload["runs"][0]["results"]}
    assert levels["pragma-error"] == "warning"


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_exit_codes(tmp_path, capsys):
    bad = tmp_path / "repro" / "models" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import jax.numpy as jnp\n"
                   "def f(a):\n"
                   "    return jnp.sum(a)\n")
    assert cli_main(["--strict", str(bad)]) == 1
    out = capsys.readouterr().out
    # findings name the rule id and a file:line anchor
    assert "no-uncompensated-reduction" in out
    assert "bad.py:3" in out

    good = tmp_path / "repro" / "models" / "good.py"
    good.write_text("def f(a):\n    return a\n")
    assert cli_main(["--strict", str(good)]) == 0

    assert cli_main(["--list-rules"]) == 0
    assert cli_main(["--rule", "no-such-rule", str(good)]) == 2
    assert cli_main([str(tmp_path / "missing.py")]) == 2


def test_cli_sarif_output(tmp_path, capsys):
    bad = tmp_path / "repro" / "models" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import jax.numpy as jnp\n"
                   "def f(a):\n"
                   "    return jnp.sum(a)\n")
    # --sarif changes the report dialect, not the exit-code contract
    assert cli_main(["--sarif", "--strict", str(bad)]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["version"] == "2.1.0"
    results = payload["runs"][0]["results"]
    assert results[0]["ruleId"] == "no-uncompensated-reduction"

    # --json and --sarif are mutually exclusive (argparse group)
    with pytest.raises(SystemExit) as exc:
        cli_main(["--json", "--sarif", str(bad)])
    assert exc.value.code == 2


def test_cli_empty_reason_fails_only_strict(tmp_path, capsys):
    f = tmp_path / "repro" / "models" / "x.py"
    f.parent.mkdir(parents=True)
    f.write_text("import jax.numpy as jnp\n"
                 "def f(a):\n"
                 "    return jnp.sum(a)"
                 "  # contract: allow-no-uncompensated-reduction()\n")
    # empty reason: the pragma is DISCARDED (finding stays) and the
    # malformed exemption is itself an error under --strict
    assert cli_main(["--strict", str(f)]) == 1
    out = capsys.readouterr().out
    assert "empty reason" in out


def test_cli_reports_every_bad_path_in_one_run(tmp_path, capsys):
    """Path validation is up-front and exhaustive: one run names every
    missing/unreadable path (and any unknown rule) instead of failing on
    the first and hiding the rest."""
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    rc = cli_main(["--rule", "no-such-rule", str(tmp_path / "missing_a.py"),
                   str(ok), str(tmp_path / "missing_b.py")])
    assert rc == 2
    err = capsys.readouterr().err
    assert "missing_a.py" in err
    assert "missing_b.py" in err
    assert "no-such-rule" in err


def test_cli_budget_ratchet(tmp_path, capsys):
    f = tmp_path / "repro" / "models" / "x.py"
    f.parent.mkdir(parents=True)
    f.write_text(
        "import jax.numpy as jnp\n"
        "def f(a):\n"
        "    return jnp.sum(a)"
        "  # contract: allow-no-uncompensated-reduction(fixture)\n")
    # one exemption: within budget 1, over budget 0
    assert cli_main(["--strict", "--budget", "1", str(f)]) == 0
    capsys.readouterr()
    assert cli_main(["--strict", "--budget", "0", str(f)]) == 1
    out = capsys.readouterr().out
    assert "exceed the budget" in out
    # the JSON artifact carries the verdict
    assert cli_main(["--json", "--budget", "0", str(f)]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["budget"] == {"limit": 0, "exemptions": 1, "ok": False}


def test_cli_module_invocation():
    """`python -m repro.analysis` is wired up (the ci.sh stage-0 form)."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--list-rules"],
        capture_output=True, text=True, cwd=str(REPO),
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0
    assert "no-raw-psum" in proc.stdout


# ---------------------------------------------------------------------------
# tier-1 repo-wide self-lint
# ---------------------------------------------------------------------------

def test_repo_self_lint_strict_clean():
    """THE acceptance gate: zero unannotated violations and zero pragma
    errors across src/repro — the same check ci.sh stage 0 runs."""
    from repro.analysis import lint_paths

    report = lint_paths([SRC])
    msgs = "\n".join(v.format() for v in report.violations)
    assert report.violations == [], f"unannotated contract violations:\n{msgs}"
    assert report.pragma_errors == [], report.pragma_errors
    assert report.exit_code(strict=True) == 0
    # the exemption audit is non-empty (models' annotated raw reductions)
    # and every exemption carries a reason
    assert len(report.exemptions) >= 40
    assert all(p.reason for p in report.exemptions)
    # no stale pragmas: every exemption suppresses a live finding
    stale = [p for p in report.exemptions if not p.used]
    assert stale == [], [(p.path, p.comment_line, p.rule) for p in stale]
