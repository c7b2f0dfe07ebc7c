#!/usr/bin/env bash
# Three-stage CI: tier-1 (fast, must stay < 120 s), the slow tier, and a
# benchmarks smoke stage (tiny shapes, interpret mode — every registered
# benchmark in benchmarks/run.py, with the rows captured to a
# BENCH_<date>.json artifact so the perf trajectory is tracked).
#
#   scripts/ci.sh            # all stages
#   scripts/ci.sh fast       # tier-1 only (what the driver runs)
#   scripts/ci.sh slow       # slow tier only
#   scripts/ci.sh bench      # benchmarks smoke stage only
#
# Deprecation gate: both pytest stages run with DeprecationWarning
# promoted to an error for warnings ATTRIBUTED to repro.* modules (e.g.
# the deprecated lock-step Server shim warns at its caller), proving no
# internal call site leans on a deprecated surface. Test call sites that
# deliberately exercise one attribute to the test module and stay
# warnings.
#
# Contract gate: stage 0 runs the AST-based engine-contract linter
# (repro.analysis) over src/repro — every clause of the numerics contract
# (no raw psum, no legacy mode= kwarg, no uncompensated hot-path
# reductions, no interpret= literals, ...) is machine-checked, and every
# exemption must carry a '# contract: allow-<rule>(<reason>)' pragma.
# The --budget pin is the exemption RATCHET: the run fails the moment
# the pragma count exceeds it, so adding an exemption means raising the
# number here in the same commit — a deliberate, reviewable act.
# Stage 0b re-audits the contract at the IR level: the registered entry
# points (repro.analysis.targets) are traced to jaxprs/HLO and checked
# for what source text cannot prove (no psum primitive however spelled,
# barriers surviving lowering, the decode tick as one pinned batched body,
# the O(#buckets) prefill program bound). Budget: < 60 s.
# Stage 0c audits the PERFORMANCE contract: every registered scheme's
# kernel bodies are traced at audit shapes, their instruction mix and
# memory traffic statically derived, and cross-checked against the ECM
# model (repro.analysis.costmodel) — declared instruction_mix vs traced
# counts, bytes/element vs elem_bytes_for_dtype, no hidden HLO copies,
# and the paper's kahan~=naive bandwidth-bound claim as a machine-checked
# invariant. Budget: < 60 s.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

stage="${1:-all}"

# -o filterwarnings treats module as a REGEX (pytest CLI -W would escape
# it to a literal full-module match and miss submodules).
DEPRECATION_GATE=(-o 'filterwarnings=error::DeprecationWarning:repro(\..*)?')

echo "=== stage 0: engine-contract lint (src/repro) ==="
python -m repro.analysis --strict --budget 65 src/repro

echo "=== stage 0b: engine-contract trace audit (jaxpr/HLO) ==="
python -m repro.analysis --trace --strict

echo "=== stage 0c: ECM cost audit (instruction mix / memory traffic) ==="
python -m repro.analysis --cost --strict

if [[ "$stage" == "fast" || "$stage" == "all" ]]; then
    echo "=== stage 1: tier-1 (fast) + repro.* deprecation gate ==="
    python -m pytest -x -q "${DEPRECATION_GATE[@]}"
fi

if [[ "$stage" == "slow" || "$stage" == "all" ]]; then
    echo "=== stage 2: slow tier ==="
    python -m pytest -q -m slow "${DEPRECATION_GATE[@]}"
fi

if [[ "$stage" == "bench" || "$stage" == "all" ]]; then
    echo "=== stage 3: benchmarks smoke (tiny shapes, interpret mode) ==="
    # (the repro.* deprecation gate lives in the pytest stages; the bench
    # modules go through the same public API they exercise)
    python -m benchmarks.run --smoke --json "BENCH_$(date +%Y%m%d).json"
fi
