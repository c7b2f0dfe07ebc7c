#!/usr/bin/env bash
# CI: the contract audits (stages 0, 0b, 0c), then tier-1 (stage 1) and
# the slow tier (stage 2). Speed is measured on the chip by bench/ (see
# BENCHMARK.json), not here.
#
#   scripts/ci.sh            # all stages
#   scripts/ci.sh fast       # audits + tier-1
#   scripts/ci.sh slow       # audits + slow tier
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

echo "=== stage 0: engine-contract lint (src/repro) ==="
python -m repro.analysis --strict --budget 65 src/repro

echo "=== stage 0b: engine-contract trace audit (jaxpr/HLO) ==="
python -m repro.analysis --trace --strict

echo "=== stage 0c: ECM cost audit (instruction mix / memory traffic) ==="
python -m repro.analysis --cost --strict

if [[ "$stage" == "fast" || "$stage" == "all" ]]; then
    echo "=== stage 1: tier-1 (fast) ==="
    python -m pytest -x -q
fi

if [[ "$stage" == "slow" || "$stage" == "all" ]]; then
    echo "=== stage 2: slow tier ==="
    python -m pytest -q -m slow
fi
