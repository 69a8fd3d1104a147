#!/usr/bin/env bash
# Full compile-hygiene static-analysis gate: every PTL rule over the
# package, the tools, the bench driver and chip_smoke.py (<30s on the CPU
# container).
# A NEW finding (unsuppressed, unbaselined) fails the same way a dirty
# worktree fails tier-1 — tools/tier1_guard.sh runs this first.
#
# Rules: PTL001 moving-api, PTL002 tracer-leak, PTL003 donation safety,
# PTL004 host-sync-in-hot-path, PTL005 lock-order cycles, PTL000
# suppression hygiene.  See README "Static analysis".
#
# Usage: tools/lint_guard.sh [extra analyzer args...]
# Exit:  0 clean, 1 findings, 2 environment error.
set -o pipefail
cd "$(dirname "$0")/.." || exit 2

start=$(date +%s)
# ptl_lint.py = the same analyzer CLI standalone-loaded without the
# paddle_tpu package import, so the gate runs jax-less and in ~1s
python tools/ptl_lint.py paddle_tpu tools bench.py chip_smoke.py "$@"
rc=$?
elapsed=$(( $(date +%s) - start ))
if [ "$rc" -eq 1 ]; then
    echo "lint_guard: FAIL — new findings (${elapsed}s)" >&2
    exit 1
fi
if [ "$rc" -ne 0 ]; then
    echo "lint_guard: analyzer failed to run (exit $rc, ${elapsed}s)" >&2
    exit 2
fi
echo "lint_guard: OK (${elapsed}s)"
