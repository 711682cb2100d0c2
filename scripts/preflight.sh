#!/usr/bin/env bash
# Mechanical end-of-round gate (VERDICT r3 #8): run before EVERY snapshot
# commit. Round 3 shipped its final two commits without re-running the
# suite and ended with 3 red tests and an rc=1 driver dryrun; this script
# makes that class of damage impossible to ship silently.
#
#   scripts/preflight.sh           # pytest + dryrun(8)
#
# On a TPU host run `python chip_smoke.py` as well: it is the check that
# the trainer and the server start on the chip. Speed is measured by
# perfbench/ alone (perfbench/README.md).
#
# Exits non-zero on ANY failure. Paste the tail of its output into the
# snapshot commit message.
set -uo pipefail
cd "$(dirname "$0")/.."

FAIL=0

echo "== preflight: pytest =="
# Pick the timeout flag by plugin availability up front — retrying on ANY
# failure would run a genuinely red suite twice and discard the first
# run's stderr (collection errors, tracebacks).
if python -c 'import pytest_timeout' 2>/dev/null; then
    PYTEST_ARGS=(--timeout=1200)
else
    PYTEST_ARGS=()
fi
if python -m pytest tests/ -q -x ${PYTEST_ARGS[@]+"${PYTEST_ARGS[@]}"}; then
    echo "preflight pytest: OK"
else
    echo "preflight pytest: FAILED"
    FAIL=1
fi

echo "== preflight: dryrun_multichip(8) =="
if python - <<'EOF'
import __graft_entry__ as ge
ge.dryrun_multichip(8)  # provisions its own virtual 8-device CPU mesh
print("preflight dryrun: OK")
EOF
then
    :
else
    echo "preflight dryrun: FAILED"
    FAIL=1
fi

if [ "$FAIL" -ne 0 ]; then
    echo "PREFLIGHT: FAILED"
    exit 1
fi
echo "PREFLIGHT: GREEN"
