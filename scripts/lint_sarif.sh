#!/usr/bin/env bash
# CI lint annotation: run the full graftlint pass (single-file G001-G010 +
# whole-program flow G011-G016, graftmesh G014-G016, graftrdzv G017-G019)
# and emit SARIF 2.1.0 so the CI can annotate PR diffs per-line (GitHub:
# upload with codeql-action/upload-sarif or any SARIF ingester; the region
# startLine/startColumn map straight onto diff positions).
#
# Usage:  scripts/lint_sarif.sh [output.sarif]
#
# GRAFTLINT_CACHE_DIR, when set, pins the content-hash cache directory —
# the tier-1 gate (tests/test_lint_clean.py) runs this script hermetically
# against a tmp cache; CI jobs can point it at a restored cache volume so
# the warm pass stays inside the flow-budget envelope.
#
# Exit status is graftlint's own: 0 clean, 1 findings (fail the check),
# 2 usage/parse errors — so the step can gate merges directly. There is
# deliberately NO baseline file: every finding fails the gate.
set -u
cd "$(dirname "$0")/.."
OUT="${1:-statis/lint.sarif}"
mkdir -p "$(dirname "$OUT")"
CACHE_ARGS=()
if [ -n "${GRAFTLINT_CACHE_DIR:-}" ]; then
    CACHE_ARGS=(--cache-dir "$GRAFTLINT_CACHE_DIR")
fi
python -m dynamic_load_balance_distributeddnn_tpu.analysis.cli \
    --flow --format sarif "${CACHE_ARGS[@]}" \
    dynamic_load_balance_distributeddnn_tpu > "$OUT"
rc=$?
count=$(python - "$OUT" <<'EOF'
import json, sys
with open(sys.argv[1]) as fh:
    sarif = json.load(fh)
print(sum(len(r.get("results", [])) for r in sarif.get("runs", [])))
EOF
)
echo "graftlint: $count finding(s) -> $OUT (exit $rc)" >&2
exit "$rc"
