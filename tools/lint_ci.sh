#!/usr/bin/env sh
# graftlint CI entry point: one invocation produces both artifacts CI
# consumes — the SARIF report (inline PR annotations) and the
# suppression-debt dashboard (--stats, printed to the job log).
#
# Usage:  tools/lint_ci.sh [paths...]        (default: bigdl_tpu tools)
#   GRAFTLINT_SARIF_OUT=path  where to write the SARIF file
#                             (default: graftlint.sarif in the repo root)
#   PYTHON=interpreter        defaults to `python`
#
# Exit status is the lint gate's: 0 clean, 1 findings, 2 usage error.
set -u

cd "$(dirname "$0")/.."
PY="${PYTHON:-python}"
OUT="${GRAFTLINT_SARIF_OUT:-graftlint.sarif}"

"$PY" -m tools.graftlint --format sarif "$@" > "$OUT"
rc=$?
echo "graftlint: SARIF report written to $OUT" >&2

# the debt dashboard is informational — it never changes the exit
# status, and a usage error above skips it (same bad args would recur)
if [ "$rc" -ne 2 ]; then
    "$PY" -m tools.graftlint --stats "$@"
fi
exit $rc
