#!/usr/bin/env bash
# Pre-PR static-analysis gate (see TESTING.md "Lint gate"):
#
#   1. full-tree plane-lint v2 (whole-program pass) with --json report;
#   2. lane-graph emission (analysis/lane_graph.json must come out
#      byte-identical to the committed artifact — the tier-1 round-trip
#      test in tests/test_lane_graph.py enforces the same);
#   3. a wall-clock budget assertion: the full-tree lint must finish in
#      under 30 s on CPU, so the analyzer's own cost stays a tracked
#      quantity;
#   4. a host-sync-family grep gate: `time.time()` is banned from the
#      hot/measurement modules — durations measured on the wall clock
#      go backwards under NTP steps and smear every latency figure.
#      A genuinely wall-clock use (epoch timestamps in metadata) must
#      carry a `wall-clock ok` comment on its line to pass.
#
# Exit 0 only when the tree is clean, the graph is fresh, the budget
# holds, and no unannotated wall-clock measurement landed.
set -euo pipefail
cd "$(dirname "$0")/.."

# ---- wall-clock measurement gate (hot/measurement modules) -----------
HOT_DIRS="elasticsearch_tpu/search elasticsearch_tpu/parallel \
elasticsearch_tpu/ops elasticsearch_tpu/observability \
elasticsearch_tpu/index elasticsearch_tpu/indices \
elasticsearch_tpu/monitor elasticsearch_tpu/snapshots \
elasticsearch_tpu/analysis"
# shellcheck disable=SC2086
if grep -rn "time\.time()" $HOT_DIRS --include='*.py' \
        | grep -v "wall-clock ok"; then
    echo "lint_gate: FAIL — time.time() on a hot/measurement path;" \
         "use time.monotonic() (or annotate an epoch-timestamp use" \
         "with '# wall-clock ok: <why>')" >&2
    exit 1
fi

BUDGET_S="${LINT_BUDGET_S:-30}"
REPORT="${LINT_REPORT:-/tmp/plane_lint_report.json}"
GRAPH="elasticsearch_tpu/analysis/lane_graph.json"

start=$(python -c 'import time; print(time.monotonic())')
JAX_PLATFORMS=cpu python -m elasticsearch_tpu.analysis elasticsearch_tpu \
    --json --emit-lane-graph "$GRAPH" > "$REPORT"
end=$(python -c 'import time; print(time.monotonic())')

wall=$(python -c "print(round($end - $start, 2))")
open=$(python -c "import json; print(json.load(open('$REPORT'))['open'])")
warn=$(python -c "import json; print(json.load(open('$REPORT'))['warnings'])")
echo "lint_gate: ${open} open finding(s), ${warn} warning(s), ${wall}s wall"

if [ "$open" != "0" ]; then
    echo "lint_gate: FAIL — open findings (see $REPORT)" >&2
    exit 1
fi
if ! git diff --quiet -- "$GRAPH"; then
    echo "lint_gate: FAIL — $GRAPH changed; commit the regenerated" \
         "lane graph" >&2
    git --no-pager diff --stat -- "$GRAPH" >&2
    exit 1
fi
python -c "import sys; sys.exit(0 if $wall < $BUDGET_S else 1)" || {
    echo "lint_gate: FAIL — full-tree lint took ${wall}s" \
         "(budget ${BUDGET_S}s)" >&2
    exit 1
}
echo "lint_gate: OK (lane graph fresh, budget ${BUDGET_S}s held)"
