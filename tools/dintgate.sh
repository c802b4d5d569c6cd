#!/usr/bin/env bash
# dintgate: ONE entry point for all six standing static gates.
#
#   tools/dintgate.sh [--sarif PATH] [--timings PATH]
#
# Gates, in dependency-free order:
#   1. dintlint --prune-allowlist --check
#                              every analysis pass over every target
#                              (calib_check + mut_check ride
#                              along in STATIC form), PLUS the allowlist
#                              staleness dry-run: a stale entry fails the
#                              gate without rewriting the file
#   2. dintcost check --prune-allowlist --check
#                              the priced budget/parity/overlap gate over
#                              the full matrix + cost_budget-scoped
#                              allowlist staleness
#   3. dintdur  check --prune-allowlist --check
#                              the durability/replication gate over the
#                              full matrix + durability-scoped allowlist
#                              staleness
#   4. dintmon  check          the counter-identity gate on the pinned
#                              fixture artifact (no trace run needed)
#   5. dintcal  check+audit    the calibration gate: pinned CALIB.json
#                              reconciles with its evidence fixture, and
#                              the checked-in decision journal replays
#                              bit-for-bit through the pure policy
#   6. dintmut  check --quick  the mutation-coverage gate: the pinned
#                              deterministic mutant sample re-executes
#                              bit-for-bit against MUTCOV.json, on top of
#                              the static mut_check policy (kill-rate
#                              floor, survivor triage, family coverage)
#
# --sarif PATH merges the finding gates' SARIF logs into one multi-run
# SARIF 2.1.0 document (one runs[] entry per gate driver) — upload-ready
# for code-scanning UIs. dintmon and dintcal audit are numeric identity
# checks, not findings passes, so they report via exit code only.
#
# Every stage is wall-clocked; the per-gate timings are printed as one
# machine-parseable JSON line ({"metric": "dintgate", ...}) and written
# to --timings PATH when given, so CI can trend gate latency the same
# way bench artifacts trend engine latency.
#
# Exit 0 iff EVERY gate passed; each failing gate is named. All gates
# always run (no fail-fast) so one invocation reports the full damage.
set -u
cd "$(dirname "$0")/.."

SARIF=""
TIMINGS_OUT=""
while [ $# -gt 0 ]; do
    case "$1" in
        --sarif) shift; SARIF="${1:?--sarif needs a path}" ;;
        --timings) shift; TIMINGS_OUT="${1:?--timings needs a path}" ;;
        -h|--help)
            sed -n '2,44p' "$0" | sed 's/^# \{0,1\}//'
            exit 0 ;;
        *) echo "dintgate: unknown argument: $1 (try --help)" >&2; exit 2 ;;
    esac
    shift
done

PY="${PYTHON:-python}"
TMP="$(mktemp -d)"
trap 'rm -rf "$TMP"' EXIT

FAIL=""
STAGES=""
T_ALL0=$(date +%s.%N)
run_gate() {
    name="$1"; shift
    echo "=== $name: $*"
    t0=$(date +%s.%N)
    if "$@"; then rc=0; else rc=$?; fi
    t1=$(date +%s.%N)
    dt=$(awk -v a="$t0" -v b="$t1" 'BEGIN{printf "%.3f", b - a}')
    ok=false; [ "$rc" = 0 ] && ok=true
    STAGES="$STAGES{\"gate\": \"$name\", \"wall_s\": $dt, \"ok\": $ok}, "
    if [ "$rc" = 0 ]; then
        echo "--- $name: ok (${dt}s)"
    else
        echo "--- $name: FAIL (exit $rc, ${dt}s)"
        FAIL="$FAIL $name"
    fi
}

run_gate dintlint "$PY" tools/dintlint.py --prune-allowlist --check \
    --sarif "$TMP/lint.sarif"
run_gate dintcost "$PY" tools/dintcost.py check --prune-allowlist --check \
    --sarif "$TMP/cost.sarif"
run_gate dintdur  "$PY" tools/dintdur.py check --prune-allowlist --check \
    --sarif "$TMP/dur.sarif"
run_gate dintmon  "$PY" tools/dintmon.py check tests/fixtures/dintmon_counters.json
run_gate dintcal  "$PY" tools/dintcal.py check --sarif "$TMP/cal.sarif"
run_gate dintcal-audit "$PY" tools/dintcal.py audit tests/fixtures/dintcal_journal.jsonl
run_gate dintmut  "$PY" tools/dintmut.py check --quick --sarif "$TMP/mut.sarif"

if [ -n "$SARIF" ]; then
    "$PY" - "$SARIF" "$TMP"/*.sarif <<'MERGE'
import json
import sys

out, paths = sys.argv[1], sys.argv[2:]
runs = []
for p in paths:
    try:
        runs.extend(json.load(open(p)).get("runs", []))
    except (OSError, ValueError) as e:       # a gate died pre-export
        print(f"dintgate: skipping unreadable {p}: {e}", file=sys.stderr)
doc = {"$schema": "https://json.schemastore.org/sarif-2.1.0.json",
       "version": "2.1.0", "runs": runs}
with open(out, "w") as fh:
    json.dump(doc, fh, indent=1)
    fh.write("\n")
print(f"dintgate: merged SARIF ({len(runs)} runs) -> {out}")
MERGE
fi

T_ALL1=$(date +%s.%N)
TOTAL=$(awk -v a="$T_ALL0" -v b="$T_ALL1" 'BEGIN{printf "%.3f", b - a}')
TIMING_LINE="{\"metric\": \"dintgate\", \"schema\": 2, \"stages\": [${STAGES%, }], \"total_s\": $TOTAL}"
echo "$TIMING_LINE"
if [ -n "$TIMINGS_OUT" ]; then
    printf '%s\n' "$TIMING_LINE" > "$TIMINGS_OUT"
    echo "dintgate: stage timings -> $TIMINGS_OUT"
fi

if [ -z "$FAIL" ]; then
    echo "dintgate: all 6 gates ok"
    exit 0
fi
echo "dintgate: FAIL —$FAIL"
exit 1
