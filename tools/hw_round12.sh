#!/bin/bash
# Round-12 hardware measurement plan: the fused-megakernel A/B (ISSUE 8
# tentpole). Lands the cheapest decisive artifact first — the per-site --fused
# stage settles whether one lock_validate / install_log dispatch beats the
# unfused pair it swallows, the bench pair settles what the shortened
# chain (~6 -> ~4 dispatches/step) buys end-to-end, and the dintscope
# diff (wave-alias fold: swallowed waves are attributed to their fused
# successor, never "missing") is the gate that names any regressed wave.
# Decision rule (PERF.md round 12): DINT_USE_FUSED stays default-off
# unless BOTH fused sites show speedup > 1 in the --fused stage AND the
# DINT_USE_FUSED=1 bench beats the baseline's committed txns/s with the
# aliased dintscope diff clean (exit 0).
cd "$(dirname "$0")/.." || exit 1

echo "=== stage 1: per-site fused A/B at production geometry ==="
# TATP geometry: the full 154M-row flat space, K = w*K lanes and
# M = 2*w write slots at the bench's w=8192. The tool reruns the round-6
# meta/val/lock sections too, so one artifact carries every kernel
# comparison; probe failures degrade to explicit nulls, never kill the
# JSON line.
timeout 1800 python tools/profile_pallas_hbm.py --compare --fused \
    32768 > pallas_fused_ab.log 2>&1 || true
tail -3 pallas_fused_ab.log

echo "=== stage 2: baseline bench (fused off) ==="
DINT_BENCH_PROFILE=1 DINT_MONITOR=1 DINT_BENCH_TRACE_DIR=trace_r12_off \
    timeout 2200 python bench.py \
    > bench_fused_off.json 2> bench_fused_off_stderr.log
tail -1 bench_fused_off.json

echo "=== stage 3: fused bench — the tentpole measurement ==="
DINT_USE_FUSED=1 DINT_BENCH_PROFILE=1 DINT_MONITOR=1 \
    DINT_BENCH_TRACE_DIR=trace_r12_fused timeout 2200 python bench.py \
    > bench_fused_on.json 2> bench_fused_on_stderr.log
tail -1 bench_fused_on.json

echo "=== stage 4: fused + hot-set interaction bench ==="
# the megakernels compose with the round-10 VMEM tier (lock_validate
# keeps the hot_n arb prefix; install_log carries the mirror streams):
# measure the stack, not just the layers
DINT_USE_FUSED=1 DINT_USE_HOTSET=1 DINT_BENCH_PROFILE=1 DINT_MONITOR=1 \
    DINT_BENCH_TRACE_DIR=trace_r12_fused_hot timeout 2200 python bench.py \
    > bench_fused_hot.json 2> bench_fused_hot_stderr.log
tail -1 bench_fused_hot.json

echo "=== stage 4b: dintscope per-wave attribution + the aliased gate ==="
# pre-attributed A/B: the report shows WHERE the dispatch count went
# (lock/meta_gather/install/log_append collapse into lock_validate and
# install_log), and the diff folds those constituents onto their fused
# successor (attrib.WAVE_ALIASES) so the gate compares like against like
# and exits 1 naming any regressed wave (recorded, not fatal — it feeds
# the decision rule above; --no-alias re-runs it on raw scopes)
for t in off fused fused_hot; do
    if [ -d "trace_r12_${t}" ]; then
        python tools/dintscope.py report "trace_r12_${t}" \
            --geom w=8192 k=4 l=3 vw=10 --json \
            > "dintscope_r12_${t}.json" 2>> dintscope_r12.log || true
    fi
done
if [ -s dintscope_r12_off.json ] && [ -s dintscope_r12_fused.json ]; then
    python tools/dintscope.py diff dintscope_r12_off.json \
        dintscope_r12_fused.json | tail -10 || true
    echo "gate exit: $?"
fi
# static prediction beside the measurement: the dintcost model the
# dintscope numbers should agree with (derived on CPU, no chip time)
JAX_PLATFORMS=cpu python tools/dintcost.py report --all --json \
    > dintcost_r12.json 2>> dintscope_r12.log || true

echo "=== stage 5: monitored fused run (fused_dispatch reconciliation) ==="
# dintmon must count fused_dispatch == steps with the xla/pallas split
# still total (counters.py invariant) — one short monitored run proves
# the counter plane reconciles on hardware like it does in CI
DINT_USE_FUSED=1 DINT_MONITOR=1 DINT_MONITOR_JSONL=mon_r12_fused.jsonl \
    timeout 1200 python bench.py > bench_fused_mon.json \
    2> bench_fused_mon_stderr.log || true
python tools/dintmon.py summarize mon_r12_fused.jsonl | tail -5 || true

echo "=== archive CALIB evidence (dintcal) ==="
# every hardware round archives its measured evidence in dintcal's
# normalized form so a recalibration is one `dintcal fit` away
JAX_PLATFORMS=cpu python tools/dintcal.py gather dintscope_r12_*.json bench_fused_*.json \
    -o calib_evidence_hw_round12.json || true

echo "=== done ==="
