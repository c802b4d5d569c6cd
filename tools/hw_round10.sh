#!/bin/bash
# Round-10 hardware measurement plan: the dintcache hot-set A/B (ISSUE 5
# tentpole). Lands the cheapest decisive artifact first — the per-op hot stage settles
# whether the VMEM mirror beats the plain DMA ring on the skewed batch at
# SmallBank geometry, the bench pair settles what that buys end-to-end.
# Decision rule (PERF.md round 10): the hot tier stays off unless
# speedup_vs_ring > 1 at SmallBank geometry AND the DINT_USE_HOTSET=1
# bench beats the baseline's smallbank_committed_txns_per_sec.
cd "$(dirname "$0")/.." || exit 1

echo "=== stage 1: per-op hot-set A/B at SmallBank geometry ==="
# bal-array shape: 2*24M+1 single-word rows (~192 MB), K = w*L at the
# bench's w=8192; --hot-frac 0.04 mirrors the reference hot set (~7.7 MB,
# VMEM-resident inside the kernel). The tool also reruns the round-6
# meta/val/lock sections, so one artifact carries both comparisons.
timeout 1500 python tools/profile_pallas_hbm.py --compare --hot-frac 0.04 \
    24576 48000001 1 > pallas_hot_ab.log 2>&1 || true
tail -3 pallas_hot_ab.log

echo "=== stage 2: baseline bench (hot tier off) ==="
DINT_BENCH_PROFILE=1 DINT_MONITOR=1 DINT_BENCH_TRACE_DIR=trace_r10_off \
    timeout 2200 python bench.py \
    > bench_hot_off.json 2> bench_hot_off_stderr.log
tail -1 bench_hot_off.json

echo "=== stage 3: hot-set bench (XLA partition route) ==="
DINT_USE_HOTSET=1 DINT_BENCH_PROFILE=1 DINT_MONITOR=1 \
    DINT_BENCH_TRACE_DIR=trace_r10_xla timeout 2200 python bench.py \
    > bench_hot_xla.json 2> bench_hot_xla_stderr.log
tail -1 bench_hot_xla.json

echo "=== stage 4: hot-set bench (VMEM kernels) — the tentpole measurement ==="
DINT_USE_HOTSET=1 DINT_USE_PALLAS=1 DINT_BENCH_PROFILE=1 DINT_MONITOR=1 \
    DINT_BENCH_TRACE_DIR=trace_r10_pallas timeout 2200 python bench.py \
    > bench_hot_pallas.json 2> bench_hot_pallas_stderr.log
tail -1 bench_hot_pallas.json

echo "=== stage 4b: dintscope per-wave attribution + regression gate ==="
# pre-attributed A/B: the per-wave ledger shows WHERE the VMEM mirror
# moved time (smallbank read/install waves) and the diff gate names any
# wave the hot tier regressed (exit 1 recorded, not fatal — it feeds the
# decision rule above)
for t in off xla pallas; do
    if [ -d "trace_r10_${t}" ]; then
        python tools/dintscope.py report "trace_r10_${t}" \
            --geom w=8192 k=4 l=3 vw=10 --json \
            > "dintscope_r10_${t}.json" 2>> dintscope_r10.log || true
    fi
done
if [ -s dintscope_r10_off.json ] && [ -s dintscope_r10_pallas.json ]; then
    python tools/dintscope.py diff dintscope_r10_off.json \
        dintscope_r10_pallas.json | tail -8 || true
fi
# static prediction beside the measurement (dintcost, CPU-derived)
JAX_PLATFORMS=cpu python tools/dintcost.py report --all --json \
    > dintcost_r10.json 2>> dintscope_r10.log || true

echo "=== stage 5: skew sweep (hot tier on vs off at each skew) ==="
timeout 2400 python exp.py --only smallbank_skew --window 5 \
    --out exp_results/skew_off > skew_off.log 2>&1 || true
DINT_USE_HOTSET=1 timeout 2400 python exp.py --only smallbank_skew \
    --window 5 --out exp_results/skew_on > skew_on.log 2>&1 || true

echo "=== archive CALIB evidence (dintcal) ==="
# every hardware round archives its measured evidence in dintcal's
# normalized form so a recalibration is one `dintcal fit` away
JAX_PLATFORMS=cpu python tools/dintcal.py gather dintscope_r10_*.json bench_hot_*.json \
    -o calib_evidence_hw_round10.json || true

echo "=== done ==="
