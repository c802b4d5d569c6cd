#!/bin/bash
# Round-6 hardware measurement plan: the Pallas DMA-ring kernel A/B
# (ISSUE 1 tentpole). Lands the cheapest decisive artifact first — the per-op microbench
# settles whether the ring beats XLA's gather per op, the bench pair
# settles what that buys end-to-end at n_sub=7e6.
cd "$(dirname "$0")/.." || exit 1

echo "=== stage 1: per-op A/B microbench (meta + val geometry + lock pass) ==="
timeout 1500 python tools/profile_pallas_hbm.py --compare \
    > pallas_ab.log 2>&1 || true
tail -3 pallas_ab.log

echo "=== stage 2: XLA baseline bench (profile + device trace) ==="
DINT_BENCH_PROFILE=1 DINT_BENCH_TRACE_DIR=trace_r6_xla \
    timeout 2200 python bench.py \
    > bench_xla.json 2> bench_xla_stderr.log
tail -1 bench_xla.json

echo "=== stage 3: pallas-path bench (profile) — the tentpole measurement ==="
DINT_USE_PALLAS=1 DINT_BENCH_PROFILE=1 DINT_BENCH_TRACE_DIR=trace_r6_pallas \
    timeout 2200 python bench.py \
    > bench_pallas.json 2> bench_pallas_stderr.log
tail -1 bench_pallas.json

echo "=== stage 4: dintscope per-wave attribution + regression gate ==="
# the A/B comes back pre-attributed: per-wave ms/step + effective HBM
# bandwidth for both traces, and the diff names exactly which waves the
# ring kernels moved (exit 1 = the pallas path REGRESSED a wave — that is
# the decision signal, recorded not fatal here)
for t in xla pallas; do
    if [ -d "trace_r6_${t}" ]; then
        python tools/dintscope.py report "trace_r6_${t}" \
            --geom w=8192 k=4 vw=10 --json \
            > "dintscope_r6_${t}.json" 2>> dintscope_r6.log || true
    fi
done
if [ -s dintscope_r6_xla.json ] && [ -s dintscope_r6_pallas.json ]; then
    python tools/dintscope.py diff dintscope_r6_xla.json \
        dintscope_r6_pallas.json | tail -8 || true
fi
# static prediction beside the measurement (dintcost, CPU-derived)
JAX_PLATFORMS=cpu python tools/dintcost.py report --all --json \
    > dintcost_r6.json 2>> dintscope_r6.log || true

echo "=== archive CALIB evidence (dintcal) ==="
# every hardware round archives its measured evidence in dintcal's
# normalized form so a recalibration is one `dintcal fit` away
JAX_PLATFORMS=cpu python tools/dintcal.py gather dintscope_r6_*.json bench_xla.json bench_pallas.json \
    -o calib_evidence_hw_round6.json || true

echo "=== done ==="
