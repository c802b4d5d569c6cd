#!/bin/bash
# Round-20 hardware measurement plan: dintscan, sequential-DMA range
# scans over the ordered store run (ISSUE 20 tentpole). Lands the cheapest
# decisive artifact first. The claims under test (PERF.md round 20):
#   1. the scan path is bandwidth-bound, not packet-bound: GB/s on the
#      95%-scan ladder point approaches the point-gather route's GB/s
#      at a fraction of the request rate (sequential rows amortize the
#      per-lane overhead the @scan dintcost rows price at 56 B/row vs
#      92 B/probe);
#   2. the scan-fraction ladder (0/5/50/95%) bends throughput DOWN in
#      requests/s but UP in rows/s — the crossover is the artifact.
cd "$(dirname "$0")/.." || exit 1

echo "=== stage 1: scan-fraction ladder ==="
# the tentpole measurement: YCSB-B (0%) through YCSB-E (95%) at one
# width, Zipfian starts, run rebuilt at every drain boundary; every
# artifact carries the "scan" object (resolved routes + mix)
timeout 3600 python exp.py --out scan_results \
    --window 10 --only store_scan > scan_sweep.log 2>&1 || true
tail -5 scan_sweep.log
for f in scan_results/store_scan_*.json; do
    [ -e "$f" ] || continue
    python - "$f" <<'EOF'
import json, sys
d = json.load(open(sys.argv[1]))
s = d.get("scan") or {}
print(f"{sys.argv[1]}: goodput={d.get('goodput')}/s "
      f"p99={d.get('p99_us')}us frac={s.get('scan_frac')} "
      f"max={s.get('scan_max')}")
EOF
done

echo "=== stage 2: static model beside the measurements ==="
# the @scan dintcost rows the measured bytes should agree with,
# including the scan-bytes-dominance gate (56 B/row < 92 B/probe at
# the calibration geometry) — derived on CPU, no chip time
JAX_PLATFORMS=cpu python tools/dintcost.py report --all --json \
    > dintcost_r20.json 2> /dev/null || true
JAX_PLATFORMS=cpu python tools/dintcost.py check --all || true

echo "=== stage 3: archive CALIB evidence + recalibration proposal ==="
# dintcal closes the loop: ladder artifacts feed a recalibration the
# operator pins as CALIB.json, never a hand edit
JAX_PLATFORMS=cpu python tools/dintcal.py gather scan_results/*.json \
    -o calib_evidence_scan.json || true
JAX_PLATFORMS=cpu python tools/dintcal.py propose \
    --evidence calib_evidence_scan.json -o CALIB.proposed.json || true

echo "=== done ==="
