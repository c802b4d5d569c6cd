#!/bin/bash
# Round-17 hardware measurement plan: dintserve, the always-on serving
# plane (ISSUE 14 tentpole). Lands the cheapest decisive
# artifact first. The claims under test (PERF.md round 17):
#   1. the serve path at occupancy == width costs what the closed loop
#      costs (bench serve probe vs the closed-loop headline);
#   2. the latency-vs-offered-load curve bends at a measurable knee,
#      with the queue/service split attributing every microsecond past
#      it to QUEUEING, not service (exp.py --only serve);
#   3. past saturation the plane sheds (counted host- AND device-side)
#      instead of stalling — achieved rate stays at the knee.
cd "$(dirname "$0")/.." || exit 1

echo "=== stage 1: bench with the serve saturation probe ==="
# one artifact carries the closed-loop headline AND the serving-plane
# capacity at the same width/geometry: the ingestion-overhead gap is the
# difference between two fields of the same JSON line
DINT_BENCH_SERVE=1 DINT_MONITOR=1 timeout 2600 python bench.py \
    > bench_serve.json 2> bench_serve_stderr.log
tail -1 bench_serve.json

echo "=== stage 2: latency-vs-offered-load curves ==="
# the tentpole measurement: open-loop Poisson schedules at a rate ladder
# anchored to the measured saturation point, TATP + SmallBank, exact
# queue/service percentile split + shed count per point
timeout 3600 python exp.py --out serve_results --window 10 --only serve \
    > serve_sweep.log 2>&1 || true
tail -5 serve_sweep.log
for f in serve_results/serve_*.json; do
    [ -e "$f" ] || continue
    python - "$f" <<'EOF'
import json, sys
d = json.load(open(sys.argv[1]))
print(f"{sys.argv[1]}: offered={d.get('offered_rate')}/s "
      f"achieved={d.get('achieved_rate')}/s shed={d.get('shed')} "
      f"queue_p99={d.get('p99_us')}us slo_met={d.get('slo_met')}")
EOF
done

echo "=== stage 3: SLO-tight low-rate point (width controller down) ==="
# the controller must settle at a SMALL width under a tight SLO at low
# rate (ms-scale p99), and at the knee width under saturation — the CPU
# tests pin both deterministically; this measures them on hardware
timeout 1200 python tools/dintserve.py run --engine tatp_dense \
    --size 7000000 --rate 20000 --window 5 --slo-us 2000 \
    --widths 256,1024,4096,8192 --json > serve_slo_tight.json || true
tail -1 serve_slo_tight.json

echo "=== stage 4: saturating point (width controller up + shed) ==="
timeout 1200 python tools/dintserve.py run --engine tatp_dense \
    --size 7000000 --rate 50000000 --window 1 --slo-us 5000 \
    --widths 256,1024,4096,8192 --no-gate --json \
    --journal serve_saturated_journal.jsonl \
    > serve_saturated.json || true
tail -1 serve_saturated.json

echo "=== stage 5: static model beside the measurements ==="
# the serve-step dintcost rows the measured numbers should agree with
# (derived on CPU, no chip time) + the wire-path pump's occupancy
# accounting from any shim run that happened this round
JAX_PLATFORMS=cpu python tools/dintcost.py report --all --json \
    > dintcost_r17.json 2> /dev/null || true
JAX_PLATFORMS=cpu python tools/dintserve.py describe || true

echo "=== stage 6: archive CALIB evidence + recalibration proposal ==="
# dintcal closes the loop: the measured (width, service) samples and
# journals feed a recalibration the operator pins as CALIB.json —
# never a hand edit
JAX_PLATFORMS=cpu python tools/dintcal.py gather serve_*.json \
    -o calib_evidence_serve.json || true
JAX_PLATFORMS=cpu python tools/dintcal.py propose \
    --evidence calib_evidence_serve.json -o CALIB.proposed.json || true
JAX_PLATFORMS=cpu python tools/dintcal.py audit \
    serve_saturated_journal.jsonl || true

echo "=== done ==="
