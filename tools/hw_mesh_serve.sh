#!/bin/bash
# Round-18 hardware measurement plan: dintmesh — the whole (hosts x
# chips) mesh as ONE open-loop transactional service, with the DCN
# exchange optionally double-buffered under the lock wave (ISSUE 16
# tentpole). Lands the cheapest decisive artifact first.
# Decision rule (PERF.md round 18, pre-registered): overlap=True ships
# default-on ONLY if
#   (a) tools/dintcost.py check --all is clean (overlap-dcn-parity and
#       overlap-footprint hold: same dcn bytes, only the priced double
#       buffer extra) — already enforced in CI, re-archived here;
#   (b) the dintscope A/B on device traces shows the route_prefetch
#       wave hidden under the owner waves (>= 80% of its issue-order
#       cost absorbed: the overlapped step time grows by < 20% of the
#       standalone exchange wave), i.e. `dintscope diff` off-vs-on is
#       clean after the route/route_prefetch alias fold;
#   (c) the overlapped serve_mesh leg is neutral-or-better on achieved
#       rate and p99 at every rate-ladder point (same admitted/shed by
#       construction — the CPU A/B test pins bit-identical service).
cd "$(dirname "$0")/.." || exit 1

MESH="${DINT_BENCH_MESH:-4x2}"

echo "=== stage 1: static model beside the measurement (CPU, no chip time) ==="
# the 5 multihost_sb/serve* rows + the overlap parity/footprint gates;
# archived so any wall-clock delta is explainable by a priced wave
JAX_PLATFORMS=cpu python tools/dintcost.py report --all --json \
    > dintcost_r18.json 2> dintcost_r18.log || true
JAX_PLATFORMS=cpu python tools/dintcost.py check --all | tail -3 || true

echo "=== stage 2: overlap A/B at ${MESH} (the tentpole measurement) ==="
# same mesh, same pre-drawn arrivals, same global controller; the ONLY
# difference is whether cohort i+1's host-aggregated DCN exchange is
# issued under cohort i's lock/arbitrate/validate waves. Device traces
# recorded per leg for stage 3's attribution.
DINT_BENCH_MESH="$MESH" DINT_MONITOR=1 DINT_SERVE_OVERLAP=0 \
    DINT_EXP_TRACE_DIR=trace_r18_off \
    timeout 3600 python exp.py --window 10 --only serve_mesh \
    --out serve_mesh_off > serve_mesh_off.log 2>&1 || true
tail -4 serve_mesh_off.log
DINT_BENCH_MESH="$MESH" DINT_MONITOR=1 DINT_SERVE_OVERLAP=1 \
    DINT_EXP_TRACE_DIR=trace_r18_on \
    timeout 3600 python exp.py --window 10 --only serve_mesh \
    --out serve_mesh_on > serve_mesh_on.log 2>&1 || true
tail -4 serve_mesh_on.log
for f in serve_mesh_off/serve_mesh_*.json serve_mesh_on/serve_mesh_*.json; do
    [ -e "$f" ] || continue
    python - "$f" <<'EOF'
import json, sys
d = json.load(open(sys.argv[1]))
print(f"{sys.argv[1]}: offered={d.get('offered_rate')}/s "
      f"achieved={d.get('achieved_rate')}/s shed={d.get('shed')} "
      f"p99={d.get('p99_us')}us prefetch="
      f"{(d.get('serve_counters') or {}).get('route_prefetch_lanes')}")
EOF
done

echo "=== stage 3: dintscope attribution + the overlap gate ==="
# per-wave breakdowns of both legs, then the CI-shaped gate: after the
# (route, route_prefetch) alias fold the overlapped leg must show NO
# regressed wave — a prefetch that stopped hiding (serialized behind
# the lock wave again) fails HERE, named dint.multihost_sb.route_prefetch,
# exactly like tests/test_dintscope.py's fixture regression test.
python tools/dintscope.py report trace_r18_off --steps 64 \
    -o scope_r18_off.json || true
python tools/dintscope.py report trace_r18_on --steps 64 \
    -o scope_r18_on.json || true
python tools/dintscope.py diff scope_r18_off.json scope_r18_on.json \
    && echo "OVERLAP GATE: clean (exchange hidden)" \
    || echo "OVERLAP GATE: REGRESSION (see named waves above)"

echo "=== stage 4: saturating mesh point (global controller + shed) ==="
# one global controller in per-device units: the knee width and the
# per-host newest-first sheds, measured at the real geometry
DINT_BENCH_MESH="$MESH" timeout 1200 python tools/dintserve.py run \
    --mesh "$MESH" --size 1000000 --rate 50000000 --window 1 \
    --slo-us 5000 --widths 256,1024,4096 --overlap --no-gate --json \
    --journal serve_mesh_journal.jsonl \
    > serve_mesh_saturated.json || true
tail -1 serve_mesh_saturated.json

echo "=== stage 5: monitored reconciliation (prefetch ledger on hw) ==="
# route_prefetch_lanes == lock_requests must hold on hardware exactly
# as the CPU tests pin it; route_ici + route_dcn == lock + install both
# modes (counters.py invariants)
DINT_BENCH_MESH="$MESH" DINT_MONITOR=1 DINT_SERVE_OVERLAP=1 \
    DINT_MONITOR_JSONL=mon_r18_mesh.jsonl \
    timeout 1200 python exp.py --quick --only serve_mesh \
    --out serve_mesh_mon > serve_mesh_mon.log 2>&1 || true
python tools/dintmon.py summarize mon_r18_mesh.jsonl | tail -8 || true

echo "=== stage 6: archive CALIB evidence + recalibration proposal ==="
# mesh-measured (width, service) samples + the per-host shed journal
# feed the dintcal loop: re-pin with `dintplan plan --calib`, never a
# DINT_PLAN_OVERRIDE=1 hand edit
JAX_PLATFORMS=cpu python tools/dintcal.py gather serve_mesh_*.json \
    -o calib_evidence_mesh.json || true
JAX_PLATFORMS=cpu python tools/dintcal.py propose \
    --evidence calib_evidence_mesh.json -o CALIB.mesh.proposed.json \
    || true
JAX_PLATFORMS=cpu python tools/dintcal.py audit serve_mesh_journal.jsonl \
    || true

echo "=== done ==="
