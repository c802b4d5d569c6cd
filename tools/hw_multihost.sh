#!/bin/bash
# Round-14 hardware measurement plan: hierarchical cross-shard 2PC over
# the 2-D (dcn x ici) mesh (ISSUE 11 tentpole). Lands the cheapest decisive
# artifact first. The static half of the decision rule (dintcost strict
# DCN-byte dominance at every calibrated 2-D geometry) is already
# enforced in CI; this script settles the dynamic half — the
# hierarchical-vs-flat transport A/B at the same global geometry, where
# outputs are BIT-IDENTICAL and only the collective decomposition
# differs.
# Decision rule (PERF.md round 14, pre-registered): hierarchical=True
# ships default-on only if tools/dintcost.py check --all is clean
# (hier-dcn-dominance holds everywhere) AND the hierarchical bench leg
# is no slower than the flat leg on the measured mesh.
cd "$(dirname "$0")/.." || exit 1

MESH="${DINT_BENCH_MESH:-4x2}"

echo "=== stage 1: static model beside the measurement (CPU, no chip time) ==="
# per-axis ici/dcn link bytes for every 2-D target + the dominance gate;
# archived next to the bench artifacts so a throughput delta is
# explainable by the wave whose dcn bytes moved
JAX_PLATFORMS=cpu python tools/dintcost.py report --all --json \
    > dintcost_r14.json 2> dintcost_r14.log || true
JAX_PLATFORMS=cpu python tools/dintcost.py check --all \
    | tail -3 || true

echo "=== stage 2: hierarchical-vs-flat A/B at ${MESH} ==="
# exp.py --only multihost_sb runs BOTH legs (multihost_sb_hier_* and
# multihost_sb_flat_*) over the same mesh; every point records
# n_shards + {n_hosts, n_ici, axes} so the artifact is self-describing.
# On a single-host TPU the "dcn" axis degrades to ICI permutes — the
# A/B then prices only the extra exchange stage; the DCN win itself is
# the statically-asserted half of the rule.
DINT_BENCH_MESH="$MESH" DINT_MONITOR=1 \
    timeout 2200 python exp.py --window 10 --only multihost_sb \
    --out exp_r14_mesh > exp_r14_mesh.log 2>&1 || true
tail -4 exp_r14_mesh.log

echo "=== stage 3: monitored run (per-axis route-counter reconciliation) ==="
# route_ici_lanes + route_dcn_lanes must equal lock_requests +
# install_writes (counters.py invariant) on hardware like in CI; the
# split itself is the measured ici/dcn traffic ratio to hold against
# stage 1's static prediction
DINT_BENCH_MESH="$MESH" DINT_MONITOR=1 \
    DINT_MONITOR_JSONL=mon_r14_mesh.jsonl \
    timeout 1200 python exp.py --quick --only multihost_sb \
    --out exp_r14_mon > exp_r14_mon.log 2>&1 || true
python tools/dintmon.py summarize mon_r14_mesh.jsonl | tail -8 || true

echo "=== stage 4: decision ==="
for leg in hier flat; do
    for f in exp_r14_mesh/multihost_sb_${leg}_closed_*.json; do
        [ -f "$f" ] && python -c "
import json, sys
d = json.load(open('$f'))
print('$leg', d.get('extra', d).get('width'), 'goodput',
      round(d.get('goodput', 0), 1))" || true
    done
done
echo "apply the PERF.md round-14 rule to the two goodput lines above"
echo "=== archive CALIB evidence (dintcal) ==="
# every hardware round archives its measured evidence in dintcal's
# normalized form so a recalibration is one `dintcal fit` away
JAX_PLATFORMS=cpu python tools/dintcal.py gather exp_results/*.json \
    -o calib_evidence_hw_multihost.json || true

echo "=== done ==="
