"""dintmesh (round 18): the whole (hosts x chips) mesh as ONE open-loop
transactional service (serve/mesh.py + the serve=True cohort form of
parallel/multihost_sb.py).

The contract under test, per acceptance criteria:
  * the mesh serving loop — per-host ingestion and NEWEST-FIRST
    shedding, ONE global SLO controller in per-device units, mesh-wide
    width switches at drain boundaries — is deterministic end-to-end
    under a VirtualClock on the 8-device virtual mesh;
  * the lane ledger closes across the mesh: occupancy + padded ==
    width x steps x devices, the per-host shed tallies mirror the
    device counter exactly, and per-host admission sums to the global
    report;
  * the double-buffered (overlap=True) serving plane produces the SAME
    service — admitted/committed/width trajectory — as the unoverlapped
    plane, with every prefetched lane accounted
    (route_prefetch_lanes == lock_requests);
  * the steady state allocates nothing: donated carry ping-pong only,
    overlap included;
  * tools/dintserve.py drives the mesh engine (--mesh HxC) under
    --virtual with unchanged exit-gate semantics.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from dint_tpu.parallel import multihost_sb as mh
from dint_tpu.serve import (ControllerCfg, MeshServeEngine, ServiceModel,
                            VirtualClock, constant_schedule,
                            poisson_schedule)

pytestmark = pytest.mark.serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.PRNGKey

H, C = 4, 2
D = H * C
N = 256
W, CPB = 16, 2


def _engine(overlap=False, widths=(8, W), mesh_shape=(H, C), seed=0):
    return MeshServeEngine(N, mesh_shape=mesh_shape,
                           cfg=ControllerCfg(widths=widths),
                           model=ServiceModel(),
                           cohorts_per_block=CPB, clock=VirtualClock(),
                           monitor=True, seed=seed, overlap=overlap)


def _identities(rep):
    assert rep["offered"] == rep["admitted"] + rep["shed"]
    c = rep["counters"]
    assert c["serve_occupancy_lanes"] == rep["admitted"] == rep["attempted"]
    assert c["serve_shed_lanes"] == rep["shed"]
    served = sum(int(w) * n for w, n in rep["steps_by_width"].items())
    # the mesh identity: D cohorts of width w serve on EVERY step
    assert c["serve_occupancy_lanes"] + c["serve_padded_lanes"] \
        == served * D
    # per-host admission sums to the global report
    assert sum(h["admitted"] for h in rep["per_host"]) == rep["admitted"]
    assert sum(h["shed"] for h in rep["per_host"]) == rep["shed"]
    assert c["route_ici_lanes"] + c["route_dcn_lanes"] == \
        c["lock_requests"] + c["install_writes"]


def test_mesh_engine_deterministic_and_ledger_closes():
    """The whole mesh serving loop is a pure function of (schedule,
    seed) under the VirtualClock — two runs give the SAME snapshot,
    field for field — and the mesh-wide lane ledger closes exactly."""
    reps = []
    for _ in range(2):
        eng = _engine()
        eng.run(poisson_schedule(300_000.0, 0.005, seed=3))
        eng.close()
        reps.append(eng.snapshot())
    assert reps[0] == reps[1]
    rep = reps[0]
    assert rep["mesh"] == {"n_hosts": H, "n_ici": C, "hierarchical": True,
                           "overlap": False}
    assert rep["offered"] > 0 and rep["committed"] > 0
    _identities(rep)
    # round-robin ingest: every host served arrivals
    assert all(h["admitted"] > 0 for h in rep["per_host"])


def test_mesh_engine_width_switch_is_mesh_coordinated():
    """A saturating burst drives the ONE global controller to the knee
    and back; each switch passes through _detach's drain — the
    recompile point that is the mesh-wide barrier — and the ledger
    still closes over the whole trajectory, sheds included."""
    eng = _engine()
    eng.run(constant_schedule(6_000_000.0, 0.004))
    eng.close()
    rep = eng.snapshot()
    ctl = rep["controller"]
    assert ctl["lanes_scale"] == D              # per-device units
    assert [w for _, w in ctl["switches"]].count(W) >= 1   # hit the knee
    assert rep["steps_by_width"][str(W)] > 0
    assert rep["shed"] > 0                      # admission did its job
    _identities(rep)
    # newest-first shedding is per host: every host's bound was enforced
    assert all(h["shed"] > 0 for h in rep["per_host"])


def test_mesh_engine_overlap_serves_identically():
    """The overlap A/B the PERF.md round-18 decision rule rests on: the
    double-buffered plane must change the SCHEDULE, never the service.
    Same arrivals => same admitted/shed/committed/width trajectory and
    same lock/install ledger; the only deltas are the overlap flag, the
    prefetch counter (== lock_requests), and the one extra drain step
    the in-flight cohort costs."""
    reps = {}
    for overlap in (False, True):
        eng = _engine(overlap=overlap)
        eng.run(poisson_schedule(400_000.0, 0.004, seed=7))
        eng.close()
        reps[overlap] = eng.snapshot()
    a, b = reps[False], reps[True]
    for k in ("offered", "admitted", "shed", "attempted", "committed",
              "blocks", "steps_by_width", "controller", "per_host"):
        assert a[k] == b[k], k
    assert a["mesh"]["overlap"] is False and b["mesh"]["overlap"] is True
    ca, cb = a["counters"], b["counters"]
    assert ca["route_prefetch_lanes"] == 0
    assert cb["route_prefetch_lanes"] == cb["lock_requests"] > 0
    for k in ("lock_requests", "install_writes", "txn_committed",
              "serve_occupancy_lanes", "serve_shed_lanes"):
        assert ca[k] == cb[k], k
    _identities(b)


def test_mesh_serve_zero_alloc_steady_state():
    """The round-17 zero-allocation pin survives the mesh AND the
    double buffer: after warmup every overlapped serve block runs
    through donated buffers — constant live-array census, the big
    sharded table leaf ping-pongs between at most two buffers."""
    mesh = mh.make_mesh_2d(H, C)
    # monitor=True matches the engine tests' config exactly, so the
    # builder memo shares the compile (and the census covers the
    # counter plane's carry leaves too)
    run, init, drain = mh.build_multihost_sb_runner(
        mesh, N, w=W, cohorts_per_block=CPB, monitor=True, serve=True,
        overlap=True)
    carry = init(mh.create_multihost_sb(mesh, N))
    occ = np.full((H, C, CPB), W, np.int32)
    shed = np.zeros((H, C, CPB), np.int32)

    def big_ptrs(c):
        leaf = max(jax.tree_util.tree_leaves(c), key=lambda x: x.nbytes)
        return tuple(s.data.unsafe_buffer_pointer()
                     for s in leaf.addressable_shards)

    for i in range(3):                          # warmup: compile + settle
        carry, s = run(carry, jax.random.fold_in(KEY(1), i), occ, shed)
    np.asarray(s)                               # sync
    base = len(jax.live_arrays())

    counts, ptrs = [], set()
    for i in range(3, 9):
        carry, s = run(carry, jax.random.fold_in(KEY(1), i), occ, shed)
        np.asarray(s)
        counts.append(len(jax.live_arrays()))
        ptrs.add(big_ptrs(carry))
    assert counts == [base] * 6, counts         # zero net allocations
    assert len(ptrs) <= 2, ptrs                 # donated ping-pong only
    drain(carry)


@pytest.mark.slow
def test_mesh_engine_3_host_reference_topology():
    """The reference's 3-machine shape serves too (every host holds a
    copy of every shard at H == replication factor); slow-marked per
    the tier-1 budget rule — the 3x2 geometry stays statically covered
    by the @h3 cost targets."""
    eng = _engine(mesh_shape=(3, 2))
    eng.run(poisson_schedule(200_000.0, 0.01, seed=1))
    eng.close()
    rep = eng.snapshot()
    assert rep["mesh"]["n_hosts"] == 3 and rep["committed"] > 0
    c = rep["counters"]
    served = sum(int(w) * n for w, n in rep["steps_by_width"].items())
    assert c["serve_occupancy_lanes"] + c["serve_padded_lanes"] \
        == served * 6
    assert sum(h["admitted"] for h in rep["per_host"]) == rep["admitted"]


@pytest.mark.slow
def test_mesh_engine_soak_reentrant_identities():
    """Soak: three back-to-back schedules (ramp, overload, trickle) on
    one long-lived OVERLAPPED mesh engine; the mesh-wide lane ledger
    must still close exactly across re-attaches."""
    eng = _engine(overlap=True, seed=2)
    start = 0.0
    for r, (rate, win) in enumerate([(150_000.0, 0.01),
                                     (6_000_000.0, 0.003),
                                     (30_000.0, 0.01)]):
        rep = eng.run(poisson_schedule(rate, win, seed=r, start_s=start))
        start = rep["elapsed_s"]
    eng.close()
    rep = eng.snapshot()
    _identities(rep)
    assert rep["shed"] > 0 and rep["committed"] > 0
    assert len(rep["controller"]["switches"]) >= 2
    assert rep["counters"]["route_prefetch_lanes"] == \
        rep["counters"]["lock_requests"] > 0


# -------------------------------------------------------------------- CLI


def _cli(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dintserve.py"),
         *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


def test_dintserve_cli_mesh_simulate_and_describe():
    """simulate --mesh rehearses the mesh plane (lanes_scale = H*C:
    8 devices absorb 8x the rate before the controller moves) and
    describe names the mesh waves and targets."""
    a = _cli("simulate", "--rate", "20000000", "--window", "0.004",
             "--mesh", "4x2", "--json")
    assert a.returncode == 0, a.stderr
    out = json.loads(a.stdout)
    assert out["mesh"] == [4, 2]
    b = _cli("simulate", "--rate", "20000000", "--window", "0.004",
             "--json")
    ref = json.loads(b.stdout)
    # same offered load looks 8x lighter per device: the mesh run's
    # width trajectory must stay at or below the single-device one
    assert out["final_width"] <= ref["final_width"]
    c = _cli("describe")
    assert c.returncode == 0, c.stderr
    for want in ("route_prefetch_lanes", "multihost_sb/serve@overlap",
                 "dint.multihost_sb.route_prefetch"):
        assert want in c.stdout, want


@pytest.mark.slow
def test_dintserve_cli_mesh_virtual_run():
    c = _cli("run", "--mesh", "4x2", "--size", str(N), "--rate", "200000",
             "--window", "0.01", "--widths", f"8,{W}", "--cpb",
             str(CPB), "--virtual", "--json")
    assert c.returncode == 0, c.stderr          # SLO gate: met -> exit 0
    rep = json.loads(c.stdout.strip().splitlines()[-1])
    assert rep["mesh"]["n_hosts"] == 4 and rep["mesh"]["n_ici"] == 2
    assert rep["offered"] == rep["admitted"] + rep["shed"] > 0
    assert rep["slo_met"] is True
    served = sum(int(w) * n for w, n in rep["steps_by_width"].items())
    assert rep["counters"]["serve_occupancy_lanes"] + \
        rep["counters"]["serve_padded_lanes"] == served * 8
