"""dintserve: the always-on serving plane (ISSUE 14 tentpole).

The acceptance pins, per ISSUE.md:
  * serving is a MASKING of batch certification, not a fork of it: at
    occupancy == width the serve path is bit-identical to the closed
    loop on the same fold_in key sequence, and a bursty schedule whose
    bursts straddle block boundaries still replays the closed-loop
    table state exactly;
  * zero steady-state allocation: after warmup the donated carry
    ping-pongs through the same buffers and jax.live_arrays() stays
    constant block over block;
  * the SLO controller moves BOTH directions deterministically on CPU:
    small width under a tight SLO at low rate (ms-scale queue p99),
    the knee width + shedding under saturation — and the whole serve
    loop under a VirtualClock is a pure function of (schedule, seed);
  * the lane ledger reconciles: occupancy + padded == width x serving
    steps, shed counted host-side AND mirrored device-side, and
    offered == admitted + shed (no arrival silently dropped).

Geometry matches tests/test_dintmon.py (tiny tables, W=64, CPB=2) so
every jit here compiles in seconds inside the tier-1 budget.
"""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from dint_tpu.serve import (ArrivalStream, ControllerCfg, ServeEngine,
                            ServiceModel, VirtualClock, WidthController,
                            burst_schedule, cached_runner, choose_width,
                            constant_schedule, make_schedule, max_backlog,
                            poisson_schedule, simulate_widths)

pytestmark = pytest.mark.serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.PRNGKey

N_SUB = 300
N_ACC = 400
W = 64
VW = 4
CPB = 2


# ------------------------------------------------------ arrival schedules


def test_constant_schedule_spacing():
    s = constant_schedule(1000.0, 0.01)
    assert len(s) == 10
    assert np.allclose(np.diff(s), 1e-3)
    assert s[0] > 0 and s[-1] <= 0.01 + 1e-12
    assert len(constant_schedule(1000.0, 0.0)) == 0


def test_poisson_schedule_deterministic_and_windowed():
    a = poisson_schedule(50_000.0, 0.01, seed=7)
    b = poisson_schedule(50_000.0, 0.01, seed=7)
    c = poisson_schedule(50_000.0, 0.01, seed=8)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert (np.diff(a) >= 0).all() and (a < 0.01).all() and (a >= 0).all()
    # rate is approximately honoured (Poisson count, generous bound)
    assert 0.5 * 500 < len(a) < 1.5 * 500


def test_burst_schedule_shape():
    s = burst_schedule(100_000.0, 0.01, burst_lanes=128,
                       burst_every_s=0.002, seed=0)
    assert (np.diff(s) >= 0).all()
    # 5 bursts at (i + 0.5) * 2ms, each exactly burst_lanes strong
    for i in range(5):
        t = (i + 0.5) * 0.002
        assert int((s == t).sum()) == 128
    # baseline takes the residual rate; total is in the right ballpark
    assert 5 * 128 <= len(s) < 2 * 100_000 * 0.01


def test_make_schedule_factory():
    assert len(make_schedule("constant", 1000.0, 0.01)) == 10
    assert np.array_equal(make_schedule("poisson", 1000.0, 0.01, seed=3),
                          poisson_schedule(1000.0, 0.01, seed=3))
    s = make_schedule("burst", 10_000.0, 0.01, seed=1, burst_lanes=16,
                      burst_every_s=0.005)
    assert len(s) > 0
    with pytest.raises(ValueError):
        make_schedule("uniform", 1.0, 1.0)


def test_arrival_stream_cursor():
    st = ArrivalStream(np.array([0.1, 0.2, 0.2, 0.5]))
    assert len(st) == 4 and st.peek() == 0.1 and not st.exhausted
    got = st.take_until(0.2)
    assert got.tolist() == [0.1, 0.2, 0.2]
    assert len(st) == 1 and st.peek() == 0.5
    assert st.take_until(0.3).tolist() == []
    assert st.take_until(1.0).tolist() == [0.5]
    assert st.exhausted and st.peek() is None
    with pytest.raises(AssertionError):
        ArrivalStream(np.array([0.2, 0.1]))


# ----------------------------------------------------- controller policy


def _svc(cfg, m):
    return {w: m.service_us(w) for w in cfg.widths}


def test_choose_width_moves_both_directions():
    cfg, m = ControllerCfg(), ServiceModel()
    s = _svc(cfg, m)
    # low rate: smallest feasible width wins (lowest latency)
    assert choose_width(1_000.0, s, cfg) == (256, False)
    # mid rate infeasible for 256 climbs exactly one notch
    assert choose_width(2.0e6, s, cfg) == (1024, False)
    # nothing feasible: the knee (max-capacity) width + saturated flag
    assert choose_width(100e6, s, cfg) == (8192, True)


def test_choose_width_tight_slo_blocks_big_cohorts():
    m = ServiceModel()
    tight = ControllerCfg(slo_us=500.0)   # block time may eat 250us
    s = _svc(tight, m)
    # service(256)=160us fits, service(4096)=314us does not
    assert choose_width(1_000.0, s, tight) == (256, False)
    # rate beyond 256's capacity with the SLO blocking everything bigger
    w, sat = choose_width(5e6, s, tight)
    assert sat and w == 8192              # knee: shed rather than stall


def test_max_backlog_floor_and_growth():
    cfg, m = ControllerCfg(), ServiceModel()
    assert max_backlog(64, 1e9, cfg) == 64          # floor: one cohort
    small = max_backlog(256, m.service_us(256), cfg)
    big = max_backlog(8192, m.service_us(8192), cfg)
    assert big > small > 256


def test_width_controller_hysteresis_and_both_directions():
    cfg, m = ControllerCfg(), ServiceModel()
    ctl = WidthController(cfg, m)
    assert ctl.width() == 256             # cold start: smallest width
    ctl.observe_service(256, m.service_us(256))
    ctl.observe_rate(50e6)
    assert ctl.width() == 256             # hysteresis holds the switch
    for _ in range(cfg.hysteresis_blocks - 1):
        ctl.observe_service(256, m.service_us(256))
    assert ctl.width() == 8192            # window elapsed: knee width
    assert ctl.saturated and ctl.switches[-1][1] == 8192
    # load vanishes: the controller comes back DOWN
    for _ in range(cfg.hysteresis_blocks):
        ctl.observe_service(8192, m.service_us(8192))
    for _ in range(40):
        ctl.observe_rate(0.0)             # EWMA decays toward zero
    assert ctl.width() == 256 and not ctl.saturated
    assert ctl.switches[-1][1] == 256 and len(ctl.switches) == 2


def test_simulate_widths_deterministic_and_moves():
    cfg, m = ControllerCfg(), ServiceModel()
    lo = simulate_widths(constant_schedule(1_000.0, 0.05), cfg, m)
    assert lo and set(lo) == {256}        # low rate never leaves small
    hi = simulate_widths(constant_schedule(20e6, 0.004), cfg, m)
    assert hi[-1] == 8192                 # saturation climbs to the knee
    assert hi[0] == 256                   # ... starting from the bottom
    again = simulate_widths(constant_schedule(20e6, 0.004), cfg, m)
    assert hi == again                    # pure function of the schedule


# --------------------------------------------------- serve-mode builders


def _td_build(serve, monitor=False):
    # cached_runner so every test (and the ServeEngine tests below)
    # shares one compile per distinct config within the process
    return cached_runner("tatp_dense", N_SUB, val_words=VW, w=W,
                         cohorts_per_block=CPB, monitor=monitor,
                         trace=False, serve=serve)


def _closed_loop_tatp(blocks, seed=0):
    from dint_tpu.engines import tatp_dense as td

    db = td.populate(np.random.default_rng(seed), N_SUB, val_words=VW)
    run, init, drain = _td_build(False)
    carry = init(db)
    tot = np.zeros(td.N_STATS, np.int64)
    for i in range(blocks):
        carry, s = run(carry, jax.random.fold_in(KEY(seed), i))
        tot += np.asarray(s, np.int64).sum(axis=0)
    out = drain(carry)
    tot += np.asarray(out[1], np.int64).sum(axis=0)
    return out[0], tot


def _assert_trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_serve_full_occupancy_bit_identical_to_closed_loop():
    """occ == width on the same fold_in keys replays the closed loop
    exactly: same table state, same stats. Serving = masking."""
    from dint_tpu.engines import tatp_dense as td

    blocks = 3
    db = td.populate(np.random.default_rng(0), N_SUB, val_words=VW)
    run, init, drain = _td_build(True)
    carry = init(db)
    occ = np.full(CPB, W, np.int32)
    shed = np.zeros(CPB, np.int32)
    tot = np.zeros(td.N_STATS, np.int64)
    for i in range(blocks):
        carry, s = run(carry, jax.random.fold_in(KEY(0), i), occ, shed)
        tot += np.asarray(s, np.int64).sum(axis=0)
    out = drain(carry)
    tot += np.asarray(out[1], np.int64).sum(axis=0)

    db_ref, tot_ref = _closed_loop_tatp(blocks)
    assert tot.tolist() == tot_ref.tolist()
    _assert_trees_equal(out[0], db_ref)


def test_serve_zero_alloc_steady_state():
    """The zero-allocation pin: after warmup, every serve block runs
    through donated buffers — the live-array census is constant block
    over block and the big table leaf ping-pongs through at most two
    device buffers (double buffer), never a fresh allocation."""
    from dint_tpu.engines import tatp_dense as td

    run, init, drain = _td_build(True)
    db = td.populate(np.random.default_rng(1), N_SUB, val_words=VW)
    carry = init(db)
    occ = np.full(CPB, W, np.int32)
    shed = np.zeros(CPB, np.int32)

    def big_ptr(c):
        leaf = max(jax.tree_util.tree_leaves(c), key=lambda x: x.nbytes)
        return leaf.unsafe_buffer_pointer()

    for i in range(3):                          # warmup: compile + settle
        carry, s = run(carry, jax.random.fold_in(KEY(1), i), occ, shed)
    np.asarray(s)                               # sync
    base = len(jax.live_arrays())

    counts, ptrs = [], set()
    for i in range(3, 9):
        carry, s = run(carry, jax.random.fold_in(KEY(1), i), occ, shed)
        np.asarray(s)
        counts.append(len(jax.live_arrays()))
        ptrs.add(big_ptr(carry))
    assert counts == [base] * 6, counts         # zero net allocations
    assert len(ptrs) <= 2, ptrs                 # donated ping-pong only
    drain(carry)


# ----------------------------------------------------------- ServeEngine


def test_serve_engine_bursty_straddle_bit_identical():
    """Bursts that straddle block boundaries (200 arrivals into 128-lane
    blocks) still fill every cohort exactly — the backlog carries the
    tail across the boundary — so the served table state is
    bit-identical to the closed loop on the same keys."""
    eng = ServeEngine("tatp_dense", N_SUB, cfg=ControllerCfg(widths=(W,)),
                      cohorts_per_block=CPB, val_words=VW,
                      clock=VirtualClock(), monitor=True, seed=0)
    # 3 blocks x 2 cohorts x 64 lanes = 384, delivered as misaligned
    # bursts; under the service model the backlog never empties, so
    # every cohort serves at full occupancy
    sched = np.sort(np.concatenate([np.zeros(200),
                                    np.full(100, 2e-4),
                                    np.full(84, 4e-4)]))
    rep = eng.run(sched)
    eng.close()
    rep = eng.snapshot()

    assert rep["blocks"] == 3
    assert rep["offered"] == rep["admitted"] == rep["attempted"] == 384
    assert rep["shed"] == 0
    c = rep["counters"]
    assert c["serve_occupancy_lanes"] == 384
    assert c["serve_padded_lanes"] == 0         # every cohort was full
    assert c["serve_shed_lanes"] == 0

    db_ref, tot_ref = _closed_loop_tatp(3)
    assert rep["committed"] == int(tot_ref[1])
    _assert_trees_equal(eng._db, db_ref)


def test_serve_engine_idle_gap_never_dispatches_empty():
    """Two bursts separated by a long idle gap: the loop parks until the
    next arrival instead of dispatching empty blocks — exactly 2 blocks,
    zero padding, and the gap shows up in elapsed time only."""
    eng = ServeEngine("tatp_dense", N_SUB, cfg=ControllerCfg(widths=(W,)),
                      cohorts_per_block=CPB, val_words=VW,
                      clock=VirtualClock(), monitor=True, seed=0)
    sched = np.sort(np.concatenate([np.zeros(CPB * W),
                                    np.full(CPB * W, 0.1)]))
    rep = eng.run(sched)
    eng.close()
    rep = eng.snapshot()
    assert rep["blocks"] == 2                   # no empty dispatches
    assert rep["counters"]["serve_padded_lanes"] == 0
    assert rep["admitted"] == rep["attempted"] == 2 * CPB * W
    assert rep["elapsed_s"] >= 0.1              # the gap was slept, not spun


def test_serve_engine_low_rate_tight_slo_stays_small():
    """Down-direction pin: at low rate the controller serves at the
    SMALLEST width — queue p99 stays ms-scale and the SLO verdict is
    MET — with partial-occupancy cohorts billed as padding."""
    eng = ServeEngine("smallbank_dense", N_ACC,
                      cfg=ControllerCfg(widths=(16, W)),
                      cohorts_per_block=CPB, clock=VirtualClock(),
                      monitor=True, seed=0)
    rep = eng.run(constant_schedule(10_000.0, 0.02))
    eng.close()
    rep = eng.snapshot()

    ctl = rep["controller"]
    assert ctl["width"] == 16 and not ctl["saturated"]
    assert ctl["switches"] == []                # never left the small width
    assert rep["shed"] == 0
    assert rep["offered"] == rep["admitted"] == 200
    assert rep["slo_met"] and 0 < rep["queue"]["p99"] <= rep["slo_us"]
    c = rep["counters"]
    assert c["serve_padded_lanes"] > 0          # open loop: partial cohorts
    served = sum(int(w) * n for w, n in rep["steps_by_width"].items())
    assert c["serve_occupancy_lanes"] + c["serve_padded_lanes"] == served
    assert c["serve_occupancy_lanes"] == rep["admitted"]
    assert c["serve_shed_lanes"] == 0


def _overload_run(seed=0):
    eng = ServeEngine("smallbank_dense", N_ACC,
                      cfg=ControllerCfg(widths=(16, W)),
                      cohorts_per_block=CPB, clock=VirtualClock(),
                      monitor=True, seed=seed)
    eng.run(constant_schedule(800_000.0, 0.01))
    eng.close()
    return eng.snapshot()


def test_serve_engine_saturation_sheds_then_recovers():
    """Up-direction pin: a saturating burst drives the controller to the
    knee width with admission shedding (host tally mirrored exactly into
    the device ledger); while the tail drains and the offered-rate EWMA
    decays, it switches back down — BOTH directions in one trajectory."""
    rep = _overload_run()
    ctl = rep["controller"]
    switch_widths = [w for _, w in ctl["switches"]]
    assert W in switch_widths                   # climbed to the knee
    assert switch_widths[-1] == 16              # ... and came back down
    assert rep["steps_by_width"][str(W)] > 0    # really SERVED at the knee
    assert rep["steps_by_width"]["16"] > 0
    assert ctl["width"] == 16 and not ctl["saturated"]  # recovered
    # no arrival unaccounted; shed mirrored host == device
    assert rep["offered"] == rep["admitted"] + rep["shed"]
    c = rep["counters"]
    assert c["serve_shed_lanes"] == rep["shed"] > 0
    assert c["serve_occupancy_lanes"] == rep["admitted"] == rep["attempted"]
    served = sum(int(w) * n for w, n in rep["steps_by_width"].items())
    assert c["serve_occupancy_lanes"] + c["serve_padded_lanes"] == served


def test_serve_engine_deterministic_under_virtual_clock():
    """The whole serving loop — ingestion, width switches, shedding,
    counters, histograms — is a pure function of (schedule, seed) under
    the VirtualClock: two runs produce the SAME snapshot, field for
    field."""
    assert _overload_run() == _overload_run()


@pytest.mark.slow
def test_serve_engine_soak_reentrant_identities():
    """Soak: three back-to-back schedules (ramp, overload, trickle) on
    one long-lived engine; the lane ledger must still close exactly."""
    eng = ServeEngine("smallbank_dense", N_ACC,
                      cfg=ControllerCfg(widths=(16, W)),
                      cohorts_per_block=CPB, clock=VirtualClock(),
                      monitor=True, seed=2)
    start = 0.0
    for r, (rate, win) in enumerate([(50_000.0, 0.05), (900_000.0, 0.02),
                                     (8_000.0, 0.05)]):
        rep = eng.run(poisson_schedule(rate, win, seed=r, start_s=start))
        start = rep["elapsed_s"]
    eng.close()
    rep = eng.snapshot()
    assert rep["offered"] == rep["admitted"] + rep["shed"]
    c = rep["counters"]
    assert c["serve_occupancy_lanes"] == rep["admitted"] == rep["attempted"]
    assert c["serve_shed_lanes"] == rep["shed"] > 0
    served = sum(int(w) * n for w, n in rep["steps_by_width"].items())
    assert c["serve_occupancy_lanes"] + c["serve_padded_lanes"] == served
    assert rep["committed"] <= rep["attempted"]
    assert len(rep["controller"]["switches"]) >= 2


def test_serve_engine_store_scan_counters_reconcile():
    """dintscan on the serve plane: the store engine family serves an
    open-loop GET/SET/SCAN mix, the ordered run rebuilds at drain
    boundaries, and the scan counter plane reconciles — every admitted
    lane lands in the ledger and scan rows stay within the static slab
    bound (scan_max x requests)."""
    eng = ServeEngine("store", N_ACC,
                      cfg=ControllerCfg(widths=(W,)),
                      cohorts_per_block=CPB, clock=VirtualClock(),
                      monitor=True, seed=3,
                      runner_kw=dict(use_scan=True, scan_frac=0.5,
                                     max_scan_len=6, scan_max=8,
                                     read_frac=0.5))
    rep = eng.run(poisson_schedule(80_000.0, 0.05, seed=5))
    eng.close()
    assert rep["offered"] == rep["admitted"] + rep["shed"]
    assert rep["admitted"] > 0
    c = rep["counters"]
    assert c["serve_occupancy_lanes"] == rep["admitted"]
    served = sum(int(w) * n for w, n in rep["steps_by_width"].items())
    assert c["serve_occupancy_lanes"] + c["serve_padded_lanes"] == served
    # the scan plane: ~half the admitted lanes issue Op.SCAN; replies
    # carry at most scan_max rows each; overlay hits only on scanned rows
    assert 0 < c["scan_requests"] < rep["admitted"]
    assert 0 < c["scan_rows"] <= 8 * c["scan_requests"]
    assert 0 <= c["scan_delta_hits"] <= c["scan_rows"]
    # stale-scan RETRYs are the only non-committed admitted lanes
    assert rep["committed"] <= rep["admitted"]


def test_serve_engine_store_scan_off_has_silent_counters():
    """use_scan=False: no run threaded, no scan counters bumped — the
    default-off decision rule leaves the serve plane bit-identical to
    the pre-dintscan store family."""
    eng = ServeEngine("store", N_ACC, cfg=ControllerCfg(widths=(W,)),
                      cohorts_per_block=CPB, clock=VirtualClock(),
                      monitor=True, seed=3,
                      runner_kw=dict(use_scan=False))
    rep = eng.run(poisson_schedule(50_000.0, 0.04, seed=6))
    eng.close()
    assert rep["admitted"] > 0 and rep["committed"] == rep["admitted"]
    c = rep["counters"]
    assert c["scan_requests"] == c["scan_rows"] == c["scan_delta_hits"] == 0


# ------------------------------------------------------------- shim pump


def test_pump_depth_and_occupancy_knobs():
    """Satellite (a): the host pump's ring depth and idle poll interval
    are constructor knobs, and latency_snapshot() carries the dintserve
    occupancy accounting (identity: occupancy + padded == width x
    batches) plus the C++-side shed count."""
    from dint_tpu.engines import store
    from dint_tpu.shim import STORE, EnginePump, ShimClient
    from dint_tpu.tables import kv

    table = kv.create(1 << 8, val_words=10)
    with pytest.raises(AssertionError):
        EnginePump(STORE, store.step, table, width=64, depth=0)
    with EnginePump(STORE, store.step, table, width=64, flush_us=2000,
                    depth=3, idle_poll_us=1000).start() as p:
        with ShimClient("127.0.0.1", p.port) as c:
            for _ in range(12):                 # absorb the first compile
                r = c.exchange(np.zeros(1, np.uint8),
                               np.array([1], np.uint64), timeout_ms=10_000)
                if r["n"] == 1:
                    break
            else:
                pytest.fail("pump did not answer warmup exchanges")
        # the reply goes out before the pump thread's tally lands; give
        # the bookkeeping a beat before snapshotting
        for _ in range(500):
            if p.batches_served >= 1:
                break
            time.sleep(0.01)
        snap = p.latency_snapshot()
    assert snap["width"] == 64 and snap["depth"] == 3
    assert snap["batches"] >= 1
    assert snap["occupancy_lanes"] >= 1
    assert snap["occupancy_lanes"] + snap["padded_lanes"] == \
        64 * snap["batches"]
    assert snap["shed"] == 0
    assert {"p50_us", "p99_us", "hist"} <= set(snap["queue"])
    assert {"p50_us", "p99_us", "hist"} <= set(snap["service"])


# -------------------------------------------------------------------- CLI


def _cli(*args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dintserve.py"),
         *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


def test_dintserve_cli_describe_and_simulate():
    c = _cli("describe")
    assert c.returncode == 0, c.stderr
    for want in ("serve_occupancy_lanes", "serve_padded_lanes",
                 "serve_shed_lanes", "tatp_dense/serve",
                 "controller defaults"):
        assert want in c.stdout
    a = _cli("simulate", "--rate", "20000000", "--window", "0.004",
             "--json")
    assert a.returncode == 0, a.stderr
    out = json.loads(a.stdout)
    assert out["final_width"] == 8192 and out["blocks"] > 0
    b = _cli("simulate", "--rate", "20000000", "--window", "0.004",
             "--json")
    assert a.stdout == b.stdout                 # deterministic


@pytest.mark.slow
def test_dintserve_cli_virtual_run():
    c = _cli("run", "--engine", "tatp_dense", "--size", str(N_SUB),
             "--rate", "30000", "--window", "0.02", "--widths", str(W),
             "--cpb", str(CPB), "--virtual", "--json")
    assert c.returncode == 0, c.stderr          # SLO gate: met -> exit 0
    rep = json.loads(c.stdout.strip().splitlines()[-1])
    assert rep["offered"] > 0
    assert rep["offered"] == rep["admitted"] + rep["shed"]
    assert rep["slo_met"] is True
    assert rep["counters"]["serve_occupancy_lanes"] == rep["admitted"]
    served = sum(int(w) * n for w, n in rep["steps_by_width"].items())
    assert rep["counters"]["serve_occupancy_lanes"] + \
        rep["counters"]["serve_padded_lanes"] == served


# ------------------------------------- controller edges (ISSUE 17 pins)


def test_choose_width_exactly_at_knee_capacity():
    """Boundary pin: the rate check is INCLUSIVE (cap >= offered x
    headroom) — at EXACTLY the knee's capacity the knee is still
    feasible (no shedding), one epsilon past it the controller
    saturates. headroom=1.0 makes the boundary float-exact because the
    test computes capacity with the controller's own arithmetic."""
    cfg, m = ControllerCfg(headroom=1.0), ServiceModel()
    s = _svc(cfg, m)
    knee = cfg.widths[-1]                    # max-capacity width
    cap = knee / (m.service_us(knee) * 1e-6)
    assert choose_width(cap, s, cfg) == (knee, False)
    assert choose_width(cap * (1 + 1e-9), s, cfg) == (knee, True)
