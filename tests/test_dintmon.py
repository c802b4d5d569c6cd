"""dintmon: device counter plane + trace layer (OBSERVABILITY.md).

The contract under test, per acceptance criteria:
  * counter totals RECONCILE with the stats vector the host already
    fetches (committed/aborted by cause), drains included, on both dense
    engines, both generic pipelines, and both sharded paths;
  * counters are reproducible (same seed -> same values), bit-identical
    between the generic and dense engines on the parity workloads
    (PARITY_NAMES);
  * per-device counters sum across shards to the psummed stats totals;
  * monitoring OFF (the default) changes no engine output;
  * the JSONL trace schema is stable and the dintmon CLI works end to end.

Builders are cached at module scope (one compile per configuration) so
the whole file stays cheap inside the tier-1 budget; every test drives a
FRESH population through the shared compiled runner.
"""
import functools
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from dint_tpu import monitor as M
from dint_tpu.monitor import counters as mc

pytestmark = pytest.mark.monitor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.PRNGKey

# one shared tiny geometry -> one compile per (engine, monitor, backend)
N_SUB = 300
N_ACC = 400
W = 64
VW = 4
CPB = 2


# --------------------------------------------------------------- registry


def test_registry_is_schema_stable():
    assert len(mc.ALL_NAMES) == mc.N_COUNTERS
    assert len(set(mc.ALL_NAMES)) == mc.N_COUNTERS          # unique names
    assert [mc.COUNTER_INDEX[n] for n in mc.ALL_NAMES] == \
        list(range(mc.N_COUNTERS))                          # contiguous
    for n in mc.ALL_NAMES:
        assert mc.COUNTER_KINDS[n] in (mc.FLOW, mc.GAUGE)
        assert mc.COUNTER_DOCS[n]
    assert set(mc.PARITY_NAMES) <= set(mc.ALL_NAMES)
    assert "ring_hwm" in mc.GAUGE_NAMES


def test_delta_wraps_u32():
    prev = dict(mc.zeros_dict(), txn_attempted=0xFFFF_FFF0)
    cur = dict(mc.zeros_dict(), txn_attempted=0x10)
    d = mc.delta(cur, prev)
    assert d["txn_attempted"] == 0x20       # wrapped, still exact
    assert mc.delta(cur, None)["txn_attempted"] == 0x10


# ------------------------------------------------------- cached builders


@functools.lru_cache(maxsize=None)
def _td_build(monitor):
    from dint_tpu.engines import tatp_dense as td

    return td.build_pipelined_runner(
        N_SUB, w=W, val_words=VW, cohorts_per_block=CPB, monitor=monitor)


@functools.lru_cache(maxsize=None)
def _sb_build(monitor):
    from dint_tpu.engines import smallbank_dense as sd

    return sd.build_pipelined_runner(
        N_ACC, w=W, cohorts_per_block=CPB, monitor=monitor)


@functools.lru_cache(maxsize=None)
def _tp_build(monitor):
    from dint_tpu.engines import tatp_pipeline as tp

    return tp.build_pipelined_runner(
        N_SUB, w=W, val_words=VW, cohorts_per_block=CPB, monitor=monitor)


# ---------------------------------------------------------- dense engines


def _run_tatp_dense(monitor, blocks=3, seed=0):
    from dint_tpu.engines import tatp_dense as td

    db = td.populate(np.random.default_rng(seed), N_SUB, val_words=VW)
    run, init, drain = _td_build(monitor)
    carry = init(db)
    tot = np.zeros(td.N_STATS, np.int64)
    for i in range(blocks):
        carry, s = run(carry, jax.random.fold_in(KEY(seed), i))
        tot += np.asarray(s, np.int64).sum(axis=0)
    out = drain(carry)
    tot += np.asarray(out[1], np.int64).sum(axis=0)
    snap = M.snapshot(out[2]) if monitor else None
    return out[0], tot, snap


def test_tatp_dense_reconciles_with_stats():
    from dint_tpu.engines import tatp_dense as td

    _, tot, snap = _run_tatp_dense(True)
    assert snap["txn_attempted"] == tot[td.STAT_ATTEMPTED]
    assert snap["txn_committed"] == tot[td.STAT_COMMITTED]
    assert snap["ab_lock"] == tot[td.STAT_AB_LOCK]
    assert snap["ab_missing"] == tot[td.STAT_AB_MISSING]
    assert snap["ab_validate"] == tot[td.STAT_AB_VALIDATE]
    assert snap["magic_bad"] == tot[td.STAT_MAGIC_BAD] == 0
    # internal ledgers close
    assert snap["lock_requests"] == \
        snap["lock_granted"] + snap["lock_rejected"]
    assert snap["lock_rejected"] == \
        snap["lock_reject_held"] + snap["lock_reject_arb"]
    assert snap["log_appends"] == snap["install_writes"] > 0
    assert snap["ring_hwm"] > 0
    assert snap["repl_push_hop1"] == 0      # single chip: no ICI pushes


def _updates_only_one_cohort_a_block():
    """(run, carry, drain, C) of dense TATP at 2w = 512 slots (C = 128)
    under an update-only mix, one cohort a block: a window's delta is one
    step's, and a step asks for, and writes, more than one chunk holds."""
    from dint_tpu.engines import tatp_dense as td
    from dint_tpu.ops import compact

    chunk = compact.chunk_lanes(512)
    assert chunk == 128
    mix = np.array([0, 0, 0, 50, 50, 0, 0], np.float64) / 100.0
    run, init, drain = td.build_pipelined_runner(
        N_SUB * 8, w=256, val_words=VW, cohorts_per_block=1, mix=mix,
        monitor=True)
    carry = init(td.populate(np.random.default_rng(0), N_SUB * 8,
                             val_words=VW))
    return run, carry, drain, chunk


def test_install_chunks_reconcile_with_install_writes_step_by_step():
    """Write-set compaction (ops/compact.py): a step's install loop makes
    ceil(install_writes / C) trips. One cohort a block, so a window's
    delta is one step's; an update-only mix, so that a step can write
    more than one chunk holds (2w = 512 slots, C = 128)."""
    run, carry, drain, chunk = _updates_only_one_cohort_a_block()
    prev, per_step = None, []
    for i in range(6):
        carry, _ = run(carry, jax.random.fold_in(KEY(0), i))
        snap = M.snapshot(carry[-1])
        d = mc.delta(snap, prev)
        prev = snap
        assert d["steps"] == 1
        assert d["install_chunks"] == -(-d["install_writes"] // chunk)
        per_step.append(d["install_chunks"])
    assert per_step[:2] == [0, 0]       # an empty c2: nothing to install
    assert max(per_step) >= 2           # more than one chunk's worth
    d = mc.delta(M.snapshot(drain(carry)[2]), prev)     # two steps
    low = -(-d["install_writes"] // chunk)
    assert d["steps"] == 2 and low <= d["install_chunks"] <= low + 1


def test_lock_chunks_reconcile_with_lock_requests_step_by_step():
    """Lock-wave compaction: a step's first lock loop makes
    ceil(lock_requests / C) trips (the second makes as many); the drain
    generates no cohort, so it asks for nothing."""
    run, carry, drain, chunk = _updates_only_one_cohort_a_block()
    prev, per_step = None, []
    for i in range(4):
        carry, _ = run(carry, jax.random.fold_in(KEY(0), i))
        snap = M.snapshot(carry[-1])
        d = mc.delta(snap, prev)
        prev = snap
        assert d["steps"] == 1 and d["lock_requests"] > chunk
        assert d["lock_chunks"] == -(-d["lock_requests"] // chunk)
        assert d["lock_requests"] == d["lock_granted"] + d["lock_rejected"]
        assert d["lock_rejected"] == \
            d["lock_reject_held"] + d["lock_reject_arb"]
        per_step.append(d["lock_chunks"])
    assert min(per_step) >= 2           # more than one chunk's worth
    d = mc.delta(M.snapshot(drain(carry)[2]), prev)     # two steps
    assert d["steps"] == 2 and d["lock_requests"] == d["lock_chunks"] == 0


def test_tatp_dense_monitoring_off_is_bit_identical():
    db_off, tot_off, _ = _run_tatp_dense(False)
    db_on, tot_on, _ = _run_tatp_dense(True)
    assert tot_off.tolist() == tot_on.tolist()
    assert np.array_equal(np.asarray(db_off.meta), np.asarray(db_on.meta))
    assert np.array_equal(np.asarray(db_off.val), np.asarray(db_on.val))
    assert np.array_equal(np.asarray(db_off.log.entries),
                          np.asarray(db_on.log.entries))


def test_tatp_dense_counters_reproducible_across_runs():
    _, _, a = _run_tatp_dense(True, seed=3)
    _, _, b = _run_tatp_dense(True, seed=3)
    assert a == b
    _, _, c = _run_tatp_dense(True, seed=4)
    assert a != c           # and they are not trivially constant


def _run_sb_dense(monitor, blocks=3, seed=1):
    from dint_tpu.engines import smallbank_dense as sd

    db = sd.create(N_ACC)
    run, init, drain = _sb_build(monitor)
    carry = init(db)
    tot = np.zeros(sd.N_STATS, np.int64)
    for i in range(blocks):
        carry, s = run(carry, jax.random.fold_in(KEY(seed), i))
        tot += np.asarray(s, np.int64).sum(axis=0)
    out = drain(carry)
    tot += np.asarray(out[1], np.int64).sum(axis=0)
    snap = M.snapshot(out[2]) if monitor else None
    return out[0], tot, snap


def test_sb_dense_reconciles_and_off_identical():
    from dint_tpu.engines import smallbank_dense as sd

    db_on, tot, snap = _run_sb_dense(True)
    assert snap["txn_attempted"] == tot[sd.STAT_ATTEMPTED]
    assert snap["txn_committed"] == tot[sd.STAT_COMMITTED]
    assert snap["ab_lock"] == tot[sd.STAT_AB_LOCK]
    assert snap["ab_logic"] == tot[sd.STAT_AB_LOGIC]
    assert snap["lock_requests"] == \
        snap["lock_granted"] + snap["lock_rejected"]
    assert snap["lock_rejected"] == \
        snap["lock_reject_held"] + snap["lock_reject_arb"]
    assert snap["install_writes"] > 0 and snap["ring_hwm"] > 0

    db_off, tot_off, _ = _run_sb_dense(False)
    assert tot_off.tolist() == tot.tolist()
    assert np.array_equal(np.asarray(db_off.bal), np.asarray(db_on.bal))


# -------------------------------------------------------- serve counters


def test_serve_counters_reconcile_and_mirror():
    """Round-17 lane ledger: on the serve-mode builders the occupancy
    counters account every lane of every SERVING step — occupancy +
    padded == width x steps (drain steps inject nothing, so they tally
    nothing) — the shed counter mirrors the host-side admission ledger
    exactly (the trace_dropped two-sided audit pattern), attempted
    follows OCCUPANCY rather than width, and the closed-loop builders
    leave all three at zero."""
    from dint_tpu.engines import tatp_dense as td
    from dint_tpu.serve import cached_runner

    run, init, drain = cached_runner(
        "tatp_dense", N_SUB, val_words=VW, w=W, cohorts_per_block=CPB,
        monitor=True, trace=False, serve=True)
    db = td.populate(np.random.default_rng(0), N_SUB, val_words=VW)
    carry = init(db)
    occs = [np.array([W, W // 2], np.int32), np.array([0, 7], np.int32),
            np.array([W, 0], np.int32)]
    sheds = [np.array([3, 0], np.int32), np.array([0, 0], np.int32),
             np.array([5, 0], np.int32)]
    tot = np.zeros(td.N_STATS, np.int64)
    for i, (o, s) in enumerate(zip(occs, sheds)):
        carry, st = run(carry, jax.random.fold_in(KEY(0), i), o, s)
        tot += np.asarray(st, np.int64).sum(axis=0)
    out = drain(carry)
    tot += np.asarray(out[1], np.int64).sum(axis=0)
    snap = M.snapshot(out[-1])

    n_occ = sum(int(o.sum()) for o in occs)
    steps = len(occs) * CPB                     # serving steps only
    assert snap["serve_occupancy_lanes"] == n_occ
    assert snap["serve_padded_lanes"] == steps * W - n_occ
    assert snap["serve_occupancy_lanes"] + snap["serve_padded_lanes"] \
        == steps * W                            # the reconciliation identity
    assert snap["serve_shed_lanes"] == sum(int(s.sum()) for s in sheds) == 8
    # attempted follows occupancy, not width: masked lanes are no-ops
    assert snap["txn_attempted"] == tot[td.STAT_ATTEMPTED] == n_occ
    assert 0 < snap["txn_committed"] == tot[td.STAT_COMMITTED] <= n_occ

    # the closed loop never touches the serve plane
    _, _, base = _run_tatp_dense(True)
    assert base["serve_occupancy_lanes"] == base["serve_padded_lanes"] \
        == base["serve_shed_lanes"] == 0


def test_mesh_serve_counters_reconcile_and_prefetch_ledger():
    """Round-18 mesh lane ledger: on the 2-D serve-mode runner the
    occupancy identity holds ACROSS the mesh (occ + padded == width x
    serving-steps x devices), the per-host shed mirror reconciles
    host<->device, and the overlap route accounts every prefetched lane:
    route_prefetch_lanes == lock_requests when the double buffer is on,
    0 when it is off — with the per-axis route split identity intact in
    both modes."""
    from dint_tpu.parallel import multihost_sb as mh

    # geometry matches tests/test_dintmesh.py's engines exactly so the
    # process-wide builder memo shares both compiled runners (tier-1
    # wall-clock: this test pays runs, not compiles)
    H, C, BLK, Wm, Nm = 4, 2, 2, 16, 256
    mesh = mh.make_mesh_2d(H, C)
    rng = np.random.default_rng(3)
    occs = [rng.integers(0, Wm + 1, size=(H, C, BLK)).astype(np.int32)
            for _ in range(BLK)]
    sheds = [rng.integers(0, 4, size=(H, C, BLK)).astype(np.int32)
             for _ in range(BLK)]

    snaps = {}
    for overlap in (False, True):
        run, init, drain = mh.build_multihost_sb_runner(
            mesh, Nm, w=Wm, cohorts_per_block=BLK, monitor=True,
            serve=True, overlap=overlap)
        carry = init(mh.create_multihost_sb(mesh, Nm))
        for i, (o, sh) in enumerate(zip(occs, sheds)):
            carry, _ = run(carry, jax.random.fold_in(KEY(5), i), o, sh)
        _, _, cnt = drain(carry)
        snaps[overlap] = M.snapshot(cnt)

    n_occ = sum(int(o.sum()) for o in occs)
    steps = len(occs) * BLK                      # serving steps only
    for overlap, snap in snaps.items():
        assert snap["serve_occupancy_lanes"] == n_occ, overlap
        assert snap["serve_occupancy_lanes"] + snap["serve_padded_lanes"] \
            == steps * Wm * H * C, overlap       # mesh-wide identity
        # host<->device shed mirror: the device ledger equals the sum of
        # the per-host tallies the host pushed through the occ/shed slots
        assert snap["serve_shed_lanes"] == sum(int(s.sum()) for s in sheds)
        assert snap["txn_attempted"] == n_occ, overlap
        # per-axis route split survives the double buffer
        assert snap["route_ici_lanes"] + snap["route_dcn_lanes"] == \
            snap["lock_requests"] + snap["install_writes"], overlap

    # the prefetch ledger: every valid lock-request lane was exchanged
    # one step early under overlap; the unoverlapped route never touches
    # the counter
    assert snaps[False]["route_prefetch_lanes"] == 0
    assert snaps[True]["route_prefetch_lanes"] == \
        snaps[True]["lock_requests"] > 0
    # scheduling must not change WHAT was locked/committed
    for k in ("lock_requests", "txn_committed", "install_writes"):
        assert snaps[False][k] == snaps[True][k], k


# ------------------------------------------------------- generic engines


def test_generic_smallbank_reconciles():
    from dint_tpu.engines import smallbank_pipeline as sp

    st = sp.create_stacked(N_ACC)
    run = sp.build_runner(N_ACC, w=W, cohorts_per_block=CPB, monitor=True)
    carry = (st, M.create())
    tot = np.zeros(sp.N_STATS, np.int64)
    for i in range(2):
        carry, s = run(carry, jax.random.fold_in(KEY(1), i))
        tot += np.asarray(s, np.int64).sum(axis=0)
    snap = M.snapshot(carry[1])
    assert snap["txn_attempted"] == tot[sp.STAT_ATTEMPTED]
    assert snap["txn_committed"] == tot[sp.STAT_COMMITTED]
    assert snap["ab_lock"] == tot[sp.STAT_AB_LOCK]
    assert snap["ab_logic"] == tot[sp.STAT_AB_LOGIC]
    assert snap["lock_requests"] == \
        snap["lock_granted"] + snap["lock_rejected"]


def test_parity_counters_generic_vs_dense():
    """Same seed -> same cohorts: at a low-contention parity geometry
    (exact CF locks draw no hash-conflation conflicts, same property the
    dense-vs-generic stats parity test pins) the engine-independent
    counter subset must be bit-identical between the dense and the
    generic sort-based engine — and the generic engine's counters must
    reconcile with its own stats vector."""
    from dint_tpu.clients import tatp_client as tc
    from dint_tpu.engines import tatp_dense as td
    from dint_tpu.engines import tatp_pipeline as tp

    blocks, seed = 2, 0

    db = td.populate(np.random.default_rng(seed), N_SUB, val_words=VW)
    run_d, init_d, drain_d = _td_build(True)
    carry_d = init_d(db)

    shards, _ = tc.populate_shards(np.random.default_rng(seed), N_SUB,
                                   val_words=VW, log_capacity=1 << 14)
    run_g, init_g, drain_g = _tp_build(True)
    carry_g = init_g(tp.stack_shards(shards))

    tot_g = np.zeros(tp.N_STATS, np.int64)
    for i in range(blocks):
        carry_d, _ = run_d(carry_d, jax.random.fold_in(KEY(seed), i))
        carry_g, s_g = run_g(carry_g, jax.random.fold_in(KEY(seed), i))
        tot_g += np.asarray(s_g, np.int64).sum(axis=0)
    _, _, cnt_d = drain_d(carry_d)
    _, tail_g, cnt_g = drain_g(carry_g)
    tot_g += np.asarray(tail_g, np.int64).sum(axis=0)
    snap_d, snap_g = M.snapshot(cnt_d), M.snapshot(cnt_g)

    # generic engine reconciles against its own stats vector
    assert snap_g["txn_attempted"] == tot_g[tp.STAT_ATTEMPTED]
    assert snap_g["txn_committed"] == tot_g[tp.STAT_COMMITTED]
    assert snap_g["ab_lock"] == tot_g[tp.STAT_AB_LOCK]
    assert snap_g["ab_validate"] == tot_g[tp.STAT_AB_VALIDATE]

    par_d = {n: snap_d[n] for n in mc.PARITY_NAMES}
    par_g = {n: snap_g[n] for n in mc.PARITY_NAMES}
    assert par_d == par_g, (par_d, par_g)
    assert par_d["txn_committed"] > 0 and par_d["install_writes"] > 0


# --------------------------------------------------------- sharded paths


def test_dense_sharded_counters_sum_across_shards():
    from dint_tpu.engines import tatp_dense as td
    from dint_tpu.parallel import dense_sharded as ds

    mesh = ds.make_mesh(4)
    run, init, drain = ds.build_sharded_pipelined_runner(
        mesh, 4, 4 * 200, w=32, val_words=4, cohorts_per_block=2,
        monitor=True)
    carry = init(ds.create_sharded(mesh, 4, 4 * 200, val_words=4,
                                   log_capacity=128))
    tot = np.zeros(td.N_STATS, np.int64)
    for i in range(3):
        carry, s = run(carry, jax.random.fold_in(KEY(2), i))
        tot += np.asarray(s, np.int64).sum(axis=0)
    # per-device planes are live mid-run too (stacked [D, N] in the carry)
    per_dev = np.asarray(carry[-1].buf)
    assert per_dev.shape == (4, mc.N_COUNTERS)
    assert (per_dev[:, mc.CTR_STEPS] == per_dev[0, mc.CTR_STEPS]).all()
    _, tail, cnt = drain(carry)
    tot += np.asarray(tail, np.int64).sum(axis=0)
    snap = M.snapshot(cnt)      # sums flows / maxes gauges over devices
    assert snap["txn_attempted"] == tot[td.STAT_ATTEMPTED]
    assert snap["txn_committed"] == tot[td.STAT_COMMITTED]
    assert snap["ab_lock"] == tot[td.STAT_AB_LOCK]
    assert snap["ab_missing"] == tot[td.STAT_AB_MISSING]
    assert snap["ab_validate"] == tot[td.STAT_AB_VALIDATE]
    # every install is pushed over BOTH ppermute hops (CommitBck x2)
    assert snap["repl_push_hop1"] == snap["install_writes"] > 0
    assert snap["repl_push_hop2"] == snap["install_writes"]


def test_bck_chunks_reconcile_with_the_hops_step_by_step():
    """Backup-apply compaction: at each receiving device a step's two
    install loops make ceil(hop 1's live / C) + ceil(hop 2's live / C)
    trips. One cohort a block, so a window's delta is one step's; an
    update-only mix at 2w = 512 slots (C = 128), so that a hop forwards
    more than one chunk holds."""
    from dint_tpu.ops import compact
    from dint_tpu.parallel import dense_sharded as ds

    chunk = compact.chunk_lanes(512)
    mesh = ds.make_mesh(4)
    run, init, drain = ds.build_sharded_pipelined_runner(
        mesh, 4, 4 * 4000, w=256, val_words=4, cohorts_per_block=1,
        mix=(0.0, 0.0, 0.0, 0.5, 0.5, 0.0, 0.0), monitor=True)
    carry = init(ds.create_sharded(mesh, 4, 4 * 4000, val_words=4,
                                   log_capacity=256))

    def trips(d):
        return (-(-d[:, mc.CTR_REPL_PUSH_HOP1] // chunk)
                - (-d[:, mc.CTR_REPL_PUSH_HOP2] // chunk))

    prev, most = np.zeros((4, mc.N_COUNTERS), np.int64), 0
    for i in range(5):
        carry, _ = run(carry, jax.random.fold_in(KEY(4), i))
        buf = np.asarray(carry[-1].buf, np.int64)       # [D, N] a device
        d, prev = buf - prev, buf
        assert (d[:, mc.CTR_STEPS] == 1).all()
        assert (d[:, mc.CTR_BCK_CHUNKS] == trips(d)).all()
        most = max(most, int(d[:, mc.CTR_BCK_CHUNKS].max()))
        if i < 2:                       # an empty c2: nothing forwarded
            assert not d[:, mc.CTR_BCK_CHUNKS].any()
    assert most >= 4                    # two trips or more a hop
    _, _, cnt = drain(carry)
    d = np.asarray(cnt.buf, np.int64) - prev            # two steps
    assert (d[:, mc.CTR_BCK_CHUNKS] >= trips(d)).all()
    assert (d[:, mc.CTR_BCK_CHUNKS] <= trips(d) + 2).all()
    snap = M.snapshot(cnt)
    assert snap["repl_push_hop1"] == snap["repl_push_hop2"] \
        == snap["install_writes"]
    # a hop's receiver makes the trips its sender's own install made
    assert snap["bck_chunks"] == 2 * snap["install_chunks"] > 0


def test_dense_sharded_sb_counters_sum_across_shards():
    from dint_tpu.parallel import dense_sharded_sb as dsb

    mesh = dsb.make_mesh(4)
    run, init, drain = dsb.build_sharded_sb_runner(
        mesh, 4, 4 * 128, w=32, cohorts_per_block=2, monitor=True)
    carry = init(dsb.create_sharded_sb(mesh, 4, 4 * 128))
    tot = np.zeros(dsb.N_STATS, np.int64)
    for i in range(3):
        carry, s = run(carry, jax.random.fold_in(KEY(3), i))
        tot += np.asarray(s, np.int64).sum(axis=0)
    _, tail, cnt = drain(carry)
    tot += np.asarray(tail, np.int64).sum(axis=0)
    snap = M.snapshot(cnt)
    assert snap["txn_attempted"] == tot[dsb.STAT_ATTEMPTED]
    assert snap["txn_committed"] == tot[dsb.STAT_COMMITTED]
    assert snap["ab_lock"] == tot[dsb.STAT_AB_LOCK]
    assert snap["ab_logic"] == tot[dsb.STAT_AB_LOGIC]
    assert snap["route_overflow"] == tot[dsb.STAT_OVERFLOW]
    assert snap["repl_push_hop1"] == snap["install_writes"] > 0
    assert snap["repl_push_hop2"] == snap["install_writes"]


def test_sharded_smallbank_chunks_reconcile_with_the_installs_step_by_step():
    """The sharded SmallBank's three appends (PR 44): at each device a
    step's owner append makes ceil(install_writes / C) trips
    (`install_chunks`) and its two forwarded appends ceil(hop 1's live /
    C) + ceil(hop 2's live / C) (`bck_chunks`). One cohort a block, so a
    window's delta is one step's; w = 256 (an inbox of D x cap = 1,536
    slots, C = 128) over accounts enough that an owner installs more than
    one chunk holds."""
    from dint_tpu.ops import compact
    from dint_tpu.parallel import dense_sharded_sb as dsb

    mesh = dsb.make_mesh(4)
    run, init, drain = dsb.build_sharded_sb_runner(
        mesh, 4, 40_000, w=256, cohorts_per_block=1, monitor=True)
    carry = init(dsb.create_sharded_sb(mesh, 4, 40_000,
                                       log_capacity=1 << 9))
    chunk = compact.chunk_lanes(4 * 2 * (256 * dsb.L // 4))
    assert chunk == 128

    def ceil(x):
        return -(-x // chunk)

    prev, most = np.zeros((4, mc.N_COUNTERS), np.int64), 0
    for i in range(5):
        carry, _ = run(carry, jax.random.fold_in(KEY(5), i))
        buf = np.asarray(carry[-1].buf, np.int64)       # [D, N] a device
        d, prev = buf - prev, buf
        assert (d[:, mc.CTR_STEPS] == 1).all()
        assert (d[:, mc.CTR_INSTALL_CHUNKS]
                == ceil(d[:, mc.CTR_INSTALL_WRITES])).all()
        assert (d[:, mc.CTR_BCK_CHUNKS]
                == ceil(d[:, mc.CTR_REPL_PUSH_HOP1])
                + ceil(d[:, mc.CTR_REPL_PUSH_HOP2])).all()
        most = max(most, int(d[:, mc.CTR_INSTALL_CHUNKS].max()))
        if i == 0:                      # an empty c1: nothing to install
            assert not d[:, mc.CTR_INSTALL_CHUNKS].any()
            assert not d[:, mc.CTR_BCK_CHUNKS].any()
    assert most >= 2                    # more than one chunk's worth
    _, _, cnt = drain(carry)
    d = np.asarray(cnt.buf, np.int64) - prev            # the drain's step
    assert (d[:, mc.CTR_INSTALL_CHUNKS]
            == ceil(d[:, mc.CTR_INSTALL_WRITES])).all()
    snap = M.snapshot(cnt)
    # a hop's receiver makes the trips its sender's own append made
    assert snap["bck_chunks"] == 2 * snap["install_chunks"] > 0


# ------------------------------------------------------------ trace layer


def test_trace_writer_schema_and_summary(tmp_path):
    p = str(tmp_path / "run.jsonl")
    with M.TraceWriter(p, meta={"name": "t"}) as w:
        d = dict(mc.zeros_dict(), txn_attempted=128, txn_committed=100,
                 ring_hwm=7)
        w.wave(step=0, t=0.0, dur_s=0.5, batch=128, counters=d)
        w.wave(step=1, t=0.5, dur_s=0.5, batch=128, counters=d)
        w.wave(step=2, t=1.0, dur_s=0.5, batch=128, counters=None)
    meta, waves = M.read_events(p)
    assert meta["schema"] == 1 and meta["counters"] == list(mc.ALL_NAMES)
    assert len(waves) == 3
    # schema-stable: every registered name present on monitored waves,
    # explicit null on unmonitored ones
    assert set(waves[0]["counters"]) == set(mc.ALL_NAMES)
    assert waves[2]["counters"] is None
    from dint_tpu.monitor.trace import summarize_events
    s = summarize_events(meta, waves)
    assert s["monitored_waves"] == 2
    assert s["counters"]["txn_attempted"] == 256    # flows sum
    assert s["counters"]["ring_hwm"] == 7           # gauges max
    assert s["abort_rate"] == pytest.approx(1 - 200 / 256)


def test_monitor_observe_and_chrome_export(tmp_path):
    from dint_tpu.engines import tatp_dense as td

    p = str(tmp_path / "run.jsonl")
    db = td.populate(np.random.default_rng(0), N_SUB, val_words=VW)
    run, init, drain = _td_build(True)
    carry = init(db)
    with M.TraceWriter(p, meta={"name": "test"}) as writer:
        monitor = M.Monitor(writer)
        for i in range(3):
            carry, _ = run(carry, jax.random.fold_in(KEY(0), i))
            monitor.observe(carry[-1], batch=CPB * W, dur_s=0.01)
    _, _, cnt = drain(carry)
    snap = M.snapshot(cnt)
    # the per-wave deltas sum to the pre-drain totals: outcomes count at
    # cohort COMPLETION, 2 steps behind dispatch in the 3-stage pipeline
    assert monitor.totals["txn_attempted"] == (3 * CPB - 2) * W
    # the drain flushes the 2 in-flight cohorts into the final snapshot
    assert snap["txn_attempted"] == 3 * CPB * W
    out = str(tmp_path / "trace.json")
    n = M.export_chrome_trace(p, out)
    with open(out) as f:
        tr = json.load(f)
    assert n == len(tr["traceEvents"]) > 3
    assert any(e.get("ph") == "X" for e in tr["traceEvents"])
    assert any(e.get("ph") == "C" for e in tr["traceEvents"])


def test_monitor_deferred_drain_deltas_bit_identical(tmp_path):
    """The dintscope double-buffered drain (observe(defer=True): block
    i-1's ~100-byte fetch materializes only after block i dispatched, via
    an on-device copy that survives the carry donation) must emit the
    SAME wave-event counter deltas as the synchronous path — only WHEN
    the bytes cross to the host changes, never what they say."""
    from dint_tpu.engines import tatp_dense as td

    def run_stream(defer):
        p = str(tmp_path / f"run_{int(defer)}.jsonl")
        db = td.populate(np.random.default_rng(0), N_SUB, val_words=VW)
        run, init, drain = _td_build(True)
        carry = init(db)
        with M.TraceWriter(p, meta={"name": "defer_pin"}) as writer:
            monitor = M.Monitor(writer)
            for i in range(4):
                carry, _ = run(carry, jax.random.fold_in(KEY(0), i))
                monitor.observe(carry[-1], batch=CPB * W, dur_s=0.01,
                                defer=defer)
            last = monitor.flush()   # lands the deferred final window
            assert (last is None) == (not defer)
        _, waves_ev = M.read_events(p)
        return waves_ev, monitor.totals

    sync_waves, sync_totals = run_stream(False)
    defr_waves, defr_totals = run_stream(True)
    assert len(sync_waves) == len(defr_waves) == 4
    for a, b in zip(sync_waves, defr_waves):
        assert a["step"] == b["step"] and a["batch"] == b["batch"]
        assert a["counters"] == b["counters"]
    assert sync_totals == defr_totals


@pytest.mark.slow  # ~11s; error-path edge, not an identity pin
def test_profiler_session_noop_and_bad_dir(tmp_path):
    from dint_tpu.monitor.trace import profiler_session

    with profiler_session(None) as info:
        assert info["trace_dir"] is None
    # a profiler failure must not raise out of the context
    with profiler_session(str(tmp_path / "t1")) as info:
        pass


# ------------------------------------------------------------------- CLI


def test_dintmon_cli_json_subprocess(tmp_path):
    p = str(tmp_path / "run.jsonl")
    with M.TraceWriter(p, meta={"name": "cli"}) as w:
        d = dict(mc.zeros_dict(), txn_attempted=64, txn_committed=60,
                 lock_requests=10, lock_granted=10, ring_hwm=3)
        w.wave(step=0, t=0.0, dur_s=1.0, batch=64, counters=d)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    c = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dintmon.py"),
         "summarize", p, "--json"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert c.returncode == 0, c.stderr
    out = json.loads(c.stdout.strip().splitlines()[-1])
    assert out["counters"]["txn_attempted"] == 64
    assert out["rates_per_s"]["txn_committed"] == 60.0

    # artifact mode: a bench.py-style JSON object with counters: null
    art = tmp_path / "BENCH_x.json"
    art.write_text(json.dumps({"metric": "m", "counters": None,
                               "window_s": 1.0}))
    c = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dintmon.py"),
         "summarize", str(art), "--json"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert c.returncode == 0, c.stderr
    assert json.loads(c.stdout)["counters"] is None

    # diff + describe stay parseable
    c = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dintmon.py"),
         "diff", p, p, "--json"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert c.returncode == 0, c.stderr
    rows = json.loads(c.stdout)["rows"]
    assert all(r["delta"] == 0 for r in rows) and rows
    c = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dintmon.py"),
         "describe", "--json"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert c.returncode == 0, c.stderr
    desc = json.loads(c.stdout)
    assert len(desc["counters"]) == mc.N_COUNTERS
