"""Multi-host (DCN x ICI) dense TATP: replication crosses host fault
domains (parallel/multihost.py)."""
import jax
import numpy as np
import pytest

from dint_tpu.engines import tatp_dense as td
from dint_tpu.parallel import dense_sharded as ds, multihost as mh

VW = 4
H, C = 4, 2          # 4 hosts x 2 chips on the 8-virtual-device mesh
D = H * C


def _run(n_sub_global, w, blocks, seed=0, h=H, c=C):
    mesh = mh.make_mesh_2d(h, c)
    state = mh.create_multihost(mesh, n_sub_global, val_words=VW,
                                seed=seed)
    run, init, drain = mh.build_multihost_runner(
        mesh, n_sub_global, w=w, val_words=VW, cohorts_per_block=2)
    carry = init(state)
    key = jax.random.PRNGKey(seed)
    total = np.zeros(td.N_STATS, np.int64)
    for i in range(blocks):
        carry, stats = run(carry, jax.random.fold_in(key, i))
        total += np.asarray(stats, np.int64).sum(axis=0)
    state, tail = drain(carry)
    total += np.asarray(tail, np.int64).sum(axis=0)
    return state, total


@pytest.mark.slow  # ~18s; the 1-D-totals equivalence pin stays tier-1
def test_accounting_closes_over_2d_mesh():
    state, total = _run(n_sub_global=D * 256, w=64, blocks=3)
    attempted = int(total[td.STAT_ATTEMPTED])
    committed = int(total[td.STAT_COMMITTED])
    assert attempted == 3 * 2 * 64 * D      # psummed over BOTH axes
    assert committed > 0
    assert int(total[td.STAT_MAGIC_BAD]) == 0
    outcomes = (committed + int(total[td.STAT_AB_LOCK])
                + int(total[td.STAT_AB_MISSING])
                + int(total[td.STAT_AB_VALIDATE]))
    assert outcomes == attempted


def test_replicas_live_on_distinct_hosts():
    """The fault-domain property the 2-D mesh exists for: device (h, c)'s
    written rows are mirrored at hosts h+1 and h+2, SAME chip coordinate
    — so all 3 copies of any row sit on 3 different hosts."""
    state, _ = _run(n_sub_global=D * 256, w=64, blocks=4)
    n_loc = mh.n_sub_local(D * 256, D)
    n1 = td.n_rows(n_loc) + 1

    meta = np.asarray(state.db.meta)                    # [H, C, n1]
    val = np.asarray(state.db.val).reshape(H, C, -1, VW)
    bck_meta = np.asarray(state.bck_meta)               # [H, C, 2*n1]
    bck_val = np.asarray(state.bck_val)                 # [H, C, 2*n1*VW]

    wrote = (meta >> 1) > 1
    assert wrote.any()
    for h in range(H):
        for c in range(C):
            for off, slot in ((1, 0), (2, 1)):
                hh = (h + off) % H          # backup HOST, same chip c
                bm = bck_meta[hh, c, slot * n1:(slot + 1) * n1]
                bv = bck_val[hh, c, slot * n1 * VW:(slot + 1) * n1 * VW]
                rows = np.nonzero(wrote[h, c])[0]
                assert np.array_equal(bm[rows], meta[h, c, rows]), \
                    (h, c, off)
                assert np.array_equal(bv.reshape(n1, VW)[rows],
                                      val[h, c, rows]), (h, c, off)


def test_host_failure_recovers_from_surviving_host():
    """Kill host h: every (h, c) range rebuilds from a SURVIVING host's
    log — (h+1, c) or (h+2, c) — via the source-tag filter, proving the
    DCN replication stream is sufficient for cross-host failover."""
    from dint_tpu import recovery

    n_sub_global = D * 256
    state, _ = _run(n_sub_global=n_sub_global, w=64, blocks=3)
    # create_multihost is a function of (geometry, seed): a second call is
    # the pre-run snapshot
    fresh = jax.tree.map(np.asarray, mh.create_multihost(
        mh.make_mesh_2d(H, C), n_sub_global, val_words=VW, seed=0).db)

    meta = np.asarray(state.db.meta)
    val = np.asarray(state.db.val)
    entries = np.asarray(state.db.log.entries)   # [H, C, L*CAP, EW]
    heads = np.asarray(state.db.log.head)        # [H, C, L]
    lanes = state.db.log.lanes
    cap = entries.shape[2] // lanes

    dead_h = 1
    for c in range(C):
        dead = dead_h * C + c                    # linear partition id
        snap = jax.tree.map(lambda x: x[dead_h, c], fresh)
        for off in (1, 2):
            hh = (dead_h + off) % H
            e = entries[hh, c].reshape(lanes, cap, -1)
            rec = recovery.recover_tatp_dense(snap, e, heads[hh, c],
                                              key_hi_filter=dead + 1)
            assert np.array_equal(np.asarray(rec.val), val[dead_h, c]), \
                (c, off)
            assert np.array_equal(np.asarray(rec.meta),
                                  meta[dead_h, c]), (c, off)


def test_matches_1d_sharded_totals():
    """Program equivalence: the 2-D mesh partitions the same global
    keyspace into H*C ranges with the same per-partition workload streams
    as the 1-D runner over D devices — total attempted/committed match
    exactly (the transport axis changed, the math did not)."""
    n_sub_global = D * 128
    _, total_2d = _run(n_sub_global, w=32, blocks=2)

    mesh = ds.make_mesh(D)
    state = ds.create_sharded(mesh, D, n_sub_global, val_words=VW, seed=0)
    run, init, drain = ds.build_sharded_pipelined_runner(
        mesh, D, n_sub_global, w=32, val_words=VW, cohorts_per_block=2)
    carry = init(state)
    key = jax.random.PRNGKey(0)
    total_1d = np.zeros(td.N_STATS, np.int64)
    for i in range(2):
        carry, stats = run(carry, jax.random.fold_in(key, i))
        total_1d += np.asarray(stats, np.int64).sum(axis=0)
    _, tail = drain(carry)
    total_1d += np.asarray(tail, np.int64).sum(axis=0)

    assert np.array_equal(total_2d, total_1d)


def test_two_hosts_refused():
    with pytest.raises(ValueError, match="3 hosts"):
        mh.create_multihost(mh.make_mesh_2d(2, 2), 64, val_words=VW)


def test_reference_topology_3_hosts():
    """The reference's exact machine count: 3 hosts (x2 chips). With
    H == replication factor, each host backs up BOTH other hosts and
    every row has a copy on every host — accounting still closes."""
    _, total = _run(6 * 128, w=32, blocks=2, seed=3, h=3, c=2)
    attempted = int(total[td.STAT_ATTEMPTED])
    committed = int(total[td.STAT_COMMITTED])
    assert attempted == 2 * 2 * 32 * 6
    assert committed > 0
    assert int(total[td.STAT_MAGIC_BAD]) == 0
    outcomes = (committed + int(total[td.STAT_AB_LOCK])
                + int(total[td.STAT_AB_MISSING])
                + int(total[td.STAT_AB_VALIDATE]))
    assert outcomes == attempted
