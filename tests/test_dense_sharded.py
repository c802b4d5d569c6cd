"""Multi-chip dense TATP: device-local txns + ppermute'd replication."""
import jax
import numpy as np

from dint_tpu.engines import tatp_dense as td
from dint_tpu.parallel import dense_sharded as ds

VW = 4
D = 8


def _fresh(n_sub_global, seed=0):
    """Per-device populated tables [D, ...] on the host: create_sharded is
    a function of (geometry, seed), so a second call is the snapshot."""
    state = ds.create_sharded(ds.make_mesh(D), D, n_sub_global,
                              val_words=VW, seed=seed)
    return jax.tree.map(np.asarray, state)


def _run(n_sub_global, w, blocks, seed=0, mix=None):
    mesh = ds.make_mesh(D)
    state = ds.create_sharded(mesh, D, n_sub_global, val_words=VW,
                              seed=seed)
    run, init, drain = ds.build_sharded_pipelined_runner(
        mesh, D, n_sub_global, w=w, val_words=VW, cohorts_per_block=2,
        mix=mix)
    carry = init(state)
    key = jax.random.PRNGKey(seed)
    total = np.zeros(td.N_STATS, np.int64)
    for i in range(blocks):
        carry, stats = run(carry, jax.random.fold_in(key, i))
        total += np.asarray(stats, np.int64).sum(axis=0)
    state, tail = drain(carry)
    total += np.asarray(tail, np.int64).sum(axis=0)
    return state, total


def test_create_sharded_populates_each_shard_on_its_own_device():
    """Every leaf is born sharded one slice per device (nothing global on
    one chip), each shard obeys the single-chip population rules, and the
    backup slots are the two ring predecessors' populated tables."""
    n_glob = 8 * 64
    mesh = ds.make_mesh(D)
    state = ds.create_sharded(mesh, D, n_glob, val_words=VW, seed=5)
    for leaf in jax.tree.leaves(state):
        shards = leaf.addressable_shards
        assert {s.device for s in shards} == set(mesh.devices.flat)
        assert all(s.data.shape == (1,) + leaf.shape[1:] for s in shards)

    n_loc = ds.n_sub_local(n_glob, D)
    n1 = td.n_rows(n_loc) + 1
    meta, val = np.asarray(state.db.meta), np.asarray(state.db.val)
    assert (meta[:, 1:n_loc + 1] == 3).all()       # subscribers: ver 1, live
    assert not meta[:, -1].any() and not val[:, -VW:].any()   # sentinel row
    assert len({m.tobytes() for m in meta}) == D   # one stream per device
    bck_meta, bck_val = np.asarray(state.bck_meta), np.asarray(state.bck_val)
    for d in range(D):
        for off in (1, 2):
            lo = (off - 1) * n1
            assert np.array_equal(bck_meta[(d + off) % D, lo:lo + n1],
                                  meta[d])
            assert np.array_equal(
                bck_val[(d + off) % D, lo * VW:(lo + n1) * VW], val[d])


def test_accounting_closes_and_scales_by_devices():
    state, total = _run(n_sub_global=8 * 512, w=128, blocks=3)
    attempted = int(total[td.STAT_ATTEMPTED])
    committed = int(total[td.STAT_COMMITTED])
    # every device contributes w txns per step (psummed stats)
    assert attempted == 3 * 2 * 128 * D
    assert committed > 0
    assert int(total[td.STAT_MAGIC_BAD]) == 0
    outcomes = (committed + int(total[td.STAT_AB_LOCK])
                + int(total[td.STAT_AB_MISSING])
                + int(total[td.STAT_AB_VALIDATE]))
    assert outcomes == attempted


def test_backups_mirror_primaries_and_logs_replicate():
    state, total = _run(n_sub_global=8 * 256, w=64, blocks=4)
    n_loc = ds.n_sub_local(8 * 256, D)
    n1 = td.n_rows(n_loc) + 1

    meta = np.asarray(state.db.meta)          # [D, n1]
    val = np.asarray(state.db.val).reshape(D, -1, VW)   # [D, n1, VW]
    bck_meta = np.asarray(state.bck_meta)     # [D, 2*n1]
    bck_val = np.asarray(state.bck_val)       # [D, 2*n1*VW]

    assert not np.asarray(state.db.locked).any()   # all stamps expired
    wrote = (meta >> 1) > 1                   # rows written past populate
    assert wrote.any()
    for d in range(D):
        for off, slot in ((1, 0), (2, 1)):
            holder = (d + off) % D            # device that backs up d
            bm = bck_meta[holder, slot * n1:(slot + 1) * n1]
            bv = bck_val[holder, slot * n1 * VW:(slot + 1) * n1 * VW]
            bv = bv.reshape(n1, VW)
            rows = np.nonzero(wrote[d])[0]
            assert np.array_equal(bm[rows], meta[d, rows]), (d, off)
            assert np.array_equal(bv[rows], val[d, rows]), (d, off)

    # replicated logging: every write appended on 3 devices
    heads = np.asarray(state.db.log.head).sum()
    # deleted rows bumped ver but exists=0; every bump logged once per
    # device x3 replicas-over-devices. ver counts bumps exactly.
    vers0 = _fresh(8 * 256).db.meta >> 1
    bumps = int(sum((meta[d].astype(np.int64) >> 1).sum()
                    - vers0[d].astype(np.int64).sum() for d in range(D)))
    assert heads == 3 * bumps, (heads, bumps)


def test_lost_device_recovers_from_any_log_stream():
    """Device d's primary range rebuilds from its local snapshot + ANY of
    the 3 logs carrying its stream: its own ring (source tag 0) or a
    backup holder's ring (tag d+1) — the failover the reference's
    write-ahead logs exist for but never implement (SURVEY.md 5.3)."""
    from dint_tpu import recovery

    n_sub_global = 8 * 256
    state, _ = _run(n_sub_global=n_sub_global, w=64, blocks=3)

    meta = np.asarray(state.db.meta)
    val = np.asarray(state.db.val)
    entries = np.asarray(state.db.log.entries)   # [D, L*CAP, EW]
    heads = np.asarray(state.db.log.head)        # [D, L]
    lanes = state.db.log.lanes
    cap = entries.shape[1] // lanes    # .capacity sees the stacked axis

    def ring_of(dev):
        return entries[dev].reshape(lanes, cap, -1), heads[dev]

    fresh = _fresh(n_sub_global).db
    for dead in (0, 3):
        snap = jax.tree.map(lambda x: x[dead], fresh)
        # own log stream (tag 0) and both backup holders' streams (tag d+1)
        sources = [(dead, 0), ((dead + 1) % D, dead + 1),
                   ((dead + 2) % D, dead + 1)]
        for holder, tag in sources:
            e, h = ring_of(holder)
            rec = recovery.recover_tatp_dense(snap, e, h,
                                              key_hi_filter=tag)
            assert np.array_equal(np.asarray(rec.val), val[dead]), \
                (dead, holder, tag)
            assert np.array_equal(np.asarray(rec.meta), meta[dead]), \
                (dead, holder, tag)


def test_uneven_partition_rounds_up():
    """n_sub_global not divisible by D: every device sizes for the ceil
    and the accounting still closes (psummed across the mesh)."""
    state, total = _run(n_sub_global=8 * 100 + 3, w=32, blocks=2)
    assert int(total[td.STAT_ATTEMPTED]) == 2 * 2 * 32 * D
    outcomes = (int(total[td.STAT_COMMITTED])
                + int(total[td.STAT_AB_LOCK])
                + int(total[td.STAT_AB_MISSING])
                + int(total[td.STAT_AB_VALIDATE]))
    assert outcomes == int(total[td.STAT_ATTEMPTED])
