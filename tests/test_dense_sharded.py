"""Multi-chip dense TATP: device-local txns + ppermute'd replication."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dint_tpu import monitor as M
from dint_tpu.engines import tatp_dense as td
from dint_tpu.ops import compact
from dint_tpu.parallel import dense_sharded as ds
from dint_tpu.tables import log as logring

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


# ------------------------- the replicated program against a plain reference


def _lane_stream(ring, heads, tag):
    """One stream of an unwrapped ring [L, CAP, EW], lane by lane, oldest
    first."""
    return [ring[lane, :int(heads[lane])][ring[lane, :int(heads[lane]), 1]
                                          == tag]
            for lane in range(ring.shape[0])]


def _acked(ring, heads, tag):
    """The stream in an order that is the order of acknowledgement for
    every row: stably by version (a row's versions rise with time)."""
    e = np.concatenate(_lane_stream(ring, heads, tag))
    return e[np.argsort(e[:, 3], kind="stable")]


def _replicas_equal_reference(seed, w, n_glob=4 * 300, mix=None,
                              monitor=False, restart_after=None):
    """Four devices, three blocks and the drain: each primary, each backup
    slot (row for row over the whole table) and each of the three rings
    that carry a device's stream (entry for entry, in order) equal what
    dint_tpu/testing/replication.py makes of the primary's acknowledged
    installs. ``restart_after``: drain after that block and `init` again
    on the drained state. Returns the run's counter snapshot under
    ``monitor``."""
    from dint_tpu.testing import replication as ref

    d = 4
    mesh = ds.make_mesh(d)
    state = ds.create_sharded(mesh, d, n_glob, val_words=VW,
                              seed=seed % (1 << 31), log_capacity=1 << 10)
    fresh = jax.tree.map(np.array, state)
    run, init, drain = ds.build_sharded_pipelined_runner(
        mesh, d, n_glob, w=w, val_words=VW, cohorts_per_block=2, mix=mix,
        monitor=monitor)
    carry = init(state)
    for i in range(3):
        carry, _ = run(carry, jax.random.fold_in(jax.random.PRNGKey(seed), i))
        if i == restart_after:
            carry = init(drain(carry)[0])
    out = drain(carry)
    live = jax.tree.map(np.asarray, out[0])

    n_loc = ds.n_sub_local(n_glob, d)
    n1 = td.n_rows(n_loc) + 1
    p1 = n_loc + 1
    table_rows = (p1, p1, 4 * p1, 4 * p1, 12 * p1)
    lanes = state.db.log.lanes
    rings = live.db.log.entries.reshape(d, lanes, -1, 4 + VW)
    heads = live.db.log.head
    assert (heads <= rings.shape[2]).all() and heads.sum() > 0
    where = ref.placement(d)
    for dev in range(d):
        acked = _acked(rings[dev], heads[dev], 0)
        assert len(acked) > 20
        stream = [(int(e[0] >> 8), int(e[2]), int(e[0] & 0xFF), int(e[3]),
                   e[4:]) for e in acked]
        for ring, tag in where[dev]["streams"]:
            meta, val, entries = ref.replay(
                fresh.db.meta[dev], fresh.db.val[dev].reshape(n1, VW),
                table_rows, stream, tag)
            np.testing.assert_array_equal(
                _acked(rings[ring], heads[ring], tag), entries)
            # and lane by lane in the primary's own order
            for a, b in zip(_lane_stream(rings[ring], heads[ring], tag),
                            _lane_stream(rings[dev], heads[dev], 0)):
                np.testing.assert_array_equal(np.delete(a, 1, 1),
                                              np.delete(b, 1, 1))
        np.testing.assert_array_equal(live.db.meta[dev], meta)
        np.testing.assert_array_equal(live.db.val[dev], val.reshape(-1))
        for holder, slot in where[dev]["backups"]:
            np.testing.assert_array_equal(
                live.bck_meta[holder, slot * n1:(slot + 1) * n1], meta)
            np.testing.assert_array_equal(
                live.bck_val[holder, slot * n1 * VW:(slot + 1) * n1 * VW],
                val.reshape(-1))
        # a ring holds nothing but the three streams it carries
        tags = {0} | {t for _, t in ref.carried(d, dev)}
        written = np.concatenate([rings[dev, lane, :int(heads[dev, lane])]
                                  for lane in range(lanes)])
        assert set(np.unique(written[:, 1]).tolist()) == tags
    return M.snapshot(out[2]) if monitor else None


@pytest.mark.parametrize("seed", [0, 11, 2**31 + 5])
def test_every_replica_equals_the_sequential_reference(seed):
    """At TATP's mix and w = 64 (C = 128 = all 2w lanes): a hop's install
    never leaves one chunk."""
    _replicas_equal_reference(seed, w=64)


def test_replicas_equal_the_reference_when_a_hop_takes_several_chunks():
    """An update-only mix at w = 256 (2w = 512 slots, C = 128) over
    subscribers enough that most transactions commit: a hop forwards
    more live lanes than one chunk holds, so the receivers'
    install and append loops take two or more trips, and every replica
    still equals the sequential reference."""
    mix = (0.0, 0.0, 0.0, 0.5, 0.5, 0.0, 0.0)
    snap = _replicas_equal_reference(7, w=256, n_glob=4 * 4000, mix=mix,
                                     monitor=True)
    chunk = compact.chunk_lanes(2 * 256)
    assert chunk == 128
    hops = snap["repl_push_hop1"] + snap["repl_push_hop2"]
    assert hops == 2 * snap["install_writes"] > 0
    # `steps` is summed over the devices, two hops a step and device:
    # more trips than hops, so some hop took at least two
    assert snap["bck_chunks"] > 2 * snap["steps"]
    assert snap["bck_chunks"] >= -(-hops // chunk)


# --------------------- the compacted apply against the full-width form


def _apply_backup_full_width(state, inst, slot, n1, val_words, src_dev):
    """`ds._apply_backup` as it was before the receiver compacted the
    record (PR 37): every one of the 2w lanes issued, masked ones out of
    bounds, and `append_rep` of all of them. The reference the chunked
    form has to equal bit for bit."""
    base, oob = slot * n1, ds.N_BCK * n1
    rows = jnp.where(inst.wmask, base + inst.rows, oob)
    meta = state.bck_meta.at[rows].set(inst.meta, mode="drop",
                                       unique_indices=True)
    flat = (rows[:, None] * val_words
            + jnp.arange(val_words, dtype=jnp.int32)).reshape(-1)
    val = state.bck_val.at[flat].set(inst.val.reshape(-1), mode="drop",
                                     unique_indices=True)
    src = jnp.broadcast_to(src_dev.astype(jnp.uint32) + jnp.uint32(1),
                           inst.key.shape)
    log = logring.append_rep(state.db.log, inst.wmask, inst.tbl,
                             inst.is_del, src, inst.key, inst.ver, inst.val)
    return state.replace(bck_val=val, bck_meta=meta,
                         db=state.db.replace(log=log))


_AW = 256                                   # 2w = 512 lanes
_AC = compact.chunk_lanes(2 * _AW)          # C = 128
_AN_SUB = 60


@functools.cache
def _apply_pair():
    """(state, chunked, full): one shard's state at a tiny size (16 rings
    of 32 slots: a second record of 512 live lanes overwrites the first)
    and the two forms jitted once for every case below."""
    n1 = td.n_rows(_AN_SUB) + 1
    db = td.populate(np.random.default_rng(3), _AN_SUB, val_words=VW,
                     log_replicas=1, log_capacity=32)
    rng = np.random.default_rng(4)
    state = ds.ShardState(
        db=db,
        bck_val=jnp.asarray(rng.integers(
            0, 1 << 32, ds.N_BCK * n1 * VW, dtype=np.uint32)),
        bck_meta=jnp.asarray(rng.integers(
            0, 1 << 32, ds.N_BCK * n1, dtype=np.uint32)))

    def chunked(state, inst, src_dev, slot):
        trips = []
        out = ds._apply_backup(state, inst, slot, n1, VW, src_dev, trips)
        return out, trips[0]

    def full(state, inst, src_dev, slot):
        return _apply_backup_full_width(state, inst, slot, n1, VW, src_dev)

    return (state, jax.jit(chunked, static_argnums=3),
            jax.jit(full, static_argnums=3))


def _forced_record(rng, n_live, n1):
    """An install record of 2w lanes with ``n_live`` live ones at random
    lanes: distinct rows among the live, row ids that would land IN
    bounds on the masked (a mask that leaks shows), deletes among them."""
    r = 2 * _AW
    wmask = np.zeros(r, bool)
    wmask[rng.choice(r, n_live, replace=False)] = True
    rows = rng.integers(0, n1 - 1, r)
    rows[wmask] = rng.choice(n1 - 1, n_live, replace=False)
    u32 = functools.partial(rng.integers, 0, 1 << 32, dtype=np.uint32)
    return td.Installs(
        wmask=jnp.asarray(wmask), rows=jnp.asarray(rows, jnp.int32),
        meta=jnp.asarray(u32(r)), val=jnp.asarray(u32((r, VW))),
        tbl=jnp.asarray(rng.integers(0, 5, r), jnp.int32),
        key=jnp.asarray(u32(r)),
        is_del=jnp.asarray(rng.integers(0, 2, r), jnp.int32),
        ver=jnp.asarray(u32(r)))


@pytest.mark.parametrize("n_live", [0, 1, _AC - 1, _AC, _AC + 1,
                                    2 * _AC + 3, 2 * _AW])
def test_compacted_apply_equals_the_full_width_form(n_live):
    """`_apply_backup` issues the live lanes of a forwarded record in
    chunks; the backup tables, every ring's entries and every head are
    bit for bit what the full-width form writes, over two hops in a row
    (the second on heads the first has moved, into rings that wrap), and
    the install loop makes ceil(live / C) trips."""
    state, chunked, full = _apply_pair()
    n1 = td.n_rows(_AN_SUB) + 1
    assert n1 - 1 >= 2 * _AW            # room for 2w distinct rows
    rng = np.random.default_rng(100 + n_live)
    a = b = state
    for slot, src in ((0, 3), (1, 2)):
        inst = _forced_record(rng, n_live, n1)
        a, trips = chunked(a, inst, jnp.asarray(src, jnp.int32), slot)
        b = full(b, inst, jnp.asarray(src, jnp.int32), slot)
        assert int(trips) == -(-n_live // _AC)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    heads = np.asarray(a.db.log.head)
    assert heads.sum() == 2 * n_live
    if n_live:
        changed = np.asarray(a.bck_meta) != np.asarray(state.bck_meta)
        assert 0 < changed.sum() <= 2 * n_live
    else:
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(state)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


_CACHE_CHILD = '''
import collections, json, sys
import jax, numpy as np
sys.path.insert(0, sys.argv[1])
from dint_tpu import _runtime
from dint_tpu.parallel import dense_sharded as ds
_runtime.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
events = collections.Counter()
jax.monitoring.register_event_listener(
    lambda name, **kw: events.update((name,)))
mesh = ds.make_mesh(4)
state = ds.create_sharded(mesh, 4, 4 * 512, val_words=4, seed=0)
run, init, drain = ds.build_sharded_pipelined_runner(
    mesh, 4, 4 * 512, w=16, val_words=4, cohorts_per_block=2, monitor=True)
carry, stats = init(state), []
for i in range(3):
    carry, s = run(carry, jax.random.PRNGKey(i))
    stats.append(np.asarray(s).tolist())
state, tail, counters = drain(carry)
print(json.dumps({
    "stats": stats + [np.asarray(tail).tolist()],
    "counters": np.asarray(counters.buf).tolist(),
    "heads": np.asarray(state.db.log.head).tolist(),
    "meta": int(np.asarray(state.db.meta, np.int64).sum()),
    "bck_meta": int(np.asarray(state.bck_meta, np.int64).sum()),
    "hits": events["/jax/compilation_cache/cache_hits"],
    "misses": events["/jax/compilation_cache/cache_misses"]}))
'''


def test_a_second_process_loads_the_sharded_programs_from_the_cache(
        tmp_path):
    """Two processes in a row, one compile cache directory: the second
    compiles nothing (the sharded, donated block and drain among what it
    loads) and gives the first's stats, counters and tables bit for bit.
    The first suspect for the four-chip cell's old exit 1 (PERF.md, PR
    37), and what the benchmark's warm runs rest on."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(
        tmp_path / "cache"),
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    lines = []
    for _ in range(2):
        c = subprocess.run([sys.executable, "-c", _CACHE_CHILD, repo],
                           env=env, capture_output=True, text=True,
                           timeout=600)
        assert c.returncode == 0, c.stderr[-2000:]
        lines.append(json.loads(c.stdout.strip().splitlines()[-1]))
    first, second = lines
    assert first["hits"] == 0 and first["misses"] >= 3   # populate, block, drain
    assert second["misses"] == 0 and second["hits"] == first["misses"]
    for k in ("stats", "counters", "heads", "meta", "bck_meta"):
        assert first[k] == second[k], k
    assert sum(first["heads"][0]) > 0


# ------------------------------------ the carry's form between dispatches


def _noisy_state(mesh, d, n_glob, seed):
    """A stacked state no leaf of which is all zero or equal on two
    devices: a few blocks run and drained, so that rings, heads and
    backups carry writes."""
    state = ds.create_sharded(mesh, d, n_glob, val_words=VW, seed=seed,
                              log_capacity=1 << 10)
    run, init, drain = ds.build_sharded_pipelined_runner(
        mesh, d, n_glob, w=64, val_words=VW, cohorts_per_block=2)
    carry = init(state)
    for i in range(2):
        carry, _ = run(carry, jax.random.fold_in(jax.random.PRNGKey(seed), i))
    return drain(carry)[0]


def test_init_and_the_way_back_keep_every_leaf_bit_for_bit():
    """Stacked -> the carry's form -> stacked is the identity on every
    leaf of a state whose rings and backups hold writes (a drain with an
    empty pipeline installs and appends nothing)."""
    d, n_glob = 4, 4 * 300
    mesh = ds.make_mesh(d)
    state = _noisy_state(mesh, d, n_glob, seed=3)
    before = jax.tree.map(np.array, state)
    assert before.db.log.head.any() and before.bck_meta.any()
    assert (before.bck_val[0] != before.bck_val[1]).any()
    _, init, drain = ds.build_sharded_pipelined_runner(
        mesh, d, n_glob, w=64, val_words=VW, cohorts_per_block=2)
    after, tail = drain(init(state))
    assert not np.asarray(tail).any()
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(before),
                            jax.tree.leaves(after)):
        assert y.shape == x.shape and y.shape[0] == d
        # two empty steps: the step counter moved, nothing else did
        moved = 2 if jax.tree_util.keystr(path) == ".db.step" else 0
        np.testing.assert_array_equal(np.asarray(y), x + np.uint32(moved),
                                      err_msg=jax.tree_util.keystr(path))


def test_the_carry_holds_each_shard_without_a_stacked_axis():
    """Between dispatches a device's shard of every ShardState leaf is
    the table as the scan carries it, [N, ...] and no [1, N, ...]
    (`db.step`: [1], a scalar a device); the contexts and the counters
    stay stacked, the counters [D, N_COUNTERS]."""
    from dint_tpu.monitor import counters as mc

    d, n_glob = 4, 4 * 300
    mesh = ds.make_mesh(d)
    state = ds.create_sharded(mesh, d, n_glob, val_words=VW,
                              log_capacity=1 << 10)
    stacked = [x.shape for x in jax.tree.leaves(state)]
    run, init, _ = ds.build_sharded_pipelined_runner(
        mesh, d, n_glob, w=64, val_words=VW, cohorts_per_block=2,
        monitor=True)
    def held_as_the_scan_carries_it(carry):
        for leaf, was in zip(jax.tree.leaves(carry[0]), stacked):
            local = was[1:] or (1,)
            assert leaf.shape == (d * local[0],) + local[1:]
            shards = leaf.addressable_shards
            assert {s.device for s in shards} == set(mesh.devices.flat)
            assert all(s.data.shape == local for s in shards)
        assert carry[-1].buf.shape == (d, mc.N_COUNTERS)
        for leaf in jax.tree.leaves(carry[1:]):
            assert leaf.shape[0] == d
            assert all(s.data.shape == (1,) + leaf.shape[1:]
                       for s in leaf.addressable_shards)

    carry = init(state)
    held_as_the_scan_carries_it(carry)
    held_as_the_scan_carries_it(run(carry, jax.random.PRNGKey(0))[0])


def test_run_and_init_donate_what_they_are_given():
    """`init` consumes the stacked state and `run` the carry: every table
    leaf handed in is deleted after the call (the block updates the
    carry's own buffers; a caller that kept the old carry would read
    freed memory, so JAX refuses)."""
    d, n_glob = 4, 4 * 300
    mesh = ds.make_mesh(d)
    state = ds.create_sharded(mesh, d, n_glob, val_words=VW,
                              log_capacity=1 << 10)
    run, init, drain = ds.build_sharded_pipelined_runner(
        mesh, d, n_glob, w=64, val_words=VW, cohorts_per_block=2,
        monitor=True)
    carry = init(state)
    assert all(x.is_deleted() for x in jax.tree.leaves(
        state.replace(db=state.db.replace(step=None))))
    after, _ = run(carry, jax.random.PRNGKey(1))
    assert all(x.is_deleted() for x in jax.tree.leaves(carry))
    assert not any(x.is_deleted() for x in jax.tree.leaves(after))
    drained = drain(after)
    assert all(x.is_deleted() for x in jax.tree.leaves(after[0]))
    assert not any(x.is_deleted() for x in jax.tree.leaves(drained))


@pytest.mark.parametrize("seed", [1, 2**31 + 9])
def test_every_replica_equals_the_reference_across_a_restart(seed):
    """The harness's `restart`: init -> run -> drain -> init(the drained
    state) -> run x 2 -> drain, both passes' writes in every replica and
    every ring."""
    _replicas_equal_reference(seed, w=64, restart_after=0)
