"""Multi-chip dense SmallBank: TRUE cross-device transactions over the
mesh — a SendPayment's two accounts land on different devices, its locks
are granted remotely, and global balance conservation must still hold."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dint_tpu.engines import smallbank_dense as sd
from dint_tpu.ops import compact
from dint_tpu.parallel import dense_sharded_sb as dsb
from dint_tpu.tables import log as logring

D = 8


def _run(n_accounts, w, blocks, seed=0, **kw):
    mesh = dsb.make_mesh(D)
    state = dsb.create_sharded_sb(mesh, D, n_accounts)
    base = dsb.total_balance_global(state)
    run, init, drain = dsb.build_sharded_sb_runner(
        mesh, D, n_accounts, w=w, cohorts_per_block=2, **kw)
    carry = init(state)
    key = jax.random.PRNGKey(seed)
    total = np.zeros(dsb.N_STATS, np.int64)
    for i in range(blocks):
        carry, stats = run(carry, jax.random.fold_in(key, i))
        total += np.asarray(stats, np.int64).sum(axis=0)
    state, tail = drain(carry)
    total += np.asarray(tail, np.int64).sum(axis=0)
    return state, total, base


def test_accounting_closes_and_balance_conserved_globally():
    state, total, base = _run(n_accounts=4096, w=128, blocks=3)
    attempted = int(total[dsb.STAT_ATTEMPTED])
    committed = int(total[dsb.STAT_COMMITTED])
    assert attempted == 3 * 2 * 128 * D     # every device contributes w
    assert committed > 0
    assert committed + int(total[dsb.STAT_AB_LOCK]) \
        + int(total[dsb.STAT_AB_LOGIC]) == attempted
    # routing slack holds: no destination bucket overflowed at this width
    assert int(total[dsb.STAT_OVERFLOW]) == 0
    final = dsb.total_balance_global(state)
    want = int(total[dsb.STAT_BAL_DELTA])
    assert (final - base) % (1 << 32) == want % (1 << 32)


def test_cross_device_transactions_commit():
    """SendPayment-only mix: every txn X-locks TWO accounts; with 8-way
    round-robin partitioning a1 and a2 usually live on different devices,
    so a nonzero commit count proves remote lock grants + remote installs
    work end to end (and conservation pins their correctness)."""
    mix = np.zeros(6)
    mix[3] = 1.0          # SB_SEND_PAYMENT (wl.SB_MIX order)
    state, total, base = _run(n_accounts=1 << 14, w=64, blocks=3,
                              mix=mix, hot_prob=0.0)
    committed = int(total[dsb.STAT_COMMITTED])
    assert committed > 0
    final = dsb.total_balance_global(state)
    assert (final - base) % (1 << 32) == int(
        total[dsb.STAT_BAL_DELTA]) % (1 << 32)
    # SendPayment moves money between accounts: committed txns with zero
    # global delta is exactly conservation
    assert int(total[dsb.STAT_BAL_DELTA]) == 0


def test_backups_mirror_primaries():
    state, total, _ = _run(n_accounts=2048, w=64, blocks=4)
    bal = np.asarray(state.bal)          # [D, m1]
    bck = np.asarray(state.bck_bal)      # [D, 2*m1]
    m1 = bal.shape[1]
    for dev in range(D):
        for off, slot in ((1, 0), (2, 1)):
            holder = (dev + off) % D
            got = bck[holder, slot * m1:(slot + 1) * m1]
            assert np.array_equal(got[:-1], bal[dev, :-1]), (dev, off)


def test_hot_contention_rejects_across_devices():
    """Whole-keyspace hot set at w=1 per device: every cohort hits the
    same few accounts from 8 different devices; cross-device no-wait
    rejects must fire."""
    _, total, _ = _run(n_accounts=16, w=4, blocks=4, seed=2,
                       hot_frac=1.0, hot_prob=1.0)
    assert int(total[dsb.STAT_AB_LOCK]) > 0


def test_lost_device_balance_range_recovers_from_any_ring():
    """A lost device's primary balances rebuild from ANY of the 3 rings
    carrying its stream (entries log GLOBAL account ids; owner =
    acct % D separates streams)."""
    from dint_tpu import recovery

    n_accounts = 2048
    state, total, _ = _run(n_accounts=n_accounts, w=64, blocks=3)
    bal = np.asarray(state.bal)                  # [D, m1]
    entries = np.asarray(state.log.entries)      # [D, L*CAP, EW]
    heads = np.asarray(state.log.head)           # [D, L]
    lanes = state.log.lanes
    cap = entries.shape[1] // lanes

    for dead in (1, 5):
        for holder in (dead, (dead + 1) % D, (dead + 2) % D):
            rec = recovery.recover_sb_shard(
                n_accounts, dead, D,
                entries[holder].reshape(lanes, cap, -1), heads[holder],
                ring_owner=holder)
            assert np.array_equal(rec, bal[dead]), (dead, holder)

    # geometry check: the key_hi source tags expose a ring replayed under
    # the wrong n_shards (here: wrong ring_owner stands in for geometry
    # drift — tags no longer match acct % D)
    wrong = (1 + 3) % D
    with pytest.raises(ValueError, match="source tags"):
        recovery.recover_sb_shard(
            n_accounts, 1, D, entries[1].reshape(lanes, cap, -1),
            heads[1], ring_owner=wrong)


def test_route_overflow_fires_and_reconciles_with_monitor():
    """Adversarial routing: every txn hits ONE hot account, so every
    device aims all w*L lanes at a single destination bucket of capacity
    2*ceil(w*L/D) — overflow MUST fire. Overflowed lanes degrade to lock
    rejects (accounting still closes) and the psummed STAT_OVERFLOW
    total reconciles EXACTLY with dintmon's route_overflow counter."""
    from dint_tpu.monitor import counters as mon

    mesh = dsb.make_mesh(D)
    state = dsb.create_sharded_sb(mesh, D, 4096)
    base = dsb.total_balance_global(state)
    run, init, drain = dsb.build_sharded_sb_runner(
        mesh, D, 4096, w=64, cohorts_per_block=2,
        hot_frac=1.0 / 4096, hot_prob=1.0, monitor=True)
    carry = init(state)
    key = jax.random.PRNGKey(3)
    total = np.zeros(dsb.N_STATS, np.int64)
    for i in range(3):
        carry, stats = run(carry, jax.random.fold_in(key, i))
        total += np.asarray(stats, np.int64).sum(axis=0)
    state, tail, cnt = drain(carry)
    total += np.asarray(tail, np.int64).sum(axis=0)

    overflow = int(total[dsb.STAT_OVERFLOW])
    assert overflow > 0
    # dropped lanes surface as lock aborts, never as lost txns
    attempted = int(total[dsb.STAT_ATTEMPTED])
    assert attempted == 3 * 2 * 64 * D
    assert int(total[dsb.STAT_COMMITTED]) + int(total[dsb.STAT_AB_LOCK]) \
        + int(total[dsb.STAT_AB_LOGIC]) == attempted
    # and conservation survives the drops
    final = dsb.total_balance_global(state)
    assert (final - base) % (1 << 32) == \
        int(total[dsb.STAT_BAL_DELTA]) % (1 << 32)
    # exact reconciliation: the stats plane and the counter plane count
    # the same event at the same site (source device, cohort completion)
    snap = mon.snapshot(cnt)
    assert snap["route_overflow"] == overflow
    assert snap["txn_attempted"] == attempted


# ------------------------- against the sequential reference (PR 43) -------
# dint_tpu/testing/smallbank_sharded.py: a step's cohorts taken as one in
# (source device, lane, lock-set) order through the sequential oracle

D4, N4, W4, CPB4, BLOCKS4 = 4, 4000, 96, 2, 3


def _cohorts_again(n_accounts, w, cpb, d):
    """block key -> (ttype, a1, a2, ts_amt), each [cpb, d, w]: what the d
    devices generate from it (`block_local`'s split, `local_step`'s fold
    of the device index and its second split)."""
    import jax.numpy as jnp

    from dint_tpu.engines import smallbank_pipeline as sp

    def one(step_key, dev):
        kgen, kamt = jax.random.split(jax.random.fold_in(step_key, dev))
        amounts = jax.random.randint(kamt, (w,), -sp.TS_AMT_MAX,
                                     sp.TS_AMT_MAX + 1, dtype=jnp.int32)
        return (*sp.gen_cohort(kgen, w, n_accounts, hot_frac=0.05,
                               hot_prob=0.9), amounts)

    devs = jnp.arange(d, dtype=jnp.int32)
    return jax.jit(lambda key: jax.vmap(
        lambda k: jax.vmap(lambda i: one(k, i))(devs))(
            jax.random.split(key, cpb)))


def _stream(ring, heads, tag):
    """Sorted (table, account, step, balance, magic) of one stream of an
    unwrapped ring [L, CAP, EW]."""
    rows = [ring[lane, :int(h)] for lane, h in enumerate(heads)]
    e = np.concatenate(rows)
    e = e[e[:, 1] == tag]
    return sorted(zip((e[:, 0] >> 8).tolist(), e[:, 2].tolist(),
                      e[:, 3].tolist(), e[:, 4].tolist(), e[:, 5].tolist()))


@pytest.mark.parametrize("seed", [0, 11, 2**31 + 5])
def test_every_replica_equals_the_sequential_reference(seed):
    """Four devices, a hot set small enough that locks collide across
    devices: every step's psummed stats row, every primary, both backup
    slots of every device and all three streams of every device's log
    (entries {balance, magic}) equal the reference's; the two counters of
    distributed traffic equal a count made from the cohorts themselves;
    a lost device's range comes back from each of its streams."""
    from dint_tpu.monitor import counters as mon
    from dint_tpu.testing import smallbank_sharded as ref

    mesh = dsb.make_mesh(D4)
    state = dsb.create_sharded_sb(mesh, D4, N4, log_capacity=1 << 9)
    run, init, drain = dsb.build_sharded_sb_runner(
        mesh, D4, N4, w=W4, cohorts_per_block=CPB4, hot_frac=0.05,
        hot_prob=0.9, monitor=True)
    again = _cohorts_again(N4, W4, CPB4, D4)
    bank = ref.ShardedSmallBank(N4, D4, cap=ref.bucket_cap(W4, D4))
    fast = ref.ShardedSmallBank(N4, D4, cap=ref.bucket_cap(W4, D4),
                                by_cohort=True)
    key = jax.random.PRNGKey(seed % (1 << 32))
    carry, got, want = init(state), [], [np.zeros(dsb.N_STATS, np.int64)]
    for i in range(BLOCKS4):
        k = jax.random.fold_in(key, i)
        carry, stats = run(carry, k)
        got.append(np.asarray(stats, np.int64))
        block = [np.asarray(x) for x in again(k)]
        for j in range(CPB4):
            cohorts = [tuple(x[j, d] for x in block) for d in range(D4)]
            want.append(bank.step(cohorts))
            np.testing.assert_array_equal(fast.step(cohorts), want[-1])
    state, tail, cnt = drain(carry)
    bank.drain(), fast.drain()
    got = np.concatenate([*got, np.asarray(tail, np.int64)])
    np.testing.assert_array_equal(got, np.stack(want))
    total = got.sum(axis=0)
    assert total[dsb.STAT_COMMITTED] > 0 and total[dsb.STAT_AB_LOCK] > 0
    assert total[dsb.STAT_OVERFLOW] == 0
    assert bank.bank.tally["x_rejected_prev_x"] > 0     # across steps

    snap = mon.snapshot(cnt)
    assert snap["xshard_txns"] == bank.distributed["xshard_txns"] > 0
    assert snap["remote_lock_lanes"] \
        == bank.distributed["remote_lock_lanes"] > 0
    assert snap["lock_requests"] == bank.distributed["lock_lanes"]
    assert snap["txn_attempted"] == bank.distributed["txns"]

    bal, bck = np.asarray(state.bal), np.asarray(state.bck_bal)
    m1 = bal.shape[1]
    rings = np.asarray(state.log.entries).reshape(
        D4, state.log.lanes, -1, state.log.entries.shape[-1])
    heads = np.asarray(state.log.head)
    assert (heads <= rings.shape[2]).all()              # none wrapped
    for d in range(D4):
        table = bank.table(d)
        np.testing.assert_array_equal(bal[d], table)
        np.testing.assert_array_equal(fast.table(d), table)
        assert (table != ref.fresh_table(bank.n_loc, 1000)).any()
        for holder, slot in bank.where[d]["backups"]:
            np.testing.assert_array_equal(
                bck[holder, slot * m1:(slot + 1) * m1], table)
        for ring, tag in bank.where[d]["streams"]:
            entries = _stream(rings[ring], heads[ring], tag)
            assert entries == sorted(bank.stream(d)) and entries
            assert {e[4] for e in entries} == {0x5B5B}
            np.testing.assert_array_equal(
                ref.replay(entries, d, N4, D4), bal[d])
    assert dsb.total_balance_global(state) == bank.total_balance()


_SB_CACHE_CHILD = r'''
import collections, json, sys
sys.path.insert(0, sys.argv[1])
import jax
import numpy as np
from dint_tpu import _runtime
from dint_tpu.parallel import dense_sharded_sb as dsb
_runtime.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
events = collections.Counter()
jax.monitoring.register_event_listener(
    lambda name, **kw: events.update((name,)))
mesh = dsb.make_mesh(4)
state = dsb.create_sharded_sb(mesh, 4, 4096, log_capacity=1 << 9)
run, init, drain = dsb.build_sharded_sb_runner(
    mesh, 4, 4096, w=32, cohorts_per_block=2, monitor=True)
carry, stats = init(state), []
for i in range(3):
    carry, s = run(carry, jax.random.PRNGKey(i))
    stats.append(np.asarray(s).tolist())
state, tail, counters = drain(carry)
print(json.dumps({
    "stats": stats + [np.asarray(tail).tolist()],
    "counters": np.asarray(counters.buf).tolist(),
    "heads": np.asarray(state.log.head).tolist(),
    "bal": int(np.asarray(state.bal, np.int64).sum()),
    "bck": int(np.asarray(state.bck_bal, np.int64).sum()),
    "log": int(np.asarray(state.log.entries, np.int64).sum()),
    "hits": events["/jax/compilation_cache/cache_hits"],
    "misses": events["/jax/compilation_cache/cache_misses"]}))
'''


def test_a_second_process_loads_the_sharded_programs_from_the_cache(
        tmp_path):
    """Two processes in a row, one compile cache directory: the second
    compiles nothing (the sharded, donated block and drain, nine
    all_to_alls and ten ppermutes each, among what it loads) and gives
    the first's stats, counters, tables and rings bit for bit: what the
    four-chip cell's warm runs rest on."""
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
        c = subprocess.run([sys.executable, "-c", _SB_CACHE_CHILD, repo],
                           env=env, capture_output=True, text=True,
                           timeout=600)
        assert c.returncode == 0, c.stderr[-2000:]
        lines.append(json.loads(c.stdout.strip().splitlines()[-1]))
    first, second = lines
    assert first["hits"] == 0 and first["misses"] >= 2      # block, drain
    assert second["misses"] == 0 and second["hits"] == first["misses"]
    for k in ("stats", "counters", "heads", "bal", "bck", "log"):
        assert first[k] == second[k], k
    assert sum(first["heads"][0]) > 0


# ------------- the appends' compacted form against the full width (PR 44) --
# An inbox's install mask is D segments of `cap` slots, each live in a
# prefix; the three appends of a step issue the live rows only
# (`compact.prefixed` + `logring.append_rep`), and the rings have to be
# what the full-width append writes, bit for bit.

_PD, _PCAP = 4, 384                        # an inbox of 1,536 slots ...
_PC = 128                                  # ... issued 128 rows a trip


def _routed_inbox(per_segment, rng):
    """(i_mask, fields) of an inbox as `_route` fills it: segment s gets
    ``per_segment[s]`` installs, in arrival order, from a source whose
    lanes aim at the D buckets in a shuffled order; what does not fit a
    bucket is dropped by `_route`'s own `valid` rule."""
    dest = np.repeat(np.arange(_PD), per_segment)
    rng.shuffle(dest)
    n = len(dest)
    active = jnp.ones((n,), bool)
    dest = jnp.asarray(dest, jnp.int32)
    pos = dsb._positions(dest, active, _PD)
    valid = active & (pos < _PCAP)
    vals = [jnp.asarray(rng.integers(1, 1 << 30, n), jnp.int32)
            for _ in range(3)]
    routed = dsb._route(dest, pos, valid, _PCAP, _PD,
                        [valid.astype(jnp.int32), *vals])
    return routed[0] != 0, routed[1:]


@functools.cache
def _append_pair():
    """(a fresh ring, the compacted append, the full-width one), the two
    jitted once for every case below."""
    def append(ring, mask, live, bal, tbl, acc, t):
        val = jnp.zeros((mask.shape[0], dsb.VW), jnp.uint32)
        val = val.at[:, 0].set(bal.astype(jnp.uint32)).at[:, 1].set(
            jnp.where(mask, jnp.uint32(dsb.MAGIC), jnp.uint32(0)))
        return logring.append_rep(
            ring, live, tbl & 1, jnp.zeros_like(bal),
            jnp.zeros_like(bal, jnp.uint32), acc.astype(jnp.uint32),
            jnp.broadcast_to(t, mask.shape), val)

    @jax.jit
    def compacted(ring, mask, bal, tbl, acc, t):
        live = compact.prefixed(mask, _PD)
        return append(ring, mask, live, bal, tbl, acc, t), live.trips

    @jax.jit
    def full(ring, mask, bal, tbl, acc, t):
        return append(ring, mask, mask, bal, tbl, acc, t)

    # 16 rings of 64 slots: a full inbox wraps every ring
    make = functools.partial(logring.create_rep, 16, 64, dsb.VW, replicas=1)
    return make, compacted, full


_APPEND_CASES = {
    "nothing_live": (0, 0, 0, 0),
    "one_lane": (0, 0, 1, 0),
    "one_segment_empty": (90, 0, 130, 55),
    "only_the_last_segment": (0, 0, 0, 200),
    "a_segment_full_and_one_lane_dropped": (_PCAP + 1, 20, 0, 77),
    "an_exact_multiple_of_the_chunk": (_PC, 100, 2 * _PC - 100, _PC),
    "one_short_of_a_chunk": (_PC - 1, 0, 0, 0),
    "one_over_a_chunk": (60, 60, 9, 0),
    "every_slot_live": (_PCAP,) * _PD,
}


@pytest.mark.parametrize("case", list(_APPEND_CASES))
def test_the_compacted_append_equals_the_full_width_append(case):
    """`append_rep` under `compact.prefixed(i_mask, D)` against
    `append_rep` under the plain mask, on inboxes that `_route` filled:
    `entries` and `head` bit for bit over two appends in a row (the
    second on heads the first has moved, into rings that wrap), and the
    loop makes ceil(live / C) trips, none where nothing is live."""
    assert compact.chunk_lanes(_PD * _PCAP) == _PC
    per_segment = _APPEND_CASES[case]
    make, compacted, full = _append_pair()
    rng = np.random.default_rng(sorted(_APPEND_CASES).index(case))
    a, b = make(), make()
    for t in (2, 3):
        mask, (bal, tbl, acc) = _routed_inbox(per_segment, rng)
        n_live = int(np.asarray(mask).sum())
        assert n_live == sum(min(n, _PCAP) for n in per_segment)
        a, trips = compacted(a, mask, bal, tbl, acc, np.uint32(t))
        b = full(b, mask, bal, tbl, acc, np.uint32(t))
        assert int(trips) == -(-n_live // _PC)
        np.testing.assert_array_equal(np.asarray(a.entries),
                                      np.asarray(b.entries))
        np.testing.assert_array_equal(np.asarray(a.head),
                                      np.asarray(b.head))
    assert int(np.asarray(a.head).sum()) == 2 * n_live
    assert bool(np.asarray(a.entries).any()) == bool(n_live)


@pytest.mark.parametrize("counts", [
    (0, 0, 0, 0), (5, 0, 0, 0), (0, 0, 0, 7), (3, 0, 9, 1), (96, 96, 96, 96),
    (96, 0, 96, 1), (40, 41, 42, 5), (128,), (17, 111)], ids=str)
def test_both_forms_of_a_live_mask_visit_the_live_lanes_in_lane_order(
        counts):
    """`compact.prefixed` (segment counts, no search) and `compact.Ranked`
    (running count, a search a chunk) over the same prefix-live mask:
    same lanes, same order, same trips, the live lanes and nothing else."""
    cap = max(96, max(counts))
    mask = (np.arange(cap) < np.asarray(counts)[:, None]).reshape(-1)
    want = np.nonzero(mask)[0]
    forms = (compact.prefixed(jnp.asarray(mask), len(counts)),
             compact.Ranked(jnp.asarray(mask),
                            *compact.live_ranks(jnp.asarray(mask))))
    chunk = forms[0].chunk
    assert chunk == forms[1].chunk == compact.chunk_lanes(mask.size)
    room = -(-mask.size // chunk) * chunk

    def visit(state, lanes, ok):
        seen, k = state
        return (jax.lax.dynamic_update_slice(
            seen, jnp.where(ok, lanes, -1), (k,)), k + chunk)

    for live in forms:
        assert int(live.n_live) == len(want)
        (seen, _), trips = live.for_chunks(
            visit, (jnp.full(room, -1, jnp.int32), jnp.asarray(0, jnp.int32)))
        assert int(trips) == int(live.trips) == -(-len(want) // chunk)
        assert np.asarray(seen).tolist() == \
            want.tolist() + [-1] * (room - len(want))


def test_every_segment_of_an_inbox_is_live_in_a_prefix():
    """What `compact.prefixed` stands on: after `_positions` + `_route` +
    `_a2a`, as `local_step` composes them for the installs, segment s of
    a device's inbox holds source s's installs at slots 0..n-1 and
    nothing behind them, one bucket overflowing included; a `ppermute`
    forwards the mask as it is. A `_route` that placed a live slot behind
    a dead one would leave its row out of all three rings."""
    from jax.sharding import PartitionSpec as P

    d, n, cap = 4, 96, 32
    mesh = dsb.make_mesh(d)
    rng = np.random.default_rng(5)
    dest = rng.integers(0, d, (d, n))
    dest[1, :60] = 2                        # source 1 overflows bucket 2
    wmask = rng.random((d, n)) < 0.6
    wmask[3] = False                        # a source with nothing to send
    wmask[1, :60] = True

    def local(dest, wmask):
        dest, wmask = dest[0], wmask[0]
        pos = dsb._positions(dest, wmask, d)
        valid = wmask & (pos < cap)
        packed, = dsb._route(dest, pos, valid, cap, d,
                             [wmask.astype(jnp.int32)])
        i_mask = dsb._a2a(packed, d, cap) != 0
        fwd = jax.lax.ppermute(i_mask, dsb.AXIS,
                               [(i, (i + 1) % d) for i in range(d)])
        return i_mask[None], fwd[None]

    i_mask, fwd = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(dsb.AXIS),) * 2,
        out_specs=(P(dsb.AXIS),) * 2))(
            jnp.asarray(dest, jnp.int32), jnp.asarray(wmask))
    i_mask = np.asarray(i_mask).reshape(d, d, cap)      # [owner, source]
    counts = i_mask.sum(axis=2)
    for owner in range(d):
        for src in range(d):
            sent = int((wmask[src] & (dest[src] == owner)).sum())
            assert counts[owner, src] == min(sent, cap)
    assert counts[2, 1] == cap and counts[:, 3].sum() == 0
    np.testing.assert_array_equal(
        i_mask, np.arange(cap) < counts[:, :, None])
    np.testing.assert_array_equal(np.asarray(fwd).reshape(d, d, cap),
                                  np.roll(i_mask, 1, axis=0))


def _full_width(real):
    """`append_rep` as the three appends called it before they compacted
    their masks: the plain mask, every inbox slot issued."""
    def append_rep(ring, mask, *fields):
        return real(ring, getattr(mask, "mask", mask), *fields)
    return append_rep


_RUNNER_CASES = {
    "plain": {},
    "monitor": {"monitor": True},
    "trace": {"trace": True},
    "hotset": {"use_hotset": True},
    "all_three": {"monitor": True, "trace": True, "use_hotset": True},
}


@pytest.mark.parametrize("case", list(_RUNNER_CASES))
def test_rings_equal_the_full_width_program_after_block_and_drain(
        case, monkeypatch):
    """The four-device runner with its appends compacted against the same
    runner with `append_rep` handed the plain masks (the parent's
    program), same seeds: every ring's `entries` and `head`, the tables
    and the stats after two blocks and a drain, with `monitor`, `trace`
    and `use_hotset` on and off. w = 256 (an inbox of 1,536 slots, C =
    128) at a mix where a step's installs take more than one trip."""
    kw = _RUNNER_CASES[case]
    mesh = dsb.make_mesh(D4)

    def go():
        dsb.build_sharded_sb_runner.cache.clear()   # not in the memo's key
        state = dsb.create_sharded_sb(mesh, D4, 40_000, log_capacity=1 << 9)
        run, init, drain = dsb.build_sharded_sb_runner(
            mesh, D4, 40_000, w=256, cohorts_per_block=3, **kw)
        carry, stats = init(state), []
        for i in range(2):
            carry, s = run(carry, jax.random.PRNGKey(20 + i))
            stats.append(np.asarray(s))
        out = drain(carry)
        state = out[0]
        return [np.concatenate([*stats, np.asarray(out[1])]),
                *(np.asarray(x) for x in (
                    state.log.entries, state.log.head, state.bal,
                    state.bck_bal))], out

    got, out = go()
    monkeypatch.setattr(dsb.logring, "append_rep",
                        _full_width(dsb.logring.append_rep))
    want, _ = go()
    monkeypatch.undo()
    dsb.build_sharded_sb_runner.cache.clear()
    for x, y in zip(got, want):
        np.testing.assert_array_equal(x, y)
    heads = got[2]
    assert heads.sum() > 0
    if kw.get("monitor"):
        from dint_tpu.monitor import counters as mon

        snap = mon.snapshot(out[-1])
        assert heads.sum() == 3 * snap["install_writes"]
        # `steps` is summed over the devices: more trips than steps, so
        # some owner's installs took a second trip
        assert snap["install_chunks"] > snap["steps"]
        assert snap["bck_chunks"] == 2 * snap["install_chunks"]
