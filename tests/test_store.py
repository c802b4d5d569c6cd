import jax
import numpy as np
import pytest

from dint_tpu.engines import store
from dint_tpu.engines.types import Op, Reply, make_batch
from dint_tpu.tables import kv, run as run_mod
from dint_tpu.testing.oracle import StoreOracle

VW = 4


def run_step(table, ops, keys, vals, width=None, bloom=False):
    batch = make_batch(ops, keys, vals, width=width or len(ops), val_words=VW)
    step = jax.jit(store.step, static_argnames=("maintain_bloom",))
    table, replies = step(table, batch, maintain_bloom=bloom)
    return table, (np.asarray(replies.rtype), np.asarray(replies.val),
                   np.asarray(replies.ver))


def rand_vals(rng, n):
    return rng.integers(0, 1 << 32, size=(n, VW), dtype=np.uint32)


def test_get_set_basic(rng):
    table = kv.create(1 << 10, slots=4, val_words=VW)
    keys = np.array([7, 9, 7], dtype=np.uint64)
    vals = rand_vals(rng, 3)
    table, (rt, rv, rver) = run_step(table, [Op.SET, Op.SET, Op.GET], keys, vals)
    assert rt[0] == Reply.ACK and rver[0] == 1
    assert rt[1] == Reply.ACK and rver[1] == 1
    # GET sees pre-batch state: key 7 absent before this batch
    assert rt[2] == Reply.NOT_EXIST

    table, (rt, rv, rver) = run_step(
        table, [Op.GET, Op.GET, Op.GET], np.array([7, 9, 1234], np.uint64),
        rand_vals(rng, 3))
    assert rt[0] == Reply.VAL and np.array_equal(rv[0], vals[0]) and rver[0] == 1
    assert rt[1] == Reply.VAL and np.array_equal(rv[1], vals[1])
    assert rt[2] == Reply.NOT_EXIST


def test_delete_and_bloom(rng):
    table = kv.create(1 << 8, slots=4, val_words=VW)
    keys = np.arange(100, dtype=np.uint64)
    table = kv.populate(table, keys, rand_vals(rng, 100))
    table, (rt, _, _) = run_step(table, [Op.DELETE] * 50,
                                 np.arange(50, dtype=np.uint64), rand_vals(rng, 50),
                                 bloom=True)
    assert (rt == Reply.ACK).all()
    d = kv.to_dict(table)
    assert set(d) == set(range(50, 100))
    # double delete -> second acks NOT_EXIST (sequential within batch)
    table, (rt, _, _) = run_step(table, [Op.DELETE, Op.DELETE],
                                 np.array([60, 60], np.uint64), rand_vals(rng, 2))
    assert rt[0] == Reply.ACK and rt[1] == Reply.NOT_EXIST


def test_conflicting_writes_same_key(rng):
    table = kv.create(1 << 8, slots=4, val_words=VW)
    vals = rand_vals(rng, 4)
    # four SETs to the same key in one batch: last lane wins, ver counts all
    table, (rt, _, rver) = run_step(table, [Op.SET] * 4,
                                    np.full(4, 42, np.uint64), vals)
    assert (rt == Reply.ACK).all()
    assert list(rver) == [1, 2, 3, 4]
    d = kv.to_dict(table)
    assert d[42] == (tuple(int(x) for x in vals[3]), 4)


def test_insert_after_delete_same_batch(rng):
    table = kv.create(1 << 8, slots=4, val_words=VW)
    v0 = rand_vals(rng, 1)
    table = kv.populate(table, np.array([5], np.uint64), v0)
    v = rand_vals(rng, 2)
    table, (rt, _, _) = run_step(table, [Op.DELETE, Op.INSERT],
                                 np.array([5, 5], np.uint64), v)
    assert rt[0] == Reply.ACK and rt[1] == Reply.ACK
    d = kv.to_dict(table)
    assert d[5][0] == tuple(int(x) for x in v[1])


def test_bucket_overflow_spills(rng):
    # 1 bucket x 2 slots: third distinct key must SPILL
    table = kv.create(1, slots=2, val_words=VW)
    keys = np.array([1, 2, 3], dtype=np.uint64)
    table, (rt, _, _) = run_step(table, [Op.INSERT] * 3, keys, rand_vals(rng, 3))
    assert sorted(rt) == sorted([Reply.ACK, Reply.ACK, Reply.SPILL])
    assert len(kv.to_dict(table)) == 2


def test_spill_reply_routing(rng):
    # full bucket: SPILL must land on the failed installs, not bystander lanes
    table = kv.create(1, slots=2, val_words=VW)
    table = kv.populate(table, np.array([1, 2], np.uint64), rand_vals(rng, 2))
    # INSERT k then GET k: insert fails -> SPILL; GET sees pre-state -> NOT_EXIST
    table, (rt, _, _) = run_step(table, [Op.INSERT, Op.GET],
                                 np.array([9, 9], np.uint64), rand_vals(rng, 2))
    assert list(rt) == [Reply.SPILL, Reply.NOT_EXIST]
    # both SETs of an un-installable key must SPILL (no phantom ACK)
    table, (rt, _, rver) = run_step(table, [Op.SET, Op.SET],
                                    np.array([9, 9], np.uint64), rand_vals(rng, 2))
    assert list(rt) == [Reply.SPILL, Reply.SPILL]
    assert list(rver) == [0, 0]
    # INSERT then DELETE of un-installable key: net effect is a no-op, so no
    # slot is ever needed — both ops ack (serial-equivalent: the transient
    # insert is observable by nobody)
    table, (rt, _, _) = run_step(table, [Op.INSERT, Op.DELETE],
                                 np.array([9, 9], np.uint64), rand_vals(rng, 2))
    assert list(rt) == [Reply.ACK, Reply.ACK]
    assert len(kv.to_dict(table)) == 2  # table untouched


def test_populate_rejects_duplicates(rng):
    table = kv.create(1 << 4, slots=4, val_words=VW)
    import pytest as _pytest
    with _pytest.raises(ValueError, match="duplicate"):
        kv.populate(table, np.array([5, 5], np.uint64), rand_vals(rng, 2))


@pytest.mark.parametrize("width", [64, 256])
def test_differential_vs_oracle(rng, width):
    table = kv.create(1 << 8, slots=8, val_words=VW)
    oracle = StoreOracle()
    keyspace = 40  # small => heavy intra-batch conflicts
    step = jax.jit(store.step)
    for _ in range(12):
        n = int(rng.integers(width // 2, width + 1))
        ops = rng.choice([Op.GET, Op.SET, Op.INSERT, Op.DELETE, Op.NOP],
                         size=n, p=[0.4, 0.25, 0.1, 0.15, 0.1]).astype(np.int32)
        keys = rng.integers(0, keyspace, size=n).astype(np.uint64)
        vals = rand_vals(rng, n)
        batch = make_batch(ops, keys, vals, width=width, val_words=VW)
        table, replies = step(table, batch)
        rt = np.asarray(replies.rtype)[:n]
        rv = np.asarray(replies.val)[:n]
        rver = np.asarray(replies.ver)[:n]
        ot, ov, over = oracle.step(ops, keys, vals)
        assert np.array_equal(rt, ot), (rt, ot)
        assert np.array_equal(rver, over)
        getmask = (ops == Op.GET) & (ot == Reply.VAL)
        assert np.array_equal(rv[getmask], ov[getmask])
        # full state equivalence every step
        d = kv.to_dict(table)
        assert d == oracle.data


def test_bloom_exact_after_churn(rng):
    table = kv.create(1 << 6, slots=8, val_words=VW)
    keys = np.arange(200, dtype=np.uint64)
    table = kv.populate(table, keys, rand_vals(rng, 200))
    table, _ = run_step(table, [Op.DELETE] * 100, keys[:100], rand_vals(rng, 100),
                        bloom=True)
    # bloom must still admit all live keys (no false negatives)
    import jax.numpy as jnp
    from dint_tpu.ops import hashing, u64
    hi, lo = map(jnp.asarray, u64.split(keys[100:]))
    b1, b2 = hashing.bucket_pair(hi, lo, table.n_buckets)
    ok = np.asarray(kv.bloom_maybe(table, hi, lo, b1, b2))
    assert ok.all()


def test_two_choice_capacity(rng):
    # load factor 0.76 with 4-slot buckets: impossible for single-choice
    # hashing (Poisson tail), fine for two-choice placement
    table = kv.create(1 << 16, slots=4, val_words=VW)
    keys = rng.choice(1 << 40, size=200_000, replace=False).astype(np.uint64)
    table = kv.populate(table, keys, np.zeros((len(keys), VW), np.uint32))
    d = kv.to_dict(table)
    assert len(d) == len(keys)


def test_insert_falls_back_to_alternate_bucket(rng):
    # craft keys sharing the same preferred bucket in a 2-bucket, 1-slot
    # table: the loser of the preferred bucket must land in its alternate,
    # not SPILL (two-choice insert fallback)
    from dint_tpu.ops import hashing
    ks = np.arange(1, 4000, dtype=np.uint64)
    b1, b2 = hashing.bucket_pair_np(ks, 2)
    cands = ks[(b1 == 0) & (b2 == 1)]
    assert len(cands) >= 2
    k1, k2 = cands[:2]
    table = kv.create(2, slots=1, val_words=VW)
    table, (rt, _, _) = run_step(table, [Op.INSERT, Op.INSERT],
                                 np.array([k1, k2], np.uint64), rand_vals(rng, 2))
    assert list(rt) == [Reply.ACK, Reply.ACK]
    assert set(kv.to_dict(table)) == {int(k1), int(k2)}
    # a third key with the same candidates now genuinely has nowhere to go
    k3 = cands[2]
    table, (rt, _, _) = run_step(table, [Op.INSERT],
                                 np.array([k3], np.uint64), rand_vals(rng, 1))
    assert list(rt) == [Reply.SPILL]


# ------------------------------------------------------------- dintscan
# Op.SCAN through step's run∪delta path: pre-batch serial order,
# route bit-identity, the stale/RETRY contract, and the oracle
# differential on adversarial mixed batches.

SMAX = 8
DCAP = 8


def scan_step(table, run, ops, keys, vals, scan_lens, scan_max=SMAX,
              width=None):
    batch = make_batch(ops, keys, vals,
                       vers=np.asarray(scan_lens, np.uint32),
                       width=width or len(ops), val_words=VW)
    step = jax.jit(store.step, static_argnames=(
        "maintain_bloom", "scan_max"))
    table, rep, run, srep = step(table, batch, run=run, scan_max=scan_max)
    return table, run, rep, srep


def srep_rows(srep, lane):
    """Device scan reply for one lane as the oracle's row list."""
    c = int(np.asarray(srep.count)[lane])
    lo = np.asarray(srep.key_lo)[lane]
    hi = np.asarray(srep.key_hi)[lane].astype(np.uint64)
    ver = np.asarray(srep.ver)[lane]
    val = np.asarray(srep.val)[lane]
    return [(int((hi[j] << 32) | lo[j]),
             tuple(int(x) for x in val[j]), int(ver[j]))
            for j in range(c)]


def test_scan_sees_pre_batch_state(rng):
    table = kv.create(1 << 6, slots=8, val_words=VW)
    vals0 = rand_vals(rng, 3)
    table = kv.populate(table, np.array([10, 20, 30], np.uint64), vals0)
    run = run_mod.from_table(table, delta_cap=DCAP)
    v = rand_vals(rng, 2)
    # SET 15 rides in the SAME batch: the scan must NOT see it (scans
    # are phase-1 reads — a valid serial order puts them with the GETs)
    table, run, rep, srep = scan_step(
        table, run, [Op.SET, Op.SCAN], np.array([15, 10], np.uint64),
        v, [0, 3])
    rt = np.asarray(rep.rtype)
    assert rt[1] == Reply.VAL
    assert int(np.asarray(rep.ver)[1]) == 3
    assert [r[0] for r in srep_rows(srep, 1)] == [10, 20, 30]
    # ...and the NEXT batch's scan sees the install, via the overlay
    table, run, rep, srep = scan_step(
        table, run, [Op.SCAN], np.array([10], np.uint64),
        rand_vals(rng, 1), [4])
    rows = srep_rows(srep, 0)
    assert [r[0] for r in rows] == [10, 15, 20, 30]
    assert rows[1][1] == tuple(int(x) for x in v[0])
    # scan lanes carry rows in the slab, never in the point-reply val
    assert (np.asarray(rep.val)[0] == 0).all()


def test_scan_differential_vs_oracle(rng):
    """Adversarial mixed batches: SCAN lanes straddling same-batch
    SET/INSERT/DELETE writes to the scanned range, reply-for-reply
    against the sequential oracle, run rebuilt at every drain boundary
    (keyspace <= 40: the oracle does not model SPILL)."""
    table = kv.create(1 << 6, slots=8, val_words=VW)
    oracle = StoreOracle()
    run = run_mod.from_table(table, delta_cap=DCAP)
    keyspace, n = 40, 24
    for it in range(12):
        ops = rng.choice(
            [Op.GET, Op.SET, Op.INSERT, Op.DELETE, Op.SCAN, Op.NOP],
            size=n, p=[0.2, 0.2, 0.05, 0.15, 0.3, 0.1]).astype(np.int32)
        keys = rng.integers(0, keyspace, size=n).astype(np.uint64)
        vals = rand_vals(rng, n)
        lens = np.where(ops == Op.SCAN,
                        rng.integers(0, SMAX + 1, size=n), 0)
        table, run, rep, srep = scan_step(table, run, ops, keys, vals,
                                          lens)
        rt = np.asarray(rep.rtype)[:n]
        rver = np.asarray(rep.ver)[:n]
        ot, ov, over, oscans = oracle.step(ops, keys, vals,
                                           scan_lens=lens, scan_max=SMAX)
        assert np.array_equal(rt, ot), (it, rt, ot)
        assert np.array_equal(rver, over), it
        for i in np.nonzero(ops == Op.SCAN)[0]:
            assert srep_rows(srep, i) == oscans[i], (it, i, keys[i])
        # drain boundary: fold the overlay before the overlay overflows
        run = store.rebuild_run(table, run)
        assert run_mod.to_items(run) == oracle.data
        assert kv.to_dict(table) == oracle.data


def test_scan_never_sees_spilled_insert(rng):
    """A SPILLed insert lands NOWHERE — not the table, not the overlay:
    a later scan over its range must skip it (the same fixup that keeps
    replies honest keeps the run honest)."""
    from dint_tpu.ops import hashing
    ks = np.arange(1, 4000, dtype=np.uint64)
    b1, b2 = hashing.bucket_pair_np(ks, 4)
    cands = ks[(b1 == 0) & (b2 == 1)]
    assert len(cands) >= 3
    k1, k2, k3 = (int(x) for x in cands[:3])
    table = kv.create(4, slots=1, val_words=VW)       # ne=4 >= 2+2
    run = run_mod.from_table(table, delta_cap=2)
    v = rand_vals(rng, 4)
    # k3's both buckets are full after k1/k2 land -> SPILL, same batch
    table, run, rep, srep = scan_step(
        table, run, [Op.INSERT, Op.INSERT, Op.INSERT, Op.SCAN],
        np.array([k1, k2, k3, 0], np.uint64), v, [0, 0, 0, 2],
        scan_max=2)
    rt = np.asarray(rep.rtype)
    assert list(rt[:3]) == [Reply.ACK, Reply.ACK, Reply.SPILL]
    assert srep_rows(srep, 3) == []                   # pre-batch: empty
    table, run, rep, srep = scan_step(
        table, run, [Op.SCAN], np.array([0], np.uint64),
        rand_vals(rng, 1), [2], scan_max=2)
    got = [r[0] for r in srep_rows(srep, 0)]
    assert got == sorted((k1, k2))[:2] and k3 not in got
    assert k3 not in run_mod.to_items(run)


def test_scan_two_routes_bit_identical(rng):
    """Acceptance: identical ScanReplies from (a) the run with a pending
    overlay and (b) the run after a drain-boundary rebuild_run folded
    the overlay."""
    table = kv.create(1 << 6, slots=8, val_words=VW)
    keys = rng.choice(40, size=25, replace=False).astype(np.uint64)
    table = kv.populate(table, keys, rand_vals(rng, 25))
    run = run_mod.from_table(table, delta_cap=DCAP)
    # populate the overlay: writes + a delete through the scan-threaded
    # step (effective-writer lanes are what delta_append receives)
    wops = [Op.SET, Op.SET, Op.INSERT, Op.DELETE]
    wkeys = np.array([keys[0], keys[1], 41, keys[2]], np.uint64)
    table, run, _, _ = scan_step(table, run, wops, wkeys,
                                 rand_vals(rng, 4), [0, 0, 0, 0])
    assert int(run.d_n) > 0
    sops = [Op.SCAN] * 6
    starts = np.array([0, 5, 17, 38, 41, 100], np.uint64)
    lens = np.array([SMAX, 3, 5, SMAX, 1, 4])
    svals = rand_vals(rng, 6)

    def answer(t, rn):
        _, _, rep, srep = scan_step(t, rn, sops, starts, svals, lens)
        return rep, srep

    rep_a, srep_a = answer(table, run)
    rebuilt = store.rebuild_run(table, run)
    assert int(rebuilt.d_n) == 0
    rep_c, srep_c = answer(table, rebuilt)
    assert np.array_equal(np.asarray(rep_c.rtype), np.asarray(rep_a.rtype))
    assert np.array_equal(np.asarray(rep_c.ver), np.asarray(rep_a.ver))
    for f in ("key_hi", "key_lo", "ver", "val", "count"):
        assert np.array_equal(np.asarray(getattr(srep_c, f)),
                              np.asarray(getattr(srep_a, f))), f
    # the overlay-pending route served rows from the delta...
    assert int(np.asarray(srep_a.delta_hits).sum()) > 0
    # ...and the rebuilt run serves the same rows from the dense run
    assert int(np.asarray(srep_c.delta_hits).sum()) == 0


def test_scan_stale_overlay_replies_retry_until_rebuild(rng):
    table = kv.create(1 << 6, slots=8, val_words=VW)
    table = kv.populate(table, np.arange(1, 9, dtype=np.uint64),
                        rand_vals(rng, 8))
    run = run_mod.from_table(table, delta_cap=2)
    # 4 distinct-key writes overflow the 2-entry overlay -> stale
    table, run, _, _ = scan_step(
        table, run, [Op.SET] * 4, np.array([1, 2, 3, 4], np.uint64),
        rand_vals(rng, 4), [0] * 4, scan_max=2)
    assert bool(np.asarray(run.stale))
    table, run, rep, srep = scan_step(
        table, run, [Op.SCAN], np.array([1], np.uint64),
        rand_vals(rng, 1), [2], scan_max=2)
    assert int(np.asarray(rep.rtype)[0]) == Reply.RETRY
    assert int(np.asarray(srep.count)[0]) == 0        # stale: no rows
    # drain-boundary refresh re-snapshots; the retry answers VAL
    run = store.rebuild_run(table, run)
    assert not bool(np.asarray(run.stale))
    table, run, rep, srep = scan_step(
        table, run, [Op.SCAN], np.array([1], np.uint64),
        rand_vals(rng, 1), [2], scan_max=2)
    assert int(np.asarray(rep.rtype)[0]) == Reply.VAL
    assert [r[0] for r in srep_rows(srep, 0)] == [1, 2]
