"""dintcache (round 10): VMEM-resident hot-set serving for the skewed
random-access hot path.

The acceptance bar of ISSUE 5: `DINT_USE_HOTSET=1` must be BIT-IDENTICAL
to the default path on every integrated engine — the hot mirror is a pure
acceleration structure (write-through keeps mirror == table prefix an
invariant), so stats, tables, arb stamps, and log rings cannot move. These
tests pin (a) each hot kernel against its XLA partition AND the plain
round-6 path, including an adversarial batch with duplicate indices
straddling the hot_n boundary; (b) the write-through coherence invariant;
(c) SmallBank dense + sharded, the store engine (Zipfian micro), the
cached store, and skewed-TATP end-to-end bit-identical under the hot tier
on BOTH serving routes (XLA partition and pallas VMEM kernels); (d) the
env/resolve plumbing and the per-kernel probe cache (the round-10 probe
recompile fix); (e) the degrade contract — a broken hot kernel costs the
VMEM residency, never the partition or the measurement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dint_tpu.clients import workloads as wl
from dint_tpu.engines import smallbank_dense as sd, tatp_dense as td
from dint_tpu.ops import pallas_gather as pg

U32 = jnp.uint32
I32 = jnp.int32


# -------------------------------------------------------- hot kernels


@pytest.mark.parametrize("n,hot,vw,k", [
    (1000, 40, 10, 333),     # val-style wide rows, 4% hot
    (512, 300, 1, 700),      # single words, most of the table mirrored
    (37, 5, 4, 5),           # K below the DMA ring depth
    (64, 1, 2, 64),          # single-row mirror
])
def test_gather_rows_hot_matches_plain_and_xla(rng, n, hot, vw, k):
    tab = jnp.asarray(rng.integers(0, 1 << 32, n * vw, np.int64)
                      .astype(np.uint32))
    mirror = tab[:hot * vw]
    idx = jnp.asarray(rng.integers(0, n, k).astype(np.int32))
    midx = jnp.where(idx < hot, idx, -1)
    got = pg.gather_rows_hot(tab, mirror, idx, midx, vw)
    assert np.array_equal(np.asarray(got),
                          np.asarray(pg.gather_rows(tab, idx, vw)))
    assert np.array_equal(
        np.asarray(got),
        np.asarray(pg._xla_hot_gather(tab, mirror, idx, midx, vw)))


def test_gather_rows_hot_duplicates_straddle_boundary(rng):
    """The adversarial batch: heavy duplication of the two rows on either
    side of hot_n — the exact lanes where a partition bug would read the
    wrong tier — interleaved so hot/cold alternate within the ring."""
    n, hot, vw = 100, 50, 3
    tab = jnp.asarray(rng.integers(0, 1 << 32, n * vw, np.int64)
                      .astype(np.uint32))
    mirror = tab[:hot * vw]
    idx = jnp.asarray(np.tile([hot - 1, hot, hot - 1, hot - 1, hot, hot],
                              32).astype(np.int32))
    midx = jnp.where(idx < hot, idx, -1)
    got = pg.gather_rows_hot(tab, mirror, idx, midx, vw)
    assert np.array_equal(np.asarray(got),
                          np.asarray(pg.gather_rows(tab, idx, vw)))


def test_scatter_rows_hot_matches_double_scatter(rng):
    n, hot, vw, k = 200, 37, 3, 300
    tab = jnp.asarray(rng.integers(0, 1 << 32, n * vw, np.int64)
                      .astype(np.uint32))
    mirror = tab[:hot * vw]
    # unique rows among masked lanes (the engines' one-writer contract),
    # straddling the boundary
    perm = rng.permutation(n)[: k % n if k % n else n]
    rows = np.zeros(k, np.int32)
    mask = np.zeros(k, bool)
    rows[: len(perm)] = perm
    mask[: len(perm)] = rng.random(len(perm)) < 0.6
    rows_j = jnp.asarray(rows)
    midx = jnp.where(rows_j < hot, rows_j, -1)
    mask_j = jnp.asarray(mask)
    vals = jnp.asarray(rng.integers(0, 1 << 32, k * vw, np.int64)
                       .astype(np.uint32))
    t_p, m_p = pg.scatter_rows_hot(jnp.array(tab), jnp.array(mirror),
                                   rows_j, midx, mask_j, vals, vw)
    t_x, m_x = pg.hot_scatter(jnp.array(tab), jnp.array(mirror), rows_j,
                              midx, mask_j, vals, vw, use_pallas=False)
    assert np.array_equal(np.asarray(t_p), np.asarray(t_x))
    assert np.array_equal(np.asarray(m_p), np.asarray(m_x))
    # write-through coherence: the mirror IS the table prefix afterwards
    assert np.array_equal(np.asarray(t_p)[: hot * vw], np.asarray(m_p))


@pytest.mark.parametrize("m,row_space,hot_n,seed", [
    (64, 8, 4, 0),     # brutal duplication, boundary inside the row set
    (64, 1000, 40, 1),  # mostly conflict-free, 4%-style prefix
    (10, 3, 1, 2),      # m > ring depth barely
    (130, 16, 8, 4),    # several ring wraps, half the rows hot
])
def test_lock_arbitrate_hot_prefix_bit_identical(m, row_space, hot_n,
                                                 seed):
    """The VMEM arb-prefix residency changes only DMA endpoints: grants
    and stamps must match both the hot_n=0 kernel and the XLA chain on
    adversarial duplicate/held batches straddling the prefix."""
    r = np.random.default_rng(seed)
    n1 = max(row_space + 1, 32)
    arb0 = np.zeros(n1, np.uint32)
    for row in r.choice(row_space, max(1, row_space // 3), replace=False):
        step = r.choice([3, 4])
        arb0[row] = np.uint32((step << td.K_ARB) | r.integers(0, 100))
    t = jnp.asarray(5, U32)
    rows = jnp.asarray(r.integers(0, row_space, m).astype(np.int32))
    act = jnp.asarray(r.random(m) < 0.75)
    a_0, g_0 = pg.lock_arbitrate(jnp.asarray(arb0), rows, act, t,
                                 td.K_ARB)
    a_h, g_h = pg.lock_arbitrate(jnp.asarray(arb0), rows, act, t,
                                 td.K_ARB, hot_n=hot_n)
    assert np.array_equal(np.asarray(a_0), np.asarray(a_h))
    assert np.array_equal(np.asarray(g_0), np.asarray(g_h))


# ------------------------------------------------- resolve + probe cache


def test_resolve_use_hotset_env(monkeypatch):
    monkeypatch.delenv("DINT_USE_HOTSET", raising=False)
    assert pg.resolve_use_hotset(None) is False
    monkeypatch.setenv("DINT_USE_HOTSET", "0")
    assert pg.resolve_use_hotset(None) is False
    monkeypatch.setenv("DINT_USE_HOTSET", "1")
    assert pg.resolve_use_hotset(None) is True
    assert pg.resolve_use_hotset(False) is False      # explicit wins


def test_probe_cache_is_per_kernel(monkeypatch):
    """The round-10 probe fix: a second kernels_available call that only
    changes the OTHER kernel's geometry must hit the gather probe's
    cache — proven by breaking gather_rows after the first call."""
    pg._probe_cache.clear()
    assert pg.kernels_available(n_idx=96, m_lock=24) is True

    def boom(*a, **k):
        raise RuntimeError("probe must not re-run (simulated)")

    monkeypatch.setattr(pg, "gather_rows", boom)
    # same gather geometry, no lock probe requested: pure cache hit
    assert pg.kernels_available(n_idx=96, m_lock=None) is True
    # same gather geometry, NEW lock geometry: only the lock re-probes
    assert pg.kernels_available(n_idx=96, m_lock=12) is True
    pg._probe_cache.clear()


def test_broken_hot_kernel_raises_and_xla_partition_still_serves(monkeypatch):
    """A Mosaic refusal of the hot kernels raises for the caller that
    asked for them (use_pallas + use_hotset) — resolver and builder both,
    never cached — while the partition itself has an XLA form that a
    caller who does NOT ask for the kernels still gets."""
    pg._probe_cache.clear()

    def boom(*a, **k):
        raise RuntimeError("Mosaic lowering failed (simulated)")

    monkeypatch.setattr(pg, "gather_rows_hot", boom)
    for _ in range(2):
        with pytest.raises(pg.KernelRefused,
                           match=r"'hot'.*Mosaic lowering failed"):
            pg.hot_kernels_available(n_idx=64)
    assert not any(k[0] == "hot" for k in pg._probe_cache)
    # bypass the builder memo: this build must see the broken kernel
    sd.build_pipelined_runner.cache.clear()
    with pytest.raises(pg.KernelRefused, match="'hot'"):
        sd.build_pipelined_runner(100, w=16, cohorts_per_block=2,
                                  use_pallas=True, use_hotset=True)
    run_f, init, drain = sd.build_pipelined_runner(
        100, w=16, cohorts_per_block=2, use_pallas=False, use_hotset=True)
    carry = init(sd.create(100))
    carry, s = run_f(carry, jax.random.PRNGKey(0))
    db, tail = drain(carry)
    tot = (np.asarray(s, np.int64).sum(axis=0)
           + np.asarray(tail, np.int64).sum(axis=0))
    assert int(tot[sd.STAT_ATTEMPTED]) == 2 * 16
    assert db.hot_n > 0                       # the partition still ran
    pg._probe_cache.clear()
    sd.build_pipelined_runner.cache.clear()


# --------------------------------------------- end-to-end: smallbank


def _run_sb(use_hotset, use_pallas, n=300, blocks=3):
    db = sd.create(n)
    run_f, init, drain = sd.build_pipelined_runner(
        n, w=64, cohorts_per_block=2, use_pallas=use_pallas,
        use_hotset=use_hotset)
    carry = init(db)
    tot = np.zeros(sd.N_STATS, np.int64)
    for i in range(blocks):
        carry, s = run_f(carry, jax.random.fold_in(jax.random.PRNGKey(3),
                                                   i))
        tot += np.asarray(s, np.int64).sum(axis=0)
    db, tail = drain(carry)
    return db, tot + np.asarray(tail, np.int64).sum(axis=0)


def _same_shared_state(db0, db1, leaves, log=True):
    for leaf in leaves:
        assert np.array_equal(np.asarray(getattr(db0, leaf)),
                              np.asarray(getattr(db1, leaf))), leaf
    if log:
        assert np.array_equal(np.asarray(db0.log.entries),
                              np.asarray(db1.log.entries))
        assert np.array_equal(np.asarray(db0.log.head),
                              np.asarray(db1.log.head))


def test_smallbank_dense_hotset_bit_identical(monkeypatch):
    """ISSUE 5 acceptance pin: DINT_USE_HOTSET=1 (env route, the exact
    production spelling, at the workload's hot_frac=0.04) reproduces the
    default path's stats, balances, stamps, and log rings bit for bit on
    BOTH serving routes, and the mirror coherence invariant holds."""
    db0, t0 = _run_sb(False, False)
    monkeypatch.setenv("DINT_USE_HOTSET", "1")
    db1, t1 = _run_sb(None, False)            # env route
    db2, t2 = _run_sb(None, True)             # + VMEM kernels
    assert t0.tolist() == t1.tolist() == t2.tolist()
    assert int(t0[sd.STAT_COMMITTED]) > 0
    for db in (db1, db2):
        _same_shared_state(db0, db, ("bal", "x_step", "s_step", "step"))
        hn, n = db.hot_n, db.n_accounts
        assert hn == max(1, int(n * wl.SB_HOT_FRAC))
        idx = np.concatenate([np.arange(hn), n + np.arange(hn)])
        assert np.array_equal(np.asarray(db.bal)[idx],
                              np.asarray(db.hot_bal))
        assert np.array_equal(np.asarray(db.x_step)[idx],
                              np.asarray(db.hot_x))
        assert np.array_equal(np.asarray(db.s_step)[idx],
                              np.asarray(db.hot_s))
    # conservation on the hot path
    start = 2 * 300 * 1000
    assert int(np.asarray(sd.total_balance(db2))) \
        == start + int(t2[sd.STAT_BAL_DELTA])


def test_smallbank_hashed_locks_skip_stamp_mirror(monkeypatch):
    """Above the slot cap the lock tables hash (cold accounts conflate
    onto hot slots), so the stamp mirror must NOT exist — only balances
    mirror — and outputs stay bit-identical."""
    monkeypatch.setattr(sd, "MAX_LOCK_SLOTS", 128)
    db0, t0 = _run_sb(False, False, n=200)
    db1, t1 = _run_sb(True, False, n=200)
    assert db1.hot_x is None and db1.hot_s is None
    assert db1.hot_bal is not None
    assert t0.tolist() == t1.tolist()
    _same_shared_state(db0, db1, ("bal", "x_step", "s_step", "step"))


# ----------------------------------------------- end-to-end: sharded


@pytest.mark.slow  # ~11s; the round-10 rule — dense + store hot pins stay tier-1
def test_dense_sharded_sb_hotset_bit_identical():
    """Two configs in tier-1 (baseline vs hot tier on the VMEM kernels —
    the XLA-partition route is pinned on single-chip above); one shard_map
    compile per config keeps the test inside the tier-1 budget."""
    from dint_tpu.parallel import dense_sharded_sb as dsb

    def run(uh, up):
        mesh = dsb.make_mesh(8)
        state = dsb.create_sharded_sb(mesh, 8, 400)
        run_f, init, drain = dsb.build_sharded_sb_runner(
            mesh, 8, 400, w=32, cohorts_per_block=2, use_pallas=up,
            use_hotset=uh)
        carry = init(state)
        tot = np.zeros(dsb.N_STATS, np.int64)
        for i in range(2):
            carry, s = run_f(carry,
                             jax.random.fold_in(jax.random.PRNGKey(2), i))
            tot += np.asarray(s, np.int64).sum(axis=0)
        state, tail = drain(carry)
        return state, tot + np.asarray(tail, np.int64).sum(axis=0)

    s0, t0 = run(False, False)
    s2, t2 = run(True, True)
    assert t0.tolist() == t2.tolist()
    assert int(t0[1]) > 0                      # committed
    for s in (s2,):
        _same_shared_state(s0, s, ("bal", "bck_bal", "x_step", "s_step",
                                   "step"))
        hl = s.hot_loc
        n_loc = np.asarray(s.bal).shape[1] // 2
        idx = np.concatenate([np.arange(hl), n_loc + np.arange(hl)])
        assert np.array_equal(np.asarray(s.bal)[:, idx],
                              np.asarray(s.hot_bal))
        assert np.array_equal(np.asarray(s.x_step)[:, idx],
                              np.asarray(s.hot_x))
        assert np.array_equal(np.asarray(s.s_step)[:, idx],
                              np.asarray(s.hot_s))


# ----------------------------------------------- end-to-end: skewed TATP


@pytest.mark.slow
def test_tatp_dense_hotset_bit_identical():
    """Skewed-TATP experiment route (builder kwarg; off by default):
    meta/magic gathers, write-through installs, and the VMEM arb-prefix
    lock pass — bit-identical stats, tables, stamps, logs. slow-marked:
    TATP's hot tier is the off-by-default experiment route, and its
    kernel mechanics (hot gather/scatter parity, the arb-prefix lock
    pass) are pinned by the tier-1 kernel tests above."""
    def run(uh, up):
        db = td.populate(np.random.default_rng(0), 200, val_words=4)
        run_f, init, drain = td.build_pipelined_runner(
            200, w=64, val_words=4, cohorts_per_block=2, use_pallas=up,
            use_hotset=uh, hot_frac=0.2)
        carry = init(db)
        tot = np.zeros(td.N_STATS, np.int64)
        for i in range(3):
            carry, s = run_f(carry,
                             jax.random.fold_in(jax.random.PRNGKey(0), i))
            tot += np.asarray(s, np.int64).sum(axis=0)
        db, tail = drain(carry)
        return db, tot + np.asarray(tail, np.int64).sum(axis=0)

    db0, t0 = run(False, False)
    db1, t1 = run(True, False)
    db2, t2 = run(True, True)
    assert t0.tolist() == t1.tolist() == t2.tolist()
    assert int(t0[td.STAT_COMMITTED]) > 0
    for db in (db1, db2):
        _same_shared_state(db0, db, ("val", "meta", "arb", "step"))
        hn = db.hot_n
        assert np.array_equal(np.asarray(db.meta)[:hn],
                              np.asarray(db.hot_meta))
        assert np.array_equal(np.asarray(db.val)[: hn * 4],
                              np.asarray(db.hot_val))


def test_tatp_dense_hotset_off_by_default(monkeypatch):
    """TATP is uniform: DINT_USE_HOTSET must NOT turn the TATP hot tier
    on — only the explicit builder kwarg does."""
    monkeypatch.setenv("DINT_USE_HOTSET", "1")
    run_f, init, _ = td.build_pipelined_runner(50, w=16, val_words=4,
                                               cohorts_per_block=2)
    carry = init(td.populate(np.random.default_rng(0), 50, val_words=4))
    assert carry[0].hot_n == 0 and carry[0].hot_meta is None


# --------------------------------------------- end-to-end: store engine


def test_store_hotset_bit_identical(rng):
    """The Zipfian store micro's engine: replies and table bit-identical
    with the hot tier threaded (both routes), mirror coherent with every
    currently-present hot key."""
    from dint_tpu.clients.micro import STORE_MAGIC, make_store_table
    from dint_tpu.engines import store
    from dint_tpu.engines.types import Op, make_batch
    from dint_tpu.ops import hashing
    from dint_tpu.tables import kv

    n_keys, width, vw, hot_n = 2000, 256, 10, 500

    def run(hot_on, up):
        r = np.random.default_rng(7)
        table = make_store_table(n_keys)
        hot = store.attach_hot(table, hot_n) if hot_on else None
        reps = []
        for _ in range(4):
            keys = wl.zipf_keys(r, width, int(n_keys * 1.2))
            u = r.random(width)
            ops = np.where(u < 0.5, Op.GET,
                           np.where(u < 0.8, Op.SET,
                                    np.where(u < 0.9, Op.INSERT,
                                             Op.DELETE))).astype(np.int32)
            vals = np.zeros((width, vw), np.uint32)
            vals[:, 0] = r.integers(0, 1 << 30, width)
            vals[:, 1] = STORE_MAGIC
            batch = make_batch(ops, keys, vals, width=width, val_words=vw)
            if hot is None:
                table, rep = store.step(table, batch)
            else:
                table, rep, hot = store.step(table, batch, hot=hot,
                                             use_pallas=up)
            reps.append(jax.tree.map(np.asarray, rep))
        return table, hot, reps

    t0, _, r0 = run(False, False)
    t1, h1, r1 = run(True, False)
    t2, h2, r2 = run(True, True)
    for other in (r1, r2):
        for a, b in zip(r0, other):
            for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                assert np.array_equal(la, lb)
    for t in (t1, t2):
        for leaf in ("key_hi", "key_lo", "val", "ver", "valid"):
            assert np.array_equal(np.asarray(getattr(t0, leaf)),
                                  np.asarray(getattr(t, leaf))), leaf
    # mirror == table for every hot key the probe can hit
    klo = jnp.arange(hot_n, dtype=U32)
    khi = jnp.zeros((hot_n,), U32)
    b1, b2 = hashing.bucket_pair(khi, klo, t1.n_buckets)
    hit, _, _, val, ver, _, _ = kv.probe(t1, khi, klo, b1, b2)
    hitn = np.asarray(hit)
    assert hitn.any()
    for h in (h1, h2):
        assert np.array_equal(np.asarray(val)[hitn],
                              np.asarray(h.val).reshape(hot_n, vw)[hitn])
        assert np.array_equal(np.asarray(ver)[hitn],
                              np.asarray(h.ver)[hitn])


@pytest.mark.slow
def test_store_cache_hotset_bit_identical():
    """Cache-mode store: replies, miss vector, MASKED flush/evicted
    records, and cache tables bit-identical across all three policies
    with the in-cache hot tier on (both routes). Flush/evicted values of
    mask-False lanes are don't-cares by contract (the host applies only
    masked lanes), so comparison is on the masked set. slow-marked (9
    jitted configs): the full-table store engine's hot tier — the same
    HotKV partition — is pinned in tier-1 above."""
    from dint_tpu.engines import store_cache as sc
    from dint_tpu.engines.types import Op, make_batch

    vw = 10

    def run(hot_keys, up, policy):
        cache = sc.create(64, val_words=vw, hot_keys=hot_keys)
        outs = []
        r = np.random.default_rng(3)
        for _ in range(4):
            keys = r.integers(1, 400, 128).astype(np.uint64)
            ops = np.where(r.random(128) < 0.6, Op.GET,
                           Op.SET).astype(np.int32)
            vals = np.zeros((128, vw), np.uint32)
            vals[:, 0] = r.integers(0, 99, 128)
            batch = make_batch(ops, keys, vals, width=128, val_words=vw)
            cache, rep, miss, flush = sc.cache_step(cache, batch,
                                                    policy=policy,
                                                    use_pallas=up)
            m = np.asarray(miss)
            rk = keys[m][:32]
            pad = 64
            rkl = np.zeros(pad, np.uint32)
            rkl[: len(rk)] = rk.astype(np.uint32)
            rv = np.zeros((pad, vw), np.uint32)
            rv[:, 0] = 7
            rver = np.zeros(pad, np.uint32)
            rver[: len(rk)] = 1
            mask = np.zeros(pad, bool)
            mask[: len(rk)] = True
            cache, ev = sc.refill(
                cache, jnp.zeros(pad, U32), jnp.asarray(rkl),
                jnp.asarray(rv), jnp.asarray(rver), jnp.zeros(pad, U32),
                jnp.zeros(pad, U32), jnp.asarray(mask))
            fm = np.asarray(flush["mask"])
            em = np.asarray(ev["mask"])
            outs.append((jax.tree.map(np.asarray, rep), m, fm,
                         np.asarray(flush["val"])[fm],
                         np.asarray(flush["ver"])[fm],
                         em, np.asarray(ev["val"])[em]))
        return cache, outs

    for pol in (sc.WB_BLOOM, sc.WB_NOBLOOM, sc.WT):
        c0, o0 = run(0, False, pol)
        c1, o1 = run(300, False, pol)
        c2, o2 = run(300, True, pol)
        for other in (o1, o2):
            for oa, ob in zip(o0, other):
                for la, lb in zip(jax.tree.leaves(oa),
                                  jax.tree.leaves(ob)):
                    assert np.array_equal(la, lb), pol
        for c in (c1, c2):
            for leaf in ("key_hi", "key_lo", "val", "ver", "valid"):
                assert np.array_equal(np.asarray(getattr(c0.kv, leaf)),
                                      np.asarray(getattr(c.kv, leaf))), \
                    (pol, leaf)
            assert np.array_equal(np.asarray(c0.dirty),
                                  np.asarray(c.dirty)), pol


# ------------------------------------------------------------ workload


def test_zipf_keys_hot_head():
    """rank == key id: the Zipfian head concentrates on the smallest ids
    (the dintcache prefix), in range, strongly skewed at theta=0.99."""
    rng = np.random.default_rng(0)
    k = wl.zipf_keys(rng, 100_000, 10_000)
    assert k.min() >= 1 and k.max() <= 10_000
    assert (k <= 400).mean() > 0.5            # 4% of keys, >50% of draws
    # theta=0 degenerates toward uniform
    u = wl.zipf_keys(rng, 100_000, 10_000, theta=0.0)
    assert abs((u <= 400).mean() - 0.04) < 0.01


def test_store_client_zipf_hotset_waves():
    """The micro client end-to-end: Zipfian + hot tier threaded through
    the jitted step, magic intact, goodput == batch width."""
    from dint_tpu.clients import micro

    rng = np.random.default_rng(0)
    c = micro.StoreClient.populated(2000, width=256, key_dist="zipfian",
                                    use_hotset=True, hot_frac=0.1)
    assert c.use_hotset
    for _ in range(3):
        assert c.run_wave(rng) == 256
