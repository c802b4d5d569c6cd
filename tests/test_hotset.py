"""dintcache (round 10): hot-set serving for the skewed random-access hot
path.

The acceptance bar of ISSUE 5: `DINT_USE_HOTSET=1` must be BIT-IDENTICAL
to the default path on every integrated engine — the hot mirror is a pure
acceleration structure (write-through keeps mirror == table prefix an
invariant), so stats, tables, arb stamps, and log rings cannot move. These
tests pin (a) the partitioned gather and scatter (ops/hotset.py) against
the plain take / double scatter, including an adversarial batch with
duplicate indices straddling the hot_n boundary; (b) the write-through
coherence invariant; (c) SmallBank dense + sharded, the store engine
(Zipfian micro), the cached store, and skewed-TATP end-to-end
bit-identical under the hot tier; (d) the env/resolve plumbing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dint_tpu.clients import workloads as wl
from dint_tpu.engines import smallbank_dense as sd, tatp_dense as td
from dint_tpu.ops import hotset

U32 = jnp.uint32
I32 = jnp.int32


# ------------------------------------------------------ the partition


def _plain_take(tab, idx, vw):
    return np.asarray(tab).reshape(-1, vw)[np.asarray(idx)].reshape(-1)


@pytest.mark.parametrize("n,hot,vw,k", [
    (1000, 40, 10, 333),     # val-style wide rows, 4% hot
    (512, 300, 1, 700),      # single words, most of the table mirrored
    (37, 5, 4, 5),           # a handful of lanes
    (64, 1, 2, 64),          # single-row mirror
])
def test_gather_rows_hot_matches_plain_and_xla(rng, n, hot, vw, k):
    tab = jnp.asarray(rng.integers(0, 1 << 32, n * vw, np.int64)
                      .astype(np.uint32))
    mirror = tab[:hot * vw]
    idx = jnp.asarray(rng.integers(0, n, k).astype(np.int32))
    midx = jnp.where(idx < hot, idx, -1)
    got = hotset.hot_gather(tab, mirror, idx, midx, vw)
    assert np.array_equal(np.asarray(got), _plain_take(tab, idx, vw))


def test_gather_rows_hot_duplicates_straddle_boundary(rng):
    """The adversarial batch: heavy duplication of the two rows on either
    side of hot_n — the exact lanes where a partition bug would read the
    wrong tier — interleaved so hot/cold alternate."""
    n, hot, vw = 100, 50, 3
    tab = jnp.asarray(rng.integers(0, 1 << 32, n * vw, np.int64)
                      .astype(np.uint32))
    mirror = tab[:hot * vw]
    idx = jnp.asarray(np.tile([hot - 1, hot, hot - 1, hot - 1, hot, hot],
                              32).astype(np.int32))
    midx = jnp.where(idx < hot, idx, -1)
    got = hotset.hot_gather(tab, mirror, idx, midx, vw)
    assert np.array_equal(np.asarray(got), _plain_take(tab, idx, vw))


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
    t_p, m_p = hotset.hot_scatter(jnp.array(tab), jnp.array(mirror),
                                  rows_j, midx, mask_j, vals, vw)
    # the plain double scatter, row by row
    t_x = np.asarray(tab).reshape(n, vw).copy()
    m_x = np.asarray(mirror).reshape(hot, vw).copy()
    v = np.asarray(vals).reshape(k, vw)
    t_x[rows[mask]] = v[mask]
    m_x[rows[mask & (rows < hot)]] = v[mask & (rows < hot)]
    assert np.array_equal(np.asarray(t_p), t_x.reshape(-1))
    assert np.array_equal(np.asarray(m_p), m_x.reshape(-1))
    # write-through coherence: the mirror IS the table prefix afterwards
    assert np.array_equal(np.asarray(t_p)[: hot * vw], np.asarray(m_p))


# ------------------------------------------------------------ resolve


def test_resolve_use_hotset_env(monkeypatch):
    monkeypatch.delenv("DINT_USE_HOTSET", raising=False)
    assert hotset.resolve_use_hotset(None) is False
    monkeypatch.setenv("DINT_USE_HOTSET", "0")
    assert hotset.resolve_use_hotset(None) is False
    monkeypatch.setenv("DINT_USE_HOTSET", "1")
    assert hotset.resolve_use_hotset(None) is True
    assert hotset.resolve_use_hotset(False) is False      # explicit wins


# --------------------------------------------- end-to-end: smallbank


def _run_sb(use_hotset, n=300, blocks=3):
    db = sd.create(n)
    run_f, init, drain = sd.build_pipelined_runner(
        n, w=64, cohorts_per_block=2, use_hotset=use_hotset)
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
    default path's stats, balances, stamps, and log rings bit for bit,
    and the mirror coherence invariant holds."""
    db0, t0 = _run_sb(False)
    monkeypatch.setenv("DINT_USE_HOTSET", "1")
    db1, t1 = _run_sb(None)                   # env route
    assert t0.tolist() == t1.tolist()
    assert int(t0[sd.STAT_COMMITTED]) > 0
    _same_shared_state(db0, db1, ("bal", "x_step", "s_step", "step"))
    hn, n = db1.hot_n, db1.n_accounts
    assert hn == max(1, int(n * wl.SB_HOT_FRAC))
    idx = np.concatenate([np.arange(hn), n + np.arange(hn)])
    assert np.array_equal(np.asarray(db1.bal)[idx], np.asarray(db1.hot_bal))
    assert np.array_equal(np.asarray(db1.x_step)[idx],
                          np.asarray(db1.hot_x))
    assert np.array_equal(np.asarray(db1.s_step)[idx],
                          np.asarray(db1.hot_s))
    # conservation on the hot path
    start = 2 * 300 * 1000
    assert int(np.asarray(sd.total_balance(db1))) \
        == start + int(t1[sd.STAT_BAL_DELTA])


def test_smallbank_hashed_locks_skip_stamp_mirror(monkeypatch):
    """Above the slot cap the lock tables hash (cold accounts conflate
    onto hot slots), so the stamp mirror must NOT exist — only balances
    mirror — and outputs stay bit-identical."""
    monkeypatch.setattr(sd, "MAX_LOCK_SLOTS", 128)
    db0, t0 = _run_sb(False, n=200)
    db1, t1 = _run_sb(True, n=200)
    assert db1.hot_x is None and db1.hot_s is None
    assert db1.hot_bal is not None
    assert t0.tolist() == t1.tolist()
    _same_shared_state(db0, db1, ("bal", "x_step", "s_step", "step"))


# ----------------------------------------------- end-to-end: sharded


@pytest.mark.slow  # ~11s; the round-10 rule — dense + store hot pins stay tier-1
def test_dense_sharded_sb_hotset_bit_identical():
    """Two configs (baseline vs hot tier); one shard_map compile per
    config."""
    from dint_tpu.parallel import dense_sharded_sb as dsb

    def run(uh):
        mesh = dsb.make_mesh(8)
        state = dsb.create_sharded_sb(mesh, 8, 400)
        run_f, init, drain = dsb.build_sharded_sb_runner(
            mesh, 8, 400, w=32, cohorts_per_block=2, use_hotset=uh)
        carry = init(state)
        tot = np.zeros(dsb.N_STATS, np.int64)
        for i in range(2):
            carry, s = run_f(carry,
                             jax.random.fold_in(jax.random.PRNGKey(2), i))
            tot += np.asarray(s, np.int64).sum(axis=0)
        state, tail = drain(carry)
        return state, tot + np.asarray(tail, np.int64).sum(axis=0)

    s0, t0 = run(False)
    s2, t2 = run(True)
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
    meta/magic gathers and write-through installs — bit-identical stats,
    tables, stamps, logs. slow-marked: TATP's hot tier is the
    off-by-default experiment route, and the partition's mechanics (hot
    gather/scatter parity) are pinned by the tier-1 tests above."""
    def run(uh):
        db = td.populate(np.random.default_rng(0), 200, val_words=4)
        run_f, init, drain = td.build_pipelined_runner(
            200, w=64, val_words=4, cohorts_per_block=2,
            use_hotset=uh, hot_frac=0.2)
        carry = init(db)
        tot = np.zeros(td.N_STATS, np.int64)
        for i in range(3):
            carry, s = run_f(carry,
                             jax.random.fold_in(jax.random.PRNGKey(0), i))
            tot += np.asarray(s, np.int64).sum(axis=0)
        db, tail = drain(carry)
        return db, tot + np.asarray(tail, np.int64).sum(axis=0)

    db0, t0 = run(False)
    db1, t1 = run(True)
    assert t0.tolist() == t1.tolist()
    assert int(t0[td.STAT_COMMITTED]) > 0
    _same_shared_state(db0, db1, ("val", "meta", "arb", "step"))
    hn = db1.hot_n
    assert np.array_equal(np.asarray(db1.meta)[:hn],
                          np.asarray(db1.hot_meta))
    assert np.array_equal(np.asarray(db1.val)[: hn * 4],
                          np.asarray(db1.hot_val))


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
    with the hot tier threaded, mirror coherent with every
    currently-present hot key."""
    from dint_tpu.clients.micro import STORE_MAGIC, make_store_table
    from dint_tpu.engines import store
    from dint_tpu.engines.types import Op, make_batch
    from dint_tpu.ops import hashing
    from dint_tpu.tables import kv

    n_keys, width, vw, hot_n = 2000, 256, 10, 500

    def run(hot_on):
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
                table, rep, hot = store.step(table, batch, hot=hot)
            reps.append(jax.tree.map(np.asarray, rep))
        return table, hot, reps

    t0, _, r0 = run(False)
    t1, h1, r1 = run(True)
    for a, b in zip(r0, r1):
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert np.array_equal(la, lb)
    for leaf in ("key_hi", "key_lo", "val", "ver", "valid"):
        assert np.array_equal(np.asarray(getattr(t0, leaf)),
                              np.asarray(getattr(t1, leaf))), leaf
    # mirror == table for every hot key the probe can hit
    klo = jnp.arange(hot_n, dtype=U32)
    khi = jnp.zeros((hot_n,), U32)
    b1, b2 = hashing.bucket_pair(khi, klo, t1.n_buckets)
    hit, _, _, val, ver, _, _ = kv.probe(t1, khi, klo, b1, b2)
    hitn = np.asarray(hit)
    assert hitn.any()
    assert np.array_equal(np.asarray(val)[hitn],
                          np.asarray(h1.val).reshape(hot_n, vw)[hitn])
    assert np.array_equal(np.asarray(ver)[hitn], np.asarray(h1.ver)[hitn])


@pytest.mark.slow
def test_store_cache_hotset_bit_identical():
    """Cache-mode store: replies, miss vector, MASKED flush/evicted
    records, and cache tables bit-identical across all three policies
    with the in-cache hot tier on. Flush/evicted values of
    mask-False lanes are don't-cares by contract (the host applies only
    masked lanes), so comparison is on the masked set. slow-marked (6
    jitted configs): the full-table store engine's hot tier — the same
    HotKV partition — is pinned in tier-1 above."""
    from dint_tpu.engines import store_cache as sc
    from dint_tpu.engines.types import Op, make_batch

    vw = 10

    def run(hot_keys, policy):
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
                                                    policy=policy)
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
        c0, o0 = run(0, pol)
        c1, o1 = run(300, pol)
        for oa, ob in zip(o0, o1):
            for la, lb in zip(jax.tree.leaves(oa), jax.tree.leaves(ob)):
                assert np.array_equal(la, lb), pol
        for leaf in ("key_hi", "key_lo", "val", "ver", "valid"):
            assert np.array_equal(np.asarray(getattr(c0.kv, leaf)),
                                  np.asarray(getattr(c1.kv, leaf))), \
                (pol, leaf)
        assert np.array_equal(np.asarray(c0.dirty),
                              np.asarray(c1.dirty)), pol


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
