"""Interpret-mode parity for the Pallas DMA-ring kernels (ops/pallas_gather).

Every kernel must be BIT-IDENTICAL to the XLA op chain it replaces — the
acceptance bar of ISSUE 1: `DINT_USE_PALLAS=1 JAX_PLATFORMS=cpu` runs the
dense engines through the kernels (interpret mode, no Mosaic) and must
reproduce the XLA path's stats, table state, and log rings exactly. These
tests pin (a) each kernel against its XLA formula, (b) the fused lock pass
against tatp_dense's actual arb chain on adversarial duplicate/held
batches, (c) both dense engines end-to-end pallas-vs-XLA, with the env-var
plumbing exercised for real, and (d) the refusal contract: a kernel
that was asked for and is broken raises KernelRefused, from the resolver
and from the builder, and is never cached as available."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dint_tpu.engines import smallbank_dense as sd, tatp_dense as td
from dint_tpu.ops import pallas_gather as pg

U32 = jnp.uint32
I32 = jnp.int32


# ------------------------------------------------------------ gather_rows


@pytest.mark.parametrize("n,vw,k", [
    (1000, 10, 333),      # val-style wide rows
    (512, 1, 700),        # meta/arb/bal-style single words, K > N
    (37, 4, 5),           # K smaller than the DMA ring depth
    (64, 2, 64),
])
def test_gather_rows_matches_xla_take(rng, n, vw, k):
    tab = jnp.asarray(rng.integers(0, 1 << 32, n * vw, np.int64)
                      .astype(np.uint32))
    idx = jnp.asarray(rng.integers(0, n, k).astype(np.int32))
    got = pg.gather_rows(tab, idx, vw)
    want = jnp.take(tab.reshape(n, vw), idx, axis=0).reshape(-1)
    assert got.dtype == jnp.uint32 and got.shape == (k * vw,)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_gather_rows_duplicate_and_sentinel_indices(rng):
    """The engines clamp every masked lane onto one sentinel row: heavy
    duplication of a single index must read clean."""
    n, vw = 100, 4
    tab = jnp.asarray(rng.integers(0, 1 << 32, n * vw, np.int64)
                      .astype(np.uint32))
    idx = jnp.asarray(np.full(64, n - 1, np.int32))   # all-sentinel batch
    got = pg.gather_rows(tab, idx, vw)
    assert np.array_equal(np.asarray(got).reshape(64, vw),
                          np.tile(np.asarray(tab[-vw:]), (64, 1)))


def test_gather_rows_word_offset_pattern(rng):
    """The magic-word check gathers ONE word at rows*VW + 1 — expressed as
    pre-scaled flat indices with vw=1."""
    n, vw = 200, 10
    tab = jnp.asarray(rng.integers(0, 1 << 32, n * vw, np.int64)
                      .astype(np.uint32))
    rows = jnp.asarray(rng.integers(0, n, 77).astype(np.int32))
    got = pg.gather_rows(tab, rows * vw + 1, 1)
    assert np.array_equal(np.asarray(got), np.asarray(tab[rows * vw + 1]))


# --------------------------------------------------------- lock_arbitrate


def _xla_chain(arb, rows, active, t, k_arb=td.K_ARB):
    """The exact 3-op chain of tatp_dense.pipe_step's XLA lock path."""
    m = rows.shape[0]
    oob = arb.shape[0]
    old = arb[rows]
    held = (old >> k_arb) == (t - 1)
    packed = (t << k_arb) | (jnp.uint32(m - 1)
                             - jnp.arange(m, dtype=jnp.uint32))
    cand = active & ~held
    arb2 = arb.at[jnp.where(cand, rows, oob)].max(packed, mode="drop")
    grant = cand & (arb2[rows] == packed)
    return arb2, grant


@pytest.mark.parametrize("m,row_space,seed", [
    (64, 8, 0),      # heavy in-batch duplication (8 rows, 64 lanes)
    (64, 1000, 1),   # mostly conflict-free
    (10, 3, 2),      # m > ring depth barely, brutal duplication
    (2, 1, 3),       # m below the ring depth, single row
    (130, 16, 4),    # several ring wraps
])
def test_lock_arbitrate_matches_xla(rng, m, row_space, seed):
    r = np.random.default_rng(seed)
    n1 = max(row_space + 1, 32)
    arb0 = np.zeros(n1, np.uint32)
    # pre-stamp a third of rows: half held (step-1), half stale/expired
    for row in r.choice(row_space, max(1, row_space // 3), replace=False):
        step = r.choice([3, 4])       # t=5: 4 == held, 3 == expired
        arb0[row] = np.uint32((step << td.K_ARB) | r.integers(0, 100))
    t = jnp.asarray(5, U32)
    rows = jnp.asarray(r.integers(0, row_space, m).astype(np.int32))
    act = jnp.asarray(r.random(m) < 0.75)

    a_x, g_x = _xla_chain(jnp.asarray(arb0), rows, act, t)
    a_p, g_p = pg.lock_arbitrate(jnp.asarray(arb0), rows, act, t, td.K_ARB)
    assert np.array_equal(np.asarray(a_x), np.asarray(a_p))
    assert np.array_equal(np.asarray(g_x), np.asarray(g_p) != 0)


def test_lock_arbitrate_held_rows_not_restamped(rng):
    """Candidates on held rows are masked OUT of the XLA scatter so hot
    rows cannot be livelocked by rejected attempts — the kernel must
    preserve exactly that: a held row's stamp survives untouched."""
    n1, m = 16, 8
    t = jnp.asarray(9, U32)
    arb0 = np.zeros(n1, np.uint32)
    arb0[2] = np.uint32((8 << td.K_ARB) | 5)          # held (t-1)
    rows = jnp.asarray(np.full(m, 2, np.int32))       # everyone wants row 2
    act = jnp.ones(m, bool)
    a_p, g_p = pg.lock_arbitrate(jnp.asarray(arb0), rows,
                                 jnp.asarray(act), t, td.K_ARB)
    assert int(np.asarray(g_p).sum()) == 0
    assert np.asarray(a_p)[2] == arb0[2]              # stamp untouched


# ------------------------------------------------- fallback + env plumbing


def test_resolve_use_pallas_env(monkeypatch):
    pg._probe_cache.clear()
    monkeypatch.delenv("DINT_USE_PALLAS", raising=False)
    assert pg.resolve_use_pallas(None) is False       # default off
    monkeypatch.setenv("DINT_USE_PALLAS", "0")
    assert pg.resolve_use_pallas(None) is False
    monkeypatch.setenv("DINT_USE_PALLAS", "1")
    assert pg.resolve_use_pallas(None) is True        # CPU interpret: works
    assert pg.resolve_use_pallas(False) is False      # explicit kwarg wins


def test_broken_kernel_raises_not_degrades(monkeypatch):
    """The refusal contract: if a kernel that was asked for fails to
    compile/run, resolve_use_pallas raises KernelRefused naming the
    kernel and carrying the compiler's text — from the resolver and from
    a builder given the flag; nobody gets the XLA route in silence. The
    refusal is not cached: the next ask raises again, and once the kernel
    works the same geometry probes clean."""
    pg._probe_cache.clear()
    real_gather = pg.gather_rows

    def boom(*a, **k):
        raise RuntimeError("Mosaic lowering failed (simulated)")

    monkeypatch.setattr(pg, "gather_rows", boom)
    for _ in range(2):                                 # never cached
        with pytest.raises(pg.KernelRefused,
                           match=r"'gather'.*Mosaic lowering failed"):
            pg.resolve_use_pallas(True, n_idx=64, m_lock=None)
    assert not pg._probe_cache
    # a builder given the env raises too (bypass the builder memo: a
    # healthy cached build would dodge the broken kernel)
    monkeypatch.setenv("DINT_USE_PALLAS", "1")
    td.build_pipelined_runner.cache.clear()
    with pytest.raises(pg.KernelRefused, match="'gather'"):
        td.build_pipelined_runner(20, w=16, val_words=4,
                                  cohorts_per_block=2)
    monkeypatch.setattr(pg, "gather_rows", real_gather)
    assert pg.resolve_use_pallas(True, n_idx=64, m_lock=None) is True
    pg._probe_cache.clear()
    td.build_pipelined_runner.cache.clear()


# --------------------------------------------- end-to-end engine parity


def _run_tatp(use_pallas, blocks=3, seed=0):
    db = td.populate(np.random.default_rng(seed), 200, val_words=4)
    run, init, drain = td.build_pipelined_runner(
        200, w=64, val_words=4, cohorts_per_block=2, use_pallas=use_pallas)
    carry = init(db)
    key = jax.random.PRNGKey(seed)
    tot = np.zeros(td.N_STATS, np.int64)
    for i in range(blocks):
        carry, s = run(carry, jax.random.fold_in(key, i))
        tot += np.asarray(s, np.int64).sum(axis=0)
    db, tail = drain(carry)
    tot += np.asarray(tail, np.int64).sum(axis=0)
    return db, tot


def _trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def test_tatp_dense_pallas_bit_identical(monkeypatch):
    """The full dense TATP pipeline — fused meta gather, magic gather,
    fused lock pass — under DINT_USE_PALLAS=1 (env route, the exact
    production spelling) produces the XLA path's stats, tables, arb
    stamps, AND log rings bit for bit."""
    db_x, tot_x = _run_tatp(False)
    monkeypatch.setenv("DINT_USE_PALLAS", "1")
    db_p, tot_p = _run_tatp(None)     # None -> env, end-to-end plumbing
    assert tot_x.tolist() == tot_p.tolist()
    assert int(tot_x[td.STAT_COMMITTED]) > 0          # not trivially empty
    assert int(tot_x[td.STAT_AB_LOCK]) >= 0
    assert _trees_equal(db_x, db_p)                   # incl. log x3 rings


def test_tatp_dense_pallas_contention_bit_identical():
    """US/IC-heavy mix over a tiny keyspace: lock conflicts and validate
    aborts fire (the adversarial case for the fused lock pass — in-batch
    duplicates and held rows every step), still bit-identical."""
    mix = np.array([0, 0, 0, 50, 0, 50, 0], np.float64) / 100.0

    def run(up):
        db = td.populate(np.random.default_rng(1), 16, val_words=4)
        run_f, init, drain = td.build_pipelined_runner(
            16, w=128, val_words=4, cohorts_per_block=2, mix=mix,
            use_pallas=up)
        carry = init(db)
        tot = np.zeros(td.N_STATS, np.int64)
        for i in range(3):
            carry, s = run_f(carry, jax.random.fold_in(jax.random.PRNGKey(9), i))
            tot += np.asarray(s, np.int64).sum(axis=0)
        db, tail = drain(carry)
        return db, tot + np.asarray(tail, np.int64).sum(axis=0)

    db_x, tot_x = run(False)
    db_p, tot_p = run(True)
    assert int(tot_x[td.STAT_AB_LOCK]) > 0            # conflicts really fired
    assert int(tot_x[td.STAT_AB_VALIDATE]) > 0
    assert tot_x.tolist() == tot_p.tolist()
    assert _trees_equal(db_x, db_p)


@pytest.mark.slow  # ~46s; the round-10 budget rule — kernel mechanics and
def test_dense_sharded_pallas_bit_identical():  # both dense parities stay tier-1
    """The tentpole's multi-chip integration: the 8-virtual-device sharded
    TATP runner (shard_map bodies run the kernels on their LOCAL shard
    arrays) is bit-identical XLA-vs-pallas — stats, tables, backups, logs."""
    from dint_tpu.parallel import dense_sharded as ds

    def run(up):
        mesh = ds.make_mesh(8)
        state = ds.create_sharded(mesh, 8, 800, val_words=4, seed=0)
        run_f, init, drain = ds.build_sharded_pipelined_runner(
            mesh, 8, 800, w=32, val_words=4, cohorts_per_block=2,
            use_pallas=up)
        carry = init(state)
        tot = np.zeros(td.N_STATS, np.int64)
        for i in range(2):
            carry, s = run_f(carry, jax.random.fold_in(jax.random.PRNGKey(0), i))
            tot += np.asarray(s, np.int64).sum(axis=0)
        state, tail = drain(carry)
        return state, tot + np.asarray(tail, np.int64).sum(axis=0)

    s_x, t_x = run(False)
    s_p, t_p = run(True)
    assert t_x.tolist() == t_p.tolist()
    assert int(t_x[td.STAT_COMMITTED]) > 0
    assert _trees_equal(s_x, s_p)


def test_dense_sharded_sb_pallas_bit_identical():
    """Sharded SmallBank with TRUE cross-device txns: the owner-side
    held-stamp + balance gathers run through the kernel per device,
    bit-identical stats and global state XLA-vs-pallas."""
    from dint_tpu.parallel import dense_sharded_sb as dsb

    def run(up):
        mesh = dsb.make_mesh(8)
        state = dsb.create_sharded_sb(mesh, 8, 400)
        run_f, init, drain = dsb.build_sharded_sb_runner(
            mesh, 8, 400, w=32, cohorts_per_block=2, use_pallas=up)
        carry = init(state)
        tot = np.zeros(dsb.N_STATS, np.int64)
        for i in range(2):
            carry, s = run_f(carry, jax.random.fold_in(jax.random.PRNGKey(2), i))
            tot += np.asarray(s, np.int64).sum(axis=0)
        state, tail = drain(carry)
        return state, tot + np.asarray(tail, np.int64).sum(axis=0)

    s_x, t_x = run(False)
    s_p, t_p = run(True)
    assert t_x.tolist() == t_p.tolist()
    assert _trees_equal(s_x, s_p)


@pytest.mark.slow  # ~19s; both dense pallas bit-identity pins stay tier-1
def test_tatp_dense_pallas_matches_generic_engine_oracle(monkeypatch):
    """ISSUE 1 acceptance: the EXISTING TATP dense parity test — dense
    engine vs the generic sort-based pipelined engine, the differential
    oracle of tests/test_tatp_dense.py (dint_tpu/testing/oracle.py's
    cross-backend role) — re-run end-to-end with DINT_USE_PALLAS=1. Only
    the dense side routes through the kernels; the generic engine is the
    untouched reference, so this catches any divergence the pallas-vs-XLA
    self-comparison above could share."""
    monkeypatch.setenv("DINT_USE_PALLAS", "1")
    from test_tatp_dense import (
        test_matches_generic_pipelined_engine_at_low_contention as parity)
    parity()


def test_smallbank_dense_pallas_bit_identical(monkeypatch):
    """SmallBank dense: held-stamp + balance gathers through the kernel,
    bit-identical stats/balances/logs, and balance conservation holds."""
    def run(up):
        db = sd.create(300)
        run_f, init, drain = sd.build_pipelined_runner(
            300, w=64, cohorts_per_block=2, use_pallas=up)
        carry = init(db)
        tot = np.zeros(sd.N_STATS, np.int64)
        for i in range(3):
            carry, s = run_f(carry, jax.random.fold_in(jax.random.PRNGKey(3), i))
            tot += np.asarray(s, np.int64).sum(axis=0)
        db, tail = drain(carry)
        return db, tot + np.asarray(tail, np.int64).sum(axis=0)

    db_x, tot_x = run(False)
    monkeypatch.setenv("DINT_USE_PALLAS", "1")
    db_p, tot_p = run(None)                           # env route
    assert tot_x.tolist() == tot_p.tolist()
    assert int(tot_x[sd.STAT_COMMITTED]) > 0
    assert _trees_equal(db_x, db_p)
    # the window-wide conservation oracle on the pallas path
    start = 2 * 300 * 1000
    assert int(np.asarray(sd.total_balance(db_p))) \
        == start + int(tot_p[sd.STAT_BAL_DELTA])
