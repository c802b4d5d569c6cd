"""Sort-free dense SmallBank pipeline: invariants, contention response,
and the engine held exactly to the sequential reference
(dint_tpu/testing/oracle.py ``SmallBankOracle``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dint_tpu import analysis
from dint_tpu.analysis import core, dataflow
from dint_tpu.engines import smallbank_dense as sd
from dint_tpu.ops import compact
from dint_tpu.tables import log as logring
from dint_tpu.testing import oracle


def _run_blocks(n_accounts, w, blocks, cohorts_per_block=2, seed=0, **kw):
    db = sd.create(n_accounts)
    base = int(np.asarray(sd.total_balance(db)))
    run, init, drain = sd.build_pipelined_runner(
        n_accounts, w=w, cohorts_per_block=cohorts_per_block, **kw)
    carry = init(db)
    key = jax.random.PRNGKey(seed)
    total = np.zeros(sd.N_STATS, np.int64)
    for i in range(blocks):
        carry, stats = run(carry, jax.random.fold_in(key, i))
        total += np.asarray(stats, np.int64).sum(axis=0)
    db, tail = drain(carry)
    total += np.asarray(tail, np.int64).sum(axis=0)
    return db, total, base


def test_invariants_small():
    db, total, base = _run_blocks(n_accounts=512, w=256, blocks=3)

    attempted = int(total[sd.STAT_ATTEMPTED])
    committed = int(total[sd.STAT_COMMITTED])
    assert attempted == 3 * 2 * 256
    assert 0 < committed <= attempted
    assert committed + total[sd.STAT_AB_LOCK] + total[sd.STAT_AB_LOGIC] \
        == attempted
    assert int(total[sd.STAT_MAGIC_BAD]) == 0

    # balance conservation: table delta == sum of committed deltas (mod 2^32)
    final = int(np.asarray(sd.total_balance(db)))
    want = int(total[sd.STAT_BAL_DELTA])
    assert (final - base) % (1 << 32) == want % (1 << 32)

    # all locks expired after drain: no slot stamped at the final step
    last = int(np.asarray(db.step)) - 1
    assert not (np.asarray(db.x_step) == last).any()
    assert not (np.asarray(db.s_step) == last).any()

    # log x3: identical replica slots, nonzero depth
    r0 = np.asarray(logring.replica_entries(db.log, 0))
    assert np.array_equal(r0, np.asarray(logring.replica_entries(db.log, 1)))
    assert np.array_equal(r0, np.asarray(logring.replica_entries(db.log, 2)))
    assert np.asarray(db.log.head).sum() > 0

    # sentinel row untouched
    assert int(np.asarray(db.bal)[-1]) == 0


def test_abort_rate_responds_to_contention():
    _, hot, _ = _run_blocks(n_accounts=64, w=512, blocks=2, seed=1)
    _, cold, _ = _run_blocks(n_accounts=1 << 16, w=64, blocks=2, seed=1)
    hot_rate = hot[sd.STAT_AB_LOCK] / hot[sd.STAT_ATTEMPTED]
    cold_rate = cold[sd.STAT_AB_LOCK] / cold[sd.STAT_ATTEMPTED]
    assert hot_rate > 0.2, hot_rate
    assert cold_rate < 0.05, cold_rate


def test_cross_cohort_lock_conflicts_exist():
    """Locks held across the step boundary: at w=1 there is NO intra-cohort
    arbitration, so every lock abort here is a cross-cohort conflict with
    the previous cohort's still-held locks (the generic per-cohort engine
    cannot express this; a release-before-acquire bug would make this 0)."""
    _, total, _ = _run_blocks(n_accounts=2, w=1, blocks=4,
                              cohorts_per_block=16, seed=2,
                              hot_frac=1.0, hot_prob=1.0)
    assert int(total[sd.STAT_AB_LOCK]) > 0


def test_shared_locks_do_not_conflict():
    """A Balance-only world (all S locks) must never lock-abort, even with
    every txn on the same tiny hot set."""
    mix = np.array([0, 100, 0, 0, 0, 0], np.float64) / 100.0
    _, total, _ = _run_blocks(n_accounts=8, w=128, blocks=3, seed=3,
                              hot_frac=1.0, hot_prob=1.0, mix=mix)
    assert int(total[sd.STAT_AB_LOCK]) == 0
    assert int(total[sd.STAT_COMMITTED]) == int(total[sd.STAT_ATTEMPTED])

def test_hashed_lock_slots_conserve_balance(monkeypatch):
    """The multiply-shift hashed lock table (engaged at reference scale,
    where 48M rows exceed the slot cap) may conflate rows into shared
    slots — that adds false no-wait rejects but must NEVER corrupt
    balances. Force hashing at test scale by shrinking the cap."""
    monkeypatch.setattr(sd, "MAX_LOCK_SLOTS", 256)
    n_acc = 4096                      # m1 = 8193 rows >> 256 slots
    db = sd.create(n_acc)
    assert db.lock_slots == 256       # hashing engaged
    base = int(np.asarray(sd.total_balance(db)))
    run, init, drain = sd.build_pipelined_runner(n_acc, w=256,
                                                 cohorts_per_block=2)
    carry = init(db)
    key = jax.random.PRNGKey(7)
    total = np.zeros(sd.N_STATS, np.int64)
    for i in range(3):
        carry, stats = run(carry, jax.random.fold_in(key, i))
        total += np.asarray(stats, np.int64).sum(axis=0)
    db, tail = drain(carry)
    total += np.asarray(tail, np.int64).sum(axis=0)

    attempted = int(total[sd.STAT_ATTEMPTED])
    committed = int(total[sd.STAT_COMMITTED])
    assert committed + int(total[sd.STAT_AB_LOCK]) \
        + int(total[sd.STAT_AB_LOGIC]) == attempted
    # heavy conflation (16 rows/slot avg on the hot set) must still commit
    # some txns and conserve every cent
    assert committed > 0
    final = int(np.asarray(sd.total_balance(db)))
    assert (final - base) % (1 << 32) == \
        int(total[sd.STAT_BAL_DELTA]) % (1 << 32)


# ------------------------------------------- against the sequential reference

ENGINE = dict(use_hotset=False, trace=False)
N_ACC, W, CPB = 2000, 128, 2


def _cohorts(key, blocks, n_acc=N_ACC, w=W, **skew):
    """The cohorts the runner generates from ``key``, again: a block splits
    its key into one per step, a step splits off the generator's key and
    the TRANSACT_SAVING amounts' (pipe_step). Traffic, shared with the
    engine; lock sets and balance logic are not."""
    out = []
    for i in range(blocks):
        for step_key in jax.random.split(jax.random.fold_in(key, i), CPB):
            kgen, kamt = jax.random.split(step_key)
            ttype, a1, a2 = sd.gen_cohort(kgen, w, n_acc, **skew)
            amt = jax.random.randint(kamt, (w,), -sd.TS_AMT_MAX,
                                     sd.TS_AMT_MAX + 1, dtype=jnp.int32)
            out.append([np.asarray(x) for x in (ttype, a1, a2, amt)])
    return out


def _engine_and_oracle(seed, phases, blocks, max_slots, n_acc=N_ACC, w=W,
                       **skew):
    """Runs ``phases`` x (``blocks`` blocks + a drain) through the engine
    and the same cohorts through the oracle: (stats rows of both, db,
    oracle). A cohort's stats leave the engine one step after its
    dispatch: the first row of a phase is the empty bootstrap cohort's,
    the drain's row the last cohort's."""
    sd.build_pipelined_runner.cache.clear()     # MAX_LOCK_SLOTS is no key
    db = sd.create(n_acc, log_capacity=1 << 12)
    run, init, drain = sd.build_pipelined_runner(
        n_acc, w=w, cohorts_per_block=CPB, **ENGINE, **skew)
    ref = oracle.SmallBankOracle(n_acc, max_lock_slots=max_slots)
    assert ref.n_slots == db.lock_slots
    got, want = [], []
    for phase in range(phases):
        key = jax.random.PRNGKey(seed + phase)
        carry = init(db)
        for i in range(blocks):
            carry, stats = run(carry, jax.random.fold_in(key, i))
            got.append(np.asarray(stats, np.int64))
        db, tail = drain(carry)
        got.append(np.asarray(tail, np.int64))
        want.append(np.zeros((1, sd.N_STATS), np.int64))
        want += [ref.step(*c)[None]
                 for c in _cohorts(key, blocks, n_acc, w, **skew)]
        ref.drain()
    sd.build_pipelined_runner.cache.clear()
    return np.concatenate(got), np.concatenate(want), db, ref


def _assert_state_equals_oracle(db, ref):
    rows, balances = ref.touched()
    want = np.full(db.bal.shape[0], 1000, np.uint32)
    want[-1] = 0
    want[rows] = balances
    np.testing.assert_array_equal(np.asarray(db.bal), want)
    assert int(np.asarray(sd.total_balance(db))) == ref.total_balance()
    heads = np.asarray(db.log.head)
    for r in range(3):
        ring = np.asarray(logring.replica_entries(db.log, r))
        # (table, account, step, balance, magic) of every entry written
        entries = sorted(
            (int(e[0] >> 8), int(e[2]), int(e[3]), int(e[4]), int(e[5]))
            for lane in range(ring.shape[0]) for e in ring[lane, :heads[lane]])
        assert entries == sorted(ref.log)
    assert int(np.asarray(db.step)) == ref.t


# seed, phases, blocks, slot cap, skew; what the oracle's own tally must
# have seen for the case to be the case; (accounts, w)
SMALL = (N_ACC, W)
FILLED = (200_000, 80)      # 400,001 words at 240 lanes: the install fills
ORACLE_CASES = {
    "exact_slots": (0, 1, 3, 1 << 25, {}, (), SMALL),
    # rows conflate in 2^10 slots, once within one transaction
    "hashed_slots": (23, 1, 3, 1 << 10, {},
                     ("x_rejected_own",), SMALL),
    "contended": (2, 1, 3, 1 << 25, {"hot_frac": 0.01},
                  ("s_shared", "x_rejected_prev_s", "x_rejected_prev_x",
                   "x_rejected_cohort", "s_rejected_prev_x",
                   "s_rejected_cohort"), SMALL),
    # two phases: a drain, then a fresh pipeline over the drained state
    "drain_and_restart": (3, 2, 2, 1 << 25, {}, (), SMALL),
    # the install issues 384 lanes for its 240 (compact.
    # sorted_scatter_lanes switches the fill on itself): blocks and drains
    "filled_install_seed5": (5, 2, 2, 1 << 25, {}, (), FILLED),
    "filled_install_seed6": (6, 2, 2, 1 << 25, {}, (), FILLED),
    "filled_install_contended": (7, 1, 3, 1 << 25, {"hot_frac": 0.001},
                                 ("s_shared", "x_rejected_cohort"), FILLED),
    "filled_install_hashed": (8, 1, 3, 1 << 14, {}, (), FILLED),
}


@pytest.mark.parametrize("case", ORACLE_CASES)
def test_dense_equals_the_sequential_oracle(case, monkeypatch):
    seed, phases, blocks, max_slots, skew, must_occur, (n_acc, w) = \
        ORACLE_CASES[case]
    assert (compact.sorted_scatter_lanes(2 * n_acc + 1, w * sd.L)
            > w * sd.L) == case.startswith("filled_install")
    monkeypatch.setattr(sd, "MAX_LOCK_SLOTS", max_slots)
    got, want, db, ref = _engine_and_oracle(seed, phases, blocks, max_slots,
                                            n_acc, w, **skew)
    assert ref.hashed == (max_slots < 2 * n_acc + 1)
    for cause in must_occur:
        assert ref.tally[cause] > 0, (cause, ref.tally)
    assert want[:, sd.STAT_COMMITTED].sum() > 0
    np.testing.assert_array_equal(got, want)
    _assert_state_equals_oracle(db, ref)
    if phases > 1:
        # the drain's step let the last locks expire: the cohort after it
        # met no lock of the cohort before it
        per_phase = 1 + blocks * CPB
        assert (got[::per_phase] == 0).all()


def test_a_doctored_engine_fails_the_oracle(monkeypatch):
    """One grant rule flipped: shared requests arbitrate as exclusive
    ones, so sharers reject each other. The comparison must notice."""
    real = sd._lock_slots

    def no_sharing(ttype, a1, a2):
        ops, tbl, acc = real(ttype, a1, a2)
        return (jnp.where(ops == sd.Op.ACQ_S_READ, sd.Op.ACQ_X_READ, ops),
                tbl, acc)

    monkeypatch.setattr(sd, "_lock_slots", no_sharing)
    got, want, _, ref = _engine_and_oracle(2, 1, 3, 1 << 25, hot_frac=0.01)
    assert ref.tally["s_shared"] > 0
    assert not np.array_equal(got, want)
    assert got[:, sd.STAT_AB_LOCK].sum() > want[:, sd.STAT_AB_LOCK].sum()


# ------------------------------- the install's lane count (ops/compact.py)

@pytest.mark.parametrize("table_words, lanes, want", [
    # dense already (most test geometries): the scatter's own lanes
    (40_001, 768, 768),
    (1 << 25, 24_576, 24_576),              # the stamp tables: sorted as is
    (1536 * 240, 240, 240),                 # the rule's edge
    # fill: the least multiple of 128 with at most 1,536 words a lane
    (1536 * 240 + 1, 240, 256),
    (400_001, 240, 384),
    (48_000_001, 24_576, 31_360),           # smallbank24m's balances
    (40_000_001, 16_384, 26_112),
    (1 << 26, 24_576, 43_776),
    (1536 * 128 * 3, 192, 384),             # just doubled: still filled
    # would more than double the lanes: not this case, no fill
    (1536 * 128 * 3 + 1, 192, 192),
    (70_000_001, 512, 512),                 # tatp7m's chunked installs
    (7_000_001, 512, 512),
])
def test_sorted_scatter_lanes(table_words, lanes, want):
    got = compact.sorted_scatter_lanes(table_words, lanes)
    assert got == want
    if got != lanes:
        assert got % 128 == 0 and lanes < got <= 2 * lanes
        assert table_words <= got * compact.SORTED_SCATTER_WORDS_PER_LANE \
            < table_words + 128 * compact.SORTED_SCATTER_WORDS_PER_LANE
    x = jnp.arange(lanes, dtype=jnp.int32)
    out = compact.filled(x, got, table_words)
    assert out.shape == (got,) and out.dtype == x.dtype
    assert (out is x) == (got == lanes)       # nothing to add: no equation
    np.testing.assert_array_equal(out[:lanes], x)
    assert (np.asarray(out[lanes:]) == table_words).all()


@pytest.mark.parametrize("geometry, issued", [
    pytest.param(SMALL, W * sd.L, id="as_is"),
    pytest.param(FILLED, 384, id="filled")])
def test_the_install_asks_for_no_sort(geometry, issued):
    """The sort is the compiler's (tests/test_chip_compile.py): the block
    holds none, filled or not, its one scatter into the balances issues
    the lanes the rule gives, and the protocol pass proves that install
    from the lock grants, through the fill and with no sort to lean on."""
    n_acc, w = geometry
    run, init, _ = sd.build_pipelined_runner(
        n_acc, w=w, cohorts_per_block=CPB, **ENGINE)
    carry = jax.eval_shape(
        lambda: init(sd.create(n_acc, log_capacity=1 << 12)))
    trace = core.trace_target("block", run, (carry, jax.random.PRNGKey(0)))
    eqns = [c.eqn for c in core.walk(trace)]
    assert "sort" not in {e.primitive.name for e in eqns}
    install, = (e for e in eqns if e.primitive.name == "scatter"
                and e.invars[0].aval.shape == (2 * n_acc + 1,))
    assert install.invars[1].aval.shape == (issued, 1)
    assert install.invars[2].aval.shape == (issued,)
    assert install.params["unique_indices"]
    assert not install.params["indices_are_sorted"]

    assert not analysis.has_errors(analysis.PASSES["protocol"](trace))
    rec, = (r for r in dataflow.analyze(trace).scatters
            if r.site == core.site_of(install))
    assert rec.is_state and rec.idx_rows == issued
    assert dataflow.LOCK_WIN in rec.write_facts
    assert dataflow.SORTED not in rec.write_facts
