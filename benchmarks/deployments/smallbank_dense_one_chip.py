"""SmallBank on one chip: engines/smallbank_dense.py, both balance tables
in one flat array, two lock-stamp tables over a hashed slot space and
three packed log rings in one device's HBM.

Nothing table-sized leaves the device: the rings are fetched (ring-sized)
and the rows they name, or the rows the reference touched, are gathered
on the device. The table has no version word, so a live balance is held
to the newest entry of its row wherever no wrap of the ring can hide a
later write. Against independent code (benchmarks/references/smallbank.py,
which shares nothing with the engine but the traffic: ``gen_cohort`` and
the amount draw): ``compare_small`` at a small size in the traced run,
and in every run the warm-up dispatches of the timed program itself, at
the deployment's scale, regenerated from their keys. The traffic's shape
(mix, hot set) is the configuration's ``traffic_shape``, handed to the
program, and the regenerated warm-up is held to it."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import checks as ck
from benchmarks.references import smallbank as ref
from dint_tpu import monitor, recovery
from dint_tpu.engines import smallbank_dense as sd
from dint_tpu.tables import log as logring

STAT_NAMES = ref.STAT_NAMES
assert [sd.STAT_ATTEMPTED, sd.STAT_COMMITTED, sd.STAT_AB_LOCK,
        sd.STAT_AB_LOGIC, sd.STAT_MAGIC_BAD,
        sd.STAT_BAL_DELTA] == list(range(sd.N_STATS))
OUTCOMES = ("committed", "ab_lock", "ab_logic")
FAULTS = ("magic_bad",)
CONTENTION = ("ab_lock",)       # another transaction held the lock
COUNTER_PAIRS = (("txn_attempted", "attempted"),
                 ("txn_committed", "committed"), ("ab_lock", "ab_lock"),
                 ("ab_logic", "ab_logic"), ("magic_bad", "magic_bad"))
GUARANTEE_CHECKS = ("lock_ledger_closes", "balance_conserved",
                    "log_replicas_identical")
COMPARE_CHECKS = ("compare.stats_equal_reference",
                  "compare.balances_equal_reference",
                  "compare.log_entries_equal_reference",
                  *(f"compare.recovered_from_replica_{r}" for r in range(3)))
ENGINE = dict(use_pallas=False, use_fused=False, use_hotset=False,
              trace=False)


def shape_args(config: dict) -> dict:
    """The configuration's ``traffic_shape`` as the program takes it
    (hashable: the builder memoizes on its arguments)."""
    shape = config["traffic_shape"]
    return dict(mix=tuple(shape["mix"]), hot_frac=shape["hot_frac"],
                hot_prob=shape["hot_prob"])


def cohort_program(n_accounts: int, w: int, cohorts_per_block: int,
                   shape: dict):
    """block key -> (ttype, a1, a2, ts_amt), each [cohorts_per_block, w]:
    the cohorts a block of the runner generates from that key, again. A
    block splits its key into one per step; a step splits off the
    generator's key and the TRANSACT_SAVING amounts' (pipe_step)."""
    def one(step_key):
        kgen, kamt = jax.random.split(step_key)
        amounts = jax.random.randint(kamt, (w,), -sd.TS_AMT_MAX,
                                     sd.TS_AMT_MAX + 1, dtype=jnp.int32)
        return (*sd.gen_cohort(kgen, w, n_accounts, **shape), amounts)

    return jax.jit(lambda key: jax.vmap(one)(
        jax.random.split(key, cohorts_per_block)))


class TrafficTally:
    """What the generator drew, against the shape the configuration
    states: transactions by type, and account draws inside the hot set
    (a draw lands there with ``hot_prob``, or uniformly). Each count
    within six standard deviations of its binomial mean: a fresh seed
    leaves that band once in 10^8, a mix off by half a point of 262,144
    transactions does not stay inside."""
    SIGMAS = 6.0

    def __init__(self, n_accounts: int, shape: dict):
        mix = np.asarray(shape["mix"], np.float64)
        self.p_type = mix / mix.sum()
        self.hot_n = max(int(n_accounts * shape["hot_frac"]), 1)
        self.p_hot = shape["hot_prob"] \
            + (1.0 - shape["hot_prob"]) * self.hot_n / n_accounts
        self.types = np.zeros(len(mix), np.int64)
        self.hot = 0
        self.txns = 0

    def add(self, ttype: np.ndarray, a1: np.ndarray, a2: np.ndarray):
        self.types += np.bincount(ttype.ravel(), minlength=len(self.types))
        self.hot += int((a1 < self.hot_n).sum() + (a2 < self.hot_n).sum())
        self.txns += ttype.size

    def within(self, count, n: int, p) -> bool:
        band = self.SIGMAS * np.sqrt(n * p * (1.0 - p)) + 1.0
        return bool(np.all(np.abs(count - n * p) <= band))

    def result(self) -> dict:
        n = self.txns
        return {"ok": n > 0 and self.within(self.types, n, self.p_type)
                and self.within(self.hot, 2 * n, self.p_hot),
                "txns": n, "types": self.types,
                "types_stated": self.p_type * n, "hot_draws": self.hot,
                "hot_draws_stated": self.p_hot * 2 * n}


def reference_run(oracle: ref.SmallBankOracle, cohorts, keys,
                  tally: TrafficTally | None = None) -> np.ndarray:
    """The stats rows a pipeline started empty, fed the blocks of ``keys``
    and drained hands out: the bootstrap cohort's zeros, then one row a
    cohort, each a step after its dispatch, the last from the drain."""
    rows = [np.zeros(len(STAT_NAMES), np.int64)]
    for key in keys:
        block = [np.asarray(x) for x in cohorts(key)]
        if tally is not None:
            tally.add(*block[:3])
        rows += [oracle.step(*(x[j] for x in block))
                 for j in range(block[0].shape[0])]
    oracle.drain()
    return np.stack(rows)


def first_difference(got: np.ndarray, want: np.ndarray) -> dict:
    if got.shape != want.shape:
        return {"shapes": [list(got.shape), list(want.shape)]}
    bad = np.argwhere(got != want)
    if not len(bad):
        return {}
    row = int(bad[0][0])
    return {"row": row, "got": got[row], "want": want[row],
            "rows_differing": int(len(set(bad[:, 0].tolist())))}


def ring_entries(ring: np.ndarray, heads: np.ndarray) -> list:
    """(table, account, step, balance, magic) of every entry an unwrapped
    ring holds, sorted."""
    e, _, _ = ck.surviving_entries(ring, heads)
    return sorted(zip((e[:, 0] >> 8).tolist(), e[:, 2].tolist(),
                      e[:, 3].tolist(), e[:, 4].tolist(), e[:, 5].tolist()))


def compare_small(config: dict, seed: int, checks: ck.Checks) -> None:
    """The engine's per-cohort stats, final balances, log entries and the
    recovery of its tables from each ring, against the sequential
    reference on the same cohorts, at the configuration's
    ``compare_small`` size. Exact: SmallBank's outcomes do not depend on
    an order the contract leaves open."""
    size = config["compare_small"]
    n, w, cpb = size["n_accounts"], size["w"], size["cohorts_per_block"]
    init_balance = config["sizes"]["init_balance"]
    db0 = sd.create(n, init_balance, log_capacity=1 << 12)
    fresh = jax.tree.map(np.array, db0)          # the runner donates db0
    shape = shape_args(config)
    run, init, drain = sd.build_pipelined_runner(
        n, w=w, cohorts_per_block=cpb, **shape, **ENGINE)
    key = jax.random.PRNGKey(seed)
    keys = [jax.random.fold_in(key, i) for i in range(size["blocks"])]
    carry, got = init(db0), []
    for k in keys:
        carry, stats = run(carry, k)
        got.append(np.asarray(stats, np.int64))
    db, tail = drain(carry)
    got = np.concatenate([*got, np.asarray(tail, np.int64)])

    oracle = ref.SmallBankOracle(n, init_balance)
    want = reference_run(oracle, cohort_program(n, w, cpb, shape), keys)
    checks.add("compare.stats_equal_reference", np.array_equal(got, want),
               cohorts=len(want) - 1, totals=got.sum(axis=0),
               **first_difference(got, want))
    live = np.asarray(db.bal)
    rows, balances = oracle.touched()
    expect = np.array(fresh.bal)
    expect[rows] = balances
    checks.add("compare.balances_equal_reference",
               np.array_equal(live, expect) and len(rows) > 0
               and int(np.asarray(sd.total_balance(db)))
               == oracle.total_balance(),
               rows_written=len(rows),
               rows_differing=int((live != expect).sum()))
    heads = np.asarray(db.log.head)
    rings = [np.asarray(logring.replica_entries(db.log, r))
             for r in range(3)]
    checks.add("compare.log_entries_equal_reference",
               ring_entries(rings[0], heads) == sorted(oracle.log),
               entries=len(oracle.log))
    for r, ring in enumerate(rings):
        rec = recovery.recover_smallbank_dense(fresh, ring, heads)
        checks.add(f"compare.recovered_from_replica_{r}",
                   np.array_equal(np.asarray(rec.bal), live))


class OneChip:
    stat_names = STAT_NAMES
    outcomes = OUTCOMES
    faults = FAULTS
    contention = CONTENTION
    depth = 2
    n_devices = 1

    def __init__(self, sizes: dict, params: dict, config: dict, emit):
        self.n = sizes["n_accounts"]
        self.init_balance = sizes["init_balance"]
        w, cpb = params["w"], params["cohorts_per_block"]
        self.txns_per_dispatch = w * cpb
        self.steps_per_dispatch = cpb

        t0 = time.perf_counter()
        db = sd.create(self.n, self.init_balance,
                       log_lanes=sizes["log_lanes"],
                       log_capacity=sizes["log_capacity"])
        jax.block_until_ready(db)
        self.ring_rows = db.log.lanes * db.log.capacity
        self.geometry = {
            "w": w, "l": sd.L, "val_words": sd.VW, "log_replicas": 3,
            "lock_slots": db.lock_slots,
            "table_bytes": int(db.bal.nbytes),
            "stamp_bytes": int(db.x_step.nbytes + db.s_step.nbytes),
            "ring_bytes": int(db.log.entries.nbytes)}
        emit(phase="populate", n_accounts=self.n, cohorts_per_block=cpb,
             seconds=time.perf_counter() - t0, **self.geometry)

        shape = shape_args(config)
        self.traffic_shape = config["traffic_shape"]
        self._run, self._init, self._drain = sd.build_pipelined_runner(
            self.n, w=w, cohorts_per_block=cpb, monitor=True, **shape,
            **ENGINE)
        self._cohorts = cohort_program(self.n, w, cpb, shape)
        self._db = db
        self._heads_seen = 0    # log entries counted by earlier phases
        self._total = jax.jit(sd.total_balance)
        self._balance = int(self._total(db))
        # the warm-up's dispatches, for the reference: (key, stats on the
        # device); None once the warm-up is verified
        self._warm: list | None = []
        sent = 2 * self.n           # the never-written last row

        @jax.jit
        def gather(db, rows):
            return db.bal[jnp.where(rows < 0, sent, rows)]

        self._gather = gather

    def start(self):
        db, self._db = self._db, None
        return self._init(db)

    def restart(self, final):
        return self._init(final[0])

    def dispatch(self, carry, key):
        carry, stats = self._run(carry, key)
        if self._warm is not None:
            self._warm.append((key, stats))
        return carry, stats

    def drain(self, carry):
        out = self._drain(carry)
        return out, np.asarray(out[1], np.int64)

    def balances(self, db, rows: np.ndarray) -> np.ndarray:
        """``db.bal[rows]``, gathered on the device in ring-sized pieces
        (one compiled shape) and fetched."""
        out = []
        for i in range(0, len(rows), self.ring_rows):
            piece = np.full(self.ring_rows, -1, np.int32)
            n = len(rows[i:i + self.ring_rows])
            piece[:n] = rows[i:i + self.ring_rows]
            out.append(np.asarray(self._gather(db, piece))[:n])
        return np.concatenate(out) if out else np.zeros(0, np.uint32)

    def read_back(self, db, ring: np.ndarray, heads: np.ndarray) -> dict:
        """One ring against the live table: every surviving entry whole
        (its magic word), every row's newest entry against the live
        balance where no wrap can hide a later write."""
        plan = ck.plan_readback(ring, heads, (self.n, self.n), sd.VW)
        entries, _, _ = ck.surviving_entries(ring, heads)
        held = plan["fresh"] | (not plan["wrapped"])
        live = self.balances(db, plan["rows"])
        differs = (live != plan["val"][:, 0]) & held
        bad_magic = int((entries[:, ck.HDR_WORDS + 1] != sd.MAGIC).sum())
        return {"ok": plan["in_range"] and bool(held.any())
                and not differs.any() and bad_magic == 0,
                "keys": len(held), "entries": plan["n_entries"],
                "held": int(held.sum()), "differs": int(differs.sum()),
                "bad_magic": bad_magic, "wrapped": plan["wrapped"],
                "in_range": plan["in_range"]}

    def check_warmup(self, db, tail: np.ndarray, checks: ck.Checks) -> None:
        """The timed program's own warm-up, at the deployment's scale,
        against the reference run over the same cohorts from the
        populated state: every cohort's stats, and the balance of every
        row the reference wrote; and those cohorts against the shape the
        configuration states."""
        t0 = time.perf_counter()
        warm, self._warm = self._warm, None
        got = np.concatenate([np.asarray(s, np.int64) for _, s in warm]
                             + [np.asarray(tail, np.int64)])
        oracle = ref.SmallBankOracle(self.n, self.init_balance)
        tally = TrafficTally(self.n, self.traffic_shape)
        want = reference_run(oracle, self._cohorts, [k for k, _ in warm],
                             tally)
        checks.add("warmup.traffic_as_configured", **tally.result())
        checks.add("warmup.stats_equal_reference",
                   np.array_equal(got, want) and len(warm) > 0,
                   cohorts=len(want) - 1, lock_slots=oracle.n_slots,
                   hashed=oracle.hashed, totals=got.sum(axis=0),
                   **first_difference(got, want))
        rows, balances = oracle.touched()
        live = self.balances(db, rows)
        checks.add("warmup.touched_rows_equal_reference",
                   np.array_equal(live, balances) and len(rows) > 0,
                   rows=len(rows), differs=int((live != balances).sum()),
                   reference_s=time.perf_counter() - t0)

    def verify(self, final, checks: ck.Checks, tag: str, totals: dict,
               dispatched: int) -> dict:
        db, tail, counters = final
        snap = monitor.snapshot(counters)
        ck.check_accounting(checks, tag, totals, snap, dispatched, OUTCOMES,
                            FAULTS, COUNTER_PAIRS)
        ck.check_lock_ledger(checks, tag, snap)
        balance = int(self._total(db))
        moved, self._balance = balance - self._balance, balance
        checks.add(f"{tag}.balance_conserved",
                   moved % (1 << 32) == totals["bal_delta"] % (1 << 32),
                   moved=moved, bal_delta=totals["bal_delta"])
        if self._warm is not None:
            self.check_warmup(db, tail, checks)

        # the three replicas, packed side by side in each slot
        heads = np.asarray(db.log.head)
        packed = np.asarray(db.log.entries)
        ew = packed.shape[1] // 3
        rings = [packed[:, r * ew:(r + 1) * ew].reshape(
            db.log.lanes, db.log.capacity, ew) for r in range(3)]
        checks.add(f"{tag}.log_replicas_identical",
                   all(np.array_equal(rings[0], r) for r in rings[1:]))
        # the rings live as long as the tables; the counters only as long
        # as this pipeline (restart makes new ones)
        appended = int(heads.astype(np.int64).sum())
        entries, self._heads_seen = appended - self._heads_seen, appended
        checks.add(f"{tag}.log_entries_equal_monitor_installs",
                   entries == snap["install_writes"] == snap["log_appends"]
                   and entries > 0, log_entries=entries,
                   install_writes=snap["install_writes"],
                   log_appends=snap["log_appends"])
        first = self.read_back(db, rings[0], heads)
        for r, ring in enumerate(rings):
            # a ring bit-identical to replica 0 reads back as it did
            res = first if np.array_equal(ring, rings[0]) \
                else self.read_back(db, ring, heads)
            checks.add(f"{tag}.acked_writes_read_back_from_replica_{r}",
                       **res)
        return snap


def build(config: dict, params: dict, seed: int, devices, emit,
          rehearse: bool) -> OneChip:
    sizes = config["rehearse"] if rehearse else config["sizes"]
    return OneChip(sizes, params, config, emit)
