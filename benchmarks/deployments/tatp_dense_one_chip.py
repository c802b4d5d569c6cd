"""TATP on one chip: engines/tatp_dense.py, all five tables, the lock
words and three packed log rings in one device's HBM, populated on the
device from the seed."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import checks as ck
from dint_tpu import monitor
from dint_tpu.engines import tatp_dense as td

STAT_NAMES = ("attempted", "committed", "ab_lock", "ab_missing",
              "ab_validate", "magic_bad")
assert [td.STAT_ATTEMPTED, td.STAT_COMMITTED, td.STAT_AB_LOCK,
        td.STAT_AB_MISSING, td.STAT_AB_VALIDATE,
        td.STAT_MAGIC_BAD] == list(range(td.N_STATS))
OUTCOMES = ("committed", "ab_lock", "ab_missing", "ab_validate")
FAULTS = ("magic_bad",)
CONTENTION = ("ab_lock", "ab_validate")     # another transaction's doing
COUNTER_PAIRS = (("txn_attempted", "attempted"),
                 ("txn_committed", "committed"), ("ab_lock", "ab_lock"),
                 ("ab_missing", "ab_missing"),
                 ("ab_validate", "ab_validate"), ("magic_bad", "magic_bad"))

# names verify makes in every phase beyond the harness's own, and names
# compare_small makes (tests/bench holds every cell to them)
GUARANTEE_CHECKS = ("lock_ledger_closes", "no_row_left_locked",
                    "ab_missing_in_analytic_band")
COMPARE_CHECKS = ("compare.dense_stats_equal_generic_engine",
                  "compare.recovered_from_replica_2")


def check_stats(checks: ck.Checks, tag: str, totals: dict, snap: dict,
                dispatched: int) -> None:
    """The accounting any deployment makes, then TATP's own."""
    ck.check_accounting(checks, tag, totals, snap, dispatched, OUTCOMES,
                        FAULTS, COUNTER_PAIRS)
    ck.check_lock_ledger(checks, tag, snap)
    ck.check_ab_missing_band(checks, tag, totals)


def compare_small(config: dict, seed: int, checks: ck.Checks) -> None:
    """Dense against generic engine and recovery from each ring, at the
    configuration's ``compare_small`` size (checks.compare_small)."""
    ck.compare_small(checks, seed, config["compare_small"],
                     config["sizes"]["val_words"])


class OneChip:
    stat_names = STAT_NAMES
    outcomes = OUTCOMES
    faults = FAULTS
    contention = CONTENTION
    depth = 3
    n_devices = 1

    def __init__(self, sizes: dict, params: dict, seed: int, emit):
        self.n_sub = sizes["n_sub"]
        self.vw = sizes["val_words"]
        w, cpb = params["w"], params["cohorts_per_block"]
        self.txns_per_dispatch = w * cpb
        self.steps_per_dispatch = cpb

        t0 = time.perf_counter()
        db = td.populate_device(
            jax.random.fold_in(jax.random.PRNGKey(seed), 1 << 20),
            self.n_sub, val_words=self.vw, log_lanes=sizes["log_lanes"],
            log_capacity=sizes["log_capacity"])
        jax.block_until_ready(db)
        self.ring_rows = db.log.lanes * db.log.capacity
        self.geometry = {
            "w": w, "k": td.K, "val_words": self.vw, "log_replicas": 3,
            "n_backups": 0,
            "table_bytes": int(db.val.nbytes + db.meta.nbytes
                               + db.arb.nbytes),
            "ring_bytes": int(db.log.entries.nbytes)}
        emit(phase="populate", n_sub=self.n_sub, cohorts_per_block=cpb,
             seconds=time.perf_counter() - t0, **self.geometry)

        self._run, self._init, self._drain = td.build_pipelined_runner(
            self.n_sub, w=w, val_words=self.vw, cohorts_per_block=cpb,
            monitor=True, use_pallas=False, use_fused=False, trace=False)
        self._db = db
        self._heads_seen = 0    # log entries counted by earlier phases
        sent = td.n_rows(self.n_sub)        # the never-written last row
        vw = self.vw

        @jax.jit
        def gather(db, rows):
            rows = jnp.where(rows < 0, sent, rows)
            flat = rows[:, None] * vw + jnp.arange(vw, dtype=jnp.int32)
            return db.meta[rows], db.val[flat]

        self._gather = gather
        self._any_locked = jax.jit(lambda db: jnp.any(db.locked))

    def start(self):
        db, self._db = self._db, None
        return self._init(db)

    def restart(self, final):
        return self._init(final[0])

    def dispatch(self, carry, key):
        return self._run(carry, key)

    def drain(self, carry):
        out = self._drain(carry)
        return out, np.asarray(out[1], np.int64)

    def verify(self, final, checks: ck.Checks, tag: str, totals: dict,
               dispatched: int) -> dict:
        db, _, counters = final
        snap = monitor.snapshot(counters)
        check_stats(checks, tag, totals, snap, dispatched)
        checks.add(f"{tag}.no_row_left_locked",
                   not bool(self._any_locked(db)))

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
        def read_back(ring):
            plan = ck.plan_readback(ring, heads,
                                    ck.tatp_table_rows(self.n_sub), self.vw)
            n = len(plan["rows"])
            rows = np.full(self.ring_rows, -1, np.int32)
            rows[:n] = plan["rows"]
            meta, val = self._gather(db, rows)
            return ck.compare_readback(plan, np.asarray(meta)[:n],
                                       np.asarray(val)[:n])

        first = read_back(rings[0])
        for r, ring in enumerate(rings):
            # a ring bit-identical to replica 0 reads back as it did
            res = first if np.array_equal(ring, rings[0]) \
                else read_back(ring)
            checks.add(f"{tag}.acked_writes_read_back_from_replica_{r}",
                       **res)
        return snap


def build(config: dict, params: dict, seed: int, devices, emit,
          rehearse: bool) -> OneChip:
    sizes = config["rehearse"] if rehearse else config["sizes"]
    return OneChip(sizes, params, seed, emit)
