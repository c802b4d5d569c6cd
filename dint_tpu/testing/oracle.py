"""Sequential (pure Python dict) oracles for differential testing.

Each oracle implements the *same serialization contract* that the batched
engine documents — so engine output must match the oracle exactly, batch for
batch. This supplies what the reference lacks entirely (it has no unit tests;
correctness there rests on magic-byte asserts and cross-backend equivalence,
see SURVEY.md §4); the oracle here plays the role of the reference's
"other backend" in cross-backend differential testing.
"""
from __future__ import annotations

import collections

import numpy as np

from ..engines.types import Op, Reply

VER0 = 0


class StoreOracle:
    """Sequential model of engines.store: per key, GETs see pre-batch state,
    then writes apply in lane order; SET/INSERT are upserts bumping a
    monotonic version; DELETE invalidates."""

    def __init__(self):
        self.data: dict[int, tuple[tuple, int]] = {}   # key -> (val tuple, ver)

    def scan(self, start_key: int, scan_len: int):
        """Range scan against pre-batch state: the first `scan_len` live
        keys >= start_key in key order, as [(key, val tuple, ver), ...].
        SCANs are reads — they sit in phase 1 with the GETs."""
        rows = []
        for k in sorted(self.data):
            if len(rows) >= scan_len:
                break
            if k >= int(start_key):
                rows.append((k, self.data[k][0], self.data[k][1]))
        return rows

    def step(self, ops, keys, vals, scan_lens=None, scan_max: int = 0):
        """One batch. `scan_lens` [r] carries Op.SCAN lanes' requested row
        counts (clipped to scan_max, the engine's static slab width).
        Returns (rtype, rval, rver) — plus `scans`, a per-lane list of
        scan row lists, when scan_max > 0."""
        r = len(ops)
        rtype = np.zeros(r, np.int32)
        rver = np.zeros(r, np.uint32)
        rval = np.zeros((r, np.asarray(vals).shape[1]), np.uint32)
        scans: list[list] = [[] for _ in range(r)]
        # phase 1: reads against pre-state
        for i in range(r):
            if ops[i] == Op.GET:
                ent = self.data.get(int(keys[i]))
                if ent is None:
                    rtype[i] = Reply.NOT_EXIST
                else:
                    rtype[i] = Reply.VAL
                    rval[i] = ent[0]
                    rver[i] = ent[1]
            elif ops[i] == Op.SCAN:
                want = int(scan_lens[i]) if scan_lens is not None else 0
                rows = self.scan(int(keys[i]), max(0, min(want, scan_max)))
                scans[i] = rows
                rtype[i] = Reply.VAL
                rver[i] = np.uint32(len(rows))
        # phase 2: writes in lane order
        # version base = pre-batch version, recorded at the key's first write
        # in the batch; versions stay monotonic across delete+reinsert within
        # a batch (ABA avoidance — stronger than the reference's kvs)
        base: dict[int, int] = {}
        cnt: dict[int, int] = {}

        def touch(k):
            if k not in base:
                base[k] = self.data[k][1] if k in self.data else VER0
                cnt[k] = 0

        for i in range(r):
            k = int(keys[i])
            if ops[i] in (Op.SET, Op.INSERT):
                touch(k)
                cnt[k] += 1
                ver = base[k] + cnt[k]
                self.data[k] = (tuple(int(x) for x in vals[i]), ver)
                rtype[i] = Reply.ACK
                rver[i] = ver
            elif ops[i] == Op.DELETE:
                touch(k)
                if k in self.data:
                    del self.data[k]
                    rtype[i] = Reply.ACK
                else:
                    rtype[i] = Reply.NOT_EXIST
        if scan_max > 0:
            return rtype, rval, rver, scans
        return rtype, rval, rver


class SXLockOracle:
    """Sequential model of engines.lock2pl: per slot, releases apply first,
    then acquires in lane order under no-wait 2PL."""

    def __init__(self, n_slots: int):
        self.num_sh = np.zeros(n_slots, np.int64)
        self.num_ex = np.zeros(n_slots, np.int64)

    def step(self, ops, slots):
        r = len(ops)
        rtype = np.zeros(r, np.int32)
        for i in range(r):  # releases first
            s = int(slots[i])
            if ops[i] == Op.REL_S:
                self.num_sh[s] = max(self.num_sh[s] - 1, 0)
                rtype[i] = Reply.ACK
            elif ops[i] == Op.REL_X:
                self.num_ex[s] = max(self.num_ex[s] - 1, 0)
                rtype[i] = Reply.ACK
        for i in range(r):  # acquires in lane order
            s = int(slots[i])
            if ops[i] == Op.ACQ_S:
                if self.num_ex[s] == 0:
                    self.num_sh[s] += 1
                    rtype[i] = Reply.GRANT
                else:
                    rtype[i] = Reply.REJECT
            elif ops[i] == Op.ACQ_X:
                if self.num_ex[s] == 0 and self.num_sh[s] == 0:
                    self.num_ex[s] += 1
                    rtype[i] = Reply.GRANT
                else:
                    rtype[i] = Reply.REJECT
        return rtype


class OCCOracle:
    """Sequential model of engines.fasst: per slot, unlocks (commit/abort)
    first, then reads, then lock acquires in lane order."""

    def __init__(self, n_slots: int):
        self.locked = np.zeros(n_slots, bool)
        self.ver = np.zeros(n_slots, np.uint32)

    def step(self, ops, slots):
        r = len(ops)
        rtype = np.zeros(r, np.int32)
        rver = np.zeros(r, np.uint32)
        rlocked = np.zeros(r, np.uint32)
        for i in range(r):  # commits/aborts first
            s = int(slots[i])
            if ops[i] == Op.COMMIT_VER:
                self.ver[s] += 1
                self.locked[s] = False
                rtype[i] = Reply.ACK
            elif ops[i] == Op.ABORT:
                self.locked[s] = False
                rtype[i] = Reply.ACK
        for i in range(r):  # reads see post-commit versions + lock bits
            if ops[i] == Op.READ_VER:
                s = int(slots[i])
                rtype[i] = Reply.VAL
                rver[i] = self.ver[s]
                rlocked[i] = np.uint32(self.locked[s])
        for i in range(r):  # lock acquires in lane order
            if ops[i] == Op.LOCK:
                s = int(slots[i])
                if not self.locked[s]:
                    self.locked[s] = True
                    rtype[i] = Reply.GRANT
                else:
                    rtype[i] = Reply.REJECT
        return rtype, rver, rlocked


# ---------------------------------------------------------------- SmallBank
# The same code as benchmarks/references/smallbank.py (the copy that
# decides the benchmark's ``correct``; tests/bench holds the two to equal
# answers), under SB_ names. The contract it holds, and where it departs
# from the source, is written there and in engines/smallbank_dense.py's
# docstring: transactions of a cohort in lane order, a transaction's lock
# requests in slot order, S/X no-wait per lock slot (slot = row while the
# table fits, else a multiply-shift hash), a granted lock held until the
# cohort's writes are installed one step later (also for a transaction
# that aborts: the source releases at once,
# smallbank/caladan/client_ebpf_shard.cc:330-370), the writes of cohort t
# installed before cohort t+1 reads. Lock sets and balance logic are the
# source's (client_ebpf_shard.cc TxnAmalgamate:255, TxnBalance:565,
# TxnDepositChecking:705, TxnSendPayment:830, TxnTransactSaving:1116,
# TxnWriteCheck:1241), not the engine's code.

SB_SAVINGS, SB_CHECKING = 0, 1
(SB_AMALGAMATE, SB_BALANCE, SB_DEPOSIT_CHECKING, SB_SEND_PAYMENT,
 SB_TRANSACT_SAVING, SB_WRITE_CHECK) = range(6)
SB_AMT = 5              # deposit, payment and check amount
SB_MAGIC = 0x5B5B       # the value's integrity word, whole only in the log
SB_MAX_LOCK_SLOTS = 1 << 25
SB_HASH_MULT = 0x9E3779B1
SB_STAT_NAMES = ("attempted", "committed", "ab_lock", "ab_logic",
                 "magic_bad", "bal_delta")
SB_FIRST_STEP = 2       # a stamp of 0 is "never held"

# each transaction's lock set, in request order: (exclusive?, table,
# which of the transaction's two accounts)
SB_LOCK_SETS = {
    SB_AMALGAMATE: ((True, SB_SAVINGS, 0), (True, SB_CHECKING, 0),
                    (True, SB_CHECKING, 1)),
    SB_BALANCE: ((False, SB_SAVINGS, 0), (False, SB_CHECKING, 0)),
    SB_DEPOSIT_CHECKING: ((True, SB_CHECKING, 0),),
    SB_SEND_PAYMENT: ((True, SB_CHECKING, 0), (True, SB_CHECKING, 1)),
    SB_TRANSACT_SAVING: ((True, SB_SAVINGS, 0),),
    SB_WRITE_CHECK: ((False, SB_SAVINGS, 0), (True, SB_CHECKING, 0)),
}


def _i32(v: int) -> int:
    """A balance is a signed 32-bit word: sums wrap."""
    return (v + (1 << 31)) % (1 << 32) - (1 << 31)


def sb_lock_slots_for(n_rows: int, cap: int = SB_MAX_LOCK_SLOTS) -> int:
    """The smallest power of two that holds every row, up to ``cap``."""
    return min(1 << (n_rows - 1).bit_length(), cap)


def sb_logic(ttype: int, bal: list, ts_amt: int):
    """A transaction's balance logic on the balances of its lock set, in
    lock-set order: (new balances | None for a logic abort). A lock-set
    row that the transaction only reads keeps None."""
    if ttype == SB_AMALGAMATE:      # all of a1's money to a2's checking
        return [0, 0, _i32(bal[2] + bal[0] + bal[1])]
    if ttype == SB_BALANCE:
        return [None, None]
    if ttype == SB_DEPOSIT_CHECKING:
        return [_i32(bal[0] + SB_AMT)]
    if ttype == SB_SEND_PAYMENT:
        if bal[0] < SB_AMT:
            return None
        return [_i32(bal[0] - SB_AMT), _i32(bal[1] + SB_AMT)]
    if ttype == SB_TRANSACT_SAVING:
        if _i32(bal[0] + ts_amt) < 0:
            return None
        return [_i32(bal[0] + ts_amt)]
    if ttype == SB_WRITE_CHECK:     # an overdraft costs one more
        penalty = 1 if _i32(bal[0] + bal[1]) < SB_AMT else 0
        return [None, _i32(bal[1] - SB_AMT - penalty)]
    raise ValueError(f"no SmallBank transaction type {ttype}")


class SmallBankOracle:
    """``step`` takes one cohort and returns its six stats. ``tally``
    counts what the lock table saw, by cause, for a test to assert that
    a case occurred."""

    def __init__(self, n_accounts: int, init_balance: int = 1000,
                 max_lock_slots: int = SB_MAX_LOCK_SLOTS):
        self.n = n_accounts
        self.init = init_balance
        self.n_slots = sb_lock_slots_for(2 * n_accounts + 1, max_lock_slots)
        self.hashed = self.n_slots < 2 * n_accounts + 1
        self.bal: dict[int, int] = {}       # row -> balance, once written
        self.x_stamp: dict[int, int] = {}   # slot -> last step X-granted
        self.s_stamp: dict[int, int] = {}   # slot -> last step S-granted
        self.t = SB_FIRST_STEP
        self.pending: list = []             # (row, balance) to install
        self.log: list = []     # (table, account, step, balance u32, magic)
        self.tally: collections.Counter = collections.Counter()

    def slots_of(self, rows: np.ndarray) -> np.ndarray:
        if not self.hashed:
            return rows
        shift = 32 - (self.n_slots.bit_length() - 1)
        return ((rows.astype(np.uint64) * SB_HASH_MULT) % (1 << 32)) >> shift

    def balance(self, row: int) -> int:
        return self.bal.get(row, self.init)

    def _install(self) -> None:
        """The last cohort's writes land, at this step."""
        for row, new in self.pending:
            self.bal[row] = new
            self.log.append((row // self.n, row % self.n, self.t,
                             new % (1 << 32), SB_MAGIC))
        self.pending = []

    def _acquire(self, exclusive: bool, slot: int, txn: int,
                 owner: dict) -> bool:
        t = self.t
        x_at, s_at = self.x_stamp.get(slot), self.s_stamp.get(slot)
        x_held = x_at in (t - 1, t)
        s_held = s_at in (t - 1, t)
        if exclusive and not x_held and not s_held:
            self.x_stamp[slot] = t
            owner[slot] = txn
            return True
        if not exclusive and not x_held:
            self.tally["s_shared"] += s_at == t
            self.s_stamp[slot] = t
            owner.setdefault(slot, txn)
            return True
        cause = ("prev_x" if x_at == t - 1 else "prev_s" if s_at == t - 1
                 else "own" if owner.get(slot) == txn else "cohort")
        self.tally[f"{'x' if exclusive else 's'}_rejected_{cause}"] += 1
        return False

    def step(self, ttype, a1, a2, ts_amt) -> np.ndarray:
        self._install()
        ttype, ts_amt = np.asarray(ttype).tolist(), np.asarray(
            ts_amt).tolist()
        accounts = np.stack([np.asarray(a1), np.asarray(a2)],
                            axis=1).astype(np.int64)
        # every lock request's row and slot, a cohort at a time (the
        # arithmetic is numpy's; the order of events is the loop's)
        rows_of, slots_of = {}, {}
        for tt, lock_set in SB_LOCK_SETS.items():
            rows = np.stack([tbl * self.n + accounts[:, which]
                             for _, tbl, which in lock_set], axis=1)
            rows_of[tt], slots_of[tt] = (rows.tolist(),
                                         self.slots_of(rows).tolist())
        committed = ab_lock = ab_logic = delta = 0
        owner: dict[int, int] = {}          # slot -> first holder, this step
        for i, tt in enumerate(ttype):
            rows = rows_of[tt][i]
            granted = [self._acquire(x, s, i, owner) for (x, _, _), s
                       in zip(SB_LOCK_SETS[tt], slots_of[tt][i])]
            if not all(granted):
                ab_lock += 1
                continue
            old = [self.balance(r) for r in rows]
            new = sb_logic(tt, old, ts_amt[i])
            if new is None:
                ab_logic += 1
                continue
            committed += 1
            for r, b, nb in zip(rows, old, new):
                if nb is not None:
                    self.pending.append((r, nb))
                    delta += nb - b
        self.t += 1
        return np.array([len(ttype), committed, ab_lock, ab_logic, 0,
                         _i32(delta)], np.int64)

    def drain(self) -> None:
        """A step with no new cohort: the last writes land, the last locks
        expire."""
        self._install()
        self.t += 1

    def touched(self):
        """(rows ascending, their balances as u32) of every row written."""
        rows = np.array(sorted(self.bal), np.int64)
        return rows, np.array([self.bal[r] % (1 << 32) for r in rows],
                              np.uint32)

    def total_balance(self) -> int:
        """The sum of all balances, as a wrapping signed 32-bit word."""
        return _i32(2 * self.n * self.init
                   + sum(b - self.init for b in self.bal.values()))
