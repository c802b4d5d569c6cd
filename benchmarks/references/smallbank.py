"""SmallBank, sequentially: the plain reference of the `smallbank24m`
configuration.

Plain Python and numpy, no JAX, nothing imported from the system under
test. It holds the contract that configuration states (strict 2PL with
shared and exclusive no-wait locks, a lock held until the writes it
protects are installed, money conserved, an abort is an answer), written
from the source: the six transactions' lock sets and balance logic are the
reference's (DINT NSDI'24, smallbank/caladan/client_ebpf_shard.cc
TxnAmalgamate:255, TxnBalance:565, TxnDepositChecking:705,
TxnSendPayment:830, TxnTransactSaving:1116, TxnWriteCheck:1241), the lock
rule its lock tables' (smallbank/ebpf/shard_kern.c:96-328: shared grants
while no exclusive holder, exclusive grants on a free slot, everything else
rejects at once).

The serialization contract, one cohort of transactions at a time:

* a cohort's transactions are taken in lane order, a transaction's lock
  requests in the order of its lock set; every request is made, also
  after an earlier one of the same transaction was rejected (the source
  sends a transaction's requests in one wave, :287-325);
* a lock lives in a slot: ``slot = row`` while the table of slots holds
  every row, else the multiply-shift hash below, so that two rows may
  share a slot and conflict (the source's lock arrays are a hash space
  too, smallbank/ebpf/utils.h:16-17); a transaction whose own two rows
  share a slot rejects itself: no lock is re-entrant;
* a granted lock is held until the cohort's writes are installed, one
  step later: cohort t+1 arbitrates against cohort t's locks, cohort t+2
  does not. DEPARTURE from the source, which releases an aborting
  transaction's locks at once (:330-370): here they too are held for the
  step. More rejections than the source, never less isolation;
* a transaction with every lock granted reads its rows and computes; one
  with a rejected request aborts (``ab_lock``) and writes nothing;
* the writes of cohort t are installed before cohort t+1 reads, and each
  is logged as ``(table, account, step, balance, magic)`` with the step
  at which it is installed.

A copy of this file is ``SmallBankOracle`` in dint_tpu/testing/oracle.py
(the program's own tests use that one); tests/bench holds the two to
equal answers. This one decides ``correct`` and belongs to the benchmark.
"""
from __future__ import annotations

import collections

import numpy as np

SAVINGS, CHECKING = 0, 1
(AMALGAMATE, BALANCE, DEPOSIT_CHECKING, SEND_PAYMENT, TRANSACT_SAVING,
 WRITE_CHECK) = range(6)
AMT = 5                 # deposit, payment and check amount
MAGIC = 0x5B5B          # the value's integrity word, whole only in the log
MAX_LOCK_SLOTS = 1 << 25
HASH_MULT = 0x9E3779B1
STAT_NAMES = ("attempted", "committed", "ab_lock", "ab_logic", "magic_bad",
              "bal_delta")
FIRST_STEP = 2          # a stamp of 0 is "never held"

# each transaction's lock set, in request order: (exclusive?, table,
# which of the transaction's two accounts)
LOCK_SETS = {
    AMALGAMATE: ((True, SAVINGS, 0), (True, CHECKING, 0),
                 (True, CHECKING, 1)),
    BALANCE: ((False, SAVINGS, 0), (False, CHECKING, 0)),
    DEPOSIT_CHECKING: ((True, CHECKING, 0),),
    SEND_PAYMENT: ((True, CHECKING, 0), (True, CHECKING, 1)),
    TRANSACT_SAVING: ((True, SAVINGS, 0),),
    WRITE_CHECK: ((False, SAVINGS, 0), (True, CHECKING, 0)),
}


def i32(v: int) -> int:
    """A balance is a signed 32-bit word: sums wrap."""
    return (v + (1 << 31)) % (1 << 32) - (1 << 31)


def lock_slots_for(n_rows: int, cap: int = MAX_LOCK_SLOTS) -> int:
    """The smallest power of two that holds every row, up to ``cap``."""
    return min(1 << (n_rows - 1).bit_length(), cap)


def logic(ttype: int, bal: list, ts_amt: int):
    """A transaction's balance logic on the balances of its lock set, in
    lock-set order: (new balances | None for a logic abort). A lock-set
    row that the transaction only reads keeps None."""
    if ttype == AMALGAMATE:         # all of a1's money to a2's checking
        return [0, 0, i32(bal[2] + bal[0] + bal[1])]
    if ttype == BALANCE:
        return [None, None]
    if ttype == DEPOSIT_CHECKING:
        return [i32(bal[0] + AMT)]
    if ttype == SEND_PAYMENT:
        if bal[0] < AMT:
            return None
        return [i32(bal[0] - AMT), i32(bal[1] + AMT)]
    if ttype == TRANSACT_SAVING:
        if i32(bal[0] + ts_amt) < 0:
            return None
        return [i32(bal[0] + ts_amt)]
    if ttype == WRITE_CHECK:        # an overdraft costs one more
        penalty = 1 if i32(bal[0] + bal[1]) < AMT else 0
        return [None, i32(bal[1] - AMT - penalty)]
    raise ValueError(f"no SmallBank transaction type {ttype}")


class SmallBankOracle:
    """``step`` takes one cohort and returns its six stats. ``tally``
    counts what the lock table saw, by cause, for a test to assert that
    a case occurred."""

    def __init__(self, n_accounts: int, init_balance: int = 1000,
                 max_lock_slots: int = MAX_LOCK_SLOTS):
        self.n = n_accounts
        self.init = init_balance
        self.n_slots = lock_slots_for(2 * n_accounts + 1, max_lock_slots)
        self.hashed = self.n_slots < 2 * n_accounts + 1
        self.bal: dict[int, int] = {}       # row -> balance, once written
        self.x_stamp: dict[int, int] = {}   # slot -> last step X-granted
        self.s_stamp: dict[int, int] = {}   # slot -> last step S-granted
        self.t = FIRST_STEP
        self.pending: list = []             # (row, balance) to install
        self.log: list = []     # (table, account, step, balance u32, magic)
        self.tally: collections.Counter = collections.Counter()

    def slots_of(self, rows: np.ndarray) -> np.ndarray:
        if not self.hashed:
            return rows
        shift = 32 - (self.n_slots.bit_length() - 1)
        return ((rows.astype(np.uint64) * HASH_MULT) % (1 << 32)) >> shift

    def balance(self, row: int) -> int:
        return self.bal.get(row, self.init)

    def _install(self) -> None:
        """The last cohort's writes land, at this step."""
        for row, new in self.pending:
            self.bal[row] = new
            self.log.append((row // self.n, row % self.n, self.t,
                             new % (1 << 32), MAGIC))
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
        for tt, lock_set in LOCK_SETS.items():
            rows = np.stack([tbl * self.n + accounts[:, which]
                             for _, tbl, which in lock_set], axis=1)
            rows_of[tt], slots_of[tt] = (rows.tolist(),
                                         self.slots_of(rows).tolist())
        committed = ab_lock = ab_logic = delta = 0
        owner: dict[int, int] = {}          # slot -> first holder, this step
        for i, tt in enumerate(ttype):
            rows = rows_of[tt][i]
            granted = [self._acquire(x, s, i, owner) for (x, _, _), s
                       in zip(LOCK_SETS[tt], slots_of[tt][i])]
            if not all(granted):
                ab_lock += 1
                continue
            old = [self.balance(r) for r in rows]
            new = logic(tt, old, ts_amt[i])
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
                         i32(delta)], np.int64)

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
        return i32(2 * self.n * self.init
                   + sum(b - self.init for b in self.bal.values()))
