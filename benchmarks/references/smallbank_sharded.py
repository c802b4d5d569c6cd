"""SmallBank over sharded, replicated servers, sequentially: the plain
reference of the `smallbank24m-x4r3` configuration.

Plain Python and numpy, nothing imported from the system under test. The
transactions, their lock sets and the lock rule are
``benchmarks/references/smallbank.py``'s (the source's six transactions
and its S/X no-wait lock tables); the ring of servers, who backs whom up
and which ring carries which stream are ``benchmarks/references/
replication.py``'s ``placement``. What this file adds is the source's
sharded topology (DINT NSDI'24, smallbank/caladan/client_ebpf_shard.cc:
a coordinator sends each lock and commit message to the server that owns
the row, :255,287-325,830; CommitBck x2 and CommitLog x3, :779-860),
mapped as the configuration states it:

* ``owner(account) = account % D``: both rows of an account (SAVINGS,
  CHECKING) live on one device, at ``local_row = table * n_loc +
  account // D`` of its tables, ``n_loc = ceil(n_accounts / D)``;
* a step's D cohorts, one a source device, are ONE cohort taken in
  (source device, lane, lock-set) order: the order in which an owner
  receives the step's requests (bucket s of its inbox is source s's, a
  source's lanes in their own order), and the only order an outcome
  depends on, since a lock's fate is decided by the requests for its own
  row. So the sequential ``SmallBankOracle`` over the concatenated cohort
  is the answer, with one lock slot a row (``EXACT_SLOTS``: no hashing,
  as a device locks its local rows themselves);
* a source may send an owner at most ``bucket_cap`` requests a step. A
  lane past that has no lawful answer here: ``step`` counts it in the
  stats row's last column, ``overflow``, which a deployment holds to
  zero as a fault;
* device d's installs are applied, in the order acknowledged, to its
  primary, to backup slot s of device d + s + 1 (s = 0, 1) and to three
  log streams: its own ring under tag 0 and the rings of d + 1 and d + 2
  under tag d + 1; an entry is ``(table, account, step, balance, magic)``
  with the GLOBAL account;
* a lost device's primary range is rebuilt from any one of its three
  streams (``replay``).

A copy of this file is dint_tpu/testing/smallbank_sharded.py (the
program's own tests use that one); tests/bench holds the two to equal
answers. This one decides ``correct`` and belongs to the benchmark."""
from __future__ import annotations

import numpy as np

from benchmarks.references import smallbank as one
from benchmarks.references.replication import carried, placement  # noqa: F401

EXACT_SLOTS = 1 << 26       # >= 2 * 24,000,000 + 1 rows: slot == row
STAT_NAMES = (*one.STAT_NAMES, "overflow")


def n_local(n_accounts: int, d: int) -> int:
    """Accounts a device owns (the last may own fewer)."""
    return -(-n_accounts // d)


def owner(account, d: int):
    return account % d


def local_row(table, account, n_loc: int, d: int):
    return table * n_loc + account // d


def bucket_cap(w: int, d: int, lock_set: int = 3) -> int:
    """Requests one source may send one owner a step: twice the share a
    uniform spread gives, as the configuration states it."""
    return 2 * -(-(w * lock_set) // d)


def lock_lanes(ttype: np.ndarray, a1: np.ndarray, a2: np.ndarray):
    """(txn index, account) of every lock request of a cohort, in (lane,
    lock-set) order."""
    accounts = np.stack([a1, a2], axis=1).astype(np.int64)
    txn, pos, acct = [], [], []
    for tt, lock_set in one.LOCK_SETS.items():
        lanes = np.nonzero(ttype == tt)[0]
        for p, (_, _, which) in enumerate(lock_set):
            txn.append(lanes)
            pos.append(np.full(len(lanes), p))
            acct.append(accounts[lanes, which])
    txn, pos, acct = (np.concatenate(x) for x in (txn, pos, acct))
    order = np.lexsort((pos, txn))
    return txn[order], acct[order]


def _i32(v: np.ndarray) -> np.ndarray:
    """``smallbank.i32`` of an int64 array: balances wrap as signed 32-bit
    words."""
    return (v + (1 << 31)) % (1 << 32) - (1 << 31)


class CohortBank:
    """``SmallBankOracle`` with exact lock slots, a cohort at a time in
    numpy: the same contract, for a deployment's scale, where the oracle's
    17 us a transaction is 18 s of every run's set-up (a million
    transactions in the warm-up of `smallbank24m-x4r3`). It keeps no log
    and no tally: ``step``, ``drain``, ``touched`` and ``total_balance``
    are the oracle's, and tests/bench holds the two to equal answers on
    contended cohorts.

    How the sequential rule becomes arithmetic. A lock's fate depends on
    the requests for its own row alone, in arrival order, and on the
    stamps the last cohort left. Sort the cohort's requests by (row,
    arrival). Then for a row: held exclusively by the last cohort, every
    request is rejected; held shared by it, every shared request is
    granted and every exclusive one rejected; free, the FIRST request
    decides: an exclusive one is granted and everything after it
    rejected, a shared one is granted with every later shared one and
    every exclusive one is rejected (a rejected request leaves no stamp,
    so it changes nothing for those after it)."""
    hashed = False
    log = None

    def __init__(self, n_accounts: int, init_balance: int = 1000):
        self.n, self.init = n_accounts, init_balance
        self.bal: dict[int, int] = {}       # row -> balance, once written
        # the rows the last cohort was granted: all a stamp is read for
        self._held_x = self._held_s = np.zeros(0, np.int64)
        self.t = one.FIRST_STEP
        self._pending = (np.zeros(0, np.int64), np.zeros(0, np.int64))

    def _install(self) -> None:
        rows, new = self._pending
        self.bal.update(zip(rows.tolist(), new.tolist()))
        self._pending = (rows[:0], new[:0])

    def _balances(self, rows: np.ndarray) -> np.ndarray:
        get, init = self.bal.get, self.init
        return np.array([get(r, init) for r in rows.reshape(-1).tolist()],
                        np.int64).reshape(rows.shape)

    def step(self, ttype, a1, a2, ts_amt) -> np.ndarray:
        self._install()
        ttype = np.asarray(ttype).astype(np.int64)
        ts_amt = np.asarray(ts_amt).astype(np.int64)
        accounts = np.stack([np.asarray(a1), np.asarray(a2)],
                            axis=1).astype(np.int64)
        w, width = len(ttype), max(len(ls) for ls in one.LOCK_SETS.values())
        row = np.zeros((w, width), np.int64)
        is_x = np.zeros((w, width), bool)
        used = np.zeros((w, width), bool)
        for tt, lock_set in one.LOCK_SETS.items():
            lanes = ttype == tt
            for p, (exclusive, tbl, which) in enumerate(lock_set):
                row[lanes, p] = tbl * self.n + accounts[lanes, which]
                is_x[lanes, p] = exclusive
                used[lanes, p] = True

        # ---- the lock table, row by row in arrival order
        at = np.nonzero(used.reshape(-1))[0]        # arrival = (lane, set)
        r, x = row.reshape(-1)[at], is_x.reshape(-1)[at]
        order = np.lexsort((at, r))
        rs, xs = r[order], x[order]
        head = np.r_[True, rs[1:] != rs[:-1]]       # a row's first request
        first_is_x = xs[np.maximum.accumulate(np.where(head,
                                                       np.arange(len(rs)),
                                                       0))]
        prev_x = np.isin(rs, self._held_x)
        prev_s = np.isin(rs, self._held_s)
        ok = ~prev_x & np.where(xs, ~prev_s & first_is_x & head,
                                prev_s | ~first_is_x)
        self._held_x, self._held_s = rs[ok & xs], rs[ok & ~xs]
        granted = np.zeros(w * width, bool)
        granted[at[order]] = ok
        alive = (granted.reshape(w, width) | ~used).all(axis=1)

        # ---- the six transactions on the balances of their lock sets
        b = np.where(used, self._balances(row), 0)
        new = np.zeros((w, width), np.int64)
        writes = np.zeros((w, width), bool)
        logic_abort = np.zeros(w, bool)
        am, dc, sp, ts, wc = (ttype == k for k in (
            one.AMALGAMATE, one.DEPOSIT_CHECKING, one.SEND_PAYMENT,
            one.TRANSACT_SAVING, one.WRITE_CHECK))
        new[am, 2] = _i32(b[am, 2] + b[am, 0] + b[am, 1])
        writes[am, :3] = True
        new[dc, 0] = _i32(b[dc, 0] + one.AMT)
        writes[dc, 0] = True
        logic_abort |= sp & (b[:, 0] < one.AMT)
        new[sp, 0] = _i32(b[sp, 0] - one.AMT)
        new[sp, 1] = _i32(b[sp, 1] + one.AMT)
        writes[sp, :2] = True
        saved = _i32(b[:, 0] + ts_amt)
        logic_abort |= ts & (saved < 0)
        new[ts, 0] = saved[ts]
        writes[ts, 0] = True
        penalty = (_i32(b[:, 0] + b[:, 1]) < one.AMT).astype(np.int64)
        new[wc, 1] = _i32(b[wc, 1] - one.AMT - penalty[wc])
        writes[wc, 1] = True

        committed = alive & ~logic_abort
        writes &= committed[:, None]
        delta = int((new - b)[writes].sum())
        self._pending = (row[writes], new[writes])      # (lane, set) order
        self.t += 1
        return np.array([w, committed.sum(), (~alive).sum(),
                         (alive & logic_abort).sum(), 0, one.i32(delta)],
                        np.int64)

    def drain(self) -> None:
        self._install()
        self._held_x = self._held_s = self._held_x[:0]
        self.t += 1

    touched = one.SmallBankOracle.touched
    total_balance = one.SmallBankOracle.total_balance


class ShardedSmallBank:
    """``step`` takes the D cohorts of one step, ``[(ttype, a1, a2,
    ts_amt)]`` by source device, and returns the seven stats summed over
    the mesh. ``distributed`` counts what of the traffic crossed devices.
    ``by_cohort``: the bank is a ``CohortBank`` (no log: ``stream`` and
    ``ring`` are then not to be had) and not the sequential oracle."""

    def __init__(self, n_accounts: int, n_devices: int,
                 init_balance: int = 1000, cap: int | None = None,
                 by_cohort: bool = False):
        self.n, self.d, self.init = n_accounts, n_devices, init_balance
        self.n_loc = n_local(n_accounts, n_devices)
        self.cap = cap
        self.where = placement(n_devices)
        if 2 * n_accounts + 1 > EXACT_SLOTS:
            raise ValueError(f"{n_accounts} accounts need more than "
                             f"{EXACT_SLOTS} exact lock slots")
        self.bank = CohortBank(n_accounts, init_balance) if by_cohort \
            else one.SmallBankOracle(n_accounts, init_balance,
                                     max_lock_slots=EXACT_SLOTS)
        self.distributed = {"txns": 0, "xshard_txns": 0, "lock_lanes": 0,
                            "remote_lock_lanes": 0}

    def _count(self, cohorts) -> int:
        """Tally the step's distributed traffic; returns the lanes past a
        bucket's capacity."""
        over = 0
        for src, (ttype, a1, a2, _) in enumerate(cohorts):
            txn, acct = lock_lanes(np.asarray(ttype), np.asarray(a1),
                                   np.asarray(a2))
            own = owner(acct, self.d)
            first = np.full(len(ttype), -1, np.int64)
            first[txn[::-1]] = own[::-1]        # a txn's first lane's owner
            self.distributed["txns"] += len(ttype)
            self.distributed["xshard_txns"] += len(
                np.unique(txn[own != first[txn]]))
            self.distributed["lock_lanes"] += len(txn)
            self.distributed["remote_lock_lanes"] += int((own != src).sum())
            if self.cap is not None:
                per_owner = np.bincount(own, minlength=self.d)
                over += int(np.maximum(per_owner - self.cap, 0).sum())
        return over

    def step(self, cohorts) -> np.ndarray:
        if len(cohorts) != self.d:
            raise ValueError(f"{self.d} source devices, {len(cohorts)} "
                             "cohorts")
        over = self._count(cohorts)
        row = self.bank.step(*(np.concatenate([np.asarray(c[k])
                                               for c in cohorts])
                               for k in range(4)))
        return np.append(row, over)

    def drain(self) -> None:
        self.bank.drain()

    # ------------------------------------------------ what each device holds

    def all_touched(self) -> list:
        """By device: (local rows ascending, balances u32) of every row of
        its primary range that was written: what its primary holds there,
        and backup slot s of device dev + s + 1."""
        rows, balances = self.bank.touched()
        table, acct = rows // self.n, rows % self.n
        local = local_row(table, acct, self.n_loc, self.d)
        out = []
        for dev in range(self.d):
            mine = np.nonzero(owner(acct, self.d) == dev)[0]
            mine = mine[np.argsort(local[mine])]
            out.append((local[mine], balances[mine]))
        return out

    def touched(self, dev: int):
        return self.all_touched()[dev]

    def table(self, dev: int) -> np.ndarray:
        """Device ``dev``'s whole primary range, u32 [2 * n_loc + 1], the
        never-written last row 0 (small sizes only)."""
        bal = fresh_table(self.n_loc, self.init)
        rows, balances = self.touched(dev)
        bal[rows] = balances
        return bal

    def stream(self, dev: int) -> list:
        """Device ``dev``'s acknowledged installs, in order: ``(table,
        account, step, balance, magic)``."""
        return [e for e in self.bank.log if owner(e[1], self.d) == dev]

    def ring(self, ring: int) -> dict:
        """{tag: entries} of what ring ``ring`` must hold: its own stream
        under tag 0, the two forwarded ones under source + 1."""
        return {tag: self.stream(src) for src, tag in carried(self.d, ring)}

    def total_balance(self) -> int:
        return self.bank.total_balance()


def fresh_table(n_loc: int, init_balance: int) -> np.ndarray:
    bal = np.full(2 * n_loc + 1, init_balance, np.uint32)
    bal[-1] = 0
    return bal


def replay(entries, dev: int, n_accounts: int, d: int,
           init_balance: int = 1000) -> np.ndarray:
    """A lost device's primary range from one stream that carries it:
    ``entries`` in any order, (table, account, step, balance, magic); a
    row's last acknowledged value is its entry with the highest step (one
    exclusive writer a row and step). An entry of another device's row,
    outside the tables or without its magic word is an error."""
    n_loc = n_local(n_accounts, d)
    bal = fresh_table(n_loc, init_balance)
    newest: dict = {}
    for table, acct, step, balance, magic in entries:
        if owner(acct, d) != dev or not (0 <= table < 2
                                         and 0 <= acct < n_accounts):
            raise ValueError(f"entry of table {table}, account {acct} in "
                             f"device {dev}'s stream")
        if magic != one.MAGIC:
            raise ValueError(f"entry of account {acct} lacks its magic "
                             "word")
        row = local_row(table, acct, n_loc, d)
        if row not in newest or newest[row][0] < step:
            newest[row] = (step, balance)
    for row, (_, balance) in newest.items():
        bal[row] = balance
    return bal
