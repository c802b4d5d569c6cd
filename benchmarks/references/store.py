"""A KV store, sequentially: the plain reference of the `store24m`
configuration.

Plain Python (a dict) and numpy for the arrays handed in and out; no JAX,
nothing imported from the system under test. It holds the contract that
configuration states, which is the reference server's seen from a client
(DINT NSDI'24, store/ebpf/store_kern.c:32-300 with the userspace KVS
behind it, store/ebpf/kvs.h; the client's view: store/caladan/
client_caladan.cc:56-66,160):

* GET of a present key answers VAL with the key's value and version, of
  an absent one NOT_EXIST; SET is an upsert that answers ACK with the new
  version, the old one plus one (1 for a key that was absent);
* every read asserts the record it got: word 0 the key, word 1 the magic
  word (client_caladan.cc:160); a VAL that fails it counts ``magic_bad``;
* requests arrive a batch at a time. Per key, the batch's GETs see the
  state before the batch, then its writes apply in lane order.
  DEPARTURE from the source: it serves packets one at a time in arrival
  order, so a GET behind a SET of the same key in one burst sees the SET.
  A client cannot tell the two apart (the order of packets of different
  clients in a burst is the network's), and both are linearizable per
  key: the batch order stands for the packet order;
* a key's version is the version it had before the batch (0 if it was
  absent) plus the batch's SETs and INSERTs of it so far, so within a
  batch versions stay monotone over DELETE and re-INSERT. DEPARTURE from
  the source, whose KVS restarts a re-inserted key's version at once;
  stronger, never weaker (no ABA inside a batch). A key deleted in one
  batch and inserted in a later one starts again at 1, as the source's
  does. The `store24m` traffic has no DELETE: no cell exercises either.

The populated state is a rule, not a dict of 24 M entries: key k in
[1, n_keys] holds {k, magic, 0, ...} at version 1 until it is written.
Only touched keys are kept.

A copy of this file's semantics is ``StoreOracle`` in
dint_tpu/testing/oracle.py (the program's own tests use that one);
tests/bench holds the two to equal answers. This one decides ``correct``
and belongs to the benchmark.
"""
from __future__ import annotations

import numpy as np

NOP, GET, SET, INSERT, DELETE = 0, 1, 2, 3, 4          # request codes
ACK, NOT_EXIST, VAL = 4, 5, 6                          # reply codes
MAGIC = 0x55AA
STAT_NAMES = ("attempted", "committed", "not_exist", "spill", "retry",
              "magic_bad", "gets", "updates", "ver_sum", "val_sum")
MOD = 1 << 32


class Store:
    def __init__(self, n_keys: int, val_words: int):
        self.n_keys = n_keys
        self.val_words = val_words
        self.touched: dict[int, tuple | None] = {}   # key -> (val, ver)

    def lookup(self, key: int):
        """(value tuple, version) of a live key, None of an absent one."""
        if key in self.touched:
            return self.touched[key]
        if 1 <= key <= self.n_keys:
            return ((key, MAGIC) + (0,) * (self.val_words - 2), 1)
        return None

    def step(self, ops, keys, vals):
        """One batch of lanes (op, key, value words). Returns (rtype [r],
        rval [r, VW], rver [r], stats row as STAT_NAMES)."""
        r = len(ops)
        rtype = np.zeros(r, np.int32)
        rval = np.zeros((r, self.val_words), np.uint32)
        rver = np.zeros(r, np.uint32)
        stats = dict.fromkeys(STAT_NAMES, 0)
        for i in range(r):                  # reads: the state before
            if ops[i] == NOP:
                continue
            stats["attempted"] += 1
            if ops[i] != GET:
                continue
            stats["gets"] += 1
            key = int(keys[i])
            ent = self.lookup(key)
            if ent is None:
                rtype[i] = NOT_EXIST
                continue
            rtype[i], rval[i], rver[i] = VAL, ent[0], ent[1]
            if ent[0][0] != key or ent[0][1] != MAGIC:
                stats["magic_bad"] += 1
        before: dict[int, int] = {}         # version before the batch
        installs: dict[int, int] = {}       # SETs + INSERTs so far
        for i in range(r):                  # writes: in lane order
            key = int(keys[i])
            if ops[i] in (SET, INSERT, DELETE) and key not in before:
                ent = self.lookup(key)
                before[key], installs[key] = (ent[1] if ent else 0), 0
            if ops[i] in (SET, INSERT):
                stats["updates"] += int(ops[i] == SET)
                installs[key] += 1
                ver = before[key] + installs[key]
                self.touched[key] = (tuple(int(x) for x in vals[i]), ver)
                rtype[i], rver[i] = ACK, ver
            elif ops[i] == DELETE:
                if self.lookup(key) is None:
                    rtype[i] = NOT_EXIST
                else:
                    self.touched[key] = None
                    rtype[i] = ACK
        done = (rtype == VAL) | (rtype == ACK)
        stats["committed"] = int(done.sum())
        stats["not_exist"] = int((rtype == NOT_EXIST).sum())
        stats["ver_sum"] = int(rver[done].astype(np.int64).sum() % MOD)
        stats["val_sum"] = int(rval[done].astype(np.int64).sum() % MOD)
        return rtype, rval, rver, np.array(
            [stats[n] for n in STAT_NAMES], np.int64)

    def run(self, batches):
        """``batches``: an iterable of (ops, keys, vals). Returns the
        replies of every step and the stats rows, stacked."""
        replies, rows = [], []
        for ops, keys, vals in batches:
            *rep, row = self.step(ops, keys, vals)
            replies.append(rep)
            rows.append(row)
        return replies, np.stack(rows)

    def final_rows(self):
        """The touched keys' final state: (keys [m], live [m] bool,
        values [m, VW], versions [m]), sorted by key."""
        keys = np.array(sorted(self.touched), np.int64)
        live = np.array([self.touched[k] is not None for k in keys], bool)
        vals = np.zeros((len(keys), self.val_words), np.uint32)
        vers = np.zeros(len(keys), np.uint32)
        for i, k in enumerate(keys):
            if live[i]:
                vals[i], vers[i] = self.touched[int(k)]
        return keys, live, vals, vers


def equal_mod32(got: np.ndarray, want: np.ndarray) -> bool:
    """Stats rows or totals equal, the two checksum columns mod 2^32 (the
    system carries them as 32-bit patterns, and a sum of rows wraps)."""
    return got.shape == want.shape and bool(
        ((np.asarray(got, np.int64) - np.asarray(want, np.int64)) % MOD
         == 0)[..., -2:].all()) and np.array_equal(
             np.asarray(got)[..., :-2], np.asarray(want)[..., :-2])
