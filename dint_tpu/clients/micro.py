"""Microbenchmark clients: store, lock_2pl, lock_fasst, log_server.

Host-side, wave-batched equivalents of the reference's four microbenchmark
clients (SURVEY.md §2.1 #6/#9/#12/#14):

  * StoreClient — TATP-subset GET/SET mix over a populated KV table;
    contention (50R/50W) and parallel (100R) mixes per
    /root/reference/store/caladan/client_caladan.cc:56-66, with the
    magic-byte check every read asserts (:160).
  * Lock2PLClient — trace replay of sorted-key lock txns under no-wait 2PL:
    all of a txn's locks go out in one wave (the reference's coordinator
    likewise batches per-shard, smallbank/caladan/client_ebpf_shard.cc:287-325);
    on any REJECT the txn releases what it got and restarts
    (lock_2pl/caladan/client.cc:205-219).
  * FasstClient — FaSST OCC replay: read-set READ_VER + write-set LOCK in
    one wave (lock_fasst/caladan/client.cc:246-277), validation re-read
    (:199-215), then COMMIT_VER or ABORT (:216-236).
  * LogClient — replication-log append replay
    (log_server/caladan/client.cc:147-167).

Latency accounting: a wave's wall time is attributed to every request in
it; txn latency = time from first wave of the attempt chain to commit —
same definition as the reference's microtime() around the whole txn
(tatp/caladan/client_ebpf_shard.cc:1617-1652).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from ..engines import fasst, lock2pl, logsrv, store
from ..engines.types import Op, Reply, make_batch
from ..stats import Recorder
from ..tables import kv, locks, log as logring
from . import workloads as wl

STORE_MAGIC = 0x55AA


def make_store_table(n_keys: int, *, n_buckets: int | None = None,
                     val_words: int = 10) -> kv.KVTable:
    """Populated store table: keys 1..n, val word0 = key, word1 = magic
    (store/caladan/client_caladan.cc:160). Shared by the in-process store
    client and the wire-path bench so both serve identical contents."""
    if n_buckets is None:
        n_buckets = max(16, 1 << int(np.ceil(np.log2(n_keys / 2))))
    keys = np.arange(1, n_keys + 1, dtype=np.uint64)
    vals = np.zeros((n_keys, val_words), np.uint32)
    vals[:, 0] = keys.astype(np.uint32)
    vals[:, 1] = STORE_MAGIC
    return kv.populate(kv.create(n_buckets, val_words=val_words), keys, vals)


class _SteppedClient:
    """Shared plumbing: jitted donated step + timed wave runner."""

    def __init__(self, state, step_fn, width: int, val_words: int):
        self.state = state
        self.width = width
        self.vw = val_words
        self._step = jax.jit(step_fn, donate_argnums=0)
        self.rec = Recorder()

    def _wave(self, ops, keys, vals=None, vers=None, tables=None):
        """Run one batch; returns (rtype, rval, rver, wall_s)."""
        m = len(ops)
        assert m <= self.width, f"wave of {m} exceeds width {self.width}"
        batch = make_batch(ops, keys, vals, vers=vers, tables=tables,
                           width=self.width, val_words=self.vw)
        t0 = time.monotonic()
        self.state, rep = self._step(self.state, batch)
        rt = np.asarray(rep.rtype)[:m]
        dt = time.monotonic() - t0
        self.rec.device_busy_s += dt
        return rt, np.asarray(rep.val)[:m], np.asarray(rep.ver)[:m], dt


class StoreClient(_SteppedClient):
    """GET/SET mix over a pre-populated table. ``read_frac=1.0`` is the
    reference's 'parallel' benchmark, 0.5 the 'contention' one
    (store/caladan/client_caladan.cc:56-66).

    ``key_dist="zipfian"`` draws keys from the YCSB-style Zipfian whose
    hot head is the smallest key ids (workloads.zipf_keys) — DINT's
    skewed store benchmark. ``use_hotset`` (None = DINT_USE_HOTSET env)
    attaches the dintcache mirror for the first ``hot_frac`` of the
    keyspace and threads it through every step (write-through,
    bit-identical replies).

    ``use_scan`` (None = DINT_USE_SCAN env) attaches the dintscan ordered
    run and lets waves carry Op.SCAN lanes (``scan_frac`` of the mix,
    zipfian start keys + uniform lengths clipped to ``scan_max``).
    In-doubt/retry semantics match the GET path's populated-key asserts:
    a scan must answer VAL, except when the run's overlay went stale —
    then the engine replies RETRY, the client rebuilds the run at the
    next maintenance point and RE-SENDS exactly those lanes, and the
    retry must answer VAL (the capped-resend discipline of the TIMEOUT
    sentinel, here with the rebuild as the recovery action)."""

    def __init__(self, table: kv.KVTable, n_keys: int, width: int = 4096,
                 val_words: int = 10, read_frac: float = 0.5,
                 key_dist: str = "uniform", zipf_theta: float = wl.ZIPF_THETA,
                 hot_frac: float | None = None, use_hotset=None,
                 use_scan=None, scan_frac: float = 0.0,
                 scan_max: int = 8, max_scan_len: int | None = None,
                 delta_cap: int = 64, rebuild_every: int = 8):
        from ..ops import hotset
        from ..tables import run as run_mod

        assert key_dist in ("uniform", "zipfian")
        self.use_hotset = hotset.resolve_use_hotset(use_hotset)
        self.use_scan = run_mod.resolve_use_scan(use_scan)
        self.scan_max = int(scan_max)
        self.scan_frac = float(scan_frac) if self.use_scan else 0.0
        self.max_scan_len = int(max_scan_len or scan_max)
        self.delta_cap = int(delta_cap)
        self.rebuild_every = max(int(rebuild_every), 1)
        self._waves_since_rebuild = 0
        run0 = None
        if self.use_scan:
            run0 = run_mod.from_table(table, delta_cap=int(delta_cap))
        hot = None
        if self.use_hotset:
            frac = 0.04 if hot_frac is None else float(hot_frac)
            # mirror ids are key_lo < hot_n; keys are 1-based, so cover
            # keys 1..frac*n with hot_n = frac*n + 1
            hot_n = min(int(n_keys * frac) + 1, n_keys + 1)
            hot = store.attach_hot(table, hot_n)

        smax = self.scan_max
        if self.use_scan and self.use_hotset:
            def step_fn(state, batch):
                t, h, rn = state
                t, rep, h, rn, srep = store.step(
                    t, batch, hot=h, run=rn, scan_max=smax)
                return (t, h, rn), (rep, srep)

            state = (table, hot, run0)
        elif self.use_scan:
            def step_fn(state, batch):
                t, rn = state
                t, rep, rn, srep = store.step(
                    t, batch, run=rn, scan_max=smax)
                return (t, rn), (rep, srep)

            state = (table, run0)
        elif self.use_hotset:
            def step_fn(state, batch):
                t, h = state
                t, rep, h = store.step(t, batch, hot=h)
                return (t, h), rep

            state = (table, hot)
        else:
            state, step_fn = table, store.step
        super().__init__(state, step_fn, width, val_words)
        if self.use_scan:
            def _rebuild(state):
                t, rest = state[0], state[1:]
                return (t,) + rest[:-1] + (store.rebuild_run(t, rest[-1]),)

            self._rebuild = jax.jit(_rebuild, donate_argnums=0)
        self.n_keys = n_keys
        self.read_frac = read_frac
        self.key_dist = key_dist
        self.zipf_theta = zipf_theta

    @classmethod
    def populated(cls, n_keys: int, *, n_buckets: int | None = None,
                  val_words: int = 10, **kw):
        table = make_store_table(n_keys, n_buckets=n_buckets,
                                 val_words=val_words)
        return cls(table, n_keys, val_words=val_words, **kw)

    def _keys(self, rng: np.random.Generator, n: int) -> np.ndarray:
        if self.key_dist == "zipfian":
            return wl.zipf_keys(rng, n, self.n_keys, self.zipf_theta)
        return rng.integers(1, self.n_keys + 1, size=n).astype(np.uint64)

    def _wave_scan(self, ops, keys, vals, vers):
        """Like _wave, for the scan-threaded step whose reply is
        (Replies, ScanReplies)."""
        m = len(ops)
        assert m <= self.width, f"wave of {m} exceeds width {self.width}"
        batch = make_batch(ops, keys, vals, vers=vers,
                           width=self.width, val_words=self.vw)
        t0 = time.monotonic()
        self.state, (rep, srep) = self._step(self.state, batch)
        rt = np.asarray(rep.rtype)[:m]
        dt = time.monotonic() - t0
        self.rec.device_busy_s += dt
        return rt, np.asarray(rep.val)[:m], np.asarray(rep.ver)[:m], srep, dt

    def run_wave(self, rng: np.random.Generator, n: int | None = None):
        n = n or self.width
        keys = self._keys(rng, n)
        is_scan = rng.random(n) < self.scan_frac
        is_read = ~is_scan & (rng.random(n) < self.read_frac)
        ops = np.where(is_scan, Op.SCAN,
                       np.where(is_read, Op.GET, Op.SET)).astype(np.int32)
        vals = np.zeros((n, self.vw), np.uint32)
        vals[:, 0] = rng.integers(0, 1 << 30, size=n).astype(np.uint32)
        vals[:, 1] = STORE_MAGIC
        srep = None
        if self.use_scan:
            vers = np.where(is_scan,
                            wl.scan_lengths(rng, n, self.max_scan_len),
                            0).astype(np.uint32)
            rt, rv, rr, srep, dt = self._wave_scan(ops, keys, vals, vers)
        else:
            assert not is_scan.any(), "scan lanes need use_scan=True"
            rt, rv, rr, dt = self._wave(ops, keys, vals)
        got = rt[is_read] == Reply.VAL
        assert got.all(), "populated key missing"
        assert (rv[is_read][:, 1] == STORE_MAGIC).all(), "magic corrupted"
        ok = int((rt == Reply.VAL).sum() + (rt == Reply.ACK).sum())
        if self.use_scan:
            sc = rt[is_scan]
            assert np.isin(sc, (Reply.VAL, Reply.RETRY)).all(), \
                "scan lane answered neither VAL nor RETRY"
            cnt = np.asarray(srep.count)[:n]
            okv = is_scan & (rt == Reply.VAL)
            assert (cnt[okv] <= np.minimum(vers[okv], self.scan_max)).all()
            assert (rr[okv] == cnt[okv]).all()
            retry = is_scan & (rt == Reply.RETRY)
            if retry.any():
                # in-doubt recovery, GET-path style: the stale overlay is
                # the known cause, so rebuild NOW and re-send exactly the
                # RETRY lanes — the retry must answer VAL
                self.state = self._rebuild(self.state)
                self._waves_since_rebuild = 0
                rt2, _, rr2, srep2, _ = self._wave_scan(
                    ops[retry], keys[retry], vals[retry], vers[retry])
                assert (rt2 == Reply.VAL).all(), "scan retry still in doubt"
                ok += int(len(rt2))
        self.rec.record(n, ok, np.full(n, dt * 1e6))
        self._waves_since_rebuild += 1
        if self.use_scan and self._waves_since_rebuild >= self.rebuild_every:
            # drain-boundary maintenance: fold the overlay into the run
            self.state = self._rebuild(self.state)
            self._waves_since_rebuild = 0
        return ok


class LogClient(_SteppedClient):
    """Append replay (log_server/caladan/client.cc:147-167)."""

    def __init__(self, ring: logring.LogRing | None = None, width: int = 4096,
                 val_words: int = 10, lanes: int = 16, capacity: int = 1 << 20):
        ring = ring or logring.create(lanes, capacity, val_words)
        super().__init__(ring, logsrv.step, width, val_words)

    def run_wave(self, rng: np.random.Generator, n: int | None = None):
        n = n or self.width
        keys = rng.integers(0, 10_000, size=n).astype(np.uint64)
        vals = rng.integers(0, 1 << 16, size=(n, self.vw)).astype(np.uint32)
        vers = rng.integers(1, 1 << 20, size=n).astype(np.uint32)
        ops = np.full(n, Op.LOG_APPEND, np.int32)
        rt, _, _, dt = self._wave(ops, keys, vals, vers)
        assert (rt == Reply.ACK).all()
        self.rec.record(n, n, np.full(n, dt * 1e6))
        return n


class _TraceCohort:
    """A rotating cohort of in-flight trace txns with retry-on-abort and
    per-txn start timestamps."""

    def __init__(self, trace, cohort: int, rng: np.random.Generator):
        self.trace = trace
        self.rng = rng
        self.next_txn = cohort
        idx = np.arange(cohort) % len(trace)
        self.cur = [trace[i] for i in idx]
        self.t_start = np.full(cohort, time.monotonic())

    def refill(self, done_mask: np.ndarray):
        """Replace completed txns with fresh ones; returns their latencies."""
        now = time.monotonic()
        lats = (now - self.t_start[done_mask]) * 1e6
        for i in np.nonzero(done_mask)[0]:
            self.cur[i] = self.trace[self.next_txn % len(self.trace)]
            self.next_txn += 1
            self.t_start[i] = now
        return lats


def _flatten(cohort_txns):
    """[(keys, is_read)] -> flat arrays + txn index per lane."""
    keys = np.concatenate([t[0] for t in cohort_txns])
    is_read = np.concatenate([t[1] for t in cohort_txns])
    txn_of = np.repeat(np.arange(len(cohort_txns)),
                       [len(t[0]) for t in cohort_txns])
    return keys.astype(np.uint64), is_read, txn_of


class Lock2PLClient(_SteppedClient):
    """No-wait 2PL trace replay (lock_2pl/caladan/client.cc:167-219)."""

    def __init__(self, trace, n_slots: int = 1 << 16, cohort: int = 512,
                 width: int = 8192, val_words: int = 1,
                 rng: np.random.Generator | None = None):
        super().__init__(locks.create_sx(n_slots), lock2pl.step, width, val_words)
        self.co = _TraceCohort(trace, cohort, rng or np.random.default_rng(1))

    def run_round(self):
        """One acquire wave + one release wave over the whole cohort."""
        keys, is_read, txn_of = _flatten(self.co.cur)
        w = len(self.co.cur)
        ops = np.where(is_read, Op.ACQ_S, Op.ACQ_X).astype(np.int32)
        rt, _, _, _ = self._wave(ops, keys)

        granted_lane = rt == Reply.GRANT
        rejected_txn = np.zeros(w, bool)
        np.logical_or.at(rejected_txn, txn_of, rt == Reply.REJECT)
        committed = ~rejected_txn

        # release everything granted (commit: txn end; abort: rollback,
        # client.cc:205-219) — one wave
        rel_mask = granted_lane
        if rel_mask.any():
            rel_ops = np.where(is_read[rel_mask], Op.REL_S, Op.REL_X).astype(np.int32)
            rrt, _, _, _ = self._wave(rel_ops, keys[rel_mask])
            assert (rrt == Reply.ACK).all()

        lats = self.co.refill(committed)  # aborted txns retry, keeping t_start
        self.rec.record(int(w), int(committed.sum()), lats)
        return int(committed.sum())


class FasstClient(_SteppedClient):
    """FaSST OCC trace replay (lock_fasst/caladan/client.cc:184-280).

    ``attribute=True`` runs the lock-attribution server variant
    (engines.fasst.step_attr == tatp/ebpf/lock_kern.c) and keeps the
    reference's conflict-attribution counters lock_cnt /
    reject_sharing_cnt / reject_same_key_cnt
    (tatp/caladan/client_lock.cc:62-64,768-771) in ``rec.extra``."""

    def __init__(self, trace, n_slots: int = 1 << 16, cohort: int = 512,
                 width: int = 8192, val_words: int = 1,
                 rng: np.random.Generator | None = None,
                 attribute: bool = False):
        state = (locks.create_occ_attr(n_slots) if attribute
                 else locks.create_occ(n_slots))
        step_fn = fasst.step_attr if attribute else fasst.step
        super().__init__(state, step_fn, width, val_words)
        self.co = _TraceCohort(trace, cohort, rng or np.random.default_rng(2))
        self.attribute = attribute
        if attribute:
            self.rec.extra.update(lock_cnt=0, reject_sharing_cnt=0,
                                  reject_same_key_cnt=0)

    def run_round(self):
        keys, is_read, txn_of = _flatten(self.co.cur)
        w = len(self.co.cur)

        # wave 1: read-set versions + write-set locks (client.cc:246-277)
        ops = np.where(is_read, Op.READ_VER, Op.LOCK).astype(np.int32)
        rt, _, rver, _ = self._wave(ops, keys)
        lock_lane = ~is_read
        got_lock = rt == Reply.GRANT
        if self.attribute:
            self.rec.extra["lock_cnt"] += int(lock_lane.sum())
            self.rec.extra["reject_sharing_cnt"] += int(
                (lock_lane & (rt == Reply.REJECT)).sum())
            self.rec.extra["reject_same_key_cnt"] += int(
                (lock_lane & (rt == Reply.REJECT_SAME_KEY)).sum())
        lock_fail = np.zeros(w, bool)
        np.logical_or.at(lock_fail, txn_of, lock_lane & ~got_lock)

        # wave 2: validate = re-read read-set; abort if the version changed OR
        # the slot is now locked by a concurrent writer (:199-215 — the
        # reference checks both; the lock bit rides reply val word 0)
        val_fail = np.zeros(w, bool)
        rd = is_read
        if rd.any():
            v_ops = np.full(int(rd.sum()), Op.READ_VER, np.int32)
            vrt, vval, vver, _ = self._wave(v_ops, keys[rd])
            assert (vrt == Reply.VAL).all()
            bad = (vver != rver[rd]) | (vval[:, 0] != 0)
            np.logical_or.at(val_fail, txn_of[rd], bad)
        aborted = lock_fail | val_fail
        committed = ~aborted

        # wave 3: COMMIT_VER for committed txns' write-sets; ABORT for
        # granted locks of aborted txns (:216-236)
        fin_lane = lock_lane & got_lock
        if fin_lane.any():
            fl_ops = np.where(aborted[txn_of[fin_lane]], Op.ABORT,
                              Op.COMMIT_VER).astype(np.int32)
            frt, _, _, _ = self._wave(fl_ops, keys[fin_lane])
            assert (frt == Reply.ACK).all()

        lats = self.co.refill(committed)
        self.rec.record(int(w), int(committed.sum()), lats)
        return int(committed.sum())
