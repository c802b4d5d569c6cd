"""Sort-free dense SmallBank engine: the TPU-first fast path.

Companion to engines/tatp_dense.py for the SmallBank workload, replacing
the vmapped sort-based smallbank.step pair the device-fused pipeline pays
per cohort (engines/smallbank_pipeline.py). Structural moves, each forced
by a measured v5e fact:

* SAVINGS/CHECKING are dense 0..N-1 (smallbank/ebpf/smallbank.h:20-66), so
  both tables live in ONE flat row-id space: row = table*N + account, with
  row M = 2N as the never-written gather sentinel. Balances are a single
  1-D u32 array — any 2-D [M, k] layout is tiled to 128 words/row by XLA
  (24 GB at the reference's 24M accounts ([48M, 3, 2] u32 does not even
  compile on a 16 GB chip — observed), and 1-D scatters/gathers are the
  fast path anyway.

* Replicas are bit-identical by construction (CommitLog x3 + CommitBck x2 +
  CommitPrim install everywhere, smallbank/caladan/client_ebpf_shard.cc:
  389-560), so table content is stored once; the replication that matters
  for recovery stays physical in the log x3 (tables/log.RepLog). The
  multi-chip path (parallel/sharded.py) places real per-device replicas.

* Locks live in a HASHED slot space like the reference's lock tables
  (lock arrays indexed by a key hash, with hash-conflation conflicts,
  smallbank/ebpf/shard_kern.c:26-38) — exact (slot == row) whenever the
  table fits the slot cap, multiply-shift hashed above that. Because every
  lock is held for EXACTLY one pipeline step (acquire at wave 1 of step T,
  release at wave 2 in step T+1), lock state is a step stamp, not a
  counter: slot held at step T iff its stamp == T-1. Releases are implicit
  (stamps go stale), which deletes the X-release scatter and the
  duplicate-index S-count inc/dec scatters (duplicate-index scatters
  serialize on TPU) from the hot loop entirely.

* Per-row version words exist in the reference to order replicated
  installs (versioned kvs_set). Under deterministic batch certification
  the pipeline step index IS that order: log entries carry ver = step, so
  recovery's max-version-per-row rule works unchanged, and the table
  needs no version array (2 fewer random ops per step).

No-wait S/X arbitration without a sort (the closed form of processing a
slot's lock requests in lane order, == the reference's per-entry CAS +
grant/reject counters, smallbank/ebpf/shard_kern.c:96-328):
  first_x, first_s = per-slot scatter-min of lane index over X / S requests
  x_wins(slot)     = first_x < first_s  and slot free last step
  X grant          = x_wins and lane == first_x
  S grant          = slot has no X stamp and not x_wins
(if any S precedes the first X, the X rejects and ALL batch S's share the
slot; if an X is first on a free slot it takes it and everything else
rejects.) The S stamp is written by the first S lane only, so every
scatter in the step has provably unique indices.

The 2-stage software pipeline fuses, per device step,
  wave 1 of cohort t     (S/X lock + fused balance read + compute),
                         arbitrated against cohort t-1's STILL-HELD stamps
  wave 2 of cohort t-1   (install + log x3), applied after
so locks are held across one step boundary and lock conflicts between
consecutive cohorts are real concurrency, exactly like the reference's
overlapping in-flight txns. The wave-1 balance gather safely precedes
c1's installs: any row c1 installs was X-stamped by c1, so this cohort's
acquire on it REJECTed and its pre-install value is never consumed.
Per-txn balance logic is shared with the generic pipeline
(smallbank_pipeline.compute_phase).

The magic-word integrity check of the generic engines (STAT_MAGIC_BAD) is
structurally vacuous here — balances live alone in their array, and the
magic word would be a never-mutated constant — so it is not stored; the
window-wide balance-conservation invariant (bench_smallbank) is the
stronger integrity oracle. The stat slot is kept (always 0) for schema
compatibility.
"""
from __future__ import annotations

import functools

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from ._memo import memoize_builder, refuse_kernel_flags
from ..monitor import counters as mon
from ..monitor import txnevents as txe
from ..monitor import waves
from ..ops import compact, hotset
from ..tables import log as logring
from .types import Op
from .smallbank_pipeline import (AMT, L, MAGIC, N_SHARDS, TS_AMT_MAX, VW,     # noqa: F401 (re-exported)
                                 STAT_ATTEMPTED, STAT_COMMITTED, STAT_AB_LOCK,
                                 STAT_AB_LOGIC, STAT_MAGIC_BAD, STAT_BAL_DELTA,
                                 N_STATS, compute_phase, gen_cohort,
                                 _lock_slots)

I32 = jnp.int32
U32 = jnp.uint32

BIG = jnp.int32(1 << 30)
MAX_LOCK_SLOTS = 1 << 25


def lock_slots_for(m1: int) -> int:
    """Lock-table size: exact (>= m1) up to 2^25, hashed above — the
    reference's lock arrays are likewise a fixed hash space (~1.5x the
    keyspace, smallbank/ebpf/utils.h:16-17) with hash-conflation rejects.
    The cap trades conflation aborts against per-access cost on the stamp
    arrays (measured on v5e at the reference's 48M rows: 2^24 slots ->
    1.07M txn/s at 11.4% aborts of which ~5% are conflation; 2^26 -> 473k
    at 6.6% with conflation ~0; 2^25 is the balance point)."""
    return min(1 << (m1 - 1).bit_length(), MAX_LOCK_SLOTS)


@flax.struct.dataclass
class DenseBank:
    """Both tables + locks + logs in flat dense arrays (row M = 2N is the
    gather sentinel; masked scatters route out of bounds and drop).

    The ``hot_*`` leaves are the dintcache hot tier (round 10): a compact
    physical mirror of the hot-account prefix — mirror index
    ``tbl * hot_n + acc`` for accounts ``acc < hot_n`` — that every
    install writes through to, so mirror == table prefix is an invariant,
    not a protocol. ``hot_x``/``hot_s`` exist only while the lock table
    is EXACT (slot == row): under the hashed slot cap a cold account can
    conflate onto a hot account's slot, which would make a slot mirror
    incoherent, so hashed geometries serve stamps from the full arrays.
    None (the default) = no hot tier; the pytree and jaxpr are unchanged."""
    bal: jax.Array       # u32 [M+1]  balances (i32 bits)
    x_step: jax.Array    # u32 [H]    last step an X grant stamped the slot
    s_step: jax.Array    # u32 [H]    last step an S grant stamped the slot
    step: jax.Array      # u32 scalar, monotonic (starts at 2: stamp 0 is
                         #   "never held", so step-1 must never be 0)
    log: logring.RepLog  # 3 replica entries packed per slot (log x3)
    hot_bal: jax.Array | None = None   # u32 [2*hot_n] balance mirror
    hot_x: jax.Array | None = None     # u32 [2*hot_n] X-stamp mirror (exact)
    hot_s: jax.Array | None = None     # u32 [2*hot_n] S-stamp mirror (exact)
    hot_n: int = flax.struct.field(pytree_node=False, default=0)

    @property
    def n_accounts(self):
        return self.bal.shape[0] // 2

    @property
    def lock_slots(self):
        return self.x_step.shape[0]


def attach_hotset(db: DenseBank, hot_n: int) -> DenseBank:
    """Build the hot mirror for accounts [0, hot_n) from the current
    tables (a few MiB at the bench's 960k-account hot set). Stamps are
    mirrored only in the exact lock regime — see DenseBank."""
    n = db.n_accounts
    hot_n = int(min(max(int(hot_n), 1), n))
    m1 = 2 * n + 1
    idx = jnp.concatenate([jnp.arange(hot_n, dtype=I32),
                           n + jnp.arange(hot_n, dtype=I32)])
    exact = db.lock_slots >= m1
    return db.replace(
        hot_bal=db.bal[idx],
        hot_x=db.x_step[idx] if exact else None,
        hot_s=db.s_step[idx] if exact else None,
        hot_n=hot_n)


def create(n_accounts: int, init_balance: int = 1000, log_lanes: int = 16,
           log_capacity: int = 1 << 16) -> DenseBank:
    """Populated on device (reference: smallbank/ebpf/shard_user.c:74-77);
    every account starts at init_balance.

    ``log_capacity`` bounds the recovery window: the ring holds
    lanes*capacity entries and wraps like the reference's fixed rings
    (log_server/ebpf/ls_kern.c:72-73), and recover_* REFUSES a wrapped
    ring. The default (1M entries) wraps within ~1 s at full bench
    throughput — benchmarks trade recoverability for HBM; pass a larger
    capacity when recovery artifacts are wanted."""
    m1 = 2 * n_accounts + 1
    h = lock_slots_for(m1)
    bal = jnp.full((m1,), np.uint32(init_balance), U32).at[-1].set(0)
    return DenseBank(
        bal=bal,
        x_step=jnp.zeros((h,), U32),
        s_step=jnp.zeros((h,), U32),
        step=jnp.asarray(2, U32),
        log=logring.create_rep(log_lanes, log_capacity, VW,
                               replicas=N_SHARDS),
    )


def _slot_of(rows, m1: int, h: int):
    """Row -> lock slot: identity when exact, multiply-shift hash when the
    keyspace exceeds the lock table (the reference's fasthash-indexed lock
    arrays conflate keys the same way)."""
    if h >= m1:
        return rows
    shift = 32 - int(np.log2(h))
    return ((rows.astype(U32) * U32(0x9E3779B1)) >> U32(shift)).astype(I32)


def total_balance(db: DenseBank, replica: int = 0):
    """Device-side balance sum (mod 2^32, i32 accumulate — conservation
    compares deltas under the same wraparound). `replica` kept for
    signature compatibility: table content is stored once."""
    return db.bal[:-1].astype(I32).sum(dtype=I32)


@flax.struct.dataclass
class BankCtx:
    """A cohort between lock+compute (wave 1) and install (wave 2); lock
    release is implicit (stamps expire). Stats are emitted when the writes
    land. Bootstrap cohorts have attempted == 0 and all-False masks."""
    rows: jax.Array      # i32 [w, L] flat row ids (sentinel if inactive)
    do_write: jax.Array  # bool [w, L]
    nw: jax.Array        # i32 [w, L] new balances
    tbl: jax.Array       # i32 [w, L] (for the log)
    acc: jax.Array       # i32 [w, L] (for the log)
    attempted: jax.Array   # i32 scalar
    committed: jax.Array   # i32 scalar
    ab_lock: jax.Array     # i32 scalar
    ab_logic: jax.Array    # i32 scalar
    magic_bad: jax.Array   # i32 scalar (structurally 0, kept for schema)
    bal_delta: jax.Array   # i32 scalar


def empty_ctx(w: int) -> BankCtx:
    def z(shape, dt):
        return jnp.asarray(np.zeros(shape, dt))

    return BankCtx(
        rows=z((w, L), np.int32), do_write=z((w, L), bool),
        nw=z((w, L), np.int32), tbl=z((w, L), np.int32),
        acc=z((w, L), np.int32),
        attempted=z((), np.int32), committed=z((), np.int32),
        ab_lock=z((), np.int32), ab_logic=z((), np.int32),
        magic_bad=z((), np.int32), bal_delta=z((), np.int32))


def _stats_of(c: BankCtx):
    return jnp.stack([c.attempted, c.committed, c.ab_lock, c.ab_logic,
                      c.magic_bad, c.bal_delta])


def pipe_step(db: DenseBank, c1: BankCtx, key, *, w: int, n_accounts: int,
              gen_new: bool = True, hot_frac=None, hot_prob=None, mix=None,
              use_hotset: bool = False,
              occupancy: jax.Array | None = None,
              shed: jax.Array | None = None,
              counters: mon.Counters | None = None,
              ring: txe.TxnRing | None = None,
              tcfg: txe.TraceCfg | None = None):
    """One fused device step: wave 1 of a NEW cohort acquires against c1's
    STILL-HELD stamps (stamp == step-1), then wave 2 installs c1's writes.
    Returns (db', new_ctx, stats-of-c1).

    ``use_hotset`` (static) serves the step's random single-word gathers —
    the held-stamp reads on x_step/s_step and the fused balance read —
    through the dintcache partition (db must carry the hot mirror —
    attach_hotset): hot lanes (account < hot_n) read the compact mirror
    (a small-array gather) while cold lanes walk the full tables, and the
    wave-2 install writes through to the mirror (a double 1-D
    unique-index scatter). Outputs stay bit-identical to the default path
    (pinned in tests/test_hotset.py).

    ``occupancy``/``shed`` (device i32 scalars, or None = off): the
    dintserve variable-occupancy plane — lanes >= occupancy have their
    lock slots zeroed BEFORE arbitration (their txns never request,
    grant, compute, or install anything) and ``attempted`` counts only
    the admitted prefix; ``shed`` mirrors the host-side SLO-shed tally
    onto the device ledger. Traced scalars: one compiled step serves
    every occupancy at this width, and occupancy == w is bit-identical
    to the closed-loop path (tests/test_dintserve.py).

    ``counters`` (monitor.Counters | None): the dintmon counter plane —
    txn outcomes from c1's completing stats, S/X arbitration won-vs-lost
    (held-slot rejects split from intra-batch losses), install/log
    counts, ring high-water, backend dispatch. When threaded the updated
    Counters is appended to the return tuple; None (default) leaves the
    jaxpr untouched.

    ``ring``/``tcfg`` (monitor.txnevents): the dinttrace flight-recorder
    plane — lock verdicts, installs, and outcome classifications of the
    deterministically sampled txn-id subset land in the per-device event
    ring with one scatter-add per step. The updated TxnRing is appended
    AFTER the Counters leaf; None (default) adds nothing to the jaxpr."""
    m1 = 2 * n_accounts + 1
    sent = m1 - 1
    oob = m1
    h = db.lock_slots
    t = db.step
    with waves.part("smallbank_dense", "sb_addr"):
        kgen, kamt = jax.random.split(key)

    # ---- wave 1: new cohort lock + fused read + compute -------------------
    if gen_new:
        skew = {"mix": mix}
        if hot_frac is not None:
            skew["hot_frac"] = hot_frac
        if hot_prob is not None:
            skew["hot_prob"] = hot_prob
        with waves.scope("smallbank_dense", "gen"):
            ttype, a1, a2 = gen_cohort(kgen, w, n_accounts, **skew)
            l_op, l_tb, l_ac = _lock_slots(ttype, a1, a2)  # [w, L]
    else:
        with waves.part("smallbank_dense", "sb_addr"):
            ttype = jnp.zeros((w,), I32)
            l_op = jnp.zeros((w, L), I32)
            l_tb = jnp.zeros((w, L), I32)
            l_ac = jnp.zeros((w, L), I32)
    with waves.part("smallbank_dense", "sb_addr"):
        ts_amt = jax.random.randint(kamt, (w,), -TS_AMT_MAX, TS_AMT_MAX + 1,
                                    dtype=I32)

    if occupancy is not None:
        # serving-plane occupancy mask: the cohort generates full-width
        # (RNG stream identical to the closed-loop path) and lanes past
        # the admitted occupancy have their lock slots erased before
        # arbitration — a padded lane requests nothing, computes nothing,
        # installs nothing
        with waves.scope("smallbank_dense", "serve"):
            occ = jnp.asarray(occupancy, I32)
            lane_ok = jnp.arange(w, dtype=I32) < occ
            l_op = jnp.where(lane_ok[:, None], l_op, 0)

    with waves.part("smallbank_dense", "sb_addr"):
        active = l_op != 0
        rows = jnp.where(active, l_tb * n_accounts + l_ac, sent)  # [w, L]
        flat_rows = rows.reshape(-1)
        slot = _slot_of(flat_rows, m1, h)                         # [wL]
        is_x_lane = (l_op == Op.ACQ_X_READ).reshape(-1)
        is_s_lane = (l_op == Op.ACQ_S_READ).reshape(-1)
        lane = jnp.arange(w * L, dtype=I32)

        # dintcache partition: a lane is hot iff its account sits in the
        # mirrored prefix; mirror index = tbl*hot_n + acc. Stamps share the
        # same mapping in the exact slot regime (slot == row).
        hn = db.hot_n
        stamp_hot = use_hotset and db.hot_x is not None
        if use_hotset:
            hot_lane = (active & (l_ac < hn)).reshape(-1)
            midx = jnp.where(hot_lane, (l_tb * hn + l_ac).reshape(-1), -1)

    with waves.scope("smallbank_dense", "lock"):
        with waves.part("smallbank_dense", "lock_arb"):
            first_x = jnp.full((h,), BIG, I32).at[
                jnp.where(is_x_lane, slot, h)].min(lane, mode="drop")
            first_s = jnp.full((h,), BIG, I32).at[
                jnp.where(is_s_lane, slot, h)].min(lane, mode="drop")
        # held = stamped by the previous step's cohort (released implicitly
        # one step later; acquire-before-release semantics preserved)
        with waves.part("smallbank_dense", "lock_held_read"):
            if stamp_hot:
                held_x = hotset.hot_gather(db.x_step, db.hot_x, slot, midx,
                                           1) == t - 1
                held_s = hotset.hot_gather(db.s_step, db.hot_s, slot, midx,
                                           1) == t - 1
            else:
                held_x = db.x_step[slot] == t - 1
                held_s = db.s_step[slot] == t - 1
            slot_free = ~held_x & ~held_s
        with waves.part("smallbank_dense", "lock_grant"):
            x_wins = (first_x[slot] < first_s[slot]) & slot_free
            grant_x = is_x_lane & x_wins & (first_x[slot] == lane)
            grant_s = is_s_lane & ~held_x & ~x_wins
            # one writer per slot: the first S lane stamps for all sharers
            s_writer = grant_s & (first_s[slot] == lane)
        with waves.part("smallbank_dense", "lock_stamp"):
            x_step = db.x_step.at[jnp.where(grant_x, slot, h)].set(
                t, mode="drop", unique_indices=True)
            s_step = db.s_step.at[
                jnp.where(s_writer, slot, h)].set(
                t, mode="drop", unique_indices=True)
            hot_x, hot_s = db.hot_x, db.hot_s
            if stamp_hot:
                # stamp write-through: the grant masks are one-writer-per-
                # slot, so their hot subsets are one-writer-per-mirror-index
                hot_x = hot_x.at[jnp.where(grant_x & (midx >= 0), midx,
                                           2 * hn)].set(t, mode="drop",
                                                        unique_indices=True)
                hot_s = hot_s.at[jnp.where(s_writer & (midx >= 0), midx,
                                           2 * hn)].set(t, mode="drop",
                                                        unique_indices=True)

        with waves.part("smallbank_dense", "lock_grant"):
            granted = (grant_x | grant_s).reshape(w, L)
            lock_rejected = (active & ~granted).any(axis=1)
            alive = ~lock_rejected & (l_op[:, 0] != 0)

    # fused reads from the pre-install table: rows c1 installs below were
    # X-stamped by c1, so this cohort never granted (or consumed) them
    with waves.scope("smallbank_dense", "read"):
        if use_hotset:
            raw_bal = hotset.hot_gather(db.bal, db.hot_bal, flat_rows, midx,
                                        1)
        else:
            raw_bal = db.bal[flat_rows]
        bal = jnp.where(granted, raw_bal.astype(I32).reshape(w, L), 0)

    with waves.scope("smallbank_dense", "compute"):
        nw, do, logic_abort, commit, committed = compute_phase(
            ttype, bal, alive, ts_amt)
        do_write = do & commit[:, None] & active
        bal_delta = jnp.sum(jnp.where(do_write, nw - bal, 0), dtype=I32)

    with waves.part("smallbank_dense", "sb_ctx"):
        new_ctx = BankCtx(
            rows=rows, do_write=do_write, nw=nw, tbl=l_tb, acc=l_ac,
            attempted=(occ if occupancy is not None
                       else jnp.asarray(w if gen_new else 0, I32)),
            committed=committed.sum(dtype=I32),
            ab_lock=(lock_rejected & (l_op[:, 0] != 0)).sum(dtype=I32),
            ab_logic=logic_abort.sum(dtype=I32),
            magic_bad=jnp.asarray(0, I32),
            bal_delta=bal_delta)

    # ---- wave 2 of c1: install + log x3 (locks expire by stamp) -----------
    # MACHINE-CHECKED (dintlint protocol pass): c1.do_write descends from
    # the S/X grants (lock-dominates-write), and the x_step/s_step writes
    # stamp the step scalar — the expiring-lock witness that discharges
    # abort-implies-unlock for this engine's release-free design.
    with waves.scope("smallbank_dense", "install"):
        dwf = c1.do_write.reshape(-1)
        wrows = jnp.where(dwf, c1.rows.reshape(-1), oob)       # [wL]
        newbal = c1.nw.reshape(-1)
        if use_hotset:
            # partitioned install: the full table AND the hot mirror take
            # the write (a double 1-D unique-index scatter) — the
            # write-through that keeps mirror == table prefix an
            # invariant, not a protocol
            w_acc = c1.acc.reshape(-1)
            w_midx = jnp.where(dwf & (w_acc < hn),
                               c1.tbl.reshape(-1) * hn + w_acc, -1)
            bal_new, hot_bal = hotset.hot_scatter(
                db.bal, db.hot_bal, c1.rows.reshape(-1), w_midx, dwf,
                newbal.astype(U32), 1)
        else:
            hot_bal = db.hot_bal
            # as many lanes as it takes for the compiler to sort the
            # scatter's indices (ops/compact.py), the added ones dropped
            # as the dead ones are
            lanes = compact.sorted_scatter_lanes(db.bal.shape[0],
                                                 wrows.shape[0])
            bal_new = db.bal.at[compact.filled(wrows, lanes, oob)].set(
                compact.filled(newbal.astype(U32), lanes, 0),
                mode="drop", unique_indices=True)

    with waves.scope("smallbank_dense", "log_append"):
        with waves.part("smallbank_dense", "log_build"):
            newval = jnp.zeros((wrows.shape[0], VW), U32)
            newval = newval.at[:, 0].set(newbal.astype(U32))
            newval = newval.at[:, 1].set(jnp.where(dwf, U32(MAGIC),
                                                   U32(0)))
            zero = jnp.zeros_like(newbal, U32)
            # log ver = step index: monotonic per row (one X-writer
            # per row per step), all recovery's max-ver-per-row rule
            # needs
            stepv = jnp.broadcast_to(t, newbal.shape)
        logs = logring.append_rep(db.log, dwf, c1.tbl.reshape(-1),
                                  jnp.zeros_like(newbal), zero,
                                  c1.acc.reshape(-1).astype(U32),
                                  stepv, newval)

    with waves.part("smallbank_dense", "sb_ctx"):
        db = db.replace(bal=bal_new, x_step=x_step, s_step=s_step,
                        step=t + 1, log=logs, hot_bal=hot_bal,
                        hot_x=hot_x, hot_s=hot_s)
    with waves.part("smallbank_dense", "stats"):
        stats = _stats_of(c1)
    extra = ()
    if ring is not None:
        # dinttrace: this step's candidate events — lock verdicts of the
        # NEW cohort (txn id = gen_step*w + lane, stable across waves),
        # its outcome classification, and c1's landing installs — in ONE
        # sampled scatter-add (monitor/txnevents.emit)
        with waves.scope("smallbank_dense", "trace"):
            tu = jnp.asarray(t).astype(U32)
            lane_w = jnp.arange(w, dtype=U32)
            txn_new = tu * U32(w) + lane_w
            txn_c1 = (tu - U32(1)) * U32(w) + lane_w
            grant_l = (grant_x | grant_s)
            held_l = held_x | held_s
            lock_aux = (jnp.where(grant_l, txe.LOCK_GRANTED, 0)
                        | jnp.where(held_l, txe.LOCK_HELD, 0))
            ab_lock_m = lock_rejected & (l_op[:, 0] != 0)
            out_mask = committed | ab_lock_m | logic_abort
            cause = jnp.where(
                ab_lock_m, txe.CAUSE_LOCK,
                jnp.where(logic_abort, txe.CAUSE_LOGIC, txe.CAUSE_COMMIT))
            groups = (
                txe.ev(active.reshape(-1), jnp.repeat(txn_new, L),
                       txe.EV_LOCK,
                       waves.full_name("smallbank_dense", "lock"),
                       aux=lock_aux, step=tu),
                txe.ev(out_mask, txn_new, txe.EV_OUTCOME,
                       waves.full_name("smallbank_dense", "compute"),
                       aux=cause, step=tu),
                txe.ev(dwf, jnp.repeat(txn_c1, L), txe.EV_INSTALL,
                       waves.full_name("smallbank_dense", "install"),
                       step=tu),
            )
            ring, counters = txe.emit(ring, tcfg, groups, counters)
        extra = (ring,)
    if counters is not None:
        with waves.part("smallbank_dense", "monitor"):
            act_l = active.reshape(-1)
            grant_l = granted.reshape(-1)
            held_l = held_x | held_s            # [wL] slot stamped last step
            rej_l = act_l & ~grant_l
            hot_ctrs = {}
            if use_hotset:
                # partition accounting: every hot-partitioned gather serves
                # (midx >= 0) lanes from the mirror and the rest from the
                # full tables
                n_g = 1 + (2 if stamp_hot else 0)
                hits = (midx >= 0).sum(dtype=I32)
                hot_ctrs = {
                    mon.CTR_HOT_HITS: n_g * hits,
                    mon.CTR_HOT_COLD_ROWS: n_g * (w * L) - n_g * hits,
                    mon.CTR_HOT_REFRESH_BYTES: 0,
                }
            serve_ctrs = {}
            if occupancy is not None:
                serve_ctrs = {
                    mon.CTR_SERVE_OCC_LANES: occ,
                    mon.CTR_SERVE_PAD_LANES: jnp.asarray(w, I32) - occ,
                    mon.CTR_SERVE_SHED_LANES:
                        jnp.asarray(0 if shed is None else shed, I32),
                }
            counters = mon.bump(counters, {
                **hot_ctrs,
                **serve_ctrs,
                mon.CTR_STEPS: 1,
                mon.CTR_TXN_ATTEMPTED: c1.attempted,
                mon.CTR_TXN_COMMITTED: c1.committed,
                mon.CTR_AB_LOCK: c1.ab_lock,
                mon.CTR_AB_LOGIC: c1.ab_logic,
                mon.CTR_MAGIC_BAD: c1.magic_bad,
                mon.CTR_LOCK_REQUESTS: act_l.sum(dtype=I32),
                mon.CTR_LOCK_GRANTED: grant_l.sum(dtype=I32),
                mon.CTR_LOCK_REJECTED: rej_l.sum(dtype=I32),
                mon.CTR_LOCK_REJECT_HELD: (rej_l & held_l).sum(dtype=I32),
                mon.CTR_LOCK_REJECT_ARB: (rej_l & ~held_l).sum(dtype=I32),
                mon.CTR_INSTALL_WRITES: dwf.sum(dtype=I32),
                mon.CTR_LOG_APPENDS: dwf.sum(dtype=I32),
                mon.CTR_DISPATCH_XLA: 1,
            })
            counters = mon.gauge_max(
                counters, {mon.CTR_RING_HWM: logs.head.max()})
        return (db, new_ctx, stats, counters) + extra
    return (db, new_ctx, stats) + extra


@memoize_builder
def build_pipelined_runner(n_accounts: int, w: int = 8192,
                           cohorts_per_block: int = 8, hot_frac=None,
                           hot_prob=None, mix=None, use_pallas=None,
                           use_hotset=None, use_fused=None,
                           monitor: bool = False, trace=None,
                           trace_rate=None, trace_cap=None,
                           serve: bool = False):
    """jit(scan(pipe_step)) over carry (db, c1). Returns (run, init, drain):
      run(carry, key) -> (carry', stats [cohorts_per_block, N_STATS])
      init(db)        -> carry with one bootstrap cohort in flight
      drain(carry)    -> (db, stats [1, N_STATS]) flushing the pipeline

    ``use_hotset``: None = honor DINT_USE_HOTSET env. Serves the step's
    random gathers through the dintcache hot/cold partition; the hot set
    defaults to the WORKLOAD's hot set (``hot_frac``, else the SmallBank
    90%/4% skew constant) so the mirror covers exactly the keys the skew
    concentrates on. init() attaches the mirror to a db that lacks one.

    ``monitor``: thread the dintmon counter plane — the carry grows a
    trailing monitor.Counters leaf and drain returns (db, stats,
    counters); off (default) = contract and jaxpr unchanged.

    ``trace``/``trace_rate``/``trace_cap``: thread the dinttrace event
    ring (None = honor DINT_TRACE / DINT_TRACE_RATE). The carry grows a
    TxnRing leaf BEFORE the Counters leaf (counters stay carry[-1]); the
    ring is zeroed at each block/drain entry so every drained window is
    self-contained, and `init.trace_cfg` exposes the resolved TraceCfg
    (None when off) for the host-side drain. Default capacity is
    lossless for a full block: candidate lanes/step x cohorts_per_block.

    ``serve``: the dintserve variable-occupancy mode — run's signature
    becomes ``run(carry, key, occ, shed)`` with occ/shed i32
    [cohorts_per_block] arrays scanned alongside the step keys
    (pipe_step's occupancy/shed). Carry layout, init, and drain are
    unchanged.
    """
    from ..clients import workloads as wl
    refuse_kernel_flags(use_pallas, use_fused)
    use_hotset = hotset.resolve_use_hotset(use_hotset)
    hot_n = 0
    if use_hotset:
        frac = wl.SB_HOT_FRAC if hot_frac is None else float(hot_frac)
        hot_n = max(1, min(int(n_accounts * frac), n_accounts))
    kw = dict(w=w, n_accounts=n_accounts, use_hotset=use_hotset)
    kw_gen = dict(kw, hot_frac=hot_frac, hot_prob=hot_prob, mix=mix)
    trace_on = txe.trace_enabled(trace)
    tcfg = None
    if trace_on:
        n_step = w * (2 * L + 1)    # lock wL + outcome w + install wL
        cap = int(trace_cap) if trace_cap is not None \
            else n_step * cohorts_per_block
        tcfg = txe.TraceCfg(rate=txe.trace_rate(trace_rate), cap=cap,
                            wave=waves.full_name("smallbank_dense",
                                                 "trace"))

    def step_mon(db, c1, key, cnt, ring, **skw):
        out = pipe_step(db, c1, key, counters=cnt, ring=ring, tcfg=tcfg,
                        **skw)
        i = 3
        cnt = out[i] if cnt is not None else None
        i += 1 if monitor else 0
        ring = out[i] if ring is not None else None
        return out[0], out[1], out[2], cnt, ring

    def scan_fn(carry, x):
        key, occ, shed = x if serve else (x, None, None)
        db, c1 = carry[:2]
        ring = carry[2] if trace_on else None
        cnt = carry[-1] if monitor else None
        db, new_ctx, stats, cnt, ring = step_mon(db, c1, key, cnt, ring,
                                                 occupancy=occ, shed=shed,
                                                 **kw_gen)
        out = ((db, new_ctx) + ((ring,) if trace_on else ())
               + ((cnt,) if monitor else ()))
        return out, stats

    def _pre(carry, key):
        with waves.part("smallbank_dense", "block_pre"):
            if trace_on:
                # each block is one drain window: self-contained ring
                carry = carry[:2] + (txe.reset(carry[2]),) + carry[3:]
            return carry, jax.random.split(key, cohorts_per_block)

    if serve:
        def block(carry, key, occ, shed):
            carry, keys = _pre(carry, key)
            return jax.lax.scan(scan_fn, carry, (keys, occ, shed))
    else:
        def block(carry, key):
            carry, keys = _pre(carry, key)
            return jax.lax.scan(scan_fn, carry, keys)

    def init(db):
        if use_hotset and db.hot_n == 0:
            db = attach_hotset(db, hot_n)
        base = (db, empty_ctx(w))
        return (base + ((txe.create_ring(tcfg.cap),) if trace_on else ())
                + ((mon.create(),) if monitor else ()))

    @functools.partial(jax.jit, donate_argnums=0)
    def drain(carry):
        db, c1 = carry[:2]
        ring = txe.reset(carry[2]) if trace_on else None
        cnt = carry[-1] if monitor else None
        db, _, s1, cnt, ring = step_mon(db, c1, jax.random.PRNGKey(0),
                                        cnt, ring, gen_new=False, **kw)
        return ((db, jnp.stack([s1]))
                + ((ring,) if trace_on else ())
                + ((cnt,) if monitor else ()))

    init.trace_cfg = tcfg
    return jax.jit(block, donate_argnums=0), init, drain
