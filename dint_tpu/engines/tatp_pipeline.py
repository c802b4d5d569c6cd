"""Device-fused TATP transaction pipeline: whole txns in one jitted step.

The reference's client-side coordinator (tatp/caladan/client_ebpf_shard.cc)
drives each transaction through 5+ network RTTs against 3 replicated shard
servers (read+lock -> validate -> CommitLog x3 -> CommitBck x2 -> CommitPrim,
SURVEY.md §3.3). The host-side port of that coordinator
(clients/tatp_client.py) keeps the same wave structure but pays a
host<->device round trip per wave, which dominates the device work.

This module is the TPU-first re-design: the *entire* cohort pipeline —
workload generation (NURand ids, txn mix), per-shard routing, all three
certification waves, replication fan-out, and abort accounting — runs inside
one jitted function over the 3 shard replicas (vmapped `tatp.step`), and a
`lax.scan` runs many cohorts per dispatch. Host traffic per scan block is one
RNG key in and one small stats matrix out.

The 3 "servers" are a stacked leading axis on the Shard pytree. A lane's
op differs per shard (NOP unless routed there; PRIM at the owner vs BCK at
backups), which is exactly the reference's per-shard message batches
(client_ebpf_shard.cc:636-641) — expressed as a [3, R] op array instead of
3 socket fan-outs.

Wave structure per cohort (3 vmapped steps total):
  wave 1  [R=4w lanes]  OCC_READ read-set + OCC_LOCK write-set at owners
  wave 2  [R lanes]     validate: re-read read-set of surviving RW txns
  wave 3  [4w lanes]    log block (COMMIT/DELETE_LOG on all shards) +
                        role block (PRIM at owner / BCK at backups / ABORT
                        of granted locks of dead txns at owner)

Abort semantics mirror clients/tatp_client.py lane for lane (which itself
mirrors client_ebpf_shard.cc:608-900); stats categories are disjoint:
ab_lock (write-set lock rejected), ab_missing (required row absent /
insert-exists), ab_validate (read-set version changed).
"""
from __future__ import annotations

import functools

import flax.struct
import jax
import jax.numpy as jnp

from ..clients import workloads as wl
from ._memo import memoize_builder
from ..monitor import counters as mon
from ..monitor import waves
from . import tatp
from .types import Batch, Op, PAD_KEY, Reply

I32 = jnp.int32
U32 = jnp.uint32

N_SHARDS = 3
K = 4                  # wave-1 lanes per txn
MAGIC = 0x7A79         # parity with clients/tatp_client.py

# stats vector layout
STAT_ATTEMPTED = 0
STAT_COMMITTED = 1
STAT_AB_LOCK = 2
STAT_AB_MISSING = 3
STAT_AB_VALIDATE = 4
STAT_MAGIC_BAD = 5
N_STATS = 6


def stack_shards(shards) -> tatp.Shard:
    """[Shard] * 3 -> one Shard pytree with leading [3] device axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *shards)


def _broadcast_batch(op_s, table, key_lo, val, ver):
    """Per-shard op array [S, R] + shared lane fields [R] -> stacked Batch."""
    s = op_s.shape[0]

    def bc(x):
        return jnp.broadcast_to(x[None], (s,) + x.shape)

    return Batch(op=op_s, table=bc(table),
                 key_hi=bc(jnp.zeros_like(key_lo)), key_lo=bc(key_lo),
                 val=bc(val), ver=bc(ver))


def _merge(owner, stacked):
    """Pick each lane's reply from its owner shard: [S, R...] -> [R...]."""
    r = owner.shape[0]
    return stacked[owner, jnp.arange(r)]


def gen_cohort(key, w: int, n_sub: int, mix=None):
    """On-device workload generation (tatp/caladan/tatp.h:40-63).

    One `random.bits` draw feeds every field via modular reduction — the
    same arithmetic the reference's generators use (`rand() % n`,
    tatp/caladan/tatp.h:40-43); the txn type comes from a searchsorted
    over the cumulative mix, which is exactly the reference's
    proportion-filled workgen array (store/caladan/client_caladan.cc:56-66)
    in closed form. 4 threefry splits + a weighted `choice` measured
    ~2.3 ms per 8192-txn step on v5e — 40% of the whole fused step — and
    this is ~6x cheaper.

    Returns (ttype [w], lane ops/tbl/keys [w, K], write-slot arrays [w, 2]).
    """
    bits = jax.random.bits(key, (w, 4), U32)
    thresh = jnp.asarray(wl.mix_thresholds(
        wl.TATP_MIX if mix is None else mix))
    ttype = jnp.minimum(
        jnp.searchsorted(thresh, bits[:, 0], side="right"), 6).astype(I32)
    # NURand: ((x | y) % n) + 1
    x = (bits[:, 1] % U32(wl.TATP_A + 1)).astype(I32)
    y = (bits[:, 2] % U32(n_sub)).astype(I32) + 1
    s_id = ((x | y) % n_sub) + 1
    kx = bits[:, 3]
    xtype = (kx % 4 + 1).astype(I32)          # ai_type / sf_type 1..4
    stime = ((kx >> 2) % 3).astype(I32) * 8   # 0 / 8 / 16

    sf_idx = s_id * 4 + (xtype - 1)
    ai_idx = sf_idx
    cfk = tatp.cf_key(s_id, xtype, stime)

    T = tatp
    t = ttype
    ops = jnp.zeros((w, K), I32)
    tbl = jnp.zeros((w, K), I32)
    kk = jnp.zeros((w, K), I32)

    def put(ops, tbl, kk, mask, lane, op, tb, keyv):
        ops = ops.at[:, lane].set(jnp.where(mask, op, ops[:, lane]))
        tbl = tbl.at[:, lane].set(jnp.where(mask, tb, tbl[:, lane]))
        kk = kk.at[:, lane].set(jnp.where(mask, keyv, kk[:, lane]))
        return ops, tbl, kk

    m = t == wl.TATP_GET_SUBSCRIBER
    ops, tbl, kk = put(ops, tbl, kk, m, 0, Op.OCC_READ, T.SUBSCRIBER, s_id)
    m = t == wl.TATP_GET_ACCESS
    ops, tbl, kk = put(ops, tbl, kk, m, 0, Op.OCC_READ, T.ACCESS_INFO, ai_idx)
    m = t == wl.TATP_GET_NEW_DEST
    ops, tbl, kk = put(ops, tbl, kk, m, 0, Op.OCC_READ, T.SPECIAL_FACILITY, sf_idx)
    ops, tbl, kk = put(ops, tbl, kk, m, 1, Op.OCC_READ, T.CALL_FORWARDING, cfk)
    m = t == wl.TATP_UPDATE_SUBSCRIBER
    ops, tbl, kk = put(ops, tbl, kk, m, 0, Op.OCC_READ, T.SUBSCRIBER, s_id)
    ops, tbl, kk = put(ops, tbl, kk, m, 1, Op.OCC_READ, T.SPECIAL_FACILITY, sf_idx)
    ops, tbl, kk = put(ops, tbl, kk, m, 2, Op.OCC_LOCK, T.SUBSCRIBER, s_id)
    ops, tbl, kk = put(ops, tbl, kk, m, 3, Op.OCC_LOCK, T.SPECIAL_FACILITY, sf_idx)
    m = t == wl.TATP_UPDATE_LOCATION
    ops, tbl, kk = put(ops, tbl, kk, m, 0, Op.OCC_READ, T.SEC_SUBSCRIBER, s_id)
    ops, tbl, kk = put(ops, tbl, kk, m, 1, Op.OCC_READ, T.SUBSCRIBER, s_id)
    ops, tbl, kk = put(ops, tbl, kk, m, 2, Op.OCC_LOCK, T.SUBSCRIBER, s_id)
    m = t == wl.TATP_INSERT_CF
    ops, tbl, kk = put(ops, tbl, kk, m, 0, Op.OCC_READ, T.SPECIAL_FACILITY, sf_idx)
    ops, tbl, kk = put(ops, tbl, kk, m, 1, Op.OCC_READ, T.CALL_FORWARDING, cfk)
    ops, tbl, kk = put(ops, tbl, kk, m, 2, Op.OCC_LOCK, T.CALL_FORWARDING, cfk)
    m = t == wl.TATP_DELETE_CF
    ops, tbl, kk = put(ops, tbl, kk, m, 0, Op.OCC_READ, T.CALL_FORWARDING, cfk)
    ops, tbl, kk = put(ops, tbl, kk, m, 1, Op.OCC_LOCK, T.CALL_FORWARDING, cfk)

    # write slots (== lock lanes): (active, lane_idx, table, key, kind)
    # kind: 0 = commit (dense install), 1 = insert (CF), 2 = delete (CF)
    is_us = t == wl.TATP_UPDATE_SUBSCRIBER
    is_ul = t == wl.TATP_UPDATE_LOCATION
    is_ic = t == wl.TATP_INSERT_CF
    is_dc = t == wl.TATP_DELETE_CF
    ws_active = jnp.stack([is_us | is_ul | is_ic | is_dc, is_us], axis=1)
    ws_lane = jnp.stack([jnp.where(is_dc, 1, 2), jnp.full((w,), 3, I32)], axis=1)
    ws_tbl = jnp.stack([
        jnp.where(is_us | is_ul, T.SUBSCRIBER, T.CALL_FORWARDING),
        jnp.full((w,), T.SPECIAL_FACILITY, I32)], axis=1)
    ws_key = jnp.stack([
        jnp.where(is_us | is_ul, s_id, cfk), sf_idx], axis=1)
    ws_kind = jnp.stack([
        jnp.where(is_ic, 1, jnp.where(is_dc, 2, 0)),
        jnp.zeros((w,), I32)], axis=1)
    return ttype, ops, tbl, kk, (ws_active, ws_lane, ws_tbl, ws_key, ws_kind)


def cohort_step(stacked: tatp.Shard, key, *, w: int, n_sub: int,
                val_words: int, validate: bool = True):
    """One full cohort of w txns against the 3 stacked replicas.

    ``validate`` (static) keeps the reference protocol's wave-2 read-set
    re-read (client_ebpf_shard.cc:688-768). In this fused pipeline it is
    *protocol-parity ballast*: cohorts serialize on the device, no commit can
    land between a txn's read and its validation, so ab_validate is
    structurally 0 — the wave is kept (and benchmarked) to pay the same
    per-txn work the reference client pays. ``validate=False`` is the
    TPU-first fast path: batch lock certification subsumes validation, a
    design win the reference cannot express.

    Returns (stacked', stats [N_STATS] i32)."""
    step_v = jax.vmap(tatp.step)
    kg, kv = jax.random.split(key)
    ttype, ops, tbl, kk, ws = gen_cohort(kg, w, n_sub)
    ws_active, ws_lane, ws_tbl, ws_key, ws_kind = ws
    r = w * K

    lane_op = ops.reshape(r)
    lane_tbl = tbl.reshape(r)
    lane_key = kk.reshape(r).astype(U32)
    used = lane_op != Op.NOP
    # NOP lanes get the pad key so they never join a real key's segment
    lane_key = jnp.where(used, lane_key, U32(PAD_KEY & 0xFFFFFFFF))
    owner = (kk.reshape(r) % N_SHARDS).astype(I32)
    sid = jnp.arange(N_SHARDS, dtype=I32)

    zval = jnp.zeros((r, val_words), U32)
    zver = jnp.zeros((r,), U32)

    # ---- wave 1: read + lock at owners ------------------------------------
    op_s = jnp.where((owner[None] == sid[:, None]) & used[None],
                     lane_op[None], Op.NOP)
    stacked, rep1 = step_v(stacked, _broadcast_batch(op_s, lane_tbl, lane_key,
                                                     zval, zver))
    rt1 = _merge(owner, rep1.rtype).reshape(w, K)
    rv1 = _merge(owner, rep1.val)
    rver1 = _merge(owner, rep1.ver).reshape(w, K)

    is_val_lane = rt1.reshape(r) == Reply.VAL
    magic_bad = jnp.sum(is_val_lane & (rv1[:, 1] != MAGIC), dtype=I32)

    # ---- outcome of wave 1 (generated cohorts always have a lane-0 op, so
    # classify_wave1's NOP guard is vacuous here) ---------------------------
    is_ro, rw, granted, lock_rejected, missing = classify_wave1(
        ttype, rt1, ops, ws_active, ws_lane)

    ab_lock = rw & lock_rejected
    ab_missing = rw & ~lock_rejected & missing
    alive = rw & ~lock_rejected & ~missing

    # ---- wave 2: validate read-set of surviving RW txns --------------------
    if validate:
        is_read_lane = (ops == Op.OCC_READ) & alive[:, None]
        v_op = jnp.where(is_read_lane.reshape(r), Op.OCC_READ, Op.NOP)
        v_used = v_op != Op.NOP
        v_key = jnp.where(v_used, kk.reshape(r).astype(U32),
                          U32(PAD_KEY & 0xFFFFFFFF))
        op_s2 = jnp.where((owner[None] == sid[:, None]) & v_used[None],
                          v_op[None], Op.NOP)
        stacked, rep2 = step_v(stacked, _broadcast_batch(op_s2, lane_tbl,
                                                         v_key, zval, zver))
        vrt = _merge(owner, rep2.rtype).reshape(w, K)
        vver = _merge(owner, rep2.ver).reshape(w, K)
        bad_lane = is_read_lane & (
            (vver != rver1) | ((vrt != Reply.VAL) & (rt1 == Reply.VAL)))
        changed = bad_lane.any(axis=1)
    else:
        changed = jnp.zeros((w,), bool)
    ab_validate = alive & changed
    alive = alive & ~changed

    # ---- wave 3: log block + role block (prim/bck/abort) -------------------
    # lanes: [log ws0 | log ws1 | role ws0 | role ws1], each w wide
    w_owner = (ws_key % N_SHARDS).astype(I32)              # [w, 2]
    do_write = ws_active & alive[:, None]
    newval = jnp.zeros((w, 2, val_words), U32)
    payload = jax.random.randint(kv, (w, 2), 0, 1 << 16, dtype=I32)
    newval = newval.at[:, :, 0].set(payload.astype(U32))
    newval = newval.at[:, :, 1].set(jnp.where(do_write, U32(MAGIC), U32(0)))

    log_op = jnp.where(do_write,
                       jnp.where(ws_kind == 2, Op.DELETE_LOG, Op.COMMIT_LOG),
                       Op.NOP)                              # [w, 2], all shards
    prim_op = jnp.select([ws_kind == 1, ws_kind == 2],
                         [Op.INSERT_PRIM, Op.DELETE_PRIM], Op.COMMIT_PRIM)
    bck_op = jnp.select([ws_kind == 1, ws_kind == 2],
                        [Op.INSERT_BCK, Op.DELETE_BCK], Op.COMMIT_BCK)
    # role op per shard s: owner -> prim; others -> bck; dead+granted -> ABORT
    dead_abort = granted & ~alive[:, None]
    role_s = jnp.where(
        do_write[None], jnp.where(w_owner[None] == sid[:, None, None],
                                  prim_op[None], bck_op[None]),
        jnp.where(dead_abort[None] & (w_owner[None] == sid[:, None, None]),
                  Op.ABORT, Op.NOP))                        # [S, w, 2]

    c_used = do_write | dead_abort
    c_key = jnp.where(c_used, ws_key.astype(U32), U32(PAD_KEY & 0xFFFFFFFF))
    lane3_key = jnp.concatenate([c_key[:, 0], c_key[:, 1],
                                 c_key[:, 0], c_key[:, 1]])
    lane3_tbl = jnp.concatenate([ws_tbl[:, 0], ws_tbl[:, 1],
                                 ws_tbl[:, 0], ws_tbl[:, 1]])
    lane3_val = jnp.concatenate([newval[:, 0], newval[:, 1],
                                 newval[:, 0], newval[:, 1]])
    op3_s = jnp.concatenate([
        jnp.broadcast_to(log_op[:, 0][None], (N_SHARDS, w)),
        jnp.broadcast_to(log_op[:, 1][None], (N_SHARDS, w)),
        role_s[:, :, 0], role_s[:, :, 1]], axis=1)
    zver3 = jnp.zeros((w * 4,), U32)
    stacked, _ = step_v(stacked, _broadcast_batch(
        op3_s, lane3_tbl, lane3_key, lane3_val, zver3))

    committed = (is_ro & ~missing) | alive
    stats = jnp.stack([
        jnp.asarray(w, I32),
        committed.sum(dtype=I32),
        ab_lock.sum(dtype=I32),
        (ab_missing | (is_ro & missing)).sum(dtype=I32),
        ab_validate.sum(dtype=I32),
        magic_bad,
    ])
    return stacked, stats


# --------------------------------------------------------------------------
# Cross-cohort software pipeline: REAL concurrency between transactions.
#
# The serialized cohort_step above runs read+lock -> validate -> commit to
# completion per cohort, so no commit can ever land between a txn's read and
# its validation (ab_validate is structurally 0 — the honest caveat in its
# docstring). This pipeline overlaps cohort lifetimes exactly like the
# reference's thousands of concurrently in-flight client txns
# (tatp/caladan/client_ebpf_shard.cc:1589-1613): device step t executes, in
# ONE combined batch,
#
#   wave 1 of cohort t     (read + lock at owners)
#   wave 2 of cohort t-1   (validate re-reads)
#   wave 3 of cohort t-2   (log x3 / prim / bck / abort)
#
# The engine's per-row phase order (commits install and release BEFORE
# reads, lock acquires LAST — engines/tatp._dense_step) gives the reference
# interleaving: cohort t-2's commits are visible to cohort t-1's validation
# re-reads, so a version bumped between read (step t-1) and validate
# (step t) aborts the txn — ab_validate is live and responds to contention.
# Locks held by in-flight cohorts likewise reject younger cohorts' lock
# attempts (no-wait, first-wins), raising ab_lock under skew. Validation is
# version-compare only, exactly the reference's verify stage
# (client_ebpf_shard.cc:765-768) — reads do not check row locks.
# --------------------------------------------------------------------------


@flax.struct.dataclass
class PipeCtx:
    """An in-flight cohort between pipeline stages (all [w]-shaped unless
    noted). Bootstrap cohorts have attempted == 0 and all-False masks, so
    they contribute NOP lanes and zero stats."""
    ops: jax.Array        # i32 [w, K] wave-1 lane ops
    tbl: jax.Array        # i32 [w, K]
    kk: jax.Array         # i32 [w, K] lane keys
    rver1: jax.Array      # u32 [w, K] versions read at wave 1
    rt1_val: jax.Array    # bool [w, K] lane replied VAL at wave 1
    granted: jax.Array    # bool [w, 2] write-slot locks granted
    alive: jax.Array      # bool [w] still commit-eligible
    ro_commit: jax.Array  # bool [w] read-only txn that succeeded at wave 1
    ws_active: jax.Array  # bool [w, 2]
    ws_tbl: jax.Array     # i32 [w, 2]
    ws_key: jax.Array     # i32 [w, 2]
    ws_kind: jax.Array    # i32 [w, 2] 0 commit / 1 insert / 2 delete
    attempted: jax.Array  # i32 scalar (w, or 0 for bootstrap)
    ab_lock: jax.Array    # i32 scalar
    ab_missing: jax.Array # i32 scalar
    ab_validate: jax.Array  # i32 scalar (set by the validate stage)
    magic_bad: jax.Array  # i32 scalar


def empty_ctx(w: int) -> PipeCtx:
    # every field materializes its OWN device buffer (via a fresh numpy
    # array): the runner donates the whole carry, and XLA rejects donating
    # an aliased buffer twice
    import numpy as np

    def z(shape, dt):
        return jnp.asarray(np.zeros(shape, dt))

    return PipeCtx(
        ops=z((w, K), np.int32), tbl=z((w, K), np.int32),
        kk=z((w, K), np.int32), rver1=z((w, K), np.uint32),
        rt1_val=z((w, K), bool), granted=z((w, 2), bool),
        alive=z((w,), bool), ro_commit=z((w,), bool),
        ws_active=z((w, 2), bool), ws_tbl=z((w, 2), np.int32),
        ws_key=z((w, 2), np.int32), ws_kind=z((w, 2), np.int32),
        attempted=z((), np.int32), ab_lock=z((), np.int32),
        ab_missing=z((), np.int32), ab_validate=z((), np.int32),
        magic_bad=z((), np.int32))


def classify_wave1(ttype, rt, ops, ws_active, ws_lane, ws_rt=None):
    """Per-txn-type wave-1 outcome rules, shared by every TATP engine.

    Given reply types rt [w, K] (VAL/NOT_EXIST for reads, GRANT/REJECT for
    locks), classifies each txn exactly like the reference coordinator
    (read-only commit on success, REJECT -> lock abort, required-row
    absence / insert-exists -> missing abort; client_ebpf_shard.cc:608-703).
    Returns (is_ro, rw, granted [w,2], lock_rejected, missing), all masked
    to lanes that exist (ops[:,0] != NOP for bootstrap/drain cohorts).

    ``ws_rt`` [w, 2]: write-slot reply types, for engines that arbitrate
    locks in write-slot space and never materialize lock replies in rt
    (engines/tatp_dense.py); defaults to gathering rt at ws_lane."""
    t = ttype
    is_ro = ((t == wl.TATP_GET_SUBSCRIBER) | (t == wl.TATP_GET_ACCESS)
             | (t == wl.TATP_GET_NEW_DEST)) & (ops[:, 0] != Op.NOP)
    rw = (ops[:, 0] != Op.NOP) & ~is_ro

    if ws_rt is None:
        ws_rt = jnp.take_along_axis(rt, ws_lane, axis=1)
    granted = ws_active & (ws_rt == Reply.GRANT)
    rejected = (ws_rt == Reply.REJECT) | (ws_rt == Reply.REJECT_SAME_KEY)
    lock_rejected = (ws_active & rejected).any(axis=1)

    missing = jnp.zeros(t.shape, bool)
    # GET_ACCESS fails on an absent ACCESS_INFO row — kNotExist returns
    # false, excluded from goodput (client_ebpf_shard.cc:583-587); by the
    # 0.625 population this fails ~37% of the time BY DESIGN (TATP spec)
    m = t == wl.TATP_GET_ACCESS
    missing |= m & (rt[:, 0] != Reply.VAL)
    # GET_NEW_DEST succeeds only when the SPECIAL_FACILITY row exists AND
    # the CALL_FORWARDING read hits (client_ebpf_shard.cc:492,549-563 —
    # kNotExist on either ends the txn unsuccessfully; the reference's
    # additional is_active/end_time predicates are over synthetic payload
    # fields this schema does not model)
    m = t == wl.TATP_GET_NEW_DEST
    missing |= m & ((rt[:, 0] != Reply.VAL) | (rt[:, 1] != Reply.VAL))
    m = (t == wl.TATP_UPDATE_SUBSCRIBER) | (t == wl.TATP_UPDATE_LOCATION)
    missing |= m & ((rt[:, 0] != Reply.VAL) | (rt[:, 1] != Reply.VAL))
    m = t == wl.TATP_INSERT_CF
    missing |= m & ((rt[:, 0] != Reply.VAL) | (rt[:, 1] == Reply.VAL))
    m = t == wl.TATP_DELETE_CF
    missing |= m & (rt[:, 0] != Reply.VAL)
    missing &= (ops[:, 0] != Op.NOP)
    return is_ro, rw, granted, lock_rejected, missing


def _wave1_lanes(ops, tbl, kk):
    """Flat wave-1 lane arrays + owner routing ([r] each, r = w*K)."""
    r = ops.shape[0] * K
    lane_op = ops.reshape(r)
    lane_tbl = tbl.reshape(r)
    used = lane_op != Op.NOP
    lane_key = jnp.where(used, kk.reshape(r).astype(U32),
                         U32(PAD_KEY & 0xFFFFFFFF))
    owner = (kk.reshape(r) % N_SHARDS).astype(I32)
    return lane_op, lane_tbl, lane_key, owner, used


def _validate_lanes(ctx: PipeCtx):
    """Wave-2 lane arrays for an in-flight cohort: re-read the read-set of
    surviving RW txns (and of nothing else)."""
    w = ctx.alive.shape[0]
    r = w * K
    is_read_lane = (ctx.ops == Op.OCC_READ) & ctx.alive[:, None]
    v_op = jnp.where(is_read_lane.reshape(r), Op.OCC_READ, Op.NOP)
    v_used = v_op != Op.NOP
    v_key = jnp.where(v_used, ctx.kk.reshape(r).astype(U32),
                      U32(PAD_KEY & 0xFFFFFFFF))
    owner = (ctx.kk.reshape(r) % N_SHARDS).astype(I32)
    return v_op, ctx.tbl.reshape(r), v_key, owner, v_used, is_read_lane


def _wave3_lanes(ctx: PipeCtx, kval, val_words: int):
    """Wave-3 lane arrays for a validated cohort (4w lanes: log ws0 | log
    ws1 | role ws0 | role ws1), identical to the serialized wave 3."""
    w = ctx.alive.shape[0]
    sid = jnp.arange(N_SHARDS, dtype=I32)
    w_owner = (ctx.ws_key % N_SHARDS).astype(I32)
    do_write = ctx.ws_active & ctx.alive[:, None]
    newval = jnp.zeros((w, 2, val_words), U32)
    payload = jax.random.randint(kval, (w, 2), 0, 1 << 16, dtype=I32)
    newval = newval.at[:, :, 0].set(payload.astype(U32))
    newval = newval.at[:, :, 1].set(jnp.where(do_write, U32(MAGIC), U32(0)))

    log_op = jnp.where(do_write,
                       jnp.where(ctx.ws_kind == 2, Op.DELETE_LOG,
                                 Op.COMMIT_LOG), Op.NOP)
    prim_op = jnp.select([ctx.ws_kind == 1, ctx.ws_kind == 2],
                         [Op.INSERT_PRIM, Op.DELETE_PRIM], Op.COMMIT_PRIM)
    bck_op = jnp.select([ctx.ws_kind == 1, ctx.ws_kind == 2],
                        [Op.INSERT_BCK, Op.DELETE_BCK], Op.COMMIT_BCK)
    dead_abort = ctx.granted & ~ctx.alive[:, None]
    role_s = jnp.where(
        do_write[None], jnp.where(w_owner[None] == sid[:, None, None],
                                  prim_op[None], bck_op[None]),
        jnp.where(dead_abort[None] & (w_owner[None] == sid[:, None, None]),
                  Op.ABORT, Op.NOP))                       # [S, w, 2]

    c_used = do_write | dead_abort
    c_key = jnp.where(c_used, ctx.ws_key.astype(U32),
                      U32(PAD_KEY & 0xFFFFFFFF))
    lane_key = jnp.concatenate([c_key[:, 0], c_key[:, 1],
                                c_key[:, 0], c_key[:, 1]])
    lane_tbl = jnp.concatenate([ctx.ws_tbl[:, 0], ctx.ws_tbl[:, 1],
                                ctx.ws_tbl[:, 0], ctx.ws_tbl[:, 1]])
    lane_val = jnp.concatenate([newval[:, 0], newval[:, 1],
                                newval[:, 0], newval[:, 1]])
    op_s = jnp.concatenate([
        jnp.broadcast_to(log_op[:, 0][None], (N_SHARDS, w)),
        jnp.broadcast_to(log_op[:, 1][None], (N_SHARDS, w)),
        role_s[:, :, 0], role_s[:, :, 1]], axis=1)
    return op_s, lane_tbl, lane_key, lane_val


def pipe_step(stacked: tatp.Shard, c1: PipeCtx, c2: PipeCtx, key, *, w: int,
              n_sub: int, val_words: int, gen_new: bool = True, mix=None,
              counters: mon.Counters | None = None):
    """One pipelined device step: wave 1 of a NEW cohort + wave 2 of c1 +
    wave 3 of c2, in a single vmapped engine step. Returns
    (stacked', new_ctx, c1', stats-of-c2) — c2 completes here.

    ``gen_new=False`` (static) feeds an empty cohort instead of generating
    one: used to drain the pipeline at end of run.

    ``counters`` (monitor.Counters | None): the dintmon counter plane;
    bumps the engine-independent parity counters (txn outcomes, lock
    grant/reject, validate lanes/failures, install/log counts — the same
    definitions as engines/tatp_dense.pipe_step, so on the parity
    workloads the two engines produce bit-identical values) and appends
    the updated Counters to the return tuple. The held-vs-arb reject
    split and the ring gauge are dense-engine observables and stay 0
    here."""
    step_v = jax.vmap(tatp.step)
    kg, kv3 = jax.random.split(key)
    r = w * K
    sid = jnp.arange(N_SHARDS, dtype=I32)

    # ---- assemble the combined batch [12w lanes] ---------------------------
    if gen_new:
        with waves.scope("tatp_pipeline", "gen"):
            ttype, ops, tbl, kk, ws = gen_cohort(kg, w, n_sub, mix=mix)
        ws_active, ws_lane, ws_tbl, ws_key, ws_kind = ws
    else:
        e = empty_ctx(w)
        ttype = jnp.zeros((w,), I32)
        ops, tbl, kk = e.ops, e.tbl, e.kk
        ws_active, ws_lane = e.ws_active, jnp.zeros((w, 2), I32)
        ws_tbl, ws_key, ws_kind = e.ws_tbl, e.ws_key, e.ws_kind
    with waves.scope("tatp_pipeline", "assemble"):
        a_op, a_tbl, a_key, a_owner, a_used = _wave1_lanes(ops, tbl, kk)
        opA_s = jnp.where((a_owner[None] == sid[:, None]) & a_used[None],
                          a_op[None], Op.NOP)

        b_op, b_tbl, b_key, b_owner, b_used, is_read_lane = \
            _validate_lanes(c1)
        opB_s = jnp.where((b_owner[None] == sid[:, None]) & b_used[None],
                          b_op[None], Op.NOP)

        opC_s, c_tbl, c_key, c_val = _wave3_lanes(c2, kv3, val_words)

        zvalAB = jnp.zeros((2 * r, val_words), U32)
        lane_tbl = jnp.concatenate([a_tbl, b_tbl, c_tbl])
        lane_key = jnp.concatenate([a_key, b_key, c_key])
        lane_val = jnp.concatenate([zvalAB, c_val])
        op_s = jnp.concatenate([opA_s, opB_s, opC_s], axis=1)
        zver = jnp.zeros((lane_key.shape[0],), U32)

    with waves.scope("tatp_pipeline", "engine_step"):
        stacked, rep = step_v(stacked, _broadcast_batch(
            op_s, lane_tbl, lane_key, lane_val, zver))

    # ---- wave-1 outcome for the new cohort --------------------------------
    with waves.scope("tatp_pipeline", "classify"):
        rtA = _merge(a_owner, rep.rtype[:, :r]).reshape(w, K)
        rvA = _merge(a_owner, rep.val[:, :r])
        rverA = _merge(a_owner, rep.ver[:, :r]).reshape(w, K)
        is_val_lane = rtA.reshape(r) == Reply.VAL
        magic_bad = jnp.sum(is_val_lane & (rvA[:, 1] != MAGIC), dtype=I32)

        is_ro, rw, granted, lock_rejected, missing = classify_wave1(
            ttype, rtA, ops, ws_active, ws_lane)

        new_ctx = PipeCtx(
            ops=ops, tbl=tbl, kk=kk, rver1=rverA,
            rt1_val=(rtA == Reply.VAL),
            granted=granted, alive=rw & ~lock_rejected & ~missing,
            ro_commit=is_ro & ~missing,
            ws_active=ws_active, ws_tbl=ws_tbl, ws_key=ws_key,
            ws_kind=ws_kind,
            attempted=jnp.asarray(w if gen_new else 0, I32),
            ab_lock=(rw & lock_rejected).sum(dtype=I32),
            ab_missing=((rw & ~lock_rejected & missing)
                        | (is_ro & missing)).sum(dtype=I32),
            ab_validate=jnp.asarray(0, I32),
            magic_bad=magic_bad)

        # ---- validate outcome for c1 --------------------------------------
        rtB = _merge(b_owner, rep.rtype[:, r:2 * r]).reshape(w, K)
        rverB = _merge(b_owner, rep.ver[:, r:2 * r]).reshape(w, K)
        bad_lane = is_read_lane & ((rverB != c1.rver1)
                                   | ((rtB != Reply.VAL) & c1.rt1_val))
        changed = bad_lane.any(axis=1)
        c1 = c1.replace(alive=c1.alive & ~changed,
                        ab_validate=(c1.alive & changed).sum(dtype=I32))

        # ---- c2 completed: emit its stats ---------------------------------
        stats = jnp.stack([
            c2.attempted,
            (c2.ro_commit | c2.alive).sum(dtype=I32),
            c2.ab_lock, c2.ab_missing, c2.ab_validate, c2.magic_bad])
    if counters is not None:
        dw2 = c2.ws_active & c2.alive[:, None]   # == _wave3_lanes do_write
        counters = mon.bump(counters, {
            mon.CTR_STEPS: 1,
            mon.CTR_TXN_ATTEMPTED: stats[STAT_ATTEMPTED],
            mon.CTR_TXN_COMMITTED: stats[STAT_COMMITTED],
            mon.CTR_AB_LOCK: c2.ab_lock,
            mon.CTR_AB_MISSING: c2.ab_missing,
            mon.CTR_AB_VALIDATE: c2.ab_validate,
            mon.CTR_MAGIC_BAD: c2.magic_bad,
            mon.CTR_LOCK_REQUESTS: ws_active.sum(dtype=I32),
            mon.CTR_LOCK_GRANTED: granted.sum(dtype=I32),
            mon.CTR_LOCK_REJECTED: (ws_active & ~granted).sum(dtype=I32),
            mon.CTR_VALIDATE_LANES: is_read_lane.sum(dtype=I32),
            mon.CTR_VALIDATE_FAILED: bad_lane.sum(dtype=I32),
            mon.CTR_INSTALL_WRITES: dw2.sum(dtype=I32),
            mon.CTR_LOG_APPENDS: dw2.sum(dtype=I32),
            mon.CTR_DISPATCH_XLA: 1,
        })
        return stacked, new_ctx, c1, stats, counters
    return stacked, new_ctx, c1, stats


@memoize_builder
def build_pipelined_runner(n_sub: int, w: int = 4096, val_words: int = 10,
                           cohorts_per_block: int = 8, mix=None,
                           monitor: bool = False):
    """jit(scan(pipe_step)) over carry (stacked, c1, c2): one dispatch runs
    `cohorts_per_block` pipelined cohorts; in-flight cohorts persist across
    blocks via the carry, so nothing is lost at block boundaries.

    Returns (run, init, drain):
      run(carry, key) -> (carry', stats [cohorts_per_block, N_STATS])
      init(stacked)   -> carry with two bootstrap (empty) cohorts in flight
      drain(carry)    -> (stacked, stats [2, N_STATS]) flushing the pipeline

    ``monitor``: thread the dintmon counter plane — the carry grows a
    trailing monitor.Counters leaf and drain returns (stacked, stats,
    counters); off (default) = contract and jaxpr unchanged.
    """
    kw = dict(w=w, n_sub=n_sub, val_words=val_words)
    kw_gen = dict(kw, mix=mix)

    def step_mon(stacked, c1, c2, key, cnt, **skw):
        out = pipe_step(stacked, c1, c2, key, counters=cnt, **skw)
        return out if cnt is not None else out + (None,)

    def scan_fn(carry, key):
        stacked, c1, c2 = carry[:3]
        cnt = carry[3] if monitor else None
        stacked, new_ctx, c1, stats, cnt = step_mon(stacked, c1, c2, key,
                                                    cnt, **kw_gen)
        out = (stacked, new_ctx, c1) + ((cnt,) if monitor else ())
        return out, stats

    def block(carry, key):
        keys = jax.random.split(key, cohorts_per_block)
        return jax.lax.scan(scan_fn, carry, keys)

    def init(stacked):
        base = (stacked, empty_ctx(w), empty_ctx(w))
        return base + ((mon.create(),) if monitor else ())

    @functools.partial(jax.jit, donate_argnums=0)
    def drain(carry):
        stacked, c1, c2 = carry[:3]
        cnt = carry[3] if monitor else None
        key = jax.random.PRNGKey(0)
        stacked, _, c1, s1, cnt = step_mon(stacked, c1, c2, key, cnt,
                                           gen_new=False, **kw)
        stacked, _, _, s2, cnt = step_mon(stacked, empty_ctx(w), c1, key,
                                          cnt, gen_new=False, **kw)
        stats = jnp.stack([s1, s2])
        if monitor:
            return stacked, stats, cnt
        return stacked, stats

    return jax.jit(block, donate_argnums=0), init, drain


@memoize_builder
def build_runner(n_sub: int, w: int = 4096, val_words: int = 10,
                 cohorts_per_block: int = 8, validate: bool = True):
    """jit(scan(cohort_step)): one dispatch runs `cohorts_per_block` cohorts.

    Returns run(stacked, key) -> (stacked', stats [cohorts_per_block, N_STATS]).
    State is donated — tables update in place in HBM.
    """
    step = functools.partial(cohort_step, w=w, n_sub=n_sub,
                             val_words=val_words, validate=validate)

    def block(stacked, key):
        keys = jax.random.split(key, cohorts_per_block)
        return jax.lax.scan(step, stacked, keys)

    return jax.jit(block, donate_argnums=0)
