"""store: batched KV server engine (GET/SET/INSERT/DELETE).

TPU equivalent of the reference's store servers — the XDP fast path
(store/ebpf/store_kern.c:32-300: parse, hash, CAS entry lock, slot scan,
reply) and the userspace KVS fallback (store/ebpf/kvs.h) — collapsed into one
batched state machine over an HBM-resident table that holds the whole
keyspace.

Batch semantics (the serialization contract, also implemented by the
sequential oracle in dint_tpu.testing.oracle):
  * Per key, a batch is processed as: all GETs first (they see pre-batch
    state), then writes in arrival (lane) order. This is a valid serial
    order; clients cannot distinguish it from the reference's
    packet-arrival interleaving.
  * SET/INSERT are upserts; each bumps the version by 1. DELETE invalidates.
  * Replies: GET -> VAL(val, ver) or NOT_EXIST; SET/INSERT -> ACK(new ver);
    DELETE -> ACK or NOT_EXIST; bucket overflow on insert -> SPILL (the host
    overflow store takes the key; the reference instead runs an
    eviction/miss protocol through userspace, store/ebpf/store_kern.c:208-246).
  * RETRY (reference entry-spinlock busy) is never emitted: there are no
    spinlocks to lose.
"""
from __future__ import annotations

import functools

import flax.struct
import jax
import jax.numpy as jnp

from ..monitor import waves
from ..ops import hashing, segments
from ..ops import hotset
from ..tables import kv
from ..tables import run as run_mod
from .types import Batch, Op, Replies, Reply, ScanReplies

I32 = jnp.int32
U32 = jnp.uint32


@flax.struct.dataclass
class HotKV:
    """dintcache hot tier for the store engine: a key-indexed write-through
    mirror of keys (0, k) with key_lo < hot_n and key_hi == 0 — the head
    of the store benchmark's Zipfian distribution, whose rank IS the key
    id (clients/workloads.zipf_keys). The mirror replaces the val/ver
    gathers of the probe for hot lanes (a small-array gather); installs
    write through, so mirror == table for every key the probe can hit. Mirror
    entries of ABSENT keys are stale by design: every consumer of
    val0/ver0 in step() is masked by hit0."""
    val: jax.Array    # u32 [hot_n * VW]
    ver: jax.Array    # u32 [hot_n]

    @property
    def hot_n(self):
        return self.ver.shape[0]


def attach_hot(table: kv.KVTable, hot_n: int) -> HotKV:
    """Build the hot mirror for key ids [0, hot_n) from the current table
    (one batched probe; run after populate)."""
    hot_n = max(int(hot_n), 1)
    klo = jnp.arange(hot_n, dtype=U32)
    khi = jnp.zeros((hot_n,), U32)
    b1, b2 = hashing.bucket_pair(khi, klo, table.n_buckets)
    hit, _, _, val, ver, _, _ = kv.probe(table, khi, klo, b1, b2)
    return HotKV(val=jnp.where(hit[:, None], val, U32(0)).reshape(-1),
                 ver=jnp.where(hit, ver, U32(0)))


def step(table: kv.KVTable, batch: Batch, *, maintain_bloom: bool = False,
         hot: HotKV | None = None,
         run: run_mod.OrderedRun | None = None, scan_max: int = 8):
    """One server step: certify and apply a batch. Returns (table', replies)
    — plus `hot'` when the dintcache hot tier is threaded, plus
    `(run', scan_replies)` when the dintscan ordered run is threaded
    (in that order: table, replies[, hot][, run, scan_replies]).

    ``maintain_bloom`` (static) keeps per-bucket bloom filters exact across
    inserts/deletes. The full-table fast path doesn't need them (probe() is
    exact); they exist for cache-mode parity with the reference's negative
    lookups (store/ebpf/store_kern.c:88-95) and cost a hash per slot per
    touched bucket, so they're off by default.

    ``hot`` (a HotKV, or None = off): serve hot keys' val/ver reads from
    the mirror and write installs through to it — replies and table are
    bit-identical to the default path (tests/test_hotset.py).

    ``run`` (a tables.run.OrderedRun, or None = off): serve Op.SCAN lanes
    from the ordered run's merged run∪delta view — scans are phase-1
    reads, so like GETs they see PRE-batch state — and write this batch's
    effective installs/deletes through to the run's delta overlay. The
    lane's Replies slot carries VAL + the row count in `ver` (RETRY when
    the run is stale); rows land in the ScanReplies slab, at most
    ``scan_max`` (static) per lane, request length in ``batch.ver``.
    """
    r = batch.width
    sb = segments.sort_batch(batch.key_hi, batch.key_lo)
    op = batch.op[sb.perm]
    val_in = batch.val[sb.perm]

    b1, b2 = hashing.bucket_pair(sb.key_hi, sb.key_lo, table.n_buckets)
    with waves.scope("store", "probe"):
        if hot is None:
            hit0, fbkt, slot0, val0, ver0, free1, free2 = kv.probe(
                table, sb.key_hi, sb.key_lo, b1, b2)
        else:
            hot_n = hot.hot_n
            vw = table.val_words
            hit0, fbkt, slot0, free1, free2 = kv.probe_loc(
                table, sb.key_hi, sb.key_lo, b1, b2)
            eidx0 = fbkt * table.slots + slot0
            kmidx = jnp.where((sb.key_hi == U32(0))
                              & (sb.key_lo < U32(hot_n)),
                              sb.key_lo.astype(I32), -1)
            val0 = hotset.hot_gather(table.val, hot.val, eidx0, kmidx,
                                     vw).reshape(r, vw)
            ver0 = hotset.hot_gather(table.ver, hot.ver, eidx0, kmidx, 1)
    # insert destination: the emptier of the two candidate buckets
    dest = jnp.where(free2 > free1, b2, b1)
    bkt = jnp.where(hit0, fbkt, dest)
    alt = jnp.where(hit0, fbkt, b1 + b2 - dest)   # the other candidate

    is_get = op == Op.GET
    is_install = (op == Op.SET) | (op == Op.INSERT)
    is_delete = op == Op.DELETE
    is_write = is_install | is_delete

    n_inst_before = segments.seg_cumsum_excl(sb, is_install.astype(I32))
    n_inst_total = segments.seg_sum(sb, is_install.astype(I32))
    last_w_rank = segments.seg_max_where(sb, is_write, sb.rank, I32(-1))
    pos_last = jnp.clip(sb.head_pos + last_w_rank, 0, r - 1)
    last_is_del = is_delete[pos_last]
    last_val = val_in[pos_last]

    ver0_eff = jnp.where(hit0, ver0, U32(0))
    any_write = last_w_rank >= 0
    final_exists = jnp.where(any_write, ~last_is_del, hit0)
    final_ver = ver0_eff + n_inst_total.astype(U32)

    # ---- replies (sorted space) -------------------------------------------
    # exact sequential existence at each write's point: the latest write
    # before me in my segment decides, else pre-batch state
    idx = jnp.arange(r, dtype=I32)
    w_pos = jax.lax.cummax(jnp.where(is_write, idx, I32(-1)))
    prev_w_pos = jnp.concatenate([jnp.full((1,), -1, I32), w_pos[:-1]])
    in_seg = prev_w_pos >= sb.head_pos
    existed_here = jnp.where(in_seg, is_install[jnp.clip(prev_w_pos, 0, r - 1)], hit0)
    rtype = jnp.full((r,), Reply.NONE, I32)
    rtype = jnp.where(is_get, jnp.where(hit0, Reply.VAL, Reply.NOT_EXIST), rtype)
    rtype = jnp.where(is_install, Reply.ACK, rtype)
    rtype = jnp.where(is_delete,
                      jnp.where(existed_here, Reply.ACK, Reply.NOT_EXIST), rtype)
    rval = jnp.where(is_get[:, None] & hit0[:, None], val0, jnp.zeros_like(val0))
    rver = jnp.where(is_get, jnp.where(hit0, ver0, U32(0)), U32(0))
    rver = jnp.where(is_install, ver0_eff + (n_inst_before + 1).astype(U32), rver)

    # ---- writer election: segment-last lane acts for its key -------------
    writer = sb.last & any_write
    w_upd = writer & final_exists & hit0
    w_alloc = writer & final_exists & ~hit0
    w_del = writer & ~final_exists & hit0

    # back to original order for phase B + scatters
    (o_upd, o_alloc, o_del, o_bkt, o_alt, o_slot0, o_ver) = segments.unsort(
        sb, w_upd, w_alloc, w_del, bkt, alt, slot0, final_ver)
    o_val = segments.unsort(sb, last_val)
    o_khi, o_klo = segments.unsort(sb, sb.key_hi, sb.key_lo)

    # ---- phase B: slot allocation for inserts, per destination bucket ----
    sb2 = segments.sort_batch(jnp.zeros((r,), U32), o_bkt.astype(U32))
    alloc2 = o_alloc[sb2.perm]
    rank_alloc = segments.seg_cumsum_excl(sb2, alloc2.astype(I32))
    bkt2 = o_bkt[sb2.perm]
    has2, slot_new2 = kv.nth_free_slot(
        table.valid[kv.bucket_rows(table, bkt2)], rank_alloc)
    ok2 = alloc2 & has2
    spill2 = alloc2 & ~has2
    ok, spill1, slot_new = segments.unsort(sb2, ok2, spill2, slot_new2)

    # ---- phase B2: overflow retries its ALTERNATE candidate bucket --------
    # (two-choice insert: only give up when both buckets are full). Ranks in
    # the alternate must skip slots phase B just handed out there.
    taken = jnp.zeros((table.n_buckets + 1,), I32).at[
        jnp.where(ok, o_bkt, table.n_buckets)].add(1, mode="drop")
    sb3 = segments.sort_batch(jnp.zeros((r,), U32), o_alt.astype(U32))
    retry3 = spill1[sb3.perm]
    rank3 = segments.seg_cumsum_excl(sb3, retry3.astype(I32)) + taken[o_alt[sb3.perm]]
    has3, slot_new3 = kv.nth_free_slot(
        table.valid[kv.bucket_rows(table, o_alt[sb3.perm])], rank3)
    ok3_s = retry3 & has3
    ok_alt, slot_alt = segments.unsort(sb3, ok3_s, slot_new3)
    spill = spill1 & ~ok_alt
    ok = ok | ok_alt
    o_bkt = jnp.where(ok_alt, o_alt, o_bkt)
    slot_new = jnp.where(ok_alt, slot_alt, slot_new)

    # spill => every install of that key failed: fix up replies for the whole
    # key segment (installs -> SPILL, deletes -> NOT_EXIST since nothing was
    # ever installed; GETs already answered from pre-state)
    seg_spill = segments.seg_any(sb, spill[sb.perm])
    rtype = jnp.where(seg_spill & is_install, I32(Reply.SPILL), rtype)
    rtype = jnp.where(seg_spill & is_delete, I32(Reply.NOT_EXIST), rtype)
    rver = jnp.where(seg_spill & is_install, U32(0), rver)

    # ---- scatters (flat 1-D unique-index: one writer per entry) ----------
    # NOTE on unique_indices=True + the OOB sentinel: every MASKED lane is
    # routed to the same out-of-bounds index (ne), so indices are only
    # unique among the lanes that actually write — duplicated OOB lanes
    # technically violate JAX's uniqueness contract (documented UB). We
    # rely on mode="drop" discarding OOB lanes before any dedup matters;
    # tests/test_ops.py::test_oob_dup_scatter_unique_indices pins this
    # lowering behavior so a jaxlib upgrade that changes it fails loudly
    # instead of corrupting tables. (Same pattern: tatp_dense.pipe_step
    # wflat / populate_device idx, smallbank_dense scatters.)
    ne = table.n_buckets * table.slots
    s = table.slots
    w_any_slot = o_upd | ok | o_del
    t_slot = jnp.where(o_upd | o_del, o_slot0, slot_new)
    with waves.scope("store", "install"):
        e_any = jnp.where(w_any_slot, o_bkt * s + t_slot, ne)
        new_valid = table.valid.at[e_any].set(~o_del, mode="drop",
                                              unique_indices=True)
        wv = (o_upd | ok)
        sl_v = jnp.where(o_upd, o_slot0, slot_new)
        e_v = jnp.where(wv, o_bkt * s + sl_v, ne)
        if hot is None:
            val_new = table.val.at[kv.val_word_idx(table, e_v)].set(
                o_val.reshape(-1), mode="drop", unique_indices=True)
            ver_new = table.ver.at[e_v].set(o_ver, mode="drop",
                                            unique_indices=True)
        else:
            # write-through install: table entry AND key-indexed mirror.
            # One writer per key segment,
            # so entry AND mirror indices are unique among masked lanes.
            w_midx = jnp.where(wv & (o_khi == U32(0))
                               & (o_klo < U32(hot_n)),
                               o_klo.astype(I32), -1)
            e_w = o_bkt * s + sl_v
            val_new, hot_val = hotset.hot_scatter(
                table.val, hot.val, e_w, w_midx, wv, o_val.reshape(-1), vw)
            ver_new, hot_ver = hotset.hot_scatter(
                table.ver, hot.ver, e_w, w_midx, wv, o_ver, 1)
            hot = hot.replace(val=hot_val, ver=hot_ver)
        table = table.replace(
            key_hi=table.key_hi.at[e_v].set(o_khi, mode="drop",
                                            unique_indices=True),
            key_lo=table.key_lo.at[e_v].set(o_klo, mode="drop",
                                            unique_indices=True),
            val=val_new,
            ver=ver_new,
            valid=new_valid,
        )
    if maintain_bloom:
        # recompute exactly for buckets whose membership changed
        table = kv.recompute_bloom(table, o_bkt, ok | o_del)

    o_rtype, o_rver = segments.unsort(sb, rtype, rver)
    o_rval = segments.unsort(sb, rval)

    # ---- dintscan: Op.SCAN lanes answered from the PRE-batch run∪delta ----
    # view (a valid serial order: scans sit in phase 1 with the GETs), then
    # this batch's effective writes — exactly the lanes the scatters above
    # installed (spilled inserts never reach table OR overlay) — write
    # through to the delta overlay, keeping run∪delta == table.
    scan_rep = None
    if run is not None:
        vw = table.val_words
        assert run.cap == ne and run.val_words == vw, \
            "run must be from_table-shaped for this table"
        lg_win = scan_max + run.delta_cap
        assert ne >= lg_win, "table too small for scan_max + delta_cap"
        is_scan = batch.op == Op.SCAN
        with waves.scope("store", "scan_locate"):
            off = run_mod.locate(run, batch.key_hi, batch.key_lo)
        # clamp so EVERY route gathers the identical in-bounds window
        # (coverage: clamping only moves the window start DOWN, and rows
        # below the lower bound are filtered by the >= start-key check)
        off_c = jnp.clip(off, 0, ne - lg_win)
        with waves.scope("store", "scan"):
            s_hi, s_lo, s_ver, s_val = run_mod.scan_slab(
                run.key_hi, run.key_lo, run.ver, run.val, off_c, lg_win,
                vw)
            # stale overlay => overflowed => the merged view may be missing
            # writes: answer no rows, reply RETRY (re-send after rebuild)
            slen = jnp.where(is_scan & ~run.stale,
                             jnp.clip(batch.ver.astype(I32), 0, scan_max),
                             I32(0))
            count, k_hi, k_lo, k_ver, k_val, d_hits = run_mod.merge_scan(
                run, s_hi, s_lo, s_ver, s_val, off_c,
                batch.key_hi, batch.key_lo, slen, scan_max)
        scan_rep = ScanReplies(key_hi=k_hi, key_lo=k_lo, ver=k_ver,
                               val=k_val, count=count, delta_hits=d_hits)
        o_rtype = jnp.where(is_scan,
                            jnp.where(run.stale, I32(Reply.RETRY),
                                      I32(Reply.VAL)), o_rtype)
        o_rver = jnp.where(is_scan, count.astype(U32), o_rver)
        o_rval = jnp.where(is_scan[:, None], U32(0), o_rval)
        with waves.scope("store", "delta_append"):
            run = run_mod.delta_append(
                run, o_khi, o_klo, o_ver, o_val.reshape(-1), o_del,
                o_upd | ok | o_del)

    replies = Replies(rtype=o_rtype, val=o_rval, ver=o_rver)
    out = (table, replies)
    if hot is not None:
        out = out + (hot,)
    if run is not None:
        out = out + (run, scan_rep)
    return out


def rebuild_run(table: kv.KVTable, run: run_mod.OrderedRun):
    """Drain-boundary run maintenance (serve plane): merge-compact the
    delta overlay into the run — or re-snapshot from the table when the
    overlay went stale. Scoped as the dint.store.run_rebuild wave."""
    with waves.scope("store", "run_rebuild"):
        return run_mod.refresh(table, run)


# ------------------------------------------------------------- dintserve

STORE_MAGIC = 0x55AA   # val word1 of populated rows (clients/micro.py)


def build_serve_runner(n_keys: int, w: int = 4096,
                       cohorts_per_block: int = 8, val_words: int = 10,
                       read_frac: float = 0.5, scan_frac: float = 0.0,
                       max_scan_len: int = 8, scan_max: int = 8,
                       delta_cap: int | None = None,
                       hot_frac: float | None = None,
                       hot_prob: float | None = None,
                       use_scan=None,
                       monitor: bool = False, trace=None,
                       serve: bool = False):
    """Serve-plane runner for the store engine (dintscan's host workload):
    jit(scan(step)) over carry (table[, run][, counters]). Returns
    (run, init, drain) under the ServeEngine contract:
      run(carry, key[, occ, shed]) -> (carry', stats [cohorts_per_block, 2])
      init(db)   -> carry (attaches the ordered run when use_scan)
      drain(carry) -> (db, stats [1, 2][, counters])

    Cohorts are generated ON DEVICE from the block key: YCSB-E-shaped —
    ``scan_frac`` of lanes issue Op.SCAN with uniform lengths in
    [1, max_scan_len] (engine clips to ``scan_max``); the rest split
    ``read_frac`` GET / else SET, keys drawn with the store benchmark's
    hot-prefix skew (hot head == smallest ids, the zipf_keys alignment).
    Stats rows are (attempted, committed): attempted = admitted lanes,
    committed = VAL/ACK replies (stale-scan RETRYs are NOT committed —
    the client re-sends after the rebuild).

    ``use_scan``: None = honor DINT_USE_SCAN. Threads the ordered-run
    snapshot + delta overlay through every step and merge-compacts it
    at each block's drain boundary (dint.store.run_rebuild) — the run
    stays sorted without ever stalling the step. Off: Op.SCAN is never
    generated and the carry/jaxpr are unchanged from the point engine.

    ``serve``: variable-occupancy mode — run takes occ/shed i32
    [cohorts_per_block]; lanes >= occ are masked to NOP/PAD before the
    step (padded lanes, the serve reconciliation identity).
    ``trace`` is accepted for contract uniformity and ignored: the
    store engine has no txn ring.
    """
    del trace
    from ..clients import workloads as wl
    from ..monitor import counters as mon
    use_scan = run_mod.resolve_use_scan(use_scan)
    hfrac = wl.SB_HOT_FRAC if hot_frac is None else float(hot_frac)
    hprob = wl.SB_HOT_PROB if hot_prob is None else float(hot_prob)
    hot_n = max(1, min(int(n_keys * hfrac), n_keys))
    if not use_scan:
        scan_frac = 0.0

    def gen(key, occ):
        """One on-device cohort: (Batch, admitted, n_scan_lanes)."""
        ks = jax.random.split(key, 6)
        lane = jnp.arange(w, dtype=I32)
        admitted = lane < occ
        is_scan = (jax.random.uniform(ks[0], (w,)) < scan_frac) \
            if scan_frac > 0.0 else jnp.zeros((w,), bool)
        is_get = ~is_scan & (jax.random.uniform(ks[1], (w,)) < read_frac)
        hot = jax.random.uniform(ks[2], (w,)) < hprob
        klo = jnp.where(
            hot, jax.random.randint(ks[3], (w,), 1, hot_n + 1),
            jax.random.randint(ks[4], (w,), 1, n_keys + 1)).astype(U32)
        op = jnp.where(is_scan, I32(Op.SCAN),
                       jnp.where(is_get, I32(Op.GET), I32(Op.SET)))
        op = jnp.where(admitted, op, I32(Op.NOP))
        klo = jnp.where(admitted, klo, U32(0xFFFFFFFF))
        khi = jnp.where(admitted, U32(0), U32(0xFFFFFFFF))
        val = jnp.zeros((w, val_words), U32)
        val = val.at[:, 0].set(klo).at[:, 1].set(U32(STORE_MAGIC))
        slen = jax.random.randint(ks[5], (w,), 1, max_scan_len + 1)
        ver = jnp.where(admitted & is_scan, slen.astype(U32), U32(0))
        batch = Batch(op=op, table=jnp.zeros((w,), I32), key_hi=khi,
                      key_lo=klo, val=val, ver=ver)
        return batch, admitted, (admitted & is_scan)

    def scan_fn(carry, x):
        key, occ, shed = x if serve else (x, None, None)
        occ = jnp.asarray(w, I32) if occ is None else occ
        shed = I32(0) if shed is None else shed
        table = carry[0]
        run = carry[1] if use_scan else None
        cnt = carry[-1] if monitor else None
        batch, admitted, scan_lanes = gen(key, occ)
        if use_scan:
            table, rep, run, srep = step(table, batch, run=run,
                                         scan_max=scan_max)
        else:
            table, rep = step(table, batch)
            srep = None
        committed = (admitted
                     & ((rep.rtype == Reply.VAL)
                        | (rep.rtype == Reply.ACK))).sum(dtype=I32)
        stats = jnp.stack([occ, committed])
        cnt = mon.bump(cnt, {
            mon.CTR_STEPS: 1,
            mon.CTR_SERVE_OCC_LANES: occ,
            mon.CTR_SERVE_PAD_LANES: jnp.asarray(w, I32) - occ,
            mon.CTR_SERVE_SHED_LANES: shed,
            mon.CTR_DISPATCH_XLA: 1,
            **({mon.CTR_SCAN_REQUESTS: scan_lanes.sum(dtype=I32),
                mon.CTR_SCAN_ROWS: srep.count.sum(dtype=I32),
                mon.CTR_SCAN_DELTA_HITS: srep.delta_hits.sum(dtype=I32)}
               if use_scan else {}),
        })
        out = (table,) + ((run,) if use_scan else ()) \
            + ((cnt,) if monitor else ())
        return out, stats

    def _post(carry):
        # block drain boundary: fold the overlay back into the run so
        # the NEXT block's scans start from a fresh (never-stale) view
        if use_scan:
            carry = ((carry[0], rebuild_run(carry[0], carry[1]))
                     + carry[2:])
        return carry

    if serve:
        def block(carry, key, occ, shed):
            keys = jax.random.split(key, cohorts_per_block)
            carry, stats = jax.lax.scan(scan_fn, carry, (keys, occ, shed))
            return _post(carry), stats
    else:
        def block(carry, key):
            keys = jax.random.split(key, cohorts_per_block)
            carry, stats = jax.lax.scan(scan_fn, carry, keys)
            return _post(carry), stats

    def init(db):
        assert db.val_words == val_words, (db.val_words, val_words)
        base = (db,)
        if use_scan:
            ne = db.n_buckets * db.slots
            # default overlay: one wave's worth of distinct writes, NOT
            # table-sized — the scan coverage window is scan_max + dcap
            # rows per lane, so an oversized overlay quadratically
            # inflates merge_scan's [w, lg, dcap] overlay compare
            dcap = min(64, max(1, ne - scan_max)) if delta_cap is None \
                else int(delta_cap)
            assert ne >= scan_max + dcap, (ne, scan_max, dcap)
            base = base + (run_mod.from_table(db, delta_cap=dcap),)
        return base + ((mon.create(),) if monitor else ())

    @functools.partial(jax.jit, donate_argnums=0)
    def drain(carry):
        # nothing is in flight (the store step is unpipelined); the run
        # is derived state — dropped here, re-snapshot at next attach
        table = carry[0]
        cnt = carry[-1] if monitor else None
        zero = jnp.zeros((1, 2), I32)
        return (table, zero) + ((cnt,) if monitor else ())

    init.trace_cfg = None
    return jax.jit(block, donate_argnums=0), init, drain
