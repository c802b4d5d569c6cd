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

import jax
import jax.numpy as jnp
import numpy as np

from ..monitor import waves
from ..ops import compact, hashing, segments
from ..tables import kv
from ..tables import run as run_mod
from .types import Batch, Op, Replies, Reply, ScanReplies

I32 = jnp.int32
U32 = jnp.uint32


def install_is_compacted(table: kv.KVTable, r: int) -> bool:
    """Whether ``step`` compacts the install of an ``r``-lane batch into
    ``table``: where the full-width scatters are too sparse
    for the compiler to sort them first (``compact.compiler_sorts``: more
    than SORTED_SCATTER_WORDS_PER_LANE entries a lane; the ratio is the
    same for all five arrays). A rule in the shapes alone. The
    populate's 65,536 lanes into 2^26 entries are dense and keep the
    full-width scatters, sorted at ~20 ns a lane; the serve block's 8,192
    are not, and a lane there costs ~88 ns landed or dropped (PERF.md
    section 6, PR 39, PR 40)."""
    ne = table.n_buckets * table.slots
    return not compact.compiler_sorts(ne, r)


def step(table: kv.KVTable, batch: Batch, *, maintain_bloom: bool = False,
         run: run_mod.OrderedRun | None = None, scan_max: int = 8):
    """One server step: certify and apply a batch. Returns (table', replies)
    — plus `(run', scan_replies)` when the dintscan ordered run is
    threaded.

    ``maintain_bloom`` (static) keeps per-bucket bloom filters exact across
    inserts/deletes. The full-table fast path doesn't need them (probe() is
    exact); they exist for cache-mode parity with the reference's negative
    lookups (store/ebpf/store_kern.c:88-95) and cost a hash per slot per
    touched bucket, so they're off by default.

    ``run`` (a tables.run.OrderedRun, or None = off): serve Op.SCAN lanes
    from the ordered run's merged run∪delta view — scans are phase-1
    reads, so like GETs they see PRE-batch state — and write this batch's
    effective installs/deletes through to the run's delta overlay. The
    lane's Replies slot carries VAL + the row count in `ver` (RETRY when
    the run is stale); rows land in the ScanReplies slab, at most
    ``scan_max`` (static) per lane, request length in ``batch.ver``.

    The install issues the step's elected writers, not its lanes, where
    the shapes make a lane dear (``install_is_compacted``; the same table
    and replies either way, tests/test_store_compact.py).

    A scan lane whose two windows cannot show its first ``slen`` live
    keys (tombstones in range, tables/run.py) answers RETRY too.
    """
    out = _step(table, batch, maintain_bloom=maintain_bloom, run=run,
                scan_max=scan_max)[0]
    if run is None:
        return out
    srep = out[-1]
    return out[:-1] + (srep.replace(val=jnp.swapaxes(srep.val, 1, 2)),)


def _install_live(table: kv.KVTable, o_upd, ok, o_del, o_bkt, o_slot0,
                  slot_new, o_khi, o_klo, o_val, o_ver):
    """The install over the elected writers alone (ops/compact.py): the
    table the five full-width scatters of ``_step`` leave, bit for bit,
    and (trips, writers) of the first of two chunk loops.

    The first loop carries ``val`` and ``ver`` and issues the lanes that
    install a record (``o_upd | ok``), C = ``chunk_lanes(r)`` a trip. The
    second carries ``valid``, ``key_hi`` and ``key_lo`` and issues the
    lanes that allocate or free a slot (``ok | o_del``): an update hits an
    entry that is valid and holds its key already (``kv.probe_loc``: a hit
    is valid AND key-equal), so the full-width path writes those three
    words onto themselves there. Under a GET / SET mix over resident keys
    it makes no trip. A loop carries the arrays it writes and closes over
    lane-space vectors only: no second copy of a table."""
    r = o_upd.shape[0]
    s = table.slots
    ne = table.n_buckets * s
    chunk = compact.chunk_lanes(r)
    with waves.part("store", "kv_meta_scatter"):
        wv = o_upd | ok
        slot_w = ok | o_del
        e_w = o_bkt * s + jnp.where(ok, slot_new, o_slot0)
    with waves.part("store", "kv_compact"):
        ranks, n_live = compact.live_ranks(wv)

        def record_chunk(tabs, lanes, on):
            val, ver = tabs
            e_c = jnp.where(on, e_w[lanes], ne)
            ver_c, val_c = o_ver[lanes], o_val[lanes]
            with waves.part("store", "kv_val_scatter"):
                val = val.at[kv.val_word_idx(table, e_c)].set(
                    val_c.reshape(-1), mode="drop", unique_indices=True)
            with waves.part("store", "kv_meta_scatter"):
                ver = ver.at[e_c].set(ver_c, mode="drop",
                                      unique_indices=True)
            return val, ver

        (val, ver), trips = compact.for_chunks(
            ranks, n_live, chunk, record_chunk, (table.val, table.ver))

        s_ranks, s_live = compact.live_ranks(slot_w)

        def slot_chunk(tabs, lanes, on):
            valid, key_hi, key_lo = tabs
            e_c = jnp.where(on, e_w[lanes], ne)
            alloc_c = ok[lanes]
            e_k = jnp.where(alloc_c, e_c, ne)      # a delete's ride ne
            khi_c, klo_c = o_khi[lanes], o_klo[lanes]
            with waves.part("store", "kv_meta_scatter"):
                valid = valid.at[e_c].set(alloc_c, mode="drop",
                                          unique_indices=True)
                key_hi = key_hi.at[e_k].set(khi_c, mode="drop",
                                            unique_indices=True)
                key_lo = key_lo.at[e_k].set(klo_c, mode="drop",
                                            unique_indices=True)
            return valid, key_hi, key_lo

        (valid, key_hi, key_lo), _ = compact.for_chunks(
            s_ranks, s_live, chunk, slot_chunk,
            (table.valid, table.key_hi, table.key_lo))
    return table.replace(key_hi=key_hi, key_lo=key_lo, val=val, ver=ver,
                         valid=valid), (trips, n_live)


def _step(table: kv.KVTable, batch: Batch, *, maintain_bloom: bool,
          run: run_mod.OrderedRun | None, scan_max: int):
    """``step``, and beside its result the compacted install's (chunk
    trips, elected writers) as i32 scalars for the counter plane: None
    where the install runs at full width."""
    r = batch.width
    with waves.part("store", "key_sort"):
        sb = segments.sort_batch(batch.key_hi, batch.key_lo)
        op = batch.op[sb.perm]
        val_in = batch.val[sb.perm]

    with waves.scope("store", "probe"):
        with waves.part("store", "probe_keys"):
            b1, b2 = hashing.bucket_pair(sb.key_hi, sb.key_lo,
                                         table.n_buckets)
        # kv.probe's own body, under the two parts the trace splits
        # the wave by: the [r, S] key / valid gathers of both
        # candidate buckets, then the hit entry's value and version
        with waves.part("store", "probe_keys"):
            hit0, fbkt, slot0, free1, free2 = kv.probe_loc(
                table, sb.key_hi, sb.key_lo, b1, b2)
        with waves.part("store", "probe_val"):
            eidx0 = fbkt * table.slots + slot0
            val0 = kv.entry_val(table, eidx0)
            ver0 = table.ver[eidx0]
    with waves.part("store", "key_sort"):
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

    with waves.part("store", "reply_build"):
        # ---- replies (sorted space) ---------------------------------------
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

    with waves.part("store", "key_sort"):
        # ---- writer election: segment-last lane acts for its key ---------
        writer = sb.last & any_write
        w_upd = writer & final_exists & hit0
        w_alloc = writer & final_exists & ~hit0
        w_del = writer & ~final_exists & hit0

        # back to original order for phase B + scatters
        (o_upd, o_alloc, o_del, o_bkt, o_alt, o_slot0, o_ver) = segments.unsort(
            sb, w_upd, w_alloc, w_del, bkt, alt, slot0, final_ver)
        o_val = segments.unsort(sb, last_val)
        o_khi, o_klo = segments.unsort(sb, sb.key_hi, sb.key_lo)

    with waves.part("store", "slot_alloc"):
        # ---- phase B: slot allocation for inserts, per destination bucket
        sb2 = segments.sort_batch(jnp.zeros((r,), U32), o_bkt.astype(U32))
        alloc2 = o_alloc[sb2.perm]
        rank_alloc = segments.seg_cumsum_excl(sb2, alloc2.astype(I32))
        bkt2 = o_bkt[sb2.perm]
        has2, slot_new2 = kv.nth_free_slot(
            table.valid[kv.bucket_rows(table, bkt2)], rank_alloc)
        ok2 = alloc2 & has2
        spill2 = alloc2 & ~has2
        ok, spill1, slot_new = segments.unsort(sb2, ok2, spill2, slot_new2)

        # ---- phase B2: overflow retries its ALTERNATE candidate bucket ----
        # (two-choice insert: only give up when both buckets are full). Ranks
        # in the alternate must skip slots phase B just handed out there.
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

    with waves.part("store", "reply_build"):
        # spill => every install of that key failed: fix up replies for the
        # whole key segment (installs -> SPILL, deletes -> NOT_EXIST since
        # nothing was ever installed; GETs already answered from pre-state)
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
    live = None
    with waves.scope("store", "install"):
        if install_is_compacted(table, r):
            # the serve block's shapes: the elected writers in chunks
            table, live = _install_live(
                table, o_upd, ok, o_del, o_bkt, o_slot0, slot_new, o_khi,
                o_klo, o_val, o_ver)
        else:
            # every lane issued: the populate's shapes and the small
            # ones (the compiler sorts these scatters, and a dropped lane
            # is cheap then)
            with waves.part("store", "kv_meta_scatter"):
                w_any_slot = o_upd | ok | o_del
                t_slot = jnp.where(o_upd | o_del, o_slot0, slot_new)
                e_any = jnp.where(w_any_slot, o_bkt * s + t_slot, ne)
                new_valid = table.valid.at[e_any].set(~o_del, mode="drop",
                                                      unique_indices=True)
                wv = (o_upd | ok)
                sl_v = jnp.where(o_upd, o_slot0, slot_new)
                e_v = jnp.where(wv, o_bkt * s + sl_v, ne)
            with waves.part("store", "kv_val_scatter"):
                val_new = table.val.at[kv.val_word_idx(table, e_v)].set(
                    o_val.reshape(-1), mode="drop", unique_indices=True)
            with waves.part("store", "kv_meta_scatter"):
                ver_new = table.ver.at[e_v].set(o_ver, mode="drop",
                                                unique_indices=True)
            with waves.part("store", "kv_meta_scatter"):
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

    with waves.part("store", "key_sort"):
        o_rtype, o_rver = segments.unsort(sb, rtype, rver)
        o_rval = segments.unsort(sb, rval)

    # ---- dintscan: Op.SCAN lanes answered from the PRE-batch run∪delta ----
    # view (a valid serial order: scans sit in phase 1 with the GETs), then
    # this batch's effective writes — exactly the lanes the scatters above
    # installed (spilled inserts never reach table OR overlay) — write
    # through to the delta overlay, keeping run∪delta == table.
    scan_rep = None
    if run is not None:
        assert run.val_words == table.val_words
        is_scan = batch.op == Op.SCAN
        with waves.scope("store", "scan_locate"):
            p, pd = run_mod.locate(run, batch.key_hi, batch.key_lo)
        with waves.scope("store", "scan"):
            with waves.part("store", "scan_window"):
                rw, dw, tomb = run_mod.windows(run, p, pd, scan_max)
            with waves.part("store", "scan_merge"):
                # a stale overlay may be missing writes: no rows, RETRY
                # (re-send after the rebuild); so is a lane whose windows
                # cannot show its first slen live keys
                slen = jnp.where(is_scan & ~run.stale,
                                 jnp.clip(batch.ver.astype(I32), 0,
                                          scan_max), I32(0))
                count, covered, k_hi, k_lo, k_ver, k_val, d_hits = \
                    run_mod.merge(rw, dw, tomb, batch.key_hi, batch.key_lo,
                                  slen, scan_max)
                scan_rep = ScanReplies(key_hi=k_hi, key_lo=k_lo, ver=k_ver,
                                       val=k_val, count=count,
                                       delta_hits=d_hits)
                retry = run.stale | ~covered
                o_rtype = jnp.where(is_scan, jnp.where(
                    retry, I32(Reply.RETRY), I32(Reply.VAL)), o_rtype)
                o_rver = jnp.where(is_scan, count.astype(U32), o_rver)
                o_rval = jnp.where(is_scan[:, None], U32(0), o_rval)
        with waves.scope("store", "delta_append"):
            run = run_mod.delta_append(
                run, o_khi, o_klo, o_ver, o_val, o_del, o_upd | ok | o_del)

    replies = Replies(rtype=o_rtype, val=o_rval, ver=o_rver)
    out = (table, replies)
    if run is not None:
        out = out + (run, scan_rep)
    return out, live


def rebuild_run(table: kv.KVTable, run: run_mod.OrderedRun):
    """Drain-boundary run maintenance (serve plane): merge-compact the
    delta overlay into the run — or re-snapshot from the table when the
    overlay went stale. Scoped as the dint.store.run_rebuild wave."""
    with waves.scope("store", "run_rebuild"):
        return run_mod.refresh(table, run)


# ------------------------------------------------------------- dintserve

STORE_MAGIC = 0x55AA   # val word1 of populated rows (clients/micro.py)

# One stats row a step. ``attempted`` and ``committed`` lead, so that a
# caller that reads two columns (the serve plane) reads them as before.
# The lawful outcomes of a lane are ``committed`` (VAL + ACK) and
# ``not_exist``; ``spill``, ``retry`` and ``magic_bad`` are faults of a
# deployment whose table holds its key space. ``ver_sum`` / ``val_sum``
# are checksums mod 2^32 (u32 sums carried as i32 bit patterns) over the
# VAL and ACK lanes: of the reply versions, and of every reply value word,
# so that every returned value and version can be held to a reference
# without a reply leaving the device.
STAT_NAMES = ("attempted", "committed", "not_exist", "spill", "retry",
              "magic_bad", "gets", "updates", "ver_sum", "val_sum")
(STAT_ATTEMPTED, STAT_COMMITTED, STAT_NOT_EXIST, STAT_SPILL, STAT_RETRY,
 STAT_MAGIC_BAD, STAT_GETS, STAT_UPDATES, STAT_VER_SUM,
 STAT_VAL_SUM) = range(len(STAT_NAMES))
N_STATS = len(STAT_NAMES)

_ZETA_CHUNK = 1 << 20


@functools.lru_cache(maxsize=None)
def zipf_constants(n: int, theta: float) -> tuple:
    """(zetan, eta, alpha, zeta2) of YCSB's ZipfianGenerator over n items
    (Gray et al., "Quickly Generating Billion-Record Synthetic
    Databases", SIGMOD'94), in float64 on the host. ``zetan`` is summed
    a chunk at a time: nothing n-sized is made."""
    zetan = 0.0
    for lo in range(1, n + 1, _ZETA_CHUNK):
        i = np.arange(lo, min(lo + _ZETA_CHUNK, n + 1), dtype=np.float64)
        zetan += float((i ** -theta).sum())
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    return zetan, eta, alpha, zeta2


def zipf_keys(key, shape, n: int, theta: float):
    """YCSB's Zipfian over [1, n] on the device, in closed form: with u
    uniform in [0, 1), ``u * zetan < 1 -> 1``, ``< 1 + 0.5^theta -> 2``,
    else ``1 + floor(n * (eta * u - eta + 1) ^ alpha)``; rank == key id.

    float32 throughout. The draw is v = 1 - u in (0, 1] from 32 random
    bits, and the power is ``exp(alpha * log1p(-eta * v))``: towards the
    tail v is small and keeps its relative precision, which ``eta * u -
    eta + 1`` (a number near 1, 6e-8 apart) would lose to a comb of ~23
    keys at alpha = 100 and n = 24 M. A rank that rounds to n + 1 is
    clipped to n."""
    zetan, eta, alpha, zeta2 = zipf_constants(int(n), float(theta))
    bits = jax.random.bits(key, shape, U32)
    v = (bits.astype(jnp.float32) + 1.0) * jnp.float32(2.0 ** -32)
    uz = (1.0 - v) * jnp.float32(zetan)
    tail = jnp.float32(n) * jnp.exp(
        jnp.float32(alpha) * jnp.log1p(-jnp.float32(eta) * v))
    rank = jnp.where(uz < 1.0, U32(1), jnp.where(
        uz < jnp.float32(zeta2), U32(2),
        U32(1) + jnp.floor(tail).astype(U32)))
    return jnp.clip(rank, U32(1), U32(n))


def _fmix32(h):
    """murmur3's 32-bit finalizer."""
    h = h ^ (h >> U32(16))
    h = h * U32(0x85EBCA6B)
    h = h ^ (h >> U32(13))
    h = h * U32(0xC2B2AE35)
    return h ^ (h >> U32(16))


def step_stamp(key):
    """32 bits of a step's key (a raw ``PRNGKey``, as everywhere in this
    repo): the stamp of the values it writes."""
    return key[-1].astype(U32)


def stamped_value(klo, stamp, val_words: int):
    """The whole record an update of key ``klo`` writes in the step whose
    stamp is ``stamp``: word 0 the key, word 1 STORE_MAGIC, word 2 the
    stamp, word j >= 3 ``fmix32(key * 0x9E3779B1 + stamp * 0x7FEB352D +
    j)``. A function of (key, stamp) alone, so every lane of a step that
    names a key writes the same value, and a torn or misplaced value is
    recognisable from the record itself."""
    j = jnp.arange(val_words, dtype=U32)[None]
    stamp = jnp.asarray(stamp, U32)
    val = _fmix32(klo[:, None] * U32(0x9E3779B1)
                  + stamp * U32(0x7FEB352D) + j)
    val = val.at[:, 0].set(klo).at[:, 1].set(U32(STORE_MAGIC))
    return val.at[:, 2].set(stamp) if val_words > 2 else val


def build_generator(n_keys: int, w: int, val_words: int = 10,
                    read_frac: float = 0.5, theta: float | None = None,
                    hot_frac: float | None = None,
                    hot_prob: float | None = None):
    """``gen(key, occ=None) -> Batch``: one on-device cohort of ``w``
    lanes from a step's key, the function ``build_serve_runner`` composes
    with ``step`` (and that a caller may run alone: the same key gives
    the same batch). Lanes >= ``occ`` are NOP / PAD.

    ``theta`` None: the hot-prefix skew (``hot_frac`` / ``hot_prob``; hot
    head == smallest ids), SETs of {key, magic, 0...}. ``theta`` a float:
    YCSB's Zipfian with that constant over [1, n_keys] (``zipf_keys``),
    ``read_frac`` GET and the rest updates of the whole record
    (``stamped_value``; the stamp is a word of the step's key, so the
    generator needs no counter)."""
    from ..clients import workloads as wl
    hfrac = wl.SB_HOT_FRAC if hot_frac is None else float(hot_frac)
    hprob = wl.SB_HOT_PROB if hot_prob is None else float(hot_prob)
    hot_n = max(1, min(int(n_keys * hfrac), n_keys))

    def gen(key, occ=None):
        ks = jax.random.split(key, 6)
        lane = jnp.arange(w, dtype=I32)
        admitted = lane < (jnp.asarray(w, I32) if occ is None else occ)
        is_get = jax.random.uniform(ks[1], (w,)) < read_frac
        if theta is None:
            hot = jax.random.uniform(ks[2], (w,)) < hprob
            klo = jnp.where(
                hot, jax.random.randint(ks[3], (w,), 1, hot_n + 1),
                jax.random.randint(ks[4], (w,), 1, n_keys + 1)).astype(U32)
        else:
            klo = zipf_keys(ks[3], (w,), n_keys, theta)
        op = jnp.where(admitted, jnp.where(is_get, I32(Op.GET),
                                           I32(Op.SET)), I32(Op.NOP))
        klo = jnp.where(admitted, klo, U32(0xFFFFFFFF))
        khi = jnp.where(admitted, U32(0), U32(0xFFFFFFFF))
        if theta is None:
            val = jnp.zeros((w, val_words), U32)
            val = val.at[:, 0].set(klo).at[:, 1].set(U32(STORE_MAGIC))
        else:
            val = stamped_value(klo, step_stamp(key), val_words)
        return Batch(op=op, table=jnp.zeros((w,), I32), key_hi=khi,
                     key_lo=klo, val=val, ver=jnp.zeros((w,), U32))

    return gen


def build_ordered_generator(n_keys: int, w: int, val_words: int = 10,
                            scan_frac: float = 0.95, max_scan_len: int = 100,
                            theta: float = 0.99):
    """``gen(key, nxt, occ=None) -> (Batch, nxt')``: YCSB's core workload
    shape over HASHED keys (CoreWorkload with insertorder=hashed): key
    number k lives under ``hashing.fnv64(k)``, keys 1..n_keys populated
    (``build_populate(..., hashed=True)``), ``nxt`` the next key number to
    insert (a carried u32 scalar, YCSB's transactioninsertkeysequence).

    A lane is a SCAN with ``scan_frac``, else an INSERT of the next key
    number (numbered in lane order: ``nxt``, ``nxt + 1``, ...). A scan
    starts at YCSB's ScrambledZipfian over the key numbers inserted so
    far: a Zipfian rank over [1, n_keys] (``zipf_keys``), scrambled as
    ``1 + fnv64(rank) mod (nxt - 1)``; its length is uniform in [1,
    max_scan_len] (in ``Batch.ver``). An insert writes ``stamped_value``
    of its key's low word. The same (key, nxt) gives the same batch."""
    def gen(key, nxt, occ=None):
        ks = jax.random.split(key, 6)
        lane = jnp.arange(w, dtype=I32)
        admitted = lane < (jnp.asarray(w, I32) if occ is None else occ)
        is_scan = jax.random.uniform(ks[0], (w,)) < scan_frac
        is_ins = ~is_scan
        ins = is_ins & admitted
        nxt = jnp.asarray(nxt, U32)
        num_ins = nxt + (jnp.cumsum(ins.astype(U32)) - U32(1))
        r_hi, r_lo = hashing.fnv64(zipf_keys(ks[3], (w,), n_keys, theta))
        num_old = U32(1) + hashing.mod_u64(r_hi, r_lo, nxt - U32(1))
        khi, klo = hashing.fnv64(jnp.where(is_ins, num_ins, num_old))
        op = jnp.where(admitted, jnp.where(is_scan, I32(Op.SCAN),
                                           I32(Op.INSERT)), I32(Op.NOP))
        klo = jnp.where(admitted, klo, U32(0xFFFFFFFF))
        khi = jnp.where(admitted, khi, U32(0xFFFFFFFF))
        val = stamped_value(klo, step_stamp(key), val_words)
        slen = jax.random.randint(ks[5], (w,), 1, max_scan_len + 1)
        ver = jnp.where(admitted & is_scan, slen.astype(U32), U32(0))
        batch = Batch(op=op, table=jnp.zeros((w,), I32), key_hi=khi,
                      key_lo=klo, val=val, ver=ver)
        return batch, nxt + ins.sum(dtype=U32)

    return gen


def reply_stats(batch: Batch, rep: Replies):
    """The stats row (i32 [N_STATS], ``STAT_NAMES``) of one step's batch
    and replies. ``magic_bad``: VAL replies of GET lanes whose word 0 is
    not the key or word 1 not STORE_MAGIC, the reference client's assert
    on every read (store/caladan/client_caladan.cc:160)."""
    admitted = batch.op != Op.NOP
    is_get = batch.op == Op.GET
    is_val = rep.rtype == Reply.VAL
    done = admitted & (is_val | (rep.rtype == Reply.ACK))
    bad = is_get & is_val & ((rep.val[:, 0] != batch.key_lo)
                             | (rep.val[:, 1] != U32(STORE_MAGIC)))

    def count(mask):
        return mask.sum(dtype=I32)

    def checksum(x):
        return jax.lax.bitcast_convert_type(x.sum(dtype=U32), I32)

    return jnp.stack([
        count(admitted), count(done),
        count(admitted & (rep.rtype == Reply.NOT_EXIST)),
        count(admitted & (rep.rtype == Reply.SPILL)),
        count(admitted & (rep.rtype == Reply.RETRY)),
        count(bad), count(is_get), count(batch.op == Op.SET),
        checksum(jnp.where(done, rep.ver, U32(0))),
        checksum(jnp.where(done[:, None], rep.val, U32(0)))])


# The columns a runner with the ordered run appends to ``STAT_NAMES``
# (``scan_stats``): the scan lanes, the INSERT lanes, the rows the scans
# returned and checksums mod 2^32 of those rows' keys (both words),
# versions and value words, so that every row a scan returns can be held
# to a reference without a row leaving the device.
SCAN_STAT_NAMES = ("scans", "inserts", "scan_rows", "scan_key_sum",
                   "scan_ver_sum", "scan_val_sum")


def scan_stats(batch: Batch, srep: ScanReplies):
    """The ``SCAN_STAT_NAMES`` columns of one step (i32); rows past a
    lane's count are zero in ``srep``, so every sum is over the rows
    returned."""
    def checksum(x):
        return jax.lax.bitcast_convert_type(x.sum(dtype=U32), I32)

    return jnp.stack([
        (batch.op == Op.SCAN).sum(dtype=I32),
        (batch.op == Op.INSERT).sum(dtype=I32),
        srep.count.sum(dtype=I32),
        checksum(srep.key_hi + srep.key_lo), checksum(srep.ver),
        checksum(srep.val)])


def build_serve_runner(n_keys: int, w: int = 4096,
                       cohorts_per_block: int = 8, val_words: int = 10,
                       read_frac: float = 0.5, scan_frac: float = 0.0,
                       max_scan_len: int = 8, scan_max: int = 8,
                       delta_cap: int | None = None,
                       hot_frac: float | None = None,
                       hot_prob: float | None = None,
                       theta: float | None = None,
                       use_scan: bool = False,
                       monitor: bool = False, trace: bool = False,
                       serve: bool = False, run_cap: int | None = None):
    """Serve-plane runner for the store engine, its normal path:
    jit(scan(step . gen)) over carry (table[, run][, counters]). Returns
    (run, init, drain) under the ServeEngine contract:
      run(carry, key[, occ, shed]) -> (carry', stats [cohorts_per_block,
                                                      N_STATS])
      init(db)   -> carry (attaches the ordered run when use_scan)
      drain(carry) -> (db, stats [1, N_STATS][, counters])

    Cohorts are generated ON DEVICE from the block key by
    ``build_generator`` (which see: ``read_frac``, ``theta``, the
    hot-prefix skew) or, with ``use_scan``, ``build_ordered_generator``;
    a block splits its key into one per step. Stats rows
    are ``STAT_NAMES`` (``reply_stats``): attempted = admitted lanes,
    committed = VAL/ACK replies (stale-scan RETRYs are NOT committed —
    the client re-sends after the rebuild).

    ``use_scan``: thread the ordered-run
    snapshot + delta overlay through every step and merge-compacts it
    at each block's drain boundary (dint.store.run_rebuild) — the run
    stays sorted without ever stalling the step. Its cohorts are YCSB-E's
    over HASHED keys (``build_ordered_generator``: ``scan_frac`` scans of
    uniform lengths in [1, max_scan_len] from ScrambledZipfian starts,
    clipped by the engine to ``scan_max``, the rest inserts of new key
    numbers; the table populated by ``build_populate(..., hashed=True)``),
    and the carry holds the next key number to insert after the run:
    (table, run, next_key[, counters]); ``init(db, run=None,
    next_key=None)`` takes a run and a next key number to go on from
    (default: ``from_table`` of ``run_cap`` rows, ``n_keys + 1``); the
    stats rows carry ``SCAN_STAT_NAMES`` after ``STAT_NAMES``. Off:
    Op.SCAN is never generated and the carry/jaxpr are unchanged from the
    point engine.

    ``serve``: variable-occupancy mode — run takes occ/shed i32
    [cohorts_per_block]; lanes >= occ are masked to NOP/PAD before the
    step (padded lanes, the serve reconciliation identity).

    ``trace`` is accepted for contract uniformity and ignored: the
    store engine has no txn ring.
    """
    del trace
    from ..monitor import counters as mon
    if use_scan:
        ordered = build_ordered_generator(
            n_keys, w, val_words=val_words, scan_frac=scan_frac,
            max_scan_len=max_scan_len,
            theta=0.99 if theta is None else theta)
    else:
        gen = build_generator(n_keys, w, val_words=val_words,
                              read_frac=read_frac, theta=theta,
                              hot_frac=hot_frac, hot_prob=hot_prob)
    n_cols = N_STATS + (len(SCAN_STAT_NAMES) if use_scan else 0)

    def scan_fn(carry, x):
        key, occ, shed = x if serve else (x, None, None)
        table = carry[0]
        run = carry[1] if use_scan else None
        cnt = carry[-1] if monitor else None
        with waves.part("store", "store_gen"):
            occ = jnp.asarray(w, I32) if occ is None else occ
            shed = I32(0) if shed is None else shed
            if use_scan:
                batch, nxt = ordered(key, carry[2], occ)
            else:
                batch = gen(key, occ)
        (table, rep, *scanned), live = _step(
            table, batch, maintain_bloom=False, run=run, scan_max=scan_max)
        run, srep = scanned if use_scan else (None, None)
        with waves.part("store", "stats"):
            stats = reply_stats(batch, rep)
            if use_scan:
                stats = jnp.concatenate([stats, scan_stats(batch, srep)])
        if monitor:
            with waves.part("store", "monitor"):
                # lanes whose key an earlier lane of the step carries: the
                # admitted lanes less their distinct keys (they sort
                # before the padding's all-ones key); ids have key_hi == 0
                if use_scan:
                    s_hi, skey = jax.lax.sort((batch.key_hi, batch.key_lo),
                                              num_keys=2)
                    fresh = jnp.concatenate([jnp.ones((1,), bool), (
                        skey[1:] != skey[:-1]) | (s_hi[1:] != s_hi[:-1])])
                else:
                    skey = jnp.sort(batch.key_lo)
                    fresh = jnp.concatenate(
                        [jnp.ones((1,), bool), skey[1:] != skey[:-1]])
                distinct = (fresh & (jnp.arange(w, dtype=I32) < occ)
                            ).sum(dtype=I32)
                cnt = mon.bump(cnt, {
                    mon.CTR_STEPS: 1,
                    mon.CTR_TXN_ATTEMPTED: stats[STAT_ATTEMPTED],
                    mon.CTR_TXN_COMMITTED: stats[STAT_COMMITTED],
                    mon.CTR_MAGIC_BAD: stats[STAT_MAGIC_BAD],
                    mon.CTR_SERVE_OCC_LANES: occ,
                    mon.CTR_SERVE_PAD_LANES: jnp.asarray(w, I32) - occ,
                    mon.CTR_SERVE_SHED_LANES: shed,
                    mon.CTR_STORE_GETS: stats[STAT_GETS],
                    mon.CTR_STORE_UPDATES: stats[STAT_UPDATES],
                    mon.CTR_STORE_NOT_EXIST: stats[STAT_NOT_EXIST],
                    mon.CTR_STORE_SPILL: stats[STAT_SPILL],
                    mon.CTR_STORE_DUP_LANES: occ - distinct,
                    **({mon.CTR_SCAN_REQUESTS:
                        (batch.op == Op.SCAN).sum(dtype=I32),
                        mon.CTR_SCAN_ROWS: srep.count.sum(dtype=I32),
                        mon.CTR_SCAN_DELTA_HITS:
                        srep.delta_hits.sum(dtype=I32),
                        mon.CTR_SCAN_WINDOW_ROWS:
                        (batch.op == Op.SCAN).sum(dtype=I32) * I32(
                            sum(run_mod.window_widths(run, scan_max))),
                        mon.CTR_STORE_INSERTS: (
                            (batch.op == Op.INSERT)
                            & (rep.rtype == Reply.ACK)).sum(dtype=I32)}
                       if use_scan else {}),
                    # the compacted install's first loop (where the shapes
                    # compact it): its trips, and the lanes it was for
                    **({mon.CTR_INSTALL_CHUNKS: live[0],
                        mon.CTR_INSTALL_WRITES: live[1]}
                       if live is not None else {}),
                })
        out = (table,) + ((run,) if use_scan else ()) \
            + ((nxt,) if use_scan else ()) + ((cnt,) if monitor else ())
        return out, stats

    def _post(carry):
        # block drain boundary: fold the overlay back into the run so
        # the NEXT block's scans start from a fresh (never-stale) view
        if use_scan:
            run = rebuild_run(carry[0], carry[1])
            carry = (carry[0], run) + carry[2:]
            if monitor:
                with waves.part("store", "monitor"):
                    carry = carry[:-1] + (mon.gauge_max(
                        carry[-1], {mon.CTR_RUN_ROWS: run.n}),)
        return carry

    def _keys(key):
        with waves.part("store", "block_pre"):
            return jax.random.split(key, cohorts_per_block)

    if serve:
        def block(carry, key, occ, shed):
            carry, stats = jax.lax.scan(scan_fn, carry,
                                        (_keys(key), occ, shed))
            return _post(carry), stats
    else:
        def block(carry, key):
            carry, stats = jax.lax.scan(scan_fn, carry, _keys(key))
            return _post(carry), stats

    def init(db, run=None, next_key=None):
        assert db.val_words == val_words, (db.val_words, val_words)
        base = (db,)
        if use_scan:
            ne = db.n_buckets * db.slots
            # default overlay: one wave's worth of distinct writes
            dcap = min(64, max(1, ne - scan_max)) if delta_cap is None \
                else int(delta_cap)
            if run is None:
                run = run_mod.from_table(db, delta_cap=dcap, cap=run_cap)
            base = base + (run, jnp.asarray(
                n_keys + 1 if next_key is None else next_key, U32))
        return base + ((mon.create(),) if monitor else ())

    @functools.partial(jax.jit, donate_argnums=0)
    def drain(carry):
        # nothing is in flight (the store step is unpipelined); the run
        # is derived state — dropped here, re-snapshot at next attach
        table = carry[0]
        cnt = carry[-1] if monitor else None
        zero = jnp.zeros((1, n_cols), I32)
        return (table, zero) + ((cnt,) if monitor else ())

    init.trace_cfg = None
    return jax.jit(block, donate_argnums=0), init, drain


# ------------------------------------------------- populate on the device


def build_populate(n_keys: int, n_buckets: int, w: int,
                   val_words: int = 10, slots: int = 4,
                   hashed: bool = False):
    """``populate() -> (table, spilled)``: keys 1..n_keys loaded on the
    device through the engine's own INSERT path, as the reference loads
    its server (store/ebpf/client_ebpf.cc:137 PopulateThread: INSERTs
    over the network, each thread its own contiguous range). Value word 0
    the key, word 1 STORE_MAGIC, version 1. Lane j is a populate thread
    over keys [j * steps + 1, (j + 1) * steps], ``steps = ceil(n_keys /
    w)``, so step i inserts key ``j * steps + i + 1`` in lane j (NOP past
    n_keys). Nothing table-sized is made on, or crosses to, the host.

    ``spilled`` (i32 scalar) counts INSERTs that found both candidate
    buckets full: such a key is in no table, so a deployment must hold
    it to zero. The greedy two-choice insert moves no resident key, and
    at load 0.36 of 2^24 x 4 slots about one key in 24 M finds both its
    buckets full, which one depending on the order of arrival (PERF.md
    section 6, PR 39).

    ``hashed``: key number k is stored under YCSB's ``hashing.fnv64(k)``
    (both words) and its value word 0 is the key's low word."""
    steps = -(-n_keys // w)

    def insert(table, i):
        klo = jnp.arange(w, dtype=U32) * U32(steps) + i.astype(U32) + U32(1)
        live = klo <= U32(n_keys)
        khi = U32(0)
        if hashed:
            khi, klo = hashing.fnv64(klo)
        val = jnp.zeros((w, val_words), U32)
        val = val.at[:, 0].set(klo).at[:, 1].set(U32(STORE_MAGIC))
        batch = Batch(
            op=jnp.where(live, I32(Op.INSERT), I32(Op.NOP)),
            table=jnp.zeros((w,), I32),
            key_hi=jnp.where(live, khi, U32(0xFFFFFFFF)),
            key_lo=jnp.where(live, klo, U32(0xFFFFFFFF)),
            val=val, ver=jnp.zeros((w,), U32))
        table, rep = step(table, batch)
        return table, (live & (rep.rtype != Reply.ACK)).sum(dtype=I32)

    @jax.jit
    def populate():
        table = kv.create(n_buckets, slots=slots, val_words=val_words)
        table, bad = jax.lax.scan(insert, table,
                                  jnp.arange(steps, dtype=I32))
        return table, bad.sum()

    return populate
