"""Cache-mode store: device-resident cache over a host backing KVS.

TPU equivalent of the reference's defining kernel/user split (SURVEY.md §3.1,
§3.2): the XDP program owns a fixed-size 4-way single-hash cache
(`struct cache_entry`, store/ebpf/utils.h:58-66) and answers hits at the NIC;
misses travel to a userspace KVS worker (store/ebpf/store_user.c:99-168) with
the evicted dirty record piggybacked (`ext_message`), and the TC egress hook
installs the fetched record into the cache on the way back
(store/ebpf/store_kern.c:302-372).

Here the device (HBM) cache is a `tables.kv.KVTable` + dirty bitmap; the
backing store is `shim.host_kvs.HostKVS`. One `cache_step` certifies a batch
against the cache and emits a miss vector; the host resolves misses and
queues refill records; `refill` installs them next step (the TC equivalent),
returning evicted dirty records for host write-back.

Three policies, matching the reference's ablation servers:
  WB_BLOOM    write-back + per-bucket bloom negatives  (#1, store_kern.c)
  WB_NOBLOOM  write-back, miss on every absent key     (#2, store_wb_kern.c)
  WT          write-through: GET served from cache; SET invalidates the
              cached slot and passes through            (#3, store_wt_kern.c:115-151)

Batch semantics: per key segment, GETs see pre-batch cache state, writes
apply in lane order (the store.step contract). If ANY lane of a key segment
misses, the WHOLE segment is deferred to the host (reply MISS), which
resolves it sequentially — coarser than the reference's per-packet
interleaving but serial-equivalent. INSERTs always defer to the host (the
reference's write-allocate happens on the refill path here; the
write-through variant's in-kernel clean-slot fill, store_wt_kern.c:153-196,
is subsumed by refill).
"""
from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp

from ..ops import hashing, segments
from ..tables import kv
from .types import Batch, Op, Replies, Reply

I32 = jnp.int32
U32 = jnp.uint32

WB_BLOOM = "wb_bloom"
WB_NOBLOOM = "wb_nobloom"
WT = "wt"
POLICIES = (WB_BLOOM, WB_NOBLOOM, WT)

# reply code for "deferred to host" lanes (internal to the cache server;
# never hits the wire — the host overwrites it before replying)
MISS = 100


@flax.struct.dataclass
class CacheTable:
    kv: kv.KVTable
    dirty: jax.Array      # bool [NB*S] (flat entries, like kv.KVTable)
    clock: jax.Array      # u32 [] victim rotor (reference picks by slot scan)


def create(n_buckets: int, slots: int = 4,
           val_words: int = 10) -> CacheTable:
    return CacheTable(kv=kv.create(n_buckets, slots, val_words),
                      dirty=jnp.zeros((n_buckets * slots,), bool),
                      clock=U32(0))


def _probe1(t: kv.KVTable, key_hi, key_lo, bkt):
    """Single-hash probe (the reference cache is single-hash 4-way)."""
    rows = kv.bucket_rows(t, bkt)
    rows_hi = t.key_hi[rows]
    rows_lo = t.key_lo[rows]
    rows_valid = t.valid[rows]
    match = rows_valid & (rows_hi == key_hi[:, None]) & (rows_lo == key_lo[:, None])
    hit = match.any(axis=-1)
    slot = jnp.argmax(match, axis=-1).astype(I32)
    eidx = bkt * t.slots + slot
    return hit, slot, kv.entry_val(t, eidx), t.ver[eidx]


def cache_step(cache: CacheTable, batch: Batch, *, policy: str = WB_BLOOM):
    """Certify a batch against the cache.

    Returns (cache', replies, miss, flush):
      miss: bool [R] — lanes the host must resolve (whole key segments;
        replies there carry rtype == MISS).
      flush: dict {mask, key_hi, key_lo, val, ver} — dirty cached records of
        deferred segments, invalidated here; the host MUST apply these as
        write-backs *before* resolving the miss lanes, or it would serve the
        deferred segment from stale backing data (the reference's analogue:
        the evicted dirty record rides the ext_message to userspace and is
        applied before the miss is served, store/ebpf/store_user.c:99-168).
    """
    assert policy in POLICIES
    r = batch.width
    t = cache.kv
    sb = segments.sort_batch(batch.key_hi, batch.key_lo)
    op = batch.op[sb.perm]
    val_in = batch.val[sb.perm]

    bkt = hashing.bucket(sb.key_hi, sb.key_lo, t.n_buckets)
    hit0, slot0, val0, ver0 = _probe1(t, sb.key_hi, sb.key_lo, bkt)

    is_get = op == Op.GET
    is_set = op == Op.SET
    is_ins = op == Op.INSERT
    is_del = op == Op.DELETE
    used = op != Op.NOP

    if policy == WB_BLOOM:
        absent = ~kv.bloom_maybe(t, sb.key_hi, sb.key_lo, bkt, bkt)
    else:
        absent = jnp.zeros((r,), bool)

    # lanes that can be served from the cache alone
    local_get = is_get & (hit0 | absent)
    local_set = is_set & hit0 if policy != WT else jnp.zeros((r,), bool)
    local = local_get | local_set
    # INSERT/DELETE and anything else — including Op.SCAN (round-20
    # dintscan): range scans need the ORDERED run over the full
    # keyspace, which only the authoritative store owns; a cache holds
    # an arbitrary working-set subset, so scan lanes always defer and
    # the host resolves them against the backing KVS — defers to the host
    lane_miss = used & ~local
    # whole-segment deferral: one miss lane defers its key's every lane
    seg_miss = segments.seg_any(sb, lane_miss)
    miss = used & seg_miss

    # ---- cache-local semantics on fully-hit segments ----------------------
    n_set_before = segments.seg_cumsum_excl(sb, is_set.astype(I32))
    n_set_total = segments.seg_sum(sb, is_set.astype(I32))
    last_s = segments.seg_max_where(sb, is_set, sb.rank, I32(-1))
    pos_last = jnp.clip(sb.head_pos + last_s, 0, r - 1)

    rtype = jnp.full((r,), Reply.NONE, I32)
    rtype = jnp.where(is_get & hit0, Reply.VAL, rtype)
    rtype = jnp.where(is_get & absent & ~hit0, Reply.NOT_EXIST, rtype)
    rtype = jnp.where(is_set, Reply.ACK, rtype)
    rtype = jnp.where(miss, MISS, rtype)
    rval = jnp.where((is_get & hit0 & ~miss)[:, None], val0, jnp.zeros_like(val0))
    rver = jnp.where(is_get & hit0 & ~miss, ver0, U32(0))
    rver = jnp.where(is_set & ~miss, ver0 + (n_set_before + 1).astype(U32), rver)

    # ---- cache mutations ---------------------------------------------------
    # 1. any deferred segment drops its cached copy (and flushes it if dirty)
    #    so the host resolves against fresh backing data; covers the
    #    write-through SET invalidate (store_wt_kern.c:115-151) and the
    #    delete/insert paths in one rule.
    inval = sb.last & seg_miss & hit0
    flush_mask = inval & cache.dirty[bkt * t.slots + slot0]
    flush = {
        "mask": flush_mask,
        "key_hi": sb.key_hi.astype(U32), "key_lo": sb.key_lo.astype(U32),
        "val": val0, "ver": ver0,
    }
    ne = t.n_buckets * t.slots
    e_i = jnp.where(inval, bkt * t.slots + slot0, ne)
    cache = cache.replace(
        kv=t.replace(valid=t.valid.at[e_i].set(False, mode="drop")),
        dirty=cache.dirty.at[e_i].set(False, mode="drop"))

    # 2. write-back: the segment-last lane of a fully-local segment installs
    #    the last SET's value and marks the slot dirty
    if policy != WT:
        t2 = cache.kv
        writer = sb.last & ~seg_miss & (last_s >= 0) & hit0
        new_ver = ver0 + n_set_total.astype(U32)
        e_w = jnp.where(writer, bkt * t2.slots + slot0,
                        t2.n_buckets * t2.slots)
        cache = cache.replace(
            kv=t2.replace(
                val=t2.val.at[kv.val_word_idx(t2, e_w)].set(
                    val_in[pos_last].reshape(-1), mode="drop"),
                ver=t2.ver.at[e_w].set(new_ver, mode="drop"),
            ),
            dirty=cache.dirty.at[e_w].set(True, mode="drop"),
        )

    o_rtype, o_rver, o_miss = segments.unsort(sb, rtype, rver, miss)
    o_rval = segments.unsort(sb, rval)
    return (cache, Replies(rtype=o_rtype, val=o_rval, ver=o_rver), o_miss,
            flush)


def refill(cache: CacheTable, key_hi, key_lo, val, ver, bloom_hi, bloom_lo,
           mask):
    """Install host-fetched records (the TC-egress equivalent,
    store_kern.c:302-372) and set each touched bucket's bloom word (the
    DELETE-path bloom handoff, tatp/ebpf/shard_kern.c:1186-1192).

    mask: bool [R] — lanes carrying a record. ver == 0 means "no record;
    just install the bloom word" (pure bloom refresh after DELETE).
    Victim choice: first invalid slot, else clock rotor over slots (the
    reference scans for invalid then overwrites, store_kern.c:208-246).
    Returns (cache', evicted dict) — evicted dirty records for host
    write-back (the ext_message ver1==1 protocol, store/ebpf/store_user.c:99-168).
    """
    t = cache.kv
    r = key_hi.shape[0]
    bkt = hashing.bucket(key_hi, key_lo, t.n_buckets)
    # one install per bucket per call (host guarantees: it dedups refills);
    # serialize same-bucket installs by keeping only the first
    sb = segments.sort_batch(jnp.zeros((r,), U32), bkt.astype(U32))
    first = sb.head
    m = mask[sb.perm] & first
    keep = segments.unsort(sb, m)

    has_rec = keep & (ver != 0)
    hit, slot_h, _, _ = _probe1(t, key_hi, key_lo, bkt)
    rows_valid = t.valid[kv.bucket_rows(t, bkt)]
    free_any = (~rows_valid).any(axis=-1)
    first_free = jnp.argmax(~rows_valid, axis=-1).astype(I32)
    rotor = ((cache.clock + jnp.arange(r, dtype=U32)) % U32(t.slots)).astype(I32)
    victim = jnp.where(hit, slot_h, jnp.where(free_any, first_free, rotor))
    e_vic = bkt * t.slots + victim

    ev_valid = has_rec & ~hit & ~free_any
    ev_dirty = ev_valid & cache.dirty[e_vic]
    evicted = {
        "mask": ev_dirty,
        "key_hi": t.key_hi[e_vic], "key_lo": t.key_lo[e_vic],
        "val": kv.entry_val(t, e_vic), "ver": t.ver[e_vic],
    }

    ne = t.n_buckets * t.slots
    e_r = jnp.where(has_rec, e_vic, ne)
    val_new = t.val.at[kv.val_word_idx(t, e_r)].set(
        val.reshape(-1), mode="drop")
    ver_new = t.ver.at[e_r].set(ver, mode="drop")
    new = t.replace(
        key_hi=t.key_hi.at[e_r].set(key_hi.astype(U32), mode="drop"),
        key_lo=t.key_lo.at[e_r].set(key_lo.astype(U32), mode="drop"),
        val=val_new,
        ver=ver_new,
        valid=t.valid.at[e_r].set(True, mode="drop"),
    )
    safe_bloom = jnp.where(keep, bkt, t.n_buckets)
    new = new.replace(
        bloom_hi=new.bloom_hi.at[safe_bloom].set(bloom_hi, mode="drop"),
        bloom_lo=new.bloom_lo.at[safe_bloom].set(bloom_lo, mode="drop"),
    )
    dirty = cache.dirty.at[e_r].set(False, mode="drop")
    return cache.replace(kv=new, dirty=dirty,
                         clock=cache.clock + U32(1)), evicted
