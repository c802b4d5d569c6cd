"""HBM-resident bucketed hash table.

TPU re-expression of the reference's two storage layers collapsed into one:
the in-kernel cache (`struct cache_entry` {key[4], val[4][V], ver[4],
valid[4], dirty[4], bloom, lock}, /root/reference/store/ebpf/utils.h:58-66)
and the userspace chained KVS (store/ebpf/kvs.h:10-153). Here the table is
sized to hold the whole keyspace in HBM, so the fast path always "hits"
(capacity permitting); bucket overflow surfaces as a SPILL reply for a host
overflow store instead of an eviction protocol.

Layout (struct-of-arrays, S slots per bucket, ALL FLAT): entry
e = bucket*S + slot indexes
  key_hi/key_lo  u32 [NB*S]
  val            u32 [NB*S*VW]  interleaved (entry e's words at [e*VW, (e+1)*VW))
  ver            u32 [NB*S]
  valid          bool [NB*S]
  bloom_hi/lo    u32 [NB]       64-bit per-bucket bloom (negative lookups)

Flat 1-D layouts are a measured v5e requirement, not a style choice: XLA
tiles a trailing dim of S=4..16 (or VW=10) to 128 lanes, so the previous
[NB, S] / [NB, S, VW] arrays cost 512 B per slot — the reference's
24M-key store config (store/ebpf/utils.h:11-14) would need ~30 GB of HBM
for ~1.3 GB of data, and 2-D-index scatters serialize where flat
unique-index scatters do not (PERF.md; same finding that shaped
engines/tatp_dense.py).

The per-entry CAS `lock` word of the reference has no equivalent: intra-batch
conflicts are resolved deterministically (ops.segments), so the table needs no
locks at all.
"""
from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import hashing, segments, u64
from ..ops.u64 import U32
from . import dense

I32 = jnp.int32


@flax.struct.dataclass
class KVTable:
    key_hi: jax.Array     # u32 [NB*S]
    key_lo: jax.Array     # u32 [NB*S]
    val: jax.Array        # u32 [NB*S*VW] interleaved
    ver: jax.Array        # u32 [NB*S]
    valid: jax.Array      # bool [NB*S]
    bloom_hi: jax.Array   # u32 [NB]
    bloom_lo: jax.Array   # u32 [NB]
    slots: int = flax.struct.field(pytree_node=False, default=4)
    val_words: int = flax.struct.field(pytree_node=False, default=10)

    @property
    def n_buckets(self):
        return self.key_hi.shape[0] // self.slots

    @property
    def val2d(self):
        """[NB*S, VW] view for host-side dumps (not the hot path)."""
        return self.val.reshape(-1, self.val_words)


def create(n_buckets: int, slots: int = 4, val_words: int = 10) -> KVTable:
    assert n_buckets & (n_buckets - 1) == 0
    ne = n_buckets * slots
    assert ne * val_words < (1 << 31), "entry*VW overflows i32 flat indices"
    return KVTable(
        key_hi=jnp.zeros((ne,), U32),
        key_lo=jnp.zeros((ne,), U32),
        val=jnp.zeros((ne * val_words,), U32),
        ver=jnp.zeros((ne,), U32),
        valid=jnp.zeros((ne,), bool),
        bloom_hi=jnp.zeros((n_buckets,), U32),
        bloom_lo=jnp.zeros((n_buckets,), U32),
        slots=slots, val_words=val_words,
    )


def bucket_rows(table: KVTable, bkt):
    """Flat entry indices of each request's bucket row: [R, S]."""
    s = table.slots
    return bkt[:, None] * s + jnp.arange(s, dtype=I32)[None]


def entry_val(table: KVTable, eidx):
    """Gather entry values: eidx [R] -> [R, VW] (flat interleaved words)."""
    return table.val[dense.row_word_idx(eidx, table.val_words)]


def val_word_idx(table: KVTable, eidx):
    """Flat word indices [R*VW] for scattering whole entry values; pair
    with values.reshape(-1). OOB entry indices propagate to OOB words."""
    return dense.row_word_idx(eidx, table.val_words).reshape(-1)


def _match_bucket(table: KVTable, key_hi, key_lo, bkt):
    rows = bucket_rows(table, bkt)                    # [R, S]
    rows_hi = table.key_hi[rows]
    rows_lo = table.key_lo[rows]
    rows_valid = table.valid[rows]
    match = rows_valid & (rows_hi == key_hi[:, None]) & (rows_lo == key_lo[:, None])
    free = (~rows_valid).sum(axis=-1).astype(I32)
    return match.any(axis=-1), jnp.argmax(match, axis=-1).astype(I32), free


def probe_loc(table: KVTable, key_hi, key_lo, b1, b2):
    """Two-choice LOCATION probe: find each key in either candidate bucket
    without fetching its value (the store's step gathers the value under
    a trace part of its own, ``probe_val``).

    Returns (hit [R] bool, bkt [R] i32, slot [R] i32, free1 [R] i32,
    free2 [R] i32)."""
    hit1, slot1, free1 = _match_bucket(table, key_hi, key_lo, b1)
    hit2, slot2, free2 = _match_bucket(table, key_hi, key_lo, b2)
    hit = hit1 | hit2
    bkt = jnp.where(hit1, b1, b2)
    slot = jnp.where(hit1, slot1, slot2)
    return hit, bkt, slot, free1, free2


def probe(table: KVTable, key_hi, key_lo, b1, b2):
    """Two-choice probe: find each key in either of its two candidate buckets.

    Returns (hit [R] bool, bkt [R] i32, slot [R] i32, val [R, VW], ver [R],
    free1 [R] i32, free2 [R] i32). ``bkt``/``slot`` are the key's actual
    location when hit, arbitrary otherwise; free1/free2 are the candidate
    buckets' free-slot counts (reusing the gathers the probe already did).
    A key lives in at most one bucket (insert picks one).
    """
    hit, bkt, slot, free1, free2 = probe_loc(table, key_hi, key_lo, b1, b2)
    eidx = bkt * table.slots + slot
    val = entry_val(table, eidx)
    ver = table.ver[eidx]
    return hit, bkt, slot, val, ver, free1, free2


def bloom_maybe(table: KVTable, key_hi, key_lo, b1, b2):
    """True if either candidate bucket's bloom admits the key (one hash)."""
    bit = hashing.bloom_bit(key_hi, key_lo)           # [R] in [0, 64)
    use_hi = bit >= 32
    shift = jnp.where(use_hi, bit - 32, bit).astype(U32)

    def hit(b):
        word = jnp.where(use_hi, table.bloom_hi[b], table.bloom_lo[b])
        return ((word >> shift) & U32(1)) == U32(1)

    return hit(b1) | hit(b2)


def nth_free_slot(valid_rows, rank):
    """For each request: index of the (rank+1)-th free slot in its bucket row.

    valid_rows: bool [R, S]; rank: i32 [R].
    Returns (has_free [R] bool, slot [R] i32).
    """
    free = ~valid_rows
    cumfree = jnp.cumsum(free.astype(I32), axis=-1)
    want = free & (cumfree == (rank[:, None] + 1))
    has = want.any(axis=-1)
    slot = jnp.argmax(want, axis=-1).astype(I32)
    return has, slot


def recompute_bloom(table: KVTable, bkt, write_mask):
    """Recompute the 64-bit bloom word for each (masked) bucket from its live
    keys, and scatter back. Exact — unlike the reference, which can only OR
    bits in-kernel and recomputes in userspace on DELETE
    (tatp/ebpf/shard_user.c DELETE path)."""
    rows = bucket_rows(table, bkt)
    rows_hi = table.key_hi[rows]
    rows_lo = table.key_lo[rows]
    rows_valid = table.valid[rows]
    bit = hashing.bloom_bit(rows_hi, rows_lo)         # [R, S]
    hi_bits = jnp.where(rows_valid & (bit >= 32),
                        U32(1) << jnp.clip(bit - 32, 0, 31).astype(U32), U32(0))
    lo_bits = jnp.where(rows_valid & (bit < 32),
                        U32(1) << jnp.clip(bit, 0, 31).astype(U32), U32(0))
    new_hi = hi_bits[:, 0]
    new_lo = lo_bits[:, 0]
    for s in range(1, hi_bits.shape[1]):  # static, small S
        new_hi = new_hi | hi_bits[:, s]
        new_lo = new_lo | lo_bits[:, s]
    return table.replace(
        bloom_hi=segments.scatter_rows(table.bloom_hi, bkt, new_hi, write_mask),
        bloom_lo=segments.scatter_rows(table.bloom_lo, bkt, new_lo, write_mask),
    )


# ---------------------------------------------------------------- host-side


def to_dict(table: KVTable) -> dict:
    """Dump live entries to {key: (val tuple, ver)} for differential tests."""
    valid = np.asarray(table.valid)
    e = np.nonzero(valid)[0]
    keys = u64.join(np.asarray(table.key_hi)[e], np.asarray(table.key_lo)[e])
    vals = np.asarray(table.val).reshape(-1, table.val_words)[e]
    vers = np.asarray(table.ver)[e]
    return {int(k): (tuple(int(x) for x in v), int(ver))
            for k, v, ver in zip(keys, vals, vers)}


def _within_bucket_rank(bkt, priority=None):
    """Rank of each key within its bucket; `priority` randomizes which keys
    count as the overflow (essential for cuckoo rebalancing: victims must be
    random, or high-priority keys ping-pong without displacing residents)."""
    if priority is not None:
        order = np.lexsort((priority, bkt))
    else:
        order = np.argsort(bkt, kind="stable")
    sorted_bkt = bkt[order]
    start = np.concatenate([[True], sorted_bkt[1:] != sorted_bkt[:-1]])
    idx = np.arange(len(bkt))
    within_sorted = idx - np.maximum.accumulate(np.where(start, idx, 0))
    within = np.empty(len(bkt), np.int64)
    within[order] = within_sorted
    return within


def assign_two_choice(keys: np.ndarray, n_buckets: int, slots: int,
                      max_iters: int = 200):
    """Offline two-choice placement: per key, pick one of its two candidate
    buckets so no bucket exceeds `slots`. Parallel random-walk cuckoo:
    each iteration, keys that overflow their bucket flip to their alternate
    (with random damping), displacing others, until no bucket overflows.
    Converges comfortably up to ~0.85 load with 4-slot buckets (the parallel
    random walk slows well short of the (2,4)-cuckoo feasibility threshold of
    ~0.98) — far beyond single-choice hashing's Poisson tail. Size production
    tables at <= 0.75 load.

    Returns (bkt [N], slot [N]); raises if it cannot converge.
    """
    keys = np.asarray(keys, np.uint64)
    b1, b2 = hashing.bucket_pair_np(keys, n_buckets)
    rng = np.random.default_rng(0xD1A7)
    choice = np.zeros(len(keys), bool)   # False -> b1
    for _ in range(max_iters):
        cur = np.where(choice, b2, b1)
        within = _within_bucket_rank(cur, priority=rng.random(len(keys)))
        over = within >= slots
        if not over.any():
            return cur, within
        flip = over & (rng.random(len(keys)) < 0.7)
        choice ^= flip
    raise ValueError(
        f"two-choice placement did not converge: {len(keys)} keys into "
        f"{n_buckets} buckets x {slots} slots = {n_buckets * slots} capacity "
        f"(load {len(keys) / (n_buckets * slots):.2f}; need <~0.9 — grow "
        "cf_buckets / n_buckets)")


def populate(table: KVTable, keys: np.ndarray, vals: np.ndarray,
             vers: np.ndarray | None = None) -> KVTable:
    """Bulk-load a table host-side (numpy), like the reference's populate
    phase (smallbank/ebpf/shard_user.c:74-77, tatp/caladan/server_shard.cc:56-70).

    Two-choice placement; raises if the table genuinely cannot hold the
    keyspace (the reference instead sizes ad hoc, e.g. SAV_HASH_SIZE =
    ACCOUNT_NUM*3/2/4, smallbank/ebpf/utils.h:16-17, and relies on chaining).
    """
    nb, s = table.n_buckets, table.slots
    ne = nb * s
    keys = np.asarray(keys, np.uint64)
    if len(np.unique(keys)) != len(keys):
        raise ValueError("duplicate keys in populate")
    vals = np.asarray(vals, np.uint32)
    if vers is None:
        vers = np.ones(len(keys), np.uint32)
    bkt, slot = assign_two_choice(keys, nb, s)
    eidx = bkt * s + slot

    k_hi, k_lo = u64.split(keys)
    key_hi = np.zeros(ne, np.uint32)
    key_lo = np.zeros(ne, np.uint32)
    val = np.zeros((ne, table.val_words), np.uint32)
    ver = np.zeros(ne, np.uint32)
    valid = np.zeros(ne, bool)
    key_hi[eidx] = k_hi
    key_lo[eidx] = k_lo
    val[eidx] = vals
    ver[eidx] = vers
    valid[eidx] = True
    bits = hashing.bloom_bit_np(keys)
    bloom = np.zeros(nb, np.uint64)
    np.bitwise_or.at(bloom, bkt, np.uint64(1) << bits.astype(np.uint64))
    b_hi, b_lo = u64.split(bloom)
    return table.replace(
        key_hi=jnp.asarray(key_hi), key_lo=jnp.asarray(key_lo),
        val=jnp.asarray(val.reshape(-1)), ver=jnp.asarray(ver),
        valid=jnp.asarray(valid),
        bloom_hi=jnp.asarray(b_hi), bloom_lo=jnp.asarray(b_lo))
