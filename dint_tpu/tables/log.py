"""Replication log: fixed-capacity multi-lane append-only rings.

TPU re-expression of the reference's per-CPU log rings
(`BPF_MAP_TYPE_PERCPU_ARRAY` of `struct log_entry {is_del, table, key, val,
ver}` + per-CPU counter, log_server/ebpf/ls_kern.c:26-38, append at :63-77;
userspace equivalents smallbank/udp/server_shard.cc:175-186).

Lanes replace CPUs: a batch's appends are distributed across L lanes, each
append gets slot = head[lane] + its arrival rank within the lane, and heads
advance by per-lane counts — all as one conflict-free scatter. Rings wrap,
exactly like the reference (ls_kern.c:72-73).

Entry layout (u32 words): [flags(is_del|table<<8), key_hi, key_lo, ver, val...]
"""
from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp

from ..monitor import waves
from ..ops import compact

I32 = jnp.int32
U32 = jnp.uint32

HDR_WORDS = 4


@flax.struct.dataclass
class LogRing:
    entries: jax.Array   # u32 [L, CAP, HDR_WORDS + VW]
    head: jax.Array      # u32 [L] (monotonic; slot = head % CAP)

    @property
    def lanes(self):
        return self.entries.shape[0]

    @property
    def capacity(self):
        return self.entries.shape[1]


def create(lanes: int, capacity: int, val_words: int = 10) -> LogRing:
    assert capacity & (capacity - 1) == 0
    return LogRing(entries=jnp.zeros((lanes, capacity, HDR_WORDS + val_words), U32),
                   head=jnp.zeros((lanes,), U32))


def append(ring: LogRing, do_append, table_id, is_del, key_hi, key_lo, ver, val):
    """Batched append. do_append: bool [R]; others [R]/[R, VW].

    Lane assignment is round-robin by lane index over the batch (the
    reference's per-CPU choice is likewise load-balancing, not semantic).
    Returns (ring', lane [R], slot [R]).
    """
    r = do_append.shape[0]
    lanes = ring.lanes
    cap = ring.capacity
    idx = jnp.arange(r, dtype=I32)
    lane = idx % lanes
    # rank of this request among appends in its lane (arrival order)
    one = do_append.astype(I32)
    # per-lane exclusive running count: segment by lane via scatter-free trick —
    # lane pattern is round-robin so lane l's appends are at positions l, l+L, ...
    # rank = (# of appends at positions j < i with j % L == l). Compute with a
    # cumulative sum per residue class using reshape (r must be multiple of L).
    pad = (-r) % lanes
    one_p = jnp.pad(one, (0, pad)).reshape(-1, lanes)            # [rows, L]
    excl = jnp.cumsum(one_p, axis=0) - one_p                     # [rows, L]
    rank = excl.reshape(-1)[:r]
    lane_counts = one_p.sum(axis=0).astype(U32)                  # [L]
    pos = ring.head[lane] + rank.astype(U32)
    slot = (pos % U32(cap)).astype(I32)

    flags = (is_del.astype(U32) | (table_id.astype(U32) << U32(8)))
    entry = jnp.concatenate(
        [flags[:, None], key_hi[:, None], key_lo[:, None], ver[:, None],
         val.astype(U32)], axis=1)
    # one writer per (lane, slot): per-lane ranks are distinct and a batch
    # appends << cap entries per lane, so slots cannot re-wrap in-batch;
    # masked lanes route to the out-of-range row `lanes` and drop
    safe_lane = jnp.where(do_append, lane, lanes)
    new_entries = ring.entries.at[safe_lane, slot].set(entry, mode="drop",
                                                       unique_indices=True)
    new_head = ring.head + lane_counts
    return ring.replace(entries=new_entries, head=new_head), lane, slot


# --------------------------------------------------------------------------
# Replicated flat ring: the dense engines' log x3.
#
# The reference replicates every log append to all 3 servers (CommitLog x3,
# tatp/caladan/client_ebpf_shard.cc:779-810) and the replicas are
# bit-identical by construction, so the dense engines keep ONE set of slots
# with the 3 replica entries packed side by side in the trailing word axis,
# written by a single row-major unique-index scatter — the same scatter
# discipline as their table installs (engines/tatp_dense.py module
# docstring). Two facts force this exact shape:
#   * the append is a FLAT 1-D row scatter (lane l's slots occupy rows
#     [l*cap, (l+1)*cap)): per-lane arrival ranks make the (lane, slot)
#     pairs provably distinct, which the flat row id turns into a plain
#     `unique_indices=True` declaration (round 7) — ~2 ms per 16 K appends
#     on v5e, where the historical [L, CAP] 2-D index form cost ~15 ms
#     before it carried the uniqueness declaration;
#   * a [slots, 3, EW] u32 array is tiled T(4,128) over its minor dims, so
#     each slot physically occupies 2 KB — 34 GB at 16M slots (observed
#     OOM). Packing replicas into the word axis pays the 128-lane padding
#     once per slot, not once per replica.
# --------------------------------------------------------------------------


@flax.struct.dataclass
class RepLog:
    entries: jax.Array   # u32 [L*CAP, S * (HDR_WORDS + VW)]
    head: jax.Array      # u32 [L] (monotonic; replicas identical)
    lanes: int = flax.struct.field(pytree_node=False, default=16)
    replicas: int = flax.struct.field(pytree_node=False, default=3)

    @property
    def entry_words(self):
        return self.entries.shape[1] // self.replicas

    @property
    def capacity(self):
        return self.entries.shape[0] // self.lanes


def create_rep(lanes: int, capacity: int, val_words: int = 10,
               replicas: int = 3) -> RepLog:
    assert capacity & (capacity - 1) == 0
    return RepLog(
        entries=jnp.zeros((lanes * capacity,
                           replicas * (HDR_WORDS + val_words)), U32),
        head=jnp.zeros((lanes,), U32), lanes=lanes, replicas=replicas)


def plan_rep(ring: RepLog, do_append, table_id, is_del, key_hi, key_lo,
             ver, val):
    """Plan a replicated append without writing: returns
    (flat [R] i32 row ids with -1 for masked lanes, entry3 [R, S*(HDR+VW)]
    u32 replica-packed rows, lane_counts u32 [L]). `append_rep` is exactly
    this plan + one unique-index row scatter + the head advance."""
    r = do_append.shape[0]
    lanes = ring.lanes
    cap = ring.capacity
    idx = jnp.arange(r, dtype=I32)
    lane = idx % lanes
    one = do_append.astype(I32)
    pad = (-r) % lanes
    one_p = jnp.pad(one, (0, pad)).reshape(-1, lanes)
    excl = jnp.cumsum(one_p, axis=0) - one_p
    rank = excl.reshape(-1)[:r]
    lane_counts = one_p.sum(axis=0).astype(U32)
    pos = ring.head[lane] + rank.astype(U32)
    slot = (pos % U32(cap)).astype(I32)
    flat = jnp.where(do_append, lane * cap + slot, -1)

    flags = (is_del.astype(U32) | (table_id.astype(U32) << U32(8)))
    entry = jnp.concatenate(
        [flags[:, None], key_hi[:, None], key_lo[:, None], ver[:, None],
         val.astype(U32)], axis=1)                        # [R, HDR+VW]
    entry3 = jnp.tile(entry, (1, ring.replicas))          # [R, S*(HDR+VW)]
    return flat, entry3, lane_counts


def append_rep(ring: RepLog, do_append, table_id, is_del, key_hi, key_lo,
               ver, val) -> RepLog:
    """Batched replicated append; same slot assignment as `append` (lane =
    round-robin, slot = head[lane] + arrival rank within the lane, rings
    wrap). One unique-index row scatter installs all replicas.

    ``do_append``: bool [R], or a ``compact.Live`` of it from a caller that
    has compacted the mask (ops/compact.py). The plan is made at full
    width either way, so lane, rank and slot, and with them the rings'
    bytes, are the same; under a ``Live`` the row scatter issues the live
    entries only, C lanes a chunk."""
    live = do_append if isinstance(do_append, compact.Live) else None
    with waves.part("log", "log_plan"):
        flat, entry3, lane_counts = plan_rep(
            ring, do_append if live is None else live.mask, table_id,
            is_del, key_hi, key_lo, ver, val)
    with waves.part("log", "log_scatter"):
        oob = ring.lanes * ring.capacity
        if live is None:
            widx = jnp.where(flat >= 0, flat, oob)
            new_entries = ring.entries.at[widx].set(entry3, mode="drop",
                                                    unique_indices=True)
        else:
            def scatter(entries, lanes, ok):
                return entries.at[jnp.where(ok, flat[lanes], oob)].set(
                    entry3[lanes], mode="drop", unique_indices=True)

            new_entries, _ = live.for_chunks(scatter, ring.entries)
        return ring.replace(entries=new_entries,
                            head=ring.head + lane_counts)


def append_rep_live(ring: RepLog, ranks, n_live, do_append, table_id, is_del,
                    key_hi, key_lo, ver, val) -> RepLog:
    """`append_rep` for a caller that has ranked ``do_append``
    (``ranks, n_live = compact.live_ranks(do_append)``) for its own chunk
    loops too."""
    return append_rep(ring, compact.Ranked(do_append, ranks, n_live),
                      table_id, is_del, key_hi, key_lo, ver, val)


def advance_watermark(ring: LogRing | RepLog, watermark, consumed):
    """Advance a ring's durability watermark after `consumed` entries per
    lane have been checkpointed or replayed downstream.

    The rings themselves wrap unconditionally, exactly like the
    reference's fixed per-CPU arrays (ls_kern.c:72-73): an append never
    blocks, and `recovery._flat_entries` refuses a wrapped ring because
    the overwritten prefix is gone. A caller that snapshots/replays its
    tables periodically owns a `watermark` u32 [L] ("entries below this
    head position are durable elsewhere") and advances it here; the ring
    is then bounded as long as head - watermark <= capacity between
    advances. No engine threads a watermark yet — that is the ROADMAP
    log-truncation item, and dintdur's `no-ring-truncation` check keys on
    exactly this call (the `jnp.minimum` clamp below is the TRUNCATED
    anchor in analysis/dataflow.py) to flag every ring that appends
    without one."""
    return jnp.minimum(ring.head, watermark + consumed.astype(U32))


def replica_entries(ring: RepLog, replica: int = 0):
    """One replica's slots in LogRing layout [L, CAP, HDR+VW] (the recovery
    path's input: any single surviving ring suffices)."""
    ew = ring.entry_words
    return ring.entries[:, replica * ew:(replica + 1) * ew].reshape(
        ring.lanes, ring.capacity, ew)
