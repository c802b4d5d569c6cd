"""Ordered run: dense key-sorted snapshot of the store + delta overlay.

The reference serves every request packet-at-a-time through per-key hash
probes (store/ebpf/store_kern.c), so a range scan costs one random probe
per key — the one access pattern where the HBM-resident table should win
by an order of magnitude, because a scan over a sorted layout is a single
sequential DMA at memory bandwidth (DINT NSDI'24 leaves scans to the
userspace KVS; YCSB-E is the canonical workload). The `OrderedRun` is the
scan-serving companion of `tables.kv.KVTable`:

  * **run** — a dense key-sorted snapshot of the table's live records,
    struct-of-arrays and FLAT like the table itself (key_hi/key_lo/ver
    u32 [cap], val u32 [cap*VW] interleaved); rows past `n` keep the
    reserved PAD key 0xFFFFFFFF:FFFFFFFF so binary search needs no
    bounds plumbing. Contiguous key-adjacent rows are what a scan reads
    as one window (`scan_slab`).
  * **delta overlay** — a small key-sorted write-through buffer fed by
    `store.step`'s installs/deletes (upserts + tombstones, at most one
    entry per key, latest write wins). Scans merge run ∪ delta so the
    run snapshot never has to be rebuilt inside a step.
  * **rebuild** — `rebuild_run` merge-compacts run ∪ delta back into a
    dense sorted run in one batched on-device pass (two stable
    `lax.sort`s + gathers, no scatters), invoked at serve drain
    boundaries (serve/engine.py) so the run stays sorted without ever
    stalling the step. If the overlay ever overflowed (`stale`),
    `refresh` falls back to `from_table` — the overlay is best-effort
    capacity, never best-effort correctness: a stale run answers no
    scans (store.step replies RETRY) until rebuilt.

Sizing rule: a scan of `scan_max` rows gathers `scan_max + delta_cap`
contiguous run rows. Each overlay tombstone can shadow at most one run
row in the scanned range, so the overshoot window always covers the
first `scan_max` live keys of the merged view — the static price of
answering scans between rebuilds without dynamic shapes.
"""
from __future__ import annotations

import os

import flax.struct
import jax
import jax.numpy as jnp

from ..ops.u64 import U32
from . import kv

I32 = jnp.int32

# reserved pad key (both words): matches engines/types.PAD_KEY's u64 form
PAD_W = 0xFFFFFFFF


@flax.struct.dataclass
class OrderedRun:
    # dense sorted snapshot (rows >= n hold the PAD key, zero ver/val)
    key_hi: jax.Array     # u32 [cap]
    key_lo: jax.Array     # u32 [cap]
    ver: jax.Array        # u32 [cap]
    val: jax.Array        # u32 [cap*VW] interleaved
    n: jax.Array          # i32 [] live rows
    # key-sorted delta overlay (rows >= d_n hold the PAD key)
    d_key_hi: jax.Array   # u32 [dcap]
    d_key_lo: jax.Array   # u32 [dcap]
    d_ver: jax.Array      # u32 [dcap]
    d_val: jax.Array      # u32 [dcap*VW]
    d_tomb: jax.Array     # bool [dcap] — True: key deleted since snapshot
    d_seq: jax.Array      # u32 [dcap] — arrival stamp (latest wins)
    d_n: jax.Array        # i32 [] live overlay entries
    d_seq_next: jax.Array  # u32 [] next arrival stamp
    stale: jax.Array      # bool [] — overlay overflowed since last rebuild
    delta_cap: int = flax.struct.field(pytree_node=False, default=64)
    val_words: int = flax.struct.field(pytree_node=False, default=10)

    @property
    def cap(self):
        return self.key_hi.shape[0]


def create(cap: int, delta_cap: int = 64, val_words: int = 10) -> OrderedRun:
    assert cap >= 1 and delta_cap >= 1
    return OrderedRun(
        key_hi=jnp.full((cap,), PAD_W, U32),
        key_lo=jnp.full((cap,), PAD_W, U32),
        ver=jnp.zeros((cap,), U32),
        val=jnp.zeros((cap * val_words,), U32),
        n=I32(0),
        d_key_hi=jnp.full((delta_cap,), PAD_W, U32),
        d_key_lo=jnp.full((delta_cap,), PAD_W, U32),
        d_ver=jnp.zeros((delta_cap,), U32),
        d_val=jnp.zeros((delta_cap * val_words,), U32),
        d_tomb=jnp.zeros((delta_cap,), bool),
        d_seq=jnp.zeros((delta_cap,), U32),
        d_n=I32(0),
        d_seq_next=jnp.zeros((), U32),
        stale=jnp.zeros((), bool),
        delta_cap=delta_cap, val_words=val_words,
    )


def _word_idx(idx, vw: int):
    """Flat val word indices for row indices `idx` (any shape)."""
    return idx[..., None] * vw + jnp.arange(vw, dtype=I32)


def _compact(keys_hi, keys_lo, ver, val_rows, live, cap_out: int, vw: int):
    """Stable-compact `live` rows (already key-sorted) to the front of a
    cap_out-sized run layout: dead rows become PAD/zero so binary search
    sees one sorted array. Pure gathers — no scatters."""
    m = keys_hi.shape[0]
    iota = jnp.arange(m, dtype=I32)
    dead = (~live).astype(U32)
    _, perm = jax.lax.sort((dead, iota), num_keys=1)   # stable: keeps order
    take = perm[:cap_out]
    rank = jnp.arange(cap_out, dtype=I32)
    n_live = jnp.sum(live.astype(I32))
    ok = rank < n_live
    out_hi = jnp.where(ok, keys_hi[take], U32(PAD_W))
    out_lo = jnp.where(ok, keys_lo[take], U32(PAD_W))
    out_ver = jnp.where(ok, ver[take], U32(0))
    out_val = jnp.where(ok[:, None], val_rows[take], U32(0)).reshape(-1)
    return out_hi, out_lo, out_ver, out_val, n_live


def from_table(table: kv.KVTable, delta_cap: int = 64) -> OrderedRun:
    """Fresh snapshot: sort the table's live entries into a dense run
    (cap = the table's entry count, so the run can never overflow).
    Jittable — the serve plane calls this at drain boundaries when the
    overlay went stale."""
    ne = table.key_hi.shape[0]
    vw = table.val_words
    iota = jnp.arange(ne, dtype=I32)
    hi = jnp.where(table.valid, table.key_hi, U32(PAD_W))
    lo = jnp.where(table.valid, table.key_lo, U32(PAD_W))
    _, _, perm = jax.lax.sort((hi, lo, iota), num_keys=2)
    s_valid = table.valid[perm]
    out = _compact(hi[perm], lo[perm], table.ver[perm],
                   table.val.reshape(-1, vw)[perm], s_valid, ne, vw)
    run = create(ne, delta_cap, vw)
    return run.replace(key_hi=out[0], key_lo=out[1], ver=out[2],
                       val=out[3], n=out[4])


def rebuild_run(run: OrderedRun) -> OrderedRun:
    """Batched on-device merge-compact: fold the delta overlay into the
    run (upserts replace/insert rows, tombstones remove them) and clear
    the overlay. Two stable sorts + gathers over cap + delta_cap rows —
    the drain-boundary cost of keeping the run sorted without stalling
    the step. A stale run (overflowed overlay) cannot be repaired from
    the overlay; use `refresh`."""
    cap, dcap, vw = run.cap, run.delta_cap, run.val_words
    d_live = jnp.arange(dcap, dtype=I32) < run.d_n
    hi = jnp.concatenate([jnp.where(d_live, run.d_key_hi, U32(PAD_W)),
                          run.key_hi])
    lo = jnp.concatenate([jnp.where(d_live, run.d_key_lo, U32(PAD_W)),
                          run.key_lo])
    # delta rows sort BEFORE the run row of the same key (pref 0 < 1), so
    # the head of each key group is the overlay's latest word on that key
    pref = jnp.concatenate([jnp.zeros((dcap,), U32), jnp.ones((cap,), U32)])
    iota = jnp.arange(dcap + cap, dtype=I32)
    s_hi, s_lo, _, perm = jax.lax.sort((hi, lo, pref, iota), num_keys=3)
    head = jnp.concatenate([jnp.ones((1,), bool),
                            (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1])])
    valid = (s_hi != U32(PAD_W)) | (s_lo != U32(PAD_W))
    tomb = jnp.concatenate([run.d_tomb, jnp.zeros((cap,), bool)])[perm]
    live = head & valid & ~tomb
    ver = jnp.concatenate([run.d_ver, run.ver])[perm]
    val_rows = jnp.concatenate(
        [run.d_val.reshape(-1, vw), run.val.reshape(-1, vw)])[perm]
    out = _compact(s_hi, s_lo, ver, val_rows, live, cap, vw)
    fresh = create(cap, dcap, vw)
    return fresh.replace(key_hi=out[0], key_lo=out[1], ver=out[2],
                         val=out[3], n=jnp.minimum(out[4], I32(cap)))


def refresh(table: kv.KVTable, run: OrderedRun) -> OrderedRun:
    """The drain-boundary entry point: merge-compact when the overlay is
    intact, full re-snapshot from the authoritative table when it went
    stale. Both branches produce identical runs on an intact overlay
    (pinned in tests/test_run.py) — `stale` only ever trades compute."""
    assert run.cap == table.key_hi.shape[0], \
        "refresh expects a from_table-sized run"
    return jax.lax.cond(run.stale,
                        lambda: from_table(table, run.delta_cap),
                        lambda: rebuild_run(run))


def delta_append(run: OrderedRun, key_hi, key_lo, ver, val, tomb,
                 mask) -> OrderedRun:
    """Write-through append of one batch's effective writes (store.step's
    post-spill-fixup writer lanes: at most one per key). Re-sorts the
    overlay by key with latest-wins dedupe — the overlay invariant every
    scan's merge relies on. Overflow beyond delta_cap sets `stale`
    (dropped entries would otherwise silently vanish from scans); the
    run serves no scans until `refresh`.

    val arrives flat [r*VW] (interleaved, like the table's install
    operand)."""
    dcap, vw = run.delta_cap, run.val_words
    r = key_hi.shape[0]
    d_live = jnp.arange(dcap, dtype=I32) < run.d_n
    hi = jnp.concatenate([jnp.where(d_live, run.d_key_hi, U32(PAD_W)),
                          jnp.where(mask, key_hi.astype(U32), U32(PAD_W))])
    lo = jnp.concatenate([jnp.where(d_live, run.d_key_lo, U32(PAD_W)),
                          jnp.where(mask, key_lo.astype(U32), U32(PAD_W))])
    seq = jnp.concatenate([run.d_seq,
                           jnp.full((r,), 1, U32) * run.d_seq_next])
    # latest wins: sort by (key, ~seq) so the newest stamp heads its group
    iota = jnp.arange(dcap + r, dtype=I32)
    s_hi, s_lo, _, perm = jax.lax.sort((hi, lo, ~seq, iota), num_keys=3)
    head = jnp.concatenate([jnp.ones((1,), bool),
                            (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1])])
    valid = (s_hi != U32(PAD_W)) | (s_lo != U32(PAD_W))
    live = head & valid
    ver_c = jnp.concatenate([run.d_ver, ver.astype(U32)])[perm]
    tomb_c = jnp.concatenate([run.d_tomb, tomb])[perm]
    seq_c = seq[perm]
    val_rows = jnp.concatenate([run.d_val.reshape(-1, vw),
                                val.reshape(-1, vw)])[perm]
    out = _compact(s_hi, s_lo, ver_c, val_rows, live, dcap, vw)
    n_live = out[4]
    # _compact zeroes ver on dead rows; redo tomb/seq with the same perm
    dead = (~live).astype(U32)
    _, perm2 = jax.lax.sort((dead, jnp.arange(dcap + r, dtype=I32)),
                            num_keys=1)
    take = perm2[:dcap]
    ok = jnp.arange(dcap, dtype=I32) < n_live
    return run.replace(
        d_key_hi=out[0], d_key_lo=out[1], d_ver=out[2], d_val=out[3],
        d_tomb=jnp.where(ok, tomb_c[take], False),
        d_seq=jnp.where(ok, seq_c[take], U32(0)),
        d_n=jnp.minimum(n_live, I32(dcap)),
        d_seq_next=run.d_seq_next + U32(1),
        stale=run.stale | (n_live > dcap),
    )


def locate_bits(cap: int) -> int:
    """Binary-search depth over a cap-row run (geometry var `lg` in the
    dint.store.scan_locate wave formula)."""
    return max(1, int(cap).bit_length())


def locate(run: OrderedRun, q_hi, q_lo):
    """Lower bound: per lane, the first run offset whose key is >= the
    lane's start key. Branchless meta binary search — `locate_bits(cap)`
    rounds of two u32 point gathers per lane; rows past `n` hold the PAD
    key (the largest key), so no bounds vector rides along."""
    cap = run.cap
    pos = jnp.zeros(q_hi.shape, I32)
    for b in reversed(range(locate_bits(cap))):
        cand = pos + I32(1 << b)
        safe = jnp.minimum(cand, I32(cap)) - 1
        kh = run.key_hi[safe]
        kl = run.key_lo[safe]
        less = (kh < q_hi) | ((kh == q_hi) & (kl < q_lo))
        pos = jnp.where((cand <= cap) & less, cand, pos)
    return pos


def env_use_scan() -> bool:
    return os.environ.get("DINT_USE_SCAN", "0") not in ("", "0")


def resolve_use_scan(explicit: bool | None = None) -> bool:
    """Engine-builder gate for the dintscan ordered-run scan path:
    explicit kwarg wins, else the DINT_USE_SCAN env."""
    if explicit is None:
        return env_use_scan()
    return bool(explicit)


def scan_slab(run_hi, run_lo, run_ver, run_val, off, lg: int, vw: int):
    """The dintscan window gather: per-lane dynamic-slice-shaped gathers
    of `lg` contiguous run rows from offset `off`. Returns (hi, lo, ver
    [K, lg], val [K, lg, vw])."""
    off = off.astype(I32)
    idx = off[:, None] + jnp.arange(lg, dtype=I32)[None, :]
    widx = (idx * vw)[:, :, None] + jnp.arange(vw, dtype=I32)[None, None, :]
    return (run_hi[idx], run_lo[idx], run_ver[idx],
            run_val[widx])


def merge_scan(run: OrderedRun, slab_hi, slab_lo, slab_ver, slab_val,
               win_base, q_hi, q_lo, slen, scan_max: int):
    """Merge a gathered run window with the delta overlay into per-lane
    scan replies: the first `slen` live keys >= the start key of the
    merged (run ∪ delta) view.

    slab_* : [r, LG(, vw)] contiguous run rows starting at win_base (the
    clamped locate offset; LG = scan_max + delta_cap). Returns
    (count [r], hi/lo/ver [r, scan_max], val [r, scan_max, vw],
    delta_hits [r]); reply rows past count are zeroed."""
    vw = run.val_words
    dcap = run.delta_cap
    r, lg = slab_hi.shape
    d_live = jnp.arange(dcap, dtype=I32) < run.d_n

    # run rows shadowed by ANY overlay entry for the same key (upsert
    # replaces, tombstone removes); the overlay is tiny, so the flat
    # [r, LG, dcap] compare beats a second search pass
    sh = (d_live[None, None, :]
          & (slab_hi[:, :, None] == run.d_key_hi[None, None, :])
          & (slab_lo[:, :, None] == run.d_key_lo[None, None, :])).any(-1)
    row_idx = win_base[:, None] + jnp.arange(lg, dtype=I32)[None, :]
    run_ok = (row_idx < run.n) & ~sh & _ge(slab_hi, slab_lo, q_hi, q_lo)

    d_hi = jnp.broadcast_to(run.d_key_hi[None, :], (r, dcap))
    d_lo = jnp.broadcast_to(run.d_key_lo[None, :], (r, dcap))
    d_ok = (d_live[None, :] & ~run.d_tomb[None, :]
            & _ge(d_hi, d_lo, q_hi, q_lo))

    c_hi = jnp.concatenate([slab_hi, d_hi], axis=1)
    c_lo = jnp.concatenate([slab_lo, d_lo], axis=1)
    c_ok = jnp.concatenate([run_ok, d_ok], axis=1)
    c_delta = jnp.concatenate([jnp.zeros((r, lg), bool),
                               jnp.ones((r, dcap), bool)], axis=1)
    iota = jnp.broadcast_to(jnp.arange(lg + dcap, dtype=I32)[None, :],
                            (r, lg + dcap))
    bad = (~c_ok).astype(U32)
    s_bad, _, _, perm = jax.lax.sort(
        (bad, c_hi, c_lo, iota), num_keys=3, dimension=1)
    take = perm[:, :scan_max]
    lane = jnp.arange(r, dtype=I32)[:, None]
    n_ok = jnp.sum(c_ok.astype(I32), axis=1)
    count = jnp.minimum(slen.astype(I32), n_ok)
    keep = jnp.arange(scan_max, dtype=I32)[None, :] < count[:, None]

    out_hi = jnp.where(keep, c_hi[lane, take], U32(0))
    out_lo = jnp.where(keep, c_lo[lane, take], U32(0))
    c_ver = jnp.concatenate([slab_ver, jnp.broadcast_to(
        run.d_ver[None, :], (r, dcap))], axis=1)
    c_val = jnp.concatenate([slab_val, jnp.broadcast_to(
        run.d_val.reshape(1, dcap, vw), (r, dcap, vw))], axis=1)
    out_ver = jnp.where(keep, c_ver[lane, take], U32(0))
    out_val = jnp.where(keep[:, :, None], c_val[lane, take], U32(0))
    delta_hits = jnp.sum((keep & c_delta[lane, take]).astype(I32), axis=1)
    return count, out_hi, out_lo, out_ver, out_val, delta_hits


def _ge(hi, lo, q_hi, q_lo):
    qh = q_hi if hi.ndim == q_hi.ndim else q_hi[:, None]
    ql = q_lo if lo.ndim == q_lo.ndim else q_lo[:, None]
    return (hi > qh) | ((hi == qh) & (lo >= ql))


# ------------------------------------------------------------- host side


def to_items(run: OrderedRun):
    """Host-side merged view {key: (val tuple, ver)} — the oracle's
    vocabulary (testing/oracle.py), for differential tests."""
    import numpy as np
    vw = run.val_words
    out = {}
    n = int(run.n)
    hi = np.asarray(run.key_hi)[:n].astype(np.uint64)
    lo = np.asarray(run.key_lo)[:n].astype(np.uint64)
    ver = np.asarray(run.ver)[:n]
    val = np.asarray(run.val).reshape(-1, vw)[:n]
    for i in range(n):
        out[int((hi[i] << 32) | lo[i])] = (
            tuple(int(x) for x in val[i]), int(ver[i]))
    dn = int(run.d_n)
    d_hi = np.asarray(run.d_key_hi)[:dn].astype(np.uint64)
    d_lo = np.asarray(run.d_key_lo)[:dn].astype(np.uint64)
    d_ver = np.asarray(run.d_ver)[:dn]
    d_val = np.asarray(run.d_val).reshape(-1, vw)[:dn]
    d_tomb = np.asarray(run.d_tomb)[:dn]
    for i in range(dn):
        k = int((d_hi[i] << 32) | d_lo[i])
        if d_tomb[i]:
            out.pop(k, None)
        else:
            out[k] = (tuple(int(x) for x in d_val[i]), int(d_ver[i]))
    return out
