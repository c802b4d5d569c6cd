"""Ordered run: dense key-sorted snapshot of the store + delta overlay.

The reference serves every request packet-at-a-time through per-key hash
probes (store/ebpf/store_kern.c), so a range scan costs one random probe
per key; a scan over a sorted layout reads contiguous rows instead (DINT
NSDI'24 leaves scans to the userspace KVS; YCSB-E is the canonical
workload). The `OrderedRun` is the scan-serving companion of
`tables.kv.KVTable`:

  * **run** — a dense key-sorted snapshot of the table's live records,
    struct-of-arrays: key_hi/key_lo/ver u32 [cap] and the value words
    WORD-MAJOR and flat, val u32 [VW * cap], word j of row i at j * cap +
    i: on the TPU a [cap, VW] array is tiled to 128 words a row (12.8x
    its bytes) and a [VW, cap] one to 16 rows of words (1.6x), a 1-D one
    to nothing. Rows past `n` hold the reserved PAD key
    0xFFFFFFFF:FFFFFFFF, version 0 and zero words, so a binary search
    needs no bounds and a window past `n` reads PAD.
  * **delta overlay** — a small key-sorted write-through buffer fed by
    `store.step`'s elected writers (upserts + tombstones, at most one
    entry per key, the latest write wins). Scans merge run ∪ overlay, so
    the run is never rebuilt inside a step.
  * **rebuild** — `rebuild_run` folds the overlay into the run in one
    pass: each overlay entry's insertion point by binary search, prefix
    counts of the deletes and inserts before every run row, then the
    rows moved by those counts in log2(delta_cap) whole-array shift
    passes, the events' own rows scattered into place. O(cap) bytes a
    pass, no sort of the run, no gather. Invoked at block drain
    boundaries. If the overlay ever overflowed (`stale`),
    `refresh` re-snapshots with `from_table`: the overlay is best-effort
    capacity, never best-effort correctness; a stale run answers no scan
    (the step replies RETRY) until rebuilt.

A scan of `L <= scan_max` rows from start key q reads two windows: run
rows [p, p + WR) and overlay rows [pd, pd + WD) (p, pd the lower bounds
of q; WR = min(scan_max, cap), WD = min(scan_max, delta_cap)). Every key
below the window's HORIZON (the smaller of the first run key and the
first overlay key past the two windows) lies in them, so the merged
live keys below it are exact; the lane is answered when there are at
least L of them or nothing lies beyond (the horizon is PAD), else it
answers RETRY (tombstones can hide up to WR run rows of a window: no
deployment in this repo deletes under scans). No intermediate grows with
the overlay's size: the merge compares [r, WR, WD]. A run holds at most
`cap` live rows; past that it goes stale.
"""
from __future__ import annotations

import math

import flax.struct
import jax
import jax.numpy as jnp

from ..monitor import waves
from ..ops.u64 import U32
from . import kv

I32 = jnp.int32

# reserved pad key (both words): matches engines/types.PAD_KEY's u64 form
PAD_W = 0xFFFFFFFF
FROM_TABLE_ROWS = 1 << 16  # rows a trip of from_table's value gather


@flax.struct.dataclass
class OrderedRun:
    # dense sorted snapshot (rows >= n hold the PAD key, zero ver/val)
    key_hi: jax.Array     # u32 [cap]
    key_lo: jax.Array     # u32 [cap]
    ver: jax.Array        # u32 [cap]
    val: jax.Array        # u32 [VW * cap] word-major
    n: jax.Array          # i32 [] live rows
    # key-sorted delta overlay (rows >= d_n hold the PAD key)
    d_key_hi: jax.Array   # u32 [dcap]
    d_key_lo: jax.Array   # u32 [dcap]
    d_ver: jax.Array      # u32 [dcap]
    d_val: jax.Array      # u32 [VW * dcap] word-major
    d_tomb: jax.Array     # bool [dcap] — True: key deleted since snapshot
    d_n: jax.Array        # i32 [] live overlay entries
    stale: jax.Array      # bool [] — overlay (or run) overflowed
    delta_cap: int = flax.struct.field(pytree_node=False, default=64)
    val_words: int = flax.struct.field(pytree_node=False, default=10)

    @property
    def cap(self):
        return self.key_hi.shape[0]


def create(cap: int, delta_cap: int = 64, val_words: int = 10) -> OrderedRun:
    assert cap >= 2 and delta_cap >= 1
    return OrderedRun(
        key_hi=jnp.full((cap,), PAD_W, U32),
        key_lo=jnp.full((cap,), PAD_W, U32),
        ver=jnp.zeros((cap,), U32),
        val=jnp.zeros((val_words * cap,), U32),
        n=I32(0),
        d_key_hi=jnp.full((delta_cap,), PAD_W, U32),
        d_key_lo=jnp.full((delta_cap,), PAD_W, U32),
        d_ver=jnp.zeros((delta_cap,), U32),
        d_val=jnp.zeros((val_words * delta_cap,), U32),
        d_tomb=jnp.zeros((delta_cap,), bool),
        d_n=I32(0),
        stale=jnp.zeros((), bool),
        delta_cap=delta_cap, val_words=val_words,
    )


def _rows_word_major(val, idx, ne: int, vw: int, cap: int):
    """u32 [VW * cap] word-major: the table's value rows ``idx`` (its
    row-major [ne * VW] layout; ``idx >= ne`` gives zeros), a chunk of
    rows at a time, each row one contiguous VW-word slice."""
    rows = math.gcd(cap, FROM_TABLE_ROWS)

    def trip(i, out):
        ids = jax.lax.dynamic_slice(idx, (i * rows,), (rows,))
        ok = ids < ne
        start = jnp.minimum(ids, ne - 1) * vw
        got = jax.vmap(lambda s: jax.lax.dynamic_slice(val, (s,), (vw,)))(
            start)
        got = jnp.where(ok[:, None], got, U32(0))
        for j in range(vw):
            out = jax.lax.dynamic_update_slice(out, got[:, j],
                                               (j * cap + i * rows,))
        return out

    return jax.lax.fori_loop(0, cap // rows, trip,
                             jnp.zeros((vw * cap,), U32))


def from_table(table: kv.KVTable, delta_cap: int = 64,
               cap: int | None = None) -> OrderedRun:
    """Fresh snapshot: the table's live entries compacted first (the
    first ``cap`` of them in entry order), then sorted into a dense run
    of ``cap`` rows (default: the table's entry count). Nothing of the
    table's entry count but the compaction's running count is made. More
    live entries than ``cap`` leaves the run stale. Jittable —
    ``refresh`` calls it when the overlay went stale."""
    ne = table.key_hi.shape[0]
    vw = table.val_words
    cap = ne if cap is None else int(cap)
    idx = jnp.nonzero(table.valid, size=cap, fill_value=ne)[0].astype(I32)
    live = idx < ne
    at = jnp.minimum(idx, ne - 1)
    hi = jnp.where(live, table.key_hi[at], U32(PAD_W))
    lo = jnp.where(live, table.key_lo[at], U32(PAD_W))
    hi, lo, idx = jax.lax.sort((hi, lo, idx), num_keys=2)
    ok = idx < ne
    ver = jnp.where(ok, table.ver[jnp.minimum(idx, ne - 1)], U32(0))
    n_live = table.valid.sum(dtype=I32)
    run = create(cap, delta_cap, vw)
    return run.replace(key_hi=hi, key_lo=lo, ver=ver,
                       val=_rows_word_major(table.val, idx, ne, vw, cap),
                       n=ok.sum(dtype=I32),
                       stale=n_live > cap)


def rebuild_run(run: OrderedRun) -> OrderedRun:
    """Fold the overlay into the run in one pass and clear the overlay:
    an upsert of a run key replaces its row, of a new key inserts one, a
    tombstone of a run key removes its row (a tombstone of a key the run
    lacks does nothing). The result is what sorting run ∪ overlay would
    give, bit for bit (tests/test_run.py). A stale run cannot be repaired
    from its overlay: use ``refresh``. A run that would pass ``cap`` live
    rows comes out stale (and answers no scan).

    Every run row moves by a count: left by the deletes before it, then
    right by the inserts before it. Both counts only grow along the run,
    so each move is ``bits`` whole-array passes (``_shift_passes``): in
    pass k the rows whose count has bit k set move 2^k rows, the deletes'
    passes from the lowest bit (no deletes, no pass), the inserts' from
    the highest, and no two rows ever meet (tests/test_run.py). A pass is
    a select between each array and its copy shifted by a constant: no
    gather, the same bytes whatever the overlay holds. Then PAD past the
    new end and the events' own rows (an insert's, an update's)
    scattered into place."""
    cap, dcap, vw = run.cap, run.delta_cap, run.val_words
    bits = int(dcap).bit_length()
    with waves.part("store", "run_merge_rank"):
        j = jnp.arange(dcap, dtype=I32)
        pos = lower_bound(run.key_hi, run.key_lo, run.d_key_hi,
                          run.d_key_lo)
        at = jnp.minimum(pos, cap - 1)
        hit = (j < run.d_n) & (pos < run.n) \
            & (run.key_hi[at] == run.d_key_hi) \
            & (run.key_lo[at] == run.d_key_lo)
        ins = (j < run.d_n) & ~hit & ~run.d_tomb
        dele = hit & run.d_tomb
        n_del = dele.sum(dtype=I32)
        n_new = run.n + ins.sum(dtype=I32) - n_del
        # the overlay is in key order: an entry's own row lands past the
        # inserts less the deletes before it
        step = ins.astype(I32) - dele.astype(I32)
        to = jnp.where(ins | (hit & ~run.d_tomb),
                       pos + jnp.cumsum(step) - step, cap)
        # where an insert lands among the rows the deletes leave
        c_ins = jnp.where(ins, pos - (jnp.cumsum(dele, dtype=I32)
                                      - dele.astype(I32)), cap)
        row = jnp.arange(cap, dtype=I32)
        grow = jnp.cumsum(jnp.zeros((cap,), I32).at[c_ins].add(
            1, mode="drop"))

    with waves.part("store", "run_merge_move"):
        def compaction():
            gone = jnp.zeros((cap,), I32).at[jnp.where(dele, pos, cap)].add(
                1, mode="drop")
            return _landings(jnp.where((row < run.n) & (gone == 0),
                                       jnp.cumsum(gone) - gone, -1), bits, -1)

        lands_del = jax.lax.cond(n_del > 0, compaction,
                                 lambda: jnp.zeros((cap,), I32))
        lands = _landings(jnp.where(row < run.n - n_del, grow, -1), bits, 1)

        def moved(a, fill):
            a = jax.lax.cond(n_del > 0, lambda a: _shifted_by(
                a, lands_del, bits, -1), lambda a: a, a)
            return jnp.where(row < n_new, _shifted_by(a, lands, bits, 1),
                             fill)

        def word(w, val):
            # a value word's plane at a time, back into its own place
            plane = jax.lax.dynamic_slice(val, (w * cap,), (cap,))
            return jax.lax.dynamic_update_slice(val, moved(plane, U32(0)),
                                                (w * cap,))

        val = jax.lax.fori_loop(0, vw, word, run.val)
        w_to = jnp.where(to < cap, jnp.arange(vw, dtype=I32)[:, None] * cap
                         + to, vw * cap).reshape(-1)
        fresh = create(cap, dcap, vw)
        return fresh.replace(
            key_hi=moved(run.key_hi, U32(PAD_W)).at[to].set(
                run.d_key_hi, mode="drop", unique_indices=True),
            key_lo=moved(run.key_lo, U32(PAD_W)).at[to].set(
                run.d_key_lo, mode="drop", unique_indices=True),
            ver=moved(run.ver, U32(0)).at[to].set(
                run.d_ver, mode="drop", unique_indices=True),
            val=val.at[w_to].set(run.d_val, mode="drop",
                                 unique_indices=True),
            n=n_new, stale=n_new > cap)


def _landings(count, bits: int, sign: int):
    """The passes that move each row ``sign * count`` rows (``count``: i32,
    -1 where no row is, growing along the rows): right (``sign`` 1) from
    the highest of ``bits`` bits down, left (-1) from the lowest up, a
    pass moving the rows whose count has its bit set; rows that meet
    nobody. Returns i32, bit k set where a row lands in pass k: all
    ``_shifted_by`` needs to move any array the same way."""
    lands = jnp.zeros(count.shape, I32)
    for k in _pass_order(bits, sign):
        moves = (count >= 0) & ((count >> k) & 1 == 1)
        here = _shift(moves, 1 << k, sign, False)
        lands = lands | (here.astype(I32) << k)
        count = jnp.where(here, _shift(count, 1 << k, sign, I32(-1)),
                          jnp.where(moves, I32(-1), count))
    return lands


def _shifted_by(a, lands, bits: int, sign: int):
    """``a`` moved as ``_landings`` planned: in pass k, where bit k of
    ``lands`` is set, the row 2^k behind. Rows moved past an end are
    lost; a row left empty keeps what it held."""
    for k in _pass_order(bits, sign):
        a = jnp.where((lands >> k) & 1 == 1,
                      _shift(a, 1 << k, sign, a.dtype.type(0)), a)
    return a


def _pass_order(bits: int, sign: int):
    return range(bits - 1, -1, -1) if sign > 0 else range(bits)


def _shift(a, s: int, sign: int, fill):
    """``a`` moved ``s`` rows right (``sign`` 1) or left (-1), ``fill``
    where nothing came."""
    n = a.shape[0]
    s = min(s, n)
    pad = jnp.full((s,), fill, a.dtype)
    return (jnp.concatenate([pad, a[:n - s]]) if sign > 0
            else jnp.concatenate([a[s:], pad]))


def refresh(table: kv.KVTable, run: OrderedRun) -> OrderedRun:
    """The drain-boundary entry point: fold the overlay when it is
    intact, re-snapshot from the authoritative table when it went stale.
    Both give identical runs on an intact overlay (tests/test_run.py) —
    `stale` only ever trades compute."""
    def snapshot():
        with waves.part("store", "run_snapshot"):
            return from_table(table, run.delta_cap, run.cap)

    return jax.lax.cond(run.stale, snapshot, lambda: rebuild_run(run))


def delta_append(run: OrderedRun, key_hi, key_lo, ver, val, tomb,
                 mask) -> OrderedRun:
    """Write-through append of one batch's effective writes (store.step's
    elected writers: at most one per key). Re-sorts the overlay by key,
    the batch's write ahead of the overlay's older entry for its key, and
    keeps the first of each key: the overlay invariant every scan's merge
    relies on. Overflow beyond delta_cap sets `stale` (dropped entries
    would otherwise silently vanish from scans); the run serves no scan
    until `refresh`.

    val arrives [r, VW] or flat [r * VW] (interleaved, like the table's
    install operand)."""
    dcap, vw = run.delta_cap, run.val_words
    r = key_hi.shape[0]
    hi = jnp.concatenate([run.d_key_hi,
                          jnp.where(mask, key_hi.astype(U32), U32(PAD_W))])
    lo = jnp.concatenate([run.d_key_lo,
                          jnp.where(mask, key_lo.astype(U32), U32(PAD_W))])
    older = jnp.concatenate([jnp.ones((dcap,), U32), jnp.zeros((r,), U32)])
    iota = jnp.arange(dcap + r, dtype=I32)
    s_hi, s_lo, _, perm = jax.lax.sort((hi, lo, older, iota), num_keys=3)
    head = jnp.concatenate([jnp.ones((1,), bool),
                            (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1])])
    live = head & ((s_hi != U32(PAD_W)) | (s_lo != U32(PAD_W)))
    _, first = jax.lax.sort(((~live).astype(U32), iota), num_keys=1)
    take = perm[first[:dcap]]
    n_live = live.sum(dtype=I32)
    ok = jnp.arange(dcap, dtype=I32) < n_live
    vals = jnp.concatenate([run.d_val.reshape(vw, dcap),
                            val.reshape(r, vw).T.astype(U32)], axis=1)
    return run.replace(
        d_key_hi=jnp.where(ok, hi[take], U32(PAD_W)),
        d_key_lo=jnp.where(ok, lo[take], U32(PAD_W)),
        d_ver=jnp.where(ok, jnp.concatenate(
            [run.d_ver, ver.astype(U32)])[take], U32(0)),
        d_val=jnp.where(ok[None], vals[:, take], U32(0)).reshape(-1),
        d_tomb=ok & jnp.concatenate([run.d_tomb, tomb])[take],
        d_n=jnp.minimum(n_live, I32(dcap)),
        stale=run.stale | (n_live > dcap),
    )


def locate_bits(cap: int) -> int:
    """Binary-search depth over a cap-row run (geometry var `lg` in the
    dint.store.scan_locate wave formula)."""
    return max(1, int(cap).bit_length())


def lower_bound(key_hi, key_lo, q_hi, q_lo):
    """Per query, the first offset of the sorted (key_hi, key_lo) whose key
    is >= the query. Branchless meta binary search: `locate_bits(len)`
    rounds of two u32 point gathers per query; rows past the live ones
    hold the PAD key (the largest), so no bounds vector rides along."""
    cap = key_hi.shape[0]
    pos = jnp.zeros(q_hi.shape, I32)
    for b in reversed(range(locate_bits(cap))):
        cand = pos + I32(1 << b)
        safe = jnp.minimum(cand, I32(cap)) - 1
        kh = key_hi[safe]
        kl = key_lo[safe]
        less = (kh < q_hi) | ((kh == q_hi) & (kl < q_lo))
        pos = jnp.where((cand <= cap) & less, cand, pos)
    return pos


def locate(run: OrderedRun, q_hi, q_lo):
    """(run offset, overlay offset): each lane's lower bound in both."""
    return (lower_bound(run.key_hi, run.key_lo, q_hi, q_lo),
            lower_bound(run.d_key_hi, run.d_key_lo, q_hi, q_lo))


def window_widths(run: OrderedRun, scan_max: int) -> tuple:
    """(WR, WD): the run and overlay rows a scan lane reads."""
    return min(scan_max, run.cap), min(scan_max, run.delta_cap)


def _window(key_hi, key_lo, ver, val, off, width: int):
    """Rows [base, base + width) a lane, base = off clamped so that the
    window lies inside the arrays (rows below off are filtered by the
    merge's start-key test): dynamic_slice-shaped gathers, ``width``
    contiguous words a lane and array. Returns (hi, lo, ver [r, width],
    the VW value words [r, width] each, the horizon key: the first row
    past the window, PAD past the end)."""
    cap = key_hi.shape[0]
    base = jnp.clip(off, 0, cap - width).astype(I32)

    def rows(a, at=0):
        return jax.vmap(lambda b: jax.lax.dynamic_slice(
            a, (at + b,), (width,)))(base)

    vals = [rows(val, j * cap) for j in range(val.shape[0] // cap)]
    past = base + width
    beyond = past < cap
    at = jnp.minimum(past, cap - 1)
    h_hi = jnp.where(beyond, key_hi[at], U32(PAD_W))
    h_lo = jnp.where(beyond, key_lo[at], U32(PAD_W))
    return rows(key_hi), rows(key_lo), rows(ver), vals, (h_hi, h_lo)


def windows(run: OrderedRun, p, pd, scan_max: int):
    """The run's and the overlay's windows of every lane (``_window``),
    and the overlay window's tombstones."""
    wr, wd = window_widths(run, scan_max)
    rw = _window(run.key_hi, run.key_lo, run.ver, run.val, p, wr)
    dw = _window(run.d_key_hi, run.d_key_lo, run.d_ver, run.d_val, pd, wd)
    base = jnp.clip(pd, 0, run.delta_cap - wd).astype(I32)
    tomb = jax.vmap(lambda b: jax.lax.dynamic_slice(
        run.d_tomb, (b,), (wd,)))(base)
    return rw, dw, tomb


def _lt(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))


def merge(rw, dw, d_tomb, q_hi, q_lo, slen, scan_max: int):
    """Per lane, the first ``slen`` live keys >= (q_hi, q_lo) of run ∪
    overlay out of the two windows: run rows whose key the overlay window
    holds are shadowed (the overlay's entry answers, or its tombstone
    hides the key), rows at or past the horizon are not known complete.
    Returns (count, covered, hi, lo, ver [r, scan_max], val [r, VW,
    scan_max], delta_hits); rows past count are zero, and a lane not
    covered (fewer than slen live keys below a horizon short of the end)
    has count 0. Intermediates: [r, WR, WD] compares, [r, WR + WD] rows."""
    r_hi, r_lo, r_ver, r_val, (hr_hi, hr_lo) = rw
    d_hi, d_lo, d_ver, d_val, (hd_hi, hd_lo) = dw
    wr = r_hi.shape[1]
    lane_q = (q_hi[:, None], q_lo[:, None])
    shadow = ((r_hi[:, :, None] == d_hi[:, None, :])
              & (r_lo[:, :, None] == d_lo[:, None, :])).any(-1)
    d_first = _lt(hd_hi, hd_lo, hr_hi, hr_lo)
    h_hi = jnp.where(d_first, hd_hi, hr_hi)
    h_lo = jnp.where(d_first, hd_lo, hr_lo)
    c_hi = jnp.concatenate([r_hi, d_hi], axis=1)
    c_lo = jnp.concatenate([r_lo, d_lo], axis=1)
    good = (jnp.concatenate([~shadow, ~d_tomb], axis=1)
            & ~_lt(c_hi, c_lo, *lane_q)
            & _lt(c_hi, c_lo, h_hi[:, None], h_lo[:, None]))
    iota = jnp.broadcast_to(jnp.arange(c_hi.shape[1], dtype=I32)[None],
                            c_hi.shape)
    _, s_hi, s_lo, perm = jax.lax.sort(
        ((~good).astype(U32), c_hi, c_lo, iota), num_keys=3, dimension=1)
    n_good = good.sum(axis=1, dtype=I32)
    slen = slen.astype(I32)
    end = (h_hi == U32(PAD_W)) & (h_lo == U32(PAD_W))
    covered = (n_good >= slen) | end
    count = jnp.where(covered, jnp.minimum(slen, n_good), 0)
    keep = jnp.arange(scan_max, dtype=I32)[None] < count[:, None]
    take = perm[:, :scan_max]
    def taken(a, b):
        return jnp.take_along_axis(jnp.concatenate([a, b], axis=1), take,
                                   axis=1)

    out_ver = taken(r_ver, d_ver)
    out_val = jnp.stack([taken(a, b) for a, b in zip(r_val, d_val)],
                        axis=1)
    zero = U32(0)
    return (count, covered,
            jnp.where(keep, s_hi[:, :scan_max], zero),
            jnp.where(keep, s_lo[:, :scan_max], zero),
            jnp.where(keep, out_ver, zero),
            jnp.where(keep[:, None, :], out_val, zero),
            (keep & (take >= wr)).sum(axis=1, dtype=I32))


# ------------------------------------------------------------- host side


def to_items(run: OrderedRun):
    """Host-side merged view {key: (val tuple, ver)} — the oracle's
    vocabulary (testing/oracle.py), for differential tests."""
    import numpy as np
    out = {}
    n = int(run.n)
    hi = np.asarray(run.key_hi)[:n].astype(np.uint64)
    lo = np.asarray(run.key_lo)[:n].astype(np.uint64)
    ver = np.asarray(run.ver)[:n]
    val = np.asarray(run.val).reshape(run.val_words, -1)[:, :n].T
    for i in range(n):
        out[int((hi[i] << 32) | lo[i])] = (
            tuple(int(x) for x in val[i]), int(ver[i]))
    dn = int(run.d_n)
    d_hi = np.asarray(run.d_key_hi)[:dn].astype(np.uint64)
    d_lo = np.asarray(run.d_key_lo)[:dn].astype(np.uint64)
    d_ver = np.asarray(run.d_ver)[:dn]
    d_val = np.asarray(run.d_val).reshape(run.val_words, -1)[:, :dn].T
    d_tomb = np.asarray(run.d_tomb)[:dn]
    for i in range(dn):
        k = int((d_hi[i] << 32) | d_lo[i])
        if d_tomb[i]:
            out.pop(k, None)
        else:
            out[k] = (tuple(int(x) for x in d_val[i]), int(d_ver[i]))
    return out
