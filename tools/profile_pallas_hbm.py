"""Pallas DMA-ring vs XLA random-access A/B microbench (HBM tables).

Round 3 blocked the Pallas route on VMEM-resident tables (Mosaic rejects
scalar VMEM stores; tools/profile_pallas.py). At reference scale the
tables are HBM-resident anyway (6.2 GB val / 0.6 GB meta), so the relevant
primitive is K random row reads from HBM — and since round 6 the
PRODUCTION kernels live in dint_tpu/ops/pallas_gather.py (this tool used
to carry its own copy; it now measures exactly what the engines run behind
DINT_USE_PALLAS=1).

Two modes:

* probe mode (default): one geometry, XLA gather vs `gather_rows`, human-
  readable timings. N now defaults to the VAL-SCALE row count (the full
  22*(n_sub+1) flat row space at the reference's n_sub=7e6 — 6.2 GB at
  VW=10): the round-5 advisor flagged that the old 0.6 GB default measured
  META-scale DMA behaviour only, and a speedup measured there must not be
  generalized to the 10x larger val table. The geometry is printed either
  way so no number can be misread.

* `--compare`: the A/B matrix a chip run records — both
  backends at BOTH production geometries (meta: VW=1, 0.6 GB; val: VW=10,
  6.2 GB; same row count, the real arrays' shapes) plus the fused
  lock-pass kernel vs its 3-op XLA chain on the meta-scale arb array.
  Emits ONE machine-parseable JSON line (artifact convention of bench.py).

Usage: python tools/profile_pallas_hbm.py [K] [N_rows] [VW]
           [--interpret] [--compare] [--fused] [--hot-frac F]

`--fused` adds the round-12 megakernel stage: per fusion site the unfused
PAIR of dispatches (lock_arbitrate + the meta gather/compare; the install
scatter + the log row-scatter) vs the single fused dispatch
(lock_validate; scatter_streams), outputs cross-checked, schema-stable
JSON with explicit nulls when a probe or section fails.

--interpret runs the kernels in pallas interpret mode (CPU-safe) at scaled-
down geometry: this reproduces the semantics validation (outputs equal
XLA's gather bit for bit), so a TPU failure is a Mosaic/compile issue, not
logic. Interpret-mode timings measure the INTERPRETER, not the hardware —
the JSON line says so.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

plat = os.environ.get("JAX_PLATFORMS")
if plat:
    jax.config.update("jax_platforms", plat)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dint_tpu.ops import pallas_gather as pg          # noqa: E402

# the reference's full flat row space: 22*(n_sub+1)+1 rows at n_sub=7e6
# (engines/tatp_dense.n_rows) — the row count of BOTH meta (VW=1, 0.6 GB)
# and val (VW=10, 6.2 GB)
VAL_SCALE_ROWS = 22 * (7_000_000 + 1) + 1

INTERPRET = "--interpret" in sys.argv
COMPARE = "--compare" in sys.argv
FUSED = "--fused" in sys.argv
HOT_FRAC = None
if "--hot-frac" in sys.argv:
    HOT_FRAC = float(sys.argv[sys.argv.index("--hot-frac") + 1])
    del sys.argv[sys.argv.index("--hot-frac"):
                 sys.argv.index("--hot-frac") + 2]
argv = [a for a in sys.argv if not a.startswith("--")]
K = int(argv[1]) if len(argv) > 1 else (256 if INTERPRET else 32_768)
N = int(argv[2]) if len(argv) > 2 else (10_000 if INTERPRET
                                        else VAL_SCALE_ROWS)
VW = int(argv[3]) if len(argv) > 3 else 10
ITERS = 2 if INTERPRET else 8
K_ARB = 18


def timeit(name, fn, *args, reps=3, count=None):
    try:
        out = fn(*args)
        np.asarray(jax.tree.leaves(out)[0][:8])
    except Exception as e:
        print(f"{name:24s} FAILED: {repr(e)[:300]}", flush=True)
        return None
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            out = fn(*args)
        np.asarray(jax.tree.leaves(out)[0][:8])
        best = min(best, (time.perf_counter() - t0) / ITERS)
    print(f"{name:24s} {best * 1e3:8.3f} ms per {count or K} indices",
          flush=True)
    return best


def xla_gather(tab, idx, vw):
    # production access pattern (tatp_dense.pipe_step wave-1 val reads)
    flat = (idx[:, None] * vw + jnp.arange(vw, dtype=jnp.int32)).reshape(-1)
    return tab[flat]


def xla_lock_chain(arb, rows, active, t):
    """The 3-op chain the fused kernel replaces (tatp_dense.pipe_step)."""
    m = rows.shape[0]
    oob = arb.shape[0]
    old = arb[rows]
    held = (old >> K_ARB) == (t - 1)
    packed = (t << K_ARB) | (jnp.uint32(m - 1)
                             - jnp.arange(m, dtype=jnp.uint32))
    cand = active & ~held
    arb2 = arb.at[jnp.where(cand, rows, oob)].max(packed, mode="drop")
    grant = cand & (arb2[rows] == packed)
    return arb2, grant


def ab_point(rng, n, vw, k):
    """One geometry: build the table, time XLA vs pallas, cross-check."""
    tab = jnp.asarray(rng.integers(0, 1 << 30, n * vw, np.int64)
                      .astype(np.uint32))
    idx = jnp.asarray(rng.integers(0, n, k).astype(np.int32))
    gb = n * vw * 4 / 1e9
    print(f"--- table [{n}*{vw}] u32 = {gb:.2f} GB, K={k} ---", flush=True)
    jit_x = jax.jit(xla_gather, static_argnums=2)
    x = timeit("xla gather", jit_x, tab, idx, vw, count=k)
    p = timeit("pallas dma-ring gather", pg.gather_rows, tab, idx, vw,
               count=k)
    equal = None
    if x and p:
        a = np.asarray(jit_x(tab, idx, vw))
        b = np.asarray(pg.gather_rows(tab, idx, vw))
        equal = bool(np.array_equal(a, b))
        print(f"outputs equal: {equal}   speedup: {x / p:.2f}x", flush=True)
    return {
        "rows": n, "vw": vw, "gb": round(gb, 3),
        "xla_ms": None if x is None else round(x * 1e3, 3),
        "pallas_ms": None if p is None else round(p * 1e3, 3),
        "speedup": None if not (x and p) else round(x / p, 2),
        "equal": equal,
        "error": None,
    }


def ab_lock(rng, n, m):
    """Fused lock pass vs the XLA 3-op chain on a meta-scale arb array.
    Both sides rebuild from the same base array each call; the delta is
    the chain cost (the copy cost is shared)."""
    arb = jnp.zeros((n + 1,), jnp.uint32)
    rows = jnp.asarray(rng.integers(0, n, m).astype(np.int32))
    act = jnp.asarray(rng.random(m) < 0.9)
    t = jnp.asarray(5, jnp.uint32)
    print(f"--- lock pass: arb [{n + 1}] u32, M={m} lanes ---", flush=True)
    jit_x = jax.jit(xla_lock_chain)
    x = timeit("xla 3-op lock chain", jit_x, arb, rows, act, t, count=m)
    p = timeit("pallas fused lock pass",
               lambda a, r, ac, tt: pg.lock_arbitrate(jnp.array(a), r, ac,
                                                      tt, K_ARB),
               arb, rows, act, t, count=m)
    equal = None
    if x and p:
        a2, g = jit_x(arb, rows, act, t)
        b2, gp = pg.lock_arbitrate(jnp.array(arb), rows, act, t, K_ARB)
        equal = bool(np.array_equal(np.asarray(a2), np.asarray(b2))
                     and np.array_equal(np.asarray(g),
                                        np.asarray(gp != 0)))
        print(f"outputs equal: {equal}   speedup: {x / p:.2f}x", flush=True)
    return {
        "lanes": m,
        "xla_ms": None if x is None else round(x * 1e3, 3),
        "pallas_ms": None if p is None else round(p * 1e3, 3),
        "speedup": None if not (x and p) else round(x / p, 2),
        "equal": equal,
        "error": None,
    }


def ab_hot(rng, n, vw, k, hot_frac, hot_prob=0.9):
    """The dintcache hot-tier point: a skewed index batch (hot_prob of
    lanes in the first hot_frac of rows — the SmallBank 90%/4% shape)
    served by XLA's gather, the plain DMA ring, and the VMEM hot-set
    kernel (gather_rows_hot with the mirror = table prefix). The hot
    kernel's win over the ring on this batch IS the hot tier's claim."""
    import jax.numpy as jnp

    hot_rows = max(1, int(n * hot_frac))
    tab = jnp.asarray(rng.integers(0, 1 << 30, n * vw, np.int64)
                      .astype(np.uint32))
    mirror = tab[:hot_rows * vw]
    is_hot = rng.random(k) < hot_prob
    idx = jnp.asarray(np.where(is_hot, rng.integers(0, hot_rows, k),
                               rng.integers(0, n, k)).astype(np.int32))
    midx = jnp.where(idx < hot_rows, idx, -1)
    gb = n * vw * 4 / 1e9
    mb = hot_rows * vw * 4 / 1e6
    print(f"--- hot point: table [{n}*{vw}] u32 = {gb:.2f} GB, mirror "
          f"{mb:.2f} MB ({hot_frac:.0%} of rows), K={k}, "
          f"{hot_prob:.0%} hot ---", flush=True)
    jit_x = jax.jit(xla_gather, static_argnums=2)
    x = timeit("xla gather", jit_x, tab, idx, vw, count=k)
    p = timeit("pallas dma-ring gather", pg.gather_rows, tab, idx, vw,
               count=k)
    h = timeit("pallas hot-set gather",
               lambda t, m, i, mi: pg.gather_rows_hot(t, m, i, mi, vw),
               tab, mirror, idx, midx, count=k)
    equal = None
    if x and h:
        a = np.asarray(jit_x(tab, idx, vw))
        b = np.asarray(pg.gather_rows_hot(tab, mirror, idx, midx, vw))
        equal = bool(np.array_equal(a, b))
        print(f"outputs equal: {equal}   vs xla: "
              f"{x / h:.2f}x   vs ring: "
              f"{(p / h if p else float('nan')):.2f}x", flush=True)
    return {
        "rows": n, "vw": vw, "gb": round(gb, 3),
        "hot_rows": hot_rows, "hot_frac": hot_frac,
        "hot_prob": hot_prob, "mirror_mb": round(mb, 3),
        "xla_ms": None if x is None else round(x * 1e3, 3),
        "ring_ms": None if p is None else round(p * 1e3, 3),
        "hot_ms": None if h is None else round(h * 1e3, 3),
        "speedup_vs_xla": None if not (x and h) else round(x / h, 2),
        "speedup_vs_ring": None if not (p and h) else round(p / h, 2),
        "equal": equal,
        "error": None,
    }


def _null_hot(n, vw, k, hot_frac, err):
    hot_rows = max(1, int(n * hot_frac))
    return {"rows": n, "vw": vw, "gb": round(n * vw * 4 / 1e9, 3),
            "hot_rows": hot_rows, "hot_frac": hot_frac, "hot_prob": 0.9,
            "mirror_mb": round(hot_rows * vw * 4 / 1e6, 3),
            "xla_ms": None, "ring_ms": None, "hot_ms": None,
            "speedup_vs_xla": None, "speedup_vs_ring": None,
            "equal": None, "error": repr(err)[:300]}


def ab_fused_lockv(rng, n, m, k):
    """Round-12 fusion site 1: the lock_arbitrate dispatch + the separate
    meta gather/compare dispatch (the unfused PAIR, both production
    paths) vs ONE lock_validate megakernel. Same operands, outputs
    cross-checked element for element — the megakernel's claim is one
    dispatch boundary and one grid, not different math."""
    arb = jnp.zeros((n + 1,), jnp.uint32)
    meta = jnp.asarray(rng.integers(0, 1 << 30, n, np.int64)
                       .astype(np.uint32))
    rows = jnp.asarray(rng.integers(0, n, m).astype(np.int32))
    act = jnp.asarray(rng.random(m) < 0.9)
    vidx = jnp.asarray(rng.integers(0, n, k).astype(np.int32))
    vv1 = jnp.where(jnp.asarray(rng.random(k) < 0.5), meta[vidx],
                    meta[vidx] + jnp.uint32(1))
    ridx = jnp.asarray(rng.integers(0, n, k).astype(np.int32))
    t = jnp.asarray(5, jnp.uint32)
    print(f"--- fused lock_validate: arb [{n + 1}] u32, M={m} lanes, "
          f"K={k} validate+read lanes ---", flush=True)

    @jax.jit
    def meta_side(meta, vidx, vv1, ridx):
        return (meta[vidx] != vv1).astype(jnp.uint32), meta[ridx]

    def unfused(a, me, vi, v1, ri, ro, ac, tt):
        arb2, grant = pg.lock_arbitrate(jnp.array(a), ro, ac, tt, K_ARB)
        vbad, rmeta = meta_side(me, vi, v1, ri)
        return arb2, grant, vbad, rmeta

    def fused(a, me, vi, v1, ri, ro, ac, tt):
        return pg.lock_validate(jnp.array(a), me, vi, v1, ri, ro, ac, tt,
                                K_ARB)

    u = timeit("unfused pair (2 disp)", unfused, arb, meta, vidx, vv1,
               ridx, rows, act, t, count=m + 2 * k)
    f = timeit("fused lock_validate", fused, arb, meta, vidx, vv1, ridx,
               rows, act, t, count=m + 2 * k)
    equal = None
    if u and f:
        ua = unfused(arb, meta, vidx, vv1, ridx, rows, act, t)
        fa = fused(arb, meta, vidx, vv1, ridx, rows, act, t)
        equal = bool(all(np.array_equal(np.asarray(x), np.asarray(y))
                         for x, y in zip(ua, fa)))
        print(f"outputs equal: {equal}   speedup: {u / f:.2f}x",
              flush=True)
    return {
        "lanes": m, "validate_lanes": k,
        "unfused_ms": None if u is None else round(u * 1e3, 3),
        "fused_ms": None if f is None else round(f * 1e3, 3),
        "speedup": None if not (u and f) else round(u / f, 2),
        "equal": equal,
        "error": None,
    }


def ab_fused_install(rng, n, vw, k, log_words=3 * (20 + 4 * 10) // 4):
    """Round-12 fusion site 2: the install scatter dispatch + the
    replication-log row-scatter dispatch (two XLA unique-index scatters,
    the production unfused path) vs ONE scatter_streams megakernel with
    the table and the log ring as two aliased output streams. Masked
    lanes carry idx = -1 on both sides."""
    cap = max(k * 2, 256)
    tab = jnp.asarray(rng.integers(0, 1 << 30, n * vw, np.int64)
                      .astype(np.uint32))
    logtab = jnp.zeros((cap * log_words,), jnp.uint32)
    lane = np.arange(k)
    mask = rng.random(k) < 0.8
    perm = rng.permutation(n)[:k]          # unique rows, engine contract
    idx = jnp.asarray(np.where(mask, perm, -1).astype(np.int32))
    widx = jnp.asarray(np.where(mask, lane % cap, -1).astype(np.int32))
    vals = jnp.asarray(rng.integers(0, 1 << 30, k * vw, np.int64)
                       .astype(np.uint32))
    entries = jnp.asarray(rng.integers(0, 1 << 30, k * log_words,
                                       np.int64).astype(np.uint32))
    gb = n * vw * 4 / 1e9
    print(f"--- fused install_log: table [{n}*{vw}] u32 = {gb:.2f} GB, "
          f"log ring [{cap}*{log_words}] u32, K={k} write lanes ---",
          flush=True)

    @jax.jit
    def unfused(tab, logtab, idx, widx, vals, entries):
        nrow = tab.shape[0] // vw
        flat = jnp.where(idx >= 0, idx, nrow)
        wf = (flat[:, None] * vw
              + jnp.arange(vw, dtype=jnp.int32)).reshape(-1)
        t2 = tab.at[wf].set(vals, mode="drop", unique_indices=True)
        lf = jnp.where(widx >= 0, widx, cap)
        wl = (lf[:, None] * log_words
              + jnp.arange(log_words, dtype=jnp.int32)).reshape(-1)
        l2 = logtab.at[wl].set(entries, mode="drop", unique_indices=True)
        return t2, l2

    def fused(tab, logtab, idx, widx, vals, entries):
        return pg.scatter_streams((jnp.array(tab), jnp.array(logtab)),
                                  (idx, widx), (vals, entries),
                                  (vw, log_words))

    u = timeit("unfused pair (2 scat)", unfused, tab, logtab, idx, widx,
               vals, entries, count=2 * k)
    f = timeit("fused scatter_streams", fused, tab, logtab, idx, widx,
               vals, entries, count=2 * k)
    equal = None
    if u and f:
        ua = unfused(tab, logtab, idx, widx, vals, entries)
        fa = fused(tab, logtab, idx, widx, vals, entries)
        equal = bool(all(np.array_equal(np.asarray(x), np.asarray(y))
                         for x, y in zip(ua, fa)))
        print(f"outputs equal: {equal}   speedup: {u / f:.2f}x",
              flush=True)
    return {
        "rows": n, "vw": vw, "gb": round(gb, 3),
        "log_words": log_words, "write_lanes": k,
        "unfused_ms": None if u is None else round(u * 1e3, 3),
        "fused_ms": None if f is None else round(f * 1e3, 3),
        "speedup": None if not (u and f) else round(u / f, 2),
        "equal": equal,
        "error": None,
    }


def _null_fused_lockv(m, k, err):
    return {"lanes": m, "validate_lanes": k, "unfused_ms": None,
            "fused_ms": None, "speedup": None, "equal": None,
            "error": repr(err)[:300]}


def _null_fused_install(n, vw, k, err):
    return {"rows": n, "vw": vw, "gb": round(n * vw * 4 / 1e9, 3),
            "log_words": 3 * (20 + 4 * 10) // 4, "write_lanes": k,
            "unfused_ms": None, "fused_ms": None, "speedup": None,
            "equal": None, "error": repr(err)[:300]}


def fused_stage(rng, rows, vw, k, m):
    """The --fused section: one record per round-12 fusion site, each
    schema-stable (explicit nulls + the failure reason when a probe or
    section dies — downstream parsing indexes the keys unconditionally).
    ``fused_available`` is the same probe-and-degrade verdict the engine
    builders consult (resolve_use_fused)."""
    try:
        avail = pg.fused_kernels_available(
            lockv=(min(k, 256), min(k, 256), min(m, 128), K_ARB, 0),
            scatters=((min(k, 128), vw), (min(k, 128), 4)))
    except Exception as e:  # noqa: BLE001 — the artifact records it
        print(f"fused probe FAILED: {repr(e)[:300]}", flush=True)
        avail = False
    try:
        lockv = ab_fused_lockv(rng, rows, m, k)
    except Exception as e:  # noqa: BLE001
        print(f"fused lock_validate point FAILED: {repr(e)[:300]}",
              flush=True)
        lockv = _null_fused_lockv(m, k, e)
    try:
        install = ab_fused_install(rng, rows, vw, min(k, m))
    except Exception as e:  # noqa: BLE001
        print(f"fused install_log point FAILED: {repr(e)[:300]}",
              flush=True)
        install = _null_fused_install(rows, vw, min(k, m), e)
    return {"fused_available": avail, "lock_validate": lockv,
            "install_log": install}


def _null_point(n, vw, k, err):
    """Schema-stable stand-in for an ab_point that died before measuring
    (table OOM, backend crash): every key the BENCH parser reads exists,
    with explicit nulls, plus the failure reason."""
    return {"rows": n, "vw": vw, "gb": round(n * vw * 4 / 1e9, 3),
            "xla_ms": None, "pallas_ms": None, "speedup": None,
            "equal": None, "error": repr(err)[:300]}


def _null_lock(m, err):
    return {"lanes": m, "xla_ms": None, "pallas_ms": None, "speedup": None,
            "equal": None, "error": repr(err)[:300]}


def main():
    rng = np.random.default_rng(0)
    if COMPARE:
        # interpret mode (CPU) cannot hold / cannot afford the real
        # geometries: scale rows down but keep the vw structure, and say so
        rows = 100_000 if INTERPRET else VAL_SCALE_ROWS
        k = 256 if INTERPRET else K
        m = 128 if INTERPRET else 16_384      # 2*w at the bench's w=8192
        if INTERPRET:
            print(f"[interpret mode: geometry scaled to {rows} rows — "
                  "timings measure the interpreter, not hardware]",
                  flush=True)
        # a failed section (OOM building a 6 GB table, a Mosaic rejection
        # escaping timeit's guard, a fallback to the XLA path) must DEGRADE
        # to explicit nulls in the one JSON line, never suppress it —
        # downstream BENCH parsing indexes these keys unconditionally
        try:
            meta = ab_point(rng, rows, 1, k)
        except Exception as e:  # noqa: BLE001 — the artifact records it
            print(f"meta point FAILED: {repr(e)[:300]}", flush=True)
            meta = _null_point(rows, 1, k, e)
        try:
            val = ab_point(rng, rows, VW, k)
        except Exception as e:  # noqa: BLE001
            print(f"val point FAILED: {repr(e)[:300]}", flush=True)
            val = _null_point(rows, VW, k, e)
        try:
            lock = ab_lock(rng, rows, m)
        except Exception as e:  # noqa: BLE001
            print(f"lock point FAILED: {repr(e)[:300]}", flush=True)
            lock = _null_lock(m, e)
        fused = None
        if FUSED:
            fused = fused_stage(rng, rows, VW, k, m)
        hot = None
        if HOT_FRAC is not None:
            # SmallBank geometry: the bal array is single-word rows; the
            # hot stage measures the skewed batch the workload generates
            try:
                hot = ab_hot(rng, rows, 1, k, HOT_FRAC)
            except Exception as e:  # noqa: BLE001
                print(f"hot point FAILED: {repr(e)[:300]}", flush=True)
                hot = _null_hot(rows, 1, k, HOT_FRAC, e)
        out = {
            "metric": "pallas_gather_ab",
            "k": k,
            "interpret": INTERPRET,
            "backend": jax.default_backend(),
            "pallas_available": pg.kernels_available(
                n_idx=min(k, 512), m_lock=min(m, 128), k_arb=K_ARB),
            "meta": meta,
            "val": val,
            "lock": lock,
            # present iff --hot-frac was passed (schema-stable otherwise:
            # consumers see the key with explicit null)
            "hot": hot,
            # present iff --fused was passed, same convention
            "fused": fused,
        }
        print(json.dumps(out), flush=True)
        return

    if FUSED:
        m = 128 if INTERPRET else 16_384
        fused_stage(rng, N, VW, min(K, N), m)
        return

    if HOT_FRAC is not None:
        ab_hot(rng, N, VW, K, HOT_FRAC)
        return

    if N == VAL_SCALE_ROWS and VW == 10:
        print("probing at VAL scale (6.2 GB); pass N_rows to override "
              "(the old default probed meta scale, 0.6 GB)", flush=True)
    else:
        print(f"probing at {N * VW * 4 / 1e9:.2f} GB — NOT the 6.2 GB "
              "val-scale geometry; do not generalize this speedup",
              flush=True)
    ab_point(rng, N, VW, K)


if __name__ == "__main__":
    main()
