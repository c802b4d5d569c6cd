"""Component-level timing of the dense TATP pipe_step on the live backend.

Times each cost center of engines/tatp_dense.pipe_step in isolation (same
shapes as the headline bench: n_sub=1e5, w=8192) with a scan of ITERS
iterations per measurement so per-dispatch overhead amortizes, then the
full pipe_step for comparison. Prints one line per component: name, ms per
iteration. Syncs by fetching ONLY a tiny probe — fetching any output of
the executable waits for the whole dispatch, and a full-carry fetch would
drag the log ring to the host and time the transfer, not the device.

Usage: python tools/profile_dense.py [w] [n_sub]
"""
from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

plat = os.environ.get("JAX_PLATFORMS")
if plat:
    jax.config.update("jax_platforms", plat)

from dint_tpu.engines import tatp_dense as td
from dint_tpu.engines.tatp_pipeline import K, gen_cohort
from dint_tpu.tables import log as logring

I32 = jnp.int32
U32 = jnp.uint32

W = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
N_SUB = int(sys.argv[2]) if len(sys.argv) > 2 else 100_000
VW = 10
ITERS = 16
BIG = jnp.int32(1 << 30)


def timeit(name, fn, *args, reps: int = 3):
    def body(carry, _):
        return fn(carry), 0

    @jax.jit
    def run(carry):
        carry, _ = jax.lax.scan(body, carry, None, length=ITERS)
        return carry

    def sync(carry):
        leaf = jax.tree.leaves(carry)[0]
        np.asarray(leaf.reshape(-1)[:64])

    try:
        carry = run(*args)          # compile
    except Exception as e:
        print(f"{name:34s} FAILED: {repr(e)[:120]}", flush=True)
        return
    sync(carry)
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        carry = run(carry)
        sync(carry)
        best = min(best, (time.time() - t0) / ITERS)
    print(f"{name:34s} {best * 1e3:9.3f} ms/iter", flush=True)
    return best


def main():
    n1 = td.n_rows(N_SUB) + 1
    r = W * K
    print(f"w={W} n_sub={N_SUB} rows={n1} lanes={r} iters={ITERS}",
          flush=True)
    rng = np.random.default_rng(0)

    db = td.populate(rng, N_SUB, val_words=VW)
    jax.tree.map(lambda x: x.block_until_ready(), jax.tree.leaves(db))

    rows = jnp.asarray(rng.integers(0, n1 - 1, size=r, dtype=np.int32))
    wrows = jnp.asarray(rng.choice(n1 - 1, size=2 * W, replace=False)
                        .astype(np.int32))
    newval = jnp.asarray(rng.integers(0, 1 << 16, size=(2 * W, VW),
                                      dtype=np.int64).astype(np.uint32))

    # 0. dispatch-overhead baseline: a near-empty scan body
    def null(k):
        return jax.random.fold_in(k, 0)

    timeit("null (dispatch baseline)", null, jax.random.PRNGKey(0))

    # 1. workload generation
    def gen(k):
        s = gen_cohort(k, W, N_SUB)[4][3].sum().astype(jnp.uint32)
        return jax.random.fold_in(k, 0) + s * 0

    timeit("gen_cohort", gen, jax.random.PRNGKey(0))

    # 2. wave-1 gather: meta [w,K] + magic word
    def gathers(c):
        db_, rws = c
        m = db_.meta[rws.reshape(W, K)]
        g = db_.val[rws.reshape(W, K) * VW + 1]
        return (db_, rws + (m.sum() + g.sum()).astype(I32) * 0)

    timeit("gathers meta+magic [wK]", gathers, (db, rows))

    # 3. install scatters: meta [2w] + val rows [2w, VW]
    def installs(c):
        db_, wr = c
        meta = db_.meta.at[wr].set(newval[:, 0], mode="drop",
                                   unique_indices=True)
        wflat = (wr[:, None] * VW + jnp.arange(VW, dtype=I32)).reshape(-1)
        val = db_.val.at[wflat].set(newval.reshape(-1), mode="drop",
                                    unique_indices=True)
        return (db_.replace(val=val, meta=meta), wr)

    timeit("install scatters meta+val", installs, (db, wrows))

    # 4. lock arbitration over [2w] write slots (step-stamped arb array:
    # gather -> masked scatter-max -> gather-back, no meta involvement)
    def arb(c):
        db_, wr = c
        t = db_.step
        old = db_.arb[wr]
        held = (old >> td.K_ARB) == (t - 1)
        inv = U32(2 * W - 1) - jnp.arange(2 * W, dtype=U32)
        packed = (t << td.K_ARB) | inv
        a = db_.arb.at[jnp.where(~held, wr, n1)].max(packed, mode="drop")
        grant = ~held & (a[wr] == packed)
        return (db_.replace(arb=a,
                            step=t + 1 + grant.sum(dtype=U32) * U32(0)),
                wr)

    timeit("lock arb stamp scatter-max [2w]", arb, (db, wrows))

    # 5. replicated log append (RepLog: one unique row scatter)
    def logs(c):
        db_, wr = c
        mask = jnp.ones((2 * W,), bool)
        tbl = jnp.zeros((2 * W,), I32)
        z = jnp.zeros((2 * W,), U32)
        lg = logring.append_rep(db_.log, mask, tbl, tbl, z, wr.astype(U32),
                                newval[:, 0], newval)
        return (db_.replace(log=lg), wr)

    timeit("log append_rep x3", logs, (db, wrows))

    # 6. full pipe_step
    def full(c):
        db_, c1, c2, key = c
        db_, nc, c1_, _ = td.pipe_step(db_, c1, c2, key, w=W, n_sub=N_SUB,
                                       val_words=VW)
        return (db_, nc, c1_, jax.random.fold_in(key, 1))

    timeit("FULL pipe_step", full,
           (db, td.empty_ctx(W), td.empty_ctx(W), jax.random.PRNGKey(0)))


if __name__ == "__main__":
    main()
