"""End-to-end drive of dint_tpu's public API (verify skill recipe).

Platform: demands a TPU (dint_tpu/_runtime.require_tpu) unless --cpu is
passed, which runs the same checks on the CPU backend: correctness only,
its timings are not device numbers.
"""
import os
import sys
import time

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from dint_tpu import _runtime  # noqa: E402

if "--cpu" in sys.argv:
    jax.config.update("jax_platforms", "cpu")
else:
    _runtime.require_tpu()
    _runtime.enable_compile_cache()

import numpy as np

from dint_tpu.engines import (fasst, lock2pl, logsrv, store,
                              smallbank_dense as sd, tatp_dense as td)
from dint_tpu.engines.types import Op, Reply, make_batch
from dint_tpu.tables import kv, log as logring, run as run_mod

rng = np.random.default_rng(0)
R = 4096
MAGIC = 0x5A5A


def check(name, ok):
    print(f"{'PASS' if ok else 'FAIL'}  {name}")
    if not ok:
        sys.exit(1)


# ---- 1. store over a populated KV table --------------------------------
n_keys = 200_000
table = kv.create(1 << 16, slots=16, val_words=10)
keys_all = np.arange(1, n_keys + 1, dtype=np.uint64)
vals = np.zeros((n_keys, 10), np.uint32)
vals[:, 0] = keys_all.astype(np.uint32)
vals[:, 1] = MAGIC
table = kv.populate(table, keys_all, vals)
step = jax.jit(store.step)

k = rng.integers(1, n_keys + 1, R).astype(np.uint64)
ops = np.where(rng.random(R) < 0.5, Op.GET, Op.SET).astype(np.int32)
wv = np.zeros((R, 10), np.uint32)
wv[:, 1] = MAGIC
table, rep = step(table, make_batch(ops, k, wv, width=R, val_words=10))
rt = np.asarray(rep.rtype)
rv = np.asarray(rep.val)
isval = rt == Reply.VAL
check("store GET replies carry populate magic",
      isval.any() and (rv[isval, 1] == MAGIC).all())

# all-lanes-same-key SET on a fresh key: vers must be base+1..base+R perm
fresh = np.uint64(n_keys + 77)
sb_ops = np.full(R, Op.INSERT, np.int32)
b = make_batch(sb_ops, np.full(R, fresh, np.uint64), wv, width=R,
               val_words=10)
table, rep = step(table, b)
vers = np.sort(np.asarray(rep.ver))
check("same-key INSERT serializes ver 1..R",
      np.array_equal(vers, np.arange(1, R + 1, dtype=np.uint32)))

# NOP-only batch + delete of nonexistent key
table, rep = step(table, make_batch(np.zeros(4, np.int32),
                                    np.zeros(4, np.uint64), width=4,
                                    val_words=10))
check("NOP batch replies NONE",
      (np.asarray(rep.rtype) == Reply.NONE).all())
table, rep = step(table, make_batch(
    np.full(4, Op.DELETE, np.int32),
    np.full(4, np.uint64(10**9)), width=4, val_words=10))
check("delete of nonexistent NOT_EXIST",
      (np.asarray(rep.rtype)[:1] == Reply.NOT_EXIST).all())

# ---- 1b. dintscan: Op.SCAN over the ordered run (run union delta) ------
SMAX = 8
srun = run_mod.from_table(table, delta_cap=64)
sstep = jax.jit(store.step, static_argnames=("maintain_bloom", "scan_max"))
n_scan = 64
s_ops = np.full(R, Op.NOP, np.int32)
s_ops[:n_scan] = Op.SCAN
s_keys = np.zeros(R, np.uint64)
s_keys[:n_scan] = rng.integers(1, n_keys - SMAX, n_scan)
s_lens = np.zeros(R, np.uint32)
s_lens[:n_scan] = rng.integers(1, SMAX + 1, n_scan)
sb_scan = make_batch(s_ops, s_keys, wv, vers=s_lens, width=R, val_words=10)
_, rep, srun, srep = sstep(table, sb_scan, run=srun, scan_max=SMAX)
rt = np.asarray(rep.rtype)[:n_scan]
cnt = np.asarray(srep.count)[:n_scan]
khi = np.asarray(srep.key_hi).astype(np.uint64)
klo = np.asarray(srep.key_lo).astype(np.uint64)
sval = np.asarray(srep.val)
ok_rows = True
for i in range(n_scan):
    L = int(s_lens[i])
    keys_got = ((khi[i] << np.uint64(32)) | klo[i])[:cnt[i]]
    # keyspace 1..n_keys is dense, so an L-row scan from k is k..k+L-1
    want = np.arange(s_keys[i], s_keys[i] + L, dtype=np.uint64)
    ok_rows &= cnt[i] == L and np.array_equal(keys_got, want) \
        and (sval[i, :L, 1] == MAGIC).all()
check("scan lanes return the dense key range with populate magic",
      ok_rows and (rt == Reply.VAL).all()
      and np.array_equal(np.asarray(rep.ver)[:n_scan], cnt))

# the same scan again after a merge-compact rebuild — bit-equal
def srep_tuple(r):
    return tuple(np.asarray(x) for x in
                 (r.count, r.key_hi, r.key_lo, r.ver, r.val))
srun_rb = store.rebuild_run(table, srun)
_, _, _, srep_rb = sstep(table, sb_scan, run=srun_rb, scan_max=SMAX)
check("scan replies bit-identical after a rebuild",
      all(np.array_equal(a, b)
          for a, b in zip(srep_tuple(srep), srep_tuple(srep_rb))))

# write-through overlay: a SET in one batch is visible to the NEXT
# batch's scan (run union delta view), without a rebuild
probe = np.uint64(s_keys[0])
w_ops = np.full(R, Op.NOP, np.int32)
w_ops[0] = Op.SET
w_keys = np.zeros(R, np.uint64)
w_keys[0] = probe
w_vals = np.zeros((R, 10), np.uint32)
w_vals[0, 2] = 0xBEEF
table2, _, srun, _ = sstep(table, make_batch(w_ops, w_keys, w_vals,
                                             width=R, val_words=10),
                           run=srun, scan_max=SMAX)
_, _, srun, srep_d = sstep(table2, sb_scan, run=srun, scan_max=SMAX)
check("scan sees prior-batch SET through the delta overlay",
      int(np.asarray(srep_d.count)[0]) >= 1
      and int(np.asarray(srep_d.val)[0, 0, 2]) == 0xBEEF
      and int(np.asarray(srep_d.delta_hits)[0]) >= 1)

# stale contract: overflow the 64-row overlay -> scans reply RETRY with
# zero rows; rebuild_run re-snapshots and the same scan serves VAL again
ov_keys = rng.choice(np.arange(1, n_keys + 1, dtype=np.uint64), 512,
                     replace=False)
ov = make_batch(np.full(512, Op.SET, np.int32), ov_keys, wv[:512],
                width=512, val_words=10)
table2, _, srun, _ = sstep(table2, ov, run=srun, scan_max=SMAX)
_, rep_st, srun, srep_st = sstep(table2, sb_scan, run=srun, scan_max=SMAX)
srun = store.rebuild_run(table2, srun)
_, rep_ok, _, _ = sstep(table2, sb_scan, run=srun, scan_max=SMAX)
check("stale overlay -> RETRY, rebuild_run -> VAL",
      bool(np.asarray(srun.stale) == False)  # noqa: E712
      and (np.asarray(rep_st.rtype)[:n_scan] == Reply.RETRY).all()
      and (np.asarray(srep_st.count)[:n_scan] == 0).all()
      and (np.asarray(rep_ok.rtype)[:n_scan] == Reply.VAL).all())

# ---- 2. lock2pl / fasst / logsrv ---------------------------------------
from dint_tpu.tables import locks
lt = locks.create_sx(1 << 16)
lstep = jax.jit(lock2pl.step)
lk = rng.integers(0, 1 << 14, R).astype(np.uint64)
lops = np.where(rng.random(R) < 0.7, Op.ACQ_S, Op.ACQ_X).astype(np.int32)
lt, lrep = lstep(lt, make_batch(lops, lk, width=R, val_words=1))
lrt = np.asarray(lrep.rtype)
check("lock2pl grants+rejects partition",
      ((lrt == Reply.GRANT) | (lrt == Reply.REJECT)).all()
      and (lrt == Reply.GRANT).any() and (lrt == Reply.REJECT).any())

ft = locks.create_occ(1 << 16)
fstep = jax.jit(fasst.step)
fk = np.arange(100, 100 + R // 4, dtype=np.uint64)
ft, frep = fstep(ft, make_batch(np.full(len(fk), Op.LOCK, np.int32), fk,
                                width=R, val_words=1))
granted = np.asarray(frep.rtype)[: len(fk)] == Reply.GRANT
# commit ONLY granted lanes (the OCC client contract: a rejected lock
# is never committed; committing a shared slot twice would double-bump)
c_ops = np.where(granted, Op.COMMIT_VER, Op.NOP).astype(np.int32)
ft, frep2 = fstep(ft, make_batch(c_ops, fk, width=R, val_words=1))
ft, frep3 = fstep(ft, make_batch(
    np.full(len(fk), Op.READ_VER, np.int32), fk, width=R, val_words=1))
v_after = np.asarray(frep3.ver)[: len(fk)]
# distinct keys can share lock slots (hash collisions -> REJECT, the
# no-wait contract); granted rows must read ver==1 after commit
check("fasst lock->commit bumps version",
      granted.mean() > 0.9 and (v_after[granted] == 1).all())

lg = logring.create(16, 1 << 12, val_words=10)
gstep = jax.jit(logsrv.step)
lg, grep = gstep(lg, make_batch(np.full(R, Op.LOG_APPEND, np.int32),
                                rng.integers(0, 1 << 20, R).astype(np.uint64),
                                wv, width=R, val_words=10))
check("log append acks all and heads sum to R",
      (np.asarray(grep.rtype) == Reply.ACK).all()
      and int(np.asarray(lg.head).sum()) == R)

# ---- 3. flagship dense TATP (host populate) ----------------------------
n_sub, w = 20_000, 1024
db = td.populate(np.random.default_rng(0), n_sub, val_words=10)
run, init, drain = td.build_pipelined_runner(n_sub, w=w,
                                             cohorts_per_block=8)
carry = init(db)
total = np.zeros(td.N_STATS, np.int64)
t0 = time.time()
for i in range(4):
    carry, s = run(carry, jax.random.fold_in(jax.random.PRNGKey(0), i))
    total += np.asarray(s, np.int64).sum(axis=0)
dt = time.time() - t0
db, tail = drain(carry)
total += np.asarray(tail, np.int64).sum(axis=0)
att, com = int(total[td.STAT_ATTEMPTED]), int(total[td.STAT_COMMITTED])
closes = com + int(total[td.STAT_AB_LOCK]) + \
    int(total[td.STAT_AB_MISSING]) + int(total[td.STAT_AB_VALIDATE])
check("tatp accounting closes", closes == att == 4 * 8 * w)
check("tatp magic_bad == 0", int(total[td.STAT_MAGIC_BAD]) == 0)
check("tatp abort floor ~25%", 0.15 < 1 - com / att < 0.40)
check("tatp all locks expired after drain",
      not np.asarray(db.locked).any())
reps = [np.asarray(logring.replica_entries(db.log, r)) for r in range(3)]
check("tatp log x3 replicas identical",
      all(np.array_equal(reps[0], r) for r in reps[1:]))
print(f"      tatp drive: {att / dt:.0f} attempted/s (w={w}, 4 blocks)")

# ---- 4. on-device populate path (small shape) --------------------------
db2 = td.populate_device(jax.random.PRNGKey(0), 5_000, val_words=10)
m = np.asarray(db2.meta)
ex = (m & 1).astype(bool)
check("populate_device: subs all exist, cf partial",
      bool(ex[1:5001].all()) and 0.10 < ex[10 * 5001:22 * 5001].mean() < 0.20)

# ---- 5. SmallBank conservation -----------------------------------------
n_acc = 100_000
bank = sd.create(n_acc)
base_bal = int(np.asarray(sd.total_balance(bank)))
srun, sinit, sdrain = sd.build_pipelined_runner(n_acc, w=1024,
                                                cohorts_per_block=8)
scarry = sinit(bank)
stot = np.zeros(sd.N_STATS, np.int64)
for i in range(4):
    scarry, s = srun(scarry, jax.random.fold_in(jax.random.PRNGKey(7), i))
    stot += np.asarray(s, np.int64).sum(axis=0)
bank, tail = sdrain(scarry)
stot += np.asarray(tail, np.int64).sum(axis=0)
final_bal = int(np.asarray(sd.total_balance(bank)))
check("smallbank balance conservation",
      (final_bal - base_bal) % (1 << 32)
      == int(stot[sd.STAT_BAL_DELTA]) % (1 << 32))
check("smallbank committed > 0", int(stot[sd.STAT_COMMITTED]) > 0)

# ---- 6. TATP over the wire (3 UDP shard servers) -----------------------
from dint_tpu.clients import tatp_wire as tw

with tw.serve_shards(500, width=256, flush_us=1000) as ports:
    with tw.WireCoordinator(ports, 500, width=256, n_socks=2) as coord:
        st = coord.run_cohort(np.random.default_rng(1), 64)
check("wire txns commit over UDP", st.committed > 0
      and st.committed + st.aborted_lock + st.aborted_validate
      + st.aborted_missing + st.aborted_timeout == st.attempted)

print("ALL CHECKS PASSED on", jax.devices()[0].platform)
