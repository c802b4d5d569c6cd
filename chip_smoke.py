#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU v5e: TATP at 7 M subscribers
    python chip_smoke.py --chips 4    # four-chip host: the sharded path
    python chip_smoke.py --rehearse   # any backend, tiny sizes, same checks

One process, one import of JAX, no child that needs the chip. Without
``--rehearse`` the script demands a TPU and exits non-zero otherwise: no
platform override, no retry, no answer from an old artifact.

The default phase drives the flagship path through the entry points a
user calls (``td.populate_device``, ``td.build_pipelined_runner``,
``init``/``run``/``drain``) at the reference's full keyspace
(tatp/caladan/tatp.h:28), holds the result to the system's guarantees on
the full-size tables, and then compares the engine with the code the repo
already has: the generic pipelined engine (tests/test_tatp_dense.py
``test_matches_generic_pipelined_engine_at_low_contention``) and recovery
from each one of the three log replicas (``recovery.recover_tatp_dense``).

Every line of output is one JSON object. The last one is
``{"ok": true, "device": {"platform", "kind", "count"}}``; a check prints
``{"check": name, "passed": ...}``, and the first that fails is followed
by ``{"ok": false, "failed": name}`` and exit code 1. The times printed
on the way are information, not a benchmark. A rehearsal never prints
``"ok": true``.
"""
from __future__ import annotations

import argparse
import collections
import importlib.metadata
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dint_tpu import _runtime, monitor, recovery
from dint_tpu.clients import tatp_client as tc
from dint_tpu.engines import tatp_dense as td
from dint_tpu.engines import tatp_pipeline as tp
from dint_tpu.parallel import dense_sharded as ds
from dint_tpu.tables import log as logring

VAL_WORDS = 10
Size = collections.namedtuple("Size", "n_sub w cpb blocks")
# what the driver's run holds the chip to, and the same phases at a size
# the CPU rehearsal can afford
FULL = Size(n_sub=7_000_000, w=8192, cpb=16, blocks=4)
TINY = Size(n_sub=20_000, w=256, cpb=2, blocks=4)
# the two comparisons run at this size on every backend
CMP = TINY

# JAX's own count of persistent-cache hits: how compile_timed tells a
# cached compile from a cold one
_cache_events: collections.Counter = collections.Counter()
jax.monitoring.register_event_listener(
    lambda name, **kw: _cache_events.update((name,)))


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(name: str, ok, **detail) -> None:
    """One invariant: print it, and stop the run at the first that fails."""
    emit(check=name, passed=bool(ok), **detail)
    if not ok:
        emit(ok=False, failed=name)
        sys.exit(1)


def device_record(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def result_line(devices, rehearse: bool) -> dict:
    """The last line. Only a run on the chip may say ``"ok": true``."""
    if rehearse:
        return {"rehearsal": "passed", "device": device_record(devices)}
    return {"ok": True, "device": device_record(devices)}


def host_rss_gb() -> float:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 1e9


def synced(tree):
    """Wait for the device, then fetch: both, so a time that ends here has
    the work inside it whatever the backend does with either alone."""
    jax.block_until_ready(tree)
    return jax.tree.map(np.asarray, tree)


def compile_timed(jitted, *args):
    """AOT-compile ``jitted`` for ``args``: (executable, seconds, whether
    the persistent cache answered)."""
    hits = _cache_events["/jax/compilation_cache/cache_hits"]
    t0 = time.perf_counter()
    exe = jitted.lower(*args).compile()
    dt = time.perf_counter() - t0
    hit = _cache_events["/jax/compilation_cache/cache_hits"] > hits
    return exe, dt, hit


# ------------------------------------------------------------ invariants


def ab_missing_band(total):
    """tests/test_tatp_dense.py::test_ab_missing_matches_population_
    analytics: the share of transactions that miss an absent row is fixed
    by the population rules and the mix. The band is the test's +-0.01,
    widened only where a rehearsal attempts too few for that (4 binomial
    standard deviations)."""
    p_sf = 0.625 + 0.375 ** 4 / 4
    p_cf = p_sf * 0.25
    expected = (0.35 * (1 - p_sf) + 0.10 * (1 - p_cf) + 0.02 * (1 - p_sf)
                + 0.02 * (1 - p_sf * 0.75) + 0.02 * (1 - p_cf))
    n = int(total[td.STAT_ATTEMPTED])
    observed = int(total[td.STAT_AB_MISSING]) / n
    band = max(0.01, 4 * (expected * (1 - expected) / n) ** 0.5)
    return observed, expected, band


def check_run(tag: str, total, snap, n_attempted: int) -> None:
    """The guarantees a run's stats vector and counter plane can show."""
    t = [int(x) for x in total]
    check(f"{tag}.attempted", t[td.STAT_ATTEMPTED] == n_attempted,
          attempted=t[td.STAT_ATTEMPTED], expected=n_attempted)
    check(f"{tag}.accounting_closes",
          t[td.STAT_COMMITTED] + t[td.STAT_AB_LOCK] + t[td.STAT_AB_MISSING]
          + t[td.STAT_AB_VALIDATE] == t[td.STAT_ATTEMPTED], stats=t)
    check(f"{tag}.magic_bad_zero", t[td.STAT_MAGIC_BAD] == 0)
    check(f"{tag}.committed_some", t[td.STAT_COMMITTED] > 0)
    pairs = (("txn_attempted", td.STAT_ATTEMPTED),
             ("txn_committed", td.STAT_COMMITTED),
             ("ab_lock", td.STAT_AB_LOCK),
             ("ab_missing", td.STAT_AB_MISSING),
             ("ab_validate", td.STAT_AB_VALIDATE),
             ("magic_bad", td.STAT_MAGIC_BAD))
    check(f"{tag}.monitor_reconciles_with_stats",
          all(snap[name] == t[i] for name, i in pairs),
          counters={name: snap[name] for name, _ in pairs})
    check(f"{tag}.lock_ledger_closes",
          snap["lock_requests"] == snap["lock_granted"]
          + snap["lock_rejected"]
          and snap["lock_rejected"] == snap["lock_reject_held"]
          + snap["lock_reject_arb"])
    obs, exp, band = ab_missing_band(total)
    check(f"{tag}.ab_missing_in_analytic_band", abs(obs - exp) < band,
          observed=obs, expected=exp, band=band)


def drive(run, drain, carry, key, blocks: int):
    """``blocks`` dispatches and the drain; every block ends in a sync and
    a fetch of its stats. Returns (drain outputs, stats total, block
    seconds)."""
    total = np.zeros(td.N_STATS, np.int64)
    secs = []
    for i in range(blocks):
        t0 = time.perf_counter()
        carry, stats = run(carry, jax.random.fold_in(key, i))
        total += synced(stats).astype(np.int64).sum(axis=0)
        secs.append(time.perf_counter() - t0)
    out = drain(carry)
    total += synced(out[1]).astype(np.int64).sum(axis=0)
    return out, total, secs


# ------------------------------------------------- default phase: tatp7m


def phase_tatp(size: Size, seed: int, snapshot: bool):
    """Populate, run, drain and hold the run to its invariants. Returns
    (live db, the populate's tables fetched before the run — the base
    snapshot recovery starts from — or None, stats total)."""
    n_sub, w, cpb, blocks = size
    key = jax.random.PRNGKey(seed)

    t0 = time.perf_counter()
    db = td.populate_device(jax.random.fold_in(key, 1 << 20), n_sub,
                            val_words=VAL_WORDS)
    synced(db.meta[-8:])
    jax.block_until_ready(db)
    emit(phase="tatp", n_sub=n_sub, w=w, cohorts_per_block=cpb,
         val_words=VAL_WORDS, table_bytes=int(db.val.nbytes + db.meta.nbytes
                                              + db.arb.nbytes),
         populate_s=time.perf_counter() - t0)
    fresh = None
    if snapshot:
        # tables only: locks are volatile and the log is what is replayed
        t0 = time.perf_counter()
        fresh = db.replace(val=np.asarray(db.val), meta=np.asarray(db.meta),
                           arb=None, log=None)
        emit(snapshot_fetch_s=time.perf_counter() - t0,
             host_rss_gb=host_rss_gb())

    run, init, drain = td.build_pipelined_runner(
        n_sub, w=w, val_words=VAL_WORDS, cohorts_per_block=cpb,
        monitor=True, trace=False)
    carry = init(db)
    del db
    run_x, run_s, run_hit = compile_timed(run, carry, key)
    drain_x, drain_s, drain_hit = compile_timed(drain, carry)
    emit(compile_s=run_s + drain_s, block_compile_s=run_s,
         drain_compile_s=drain_s,
         compile_cache="hit" if run_hit and drain_hit else "cold")

    t0 = time.perf_counter()
    (db, _, counters), total, secs = drive(run_x, drain_x, carry, key,
                                           blocks)
    wall = time.perf_counter() - t0
    n_att = blocks * cpb * w
    emit(blocks=blocks, ms_per_block=float(np.median(secs)) * 1e3,
         ms_per_block_all=[s * 1e3 for s in secs],
         attempted_txn_per_s=n_att / sum(secs),
         committed_txn_per_s=int(total[td.STAT_COMMITTED]) / sum(secs),
         run_and_drain_s=wall)

    snap = monitor.snapshot(counters)
    check_run("tatp", total, snap, n_att)
    check("tatp.no_row_left_locked", not bool(jnp.any(db.locked)))
    r0 = logring.replica_entries(db.log, 0)
    check("tatp.log_replicas_identical",
          all(bool(jnp.array_equal(r0, logring.replica_entries(db.log, r)))
              for r in (1, 2)))
    entries = int(np.asarray(db.log.head, np.int64).sum())
    check("tatp.log_entries_equal_monitor_installs",
          entries == snap["install_writes"] == snap["log_appends"] > 0,
          log_entries=entries, install_writes=snap["install_writes"],
          log_appends=snap["log_appends"])
    return db, fresh, total


def recover_from_each_replica(tag: str, fresh, db) -> None:
    """An acknowledged write is read back from each of the three
    replicas: the pre-run populate plus any ONE log ring must rebuild the
    live tables. The rebuilt copy is compared where the live tables are
    (on the device), one replica at a time."""
    check(f"{tag}.run_changed_the_tables",
          not bool(jnp.array_equal(fresh.meta, db.meta)))
    heads = np.asarray(db.log.head)
    for r in range(3):
        rec = recovery.recover_tatp_dense(
            fresh, np.asarray(logring.replica_entries(db.log, r)), heads)
        check(f"{tag}.recovered_from_replica_{r}",
              bool(jnp.array_equal(rec.val, db.val))
              and bool(jnp.array_equal(rec.meta, db.meta)),
              host_rss_gb=host_rss_gb())
        del rec         # before the next rebuild: one copy at a time


def compare_small(seed: int) -> None:
    """What the engine is compared with, at CMP size with the full row
    width and the standard mix: the generic pipelined engine, and
    recovery from each log replica."""
    n_sub, w, cpb, blocks = CMP
    key = jax.random.PRNGKey(seed)

    db0 = td.populate(np.random.default_rng(seed), n_sub,
                      val_words=VAL_WORDS)
    fresh = jax.tree.map(np.array, db0)      # the runner donates db0
    run_d, init_d, drain_d = td.build_pipelined_runner(
        n_sub, w=w, val_words=VAL_WORDS, cohorts_per_block=cpb,
        trace=False)
    (db, _), tot_d, _ = drive(run_d, drain_d, init_d(db0), key, blocks)

    shards, _ = tc.populate_shards(np.random.default_rng(seed), n_sub,
                                   val_words=VAL_WORDS,
                                   log_capacity=1 << 14)
    run_g, init_g, drain_g = tp.build_pipelined_runner(
        n_sub, w=w, val_words=VAL_WORDS, cohorts_per_block=cpb)
    (stacked, _), tot_g, _ = drive(run_g, drain_g,
                                   init_g(tp.stack_shards(shards)), key,
                                   blocks)
    check("compare.dense_stats_equal_generic_engine",
          tot_d.tolist() == tot_g.tolist(),
          dense=tot_d.tolist(), generic=tot_g.tolist())
    base = td._bases(n_sub + 1)
    ver_d = np.asarray(db.ver)
    for tid, t in enumerate((stacked.sub, stacked.sec, stacked.ai,
                             stacked.sf)):
        want = np.asarray(t.ver)[0]
        check(f"compare.table_{tid}_versions_equal_generic_engine",
              np.array_equal(ver_d[base[tid]:base[tid] + len(want)], want))

    recover_from_each_replica("durability_cmp", fresh, db)


# ---------------------------------------------------- phase --chips 4


def phase_sharded(size: Size, seed: int, devices):
    """parallel/dense_sharded.py at the same global size over the mesh:
    subscriber-partitioned TATP, installs forwarded over ICI to two
    backups, every write in three devices' logs. Returns the stats
    total."""
    n_sub, w, cpb, blocks = size
    n = len(devices)
    mesh = jax.sharding.Mesh(np.array(devices), (ds.SHARD_AXIS,))
    key = jax.random.PRNGKey(seed)

    t0 = time.perf_counter()
    state = ds.create_sharded(mesh, n, n_sub, val_words=VAL_WORDS, seed=seed)
    jax.block_until_ready(state)
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    emit(phase="sharded", n_devices=n, n_sub=n_sub, w=w,
         cohorts_per_block=cpb, populate_s=time.perf_counter() - t0,
         bytes_in_use=used)
    if None not in used:
        check("sharded.bytes_in_use_balanced",
              max(used) - min(used) <= 0.05 * max(used), bytes_in_use=used)
    check("sharded.every_leaf_on_every_device",
          all(len(x.sharding.device_set) == n
              for x in jax.tree.leaves(state)))
    fresh = jax.tree.map(np.array, state.db)    # init donates the state

    run, init, drain = ds.build_sharded_pipelined_runner(
        mesh, n, n_sub, w=w, val_words=VAL_WORDS, cohorts_per_block=cpb,
        monitor=True)
    carry = init(state)
    del state
    t0 = time.perf_counter()
    (state, _, counters), total, secs = drive(run, drain, carry, key, blocks)
    n_att = blocks * cpb * w * n
    emit(blocks=blocks, first_block_s_with_compile=secs[0],
         ms_per_block=float(np.median(secs[1:])) * 1e3,
         attempted_txn_per_s=(n_att - cpb * w * n) / sum(secs[1:]),
         run_and_drain_s=time.perf_counter() - t0)

    snap = monitor.snapshot(counters)
    check_run("sharded", total, snap, n_att)
    check("sharded.no_row_left_locked",
          not bool(jnp.any(state.db.locked)))
    check("sharded.replication_pushes_equal_installs",
          snap["repl_push_hop1"] == snap["repl_push_hop2"]
          == snap["install_writes"] > 0,
          install_writes=snap["install_writes"])

    # each row's primary and its two backup copies are equal
    n1 = td.n_rows(ds.n_sub_local(n_sub, n)) + 1
    meta = np.asarray(state.db.meta)
    val = np.asarray(state.db.val)
    bck_meta = np.asarray(state.bck_meta)
    bck_val = np.asarray(state.bck_val)
    for d in range(n):
        for off in (1, 2):
            holder, lo = (d + off) % n, (off - 1) * n1
            check(f"sharded.backup_{off}_of_device_{d}_equals_primary",
                  np.array_equal(bck_meta[holder, lo:lo + n1], meta[d])
                  and np.array_equal(
                      bck_val[holder, lo * VAL_WORDS:(lo + n1) * VAL_WORDS],
                      val[d]))
    del bck_meta, bck_val

    # the per-source log streams are separable (key_hi) and complete:
    # device d's primary range rebuilds from its own ring (tag 0) and
    # from either backup holder's ring (tag d+1)
    entries = np.asarray(state.db.log.entries)
    heads = np.asarray(state.db.log.head)
    lanes = state.db.log.lanes
    check("sharded.every_write_in_three_logs",
          int(heads.astype(np.int64).sum()) == 3 * snap["install_writes"])
    for d in range(n):
        fresh_d = jax.tree.map(lambda x: x[d], fresh)
        for holder, tag in ((d, 0), ((d + 1) % n, d + 1),
                            ((d + 2) % n, d + 1)):
            rec = recovery.recover_tatp_dense(
                fresh_d, entries[holder].reshape(lanes, -1,
                                                 entries.shape[-1]),
                heads[holder], key_hi_filter=tag)
            check(f"sharded.device_{d}_recovered_from_ring_{holder}",
                  np.array_equal(np.asarray(rec.val), val[d])
                  and np.array_equal(np.asarray(rec.meta), meta[d]))
            del rec
    return total


def compare_with_one_chip(total_mesh, size: Size, seed: int) -> None:
    """What the sharded run is compared with: the one-chip run of the same
    seed and size, held to the same invariants. The populations are drawn
    from different streams, so the outcome SHARES must agree, not the
    counts."""
    _, _, total_one = phase_tatp(size, seed, snapshot=False)
    for name, i in (("committed", td.STAT_COMMITTED),
                    ("ab_missing", td.STAT_AB_MISSING)):
        mesh_share = int(total_mesh[i]) / int(total_mesh[td.STAT_ATTEMPTED])
        one_share = int(total_one[i]) / int(total_one[td.STAT_ATTEMPTED])
        band = 2 * ab_missing_band(total_one)[2]
        check(f"sharded.{name}_share_agrees_with_one_chip",
              abs(mesh_share - one_share) < band, sharded=mesh_share,
              one_chip=one_share, band=band)


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="populations and workloads are drawn from it")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded path and the one-chip run it is "
                         "compared with, and no other phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend is there; same "
                         "phases and checks; never prints \"ok\": true")
    args = ap.parse_args(argv)

    if args.rehearse:
        devices = jax.devices()[:args.chips]
        if len(devices) < args.chips:
            raise SystemExit(f"need {args.chips} devices to rehearse")
    else:
        devices = _runtime.require_tpu(args.chips)
        # XLA:CPU executables do not survive the cache (tests/conftest.py
        # NOTE), so a rehearsal compiles afresh
        emit(compile_cache_dir=_runtime.enable_compile_cache())
    stats = devices[0].memory_stats() or {}
    emit(jax=jax.__version__, jaxlib=importlib.metadata.version("jaxlib"),
         libtpu=importlib.metadata.version("libtpu"),
         platform=devices[0].platform, device_kind=devices[0].device_kind,
         n_devices_used=len(devices),
         hbm_bytes_limit=stats.get("bytes_limit"), seed=args.seed,
         rehearsal=args.rehearse)

    size = TINY if args.rehearse else FULL
    t0 = time.perf_counter()
    if args.chips == 4:
        total = phase_sharded(size, args.seed, devices)
        compare_with_one_chip(total, size, args.seed)
    else:
        db, fresh, _ = phase_tatp(size, args.seed, snapshot=True)
        compare_small(args.seed)
        # the same guarantee at the run's own size; the live tables and
        # one rebuilt copy at a time share the chip (2 x 6.8 GB at 7 M)
        t1 = time.perf_counter()
        recover_from_each_replica("durability_full", fresh, db)
        emit(durability_n_sub=size.n_sub,
             durability_s=time.perf_counter() - t1)
    emit(total_s=time.perf_counter() - t0)
    emit(**result_line(devices, args.rehearse))
    return 0


if __name__ == "__main__":
    sys.exit(main())
