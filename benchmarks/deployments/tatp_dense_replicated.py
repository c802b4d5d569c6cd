"""TATP replicated over the four devices of one host, held to its
guarantees by the four-device program itself.

The object a loop drives is ``tatp_dense_sharded.Sharded`` (subscribers
partitioned over the devices, each row's primary and two backups on three
different devices, every write in three devices' log rings). What this
module adds to it:

* ``verify``, in every run: the surviving entries of device d's log
  stream in the three rings that carry it are equal entry for entry
  (``stream_<d>_identical_in_three_rings``), beside the twelve read-backs
  and the eight backup comparisons ``Sharded.verify`` makes;
* ``compare_small``, in the traced run: the FOUR-DEVICE block and drain
  at a small size against independent code. Transactions: the generic
  engine (engines/tatp_pipeline.py) run per partition on that partition's
  population and step keys. Replication: the plain reference
  (benchmarks/references/replication.py), exactly: every backup table,
  every entry of every ring, and a lost device's tables recovered from
  each of its three streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import checks as ck
from benchmarks.deployments import tatp_dense_sharded as sharded
from benchmarks.references import replication as ref
from dint_tpu.engines import tatp_dense as td

N = 4                           # the configuration's four devices
WHERE = ref.placement(N)        # who holds device d's backups and streams
# The generic engine locks CALL_FORWARDING rows through a HASHED slot table
# (tables/locks.py; 2^15 slots by default at 5,000 subscribers), so two
# distinct keys in flight can share a slot and one is refused where the
# dense engine, which locks rows, grants both: one seed in ten read one
# transaction `ab_lock` against `ab_missing` with no CF race in the run
# (seed 7 on the CPU, PR 37). With 2^24 slots for 60,012 keys it keeps to
# what both engines define.
CF_LOCK_SLOTS = 1 << 24

GUARANTEE_CHECKS = (
    *sharded.GUARANTEE_CHECKS, "replication_pushes_equal_installs",
    "every_write_in_three_logs",
    *(f"device_{d}_acked_writes_read_back_from_ring_{ring}"
      for d in range(N) for ring, _ in WHERE[d]["streams"]),
    *(f"backup_{slot + 1}_of_device_{d}_equals_primary"
      for d in range(N) for _, slot in WHERE[d]["backups"]),
    *(f"stream_{d}_identical_in_three_rings" for d in range(N)))
COMPARE_CHECKS = (
    "compare.sharded_stats_equal_generic_engine",
    "compare.table_versions_equal_generic_engine",
    "compare.run_changed_the_tables",
    "compare.primaries_equal_reference",
    "compare.backups_equal_reference",
    "compare.three_log_streams_equal_reference",
    *(f"compare.lost_device_recovered_from_stream_{h}" for h in range(3)))


# ------------------------------------------------ the streams in the rings


def lane_streams(ring: np.ndarray, heads: np.ndarray, tag: int) -> list:
    """Per lane, oldest first, the surviving entries of one stream of a
    ring that carries three: ring [L, CAP, EW], heads [L] (appends per
    lane; slot = position % CAP), ``tag`` the stream's ``key_hi`` word."""
    lanes, cap, _ = ring.shape
    out = []
    for lane in range(lanes):
        head = int(heads[lane])
        kept = min(head, cap)
        e = ring[lane, (head - kept + np.arange(kept)) % cap]
        out.append(e[e[:, 1] == np.uint32(tag)])
    return out


def streams_identical(rings: np.ndarray, heads: np.ndarray) -> list:
    """For each device d: do the three rings that carry its stream hold
    the same entries in the same order? Lane by lane, over the entries
    all three still hold (a ring that carries faster streams beside d's
    wraps over d's sooner), the tag word apart. rings [N, L, CAP, EW],
    heads [N, L]."""
    out = []
    for d in range(N):
        copies = [lane_streams(rings[r], heads[r], tag)
                  for r, tag in WHERE[d]["streams"]]
        compared = differs = 0
        for lane in zip(*copies):
            k = min(len(e) for e in lane)
            first = np.delete(lane[0][len(lane[0]) - k:], 1, axis=1)
            compared += k
            differs += sum(int((np.delete(e[len(e) - k:], 1, axis=1)
                                != first).any(axis=1).sum())
                           for e in lane[1:])
        out.append({"ok": compared > 0 and differs == 0,
                    "compared": compared, "differs": differs,
                    "rings": [r for r, _ in WHERE[d]["streams"]]})
    return out


def rings_of(log, n_devices: int) -> tuple:
    """(rings [N, L, CAP, EW], heads [N, L]) of a stacked log on the host
    (ring-sized; the arrays keep their host copy, so a second reader
    fetches nothing)."""
    entries = np.asarray(log.entries)
    return (entries.reshape(n_devices, log.lanes, -1, entries.shape[-1]),
            np.asarray(log.head))


class Replicated(sharded.Sharded):
    def verify(self, final, checks: ck.Checks, tag: str, totals: dict,
               dispatched: int) -> dict:
        snap = super().verify(final, checks, tag, totals, dispatched)
        rings, heads = rings_of(final[0].db.log, self.n_devices)
        for d, res in enumerate(streams_identical(rings, heads)):
            checks.add(f"{tag}.stream_{d}_identical_in_three_rings", **res)
        return snap


def build(config: dict, params: dict, seed: int, devices, emit,
          rehearse: bool) -> Replicated:
    if len(devices) != N:
        raise SystemExit(f"this deployment is laid out on {N} devices, "
                         f"not {len(devices)}")
    sizes = config["rehearse"] if rehearse else config["sizes"]
    return Replicated(sizes, params, seed, devices, emit)


# ------------------------------------ against independent code, small size


def generic_shards(exists: np.ndarray, n_loc: int, vw: int,
                   log_capacity: int) -> list:
    """The generic engine's three shard replicas holding the population a
    device drew: ``exists`` is the exists bit of every row of its tables
    laid end to end (clients/tatp_client.populate_shards, with the
    presence given instead of drawn)."""
    from dint_tpu.clients import tatp_client as tc
    from dint_tpu.engines import tatp
    from dint_tpu.tables import kv

    p1 = n_loc + 1
    rows = ck.tatp_table_rows(n_loc)
    base = ck.table_bases(rows)

    def present(table):
        return exists[base[table]:base[table] + rows[table]]

    def table(t, ids, ver):
        val = np.zeros((len(ver), vw), np.uint32)
        val[:, 0], val[:, 1] = ids, tc.MAGIC
        return t.replace(val=jnp.asarray(val.reshape(-1)),
                         ver=jnp.asarray(ver.astype(np.uint32)))

    cf_keys = np.nonzero(present(tatp.CALL_FORWARDING))[0].astype(np.uint64)
    cf_val = np.zeros((len(cf_keys), vw), np.uint32)
    cf_val[:, 0], cf_val[:, 1] = cf_keys.astype(np.uint32), tc.MAGIC
    shards = []
    for _ in range(tc.N_SHARDS):
        s = tatp.create(n_loc, val_words=vw, log_capacity=log_capacity,
                        cf_lock_slots=CF_LOCK_SLOTS)
        shards.append(jax.tree.map(jnp.array, s.replace(
            sub=table(s.sub, np.arange(p1), present(tatp.SUBSCRIBER)),
            sec=table(s.sec, np.arange(p1), present(tatp.SEC_SUBSCRIBER)),
            ai=table(s.ai, np.arange(4 * p1), present(tatp.ACCESS_INFO)),
            sf=table(s.sf, np.arange(4 * p1),
                     present(tatp.SPECIAL_FACILITY)),
            cf=kv.populate(s.cf, cf_keys, cf_val))))
    return shards


@functools.lru_cache(maxsize=None)
def _generic_steps(n_loc: int, w: int, vw: int):
    """jit(scan(tatp_pipeline.pipe_step)) over GIVEN step keys: the
    generic runner splits a block key itself, and a device of the mesh
    folds its index into each step's."""
    from dint_tpu.engines import tatp_pipeline as tp

    def body(carry, key):
        stacked, c1, c2 = carry
        stacked, new, c1, stats = tp.pipe_step(
            stacked, c1, c2, key, w=w, n_sub=n_loc, val_words=vw)
        return (stacked, new, c1), stats

    return jax.jit(lambda carry, keys: jax.lax.scan(body, carry, keys),
                   donate_argnums=0)


@functools.lru_cache(maxsize=None)
def _step_keys(blocks: int, cpb: int):
    """[N, blocks * cpb] step keys as the mesh derives them: block i gets
    fold_in(key, i), splits it into one key a step, and device d folds d
    into each (dense_sharded ``block_local`` / ``local_step``)."""
    def keys(key):
        steps = jax.vmap(lambda i: jax.random.split(
            jax.random.fold_in(key, i), cpb))(
                jnp.arange(blocks, dtype=jnp.uint32)).reshape(-1, 2)
        return jax.vmap(lambda d: jax.vmap(
            lambda k: jax.random.fold_in(k, d))(steps))(
                jnp.arange(N, dtype=jnp.uint32))

    return jax.jit(keys)


def _cf_races(step_keys, w: int, n_loc: int) -> int:
    """``checks.cf_races`` of one partition's cohorts, generated again."""
    from dint_tpu.engines import tatp
    from dint_tpu.engines import tatp_pipeline as tp
    from dint_tpu.engines.types import Op

    gen = jax.jit(lambda step_key: tp.gen_cohort(
        jax.random.split(step_key)[0], w, n_loc))
    reads, writes = [], []
    for step_key in step_keys:
        _, ops, tbl, kk, (ws_on, _, ws_tbl, ws_key, _) = (
            jax.tree.map(np.asarray, gen(step_key)))
        reads.append(kk[(tbl == tatp.CALL_FORWARDING)
                        & (ops == Op.OCC_READ)])
        writes.append(ws_key[ws_on & (ws_tbl == tatp.CALL_FORWARDING)])
    return ck.cf_races(reads, writes)


def stream_of(ring: np.ndarray, heads: np.ndarray, tag: int) -> np.ndarray:
    """One stream of a ring as entries in an order that is the order of
    acknowledgement for every row: lane by lane, then stably by version
    (a row's versions rise with time, and installs of different rows
    commute)."""
    e = np.concatenate(lane_streams(ring, heads, tag))
    return e[np.argsort(e[:, 3], kind="stable")]


def compare_small(config: dict, seed: int, checks: ck.Checks) -> None:
    """The four-device block and drain at the configuration's
    ``compare_small`` size (rings sized so that none wraps) against the
    generic engine per partition and the plain replication reference."""
    from dint_tpu import recovery
    from dint_tpu.engines import tatp_pipeline as tp

    size, vw = config["compare_small"], config["sizes"]["val_words"]
    w, cpb, blocks = size["w"], size["cohorts_per_block"], size["blocks"]
    devices = jax.devices()[:N]
    if len(devices) < N:
        raise SystemExit(f"the comparison runs the {N}-device program: "
                         f"found {len(devices)} devices")
    dep = Replicated({**size, "val_words": vw},
                     {"w": w, "cohorts_per_block": cpb}, seed, devices,
                     lambda **kw: None)
    n_loc, n1 = dep.n_loc, dep.n1
    fresh = jax.tree.map(np.array, dep._state)      # start() donates it
    key = jax.random.PRNGKey(seed)
    carry = dep.start()
    total = np.zeros(td.N_STATS, np.int64)
    for i in range(blocks):
        carry, stats = dep.dispatch(carry, jax.random.fold_in(key, i))
        total += np.asarray(stats, np.int64).sum(axis=0)
    (state, _, _), tail = dep.drain(carry)
    total += tail.sum(axis=0)
    live = jax.tree.map(np.asarray, state)
    rings, heads = rings_of(state.db.log, N)

    # ---- transactions: the generic engine, partition by partition
    step_keys = _step_keys(blocks, cpb)(key)
    _, init_g, drain_g = tp.build_pipelined_runner(
        n_loc, w=w, val_words=vw, cohorts_per_block=cpb)
    base = ck.table_bases(ck.tatp_table_rows(n_loc))
    total_g = np.zeros(td.N_STATS, np.int64)
    versions = []
    for d in range(N):
        shards = generic_shards(fresh.db.meta[d] & 1, n_loc, vw,
                                size["log_capacity"])
        carry_g, stats = _generic_steps(n_loc, w, vw)(
            init_g(tp.stack_shards(shards)), step_keys[d])
        stacked, tail_g = drain_g(carry_g)
        total_g += np.asarray(stats, np.int64).sum(axis=0) \
            + np.asarray(tail_g, np.int64).sum(axis=0)
        ver = live.db.meta[d] >> 1
        versions.append(all(
            np.array_equal(ver[base[t]:base[t] + table.ver.shape[1]],
                           np.asarray(table.ver)[0])
            for t, table in enumerate((stacked.sub, stacked.sec,
                                       stacked.ai, stacked.sf))))
    # counted only where the vectors differ: an equal run compiles and
    # dispatches nothing for it
    races = None if np.array_equal(total, total_g) else sum(
        _cf_races(step_keys[d], w, n_loc) for d in range(N))
    agree, moved = ck.tatp_stats_agree(
        dict(zip(dep.stat_names, total.tolist())),
        dict(zip(dep.stat_names, total_g.tolist())),
        all(versions), races or 0)
    checks.add("compare.sharded_stats_equal_generic_engine", agree,
               sharded=total.tolist(), generic=total_g.tolist(),
               difference=(total - total_g).tolist(), moved=moved,
               cf_races=races)
    checks.add("compare.table_versions_equal_generic_engine",
               all(versions), per_device=versions)
    checks.add("compare.run_changed_the_tables",
               not np.array_equal(fresh.db.meta, live.db.meta))

    # ---- replication: the plain reference, exactly
    table_rows = ck.tatp_table_rows(n_loc)
    wrapped = bool((heads.astype(np.int64) > rings.shape[2]).any())
    primaries, backups, logs = [], [], []
    for d in range(N):
        acked = stream_of(rings[d], heads[d], 0)
        stream = [(int(e[0] >> 8), int(e[2]), int(e[0] & 0xFF), int(e[3]),
                   e[ck.HDR_WORDS:]) for e in acked]
        # the tables the stream gives, and its entries under each tag
        replayed = {ring: ref.replay(
            fresh.db.meta[d], fresh.db.val[d].reshape(n1, vw), table_rows,
            stream, tag) for ring, tag in WHERE[d]["streams"]}
        meta, val, _ = replayed[d]
        primaries.append(np.array_equal(live.db.meta[d], meta)
                         and np.array_equal(live.db.val[d],
                                            val.reshape(-1)))
        backups.append([
            np.array_equal(live.bck_meta[h, slot * n1:(slot + 1) * n1],
                           meta)
            and np.array_equal(
                live.bck_val[h, slot * n1 * vw:(slot + 1) * n1 * vw],
                val.reshape(-1)) for h, slot in WHERE[d]["backups"]])
        logs.append([np.array_equal(stream_of(rings[ring], heads[ring],
                                              tag), replayed[ring][2])
                     for ring, tag in WHERE[d]["streams"]])
    same_order = streams_identical(rings, heads)
    checks.add("compare.primaries_equal_reference", all(primaries),
               per_device=primaries)
    checks.add("compare.backups_equal_reference",
               all(all(b) for b in backups), per_device=backups)
    checks.add("compare.three_log_streams_equal_reference",
               not wrapped and all(all(x) for x in logs)
               and all(s["ok"] for s in same_order),
               wrapped=wrapped, per_device=logs, entries=[
                   s["compared"] for s in same_order],
               out_of_order=[s["differs"] for s in same_order])
    for h in range(3):
        ok = []
        for d in range(N):
            ring, tag = WHERE[d]["streams"][h]
            rec = recovery.recover_tatp_dense(
                jax.tree.map(lambda x: x[d], fresh.db), rings[ring],
                heads[ring], key_hi_filter=tag)
            ok.append(np.array_equal(np.asarray(rec.val), live.db.val[d])
                      and np.array_equal(np.asarray(rec.meta),
                                         live.db.meta[d]))
        checks.add(f"compare.lost_device_recovered_from_stream_{h}",
                   all(ok), per_device=ok)
