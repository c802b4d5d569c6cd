"""TATP over a mesh: parallel/dense_sharded.py. Subscribers partitioned
over the devices, each row's primary and two backups on three different
devices, every write in three devices' log rings: the reference's three
shard servers."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from benchmarks import checks as ck
from benchmarks.deployments import tatp_dense_one_chip as one
from dint_tpu import monitor
from dint_tpu.engines import tatp_dense as td
from dint_tpu.parallel import dense_sharded as ds

AX = ds.SHARD_AXIS
# the same engine step, stats and small comparison as on one chip
GUARANTEE_CHECKS = one.GUARANTEE_CHECKS
COMPARE_CHECKS = one.COMPARE_CHECKS
compare_small = one.compare_small


class Sharded:
    stat_names = one.STAT_NAMES
    outcomes = one.OUTCOMES
    faults = one.FAULTS
    contention = one.CONTENTION
    depth = 3

    def __init__(self, sizes: dict, params: dict, seed: int, devices, emit):
        n = self.n_devices = len(devices)
        self.vw = vw = sizes["val_words"]
        self.n_loc = n_loc = ds.n_sub_local(sizes["n_sub"], n)
        self.n1 = n1 = td.n_rows(n_loc) + 1
        w, cpb = params["w"], params["cohorts_per_block"]
        self.txns_per_dispatch = w * cpb * n
        self.steps_per_dispatch = cpb
        self.seed = seed
        mesh = Mesh(np.array(devices), (AX,))

        # ds.create_sharded's program with the seed as an argument: there
        # it is a constant of the program, and every new seed would
        # compile populate again
        def pull(x, off):
            return jax.lax.ppermute(x, AX, ds.ring_perm(n, off))

        def populate(seed32):
            one = ds.populate_local(
                seed32, jax.lax.axis_index(AX), n_loc, vw, pull,
                log_lanes=sizes["log_lanes"],
                log_capacity=sizes["log_capacity"])
            return jax.tree.map(lambda x: x[None], one)

        t0 = time.perf_counter()
        state = jax.jit(jax.shard_map(populate, mesh=mesh, in_specs=P(),
                                      out_specs=P(AX)))(
            np.uint32(seed % (1 << 32)))
        jax.block_until_ready(state)
        self.ring_rows = state.db.log.entries.shape[-2]   # lanes * slots
        self.geometry = {
            "w": w, "k": td.K, "val_words": vw, "log_replicas": 1,
            "n_backups": ds.N_BCK,
            "state_bytes_per_device": sum(
                int(x.nbytes) for x in jax.tree.leaves(state)) // n,
            "ring_bytes": int(state.db.log.entries.nbytes) // n}
        emit(phase="populate", n_sub=sizes["n_sub"], n_sub_local=n_loc,
             cohorts_per_block=cpb, seconds=time.perf_counter() - t0,
             bytes_in_use=[(d.memory_stats() or {}).get("bytes_in_use")
                           for d in devices], **self.geometry)

        self._run, self._init, self._drain = \
            ds.build_sharded_pipelined_runner(
                mesh, n, sizes["n_sub"], w=w, val_words=vw,
                cohorts_per_block=cpb, monitor=True, use_pallas=False,
                use_fused=False)
        self._state = state
        self._heads_seen = 0

        def gatherer(base: int):
            """Rows [D, R] of each device's own shard of (meta, val),
            ``base`` rows into it; -1 reads the all-zero last row."""
            def local(meta, val, rows):
                r = jnp.where(rows[0] < 0, n1 - 1, rows[0]) + base
                flat = r[:, None] * vw + jnp.arange(vw, dtype=jnp.int32)
                return meta[0][r][None], val[0][flat][None]

            return jax.jit(jax.shard_map(
                local, mesh=mesh, in_specs=(P(AX),) * 3,
                out_specs=(P(AX),) * 2))

        self._gather_primary = gatherer(0)
        self._gather_backup = [gatherer(slot * n1)
                               for slot in range(ds.N_BCK)]
        self._any_locked = jax.jit(lambda db: jnp.any(db.locked))

    def start(self):
        state, self._state = self._state, None
        return self._init(state)

    def restart(self, final):
        return self._init(final[0])

    def dispatch(self, carry, key):
        return self._run(carry, key)

    def drain(self, carry):
        out = self._drain(carry)
        return out, np.asarray(out[1], np.int64)

    def verify(self, final, checks: ck.Checks, tag: str, totals: dict,
               dispatched: int) -> dict:
        state, _, counters = final
        n, vw = self.n_devices, self.vw
        snap = monitor.snapshot(counters)
        one.check_stats(checks, tag, totals, snap, dispatched)
        checks.add(f"{tag}.no_row_left_locked",
                   not bool(self._any_locked(state.db)))
        checks.add(f"{tag}.replication_pushes_equal_installs",
                   snap["repl_push_hop1"] == snap["repl_push_hop2"]
                   == snap["install_writes"] > 0,
                   hop1=snap["repl_push_hop1"], hop2=snap["repl_push_hop2"],
                   install_writes=snap["install_writes"])
        heads = np.asarray(state.db.log.head)
        appended = int(heads.astype(np.int64).sum())
        entries, self._heads_seen = appended - self._heads_seen, appended
        checks.add(f"{tag}.every_write_in_three_logs",
                   entries == 3 * snap["install_writes"],
                   log_entries=entries,
                   install_writes=snap["install_writes"])

        log = state.db.log
        rings = np.asarray(log.entries).reshape(
            n, log.lanes, self.ring_rows // log.lanes, -1)
        # device d's stream: in its own ring (tag 0) and, tagged d + 1, in
        # the rings of the two devices that hold its backups
        rng = np.random.default_rng(self.seed % (1 << 32))
        table_rows = ck.tatp_table_rows(self.n_loc)
        sample = primary = None
        for off in range(3):
            plans = [ck.plan_readback(
                rings[(d + off) % n], heads[(d + off) % n], table_rows, vw,
                key_hi=0 if off == 0 else d + 1) for d in range(n)]
            rows = np.full((n, self.ring_rows), -1, np.int32)
            if off == 0:    # the padding is the sample the backups get
                rows = rng.integers(0, self.n1 - 1, rows.shape,
                                    dtype=np.int32)
            for d, plan in enumerate(plans):
                rows[d, :len(plan["rows"])] = plan["rows"]
            meta, val = (np.asarray(x) for x in self._gather_primary(
                state.db.meta, state.db.val, rows))
            if off == 0:
                sample, primary = rows, (meta, val)
            for d, plan in enumerate(plans):
                k = len(plan["rows"])
                res = ck.compare_readback(plan, meta[d, :k], val[d, :k])
                checks.add(f"{tag}.device_{d}_acked_writes_read_back_"
                           f"from_ring_{(d + off) % n}", **res)
        # the rows device d wrote (and a seeded sample of the rest) on the
        # two devices that hold its backups: slot s of device d + s + 1
        for slot in range(ds.N_BCK):
            shift = slot + 1
            meta, val = (np.asarray(x) for x in self._gather_backup[slot](
                state.bck_meta, state.bck_val, np.roll(sample, shift, 0)))
            for d in range(n):
                h = (d + shift) % n
                checks.add(
                    f"{tag}.backup_{shift}_of_device_{d}_equals_primary",
                    np.array_equal(meta[h], primary[0][d])
                    and np.array_equal(val[h], primary[1][d]),
                    rows=int(sample.shape[1]), holder=h)
        return snap


def build(config: dict, params: dict, seed: int, devices, emit,
          rehearse: bool) -> Sharded:
    sizes = config["rehearse"] if rehearse else config["sizes"]
    return Sharded(sizes, params, seed, devices, emit)
