"""SmallBank sharded over the four devices of one host, with cross-device
2PL: parallel/dense_sharded_sb.py. Accounts round-robin (``owner =
account % 4``), each device the primary of a quarter of them, the backup
of its two predecessors' ranges, and one log ring that carries its own
stream and the two forwarded ones: the reference's three sharded servers.

Nothing table-sized leaves the device: the rings are fetched (ring-sized)
and the rows they name, or the rows the reference touched, are gathered
on the device, from the primaries and from both backup slots. The tables
have no version word, so a live balance is held to the newest entry of
its row wherever no wrap of the ring can hide a later write
(checks.surviving_entries). Against independent code
(benchmarks/references/smallbank_sharded.py, which shares nothing with
the program but the traffic: ``gen_cohort`` and the amount draw):
``compare_small`` runs the four-device block and drain at a small size
in the traced run, and every run holds the warm-up dispatches of the
timed program itself, at the deployment's scale, regenerated from their
keys, to the reference's stats rows and touched rows."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from benchmarks import checks as ck
from benchmarks.deployments import smallbank_dense_one_chip as one_chip
from benchmarks.deployments.tatp_dense_replicated import (lane_streams,
                                                          rings_of,
                                                          streams_identical)
from benchmarks.references import smallbank as ref_one
from benchmarks.references import smallbank_sharded as ref
from dint_tpu import monitor, recovery
from dint_tpu.engines import smallbank_pipeline as sp
from dint_tpu.monitor import counters as mon
from dint_tpu.parallel import dense_sharded_sb as dsb

N = 4                           # the configuration's four devices
AX = dsb.AXIS
WHERE = ref.placement(N)        # who holds device d's backups and streams
STAT_NAMES = ref.STAT_NAMES
assert [dsb.STAT_ATTEMPTED, dsb.STAT_COMMITTED, dsb.STAT_AB_LOCK,
        dsb.STAT_AB_LOGIC, sp.STAT_MAGIC_BAD, dsb.STAT_BAL_DELTA,
        dsb.STAT_OVERFLOW] == list(range(dsb.N_STATS))
OUTCOMES = ("committed", "ab_lock", "ab_logic")
FAULTS = ("magic_bad", "overflow")
CONTENTION = ("ab_lock",)       # another transaction held the lock
COUNTER_PAIRS = (("txn_attempted", "attempted"),
                 ("txn_committed", "committed"), ("ab_lock", "ab_lock"),
                 ("ab_logic", "ab_logic"), ("magic_bad", "magic_bad"),
                 ("route_overflow", "overflow"))
GUARANTEE_CHECKS = (
    "lock_ledger_closes", "balance_conserved",
    "replication_pushes_equal_installs", "every_write_in_three_logs",
    *(f"device_{d}_acked_writes_read_back_from_ring_{ring}"
      for d in range(N) for ring, _ in WHERE[d]["streams"]),
    *(f"backup_{slot + 1}_of_device_{d}_equals_primary"
      for d in range(N) for _, slot in WHERE[d]["backups"]),
    *(f"stream_{d}_identical_in_three_rings" for d in range(N)),
    "xshard_txns_as_partitioned", "remote_lock_lanes_as_partitioned")
COMPARE_CHECKS = (
    "compare.stats_equal_reference", "compare.primaries_equal_reference",
    "compare.backups_equal_reference",
    "compare.three_log_streams_equal_reference",
    *(f"compare.lost_device_recovered_from_stream_{h}" for h in range(3)))
ENGINE = dict(use_hotset=False, trace=False)
SIGMAS = 6.0


# A program from before this deployment (no ``xshard_txns`` counter, no
# magic word in its log entries) cannot run it: say so and exit as the
# module is imported, which run.py does before it starts the chips.
if not hasattr(mon, "CTR_XSHARD_TXNS"):
    raise SystemExit("this program's dense_sharded_sb lacks the counters "
                     "and log entries smallbank24m-x4r3 is held to")


def cohort_program(n_accounts: int, w: int, cohorts_per_block: int,
                   shape: dict):
    """block key -> (ttype, a1, a2, ts_amt), each [cohorts_per_block, N,
    w]: the cohorts the four devices generate from that key, again. A
    block splits its key into one per step; a device folds its index into
    the step's key and splits off the generator's key and the
    TRANSACT_SAVING amounts' (dense_sharded_sb ``local_step``)."""
    def one(step_key, dev):
        kgen, kamt = jax.random.split(jax.random.fold_in(step_key, dev))
        amounts = jax.random.randint(kamt, (w,), -sp.TS_AMT_MAX,
                                     sp.TS_AMT_MAX + 1, dtype=jnp.int32)
        return (*sp.gen_cohort(kgen, w, n_accounts, **shape), amounts)

    devs = jnp.arange(N, dtype=jnp.int32)
    return jax.jit(lambda key: jax.vmap(
        lambda k: jax.vmap(lambda d: one(k, d))(devs))(
            jax.random.split(key, cohorts_per_block)))


def reference_run(bank: ref.ShardedSmallBank, cohorts, keys,
                  tally=None) -> np.ndarray:
    """The stats rows a pipeline started empty, fed the blocks of ``keys``
    and drained hands out (smallbank_dense_one_chip.reference_run, a
    step's four cohorts at a time)."""
    rows = [np.zeros(len(STAT_NAMES), np.int64)]
    for key in keys:
        block = [np.asarray(x) for x in cohorts(key)]
        if tally is not None:
            tally.add(*block[:3])
        rows += [bank.step([tuple(x[j, d] for x in block)
                            for d in range(N)])
                 for j in range(block[0].shape[0])]
    bank.drain()
    return np.stack(rows)


def distributed_shares(shape: dict, n_accounts: int) -> dict:
    """Per transaction, from the mix and ``owner = account % N``: the
    chance that its lock set names two owners, and the mean and variance
    of its lock requests to another device than its source (the source is
    independent of the accounts, a draw's owner is not uniform where N
    does not divide the hot set or the table)."""
    def residues(n):     # the share of [0, n) that each device owns
        return np.array([len(range(r, n, N)) for r in range(N)]) / n

    hot_n = max(int(n_accounts * shape["hot_frac"]), 1)
    p_own = shape["hot_prob"] * residues(hot_n) \
        + (1 - shape["hot_prob"]) * residues(n_accounts)
    same = float((p_own ** 2).sum())        # two draws, one owner
    remote = 1.0 - 1.0 / N      # a lane's owner is not its source
    mix = np.asarray(shape["mix"], np.float64)
    mix = mix / mix.sum()
    p_x = mean = second = 0.0
    for tt, lock_set in ref_one.LOCK_SETS.items():
        on = [sum(which == k for _, _, which in lock_set) for k in (0, 1)]
        two = all(on)
        p_x += mix[tt] * (1.0 - same) * two
        # lanes on account k are remote together: on[k] * Bernoulli
        m = sum(c * remote for c in on)
        v = sum(c * c * remote * (1 - remote) for c in on)
        mean += mix[tt] * m
        second += mix[tt] * (v + m * m)
    return {"p_xshard": p_x, "remote_mean": mean,
            "remote_var": second - mean * mean}


def stream_entries(ring: np.ndarray, heads: np.ndarray, tag: int) -> list:
    """(table, account, step, balance, magic) of every entry of one
    stream an unwrapped ring holds, sorted."""
    e = np.concatenate(lane_streams(ring, heads, tag))
    return sorted(zip((e[:, 0] >> 8).tolist(), e[:, 2].tolist(),
                      e[:, 3].tolist(), e[:, 4].tolist(), e[:, 5].tolist()))


class ShardedBank:
    stat_names = STAT_NAMES
    outcomes = OUTCOMES
    faults = FAULTS
    contention = CONTENTION
    depth = 2
    n_devices = N

    def __init__(self, sizes: dict, params: dict, config: dict, seed: int,
                 devices, emit):
        self.n = n = sizes["n_accounts"]
        self.init_balance = sizes["init_balance"]
        self.n_loc = dsb.n_acct_local(n, N)
        self.m1 = m1 = dsb.m1_local(n, N)
        self.w = w = params["w"]
        cpb = params["cohorts_per_block"]
        self.txns_per_dispatch = N * w * cpb
        self.steps_per_dispatch = cpb
        self.seed = seed
        mesh = Mesh(np.array(devices), (AX,))

        t0 = time.perf_counter()
        state = dsb.create_sharded_sb(
            mesh, N, n, self.init_balance, log_lanes=sizes["log_lanes"],
            log_capacity=sizes["log_capacity"])
        jax.block_until_ready(state)
        self.ring_rows = state.log.entries.shape[-2]    # lanes * slots
        self.geometry = {
            "w": w, "l": sp.L, "val_words": sp.VW, "log_replicas": 3,
            "n_backups": dsb.N_BCK, "n_devices": N,
            "bucket_cap": ref.bucket_cap(w, N, sp.L),
            "state_bytes_per_device": sum(
                int(x.nbytes) for x in jax.tree.leaves(state)) // N,
            "ring_bytes": int(state.log.entries.nbytes) // N}
        emit(phase="populate", n_accounts=n, n_accounts_local=self.n_loc,
             cohorts_per_block=cpb, seconds=time.perf_counter() - t0,
             bytes_in_use=[(d.memory_stats() or {}).get("bytes_in_use")
                           for d in devices], **self.geometry)

        shape = one_chip.shape_args(config)
        self.traffic_shape = config["traffic_shape"]
        self._run, self._init, self._drain = dsb.build_sharded_sb_runner(
            mesh, N, n, w=w, cohorts_per_block=cpb, monitor=True, **shape,
            **ENGINE)
        self._cohorts = cohort_program(n, w, cpb, shape)
        self._state = state
        self._heads_seen = 0
        # the warm-up's dispatches, for the reference: (key, stats on the
        # device); None once the warm-up is verified
        self._warm: list | None = []

        def total(bal):
            own = jax.lax.bitcast_convert_type(bal[0, :-1], jnp.int32)
            return jax.lax.psum(jnp.sum(own, dtype=jnp.int32), AX)

        self._total = jax.jit(jax.shard_map(
            total, mesh=mesh, in_specs=P(AX), out_specs=P()))
        self._balance = int(self._total(state.bal))

        def gatherer(base: int):
            """Rows [N, R] of each device's own shard of a balance array,
            ``base`` rows into it; -1 reads the never-written last row."""
            def local(bal, rows):
                r = jnp.where(rows[0] < 0, m1 - 1, rows[0]) + base
                return bal[0][r][None]

            return jax.jit(jax.shard_map(
                local, mesh=mesh, in_specs=(P(AX),) * 2, out_specs=P(AX)))

        self._gather_primary = gatherer(0)
        self._gather_backup = [gatherer(slot * m1)
                               for slot in range(dsb.N_BCK)]

    def start(self):
        state, self._state = self._state, None
        return self._init(state)

    def restart(self, final):
        return self._init(final[0])

    def dispatch(self, carry, key):
        carry, stats = self._run(carry, key)
        if self._warm is not None:
            self._warm.append((key, stats))
        return carry, stats

    def drain(self, carry):
        out = self._drain(carry)
        return out, np.asarray(out[1], np.int64)

    # ------------------------------------------------------- on the device

    def rows_of(self, gather, table, rows: np.ndarray) -> np.ndarray:
        """``gather(table, rows)`` for rows [N, k], in ring-sized pieces
        (one compiled shape), fetched: [N, k]."""
        out = []
        for i in range(0, rows.shape[1], self.ring_rows):
            piece = np.full((N, self.ring_rows), -1, np.int32)
            k = rows[:, i:i + self.ring_rows].shape[1]
            piece[:, :k] = rows[:, i:i + self.ring_rows]
            out.append(np.asarray(gather(table, piece))[:, :k])
        return np.concatenate(out, axis=1) if out \
            else np.zeros((N, 0), np.uint32)

    def everywhere(self, state, rows: np.ndarray) -> tuple:
        """Rows [N, k] of every device's primary range (-1: the sentinel)
        as the primary holds them and as both backup slots do: (primary
        [N, k], [backup slot s of device d + s + 1, by d: [N, k]])."""
        primary = self.rows_of(self._gather_primary, state.bal, rows)
        backups = []
        for slot in range(dsb.N_BCK):
            held = self.rows_of(self._gather_backup[slot], state.bck_bal,
                                np.roll(rows, slot + 1, axis=0))
            backups.append(np.roll(held, -(slot + 1), axis=0))
        return primary, backups

    # ------------------------------------------------------------ warm-up

    def check_warmup(self, state, tail: np.ndarray,
                     checks: ck.Checks) -> None:
        """The timed program's own warm-up, at the deployment's scale,
        against the reference run over the same cohorts from the
        populated state: every step's stats, and every row the reference
        wrote on its primary and on both backups; and those cohorts
        against the shape the configuration states."""
        t0 = time.perf_counter()
        warm, self._warm = self._warm, None
        got = np.concatenate([np.asarray(s, np.int64) for _, s in warm]
                             + [np.asarray(tail, np.int64)])
        bank = ref.ShardedSmallBank(
            self.n, N, self.init_balance, cap=self.geometry["bucket_cap"],
            by_cohort=True)
        tally = one_chip.TrafficTally(self.n, self.traffic_shape)
        want = reference_run(bank, self._cohorts, [k for k, _ in warm],
                             tally)
        checks.add("warmup.traffic_as_configured", **tally.result())
        checks.add("warmup.stats_equal_reference",
                   np.array_equal(got, want) and len(warm) > 0,
                   steps=len(want) - 1, totals=got.sum(axis=0),
                   distributed=bank.distributed,
                   **one_chip.first_difference(got, want))
        touched = bank.all_touched()
        k = max(len(rows) for rows, _ in touched)
        rows = np.full((N, k), -1, np.int64)
        want = np.zeros((N, k), np.uint32)      # the sentinel's balance
        for d, (r, b) in enumerate(touched):
            rows[d, :len(r)], want[d, :len(r)] = r, b
        primary, backups = self.everywhere(state, rows.astype(np.int32))
        differs = [int((x != want).sum()) for x in (primary, *backups)]
        checks.add("warmup.touched_rows_equal_reference",
                   not any(differs) and min(len(r) for r, _ in touched) > 0,
                   rows=[len(r) for r, _ in touched],
                   differs_primary_backup1_backup2=differs,
                   reference_s=time.perf_counter() - t0)

    # ------------------------------------------------------- every phase

    def read_back(self, ring: np.ndarray, heads: np.ndarray, tag: int,
                  dev: int) -> dict:
        """One stream of one ring, planned: the newest surviving entry of
        every row it names, as device ``dev``'s local row, with the
        balance the live row must hold."""
        plan = ck.plan_readback(ring, heads, (self.n, self.n), sp.VW,
                                key_hi=tag)
        table, acct = plan["rows"] // self.n, plan["rows"] % self.n
        entries, _, _ = ck.surviving_entries(ring, heads)
        entries = entries[entries[:, 1] == np.uint32(tag)]
        plan["local"] = ref.local_row(table, acct, self.n_loc, N)
        plan["in_range"] = plan["in_range"] and bool(
            (ref.owner(acct, N) == dev).all())
        plan["bad_magic"] = int(
            (entries[:, ck.HDR_WORDS + 1] != ref_one.MAGIC).sum())
        return plan

    @staticmethod
    def held_to(plan: dict, live: np.ndarray) -> dict:
        held = plan["fresh"] | (not plan["wrapped"])
        differs = (live != plan["val"][:, 0]) & held
        return {"ok": plan["in_range"] and bool(held.any())
                and not differs.any() and plan["bad_magic"] == 0,
                "keys": len(held), "entries": plan["n_entries"],
                "held": int(held.sum()), "differs": int(differs.sum()),
                "bad_magic": plan["bad_magic"], "wrapped": plan["wrapped"],
                "in_range": plan["in_range"]}

    def verify(self, final, checks: ck.Checks, tag: str, totals: dict,
               dispatched: int) -> dict:
        state, tail, counters = final
        snap = monitor.snapshot(counters)
        ck.check_accounting(checks, tag, totals, snap, dispatched, OUTCOMES,
                            FAULTS, COUNTER_PAIRS)
        ck.check_lock_ledger(checks, tag, snap)
        balance = int(self._total(state.bal))
        moved, self._balance = balance - self._balance, balance
        checks.add(f"{tag}.balance_conserved",
                   moved % (1 << 32) == totals["bal_delta"] % (1 << 32),
                   moved=moved, bal_delta=totals["bal_delta"])
        if self._warm is not None:
            self.check_warmup(state, tail, checks)

        checks.add(f"{tag}.replication_pushes_equal_installs",
                   snap["repl_push_hop1"] == snap["repl_push_hop2"]
                   == snap["install_writes"] > 0,
                   hop1=snap["repl_push_hop1"], hop2=snap["repl_push_hop2"],
                   install_writes=snap["install_writes"])
        rings, heads = rings_of(state.log, N)
        appended = int(heads.astype(np.int64).sum())
        entries, self._heads_seen = appended - self._heads_seen, appended
        checks.add(f"{tag}.every_write_in_three_logs",
                   entries == 3 * snap["install_writes"]
                   == 3 * snap["log_appends"], log_entries=entries,
                   install_writes=snap["install_writes"])

        # device d's stream: in its own ring (tag 0) and, tagged d + 1, in
        # the rings of the two devices that hold its backups. The rows its
        # own ring names, padded with a seeded sample of the rest, are
        # also what both backup slots are held to
        rng = np.random.default_rng(self.seed % (1 << 32))
        sample = rng.integers(0, self.m1 - 1, (N, self.ring_rows),
                              dtype=np.int32)
        for off in range(3):
            plans = [self.read_back(rings[(d + off) % N],
                                    heads[(d + off) % N],
                                    0 if off == 0 else d + 1, d)
                     for d in range(N)]
            rows = sample if off == 0 else np.full_like(sample, -1)
            for d, plan in enumerate(plans):
                rows[d, :len(plan["local"])] = plan["local"]
            if off == 0:
                live, backups = self.everywhere(state, rows)
                primary = live
            else:
                live = self.rows_of(self._gather_primary, state.bal, rows)
            for d, plan in enumerate(plans):
                checks.add(f"{tag}.device_{d}_acked_writes_read_back_"
                           f"from_ring_{(d + off) % N}",
                           **self.held_to(plan, live[d, :len(plan["local"])]))
        for slot, held in enumerate(backups):
            for d in range(N):
                checks.add(
                    f"{tag}.backup_{slot + 1}_of_device_{d}_equals_primary",
                    np.array_equal(held[d], primary[d]),
                    rows=int(sample.shape[1]), holder=(d + slot + 1) % N,
                    differs=int((held[d] != primary[d]).sum()))
        for d, res in enumerate(streams_identical(rings, heads)):
            checks.add(f"{tag}.stream_{d}_identical_in_three_rings", **res)

        # how much of the traffic was distributed, against what the mix
        # and the partition give
        n = totals["attempted"]
        stated = distributed_shares(self.traffic_shape, self.n)
        p = stated["p_xshard"]
        for name, count, mean, var in (
                ("xshard_txns", snap["xshard_txns"], n * p,
                 n * p * (1 - p)),
                ("remote_lock_lanes", snap["remote_lock_lanes"],
                 n * stated["remote_mean"], n * stated["remote_var"])):
            band = SIGMAS * var ** 0.5 + 1.0
            checks.add(f"{tag}.{name}_as_partitioned",
                       n > 0 and abs(count - mean) <= band, counted=count,
                       stated=mean, band=band, transactions=n)
        return snap


def build(config: dict, params: dict, seed: int, devices, emit,
          rehearse: bool) -> ShardedBank:
    if len(devices) != N:
        raise SystemExit(f"this deployment is laid out on {N} devices, "
                         f"not {len(devices)}")
    sizes = config["rehearse"] if rehearse else config["sizes"]
    return ShardedBank(sizes, params, config, seed, devices, emit)


# ------------------------------------ against independent code, small size


def compare_small(config: dict, seed: int, checks: ck.Checks) -> None:
    """The four-device block and drain at the configuration's
    ``compare_small`` size (rings sized so that none wraps) against the
    sequential reference on the same cohorts, exactly: every step's
    stats, every primary and backup table whole, every entry of every
    stream of every ring, and a lost device's range recovered from each
    of its three streams, by the program's recovery and by the
    reference's replay."""
    size = config["compare_small"]
    n, w, cpb = size["n_accounts"], size["w"], size["cohorts_per_block"]
    init_balance = config["sizes"]["init_balance"]
    devices = jax.devices()[:N]
    if len(devices) < N:
        raise SystemExit(f"the comparison runs the {N}-device program: "
                         f"found {len(devices)} devices")
    dep = ShardedBank({**size, "init_balance": init_balance},
                      {"w": w, "cohorts_per_block": cpb}, config, seed,
                      devices, lambda **kw: None)
    dep._warm = None
    key = jax.random.PRNGKey(seed)
    keys = [jax.random.fold_in(key, i) for i in range(size["blocks"])]
    carry, got = dep.start(), []
    for k in keys:
        carry, stats = dep.dispatch(carry, k)
        got.append(np.asarray(stats, np.int64))
    (state, _, _), tail = dep.drain(carry)
    got = np.concatenate([*got, tail])

    bank = ref.ShardedSmallBank(n, N, init_balance,
                                cap=dep.geometry["bucket_cap"])
    want = reference_run(bank, dep._cohorts, keys)
    checks.add("compare.stats_equal_reference", np.array_equal(got, want),
               steps=len(want) - 1, totals=got.sum(axis=0),
               distributed=bank.distributed,
               **one_chip.first_difference(got, want))

    m1 = dep.m1
    live, bck = np.asarray(state.bal), np.asarray(state.bck_bal)
    tables = [bank.table(d) for d in range(N)]
    primaries = [np.array_equal(live[d], tables[d]) for d in range(N)]
    checks.add("compare.primaries_equal_reference",
               all(primaries) and int(dep._total(state.bal))
               == bank.total_balance()
               and any((t != ref.fresh_table(dep.n_loc, init_balance)).any()
                       for t in tables),
               per_device=primaries)
    backups = [[np.array_equal(bck[h, slot * m1:(slot + 1) * m1], tables[d])
                for h, slot in WHERE[d]["backups"]] for d in range(N)]
    checks.add("compare.backups_equal_reference",
               all(all(b) for b in backups), per_device=backups)

    rings, heads = rings_of(state.log, N)
    wrapped = bool((heads.astype(np.int64) > rings.shape[2]).any())
    logs = [[stream_entries(rings[ring], heads[ring], tag)
             == sorted(bank.stream(d)) for ring, tag in WHERE[d]["streams"]]
            for d in range(N)]
    same_order = streams_identical(rings, heads)
    checks.add("compare.three_log_streams_equal_reference",
               not wrapped and all(all(x) for x in logs)
               and all(s["ok"] for s in same_order),
               wrapped=wrapped, per_device=logs,
               entries=[s["compared"] for s in same_order],
               out_of_order=[s["differs"] for s in same_order])
    for h in range(3):
        ok = []
        for d in range(N):
            ring, tag = WHERE[d]["streams"][h]
            try:
                program = recovery.recover_sb_shard(
                    n, d, N, rings[ring], heads[ring], init_balance,
                    ring_owner=ring)
                replayed = ref.replay(
                    stream_entries(rings[ring], heads[ring], tag), d, n, N,
                    init_balance)
            except ValueError as e:     # a stream neither can follow
                ok.append(str(e))
                continue
            ok.append(np.array_equal(program, live[d])
                      and np.array_equal(replayed, live[d]))
        checks.add(f"compare.lost_device_recovered_from_stream_{h}",
                   all(x is True for x in ok), per_device=ok)
