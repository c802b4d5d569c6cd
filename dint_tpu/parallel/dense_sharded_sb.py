"""Multi-chip dense SmallBank: cross-device transactions over ICI.

Unlike TATP (every table keys by subscriber id, so parallel/
dense_sharded.py makes txns device-local by re-partitioning), SmallBank's
Amalgamate/SendPayment touch TWO accounts that land on different shards no
matter how the keyspace is cut (smallbank/caladan/client_ebpf_shard.cc:255,
830) — the reference's coordinator fans each transaction's lock/commit
messages to up to 3 servers and pays a network RTT per wave. This module
is that distributed transaction structure as ICI collectives:

  wave 1 of step T (cohort t):
    * every device generates w txns over the GLOBAL keyspace (accounts
      round-robin partitioned: owner = account % D, so the 4% hot set
      spreads across all devices);
    * lock+read requests are compacted per owner and exchanged with ONE
      `all_to_all` (the reference's per-shard request batches,
      client_ebpf_shard.cc:287-325, as one collective instead of D
      socket fan-outs);
    * owners arbitrate no-wait S/X grants against their local step-stamp
      tables (same closed form as engines/smallbank_dense.py) and serve
      the fused balance read; replies return with a second `all_to_all`;
    * the source device classifies outcomes and runs the shared
      compute_phase.

  wave 2 of step T+1 (cohort t installs):
    * committed writes are routed to owners the same way and installed;
    * each owner forwards its applied installs to devices owner+1/owner+2
      with `ppermute`, which update their backup copies and append their
      own logs — CommitBck x2 + CommitLog x3
      (client_ebpf_shard.cc:779-860);
    * stats are `psum`med: batched 2PC vote collection.

Locks are held across exactly one step boundary (stamps expire), so
cross-device lock conflicts between consecutive cohorts are real, like
the single-chip dense engine — but here the conflicting txns live on
different devices.

Static-shape routing: per-destination capacity is 2x the uniform share
(`cap = 2 * ceil(w*L/D)`); lanes that overflow a destination bucket are
counted as lock rejects (the reference client's retry under overload —
here a no-wait reject, bounded by the slack) AND separately in the
psummed STAT_OVERFLOW counter, so overflow is observable — tests assert
it is zero at configured widths (round-robin partitioning keeps
destinations near-uniform even under the 90%/4% hot skew).

Balance conservation holds GLOBALLY: psummed STAT_BAL_DELTA must equal
the delta of the all-device balance sum — checked in tests; a
cross-device install bug cannot hide.
"""
from __future__ import annotations

import functools

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engines.smallbank_pipeline import (L, MAGIC, TS_AMT_MAX, VW, N_STATS,
                                          STAT_ATTEMPTED, STAT_COMMITTED,
                                          STAT_AB_LOCK, STAT_AB_LOGIC,
                                          STAT_BAL_DELTA, compute_phase,
                                          gen_cohort, _lock_slots)
from ..engines.types import Op
from ..engines._memo import memoize_builder
from ..monitor import counters as mon
from ..monitor import txnevents as txe
from ..monitor import waves
from ..ops import compact, hotset
from ..tables import log as logring
from .sharded import SHARD_AXIS, make_mesh, pcast_varying   # noqa: F401 (re-exported)

I32 = jnp.int32
U32 = jnp.uint32

BIG = jnp.int32(1 << 30)
N_BCK = 2
AXIS = SHARD_AXIS

# sharded stats append a routing-overflow counter to the shared layout
STAT_OVERFLOW = N_STATS
N_STATS = N_STATS + 1


@flax.struct.dataclass
class SBShard:
    """One device's slice: primary balances for its account range, backup
    copies of the two predecessors' ranges, step-stamp lock tables, log.

    The ``hot_*`` leaves are the per-device dintcache hot tier (round 10):
    round-robin partitioning puts global hot account ``a < hot_n`` at
    device ``a % D`` local index ``a // D``, so each device's hot set is
    its LOCAL account prefix ``q < hot_loc`` (hot_loc = ceil(hot_n / D);
    the mirror may cover a couple of tail accounts past the global hot_n
    on some devices — a superset is harmless, coherence is per-row).
    Mirror index = tbl*hot_loc + q; installs write through. The sharded
    lock tables are exact (slot == local row), so stamps always mirror."""
    bal: jax.Array       # u32 [m1_loc]  (sentinel last)
    bck_bal: jax.Array   # u32 [N_BCK * m1_loc]
    x_step: jax.Array    # u32 [m1_loc]
    s_step: jax.Array    # u32 [m1_loc]
    step: jax.Array      # u32 scalar (starts at 2, == single-chip engine)
    log: logring.RepLog  # replicas=1: the 3 copies live on 3 devices
    hot_bal: jax.Array | None = None   # u32 [2*hot_loc]
    hot_x: jax.Array | None = None     # u32 [2*hot_loc]
    hot_s: jax.Array | None = None     # u32 [2*hot_loc]
    hot_loc: int = flax.struct.field(pytree_node=False, default=0)


def n_acct_local(n_accounts: int, d: int) -> int:
    return (n_accounts + d - 1) // d


def m1_local(n_accounts: int, d: int) -> int:
    return 2 * n_acct_local(n_accounts, d) + 1


def attach_hotset_sb(mesh: Mesh, state: SBShard, hot_loc: int) -> SBShard:
    """Build each device's hot mirror from its current local tables
    (leaves here are the stacked [D, ...] arrays)."""
    n_loc = state.bal.shape[1] // 2
    hot_loc = int(min(max(int(hot_loc), 1), n_loc))
    idx = jnp.concatenate([jnp.arange(hot_loc, dtype=I32),
                           n_loc + jnp.arange(hot_loc, dtype=I32)])
    shard = NamedSharding(mesh, P(AXIS))
    put = lambda x: jax.device_put(x, shard)    # noqa: E731
    return state.replace(
        hot_bal=put(state.bal[:, idx]),
        hot_x=put(state.x_step[:, idx]),
        hot_s=put(state.s_step[:, idx]),
        hot_loc=hot_loc)


def create_sharded_sb(mesh: Mesh, n_shards: int, n_accounts: int,
                      init_balance: int = 1000, log_lanes: int = 16,
                      log_capacity: int = 1 << 16) -> SBShard:
    m1 = m1_local(n_accounts, n_shards)
    bal = jnp.full((m1,), np.uint32(init_balance), U32).at[-1].set(0)
    one = SBShard(
        bal=bal,
        bck_bal=jnp.concatenate([bal, bal]),
        x_step=jnp.zeros((m1,), U32),
        s_step=jnp.zeros((m1,), U32),
        step=jnp.asarray(2, U32),
        log=logring.create_rep(log_lanes, log_capacity, VW, replicas=1))
    shard = NamedSharding(mesh, P(AXIS))
    return jax.tree.map(
        lambda x: jax.device_put(
            jnp.broadcast_to(x[None], (n_shards,) + x.shape), shard), one)


def total_balance_global(state: SBShard):
    """Host-side: global balance sum over all primaries (i32 wraparound,
    matching STAT_BAL_DELTA accounting)."""
    bal = np.asarray(state.bal)            # [D, m1]
    return int(bal[:, :-1].astype(np.uint32).view(np.int32)
               .sum(dtype=np.int32))


def _route(dest, pos, valid, cap, n_shards, fields):
    """Scatter per-lane fields into [D*cap] destination buckets (flat
    index dest*cap + pos; invalid lanes drop out of bounds). Returns the
    list of routed [D*cap] arrays."""
    idx = jnp.where(valid, dest * cap + pos, n_shards * cap)
    return [jnp.zeros((n_shards * cap,), f.dtype)
            .at[idx].set(f, mode="drop", unique_indices=True)
            for f in fields]


def _a2a(x, n_shards, cap):
    """Exchange [D*cap] buckets: device s's bucket d lands at device d's
    slot s."""
    return jax.lax.all_to_all(x.reshape(n_shards, cap), AXIS, 0, 0,
                              tiled=False).reshape(n_shards * cap)


def _positions(dest, active, n_shards):
    """Per-destination arrival ranks: pos[i] = #{j < i : dest j == dest i,
    active}. One [wL, D] one-hot exclusive cumsum — no sort."""
    oh = (dest[:, None] == jnp.arange(n_shards, dtype=I32)[None]) & \
        active[:, None]
    excl = jnp.cumsum(oh.astype(I32), axis=0) - oh.astype(I32)
    return jnp.take_along_axis(excl, dest[:, None], axis=1)[:, 0]


@flax.struct.dataclass
class SBCtx:
    """A cohort between cross-device lock+compute and install."""
    acc: jax.Array       # i32 [w, L] global accounts
    tbl: jax.Array       # i32 [w, L]
    do_write: jax.Array  # bool [w, L]
    nw: jax.Array        # i32 [w, L]
    attempted: jax.Array
    committed: jax.Array
    ab_lock: jax.Array
    ab_logic: jax.Array
    magic_bad: jax.Array
    bal_delta: jax.Array
    overflow: jax.Array  # lanes dropped by destination-bucket overflow


def _empty_sb_ctx(w: int) -> SBCtx:
    def z(shape, dt):
        return jnp.asarray(np.zeros(shape, dt))

    return SBCtx(acc=z((w, L), np.int32), tbl=z((w, L), np.int32),
                 do_write=z((w, L), bool), nw=z((w, L), np.int32),
                 attempted=z((), np.int32), committed=z((), np.int32),
                 ab_lock=z((), np.int32), ab_logic=z((), np.int32),
                 magic_bad=z((), np.int32), bal_delta=z((), np.int32),
                 overflow=z((), np.int32))


def _stats_of(c: SBCtx):
    return jnp.stack([c.attempted, c.committed, c.ab_lock, c.ab_logic,
                      c.magic_bad, c.bal_delta, c.overflow])


@memoize_builder
def build_sharded_sb_runner(mesh: Mesh, n_shards: int, n_accounts: int,
                            w: int = 2048, cohorts_per_block: int = 8,
                            hot_frac=None, hot_prob=None, mix=None,
                            use_hotset=None, monitor: bool = False,
                            trace=None, trace_rate=None, trace_cap=None):
    """jit(shard_map(scan(step))). Contract mirrors the single-chip dense
    runner: (run, init, drain); stats are psummed across the mesh.

    ``use_hotset``: None = honor DINT_USE_HOTSET env. Per-device dintcache
    partition over the owner-side gathers (SBShard docstring): hot lanes
    read the local mirror, installs write through; init() attaches the
    mirror. Hot set defaults to the workload's (``hot_frac``). Outputs
    bit-identical to the default path (tests/test_hotset.py).

    ``monitor``: thread the dintmon counter plane PER DEVICE. Txn
    outcomes count at the source device (where the cohort completes);
    lock arbitration and installs count at the OWNER device (where they
    execute); replication pushes count at the receiving backup; routing
    overflow counts with the completing cohort's stats. Flow counters
    therefore sum across the device axis to the psummed stats totals.
    Drain returns (state, stats, counters); off (default) = contract and
    jaxpr unchanged.

    ``trace`` / ``trace_rate`` / ``trace_cap``: the dinttrace flight
    recorder (None = honor DINT_TRACE / DINT_TRACE_RATE); a per-device
    txnevents.TxnRing carry leaf lands BEFORE the counters leaf. This is
    the payoff path: the txn id — (gen_step*D + source_dev)*w + lane, the
    same id on every device — RIDES THE ROUTE (one extra u32 field
    through the lock all_to_all, one through the install all_to_all, and
    the ppermute fan-out forwards it to the backups), so source-side
    ROUTE/VOTE/OUTCOME, owner-side LOCK/INSTALL, and backup-side REPL
    events of one transaction join by id into a single 2PC span tree.
    Off = routed fields, jaxpr, and outputs all bit-identical."""
    d = n_shards
    n_loc = n_acct_local(n_accounts, d)
    m1 = m1_local(n_accounts, d)
    sent = m1 - 1
    oob = m1
    cap = 2 * ((w * L + d - 1) // d)
    use_hotset = hotset.resolve_use_hotset(use_hotset)
    hot_loc = 0
    if use_hotset:
        from ..clients import workloads as wl
        frac = wl.SB_HOT_FRAC if hot_frac is None else float(hot_frac)
        hot_n = max(1, min(int(n_accounts * frac), n_accounts))
        hot_loc = min((hot_n + d - 1) // d, n_loc)
    kw_gen = {}
    if hot_frac is not None:
        kw_gen["hot_frac"] = hot_frac
    if hot_prob is not None:
        kw_gen["hot_prob"] = hot_prob
    trace_on = txe.trace_enabled(trace)
    tcfg = None
    if trace_on:
        # per-device candidates/step: ROUTE [wL] + owner LOCK [d*cap] +
        # VOTE [w] + owner INSTALL [d*cap] + REPL x2 [2*d*cap] +
        # OUTCOME [w]; d*cap = 2*wL rounded up
        n_step = w * L + 4 * d * cap + 2 * w
        rcap = int(trace_cap) if trace_cap else n_step * cohorts_per_block
        tcfg = txe.TraceCfg(rate=txe.trace_rate(trace_rate), cap=rcap,
                            wave=waves.full_name("dense_sharded_sb",
                                                 "trace"))

    def local_step(state: SBShard, c1: SBCtx, key, cnt, ring,
                   gen_new=True):
        with waves.part("dense_sharded_sb", "sbx_frame"):
            dev = jax.lax.axis_index(AXIS)
            t = state.step
            kgen, kamt = jax.random.split(jax.random.fold_in(key, dev))

        # ---- wave 1: generate + route lock/read requests to owners ----
        if gen_new:
            with waves.scope("dense_sharded_sb", "gen"):
                ttype, a1, a2 = gen_cohort(kgen, w, n_accounts, mix=mix,
                                           **kw_gen)
                l_op, l_tb, l_ac = _lock_slots(ttype, a1, a2)
        else:
            with waves.part("dense_sharded_sb", "sbx_frame"):
                ttype = jnp.zeros((w,), I32)
                l_op = jnp.zeros((w, L), I32)
                l_tb = jnp.zeros((w, L), I32)
                l_ac = jnp.zeros((w, L), I32)
        with waves.part("dense_sharded_sb", "sbx_frame"):
            ts_amt = jax.random.randint(kamt, (w,), -TS_AMT_MAX,
                                        TS_AMT_MAX + 1, dtype=I32)

        if ring is not None:
            # dinttrace ids: one per generated txn, identical on every
            # device that touches it (the routed copies below carry it)
            with waves.part("dense_sharded_sb", "sbx_frame"):
                tu = jnp.asarray(t).astype(U32)
                du = dev.astype(U32)
                lane_w = jnp.arange(w, dtype=U32)
                txn_new = (tu * U32(d) + du) * U32(w) + lane_w
                txn_c1 = ((tu - U32(1)) * U32(d) + du) * U32(w) + lane_w

        with waves.scope("dense_sharded_sb", "route"):
            with waves.part("dense_sharded_sb", "route_addr"):
                active = (l_op != 0).reshape(-1)
                dest = (l_ac.reshape(-1) % d).astype(I32)
                row_loc = (l_tb.reshape(-1) * n_loc
                           + l_ac.reshape(-1) // d).astype(I32)
            with waves.part("dense_sharded_sb", "a2a_rank"):
                pos = _positions(dest, active, d)
            with waves.part("dense_sharded_sb", "route_addr"):
                valid = active & (pos < cap)

                fields = [l_op.reshape(-1), row_loc]
                if ring is not None:
                    fields.append(jnp.repeat(txn_new, L))
            with waves.part("dense_sharded_sb", "a2a_pack"):
                packed = _route(dest, pos, valid, cap, d, fields)
            with waves.part("dense_sharded_sb", "a2a_requests"):
                routed = [_a2a(x, d, cap) for x in packed]
            r_op, r_row = routed[:2]
            r_txn = routed[2] if ring is not None else None

        # ---- owner side: no-wait S/X arbitration + fused read ---------
        with waves.part("dense_sharded_sb", "owner_addr"):
            lanes = jnp.arange(d * cap, dtype=I32)
            is_x = r_op == Op.ACQ_X_READ
            is_s = r_op == Op.ACQ_S_READ
            rows = jnp.where(r_op != 0, r_row, sent)
        with waves.scope("dense_sharded_sb", "arbitrate"):

            def mirror_idx(rr, mask):
                """Local row -> hot mirror index (tbl*hot_loc + q), -1
                cold. The sentinel row (q == n_loc) is never hot:
                hot_loc <= n_loc."""
                tb = (rr >= n_loc).astype(I32)
                q = rr - tb * n_loc
                return jnp.where(mask & (q < hot_loc),
                                 tb * hot_loc + q, -1)

            if use_hotset:
                with waves.part("dense_sharded_sb", "owner_addr"):
                    midx = mirror_idx(rows, r_op != 0)
            with waves.part("dense_sharded_sb", "owner_arb"):
                first_x = jnp.full((m1,), BIG, I32).at[
                    jnp.where(is_x, rows, oob)].min(lanes, mode="drop")
                first_s = jnp.full((m1,), BIG, I32).at[
                    jnp.where(is_s, rows, oob)].min(lanes, mode="drop")
            with waves.part("dense_sharded_sb", "owner_held_read"):
                if use_hotset:
                    held_x = hotset.hot_gather(state.x_step, state.hot_x,
                                               rows, midx, 1) == t - 1
                    held_s = hotset.hot_gather(state.s_step, state.hot_s,
                                               rows, midx, 1) == t - 1
                else:
                    held_x = state.x_step[rows] == t - 1
                    held_s = state.s_step[rows] == t - 1
                slot_free = ~held_x & ~held_s
            with waves.part("dense_sharded_sb", "owner_grant"):
                x_wins = (first_x[rows] < first_s[rows]) & slot_free
                grant_x = is_x & x_wins & (first_x[rows] == lanes)
                grant_s = is_s & ~held_x & ~x_wins
                s_writer = grant_s & (first_s[rows] == lanes)
            with waves.part("dense_sharded_sb", "owner_stamp"):
                x_step = state.x_step.at[
                    jnp.where(grant_x, rows, oob)].set(
                    t, mode="drop", unique_indices=True)
                s_step = state.s_step.at[
                    jnp.where(s_writer, rows, oob)].set(
                    t, mode="drop", unique_indices=True)
                hot_x, hot_s = state.hot_x, state.hot_s
                if use_hotset:
                    # stamp write-through (one-writer grant masks stay
                    # unique on the mirror's index subset)
                    hot_x = hot_x.at[jnp.where(grant_x & (midx >= 0), midx,
                                               2 * hot_loc)].set(
                        t, mode="drop", unique_indices=True)
                    hot_s = hot_s.at[jnp.where(s_writer & (midx >= 0),
                                               midx, 2 * hot_loc)].set(
                        t, mode="drop", unique_indices=True)
            with waves.part("dense_sharded_sb", "owner_bal_read"):
                if use_hotset:
                    raw_bal = hotset.hot_gather(state.bal, state.hot_bal,
                                                rows, midx, 1)
                else:
                    raw_bal = state.bal[rows]
                g_bal = jnp.where(grant_x | grant_s, raw_bal.astype(I32),
                                  0)

        # ---- replies back to sources + classify -----------------------
        with waves.scope("dense_sharded_sb", "reply"):
            with waves.part("dense_sharded_sb", "a2a_replies"):
                rep_g = _a2a((grant_x | grant_s), d, cap)
                rep_b = _a2a(g_bal, d, cap)
            with waves.part("dense_sharded_sb", "reply_unpack"):
                back = jnp.where(valid, dest * cap + pos, 0)
                granted = (jnp.where(valid, rep_g[back], False)
                           .reshape(w, L))
                bal = jnp.where(granted, rep_b[back].reshape(w, L), 0)
            with waves.part("dense_sharded_sb", "reply_classify"):
                # overflowed lanes have valid=False -> granted=False, so
                # the no-wait reject covers them (the reference client's
                # retry under overload, here a bounded no-wait reject)
                lock_rejected = ((l_op != 0) & ~granted).any(axis=1)
                alive = ~lock_rejected & (l_op[:, 0] != 0)

                nw, do, logic_abort, commit, committed = compute_phase(
                    ttype, bal, alive, ts_amt)
                do_write = do & commit[:, None] & (l_op != 0)
                bal_delta = jnp.sum(jnp.where(do_write, nw - bal, 0),
                                    dtype=I32)

        with waves.part("dense_sharded_sb", "sbx_frame"):
            new_ctx = SBCtx(
                acc=l_ac, tbl=l_tb, do_write=do_write, nw=nw,
                attempted=jnp.asarray(w if gen_new else 0, I32),
                committed=committed.sum(dtype=I32),
                ab_lock=(lock_rejected & (l_op[:, 0] != 0)).sum(dtype=I32),
                ab_logic=logic_abort.sum(dtype=I32),
                magic_bad=jnp.asarray(0, I32),
                bal_delta=bal_delta,
                overflow=(active & ~valid).sum(dtype=I32))

        # ---- wave 2 of c1: route installs to owners -------------------
        with waves.scope("dense_sharded_sb", "install_route"):
            with waves.part("dense_sharded_sb", "route_addr"):
                wmask = c1.do_write.reshape(-1)
                wdest = (c1.acc.reshape(-1) % d).astype(I32)
                wrow = (c1.tbl.reshape(-1) * n_loc
                        + c1.acc.reshape(-1) // d).astype(I32)
            with waves.part("dense_sharded_sb", "a2a_rank"):
                wpos = _positions(wdest, wmask, d)
            with waves.part("dense_sharded_sb", "route_addr"):
                wvalid = wmask & (wpos < cap)  # no overflow: writes <= locks
                ifields = [wmask.astype(I32), wrow, c1.nw.reshape(-1),
                           c1.tbl.reshape(-1), c1.acc.reshape(-1)]
                if ring is not None:
                    ifields.append(jnp.repeat(txn_c1, L))
            with waves.part("dense_sharded_sb", "a2a_pack"):
                ipacked = _route(wdest, wpos, wvalid, cap, d, ifields)
            with waves.part("dense_sharded_sb", "a2a_installs"):
                inst = [_a2a(x, d, cap) for x in ipacked]
            i_m, i_row, i_bal, i_tbl, i_acc = inst[:5]
            i_txn = inst[5] if ring is not None else None

            with waves.part("dense_sharded_sb", "owner_install"):
                i_mask = i_m != 0
                irows = jnp.where(i_mask, i_row, oob)
                hot_bal = state.hot_bal
                if use_hotset:
                    # partitioned write-through install (double 1-D
                    # unique-index scatter)
                    i_midx = mirror_idx(i_row, i_mask)
                    bal_new, hot_bal = hotset.hot_scatter(
                        state.bal, hot_bal, i_row, i_midx, i_mask,
                        i_bal.astype(U32), 1)
                else:
                    bal_new = state.bal.at[irows].set(i_bal.astype(U32),
                                                      mode="drop",
                                                      unique_indices=True)

        def log_value(mask, balv):
            # the value a ring names: {balance, magic}, as
            # engines/smallbank_dense.py logs it (the table keeps the
            # balance alone)
            newval = jnp.zeros((mask.shape[0], VW), U32)
            newval = newval.at[:, 0].set(balv.astype(U32))
            return newval.at[:, 1].set(jnp.where(mask, U32(MAGIC), U32(0)))

        def mk_entry(mask, row, balv, tblv, accv, ring, bck, slot, src_dev):
            # forwarded entries tag key_hi = SOURCE device + 1 (own entries
            # log 0, below) — same separable-stream convention as the TATP
            # path (parallel/dense_sharded._apply_backup), so recovery can
            # verify a ring's streams against acct % n_shards geometry
            with waves.part("dense_sharded_sb", "sb_bck_scatter"):
                rr = jnp.where(mask, slot * m1 + row, N_BCK * m1)
                bck = bck.at[rr].set(balv.astype(U32), mode="drop",
                                     unique_indices=True)
            with waves.part("dense_sharded_sb", "sb_bck_log_append"):
                live = compact.prefixed(mask, d)
                ring = logring.append_rep(
                    ring, live, tblv, jnp.zeros_like(balv),
                    jnp.broadcast_to(src_dev.astype(U32) + U32(1),
                                     mask.shape),
                    accv.astype(U32), jnp.broadcast_to(t, mask.shape),
                    log_value(mask, balv))
            return ring, bck, live

        # owner logs its installs (CommitLog at the primary). An inbox's
        # install mask is D segments of `cap` slots, each live in a prefix:
        # `_route` places a source's valid installs at dest * cap + their
        # arrival rank, the all_to_all moves whole segments and a ppermute
        # forwards the mask unchanged. So the three appends issue their
        # live rows, found with no search (~17 % of the D x cap slots at
        # the benchmark's width; a dropped row costs what a live one costs)
        with waves.scope("dense_sharded_sb", "install_route"):
            with waves.part("dense_sharded_sb", "owner_log_append"):
                i_live = compact.prefixed(i_mask, d)
                log = logring.append_rep(state.log, i_live, i_tbl,
                                         jnp.zeros_like(i_bal),
                                         jnp.zeros_like(i_bal, U32),
                                         i_acc.astype(U32),
                                         jnp.broadcast_to(t, i_mask.shape),
                                         log_value(i_mask, i_bal))
        # CommitBck x2 + CommitLog at the backups: forward applied installs
        with waves.scope("dense_sharded_sb", "replicate"):
            bck = state.bck_bal
            repl_groups = []
            fwd_lives = []
            for off in (1, 2):
                with waves.part("dense_sharded_sb", "sb_repl_hop"):
                    perm = [(i, (i + off) % d) for i in range(d)]
                    pp = functools.partial(jax.lax.ppermute,
                                           axis_name=AXIS, perm=perm)
                    fwd_mask = pp(i_mask)
                    if cnt is not None:
                        # replication pushes, counted where they are
                        # APPLIED
                        hop = (mon.CTR_REPL_PUSH_HOP1 if off == 1
                               else mon.CTR_REPL_PUSH_HOP2)
                        cnt = mon.bump(cnt, {hop: fwd_mask.sum(dtype=I32)})
                    if ring is not None:
                        # the forwarded txn id makes the backup-side event
                        # joinable: same id, shard = the APPLYING device
                        repl_groups.append(txe.ev(
                            fwd_mask, pp(i_txn), txe.EV_REPL,
                            waves.full_name("dense_sharded_sb",
                                            "replicate"),
                            shard=dev, aux=off, step=t.astype(U32)))
                    fwd = (pp(i_row), pp(i_bal), pp(i_tbl), pp(i_acc))
                    src_dev = (dev - off) % d
                log, bck, fwd_live = mk_entry(fwd_mask, *fwd, log, bck,
                                              off - 1, src_dev)
                fwd_lives.append(fwd_live)

        with waves.part("dense_sharded_sb", "sbx_frame"):
            state = state.replace(bal=bal_new, bck_bal=bck, x_step=x_step,
                                  s_step=s_step, step=t + 1, log=log,
                                  hot_bal=hot_bal, hot_x=hot_x,
                                  hot_s=hot_s)

        if cnt is not None and use_hotset:
            # partition accounting: 3 hot-partitioned gathers per step
            # (x/s stamps + balances), each serving (midx >= 0) lanes
            # from the mirror
            with waves.part("dense_sharded_sb", "monitor"):
                n_g = 3
                hits = (midx >= 0).sum(dtype=I32)
                cnt = mon.bump(cnt, {
                    mon.CTR_HOT_HITS: n_g * hits,
                    mon.CTR_HOT_COLD_ROWS: n_g * (d * cap) - n_g * hits,
                    mon.CTR_HOT_REFRESH_BYTES: 0,
                })
        if cnt is not None:
            # txn outcomes + overflow at the SOURCE (c1 completes here);
            # lock arbitration + installs at the OWNER (they ran here) —
            # either way each event is counted on exactly one device, so
            # the device-axis sum reconciles with the psummed stats
            with waves.part("dense_sharded_sb", "monitor"):
                req = r_op != 0
                grant = grant_x | grant_s
                rej = req & ~grant
                held = held_x | held_s
                # how much of the new cohort is distributed, at its
                # SOURCE: transactions whose lock set names rows of more
                # than one owner, lock requests whose owner is another
                # device (a drain generates nothing and counts nothing)
                act2 = active.reshape(w, L)
                own2 = dest.reshape(w, L)
                xshard = (act2 & (own2 != own2[:, :1])).any(axis=1)
                cnt = mon.bump(cnt, {
                    mon.CTR_STEPS: 1,
                    mon.CTR_TXN_ATTEMPTED: c1.attempted,
                    mon.CTR_TXN_COMMITTED: c1.committed,
                    mon.CTR_AB_LOCK: c1.ab_lock,
                    mon.CTR_AB_LOGIC: c1.ab_logic,
                    mon.CTR_MAGIC_BAD: c1.magic_bad,
                    mon.CTR_ROUTE_OVERFLOW: c1.overflow,
                    mon.CTR_LOCK_REQUESTS: req.sum(dtype=I32),
                    mon.CTR_LOCK_GRANTED: grant.sum(dtype=I32),
                    mon.CTR_LOCK_REJECTED: rej.sum(dtype=I32),
                    mon.CTR_LOCK_REJECT_HELD: (rej & held).sum(dtype=I32),
                    mon.CTR_LOCK_REJECT_ARB: (rej & ~held).sum(dtype=I32),
                    mon.CTR_INSTALL_WRITES: i_mask.sum(dtype=I32),
                    mon.CTR_LOG_APPENDS: i_mask.sum(dtype=I32),
                    # the trips of the three appends' chunk loops, where
                    # they run: the owner's, the two forwarded ones'
                    mon.CTR_INSTALL_CHUNKS: i_live.trips,
                    mon.CTR_BCK_CHUNKS: (fwd_lives[0].trips
                                         + fwd_lives[1].trips),
                    mon.CTR_DISPATCH_XLA: 1,
                    mon.CTR_XSHARD_TXNS: xshard.sum(dtype=I32),
                    mon.CTR_REMOTE_LOCK_LANES:
                        (active & (dest != dev)).sum(dtype=I32),
                })
                cnt = mon.gauge_max(cnt,
                                    {mon.CTR_RING_HWM: log.head.max()})

        if ring is not None:
            # dinttrace: each event lands on exactly ONE device — ROUTE/
            # VOTE/OUTCOME at the source (this cohort classifies here this
            # step), LOCK/INSTALL at the owner, REPL at the applying
            # backup — mirroring the counter attribution above, so the
            # device-axis event sum reconciles with the summed ledger.
            with waves.scope("dense_sharded_sb", "trace"):
                req = r_op != 0
                grant_l = grant_x | grant_s
                held_l = held_x | held_s
                lock_aux = (jnp.where(grant_l, txe.LOCK_GRANTED, 0)
                            | jnp.where(held_l, txe.LOCK_HELD, 0))
                ab_lock_m = lock_rejected & (l_op[:, 0] != 0)
                out_mask = committed | ab_lock_m | logic_abort
                cause = jnp.where(
                    ab_lock_m, txe.CAUSE_LOCK,
                    jnp.where(logic_abort, txe.CAUSE_LOGIC,
                              txe.CAUSE_COMMIT))
                groups = (
                    txe.ev(valid, jnp.repeat(txn_new, L), txe.EV_ROUTE,
                           waves.full_name("dense_sharded_sb", "route"),
                           shard=dev, aux=dest, step=tu),
                    txe.ev(req, r_txn, txe.EV_LOCK,
                           waves.full_name("dense_sharded_sb",
                                           "arbitrate"),
                           shard=dev, aux=lock_aux, step=tu),
                    txe.ev(l_op[:, 0] != 0, txn_new, txe.EV_VOTE,
                           waves.full_name("dense_sharded_sb", "reply"),
                           shard=dev, aux=commit, step=tu),
                    txe.ev(i_mask, i_txn, txe.EV_INSTALL,
                           waves.full_name("dense_sharded_sb",
                                           "install_route"),
                           shard=dev, step=tu),
                ) + tuple(repl_groups) + (
                    txe.ev(out_mask, txn_new, txe.EV_OUTCOME,
                           waves.full_name("dense_sharded_sb", "reply"),
                           shard=dev, aux=cause, step=tu),
                )
                ring, cnt = txe.emit(ring, tcfg, groups, cnt)

        with waves.part("dense_sharded_sb", "sbx_frame"):
            new_ctx = jax.tree.map(lambda x: pcast_varying(x, AXIS),
                                   new_ctx)
        with waves.part("dense_sharded_sb", "stats"):
            stats = jax.lax.psum(_stats_of(c1), AXIS)
        return state, new_ctx, stats, cnt, ring

    def scan_fn(carry, key, gen_new=True):
        state, c1 = carry[:2]
        ring = carry[2] if trace_on else None
        cnt = carry[-1] if monitor else None
        state, new_ctx, stats, cnt, ring = local_step(state, c1, key, cnt,
                                                      ring, gen_new)
        out = ((state, new_ctx) + ((ring,) if trace_on else ())
               + ((cnt,) if monitor else ()))
        return out, stats

    def sq(tree):
        return jax.tree.map(lambda x: x[0], tree)

    def unsq(tree):
        return jax.tree.map(lambda x: x[None], tree)

    def _reset_ring(carry):
        if trace_on:    # each drained window is self-contained
            carry = carry[:2] + (txe.reset(carry[2]),) + carry[3:]
        return carry

    def block_local(*args):
        key = args[-1]
        with waves.part("dense_sharded_sb", "block_pre"):
            keys = jax.random.split(key, cohorts_per_block)
        with waves.part("dense_sharded_sb", "sbx_carry"):
            carry = tuple(sq(a) for a in args[:-1])
        with waves.part("dense_sharded_sb", "block_pre"):
            carry = _reset_ring(carry)
        carry, stats = jax.lax.scan(scan_fn, carry, keys)
        with waves.part("dense_sharded_sb", "sbx_carry"):
            return tuple(unsq(x) for x in carry) + (stats,)

    def drain_local(*args):
        key = args[-1]
        with waves.part("dense_sharded_sb", "sbx_carry"):
            carry = tuple(sq(a) for a in args[:-1])
        with waves.part("dense_sharded_sb", "block_pre"):
            carry = _reset_ring(carry)
        carry, s1 = scan_fn(carry, key, gen_new=False)
        with waves.part("dense_sharded_sb", "sbx_carry"):
            out = (unsq(carry[0]),)
            if trace_on:
                out = out + (unsq(carry[2]),)
            if monitor:
                out = out + (unsq(carry[-1]),)
            return out + (jnp.stack([s1]),)

    n_carry = 2 + int(trace_on) + int(monitor)
    spec = (P(AXIS),) * n_carry + (P(),)
    block = jax.shard_map(block_local, mesh=mesh, in_specs=spec,
                          out_specs=(P(AXIS),) * n_carry + (P(),))
    drain_m = jax.shard_map(
        drain_local, mesh=mesh, in_specs=spec,
        out_specs=(P(AXIS),) * (n_carry - 1) + (P(),))
    donate = tuple(range(n_carry))
    jit_block = jax.jit(block, donate_argnums=donate)
    jit_drain = jax.jit(drain_m, donate_argnums=donate)

    def stack_leaf(one):
        shard = NamedSharding(mesh, P(AXIS))
        return jax.tree.map(
            lambda x: jax.device_put(
                jnp.broadcast_to(x[None], (d,) + x.shape), shard), one)

    def run(carry, key):
        out = jit_block(*carry, key)
        return out[:-1], out[-1]

    def init(state):
        if use_hotset and state.hot_loc == 0:
            state = attach_hotset_sb(mesh, state, hot_loc)
        base = (state, stack_leaf(_empty_sb_ctx(w)))
        return (base
                + ((stack_leaf(txe.create_ring(tcfg.cap)),)
                   if trace_on else ())
                + ((stack_leaf(mon.create()),) if monitor else ()))

    init.trace_cfg = tcfg

    def drain(carry):
        out = jit_drain(*carry, jax.random.PRNGKey(0))
        i = 1
        ring = out[i] if trace_on else None
        i += int(trace_on)
        cnt = out[i] if monitor else None
        return ((out[0], out[-1]) + ((ring,) if trace_on else ())
                + ((cnt,) if monitor else ()))

    return run, init, drain
