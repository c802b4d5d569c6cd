"""Cross-shard SmallBank 2PC over the 2-D (dcn x ici) multi-host mesh.

parallel/dense_sharded_sb.py reproduces DINT's distributed SmallBank 2PC
(lock/read fan-out, owner arbitration, install + CommitBck x2/CommitLog
x3) over ONE flat ICI axis — a single host. parallel/multihost.py has
the 2-D (host, chip) mesh but only runs device-local TATP on it. This
module is the junction: the SAME cross-shard transaction step, with the
transport restructured for a mesh whose major axis is the data-center
network (ROADMAP open item "true cross-shard distributed transactions,
then take them off one host"; FaSST OSDI'16 design space — remote bytes
are the budget, so route so only truly-remote lanes pay them):

  * **Hierarchical routing.** A routed bucket array [D*cap] (D = H*C
    global shards) reshaped to [H, C, cap] is exchanged in two stages:
    an ICI `all_to_all` inside each host (split/concat the CHIP dim),
    then ONE host-aggregated DCN `all_to_all` (split/concat the HOST
    dim). Host-local lanes never leave the ICI stage — `all_to_all`
    keeps the self shard local, so the DCN stage moves (H-1)/H of the
    operand instead of scheduling the full (D-1)/D exchange on the slow
    axis. The composition is a pure permutation: on device (h, c) the
    received flat index hs*C*cap + cs*cap + p equals the 1-D runner's
    s'*cap + p for source shard s' = hs*C + cs — bit-identical owner
    arbitration by construction (pinned in tests/test_multihost_sb.py).
    ``hierarchical=False`` lowers the SAME step with flat tuple-axis
    ``all_to_all(("dcn", "ici"))`` collectives: the A/B twin dintcost's
    hier-dcn-dominance gate compares against (analysis/cost.py prices a
    dcn-bearing collective's link bytes on the slow axis).
  * **Host fault domains.** The CommitBck x2 / CommitLog x3 replicate
    fan-out moves to ``ppermute(axis="dcn")`` at the same ICI
    coordinate — the 3 replicas of every row live on 3 DIFFERENT HOSTS,
    the reference's machine-failure guarantee and the same placement as
    multihost.py. (This is the one deliberate divergence from the 1-D
    runner: stats and primary state are bit-identical, backup/log
    PLACEMENT is not — replicas sit at (h+1, c)/(h+2, c) instead of
    global shards s+1/s+2.)
  * **Hierarchical reductions.** The commit/abort vote stats psum runs
    ici-then-dcn (integer adds — associative, so bit-identical to the
    flat psum), and the monitor plane gains per-axis route counters
    (route_ici_lanes / route_dcn_lanes) so the host-locality of the
    traffic is observable, not just priced.

Requires n_hosts >= 3 (the +2 dcn hop would alias the source on a
2-host mesh and double-log — same rule as multihost.py). The hotset
lever of the 1-D runner is orthogonal to the transport and stays on the
flat-axis path (PERF.md round 14).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engines.smallbank_pipeline import (L, TS_AMT_MAX, VW,
                                          compute_phase, gen_cohort,
                                          _lock_slots)
from ..engines.types import Op
from ..engines._memo import memoize_builder
from ..monitor import counters as mon
from ..monitor import txnevents as txe
from ..monitor import waves
from ..tables import log as logring
from .dense_sharded_sb import (N_BCK, SBCtx, SBShard, _empty_sb_ctx,
                               _positions, _route, _stats_of,
                               m1_local, n_acct_local)
from .multihost import DCN_AXIS, ICI_AXIS, make_mesh_2d   # noqa: F401
from .sharded import pcast_varying

I32 = jnp.int32
U32 = jnp.uint32

BIG = jnp.int32(1 << 30)


def create_multihost_sb(mesh: Mesh, n_accounts: int,
                        init_balance: int = 1000, log_lanes: int = 16,
                        log_capacity: int = 1 << 16) -> SBShard:
    """Stacked per-device state [H, C, ...]: device (h, c) is primary for
    global shard h*C + c of the round-robin account partition (the same
    partition as create_sharded_sb at D = H*C)."""
    n_hosts, n_ici = mesh.devices.shape
    if n_hosts < 3:
        raise ValueError("multihost replication needs >= 3 hosts "
                         "(reference topology: 3 server machines; with 2 "
                         "the +2 dcn hop aliases the source)")
    d = n_hosts * n_ici
    m1 = m1_local(n_accounts, d)
    bal = jnp.full((m1,), np.uint32(init_balance), U32).at[-1].set(0)
    one = SBShard(
        bal=bal,
        bck_bal=jnp.concatenate([bal, bal]),
        x_step=jnp.zeros((m1,), U32),
        s_step=jnp.zeros((m1,), U32),
        step=jnp.asarray(2, U32),
        log=logring.create_rep(log_lanes, log_capacity, VW, replicas=1))
    shard = NamedSharding(mesh, P(DCN_AXIS, ICI_AXIS))
    return jax.tree.map(
        lambda x: jax.device_put(
            jnp.broadcast_to(x[None, None], (n_hosts, n_ici) + x.shape),
            shard), one)


def total_balance_global(state: SBShard):
    """Host-side: global balance sum over all primaries (i32 wraparound,
    matching STAT_BAL_DELTA accounting; [H, C, m1] leaves)."""
    bal = np.asarray(state.bal)
    return int(bal.reshape(-1, bal.shape[-1])[:, :-1]
               .astype(np.uint32).view(np.int32).sum(dtype=np.int32))


@memoize_builder
def build_multihost_sb_runner(mesh: Mesh, n_accounts: int, w: int = 2048,
                              cohorts_per_block: int = 8, hot_frac=None,
                              hot_prob=None, mix=None,
                              hierarchical: bool = False,
                              monitor: bool = False, trace=None,
                              trace_rate=None, trace_cap=None,
                              serve: bool = False, overlap: bool = False):
    """jit(shard_map(scan(step))) over the 2-D mesh. Contract mirrors
    build_sharded_sb_runner: (run, init, drain); stats psummed ici then
    dcn. ``hierarchical`` picks the two-stage (ici, dcn) exchange or the
    flat tuple-axis all_to_all — outputs are bit-identical either way,
    only the transport differs. The default follows PERF.md round 14's
    pre-registered rule: hierarchical derives strictly fewer DCN-axis
    bytes at every calibrated geometry (enforced by hier-dcn-dominance)
    but costs ~3.4% on the virtual mesh where both axes are the same
    fabric, so it stays OPT-IN until a dcn-bearing hardware A/B
    (tools/hw_multihost.sh) lands.

    ``trace`` / ``trace_rate`` / ``trace_cap``: the dinttrace flight
    recorder, dsb convention (per-device TxnRing carry leaf before the
    counters leaf; the txn id rides the lock/install exchanges and the
    dcn ppermute fan-out, so one transaction's ROUTE -> owner LOCK ->
    VOTE -> INSTALL -> hop-1/hop-2 REPL events join across hosts). ROUTE
    events additionally carry the txnevents.ROUTE_DCN aux bit when the
    owner lives on another host — the hop that pays DCN bytes is visible
    per transaction, not just in the route_*_lanes totals. Off = routed
    fields, jaxpr, and outputs all bit-identical.

    ``serve``: the dintserve variable-occupancy cohort form (round 17's
    dense-engine contract, lifted to the mesh). ``run(carry, key, occ,
    shed)`` takes per-device occupancy/shed-mirror arrays shaped
    [n_hosts, n_ici, cohorts_per_block] i32; lock slots past each
    device's admitted occupancy are zeroed AFTER full-width generation,
    so occ == w replays the closed loop bit-identically and the serve
    counter trio reconciles per device (occupancy + padded == w x
    serving steps, summed over the mesh).

    ``overlap``: double-buffered cohorts (requires ``serve``; refuses
    ``trace`` — txn ids are stamped with the generation step). Each step
    PREFETCHES cohort i+1's routed lock/read buckets — generation plus
    the hierarchical ICI-then-DCN exchange under the ``route_prefetch``
    wave — and carries them (p_key, p_occ, r_op, r_row) to the next
    step, so XLA can start cohort i+1's host-aggregated DCN all_to_all
    while cohort i's arbitrate/reply waves still run on data already on
    device. Cohort i's source-side locals (lock slots, amounts, reply
    back-map) are REGENERATED from the carried key instead of carried —
    generation is pure in (key, occ), so the replay is free of comm and
    the extra in-flight state is just the 2 routed bucket fields
    (priced by dintcost's overlap-footprint expectation). Pinned
    bit-identical to the unoverlapped serve route: the init step starts
    one earlier (a bootstrap step arbitrates an empty prefetch buffer)
    and the drain runs two flush steps, so cohort j is arbitrated at
    step 2+j and installed at 3+j in BOTH modes — the entire final
    state (primaries, stamps, backups, log rings) matches exactly; only
    the per-block stats ALIGNMENT shifts (compare run+drain totals)."""
    n_hosts, n_ici = mesh.devices.shape
    if n_hosts < 3:
        raise ValueError("multihost replication needs >= 3 hosts "
                         "(reference topology: 3 server machines; with 2 "
                         "the +2 dcn hop aliases the source)")
    d = n_hosts * n_ici
    n_loc = n_acct_local(n_accounts, d)
    m1 = m1_local(n_accounts, d)
    sent = m1 - 1
    oob = m1
    cap = 2 * ((w * L + d - 1) // d)
    if overlap and not serve:
        raise ValueError("overlap=True requires serve=True: the double-"
                         "buffered route is defined over admitted "
                         "serving cohorts (occ rides the prefetch carry)")
    if overlap and txe.trace_enabled(trace):
        raise ValueError("overlap=True is incompatible with trace: "
                         "dinttrace txn ids are stamped with the "
                         "generation step, which the double buffer "
                         "shifts by one")
    kw_gen = {}
    if hot_frac is not None:
        kw_gen["hot_frac"] = hot_frac
    if hot_prob is not None:
        kw_gen["hot_prob"] = hot_prob
    trace_on = txe.trace_enabled(trace)
    tcfg = None
    if trace_on:
        # per-device candidates/step: same census as the 1-D runner —
        # ROUTE [wL] + LOCK [d*cap] + VOTE [w] + INSTALL [d*cap] +
        # REPL x2 [2*d*cap] + OUTCOME [w]
        n_step = w * L + 4 * d * cap + 2 * w
        rcap = int(trace_cap) if trace_cap else n_step * cohorts_per_block
        tcfg = txe.TraceCfg(rate=txe.trace_rate(trace_rate), cap=rcap,
                            wave=waves.full_name("multihost_sb", "trace"))

    def _exchange(x):
        """[D*cap] bucket exchange. Hierarchical: ICI a2a inside each
        host, then ONE dcn a2a of the host-aggregated buckets (host-local
        lanes stay on ICI). Flat: one tuple-axis a2a, dcn-major shard
        order — both are the 1-D runner's permutation exactly."""
        if hierarchical:
            x3 = x.reshape(n_hosts, n_ici, cap)
            x3 = jax.lax.all_to_all(x3, ICI_AXIS, 1, 1, tiled=False)
            x3 = jax.lax.all_to_all(x3, DCN_AXIS, 0, 0, tiled=False)
            return x3.reshape(d * cap)
        return jax.lax.all_to_all(x.reshape(d, cap),
                                  (DCN_AXIS, ICI_AXIS), 0, 0,
                                  tiled=False).reshape(d * cap)

    def _src_cohort(key, occ_i, dev, gen_new):
        """Source-side cohort materialization, pure in (key, occ_i, dev):
        full-width generation from the cohort key, then (serve) zero the
        lock slots of lanes past the admitted occupancy — so occ == w is
        value-identical to the closed loop, and the overlap path can
        REPLAY this from a carried (key, occ) to recover the in-flight
        cohort's locals without carrying them."""
        kgen, kamt = jax.random.split(jax.random.fold_in(key, dev))
        if gen_new:
            with waves.scope("multihost_sb", "gen"):
                ttype, a1, a2 = gen_cohort(kgen, w, n_accounts, mix=mix,
                                           **kw_gen)
                l_op, l_tb, l_ac = _lock_slots(ttype, a1, a2)
            if occ_i is not None:
                with waves.scope("multihost_sb", "serve"):
                    lane_ok = jnp.arange(w, dtype=I32) < occ_i
                    l_op = jnp.where(lane_ok[:, None], l_op, 0)
        else:
            ttype = jnp.zeros((w,), I32)
            l_op = jnp.zeros((w, L), I32)
            l_tb = jnp.zeros((w, L), I32)
            l_ac = jnp.zeros((w, L), I32)
        ts_amt = jax.random.randint(kamt, (w,), -TS_AMT_MAX,
                                    TS_AMT_MAX + 1, dtype=I32)
        return ttype, l_op, l_tb, l_ac, ts_amt

    def _route_src(l_op, l_tb, l_ac):
        """Destination shard / bucket position / validity for every lock
        slot — the source half of the route; no collectives."""
        active = (l_op != 0).reshape(-1)
        dest = (l_ac.reshape(-1) % d).astype(I32)
        row_loc = (l_tb.reshape(-1) * n_loc
                   + l_ac.reshape(-1) // d).astype(I32)
        pos = _positions(dest, active, d)
        valid = active & (pos < cap)
        return active, dest, row_loc, pos, valid

    def _empty_pf():
        """Prefetch carry (p_key, p_occ, r_op, r_row): the key + admitted
        occupancy of the in-flight cohort plus its already-exchanged
        routed buckets. Empty = the bootstrap/flush no-op cohort."""
        return (jnp.zeros((2,), U32), jnp.asarray(0, I32),
                jnp.zeros((d * cap,), I32), jnp.zeros((d * cap,), I32))

    def local_step(state: SBShard, c1: SBCtx, pf, key, occ_i, shed_i,
                   cnt, ring, gen_new=True):
        h = jax.lax.axis_index(DCN_AXIS)
        c = jax.lax.axis_index(ICI_AXIS)
        dev = h * n_ici + c             # global shard id, dcn-major
        t = state.step

        # ---- wave 1: generate + route lock/read requests to owners ----
        p_valid = r_txn = None
        if overlap:
            # prefetch cohort i+1: generate from THIS step's key and push
            # the routed buckets through the exchange NOW — the host-
            # aggregated DCN all_to_all runs under cohort i's owner waves
            if gen_new:
                _, n_op, n_tb, n_ac, _ = _src_cohort(key, occ_i, dev,
                                                     True)
                with waves.scope("multihost_sb", "route_prefetch"):
                    _, n_dest, n_rowloc, n_pos, p_valid = _route_src(
                        n_op, n_tb, n_ac)
                    pr = [_exchange(x) for x in _route(
                        n_dest, n_pos, p_valid, cap, d,
                        [n_op.reshape(-1), n_rowloc])]
                pf_next = (key, jnp.asarray(occ_i, I32), pr[0], pr[1])
            else:
                pf_next = _empty_pf()
            # regenerate the in-flight cohort's source-side locals from
            # its carried (key, occ) — pure replay, no collective
            ttype, l_op, l_tb, l_ac, ts_amt = _src_cohort(
                pf[0], pf[1], dev, True)
            active, dest, row_loc, pos, valid = _route_src(l_op, l_tb,
                                                           l_ac)
            r_op, r_row = pf[2], pf[3]
            attempted = pf[1]
        else:
            pf_next = None
            ttype, l_op, l_tb, l_ac, ts_amt = _src_cohort(key, occ_i,
                                                          dev, gen_new)

            if ring is not None:
                # dinttrace ids: one per generated txn, identical on
                # every device/host that touches it (routed copies below
                # carry it)
                tu = jnp.asarray(t).astype(U32)
                du = dev.astype(U32)
                lane_w = jnp.arange(w, dtype=U32)
                txn_new = (tu * U32(d) + du) * U32(w) + lane_w
                txn_c1 = ((tu - U32(1)) * U32(d) + du) * U32(w) + lane_w

            with waves.scope("multihost_sb", "route"):
                active, dest, row_loc, pos, valid = _route_src(
                    l_op, l_tb, l_ac)
                fields = [l_op.reshape(-1), row_loc]
                if ring is not None:
                    fields.append(jnp.repeat(txn_new, L))
                routed = [_exchange(x)
                          for x in _route(dest, pos, valid, cap, d,
                                          fields)]
                r_op, r_row = routed[:2]
                r_txn = routed[2] if ring is not None else None
            if serve:
                attempted = (jnp.asarray(occ_i, I32) if gen_new
                             else jnp.asarray(0, I32))
            else:
                attempted = jnp.asarray(w if gen_new else 0, I32)

        # ---- owner side: no-wait S/X arbitration + fused read ---------
        lanes = jnp.arange(d * cap, dtype=I32)
        is_x = r_op == Op.ACQ_X_READ
        is_s = r_op == Op.ACQ_S_READ
        rows = jnp.where(r_op != 0, r_row, sent)
        with waves.scope("multihost_sb", "arbitrate"):
            first_x = jnp.full((m1,), BIG, I32).at[
                jnp.where(is_x, rows, oob)].min(lanes, mode="drop")
            first_s = jnp.full((m1,), BIG, I32).at[
                jnp.where(is_s, rows, oob)].min(lanes, mode="drop")
            held_x = state.x_step[rows] == t - 1
            held_s = state.s_step[rows] == t - 1
            slot_free = ~held_x & ~held_s
            x_wins = (first_x[rows] < first_s[rows]) & slot_free
            grant_x = is_x & x_wins & (first_x[rows] == lanes)
            grant_s = is_s & ~held_x & ~x_wins
            s_writer = grant_s & (first_s[rows] == lanes)
            x_step = state.x_step.at[jnp.where(grant_x, rows, oob)].set(
                t, mode="drop", unique_indices=True)
            s_step = state.s_step.at[
                jnp.where(s_writer, rows, oob)].set(
                t, mode="drop", unique_indices=True)
            raw_bal = state.bal[rows]
            g_bal = jnp.where(grant_x | grant_s, raw_bal.astype(I32), 0)

        # ---- replies back to sources + classify -----------------------
        with waves.scope("multihost_sb", "reply"):
            rep_g = _exchange(grant_x | grant_s)
            rep_b = _exchange(g_bal)
            back = jnp.where(valid, dest * cap + pos, 0)
            granted = (jnp.where(valid, rep_g[back], False)
                       .reshape(w, L))
            bal = jnp.where(granted, rep_b[back].reshape(w, L), 0)
            lock_rejected = ((l_op != 0) & ~granted).any(axis=1)
            alive = ~lock_rejected & (l_op[:, 0] != 0)

            nw, do, logic_abort, commit, committed = compute_phase(
                ttype, bal, alive, ts_amt)
            do_write = do & commit[:, None] & (l_op != 0)
            bal_delta = jnp.sum(jnp.where(do_write, nw - bal, 0),
                                dtype=I32)

        new_ctx = SBCtx(
            acc=l_ac, tbl=l_tb, do_write=do_write, nw=nw,
            attempted=attempted,
            committed=committed.sum(dtype=I32),
            ab_lock=(lock_rejected & (l_op[:, 0] != 0)).sum(dtype=I32),
            ab_logic=logic_abort.sum(dtype=I32),
            magic_bad=jnp.asarray(0, I32),
            bal_delta=bal_delta,
            overflow=(active & ~valid).sum(dtype=I32))

        # ---- wave 2 of c1: route installs to owners -------------------
        with waves.scope("multihost_sb", "install_route"):
            wmask = c1.do_write.reshape(-1)
            wdest = (c1.acc.reshape(-1) % d).astype(I32)
            wrow = (c1.tbl.reshape(-1) * n_loc
                    + c1.acc.reshape(-1) // d).astype(I32)
            wpos = _positions(wdest, wmask, d)
            wvalid = wmask & (wpos < cap)   # no overflow: writes <= locks
            ifields = [wmask.astype(I32), wrow, c1.nw.reshape(-1),
                       c1.tbl.reshape(-1), c1.acc.reshape(-1)]
            if ring is not None:
                ifields.append(jnp.repeat(txn_c1, L))
            inst = [_exchange(x)
                    for x in _route(wdest, wpos, wvalid, cap, d, ifields)]
            i_m, i_row, i_bal, i_tbl, i_acc = inst[:5]
            i_txn = inst[5] if ring is not None else None
            i_mask = i_m != 0

            irows = jnp.where(i_mask, i_row, oob)
            bal_new = state.bal.at[irows].set(i_bal.astype(U32),
                                              mode="drop",
                                              unique_indices=True)
            newval = jnp.zeros((d * cap, VW), U32).at[:, 0].set(
                i_bal.astype(U32))
            log = logring.append_rep(state.log, i_mask, i_tbl,
                                     jnp.zeros_like(i_bal),
                                     jnp.zeros_like(i_bal, U32),
                                     i_acc.astype(U32),
                                     jnp.broadcast_to(t, i_mask.shape),
                                     newval)

        def mk_entry(mask, row, balv, tblv, accv, ring, bck, slot,
                     src_dev):
            # forwarded entries tag key_hi = SOURCE shard + 1 (own entries
            # log 0 above), so recovery can verify a ring's streams
            # against acct % D geometry — same convention as the 1-D
            # runner; the source here is host h-off at the SAME chip
            rr = jnp.where(mask, slot * m1 + row, N_BCK * m1)
            bck = bck.at[rr].set(balv.astype(U32), mode="drop",
                                 unique_indices=True)
            nv = jnp.zeros((mask.shape[0], VW), U32)
            nv = nv.at[:, 0].set(balv.astype(U32))
            stepv = jnp.broadcast_to(t, mask.shape)
            src = jnp.broadcast_to(src_dev.astype(U32) + U32(1),
                                   mask.shape)
            ring = logring.append_rep(ring, mask, tblv,
                                      jnp.zeros_like(balv),
                                      src, accv.astype(U32), stepv, nv)
            return ring, bck

        # CommitBck x2 + CommitLog at the backups: forward applied
        # installs to hosts h+1, h+2 at the SAME chip coordinate — the 3
        # replicas of every row live on 3 different hosts
        with waves.scope("multihost_sb", "replicate"):
            bck = state.bck_bal
            repl_groups = []
            for off in (1, 2):
                perm = [(i, (i + off) % n_hosts) for i in range(n_hosts)]
                pp = functools.partial(jax.lax.ppermute,
                                       axis_name=DCN_AXIS, perm=perm)
                fwd_mask = pp(i_mask)
                if cnt is not None:
                    hop = (mon.CTR_REPL_PUSH_HOP1 if off == 1
                           else mon.CTR_REPL_PUSH_HOP2)
                    cnt = mon.bump(cnt, {hop: fwd_mask.sum(dtype=I32)})
                if ring is not None:
                    # the forwarded txn id makes the backup-side event
                    # joinable: same id, shard = the APPLYING device
                    repl_groups.append(txe.ev(
                        fwd_mask, pp(i_txn), txe.EV_REPL,
                        waves.full_name("multihost_sb", "replicate"),
                        shard=dev, aux=off, step=t.astype(U32)))
                src_dev = ((h - off) % n_hosts) * n_ici + c
                log, bck = mk_entry(fwd_mask, pp(i_row), pp(i_bal),
                                    pp(i_tbl), pp(i_acc), log, bck,
                                    off - 1, src_dev)

        state = state.replace(bal=bal_new, bck_bal=bck, x_step=x_step,
                              s_step=s_step, step=t + 1, log=log)

        if cnt is not None:
            # txn outcomes + overflow at the SOURCE, lock arbitration +
            # installs at the OWNER (dsb convention), PLUS the per-axis
            # route split counted at the source: a valid lane whose owner
            # host == h crosses only ICI, otherwise it pays the DCN hop.
            # Summed over devices: route_ici + route_dcn ==
            # lock_requests + install_writes.
            req = r_op != 0
            grant = grant_x | grant_s
            rej = req & ~grant
            held = held_x | held_s
            ici_lanes = ((valid & (dest // n_ici == h)).sum(dtype=I32)
                         + (wvalid & (wdest // n_ici == h))
                         .sum(dtype=I32))
            dcn_lanes = ((valid & (dest // n_ici != h)).sum(dtype=I32)
                         + (wvalid & (wdest // n_ici != h))
                         .sum(dtype=I32))
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
                mon.CTR_ROUTE_ICI_LANES: ici_lanes,
                mon.CTR_ROUTE_DCN_LANES: dcn_lanes,
                mon.CTR_DISPATCH_XLA: 1,
            })
            if serve and gen_new:
                # admission accounting at the DISPATCH step (the cohort
                # the host just handed over), independent of arbitration
                # timing: occupancy + padded == w x serving steps and
                # shed mirrors the host tally in both overlap modes
                occ32 = jnp.asarray(occ_i, I32)
                cnt = mon.bump(cnt, {
                    mon.CTR_SERVE_OCC_LANES: occ32,
                    mon.CTR_SERVE_PAD_LANES: jnp.asarray(w, I32) - occ32,
                    mon.CTR_SERVE_SHED_LANES: jnp.asarray(shed_i, I32),
                })
            if overlap and gen_new:
                cnt = mon.bump(cnt, {mon.CTR_ROUTE_PREFETCH_LANES:
                                     p_valid.sum(dtype=I32)})
            cnt = mon.gauge_max(cnt, {mon.CTR_RING_HWM: log.head.max()})

        if ring is not None:
            # dinttrace (dsb attribution: source emits ROUTE/VOTE/OUTCOME,
            # owner emits LOCK/INSTALL, applying backup emits REPL); the
            # ROUTE aux carries dest | ROUTE_DCN when the owner lives on
            # another host — the per-txn twin of route_dcn_lanes
            with waves.scope("multihost_sb", "trace"):
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
                route_aux = dest | jnp.where(dest // n_ici != h,
                                             txe.ROUTE_DCN, 0)
                groups = (
                    txe.ev(valid, jnp.repeat(txn_new, L), txe.EV_ROUTE,
                           waves.full_name("multihost_sb", "route"),
                           shard=dev, aux=route_aux, step=tu),
                    txe.ev(req, r_txn, txe.EV_LOCK,
                           waves.full_name("multihost_sb", "arbitrate"),
                           shard=dev, aux=lock_aux, step=tu),
                    txe.ev(l_op[:, 0] != 0, txn_new, txe.EV_VOTE,
                           waves.full_name("multihost_sb", "reply"),
                           shard=dev, aux=commit, step=tu),
                    txe.ev(i_mask, i_txn, txe.EV_INSTALL,
                           waves.full_name("multihost_sb",
                                           "install_route"),
                           shard=dev, step=tu),
                ) + tuple(repl_groups) + (
                    txe.ev(out_mask, txn_new, txe.EV_OUTCOME,
                           waves.full_name("multihost_sb", "reply"),
                           shard=dev, aux=cause, step=tu),
                )
                ring, cnt = txe.emit(ring, tcfg, groups, cnt)

        # pf_next too: its key/occupancy arrive replicated (scan xs) but
        # ride a carry whose other leaves vary over the mesh
        new_ctx, pf_next = jax.tree.map(
            lambda x: pcast_varying(x, DCN_AXIS, ICI_AXIS),
            (new_ctx, pf_next))
        stats = jax.lax.psum(
            jax.lax.psum(_stats_of(c1), ICI_AXIS), DCN_AXIS)
        return state, new_ctx, pf_next, stats, cnt, ring

    def scan_fn(carry, xs, gen_new=True):
        state, c1 = carry[:2]
        pf = carry[2] if overlap else None
        ring = carry[2 + int(overlap)] if trace_on else None
        cnt = carry[-1] if monitor else None
        if serve:
            key, occ_i, shed_i = xs
        else:
            key, occ_i, shed_i = xs, None, None
        state, new_ctx, pf, stats, cnt, ring = local_step(
            state, c1, pf, key, occ_i, shed_i, cnt, ring, gen_new)
        out = ((state, new_ctx) + ((pf,) if overlap else ())
               + ((ring,) if trace_on else ())
               + ((cnt,) if monitor else ()))
        return out, stats

    def sq(tree):
        return jax.tree.map(lambda x: x[0, 0], tree)

    def unsq(tree):
        return jax.tree.map(lambda x: x[None, None], tree)

    def _reset_ring(carry):
        if trace_on:    # each drained window is self-contained
            i = 2 + int(overlap)
            carry = carry[:i] + (txe.reset(carry[i]),) + carry[i + 1:]
        return carry

    def block_local(*args):
        if serve:
            key, occ, shed = args[-3], args[-2], args[-1]
            carries = args[:-3]
            xs = (jax.random.split(key, cohorts_per_block),
                  sq(occ), sq(shed))
        else:
            key = args[-1]
            carries = args[:-1]
            xs = jax.random.split(key, cohorts_per_block)
        carry, stats = jax.lax.scan(
            scan_fn, _reset_ring(tuple(sq(a) for a in carries)), xs)
        return tuple(unsq(x) for x in carry) + (stats,)

    def drain_local(*args):
        key = args[-1]
        carry = _reset_ring(tuple(sq(a) for a in args[:-1]))

        def flush(carry):
            zero = jnp.asarray(0, I32)
            xs = (key, zero, zero) if serve else key
            return scan_fn(carry, xs, gen_new=False)

        carry, s1 = flush(carry)
        stats = [s1]
        if overlap:
            # two flush steps: arbitrate the last prefetched cohort,
            # then install it — the double buffer's extra pipeline stage
            carry, s2 = flush(carry)
            stats.append(s2)
        out = (unsq(carry[0]),)
        if trace_on:
            out = out + (unsq(carry[2 + int(overlap)]),)
        if monitor:
            out = out + (unsq(carry[-1]),)
        return out + (jnp.stack(stats),)

    grid = P(DCN_AXIS, ICI_AXIS)
    n_carry = 2 + int(overlap) + int(trace_on) + int(monitor)
    spec_run = ((grid,) * n_carry + (P(),)
                + ((grid, grid) if serve else ()))
    spec_drain = (grid,) * n_carry + (P(),)
    block = jax.shard_map(block_local, mesh=mesh, in_specs=spec_run,
                          out_specs=(grid,) * n_carry + (P(),))
    drain_m = jax.shard_map(
        drain_local, mesh=mesh, in_specs=spec_drain,
        out_specs=(grid,) * (1 + int(trace_on) + int(monitor)) + (P(),))
    donate = tuple(range(n_carry))
    jit_block = jax.jit(block, donate_argnums=donate)
    jit_drain = jax.jit(drain_m, donate_argnums=donate)

    def stack_leaf(one):
        shard = NamedSharding(mesh, grid)
        return jax.tree.map(
            lambda x: jax.device_put(
                jnp.broadcast_to(x[None, None],
                                 (n_hosts, n_ici) + x.shape), shard),
            one)

    def run(carry, key, occ=None, shed=None):
        if serve:
            out = jit_block(*carry, key, jnp.asarray(occ, I32),
                            jnp.asarray(shed, I32))
        else:
            out = jit_block(*carry, key)
        return out[:-1], out[-1]

    def init(state):
        if overlap:
            # start one step EARLY: the bootstrap step arbitrates the
            # empty prefetch buffer (a provable no-op), so cohort j is
            # arbitrated at step 2+j and installed at 3+j exactly as on
            # the unoverlapped route — the bit-identity anchor
            state = state.replace(step=state.step - 1)
        base = (state, stack_leaf(_empty_sb_ctx(w)))
        return (base
                + ((stack_leaf(_empty_pf()),) if overlap else ())
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
