"""Sort-free dense TATP engine: semantics vs the generic pipelined engine."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dint_tpu.clients import tatp_client as tc
from dint_tpu.engines import tatp, tatp_dense as td, tatp_pipeline as tp
from dint_tpu.monitor import counters as mon
from dint_tpu.monitor import txnevents as txe
from dint_tpu.monitor import waves
from dint_tpu.ops import compact
from dint_tpu.tables import log as logring

VW = 4


def _run(n_sub, w, blocks, cohorts_per_block=2, seed=0, mix=None):
    db = td.populate(np.random.default_rng(seed), n_sub, val_words=VW)
    run, init, drain = td.build_pipelined_runner(
        n_sub, w=w, val_words=VW, cohorts_per_block=cohorts_per_block,
        mix=mix)
    carry = init(db)
    key = jax.random.PRNGKey(seed)
    total = np.zeros(td.N_STATS, np.int64)
    for i in range(blocks):
        carry, stats = run(carry, jax.random.fold_in(key, i))
        total += np.asarray(stats, np.int64).sum(axis=0)
    db, tail = drain(carry)
    total += np.asarray(tail, np.int64).sum(axis=0)
    return db, total


def test_contention_fires_validate_aborts():
    # same forced US/IC-heavy mix over a tiny keyspace as the generic
    # pipelined engine's test: in-flight cohorts commit sf rows between a
    # younger cohort's read and its validate
    mix = np.array([0, 0, 0, 50, 0, 50, 0], np.float64) / 100.0
    db, total = _run(n_sub=32, w=256, blocks=4, mix=mix)
    attempted = int(total[td.STAT_ATTEMPTED])
    committed = int(total[td.STAT_COMMITTED])
    assert attempted == 4 * 2 * 256
    assert committed > 0
    assert int(total[td.STAT_MAGIC_BAD]) == 0
    assert int(total[td.STAT_AB_VALIDATE]) > 0
    assert int(total[td.STAT_AB_LOCK]) > 0
    outcomes = (committed + int(total[td.STAT_AB_LOCK])
                + int(total[td.STAT_AB_MISSING])
                + int(total[td.STAT_AB_VALIDATE]))
    assert outcomes == attempted


def test_low_contention_mostly_commits():
    db, total = _run(n_sub=20_000, w=64, blocks=3)
    attempted = int(total[td.STAT_ATTEMPTED])
    committed = int(total[td.STAT_COMMITTED])
    # abort rate ~= the analytic ab_missing floor (~25%, see
    # test_ab_missing_matches_population_analytics — TATP's read txns
    # fail on absent rows BY DESIGN) + ~0 contention
    assert 1 - committed / attempted < 0.30
    contention = int(total[td.STAT_AB_LOCK]) + int(total[td.STAT_AB_VALIDATE])
    assert contention / attempted < 0.01, total
    assert int(total[td.STAT_MAGIC_BAD]) == 0


def test_ab_missing_matches_population_analytics():
    """VERDICT #9: ab_missing dominates the abort mix — prove it is
    workload semantics, not a lookup bug, by pinning observed rates to the
    analytic expectations of the population rules + txn mix:

      P(ai/sf present)  p_sf = 0.625 + 0.375^4/4   (the >=1-per-sub fix)
      P(cf present)     p_cf = p_sf * 0.25
      GET_ACCESS   (35%) misses at 1 - p_sf          (ai row required)
      GET_NEW_DEST (10%) misses at 1 - p_cf          (sf AND cf required)
      UPDATE_SUB    (2%) misses at 1 - p_sf          (sub always present)
      INSERT_CF     (2%) misses at 1 - p_sf*0.75     (cf must NOT exist)
      DELETE_CF     (2%) misses at 1 - p_cf          (cf must exist)
      others        (49%) never miss

    Few blocks over a fresh populate so insert/delete drift of CF
    occupancy stays negligible."""
    n_sub, w, blocks = 50_000, 1024, 3
    _, total = _run(n_sub=n_sub, w=w, blocks=blocks, seed=11)
    attempted = int(total[td.STAT_ATTEMPTED])
    observed = int(total[td.STAT_AB_MISSING]) / attempted

    p_sf = 0.625 + 0.375 ** 4 / 4
    p_cf = p_sf * 0.25
    expected = (0.35 * (1 - p_sf)
                + 0.10 * (1 - p_cf)
                + 0.02 * (1 - p_sf)
                + 0.02 * (1 - p_sf * 0.75)
                + 0.02 * (1 - p_cf))
    # binomial sd at n=attempted is ~0.3%; allow drift + NURand skew
    assert abs(observed - expected) < 0.01, (observed, expected)


def test_drain_releases_locks_and_log_replicas_converge():
    db, _ = _run(n_sub=64, w=128, blocks=3, seed=3)
    assert not np.asarray(db.locked).any()
    # log x3 (the physically replicated artifact): slots bit-identical
    r0 = np.asarray(logring.replica_entries(db.log, 0))
    assert np.array_equal(r0, np.asarray(logring.replica_entries(db.log, 1)))
    assert np.array_equal(r0, np.asarray(logring.replica_entries(db.log, 2)))
    # sentinel row untouched
    assert not bool(np.asarray(db.exists)[-1])
    assert int(np.asarray(db.ver)[-1]) == 0


def test_delete_only_mix_empties_cf():
    # DELETE_CF-only mix over a tiny keyspace: every present CF row is
    # eventually deleted; deletes log is_del entries and bump versions
    mix = np.array([0, 0, 0, 0, 0, 0, 100], np.float64) / 100.0
    n_sub = 4
    db0 = td.populate(np.random.default_rng(0), n_sub, val_words=VW)
    cf0 = np.asarray(db0.exists)[10 * (n_sub + 1):-1]
    assert cf0.any()
    db, total = _run(n_sub=n_sub, w=128, blocks=6, mix=mix)
    cf1 = np.asarray(db.exists)[10 * (n_sub + 1):-1]
    assert not cf1.any()
    assert int(total[td.STAT_COMMITTED]) == int(cf0.sum())
    # committed deletes bumped their rows' versions past populate's 1
    vers = np.asarray(db.ver)[10 * (n_sub + 1):-1]
    assert (vers[cf0] >= 2).all()


def test_insert_mix_fills_cf_and_versions_are_monotonic():
    mix = np.array([0, 0, 0, 0, 0, 100, 0], np.float64) / 100.0
    n_sub = 4
    db0 = td.populate(np.random.default_rng(0), n_sub, val_words=VW)
    cf0 = np.asarray(db0.exists)[10 * (n_sub + 1):-1].sum()
    db, total = _run(n_sub=n_sub, w=128, blocks=6, mix=mix)
    cf1 = np.asarray(db.exists)[10 * (n_sub + 1):-1].sum()
    assert int(total[td.STAT_COMMITTED]) == cf1 - cf0
    assert int(total[td.STAT_MAGIC_BAD]) == 0


def test_rebase_stamps_preserves_lock_state():
    """rebase_stamps fires only after ~12k steps on hardware; pin its
    remap directly: live stamps (step-1 held, step-2 expiring) keep their
    held/free meaning and slot fields, older stamps zero."""
    n_sub = 8
    db = td.populate(np.random.default_rng(0), n_sub, val_words=VW)
    t = np.uint32(td.REBASE_AT + 7)
    arb = np.zeros(td.n_rows(n_sub) + 1, np.uint32)
    arb[3] = ((t - 1) << td.K_ARB) | 11       # held (stamped last step)
    arb[5] = ((t - 2) << td.K_ARB) | 22       # expiring this step
    arb[7] = ((t - 3) << td.K_ARB) | 33       # stale
    db = db.replace(arb=jax.numpy.asarray(arb),
                    step=jax.numpy.asarray(t, jax.numpy.uint32))
    held_before = np.asarray(db.locked)

    db2 = td.rebase_stamps(db)
    assert int(np.asarray(db2.step)) == 3
    arb2 = np.asarray(db2.arb)
    assert np.array_equal(np.asarray(db2.locked), held_before)
    assert arb2[3] == (2 << td.K_ARB) | 11    # held -> step 2, slot kept
    assert arb2[5] == (1 << td.K_ARB) | 22    # expiring -> step 1
    assert arb2[7] == 0                       # stale zeroed
    assert (arb2[np.arange(len(arb2)) % 2 == 0] == 0).all()

    # and the engine keeps running correctly from a rebased state: the
    # next steps' grants/stats still close
    run, init, drain = td.build_pipelined_runner(n_sub, w=16, val_words=VW,
                                                 cohorts_per_block=2)
    carry = init(db)
    carry, s = run(carry, jax.random.PRNGKey(0))
    tot = np.asarray(s, np.int64).sum(axis=0)
    _, tail = drain(carry)
    tot += np.asarray(tail, np.int64).sum(axis=0)
    outcomes = (tot[td.STAT_COMMITTED] + tot[td.STAT_AB_LOCK]
                + tot[td.STAT_AB_MISSING] + tot[td.STAT_AB_VALIDATE])
    assert outcomes == tot[td.STAT_ATTEMPTED]
    assert int(tot[td.STAT_MAGIC_BAD]) == 0


def test_populate_device_matches_population_rules():
    """On-device populate (the 7M-scale path) obeys the same population
    rules as the numpy path (client_ebpf_shard.cc:96-341): subscribers all
    present, ai/sf ~0.625 with >=1 per subscriber, CF ~25% of present sf
    slots, payload/magic/meta wiring identical."""
    n_sub = 500
    p1 = n_sub + 1
    db = td.populate_device(jax.random.PRNGKey(0), n_sub, val_words=VW)
    ex = np.asarray(db.exists)
    meta = np.asarray(db.meta)
    val = np.asarray(db.val).reshape(-1, VW)
    base = td._bases(p1)

    assert ex[base[0] + 1: base[0] + p1].all() and not ex[0]
    assert ex[base[1] + 1: base[1] + p1].all() and not ex[base[1]]
    assert not ex[-1]
    sf = ex[base[3]:base[3] + 4 * p1].reshape(p1, 4)
    assert not sf[0].any()
    assert sf[1:].any(axis=1).all()              # >=1 sf_type each
    assert 0.57 < sf[1:].mean() < 0.69           # p=0.625 (+ the >=1 fix)
    cf = ex[base[4]:-1].reshape(p1, 4, 3)
    assert not cf[~sf].any()                     # CF only under present sf
    assert 0.19 < cf[sf].mean() < 0.31           # p=0.25
    rows = np.nonzero(ex[:-1])[0]
    region = np.searchsorted(base, rows, side="right") - 1
    assert (val[rows, 0] == rows - base[region]).all()
    assert (val[rows, 1] == td.MAGIC).all()
    assert (meta[rows] >> 1 == 1).all()          # populate version 1
    absent = np.nonzero(~ex[:-1])[0]
    assert (val[absent] == 0).all() and (meta[absent] == 0).all()

    # and the engine runs clean on it
    run, init, drain = td.build_pipelined_runner(
        n_sub, w=64, val_words=VW, cohorts_per_block=2)
    carry = init(db)
    carry, stats = run(carry, jax.random.PRNGKey(1))
    total = np.asarray(stats, np.int64).sum(axis=0)
    _, tail = drain(carry)
    total += np.asarray(tail, np.int64).sum(axis=0)
    assert int(total[td.STAT_MAGIC_BAD]) == 0
    assert int(total[td.STAT_COMMITTED]) > 0


def test_matches_generic_pipelined_engine_at_low_contention():
    """Same seed -> same population + same cohorts; at low contention the
    dense engine must produce the exact same stats as the generic
    sort-based engine (engines/tatp_pipeline): exact CF locks only remove
    hash-conflation conflicts, so the seed must draw none. Seed 7 draws
    exactly one (the generic engine conflates two CF keys into one lock
    row and aborts a txn the dense engine correctly commits — seeds 0-3
    draw zero); the test ran broken on that seed since the seed drop."""
    n_sub, w, blocks, seed = 2000, 256, 2, 0

    db = td.populate(np.random.default_rng(seed), n_sub, val_words=VW)
    run_d, init_d, drain_d = td.build_pipelined_runner(
        n_sub, w=w, val_words=VW, cohorts_per_block=2)
    carry = init_d(db)

    shards, _ = tc.populate_shards(np.random.default_rng(seed), n_sub,
                                   val_words=VW, log_capacity=1 << 14)
    stacked = tp.stack_shards(shards)
    run_g, init_g, drain_g = tp.build_pipelined_runner(
        n_sub, w=w, val_words=VW, cohorts_per_block=2)
    carry_g = init_g(stacked)

    key = jax.random.PRNGKey(seed)
    tot_d = np.zeros(td.N_STATS, np.int64)
    tot_g = np.zeros(tp.N_STATS, np.int64)
    for i in range(blocks):
        carry, s_d = run_d(carry, jax.random.fold_in(key, i))
        carry_g, s_g = run_g(carry_g, jax.random.fold_in(key, i))
        tot_d += np.asarray(s_d, np.int64).sum(axis=0)
        tot_g += np.asarray(s_g, np.int64).sum(axis=0)
    db, tail_d = drain_d(carry)
    stacked, tail_g = drain_g(carry_g)
    tot_d += np.asarray(tail_d, np.int64).sum(axis=0)
    tot_g += np.asarray(tail_g, np.int64).sum(axis=0)

    assert tot_d.tolist() == tot_g.tolist(), (tot_d, tot_g)

    # table end-states agree too: dense flat rows vs the generic engine's
    # per-table arrays (dense tables only; CF layouts differ by design)
    p1 = n_sub + 1
    base = td._bases(p1)
    ver_d = np.asarray(db.ver)
    for tid, t in ((tatp.SUBSCRIBER, stacked.sub), (tatp.SEC_SUBSCRIBER,
                   stacked.sec), (tatp.ACCESS_INFO, stacked.ai),
                   (tatp.SPECIAL_FACILITY, stacked.sf)):
        n = np.asarray(t.ver).shape[1]
        got = ver_d[base[tid]:base[tid] + n]
        want = np.asarray(t.ver)[0]
        assert np.array_equal(got, want), tid


# ---------------------------------------------------- write-set compaction
# (ops/compact.py, PR 30): the install and the log append issue the live
# write slots only, C lanes a chunk. The reference below is the parent's
# form: one full-width masked scatter each, all 2w slots at once.

UPDATES_ONLY = np.array([0, 0, 0, 50, 50, 0, 0], np.float64) / 100.0


@functools.lru_cache(maxsize=None)
def _forced_step(w, n_sub):
    step = functools.partial(td.pipe_step, w=w, n_sub=n_sub, val_words=VW,
                             gen_new=False, emit_installs=True,
                             counters=mon.create())
    return jax.jit(step)


def _forced_ctx(rng, w, n_sub, n_live):
    """A cohort t-2 with exactly n_live live write slots at random places
    among the 2w (the rest split between inactive slots and dead txns),
    unique rows, and commits, inserts and deletes among them."""
    live = np.zeros(2 * w, bool)
    live[rng.choice(2 * w, n_live, replace=False)] = True
    live = live.reshape(w, 2)
    alive = live.any(axis=1) | (rng.random(w) < 0.5)
    active = live | (~alive[:, None] & (rng.random((w, 2)) < 0.5))
    rows = rng.choice(td.n_rows(n_sub), 2 * w, replace=False)
    return td.empty_ctx(w).replace(
        alive=jnp.asarray(alive), ws_active=jnp.asarray(active),
        ws_rows=jnp.asarray(rows.reshape(w, 2), jnp.int32),
        ws_vv=jnp.asarray(rng.integers(0, 1 << 20, (w, 2)), jnp.uint32),
        ws_tbl=jnp.asarray(rng.integers(0, 5, (w, 2)), jnp.int32),
        ws_key=jnp.asarray(rng.integers(0, n_sub, (w, 2)), jnp.int32),
        ws_kind=jnp.asarray(rng.integers(0, 3, (w, 2)), jnp.int32))


@pytest.mark.parametrize("w,n_live", [
    (512, 0), (512, 1), (512, 127), (512, 128), (512, 129), (512, 1024),
    (100, 150)], ids=lambda v: str(v))
def test_compacted_install_and_log_equal_full_width_reference(w, n_live):
    """C = 128 at both widths: 0, 1, C-1, C, C+1 and 2w live slots, and a
    width that is no multiple of C (the last chunk's positions run past the
    last lane)."""
    n_sub, chunk = 2000, compact.chunk_lanes(2 * w)
    assert chunk == 128
    rng = np.random.default_rng(n_live)
    db = td.populate(rng, n_sub, val_words=VW, log_capacity=1 << 10)
    # heads in mid-ring, so that slots wrap inside the step's appends
    db = db.replace(log=db.log.replace(head=jnp.asarray(
        rng.integers(0, 1 << 12, db.log.lanes), jnp.uint32)))
    c2 = _forced_ctx(rng, w, n_sub, n_live)
    got, _, _, _, inst, cnt = _forced_step(w, n_sub)(
        db, td.empty_ctx(w), c2, jax.random.PRNGKey(n_live))

    assert int(inst.wmask.sum()) == n_live
    assert (np.asarray(inst.is_del)[np.asarray(inst.wmask)] == 1).any() \
        or n_live < 2
    oob = td.n_rows(n_sub) + 1
    wrows = jnp.where(inst.wmask, inst.rows, oob)
    meta = db.meta.at[wrows].set(inst.meta, mode="drop")
    wflat = (wrows[:, None] * VW + jnp.arange(VW)).reshape(-1)
    val = db.val.at[wflat].set(inst.val.reshape(-1), mode="drop")
    log = logring.append_rep(db.log, inst.wmask, inst.tbl, inst.is_del,
                             jnp.zeros_like(inst.key), inst.key, inst.ver,
                             inst.val)
    assert np.array_equal(np.asarray(got.meta), np.asarray(meta))
    assert np.array_equal(np.asarray(got.val), np.asarray(val))
    assert np.array_equal(np.asarray(got.log.entries),
                          np.asarray(log.entries))
    assert np.array_equal(np.asarray(got.log.head), np.asarray(log.head))
    assert int(np.asarray(got.log.head - db.log.head).sum()) == n_live
    snap = mon.snapshot(cnt)
    assert snap["install_writes"] == n_live
    assert snap["install_chunks"] == -(-n_live // chunk)


def _lock_wave(monkeypatch, w, n_sub, t, arb0, active, keys):
    """One pipe_step at step ``t`` over the stamp table ``arb0`` whose
    new cohort is forced: write slot j is ``active[j]`` and asks for row
    ``keys[j]`` of the SUBSCRIBER table (base 0), no reads. Returns (arb',
    granted [2w], held [2w] as the flight recorder saw it, counters)."""
    ws = (jnp.asarray(active.reshape(w, 2)),
          jnp.zeros((w, 2), jnp.int32), jnp.zeros((w, 2), jnp.int32),
          jnp.asarray(keys.reshape(w, 2), jnp.int32),
          jnp.zeros((w, 2), jnp.int32))
    monkeypatch.setattr(
        td, "gen_cohort", lambda key, w_, n_sub_, mix=None: (
            jnp.zeros(w, jnp.int32), jnp.zeros((w, td.K), jnp.int32),
            jnp.zeros((w, td.K), jnp.int32), jnp.zeros((w, td.K), jnp.int32),
            ws))
    cap = 8 * w
    tcfg = txe.TraceCfg(rate=1.0, cap=cap,
                        wave=waves.full_name("tatp_dense", "trace"))
    db = td.create(n_sub, val_words=VW, log_capacity=1 << 8)
    db = db.replace(arb=jnp.asarray(arb0), step=jnp.asarray(t, db.step.dtype))
    got, ctx, _, _, cnt, ring = jax.jit(functools.partial(
        td.pipe_step, w=w, n_sub=n_sub, val_words=VW, tcfg=tcfg))(
        db, td.empty_ctx(w), td.empty_ctx(w), jax.random.PRNGKey(0),
        counters=mon.create(), ring=txe.create_ring(cap))
    ev = txe.decode(ring.buf, ring.head, cap)
    ev = ev[(ev[:, 1] >> 24) == txe.EV_LOCK]
    assert sorted(ev[:, 3].tolist()) == np.nonzero(active)[0].tolist()
    aux = np.zeros(2 * w, np.uint32)
    aux[ev[:, 3]] = ev[:, 1] & 0xFF
    granted = np.asarray(ctx.granted).reshape(-1)
    assert np.array_equal(granted, (aux & txe.LOCK_GRANTED) != 0)
    return (np.asarray(got.arb), granted, (aux & txe.LOCK_HELD) != 0,
            mon.snapshot(cnt))


def _full_width_lock(w, n_sub, t, arb0, active, keys):
    """The wave as it ran before it was compacted: a stamp gather, a
    masked scatter-max and a winner read-back over all 2w slots."""
    rows = np.where(active, keys, td.n_rows(n_sub))    # NOP: the sentinel
    held = active & ((arb0[rows] >> td.K_ARB) == t - 1)
    packed = ((np.uint32(t) << np.uint32(td.K_ARB))
              | (2 * w - 1 - np.arange(2 * w)).astype(np.uint32))
    cand = active & ~held
    arb = arb0.copy()
    np.maximum.at(arb, rows[cand], packed[cand])
    return arb, cand & (arb[rows] == packed), held


@pytest.mark.parametrize("w,n_active,n_rows", [
    (512, 0, None), (512, 1, None), (512, 127, None), (512, 128, None),
    (512, 129, None), (512, 1024, None), (100, 150, None),
    (512, 64, 8), (512, 2, 1), (512, 130, 16)], ids=lambda v: str(v))
def test_compacted_lock_wave_equals_full_width_reference(
        monkeypatch, w, n_active, n_rows):
    """C = 128 at both widths: 0, 1, C-1, C, C+1 and 2w active slots, and a
    width that is no multiple of C. The requests fall on half as many
    rows as there are requests (so that rows are fought over within a
    chunk and across chunks), or on ``n_rows`` of them (64 requests on 8
    rows, 2 on 1, 130 on 16 across two chunks: heavy duplication), a
    third of the rows held from step t-1, the rest bearing older
    stamps."""
    n_sub, t, chunk = 2000, 40, compact.chunk_lanes(2 * w)
    assert chunk == 128
    rng = np.random.default_rng(n_active)
    active = np.zeros(2 * w, bool)
    active[rng.choice(2 * w, n_active, replace=False)] = True
    pool = rng.choice(n_sub, n_rows or max(4, n_active // 2), replace=False)
    keys = rng.choice(pool, 2 * w)
    arb0 = np.zeros(td.n_rows(n_sub) + 1, np.uint32)
    arb0[pool] = (rng.integers(t - 5, t - 1, len(pool)) << td.K_ARB) \
        | rng.integers(0, 2 * w, len(pool))
    was_held = pool[: len(pool) // 3]
    arb0[was_held] = ((t - 1) << td.K_ARB) | rng.integers(
        0, 2 * w, len(was_held))

    arb, granted, held, snap = _lock_wave(
        monkeypatch, w, n_sub, t, arb0, active, keys)
    want_arb, want_grant, want_held = _full_width_lock(
        w, n_sub, t, arb0, active, keys)
    assert np.array_equal(arb, want_arb)
    assert np.array_equal(granted, want_grant)
    assert np.array_equal(held, want_held)
    if n_active > 8:
        assert want_held.any() and want_grant.any() \
            and (active & ~want_held & ~want_grant).any()
    assert snap["lock_requests"] == n_active
    assert snap["lock_granted"] == want_grant.sum()
    assert snap["lock_rejected"] == n_active - want_grant.sum()
    assert snap["lock_reject_held"] == want_held.sum()
    assert snap["lock_reject_arb"] == \
        (active & ~want_held & ~want_grant).sum()
    assert snap["lock_chunks"] == -(-n_active // chunk)


@pytest.mark.parametrize("held_before", [False, True],
                         ids=["free_row", "held_row"])
def test_one_row_requested_from_two_chunks(monkeypatch, held_before):
    """Slots 3 and 299 of 300 active ask for row 7: their turns are 3 and
    299, chunks 0 and 2 of 128. On a free row the first slot wins and the
    later chunk's scatter-max cannot take the row from it; on a row held
    from step t-1 both are rejected and the word stays as it was, so the
    stamp expires and the row is free at t+1 (no livelock)."""
    w, n_sub, t = 512, 2000, 40
    active = np.arange(2 * w) < 300
    keys = 100 + np.arange(2 * w)
    keys[[3, 299]] = 7
    arb0 = np.zeros(td.n_rows(n_sub) + 1, np.uint32)
    arb0[7] = ((t - 1 if held_before else t - 2) << td.K_ARB) | 11
    arb, granted, held, snap = _lock_wave(
        monkeypatch, w, n_sub, t, arb0, active, keys)
    assert snap["lock_chunks"] == 3
    assert held[[3, 299]].tolist() == [held_before] * 2
    if held_before:
        assert granted[[3, 299]].tolist() == [False, False]
        assert arb[7] == arb0[7]
        assert snap["lock_reject_held"] == 2 and snap["lock_granted"] == 298
        arb, granted, held, _ = _lock_wave(
            monkeypatch, w, n_sub, t + 1, arb, active, keys)
        assert not held[[3, 299]].any()
    assert granted[[3, 299]].tolist() == [True, False]
    step = t + 1 if held_before else t
    assert arb[7] == (step << td.K_ARB) | (2 * w - 1 - 3)
    # (at t+1 the 298 other rows are held by the grants of step t)
    assert granted.sum() == (1 if held_before else 299)


@pytest.mark.parametrize("r,p,chunk", [
    (1, 1.0, 1), (7, 0.5, 4), (256, 0.0, 128), (256, 0.1, 128),
    (256, 0.6, 128), (256, 1.0, 128), (200, 0.9, 128)],
    ids=lambda v: str(v))
def test_for_chunks_visits_the_live_lanes_in_lane_order(r, p, chunk):
    mask = np.random.default_rng(r).random(r) < p
    live = np.nonzero(mask)[0]
    ranks, n_live = compact.live_ranks(jnp.asarray(mask))
    assert int(n_live) == len(live)

    def visit(state, lanes, ok):
        seen, k = state
        return (jax.lax.dynamic_update_slice(
            seen, jnp.where(ok, lanes, -1), (k,)), k + chunk)

    room = -(-r // chunk) * chunk
    (seen, _), trips = compact.for_chunks(
        ranks, n_live, chunk, visit,
        (jnp.full(room, -1, jnp.int32), jnp.asarray(0, jnp.int32)))
    assert int(trips) == -(-len(live) // chunk)
    assert np.asarray(seen).tolist() == \
        live.tolist() + [-1] * (room - len(live))


def test_chunk_lanes_is_a_rule_in_the_width_alone():
    assert [compact.chunk_lanes(r) for r in
            (16, 128, 200, 512, 4096, 8192, 16384)] == \
        [16, 128, 128, 128, 128, 256, 512]


def test_write_heavy_mix_runs_several_chunks_and_matches_generic_engine():
    """Every transaction an update (two write slots or one): more live
    slots than a chunk holds, so several chunks run a step; stats and
    table versions equal the generic engine's, as chip_smoke.compare_small
    compares them."""
    n_sub, w, blocks, seed = 2000, 256, 2, 0
    db = td.populate(np.random.default_rng(seed), n_sub, val_words=VW)
    run_d, init_d, drain_d = td.build_pipelined_runner(
        n_sub, w=w, val_words=VW, cohorts_per_block=2, mix=UPDATES_ONLY,
        monitor=True)
    shards, _ = tc.populate_shards(np.random.default_rng(seed), n_sub,
                                   val_words=VW, log_capacity=1 << 14)
    run_g, init_g, drain_g = tp.build_pipelined_runner(
        n_sub, w=w, val_words=VW, cohorts_per_block=2, mix=UPDATES_ONLY)
    carry, carry_g = init_d(db), init_g(tp.stack_shards(shards))
    key = jax.random.PRNGKey(seed)
    tot_d = np.zeros(td.N_STATS, np.int64)
    tot_g = np.zeros(tp.N_STATS, np.int64)
    for i in range(blocks):
        carry, s_d = run_d(carry, jax.random.fold_in(key, i))
        carry_g, s_g = run_g(carry_g, jax.random.fold_in(key, i))
        tot_d += np.asarray(s_d, np.int64).sum(axis=0)
        tot_g += np.asarray(s_g, np.int64).sum(axis=0)
    db, tail_d, cnt = drain_d(carry)
    stacked, tail_g = drain_g(carry_g)
    tot_d += np.asarray(tail_d, np.int64).sum(axis=0)
    tot_g += np.asarray(tail_g, np.int64).sum(axis=0)
    assert tot_d.tolist() == tot_g.tolist(), (tot_d, tot_g)

    base = td._bases(n_sub + 1)
    ver_d = np.asarray(db.ver)
    for tid, t in enumerate((stacked.sub, stacked.sec, stacked.ai,
                             stacked.sf)):
        want = np.asarray(t.ver)[0]
        assert np.array_equal(ver_d[base[tid]:base[tid] + len(want)],
                              want), tid
    for r in (1, 2):
        assert np.array_equal(
            np.asarray(logring.replica_entries(db.log, r)),
            np.asarray(logring.replica_entries(db.log, 0)))

    snap = mon.snapshot(cnt)
    chunk = compact.chunk_lanes(2 * w)
    writing_steps = blocks * 2            # cohorts complete two steps late
    assert snap["install_writes"] > writing_steps * chunk
    assert snap["install_chunks"] >= 2 * writing_steps
    assert snap["install_chunks"] <= \
        -(-snap["install_writes"] // chunk) + writing_steps
    # every generating step asks for more locks than a chunk holds
    assert snap["lock_requests"] > blocks * 2 * chunk
    assert 2 * blocks * 2 <= snap["lock_chunks"] <= \
        -(-snap["lock_requests"] // chunk) + blocks * 2


# ------------------------------------- the builders' compatibility keywords


def _small_builders():
    """The three builders the benchmark's deployments call with
    ``use_pallas=False, use_fused=False``, each at a tiny geometry:
    name -> (builder, build(**kw), abstract (carry, key))."""
    from dint_tpu.engines import smallbank_dense as sd
    from dint_tpu.parallel import dense_sharded as ds

    def tatp_args(init):
        return init(td.create(200, val_words=VW, log_capacity=128))

    def bank_args(init):
        return init(sd.create(200, log_capacity=128))

    def sharded_args(init):
        return init(ds.create_sharded(ds.make_mesh(4), 4, 800, val_words=VW,
                                      log_capacity=128))

    return {
        "tatp_dense": (
            td.build_pipelined_runner,
            lambda **kw: td.build_pipelined_runner(
                200, w=16, val_words=VW, cohorts_per_block=2, **kw),
            tatp_args),
        "smallbank_dense": (
            sd.build_pipelined_runner,
            lambda **kw: sd.build_pipelined_runner(
                200, w=16, cohorts_per_block=2, **kw),
            bank_args),
        "dense_sharded": (
            ds.build_sharded_pipelined_runner,
            lambda **kw: ds.build_sharded_pipelined_runner(
                ds.make_mesh(4), 4, 800, w=16, val_words=VW,
                cohorts_per_block=2, **kw),
            sharded_args),
    }


BUILDERS = ("tatp_dense", "smallbank_dense", "dense_sharded")


@pytest.mark.parametrize("name", BUILDERS)
def test_kernel_keywords_are_accepted_only_as_off(name):
    """``use_pallas`` / ``use_fused`` stay as keywords until the benchmark
    stops passing them: False and None build, a truthy value raises."""
    _, build, _ = _small_builders()[name]
    for kw in ({"use_pallas": True}, {"use_fused": True},
               {"use_pallas": 1, "use_fused": False}):
        with pytest.raises(ValueError, match="retired keyword"):
            build(**kw)
    for off in (False, None):
        run, init, drain = build(use_pallas=off, use_fused=off)
        assert callable(run) and callable(init) and callable(drain)


@pytest.mark.parametrize("name", BUILDERS)
def test_no_environment_variable_changes_the_block_program(
        monkeypatch, name):
    """With the three variables that used to route a builder to a kernel
    set, the block's jaxpr is the one built without them, and holds no
    pallas_call."""
    builder, build, args = _small_builders()[name]

    def block_text():
        builder.cache.clear()       # a fresh build, not the memoised one
        run, init, _ = build()      # None: the parent asked the environment
        carry = jax.eval_shape(lambda: args(init))
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        return str(jax.make_jaxpr(run)(carry, key))

    for var in ("DINT_USE_PALLAS", "DINT_USE_FUSED",
                "DINT_PALLAS_INTERPRET"):
        monkeypatch.delenv(var, raising=False)
    plain = block_text()
    for var in ("DINT_USE_PALLAS", "DINT_USE_FUSED",
                "DINT_PALLAS_INTERPRET"):
        monkeypatch.setenv(var, "1")
    routed = block_text()
    builder.cache.clear()
    assert routed == plain
    assert "pallas_call" not in plain and "scatter" in plain
