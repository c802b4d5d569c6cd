"""What decides ``correct``.

Everything here is plain numpy on what a run fetched: stats vectors,
counter snapshots, log rings (ring-sized, never table-sized) and the few
rows the rings name, gathered on the device. The invariants are
chip_smoke.py's (``check_run``, ``ab_missing_band``, ``compare_small``);
the read-back of acknowledged writes from each replica ring is new, and
unlike chip_smoke's recovery it never rebuilds a table on the host.

A band is exact or at least four binomial standard deviations wide, so
no seed can fail one by chance."""
from __future__ import annotations

import json

import numpy as np

HDR_WORDS = 4          # entry layout: flags(is_del | table << 8), key_hi,
#                        key_lo, ver, val words... (dint_tpu/tables/log.py)


class Checks:
    """The run's verdicts, printed as they are made. A failed check does
    not stop the run: the last line then says ``"correct": false`` and
    ``failed_checks`` names every one, so a refused run tells why."""

    def __init__(self, emit):
        self._emit = emit
        self.failed: list[str] = []
        self.n = 0

    def add(self, name: str, ok, **detail) -> bool:
        ok = bool(ok)
        self.n += 1
        if not ok:
            self.failed.append(name)
        self._emit(check=name, passed=ok, **_plain(detail))
        return ok

    @property
    def ok(self) -> bool:
        return self.n > 0 and not self.failed


def _plain(obj):
    """numpy scalars and arrays as JSON can carry them."""
    return json.loads(json.dumps(
        obj, default=lambda x: x.tolist() if hasattr(x, "tolist") else str(x)))


# ------------------------------------------------ stats and counter plane


def ab_missing_band(attempted: int, ab_missing: int):
    """The share of transactions that miss an absent row is fixed by
    TATP's population rules and mix (tests/test_tatp_dense.py::
    test_ab_missing_matches_population_analytics): (observed, expected,
    band) with band = max(0.01, four binomial standard deviations)."""
    p_sf = 0.625 + 0.375 ** 4 / 4
    p_cf = p_sf * 0.25
    expected = (0.35 * (1 - p_sf) + 0.10 * (1 - p_cf) + 0.02 * (1 - p_sf)
                + 0.02 * (1 - p_sf * 0.75) + 0.02 * (1 - p_cf))
    band = max(0.01, 4 * (expected * (1 - expected) / attempted) ** 0.5)
    return ab_missing / attempted, expected, band


def check_accounting(checks: Checks, tag: str, t: dict, snap: dict,
                     dispatched: int, outcomes, faults, counter_pairs
                     ) -> None:
    """What any deployment's stats totals ``t`` (name -> int, run and
    drain together) and counter snapshot ``snap`` can show: every
    transaction dispatched was attempted and got one of the lawful
    ``outcomes``, no ``faults`` column counted anything, something
    committed, and each (counter, stat) of ``counter_pairs`` agrees."""
    checks.add(f"{tag}.attempted_equals_dispatched",
               t["attempted"] == dispatched, attempted=t["attempted"],
               dispatched=dispatched)
    checks.add(f"{tag}.accounting_closes",
               sum(t[n] for n in outcomes) == t["attempted"], stats=t)
    for name in faults:
        checks.add(f"{tag}.{name}_zero", t[name] == 0)
    checks.add(f"{tag}.committed_some", t["committed"] > 0)
    checks.add(f"{tag}.monitor_reconciles_with_stats",
               all(snap[c] == t[s] for c, s in counter_pairs),
               counters={c: snap[c] for c, _ in counter_pairs})


def check_lock_ledger(checks: Checks, tag: str, snap: dict) -> None:
    """Every lock request was granted or rejected, and every rejection has
    its cause (the slot was held, or it lost the step's arbitration)."""
    checks.add(f"{tag}.lock_ledger_closes",
               snap["lock_requests"] == snap["lock_granted"]
               + snap["lock_rejected"]
               and snap["lock_rejected"] == snap["lock_reject_held"]
               + snap["lock_reject_arb"])


def check_ab_missing_band(checks: Checks, tag: str, t: dict) -> None:
    """TATP's own: see ``ab_missing_band``."""
    obs, exp, band = ab_missing_band(t["attempted"], t["ab_missing"])
    checks.add(f"{tag}.ab_missing_in_analytic_band", abs(obs - exp) < band,
               observed=obs, expected=exp, band=band)


# ------------------------------------------------ read-back from the rings


def tatp_table_rows(n_sub: int) -> tuple:
    """Rows of TATP's five tables in log-table-id order: subscriber,
    secondary index, access info, special facility, call forwarding, over
    ``n_sub + 1`` subscriber slots (1, 1, 4, 4 and 12 rows each)."""
    p1 = n_sub + 1
    return (p1, p1, 4 * p1, 4 * p1, 12 * p1)


def table_bases(table_rows) -> np.ndarray:
    """Flat row id of each table's row 0, the tables laid end to end in
    log-table-id order."""
    return np.cumsum([0, *table_rows[:-1]]).astype(np.int64)


def surviving_entries(entries: np.ndarray, heads: np.ndarray):
    """What a wrapping multi-lane ring still holds.

    entries [L, CAP, EW] u32, heads [L] (monotonic appends per lane; slot
    = position % CAP) -> (rows [n, EW], fresh [n] bool, wrapped bool).

    ``fresh`` marks an entry so recent that no later write can have been
    wrapped over in ANY lane. The lanes do not fill at one rate (a
    batch's lane is its write slot's index mod L, and a transaction's
    second write slot is rarely used: on the chip the odd lanes fill
    several times slower than the even ones), so an entry's age is scaled
    by how much faster the fastest lane fills than its own, with the
    heads as the rates: fresh iff age * max(heads) / heads[lane] <
    CAP / 2. A write later than a fresh entry then sits less than about
    CAP / 2 appends deep in its own lane, whichever that is, and the
    other half of the lane is the margin for the lanes' rates not being
    steady. For a fresh entry the check below is exact even on a wrapped
    ring."""
    lanes, cap, _ = entries.shape
    heads = heads.astype(np.int64)
    counts = np.minimum(heads, cap)
    lane_of = np.repeat(np.arange(lanes), counts)
    # age 0 = the lane's newest entry
    age = np.concatenate([np.arange(c)[::-1] for c in counts]) \
        if counts.sum() else np.zeros(0, np.int64)
    slot_of = (heads[lane_of] - 1 - age) % cap
    fresh = age * heads.max() < (cap // 2) * heads[lane_of]
    return entries[lane_of, slot_of], fresh, bool((heads > cap).any())


def newest_per_key(rows_flat: np.ndarray, ver: np.ndarray):
    """Index of the highest-version entry of each distinct row (versions
    are monotonic per row, so that entry is the row's last logged state).
    Returns (row ids, index into the inputs)."""
    if len(rows_flat) == 0:
        return rows_flat, np.zeros(0, np.int64)
    order = np.lexsort((ver, rows_flat))
    sr = rows_flat[order]
    last = np.r_[sr[1:] != sr[:-1], True]
    return sr[last], order[last]


def plan_readback(entries: np.ndarray, heads: np.ndarray, table_rows,
                  val_words: int, key_hi: int | None = None) -> dict:
    """From one replica ring: the newest surviving entry of every key, as
    the flat row it names and the (meta, value) the live row must hold.
    ``table_rows``: the number of rows of each table, in log-table-id
    order (the flat row of table t's key k is ``table_bases[t] + k``).
    ``key_hi`` keeps one source's stream of a ring that carries three
    (dense_sharded tags own entries 0 and forwarded ones source + 1)."""
    e, fresh, wrapped = surviving_entries(entries, heads)
    if key_hi is not None:
        keep = e[:, 1] == np.uint32(key_hi)
        e, fresh = e[keep], fresh[keep]
    table = (e[:, 0] >> 8).astype(np.int64)
    key = e[:, 2].astype(np.int64)
    sizes = np.asarray(table_rows, np.int64)
    n_tables = len(sizes)
    in_range = bool(((table < n_tables)
                     & (key < sizes[np.minimum(table, n_tables - 1)])).all())
    if not in_range:
        # garbage in the ring: report it, and keep the indices usable
        table = np.minimum(table, n_tables - 1)
        key = np.minimum(key, sizes[table] - 1)
    rows = table_bases(sizes)[table] + key
    urows, idx = newest_per_key(rows, e[:, 3])
    is_del = (e[idx, 0] & 0xFF) != 0
    return {
        "rows": urows,
        "meta": (e[idx, 3] << np.uint32(1)) | (~is_del).astype(np.uint32),
        "val": e[idx, HDR_WORDS:HDR_WORDS + val_words],
        "fresh": fresh[idx], "wrapped": wrapped, "in_range": in_range,
        "n_entries": int(len(e)),
    }


def compare_readback(plan: dict, live_meta: np.ndarray,
                     live_val: np.ndarray) -> dict:
    """Hold the live rows (gathered at ``plan['rows']``) to the ring.

    ``lost``: the live row is OLDER than a logged write — never lawful.
    ``differs``: same version, other liveness or value — never lawful.
    ``stale``: the live row is newer than the newest surviving entry, so
    the entry of its last write is gone. Lawful only where the ring
    wrapped over it: never on an unwrapped ring, and never for a fresh
    entry (see ``surviving_entries``)."""
    want_ver = plan["meta"] >> 1
    live_ver = live_meta >> 1
    same = live_ver == want_ver
    differs = same & ((live_meta != plan["meta"])
                      | (live_val != plan["val"]).any(axis=1))
    stale = live_ver > want_ver
    unlawful_stale = stale & (plan["fresh"] | (not plan["wrapped"]))
    out = {
        "keys": int(len(want_ver)), "entries": plan["n_entries"],
        "wrapped": plan["wrapped"], "in_range": plan["in_range"],
        "lost": int((live_ver < want_ver).sum()),
        "differs": int(differs.sum()), "stale": int(stale.sum()),
        "unlawful_stale": int(unlawful_stale.sum()),
        "matched": int((same & ~differs).sum()),
    }
    out["ok"] = (out["in_range"] and out["keys"] > 0 and out["lost"] == 0
                 and out["differs"] == 0 and out["unlawful_stale"] == 0)
    return out


# ------------------------------------ against independent code, small size


ORDER_DEPENDENT = ("committed", "ab_missing", "ab_validate")


def cf_races(reads: list, writes: list) -> int:
    """How many transactions read a CALL_FORWARDING key that a transaction
    two cohorts earlier writes. ``reads[j]`` / ``writes[j]``: the CF keys
    cohort j's transactions read / insert or delete, attempted, in
    dispatch order. Cohort j reads in the step in which cohort j - 2
    installs, so these are the transactions whose answer depends on the
    order of the two inside that step."""
    return sum(int(np.isin(reads[j], writes[j - 2]).sum())
               for j in range(2, len(reads)))


def tatp_stats_agree(dense: dict, generic: dict, versions_equal: bool,
                     races: int) -> tuple:
    """(ok, moved): do the two TATP engines' stats totals (name -> int)
    tell one story? Equal vectors do. So does the one difference both
    engines may lawfully show. A transaction of cohort t reads a
    CALL_FORWARDING row that cohort t-2 inserts or deletes in the same
    step: the dense engine installs before it reads, the generic CF path
    serves the read from the pre-batch state. Both orders are
    serializable, and the transaction's class follows the order: found
    and committed against ``ab_missing`` (row inserted: 7 of 600 seeds on
    the CPU at the comparison's size, PERF.md, PR 32), ``ab_missing``
    against committed or ``ab_validate`` (row deleted). So ``moved``
    transactions may change class among ``ORDER_DEPENDENT``, at most as
    many as the run has such ``races`` (``cf_races``, from the cohorts
    regenerated, not from either engine's state), and only while every
    other column and every compared table's versions are equal."""
    diff = {n: dense[n] - generic[n] for n in dense}
    if not any(diff.values()):
        return True, 0
    moved = sum(abs(diff[n]) for n in ORDER_DEPENDENT) // 2
    ok = (all(diff[n] == 0 for n in diff if n not in ORDER_DEPENDENT)
          and sum(diff[n] for n in ORDER_DEPENDENT) == 0
          and versions_equal and moved <= races)
    return ok, moved


def compare_small(checks: Checks, seed: int, size: dict,
                  val_words: int) -> None:
    """chip_smoke.compare_small with the run's seed, outside the window:
    the dense engine against the generic pipelined engine
    (engines/tatp_pipeline.py: sort-based, sharded tables, other code) in
    stats (``tatp_stats_agree``) and in every table's versions, and
    recovery of the live tables from each one of the three log rings.
    ``size``: n_sub, w, cohorts_per_block, blocks."""
    import jax
    import jax.numpy as jnp

    from dint_tpu import recovery
    from dint_tpu.clients import tatp_client as tc
    from dint_tpu.engines import tatp
    from dint_tpu.engines import tatp_dense as td
    from dint_tpu.engines import tatp_pipeline as tp
    from dint_tpu.engines.types import Op
    from dint_tpu.tables import log as logring

    n_sub, w, cpb, blocks = (size["n_sub"], size["w"],
                             size["cohorts_per_block"], size["blocks"])
    key = jax.random.PRNGKey(seed)
    rng_seed = seed % (1 << 32)

    def drive(run, drain, carry):
        total = np.zeros(td.N_STATS, np.int64)
        for i in range(blocks):
            carry, stats = run(carry, jax.random.fold_in(key, i))
            total += np.asarray(stats, np.int64).sum(axis=0)
        out = drain(carry)
        return out, total + np.asarray(out[1], np.int64).sum(axis=0)

    db0 = td.populate(np.random.default_rng(rng_seed), n_sub,
                      val_words=val_words)
    fresh = jax.tree.map(np.array, db0)          # the runner donates db0
    run_d, init_d, drain_d = td.build_pipelined_runner(
        n_sub, w=w, val_words=val_words, cohorts_per_block=cpb,
        use_pallas=False, use_fused=False, trace=False)
    (db, _), tot_d = drive(run_d, drain_d, init_d(db0))

    shards, _ = tc.populate_shards(np.random.default_rng(rng_seed), n_sub,
                                   val_words=val_words,
                                   log_capacity=1 << 14)
    run_g, init_g, drain_g = tp.build_pipelined_runner(
        n_sub, w=w, val_words=val_words, cohorts_per_block=cpb)
    (stacked, _), tot_g = drive(run_g, drain_g,
                                init_g(tp.stack_shards(shards)))
    base = table_bases(tatp_table_rows(n_sub))
    ver_d = np.asarray(db.ver)
    versions_equal = True
    for tid, t in enumerate((stacked.sub, stacked.sec, stacked.ai,
                             stacked.sf)):
        want = np.asarray(t.ver)[0]
        versions_equal &= np.array_equal(
            ver_d[base[tid]:base[tid] + len(want)], want)
    column = {"attempted": td.STAT_ATTEMPTED, "committed": td.STAT_COMMITTED,
              "ab_lock": td.STAT_AB_LOCK, "ab_missing": td.STAT_AB_MISSING,
              "ab_validate": td.STAT_AB_VALIDATE,
              "magic_bad": td.STAT_MAGIC_BAD}
    def races_of_the_run() -> int:
        """The cohorts both engines generated, again: a block splits its
        key into one per step, a step splits off the generator's
        (tatp_dense.pipe_step and tatp_pipeline's alike)."""
        gen = jax.jit(lambda step_key: tp.gen_cohort(
            jax.random.split(step_key)[0], w, n_sub))
        reads, writes = [], []
        for i in range(blocks):
            for step_key in jax.random.split(jax.random.fold_in(key, i),
                                             cpb):
                _, ops, tbl, kk, (ws_on, _, ws_tbl, ws_key, _) = (
                    jax.tree.map(np.asarray, gen(step_key)))
                reads.append(kk[(tbl == tatp.CALL_FORWARDING)
                                & (ops == Op.OCC_READ)])
                writes.append(
                    ws_key[ws_on & (ws_tbl == tatp.CALL_FORWARDING)])
        return cf_races(reads, writes)

    # counted only where the vectors differ (about 1 seed in 60): an equal
    # run compiles and dispatches nothing for it
    races = None if np.array_equal(tot_d, tot_g) else races_of_the_run()
    agree, moved = tatp_stats_agree(
        {n: int(tot_d[i]) for n, i in column.items()},
        {n: int(tot_g[i]) for n, i in column.items()}, versions_equal,
        races or 0)
    checks.add("compare.dense_stats_equal_generic_engine", agree,
               dense=tot_d.tolist(), generic=tot_g.tolist(),
               difference=(tot_d - tot_g).tolist(), moved=moved,
               cf_races=races)
    checks.add("compare.table_versions_equal_generic_engine",
               versions_equal)

    heads = np.asarray(db.log.head)
    live_val, live_meta = np.asarray(db.val), np.asarray(db.meta)
    checks.add("compare.run_changed_the_tables",
               not np.array_equal(fresh.meta, live_meta))
    for r in range(3):
        rec = recovery.recover_tatp_dense(
            fresh, np.asarray(logring.replica_entries(db.log, r)), heads)
        checks.add(f"compare.recovered_from_replica_{r}",
                   bool(jnp.array_equal(rec.val, live_val))
                   and bool(jnp.array_equal(rec.meta, live_meta)))
