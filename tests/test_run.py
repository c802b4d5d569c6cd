"""dintscan ordered run (tables/run.py): snapshot, overlay, rebuild and
scan-merge unit tests. The differential and serial-order tests against
the store engine live in test_store.py; these pin the run's own
invariants — sortedness, latest-wins dedupe, tombstone shadowing, the
stale contract and the locate lower bound — directly."""
import jax.numpy as jnp
import numpy as np

from dint_tpu.tables import kv, run as run_mod

VW = 4
U32 = jnp.uint32


def mk_table(rng, keys, n_buckets=1 << 8):
    keys = np.asarray(keys, np.uint64)
    vals = rng.integers(0, 1 << 32, size=(len(keys), VW), dtype=np.uint32)
    table = kv.create(n_buckets, slots=8, val_words=VW)
    return kv.populate(table, keys, vals), vals


def append(run, keys, vals, tomb=None, ver=None, mask=None):
    """delta_append with u64 host keys < 2**32 (hi word zero)."""
    keys = np.asarray(keys, np.uint64)
    r = len(keys)
    vals = np.asarray(vals, np.uint32).reshape(r, VW)
    return run_mod.delta_append(
        run,
        jnp.zeros((r,), U32), jnp.asarray(keys.astype(np.uint32)),
        jnp.asarray(np.ones(r) if ver is None else ver, U32),
        jnp.asarray(vals.reshape(-1)),
        jnp.asarray(np.zeros(r, bool) if tomb is None else tomb),
        jnp.asarray(np.ones(r, bool) if mask is None else mask))


def run_keys(run):
    n = int(run.n)
    return np.asarray(run.key_lo)[:n].astype(np.uint64)


def test_from_table_sorted_dense_snapshot(rng):
    keys = rng.choice(10_000, size=200, replace=False)
    table, _ = mk_table(rng, keys)
    run = run_mod.from_table(table, delta_cap=16)
    assert int(run.n) == 200
    got = run_keys(run)
    assert np.array_equal(got, np.sort(keys))
    # rows past n hold the PAD key so binary search needs no bounds
    assert (np.asarray(run.key_hi)[200:] == 0xFFFFFFFF).all()
    assert (np.asarray(run.key_lo)[200:] == 0xFFFFFFFF).all()
    # merged view == the authoritative table's view
    assert run_mod.to_items(run) == kv.to_dict(table)


def test_locate_is_lower_bound(rng):
    keys = np.sort(rng.choice(5_000, size=100, replace=False))
    table, _ = mk_table(rng, keys)
    run = run_mod.from_table(table, delta_cap=8)
    q = np.concatenate([keys, keys + 1, keys - 1,
                        np.array([0, 4_999, 10_000])]).astype(np.uint64)
    pos = np.asarray(run_mod.locate(
        run, jnp.zeros(len(q), U32), jnp.asarray(q.astype(np.uint32))))
    want = np.searchsorted(run_keys(run), q, side="left")
    assert np.array_equal(pos, want)


def test_delta_append_latest_wins_and_dedupes(rng):
    table, _ = mk_table(rng, [10, 20, 30])
    run = run_mod.from_table(table, delta_cap=8)
    v1 = rng.integers(0, 1 << 32, size=(1, VW), dtype=np.uint32)
    v2 = rng.integers(0, 1 << 32, size=(1, VW), dtype=np.uint32)
    run = append(run, [20], v1, ver=[5])
    run = append(run, [20], v2, ver=[6])      # same key, later batch
    assert int(run.d_n) == 1                  # deduped, latest wins
    items = run_mod.to_items(run)
    assert items[20] == (tuple(int(x) for x in v2[0]), 6)
    # within ONE batch the overlay keeps the masked writes it was given
    run2 = run_mod.from_table(table, delta_cap=8)
    run2 = append(run2, [40, 50], np.vstack([v1, v2]),
                  mask=np.array([True, False]))
    assert int(run2.d_n) == 1                 # masked lane never lands
    assert 50 not in run_mod.to_items(run2)


def test_tombstone_shadows_run_row(rng):
    table, _ = mk_table(rng, [1, 2, 3, 4])
    run = run_mod.from_table(table, delta_cap=8)
    run = append(run, [2], np.zeros((1, VW), np.uint32),
                 tomb=np.array([True]))
    items = run_mod.to_items(run)
    assert 2 not in items and set(items) == {1, 3, 4}
    # rebuild folds the tombstone: the row is gone from the dense run
    rb = run_mod.rebuild_run(run)
    assert int(rb.n) == 3 and int(rb.d_n) == 0
    assert np.array_equal(run_keys(rb), [1, 3, 4])


def test_rebuild_matches_merged_view(rng):
    keys = rng.choice(1_000, size=60, replace=False)
    table, _ = mk_table(rng, keys)
    run = run_mod.from_table(table, delta_cap=16)
    # upserts on existing + new keys, one delete
    up = rng.choice(keys, size=5, replace=False)
    new = np.array([2_001, 2_002, 2_003])
    vals = rng.integers(0, 1 << 32, size=(9, VW), dtype=np.uint32)
    run = append(run, np.concatenate([up, new, up[:1]]), vals,
                 tomb=np.array([False] * 8 + [True]))
    want = run_mod.to_items(run)              # merged run ∪ delta
    rb = run_mod.rebuild_run(run)
    assert run_mod.to_items(rb) == want
    assert int(rb.d_n) == 0 and not bool(rb.stale)
    assert np.array_equal(run_keys(rb), np.sort(run_keys(rb)))


def test_overlay_overflow_sets_stale_and_refresh_recovers(rng):
    keys = rng.choice(1_000, size=40, replace=False)
    table, _ = mk_table(rng, keys)
    run = run_mod.from_table(table, delta_cap=4)
    new = np.arange(3_000, 3_006, dtype=np.uint64)   # 6 > delta_cap
    run = append(run, new, rng.integers(0, 1 << 32, size=(6, VW),
                                        dtype=np.uint32))
    assert bool(run.stale)
    # stale == overlay dropped writes: the run CANNOT be repaired from
    # itself; refresh re-snapshots from the authoritative table
    fresh = run_mod.refresh(table, run)
    assert not bool(fresh.stale) and int(fresh.d_n) == 0
    assert run_mod.to_items(fresh) == kv.to_dict(table)


def test_refresh_branches_agree_on_intact_overlay(rng):
    """refresh's two branches (merge-compact vs re-snapshot) must build
    identical runs when the overlay is intact AND the table saw the same
    writes — `stale` only ever trades compute."""
    keys = rng.choice(1_000, size=30, replace=False)
    table, _ = mk_table(rng, keys)
    run = run_mod.from_table(table, delta_cap=8)
    up = rng.choice(keys, size=4, replace=False)
    vals = rng.integers(0, 1 << 32, size=(4, VW), dtype=np.uint32)
    run = append(run, up, vals, ver=[7, 7, 7, 7])
    table = kv.populate(kv.create(1 << 8, slots=8, val_words=VW),
                        *_items_to_arrays(run_mod.to_items(run)))
    a = run_mod.rebuild_run(run)
    b = run_mod.from_table(table, delta_cap=8)
    assert run_mod.to_items(a) == run_mod.to_items(b)
    assert np.array_equal(run_keys(a), run_keys(b))


def _items_to_arrays(items):
    keys = np.array(sorted(items), np.uint64)
    vals = np.array([items[int(k)][0] for k in keys], np.uint32)
    vers = np.array([items[int(k)][1] for k in keys], np.uint32)
    return keys, vals, vers


def _scan_oracle(items, start, slen):
    rows = sorted((k, v) for k, v in items.items() if k >= start)
    return rows[:slen]


def test_merge_scan_matches_sorted_view(rng):
    """locate → slab gather (either route) → merge_scan == the first
    slen live keys >= start of the merged dict, in key order."""
    scan_max, dcap = 6, 4
    keys = rng.choice(200, size=50, replace=False)
    table, _ = mk_table(rng, keys)
    run = run_mod.from_table(table, delta_cap=dcap)
    up = rng.choice(keys, size=2, replace=False)
    vals = rng.integers(0, 1 << 32, size=(3, VW), dtype=np.uint32)
    run = append(run, np.concatenate([up, up[:1]]), vals,
                 tomb=np.array([False, False, True]))
    items = run_mod.to_items(run)

    starts = rng.integers(0, 220, size=16).astype(np.uint64)
    slens = rng.integers(0, scan_max + 1, size=16)
    lg = scan_max + dcap
    q_hi = jnp.zeros(16, U32)
    q_lo = jnp.asarray(starts.astype(np.uint32))
    off = jnp.clip(run_mod.locate(run, q_hi, q_lo), 0, run.cap - lg)
    s_hi, s_lo, s_ver, s_val = run_mod.scan_slab(
        run.key_hi, run.key_lo, run.ver, run.val, off, lg, VW)
    count, k_hi, k_lo, k_ver, k_val, d_hits = run_mod.merge_scan(
        run, s_hi, s_lo, s_ver, s_val, off, q_hi, q_lo,
        jnp.asarray(slens, jnp.int32), scan_max)
    count = np.asarray(count)
    k_lo, k_ver = np.asarray(k_lo), np.asarray(k_ver)
    k_val = np.asarray(k_val)
    for i in range(16):
        want = _scan_oracle(items, int(starts[i]), int(slens[i]))
        assert count[i] == len(want), (i, starts[i], slens[i])
        for j, (k, (v, ver)) in enumerate(want):
            assert int(k_lo[i, j]) == k
            assert int(k_ver[i, j]) == ver
            assert tuple(int(x) for x in k_val[i, j]) == v
        # rows past count are zeroed (the reply-slab contract)
        assert (k_lo[i, count[i]:] == 0).all()
        assert (k_ver[i, count[i]:] == 0).all()
    assert (np.asarray(d_hits) <= count).all()


def test_locate_bits_matches_formula():
    # lg in the dint.store.scan_locate wave formula == locate rounds
    assert run_mod.locate_bits(64) == 7
    assert run_mod.locate_bits(1) == 1
    for cap in (2, 3, 64, 100, 1 << 16):
        assert run_mod.locate_bits(cap) == int(cap).bit_length()
