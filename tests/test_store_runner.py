"""The KV store's normal path as the cell store-ycsb-b runs it:
``build_serve_runner`` over ``build_generator``'s YCSB traffic (every
reply and the final table against the sequential oracle and against the
benchmark's plain reference), the device Zipfian against YCSB's law, the
populate through the INSERT path against ``kv.populate``, and the stats
row and the store's counters against numpy on the regenerated batches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import store as ref
from dint_tpu import monitor
from dint_tpu.clients import micro, workloads
from dint_tpu.engines import store
from dint_tpu.engines.types import Batch, Op, Reply
from dint_tpu.tables import kv
from dint_tpu.testing.oracle import StoreOracle

N, NB, W, CPB, VW = 20_000, 1 << 14, 256, 2, 10
ARGS = dict(val_words=VW, read_frac=0.95, theta=0.99)


def _populated(n=N, nb=NB, lanes=1024):
    table, spilled = store.build_populate(n, nb, lanes, val_words=VW)()
    assert int(spilled) == 0
    return table


def _blocks(seed: int, n_blocks: int, **args):
    """(block keys, the batches each block's steps are handed) from the
    generator run alone."""
    gen = store.build_generator(N, W, **{**ARGS, **args})
    cohorts = jax.jit(lambda key: jax.vmap(gen)(
        jax.random.split(key, CPB)))
    keys = [jax.random.fold_in(jax.random.PRNGKey(seed), i)
            for i in range(n_blocks)]
    return keys, [jax.tree.map(np.asarray, cohorts(k)) for k in keys]


def _oracle_populated():
    o = StoreOracle()
    o.data = {k: ((k, store.STORE_MAGIC) + (0,) * (VW - 2), 1)
              for k in range(1, N + 1)}
    return o


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_runner_equals_oracle_and_plain_reference_on_seeded_blocks(seed):
    """Stats rows from the runner; every reply from a second jit of
    ``step`` over the same batches; the final tables of both, entry for
    entry, against ``StoreOracle`` and benchmarks/references/store.py."""
    run, init, drain = store.build_serve_runner(
        N, w=W, cohorts_per_block=CPB, **ARGS)
    keys, blocks = _blocks(seed, 4)
    step = jax.jit(store.step)
    carry, shadow = init(_populated()), _populated()
    oracle, plain = _oracle_populated(), ref.Store(N, VW)
    writes = 0
    for key, block in zip(keys, blocks):
        carry, stats = run(carry, key)
        stats = np.asarray(stats, np.int64)
        for j in range(CPB):
            batch = jax.tree.map(lambda x: x[j], block)
            shadow, rep = step(shadow, batch)
            klo = batch.key_lo.astype(np.int64)
            o_rtype, o_rval, o_rver = oracle.step(batch.op, klo, batch.val)
            p_rtype, p_rval, p_rver, row = plain.step(batch.op, klo,
                                                      batch.val)
            for got, a, b in ((rep.rtype, o_rtype, p_rtype),
                              (rep.val, o_rval, p_rval),
                              (rep.ver, o_rver, p_rver)):
                np.testing.assert_array_equal(np.asarray(got), a)
                np.testing.assert_array_equal(np.asarray(got), b)
            assert ref.equal_mod32(stats[j], row), (stats[j], row)
            writes += int((batch.op == Op.SET).sum())
    table, tail = drain(carry)
    assert writes > 50 and not np.asarray(tail).any()
    assert np.asarray(tail).shape == (1, store.N_STATS)
    final = kv.to_dict(table)
    assert final == kv.to_dict(shadow) == oracle.data
    rkeys, live, vals, vers = plain.final_rows()
    assert live.all() and len(rkeys) > 20
    for k, val, ver in zip(rkeys.tolist(), vals.tolist(), vers.tolist()):
        assert final[k] == (tuple(val), ver)
    untouched = set(range(1, N + 1)) - set(rkeys.tolist())
    assert all(final[k] == ((k, store.STORE_MAGIC) + (0,) * (VW - 2), 1)
               for k in list(untouched)[:500])


def test_the_two_copies_of_the_reference_agree_on_every_op():
    """GET / SET / INSERT / DELETE over a small key space, absent keys and
    re-inserts included: ``StoreOracle`` (the program's copy) and the
    benchmark's plain reference give every reply alike."""
    rng = np.random.default_rng(5)
    oracle, plain = StoreOracle(), ref.Store(40, 3)
    oracle.data = {k: ((k, store.STORE_MAGIC, 0), 1) for k in range(1, 41)}
    seen = set()
    for _ in range(30):
        ops = rng.choice([Op.NOP, Op.GET, Op.SET, Op.INSERT, Op.DELETE],
                         64, p=[0.05, 0.4, 0.25, 0.1, 0.2])
        keys = rng.integers(1, 61, 64)          # 41..60 start absent
        vals = rng.integers(0, 1 << 32, (64, 3), dtype=np.uint64).astype(
            np.uint32)
        a = oracle.step(ops, keys, vals)
        b = plain.step(ops, keys, vals)[:3]
        live = ops != Op.NOP
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x[live], y[live])
        seen |= set(np.unique(a[0]).tolist())
    assert {Reply.VAL, Reply.ACK, Reply.NOT_EXIST} <= seen
    keys, live, vals, vers = plain.final_rows()
    assert {int(k): (tuple(v), int(r)) for k, a, v, r in zip(
        keys, live, vals.tolist(), vers) if a} == {
            k: v for k, v in oracle.data.items() if k in set(keys.tolist())}
    assert not any(int(k) in oracle.data for k, a in zip(keys, live)
                   if not a)


# ------------------------------------------------------- the device Zipfian


def _law_cdf(n, theta):
    zetan, eta, _, _ = store.zipf_constants(n, theta)

    def cdf(k):
        k = np.asarray(k, np.float64)
        tail = ((np.maximum(k, 2) / n) ** (1 - theta) - 1 + eta) / eta
        return np.where(k < 1, 0.0, np.where(k < 2, 1 / zetan,
                                             np.minimum(tail, 1.0)))
    return cdf


@pytest.mark.parametrize("n", [20_000, 24_000_000])
def test_device_zipfian_against_ycsbs_law_at_a_million_draws(n):
    """Shares of key 1, key 2, keys 3-10, the first 1 % of the keys and
    every decile of the key space, each within 6 sigma of the law of
    YCSB's generator in float64; keys 1 and 2 also against the ideal
    Zipfian (clients/workloads.zipf_cdf: they are its own); every key in
    [1, n]. Nothing n-sized is made for the 24 M case but zipf_cdf's."""
    theta, draws = 0.99, 1 << 20
    keys = np.asarray(jax.jit(lambda k: store.zipf_keys(
        k, (draws,), n, theta))(jax.random.PRNGKey(11))).astype(np.int64)
    assert keys.min() >= 1 and keys.max() <= n
    cdf = _law_cdf(n, theta)
    edges = np.array([0, 1, 2, 10, n // 100, *(n * np.arange(1, 11) // 10)])
    edges = np.unique(edges)
    p = np.diff(cdf(edges))
    got = np.histogram(keys, bins=edges + 0.5)[0]
    assert got.sum() == draws
    band = 6 * np.sqrt(draws * p * (1 - p)) + 1
    assert (np.abs(got - draws * p) <= band).all(), (got, draws * p)
    ideal = workloads.zipf_cdf(n, theta)
    for k, p_k in ((1, ideal[0]), (2, ideal[1] - ideal[0])):
        assert abs((keys == k).sum() - draws * p_k) \
            <= 6 * np.sqrt(draws * p_k) + 1
    # the tail is reached, and not as a comb: many distinct late keys
    late = keys[keys > n // 2]
    assert len(np.unique(late)) > 0.97 * min(len(late), n // 2) \
        or n < 10**6


def test_zipf_constants_are_ycsbs_at_the_cells_size():
    zetan, eta, alpha, zeta2 = store.zipf_constants(24_000_000, 0.99)
    assert zetan == pytest.approx(19.10, abs=0.01)
    assert alpha == pytest.approx(100.0) and zeta2 == pytest.approx(
        1 + 0.5 ** 0.99)
    assert 1 / zetan == pytest.approx(0.05236, abs=2e-5)
    assert 0.5 ** 0.99 / zetan == pytest.approx(0.02636, abs=2e-5)
    cdf = _law_cdf(24_000_000, 0.99)
    assert float(cdf(10) - cdf(2)) == pytest.approx(0.0844, abs=2e-4)
    assert float(cdf(240_000)) == pytest.approx(0.7243, abs=2e-4)


def test_the_generator_is_a_function_of_its_key_and_states_its_mix():
    gen = jax.jit(store.build_generator(N, W, **ARGS))
    a, b = gen(jax.random.PRNGKey(4)), gen(jax.random.PRNGKey(4))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    big = jax.jit(store.build_generator(N, 1 << 16, **ARGS))(
        jax.random.PRNGKey(9))
    ops = np.asarray(big.op)
    assert set(np.unique(ops)) == {Op.GET, Op.SET}
    n = ops.size
    assert abs((ops == Op.GET).sum() - 0.95 * n) \
        <= 6 * np.sqrt(n * 0.95 * 0.05)
    val, klo = np.asarray(big.val), np.asarray(big.key_lo)
    stamp = int(np.asarray(jax.random.PRNGKey(9))[-1])
    assert (val[:, 0] == klo).all() and (val[:, 1] == store.STORE_MAGIC
                                         ).all()
    assert (val[:, 2] == stamp).all()
    # a value is a function of (key, stamp): lanes of one key agree
    order = np.argsort(klo, kind="stable")
    same = klo[order][1:] == klo[order][:-1]
    assert same.sum() > 1000
    assert (val[order][1:][same] == val[order][:-1][same]).all()
    other = np.asarray(jax.jit(store.build_generator(N, 1 << 16, **ARGS))(
        jax.random.PRNGKey(10)).val)
    assert (other[:, 3:] != val[:, 3:]).mean() > 0.99
    # the occupancy mask pads with NOP / PAD lanes
    part = gen(jax.random.PRNGKey(4), jnp.int32(100))
    assert (np.asarray(part.op)[100:] == Op.NOP).all()
    assert (np.asarray(part.key_lo)[100:] == 0xFFFFFFFF).all()
    np.testing.assert_array_equal(np.asarray(part.key_lo)[:100],
                                  np.asarray(a.key_lo)[:100])


# --------------------------------------------------------------- populate


@pytest.mark.parametrize("n,nb,lanes", [(N, NB, 1024), (5000, 1 << 12, 256),
                                        (1000, 1 << 10, 128)])
def test_populate_by_insert_holds_what_kv_populate_holds(n, nb, lanes):
    table, spilled = store.build_populate(n, nb, lanes, val_words=VW)()
    assert int(spilled) == 0
    want = kv.to_dict(micro.make_store_table(n, n_buckets=nb,
                                             val_words=VW))
    assert kv.to_dict(table) == want and len(want) == n
    assert int(np.asarray(table.valid).sum()) == n       # each key once


def test_populate_counts_the_keys_it_could_not_place():
    """16 buckets x 4 slots cannot hold 100 keys: what does not fit is
    counted, and is in no entry."""
    table, spilled = store.build_populate(100, 16, 32, val_words=VW)()
    held = kv.to_dict(table)
    assert int(spilled) == 100 - len(held) > 0
    assert len(held) == int(np.asarray(table.valid).sum()) <= 64


# ------------------------------------------------- stats row and counters


def test_stats_rows_and_counters_against_numpy_on_the_batches():
    run, init, drain = store.build_serve_runner(
        N, w=W, cohorts_per_block=CPB, monitor=True, **ARGS)
    keys, blocks = _blocks(17, 3)
    carry, rows = init(_populated()), []
    for key in keys:
        carry, stats = run(carry, key)
        rows.append(np.asarray(stats, np.int64))
    rows = np.concatenate(rows)
    _, tail, counters = drain(carry)
    snap = monitor.snapshot(counters)
    ops = np.concatenate([b.op for b in blocks])
    klo = np.concatenate([b.key_lo for b in blocks])
    col = {n: rows[:, i] for i, n in enumerate(store.STAT_NAMES)}
    assert (col["attempted"] == W).all()
    assert (col["committed"] == W).all()         # the table holds them all
    np.testing.assert_array_equal(col["gets"], (ops == Op.GET).sum(1))
    np.testing.assert_array_equal(col["updates"], (ops == Op.SET).sum(1))
    for name in ("not_exist", "spill", "retry", "magic_bad"):
        assert not col[name].any()
    dup = sum(W - len(np.unique(k)) for k in klo)
    assert snap["store_dup_lanes"] == dup > 100
    assert snap["steps"] == len(rows)
    for counter, stat in (("txn_attempted", "attempted"),
                          ("txn_committed", "committed"),
                          ("store_gets", "gets"),
                          ("store_updates", "updates"),
                          ("store_not_exist", "not_exist"),
                          ("store_spill", "spill"),
                          ("magic_bad", "magic_bad")):
        assert snap[counter] == col[stat].sum(), counter


def test_stats_count_absent_keys_and_a_bad_magic_word():
    """``reply_stats`` on hand-made replies: a GET of an absent key is
    ``not_exist``, a VAL whose word 0 is another key's is ``magic_bad``,
    and the checksums are the sums of the VAL and ACK lanes mod 2^32."""
    table = _populated(1000, 1 << 10, 128)
    # key 7's record under key 8's name: a misplaced value
    e = int(np.nonzero(np.asarray(table.key_lo) == 8)[0][0])
    table = table.replace(val=table.val.at[e * VW].set(jnp.uint32(7)))
    ops = np.array([Op.GET, Op.GET, Op.GET, Op.SET, Op.NOP], np.int32)
    keys = np.array([5, 8, 5000, 9, 0xFFFFFFFF], np.uint32)
    vals = np.zeros((5, VW), np.uint32)
    vals[3] = np.arange(VW) + 0xFFFFFFF0
    batch = Batch(op=jnp.asarray(ops), table=jnp.zeros(5, jnp.int32),
                  key_hi=jnp.asarray([0, 0, 0, 0, 0xFFFFFFFF], jnp.uint32),
                  key_lo=jnp.asarray(keys), val=jnp.asarray(vals),
                  ver=jnp.zeros(5, jnp.uint32))
    _, rep = jax.jit(store.step)(table, batch)
    row = dict(zip(store.STAT_NAMES, np.asarray(
        store.reply_stats(batch, rep), np.int64).tolist()))
    assert (row["attempted"], row["committed"], row["not_exist"]) \
        == (4, 3, 1)
    assert (row["gets"], row["updates"], row["magic_bad"]) == (3, 1, 1)
    assert row["ver_sum"] % 2**32 == 1 + 1 + 2
    want = (5 + store.STORE_MAGIC) + (7 + store.STORE_MAGIC)   # SET: ACK
    assert row["val_sum"] % 2**32 == want % 2**32
    assert (row["spill"], row["retry"]) == (0, 0)


def test_the_two_column_callers_read_a_prefix():
    assert store.STAT_NAMES[:2] == ("attempted", "committed")
    assert (store.STAT_ATTEMPTED, store.STAT_COMMITTED) == (0, 1)
    assert store.N_STATS == len(ref.STAT_NAMES) == 10
    assert tuple(store.STAT_NAMES) == ref.STAT_NAMES
