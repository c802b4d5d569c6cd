"""The KV store's compacted install (engines/store.py ``_install_live``,
PR 40): where the shapes make a scatter lane dear, ``step`` issues the
step's elected writers in chunks (ops/compact.py) and not its lanes.

Pinned here: at a geometry where the rule compacts, the table (all five
arrays) and the replies are bit-identical to the full-width path's and
agree with ``testing/oracle.StoreOracle``, case by case; the loops make
the trips the live counts need and the runner's counters say so step by
step; and the rule itself picks the form by shape: the populate and the
small sizes keep the full-width program, the serve block loops."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dint_tpu import monitor as M
from dint_tpu.engines import store
from dint_tpu.engines.types import Op, Reply, make_batch
from dint_tpu.monitor import counters as mc
from dint_tpu.ops import compact, hashing
from dint_tpu.tables import kv
from dint_tpu.testing.oracle import StoreOracle

# 2^17 buckets x 4 slots = 524,288 entries, 2,048 a lane: past the 1,536
# at which the compiler would sort a full-width scatter
W, VW, NB, SLOTS = 256, 10, 1 << 17, 4
C = compact.chunk_lanes(W)
RESIDENT = 2000
ARRAYS = ("key_hi", "key_lo", "val", "ver", "valid")


def test_the_geometry_compacts_and_a_chunk_is_half_the_width():
    table = jax.eval_shape(lambda: kv.create(NB, slots=SLOTS, val_words=VW))
    assert store.install_is_compacted(table, W)
    assert not store.install_is_compacted(table, 2 * W)
    assert C == 128 and W == 2 * C


def _step(table, batch):
    return store._step(table, batch, maintain_bloom=False, hot=None,
                       run=None, scan_max=8)


def _step_at_full_width(table, batch):
    """``_step`` traced with the rule answering `dense`: the parent's five
    full-width scatters at this geometry, the form to be equal to."""
    rule = store.install_is_compacted
    store.install_is_compacted = lambda table, r: False
    try:
        return _step(table, batch)
    finally:
        store.install_is_compacted = rule


@pytest.fixture(scope="module")
def steps():
    """(compacted, full_width), jitted, and what tells them apart: two
    chunk loops in the one, none in the other."""
    compacted, full = jax.jit(_step), jax.jit(_step_at_full_width)
    shapes = jax.eval_shape(lambda: (
        kv.create(NB, slots=SLOTS, val_words=VW),
        make_batch([Op.GET], np.array([1], np.uint64), width=W,
                   val_words=VW)))
    assert _count_whiles(jax.make_jaxpr(compacted)(*shapes).jaxpr) == 2
    assert _count_whiles(jax.make_jaxpr(full)(*shapes).jaxpr) == 0
    return compacted, full


def _count_whiles(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "while"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_whiles(sub)
    return n


def _resident(rng):
    table = kv.create(NB, slots=SLOTS, val_words=VW)
    keys = np.arange(1, RESIDENT + 1, dtype=np.uint64)
    vals = rng.integers(0, 1 << 32, size=(RESIDENT, VW), dtype=np.uint32)
    oracle = StoreOracle()
    oracle.data = {int(k): (tuple(int(x) for x in v), 1)
                   for k, v in zip(keys, vals)}
    return kv.populate(table, keys, vals), oracle


def _vals(rng, n):
    return rng.integers(0, 1 << 32, size=(n, VW), dtype=np.uint32)


def _both(steps, table, ops, keys, vals):
    """One step through both forms from the same table: every array of
    the two tables and every reply equal; returns the table, the replies
    and the first loop's (trips, writers)."""
    compacted, full = steps
    batch = make_batch(ops, keys, vals, width=W, val_words=VW)
    (t_c, rep_c), live = compacted(table, batch)
    (t_f, rep_f), none = full(table, batch)
    assert none is None
    for name in ARRAYS + ("bloom_lo", "bloom_hi"):
        np.testing.assert_array_equal(np.asarray(getattr(t_c, name)),
                                      np.asarray(getattr(t_f, name)), name)
    for got, want in zip(jax.tree.leaves(rep_c), jax.tree.leaves(rep_f)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    return t_c, rep_c, tuple(int(x) for x in live)


def _agrees_with_oracle(oracle, table, rep, ops, keys, vals):
    n = len(ops)
    ot, ov, over = oracle.step(np.asarray(ops), keys, vals)
    np.testing.assert_array_equal(np.asarray(rep.rtype)[:n], ot)
    np.testing.assert_array_equal(np.asarray(rep.ver)[:n], over)
    got = (np.asarray(ops) == Op.GET) & (ot == Reply.VAL)
    np.testing.assert_array_equal(np.asarray(rep.val)[:n][got], ov[got])
    assert kv.to_dict(table) == oracle.data


def _all_gets(rng):
    keys = rng.integers(1, RESIDENT + 500, size=W).astype(np.uint64)
    return [([Op.GET] * W, keys, _vals(rng, W))], [(0, 0)]


def _ycsb_b_with_a_hot_key(rng):
    """95 % GET / 5 % SET, a fifth of the lanes on key 1: several of its
    SETs a step, one elected writer."""
    batches, live = [], []
    for _ in range(4):
        ops = np.where(rng.random(W) < 0.95, Op.GET, Op.SET).astype(np.int32)
        ops[:3] = Op.SET
        keys = rng.integers(1, RESIDENT + 1, size=W).astype(np.uint64)
        keys[rng.random(W) < 0.2] = 1
        keys[:3] = 1
        writers = len(set(keys[ops == Op.SET].tolist()))
        assert 0 < writers < (ops == Op.SET).sum()
        batches.append((ops, keys, _vals(rng, W)))
        live.append((-(-writers // C), writers))
    return batches, live


def _every_lane_a_live_insert(rng):
    keys = np.arange(RESIDENT + 1, RESIDENT + W + 1, dtype=np.uint64)
    return [([Op.INSERT] * W, keys, _vals(rng, W))], [(W // C, W)]


def _deletes_mixed_with_reinserts(rng):
    """In one step, key by key: delete then re-insert (an update of the
    resident entry), insert then delete of a new key (nothing lands),
    delete of a resident key (a slot freed), insert of a new key (a slot
    allocated), delete of an absent key, and three writes of one key."""
    ops = [Op.DELETE, Op.INSERT,  Op.INSERT, Op.DELETE,  Op.DELETE,
           Op.INSERT,  Op.DELETE,  Op.SET, Op.DELETE, Op.INSERT, Op.GET]
    keys = [5, 5,  9001, 9001,  6,  9002,  9003,  7, 7, 7, 6]
    first = (ops, np.array(keys, np.uint64), _vals(rng, len(ops)))
    # and what the step left is what the next one finds
    ops2 = [Op.GET, Op.GET, Op.GET, Op.GET, Op.INSERT, Op.DELETE]
    keys2 = [5, 6, 7, 9002, 6, 9002]
    second = (ops2, np.array(keys2, np.uint64), _vals(rng, len(ops2)))
    return [first, second], [(1, 3), (1, 1)]


def _writers(n):
    def case(rng):
        keys = rng.integers(1, RESIDENT + 1, size=W).astype(np.uint64)
        keys[:n] = np.arange(1, n + 1)
        keys[n:] = np.where(keys[n:] <= n, keys[n:] + n, keys[n:])
        ops = np.array([Op.SET] * n + [Op.GET] * (W - n), np.int32)
        return [(ops, keys, _vals(rng, W))], [(-(-n // C), n)]
    return case


CASES = {
    "all_gets": _all_gets,
    "ycsb_b_with_a_hot_key": _ycsb_b_with_a_hot_key,
    "every_lane_a_live_insert": _every_lane_a_live_insert,
    "deletes_mixed_with_reinserts": _deletes_mixed_with_reinserts,
    "a_chunk_of_writers": _writers(C),
    "a_chunk_and_one_writer": _writers(C + 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compacted_install_equals_full_width_and_the_oracle(
        steps, rng, case):
    table, oracle = _resident(rng)
    batches, live = CASES[case](rng)
    for (ops, keys, vals), want in zip(batches, live):
        table, rep, got = _both(steps, table, ops, keys, vals)
        assert got == want
        _agrees_with_oracle(oracle, table, rep, ops, keys, vals)


def test_a_spill_into_a_full_bucket_pair_lands_nowhere(steps, rng):
    """A key whose two candidate buckets are full gets SPILL from both
    forms and is in neither table; its neighbours in the batch land."""
    table, oracle = _resident(rng)
    k = 777_777
    hi, lo = jnp.zeros((1,), jnp.uint32), jnp.full((1,), k, jnp.uint32)
    b1, b2 = (int(b[0]) for b in hashing.bucket_pair(hi, lo, NB))
    assert b1 != b2
    rows = np.concatenate([np.arange(b * SLOTS, (b + 1) * SLOTS)
                           for b in (b1, b2)])
    free = rows[~np.asarray(table.valid)[rows]]
    # squatters: keys the hash would never send there, so no batch below
    # names them; the engine asks only whether a slot is valid
    table = table.replace(
        valid=table.valid.at[free].set(True),
        key_hi=table.key_hi.at[free].set(jnp.uint32(7)),
        key_lo=table.key_lo.at[free].set(
            jnp.arange(len(free), dtype=jnp.uint32)))
    ops = [Op.INSERT, Op.SET, Op.GET, Op.INSERT, Op.SET]
    keys = np.array([k, k, k, 9100, 3], np.uint64)
    table, rep, live = _both(steps, table, ops, keys, _vals(rng, 5))
    assert list(np.asarray(rep.rtype)[:5]) == [
        Reply.SPILL, Reply.SPILL, Reply.NOT_EXIST, Reply.ACK, Reply.ACK]
    assert live == (1, 2)
    left = kv.to_dict(table)
    assert k not in left and 9100 in left and left[3][1] == 2


# ------------------------------------------------ through the runner


def test_install_chunks_reconcile_with_install_writes_step_by_step():
    """One step a block, so a window's delta is one step's: the first
    loop makes ceil(install_writes / C) trips, two where a step's elected
    writers pass a chunk; a read-only runner makes none."""
    n_keys = 4000
    table, spilled = store.build_populate(n_keys, NB, 2 * W,
                                          val_words=VW)()
    assert int(spilled) == 0

    def deltas(read_frac, steps):
        run, init, drain = store.build_serve_runner(
            n_keys, w=W, cohorts_per_block=1, val_words=VW,
            read_frac=read_frac, hot_frac=1.0, hot_prob=0.0,
            use_scan=False, monitor=True)
        carry, prev, out = init(jax.tree.map(jnp.copy, table)), None, []
        for i in range(steps):
            carry, stats = run(carry, jax.random.fold_in(
                jax.random.PRNGKey(40), i))
            snap = M.snapshot(carry[-1])
            d = mc.delta(snap, prev)
            prev = snap
            assert d["steps"] == 1
            assert d["install_chunks"] == -(-d["install_writes"] // C)
            assert d["install_writes"] <= d["store_updates"] \
                == int(np.asarray(stats)[0, store.STAT_UPDATES])
            out.append(d)
        return out

    writing = deltas(0.3, 5)
    assert all(d["install_writes"] > C for d in writing)
    assert all(d["install_chunks"] == 2 for d in writing)
    assert all(d["install_writes"] == 0 == d["install_chunks"]
               for d in deltas(1.0, 2))


# ----------------------------------------------------- the shape rule

CONFIG = json.loads((pathlib.Path(__file__).parent.parent / "benchmarks"
                     / "configs" / "store24m.json").read_text())
TRAFFIC = json.loads((pathlib.Path(__file__).parent.parent / "benchmarks"
                      / "traffic" / "ycsb-b.json").read_text())


def _populate_whiles(n_keys, n_buckets, lanes) -> int:
    populate = store.build_populate(n_keys, n_buckets, lanes, val_words=VW)
    return _count_whiles(jax.make_jaxpr(populate)().jaxpr)


def _block_whiles(n_keys, n_buckets, w, cpb) -> int:
    run, init, _ = store.build_serve_runner(
        n_keys, w=w, cohorts_per_block=cpb, val_words=VW, read_frac=0.95,
        theta=0.99, use_scan=False, monitor=True)
    carry = jax.eval_shape(lambda: init(kv.create(n_buckets,
                                                  val_words=VW)))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return _count_whiles(jax.make_jaxpr(run)(carry, key).jaxpr)


def test_the_populate_keeps_the_full_width_program_at_every_size():
    """Abstract traces, nothing allocated. 65,536 all-live lanes into 2^26
    entries are one index per 1,024 words: the compiler sorts those, and a
    chunk loop over an all-live mask would issue them unsorted."""
    sizes = CONFIG["sizes"]
    assert _populate_whiles(sizes["n_keys"], sizes["n_buckets"],
                            sizes["populate_lanes"]) == 0
    for small in (CONFIG["compare_small"], CONFIG["rehearse"]):
        assert _populate_whiles(small["n_keys"], small["n_buckets"],
                                small["populate_lanes"]) == 0


def test_the_serve_block_loops_at_the_cell_and_not_at_the_small_sizes():
    sizes, params = CONFIG["sizes"], TRAFFIC["params"]
    assert (sizes["n_buckets"] * sizes["slots"]) // params["w"] == 8192
    assert _block_whiles(sizes["n_keys"], sizes["n_buckets"], params["w"],
                         params["cohorts_per_block"]) == 2
    small = CONFIG["compare_small"]
    assert _block_whiles(small["n_keys"], small["n_buckets"], small["w"],
                         small["cohorts_per_block"]) == 0
    rehearse = TRAFFIC["rehearse"]["params"]
    assert _block_whiles(CONFIG["rehearse"]["n_keys"],
                         CONFIG["rehearse"]["n_buckets"], rehearse["w"],
                         rehearse["cohorts_per_block"]) == 0
