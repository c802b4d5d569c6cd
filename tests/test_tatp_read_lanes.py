"""Dense TATP issues its wave-1 / wave-2 gathers over the cohort's read
lanes only (tatp_pipeline.READ_LANES of the K lanes a transaction has).

That is sound because of two facts of gen_cohort's layout, checked here
for each transaction type: no OCC_READ sits in a lane >= READ_LANES, and
every active write slot names the row of one of its transaction's read
lanes, so a lock lane's wave-1 meta is the read lane's. The digests pin
the engine's results, bit for bit, as they were when the gathers issued
all K lanes."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dint_tpu import monitor as M
from dint_tpu.clients import workloads as wl
from dint_tpu.engines import tatp_dense as td
from dint_tpu.engines import tatp_pipeline as tp
from dint_tpu.engines.types import Op
from dint_tpu.parallel import dense_sharded as ds

VW = 4
TYPES = range(len(wl.TATP_MIX))
SEEDS = (0, 5, 2**31 + 3)


def _one_hot(t):
    return np.eye(len(wl.TATP_MIX))[t]


def _rows(n_sub, ops, tbl, kk):
    """Flat row id of every lane, the sentinel for NOP lanes."""
    base = td._bases(n_sub + 1)
    return np.where(ops != Op.NOP, base[tbl] + kk, td.n_rows(n_sub))


@pytest.mark.parametrize("t", TYPES)
def test_reads_sit_in_the_read_lanes_and_writes_name_a_read_row(t):
    n_sub, w = 2000, 512
    base = td._bases(n_sub + 1)
    for seed in SEEDS:
        ttype, ops, tbl, kk, ws = jax.tree.map(np.asarray, tp.gen_cohort(
            jax.random.PRNGKey(seed), w, n_sub, mix=_one_hot(t)))
        ws_active, _, ws_tbl, ws_key, _ = ws
        assert (ttype == t).mean() > 0.99
        assert not (ops[:, tp.READ_LANES:] == Op.OCC_READ).any()
        rows = _rows(n_sub, ops, tbl, kk)[:, :tp.READ_LANES]
        is_read = ops[:, :tp.READ_LANES] == Op.OCC_READ
        ws_rows = base[ws_tbl] + ws_key
        hit = ((ws_rows[:, :, None] == rows[:, None, :])
               & is_read[:, None, :]).any(axis=2)
        assert (hit | ~ws_active).all()


@pytest.mark.parametrize("t", TYPES)
def test_write_slot_meta_equals_the_lock_lane_gather(t):
    """pipe_step's ws_vv against what a K-lane meta gather gives at each
    slot's lock lane, over a meta array whose every row differs."""
    n_sub, w = 2000, 512
    rng = np.random.default_rng(t)
    db = td.populate(rng, n_sub, val_words=VW)
    n1 = td.n_rows(n_sub) + 1
    meta = (rng.permutation(n1).astype(np.uint32) << 1) \
        | (np.asarray(db.meta) & 1)
    meta[n1 - 1] = 0                        # the sentinel is never written
    db = db.replace(meta=jnp.asarray(meta))
    step = jax.jit(lambda db, key: td.pipe_step(
        db, td.empty_ctx(w), td.empty_ctx(w), key, w=w, n_sub=n_sub,
        val_words=VW, mix=_one_hot(t)))
    for seed in SEEDS:
        key = jax.random.PRNGKey(seed)
        _, ctx, _, _ = step(db, key)
        _, ops, tbl, kk, ws = jax.tree.map(np.asarray, tp.gen_cohort(
            jax.random.split(key)[0], w, n_sub, mix=_one_hot(t)))
        ws_active, ws_lane = ws[0], ws[1]
        full = meta[_rows(n_sub, ops, tbl, kk)]                  # [w, K]
        want = np.take_along_axis(full, ws_lane, axis=1)
        got = np.asarray(ctx.ws_vv)
        assert ws_active.any() == (t >= wl.TATP_UPDATE_SUBSCRIBER)
        np.testing.assert_array_equal(got[ws_active], want[ws_active])


def _digest(*trees) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(trees):
        a = np.ascontiguousarray(np.asarray(leaf))
        h.update(str((a.dtype, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


WRITES_ONLY = np.array([0, 0, 0, 25, 25, 25, 25], np.float64)


def _one_chip_digest(seed, mix):
    n_sub, w = 20_000, 256
    db = td.populate(np.random.default_rng(seed), n_sub, val_words=VW)
    run, init, drain = td.build_pipelined_runner(
        n_sub, w=w, val_words=VW, cohorts_per_block=2, mix=mix,
        monitor=True)
    carry = init(db)
    stats = []
    for i in range(4):
        carry, s = run(carry, jax.random.fold_in(jax.random.PRNGKey(seed),
                                                 i))
        stats.append(np.asarray(s))
    db, tail, cnt = drain(carry)
    stats.append(np.asarray(tail))
    snap = M.snapshot(cnt)
    return _digest(np.concatenate(stats), db.meta, db.val, db.arb, db.step,
                   db.log.entries, db.log.head,
                   np.asarray(sorted(snap.items()), dtype=object)[:, 1]
                   .astype(np.int64))


# recorded with every gather issuing all K = 4 lanes of a transaction
# (9ffde110ead38e96, 67caad876021bee2, 3aa1ef58fc14cd79), then again when
# six counter rows left the registry: this tree gives the first three
# with those rows added back to the snapshot (dispatch_xla == steps, the
# other five 0), so only the snapshot's length moved
@pytest.mark.parametrize("seed,mix,want", [
    (7, None, "63105026d5e75ad6"),
    (2**31 + 11, None, "96fe6dc7d55a59ce"),
    (7, WRITES_ONLY, "eead7c16d5e7ce08"),
], ids=["tatp-mix", "tatp-mix-large-seed", "writes-only"])
def test_one_chip_runner_is_bit_identical_to_the_k_lane_gathers(seed, mix,
                                                                want):
    assert _one_chip_digest(seed, mix) == want


def _sharded_digest(seed, mix):
    d, n_glob, w = 4, 4 * 300, 64
    mesh = ds.make_mesh(d)
    state = ds.create_sharded(mesh, d, n_glob, val_words=VW,
                              seed=seed % (1 << 31), log_capacity=1 << 10)
    run, init, drain = ds.build_sharded_pipelined_runner(
        mesh, d, n_glob, w=w, val_words=VW, cohorts_per_block=2, mix=mix)
    carry = init(state)
    stats = []
    for i in range(3):
        carry, s = run(carry, jax.random.fold_in(jax.random.PRNGKey(seed),
                                                 i))
        stats.append(np.asarray(s))
    state, tail = drain(carry)
    stats.append(np.asarray(tail))
    return _digest([np.concatenate([x.reshape(-1) for x in stats])], state)


@pytest.mark.parametrize("seed,mix,want", [
    (11, None, "e161d3a7bba10351"),
    (11, WRITES_ONLY, "77d6e34195ca2e34"),
], ids=["tatp-mix", "writes-only"])
def test_sharded_runner_is_bit_identical_to_the_k_lane_gathers(seed, mix,
                                                               want):
    assert _sharded_digest(seed, mix) == want
