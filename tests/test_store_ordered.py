"""The store with its ordered run, at a small size on the CPU, against the
plain reference of `store24m-ordered` (benchmarks/references/store_scan.py)
over SCAN + INSERT + DELETE + SET + GET: tombstones inside a scan's
windows, an overlay at capacity (RETRY, then exact after the refresh),
scans that start past the last key; YCSB's insert sequence and hashed
keys in the generator; and the `store-ycsb-b` cell's programs, which this
code shares, lowering to the parent's text."""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import store_scan as ref
from dint_tpu.engines import store
from dint_tpu.engines.types import Batch, Op, Reply
from dint_tpu.monitor import counters as mon
from dint_tpu.ops import hashing
from dint_tpu.tables import kv, run as run_mod

VW = 4
N_KEYS = 300
R = 64
PAD = 0xFFFFFFFF


def hashed_table():
    keys = ref.fnv64(np.arange(1, N_KEYS + 1))
    vals = np.zeros((N_KEYS, VW), np.uint32)
    vals[:, 0] = (keys & np.uint64(PAD)).astype(np.uint32)
    vals[:, 1] = ref.MAGIC
    return kv.populate(kv.create(1 << 8, slots=8, val_words=VW), keys, vals,
                       np.ones(N_KEYS, np.uint32))


def batch(ops, keys, vals, slens):
    keys = np.asarray(keys, np.uint64)
    return Batch(op=jnp.asarray(ops, jnp.int32),
                 table=jnp.zeros(len(ops), jnp.int32),
                 key_hi=jnp.asarray((keys >> np.uint64(32)).astype(
                     np.uint32)),
                 key_lo=jnp.asarray((keys & np.uint64(PAD)).astype(
                     np.uint32)),
                 val=jnp.asarray(vals, jnp.uint32),
                 ver=jnp.asarray(slens, jnp.uint32))


@pytest.fixture(scope="module")
def step():
    return jax.jit(store.step, static_argnames=("scan_max",))


def random_batch(rng, added, p):
    """Lanes over populated keys, keys added so far, new keys and start
    keys anywhere in the 64-bit space (past the last key too)."""
    pool = np.concatenate([ref.fnv64(np.arange(1, N_KEYS + 1)),
                           np.asarray(added, np.uint64)])
    ops = rng.choice([Op.GET, Op.SET, Op.INSERT, Op.DELETE, Op.SCAN,
                      Op.NOP], size=R, p=p).astype(np.int32)
    keys = rng.choice(pool, size=R)
    fresh = rng.random(R) < 0.4
    keys = np.where(fresh & ((ops == Op.INSERT) | (ops == Op.SET)),
                    rng.integers(1, 1 << 63, size=R, dtype=np.uint64), keys)
    anywhere = rng.random(R) < 0.3
    keys = np.where((ops == Op.SCAN) & anywhere,
                    rng.integers(0, 1 << 64, size=R, dtype=np.uint64), keys)
    vals = rng.integers(0, 1 << 32, size=(R, VW), dtype=np.uint32)
    vals[:, 0] = (keys & np.uint64(PAD)).astype(np.uint32)
    vals[:, 1] = ref.MAGIC
    slens = np.where(ops == Op.SCAN, rng.integers(0, 9, size=R), 0)
    return ops, keys, vals, slens


def check_against(oracle, step, table, run, ops, keys, vals, slens, smax):
    """One batch through the engine and the reference; returns the new
    state and the scan lanes the engine answered RETRY (checked: only
    where a tombstone or a stale overlay can explain it)."""
    b = batch(ops, keys, vals, slens)
    stale = bool(run.stale)
    tombs = bool(np.asarray(run.d_tomb).any())
    table, rep, run, srep = step(table, b, run=run, scan_max=smax)
    rt, rv, rr, _, (k, v, val, cnt) = oracle.step(
        ops, b.key_hi, b.key_lo, vals, slens, smax, rows=True)
    rtype = np.asarray(rep.rtype)
    retry = (ops == Op.SCAN) & (rtype == Reply.RETRY)
    assert not retry.any() or stale or tombs
    ok = ~retry
    assert np.array_equal(rtype[ok], rt[ok])
    assert np.array_equal(np.asarray(rep.ver)[ok], rr[ok])
    assert np.array_equal(np.asarray(rep.val)[ok], rv[ok])
    s = jax.tree.map(np.asarray, srep)
    got_k = (s.key_hi.astype(np.uint64) << np.uint64(32)) \
        | s.key_lo.astype(np.uint64)
    assert np.array_equal(got_k[ok], k[ok])
    assert np.array_equal(s.ver[ok], v[ok]) and np.array_equal(
        s.val[ok], val[ok]) and np.array_equal(s.count[ok], cnt[ok])
    return table, run, retry


@pytest.mark.parametrize("seed, dcap", [(0, 64), (1, 64), (2, 16)],
                         ids=["a", "b", "overlay_at_capacity"])
def test_engine_equals_the_reference_over_scans_and_writes(step, seed,
                                                           dcap):
    """Mixed batches with the run rebuilt every third batch; a RETRY lane
    is re-sent after a refresh and must then equal the reference. At a
    16-entry overlay the writes overflow it: every scan answers RETRY
    until the refresh re-snapshots."""
    rng = np.random.default_rng(seed)
    smax = 8
    table = hashed_table()
    run = run_mod.from_table(table, delta_cap=dcap)
    oracle = ref.OrderedStore(N_KEYS, VW)
    retried = stale_seen = 0
    for it in range(10):
        ops, keys, vals, slens = random_batch(
            rng, oracle.added, [0.1, 0.15, 0.15, 0.15, 0.4, 0.05])
        stale_seen += bool(run.stale)
        table, run, retry = check_against(oracle, step, table, run, ops,
                                          keys, vals, slens, smax)
        if retry.any() or it % 3 == 2:
            run = store.rebuild_run(table, run)
            assert run_mod.to_items(run) == kv.to_dict(table)
        if retry.any():
            n = int(retry.sum())
            retried += n
            again = np.full(R, Op.NOP, np.int32)
            again[:n] = Op.SCAN
            k2 = np.zeros(R, np.uint64)
            k2[:n] = keys[retry]
            s2 = np.zeros(R, np.int64)
            s2[:n] = slens[retry]
            table, run, retry2 = check_against(
                oracle, step, table, run, again, k2, np.zeros((R, VW),
                                                             np.uint32),
                s2, smax)
            assert not retry2.any()
    assert kv.to_dict(table) == {int(k): v for k, v in
                                 _oracle_items(oracle).items()}
    if dcap == 16:
        assert stale_seen and retried


def _oracle_items(oracle):
    out = {}
    for k in oracle.base:
        if int(k) not in oracle.touched:
            out[int(k)] = oracle.initial(int(k))
    for k, v in oracle.touched.items():
        if v is not None:
            out[k] = v
    return out


def test_a_tombstone_inside_the_window_is_exact_or_retry_then_exact(step):
    """Deleting keys a scan's windows hold: a scan that still sees its
    rows below the horizon is exact; one that cannot answers RETRY, and
    after the rebuild folds the tombstones it is exact."""
    smax = 8
    table = hashed_table()
    run = run_mod.from_table(table, delta_cap=64)
    oracle = ref.OrderedStore(N_KEYS, VW)
    base = np.sort(ref.fnv64(np.arange(1, N_KEYS + 1)))
    gone = base[100:106]                   # six rows in a row
    ops = np.full(R, Op.NOP, np.int32)
    ops[:6] = Op.DELETE
    keys = np.zeros(R, np.uint64)
    keys[:6] = gone
    z = np.zeros((R, VW), np.uint32)
    table, run, _ = check_against(oracle, step, table, run, ops, keys, z,
                                  np.zeros(R), smax)
    ops = np.full(R, Op.SCAN, np.int32)
    keys = np.concatenate([np.repeat(base[98], 32), np.repeat(base[50], 32)])
    slens = np.concatenate([np.full(32, 8), np.full(32, 2)])
    table, run, retry = check_against(oracle, step, table, run, ops, keys,
                                      z, slens, smax)
    assert retry[:32].all() and not retry[32:].any()
    run = store.rebuild_run(table, run)
    table, run, retry = check_against(oracle, step, table, run, ops, keys,
                                      z, slens, smax)
    assert not retry.any()


def test_scans_past_the_last_key_return_nothing(step):
    table = hashed_table()
    run = run_mod.from_table(table, delta_cap=16)
    oracle = ref.OrderedStore(N_KEYS, VW)
    ops = np.full(R, Op.SCAN, np.int32)
    keys = np.full(R, (1 << 64) - 2, np.uint64)
    keys[1] = np.sort(ref.fnv64(np.arange(1, N_KEYS + 1)))[-1]
    table, run, retry = check_against(
        oracle, step, table, run, ops, keys, np.zeros((R, VW), np.uint32),
        np.full(R, 8), 8)
    assert not retry.any()


# ------------------------------------------------------------ generator


def test_fnv64_is_ycsbs_and_the_modulus_exact():
    nums = np.array([1, 2, 3, 255, 256, 65_537, 24_000_000, 33_554_431,
                     (1 << 32) - 1], np.uint32)
    hi, lo = hashing.fnv64(jnp.asarray(nums))
    got = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) \
        | np.asarray(lo).astype(np.uint64)
    assert np.array_equal(got, ref.fnv64(nums))
    assert int(ref.fnv64([1])[0]) == 0x7632CED6E2D5105C
    assert (got >> np.uint64(63) == 0).all()
    for m in (1, 2, 7, 24_000_000, (1 << 28) - 1):
        r = np.asarray(hashing.mod_u64(hi, lo, m))
        assert np.array_equal(r.astype(np.uint64), got % np.uint64(m))


def test_the_generator_inserts_ycsbs_sequence_under_hashed_keys():
    w, n = 512, 10_000
    gen = jax.jit(store.build_ordered_generator(
        n, w, val_words=VW, scan_frac=0.95, max_scan_len=100, theta=0.99))
    key = jax.random.PRNGKey(7)
    nxt = n + 1
    seen = set(ref.fnv64(np.arange(1, nxt)).tolist())
    for i in range(4):
        b, nxt2 = gen(jax.random.fold_in(key, i), nxt)
        b = jax.tree.map(np.asarray, b)
        keys = (b.key_hi.astype(np.uint64) << np.uint64(32)) \
            | b.key_lo.astype(np.uint64)
        ins = b.op == Op.INSERT
        assert int(nxt2) == nxt + ins.sum()
        assert np.array_equal(keys[ins], ref.fnv64(
            np.arange(nxt, nxt + ins.sum())))
        sc = b.op == Op.SCAN
        assert (sc | ins).all() and 0.9 < sc.mean() < 0.99
        assert set(keys[sc].tolist()) <= seen          # inserted before
        assert ((b.ver[sc] >= 1) & (b.ver[sc] <= 100)).all()
        assert (b.ver[ins] == 0).all()
        assert np.array_equal(b.val[:, 0], b.key_lo)
        assert (b.val[:, 1] == ref.MAGIC).all()
        seen |= set(keys[ins].tolist())
        nxt = int(nxt2)
    again = jax.tree.map(np.asarray, gen(jax.random.fold_in(key, 3),
                                         nxt - ins.sum())[0])
    assert np.array_equal(again.key_lo, b.key_lo)


def test_the_hashed_populate_holds_ycsbs_keys():
    table, spilled = store.build_populate(2_000, 1 << 10, 256, val_words=VW,
                                          hashed=True)()
    got = kv.to_dict(table)
    keys = ref.fnv64(np.arange(1, 2_001))
    assert int(spilled) == 0 and set(got) == set(keys.tolist())
    for k in keys[:50]:
        assert got[int(k)] == ((int(k) & PAD, ref.MAGIC, 0, 0), 1)


# -------------------------------------------- the shared cell's programs


# sha256 of the `store-ycsb-b` runner's COMPILED program (24 M keys,
# 2^24 buckets, 8,192 x 16; and the dense 256 x 2 form) as ``canon``
# writes it: the counter plane's width written NC (PR 45 adds three
# counters, which widen the counter vector of every monitored program
# and change nothing else), no source lines, ops renumbered. PR 45 takes
# the scan lanes out of ``build_generator``, whose ``scan_frac=0`` ops
# (a zero mask, an unused draw) the parent lowered and the compiler
# folded away: its lowered text differs, its program does not. The
# parent's tree gives the same four hashes (PR 45's CHANGES.md).
# The resize that took six counter rows out renumbered the counters after
# them and dropped the block's constant `dispatch_xla` bump: the two
# block hashes moved with it, the drains' did not (the drain bumps no
# counter), and with ``monitor=False`` the block lowers to the text it
# lowered before the resize.
PARENT_PROGRAM = {
    (8192, "block"):
        "bb37456a3695bb90aec067847d3f046961721b9b2593fa3fbffab93f78c703e3",
    (8192, "drain"):
        "7868278dd266dc3ff8b0e254750f55fc70545c69bc8e7ddd469f5eaa26108647",
    (256, "block"):
        "de45c0ad84b735806a4eb7bbaff7450477f5f58f09d3722edfae5c4296731166",
    (256, "drain"):
        "db59ded0fdc6e05f38d8a45d0930d6532ddb26d9a06009be037290524dd0f53a",
}


def canon(text: str, nc: int) -> str:
    """Compiled HLO text with what source lines and op numbering decide
    taken out: the stack-frame tables, frame ids and metadata, every
    ``%`` name (some are a caller's Python function's) renamed by first
    appearance, the counter width NC."""
    text = text[:text.index("\n")] + text[text.index("\n%"):]
    text = re.sub(r", stack_frame_id=\d+", "", text)
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = text.replace(f"u32[{nc}]", "u32[NC]")
    seen: dict = {}

    def renumber(m):
        return seen.setdefault(m.group(0), f"%v{len(seen)}")

    return re.sub(r"%[A-Za-z_][\w\-.]*", renumber, text)


@pytest.mark.parametrize("n, w, cpb, nb", [
    (24_000_000, 8192, 16, 1 << 24), (20_000, 256, 2, 1 << 14)],
    ids=["cell", "dense"])
def test_store_ycsb_b_programs_compile_to_the_parents(n, w, cpb, nb):
    run, init, drain = store.build_serve_runner(
        n, w=w, cohorts_per_block=cpb, val_words=10, read_frac=0.95,
        theta=0.99, monitor=True)
    carry = jax.eval_shape(lambda: init(kv.create(nb)))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    for name, fn, args in (("block", run, (carry, key)),
                           ("drain", drain, (carry,))):
        text = canon(fn.lower(*args).compile().as_text(), mon.N_COUNTERS)
        assert hashlib.sha256(text.encode()).hexdigest() \
            == PARENT_PROGRAM[(w, name)], name
