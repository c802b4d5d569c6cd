"""Round-12 megakernels: lock_validate + install_log (ISSUE 8).

The contract under test, per acceptance criteria:
  * kernel-vs-unfused parity at the op level, including adversarial
    duplicate-index batches and lock batches straddling the hot_n VMEM
    prefix — the fused dispatch must be bit-identical to the two
    dispatches it swallows;
  * the probe-and-degrade contract: DINT_USE_FUSED defaults off,
    explicit kwarg beats the env, and a Mosaic rejection (simulated)
    degrades to the unfused path without raising — and is cached;
  * both dense engines and both sharded paths produce bit-identical
    final state + stats with the fused waves on vs off (the tatp pin
    drives the env plumbing: DINT_USE_FUSED=1 with use_fused=None);
  * the fused waves compose with the round-10 hot-set tier and the
    round-6 Pallas backends (DINT_USE_FUSED x DINT_USE_HOTSET x
    DINT_USE_PALLAS all-on == all-off);
  * the dintscope diff gate folds the swallowed waves onto their fused
    successor (attrib.WAVE_ALIASES) and still exits 1 naming the fused
    wave on an injected regression — which --no-alias provably hides.

Everything runs in Pallas interpret mode on CPU (conftest pins
JAX_PLATFORMS=cpu), so fused-vs-unfused parity is a tier-1 CI fact;
tools/hw_round12.sh carries the same comparisons to hardware.
"""
import copy
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dint_tpu.monitor import attrib, waves
from dint_tpu.ops import pallas_gather as pg

pytestmark = pytest.mark.fused

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "dintscope_trace.json")
GEOM = {"w": 8192, "k": 4, "l": 3, "vw": 10, "d": 8}
CLI = [sys.executable, os.path.join(REPO, "tools", "dintscope.py")]
KEY = jax.random.PRNGKey
U32 = jnp.uint32
I32 = jnp.int32

# one shared tiny geometry per path -> one compile per configuration
# (BLOCKS=1 still overlaps cohorts: CPB=2 steps + the drain finish the
# pipeline, and the fused kernels run interpret-mode per step, so block
# count is execution cost, not coverage — tier-1 budget, round-10 rule)
N_SUB = 256
N_ACC = 128
W = 64
VW = 4
CPB = 2
BLOCKS = 1


def _cli(args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CLI + args, capture_output=True, text=True,
                          timeout=120, env=env, cwd=REPO, **kw)


def _trees_equal(ta, tb):
    la, lb = jax.tree.leaves(ta), jax.tree.leaves(tb)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# ------------------------------------------------------- kernel parity


def test_lock_validate_matches_unfused_composition():
    """One lock_validate dispatch == lock_arbitrate + the XLA validate
    compare + the XLA read-meta gather, bit for bit — with duplicate
    rows in the lock batch (arbitration must pick the same winner),
    duplicate validate indices, inactive lanes, and (hot_n=24) a batch
    straddling the VMEM arb prefix: duplicates on both sides of the
    boundary and pairs that cross it."""
    n, m, v, r, k_arb = 96, 64, 48, 40, 18
    rng = np.random.default_rng(7)
    meta = jnp.asarray(rng.integers(0, 1 << 31, n), U32)
    step = jnp.asarray(5, U32)
    rows = jnp.asarray(np.concatenate([
        rng.integers(0, n, m - 10),
        # adversarial tail: duplicates below, at, and above hot_n=24
        [3, 3, 23, 23, 24, 24, 50, 50, 23, 24]]), I32)
    act = jnp.asarray(rng.integers(0, 2, m), bool)
    vidx = jnp.asarray(np.concatenate([
        rng.integers(0, n, v - 4), [5, 5, 9, 9]]), I32)
    vv1 = jnp.where(jnp.arange(v) % 2 == 0, meta[vidx],
                    meta[vidx] ^ U32(1))
    ridx = jnp.asarray(rng.integers(0, n, r), I32)
    for hot_n in (0, 24):
        arb0 = jnp.asarray(
            (np.uint32(4) << k_arb) * rng.integers(0, 2, n + 1)
            + rng.integers(0, 1 << 10, n + 1), U32)
        arb_u, grant_u = pg.lock_arbitrate(jnp.array(arb0), rows, act,
                                           step, k_arb, hot_n=hot_n)
        vbad_u = (meta[vidx] != vv1).astype(U32)
        rmeta_u = meta[ridx]
        arb_f, grant_f, vbad_f, rmeta_f = pg.lock_validate(
            jnp.array(arb0), meta, vidx, vv1, ridx, rows, act, step,
            k_arb, hot_n=hot_n)
        assert np.array_equal(arb_f, arb_u), hot_n
        assert np.array_equal(grant_f, grant_u), hot_n
        assert np.array_equal(vbad_f, vbad_u), hot_n
        assert np.array_equal(rmeta_f, rmeta_u), hot_n


def test_gather_streams_matches_xla_gathers():
    """One dispatch, three streams of different row widths — including a
    stream whose every lane hits the SAME row (maximal duplicate-index
    pressure on the DMA ring)."""
    n = 64
    rng = np.random.default_rng(11)
    vws = (1, 4, 3)
    tabs = tuple(jnp.asarray(rng.integers(0, 1 << 31, n * vw), U32)
                 for vw in vws)
    idxs = (jnp.asarray(rng.integers(0, n, 40), I32),
            jnp.full((24,), 17, I32),             # all-duplicate stream
            jnp.asarray(rng.integers(0, n, 8), I32))
    got = pg.gather_streams(tabs, idxs, vws)
    want = pg._xla_gather_streams(tabs, idxs, vws)
    for g, w_ in zip(got, want):
        assert np.array_equal(g, w_)


def test_scatter_streams_matches_xla_scatters():
    """One dispatch, three masked scatter streams == the per-stream XLA
    drop-scatters. Adversarial: the same row numbers masked-in across
    different streams (disjoint tables), duplicate row values on
    masked-OUT lanes (idx stays -1, so the one-writer contract holds),
    and one stream entirely masked (zero traffic)."""
    n, k = 64, 40
    rng = np.random.default_rng(13)
    vws = (4, 1, 3)
    tabs = [jnp.asarray(rng.integers(0, 1 << 31, n * vw), U32)
            for vw in vws]
    perm = rng.permutation(n)[:k].astype(np.int32)
    lane = np.arange(k)
    idx0 = np.where(lane % 3 == 0, perm, -1).astype(np.int32)
    # stream 1 masks IN the rows stream 0 masked OUT (cross-stream
    # duplicates of the same row ids against a disjoint table)
    idx1 = np.where(lane % 3 != 0, perm, -1).astype(np.int32)
    idx2 = np.full((k,), -1, np.int32)            # all-masked stream
    idxs = tuple(jnp.asarray(i) for i in (idx0, idx1, idx2))
    vals = tuple(jnp.asarray(rng.integers(0, 1 << 31, k * vw), U32)
                 for vw in vws)
    got = pg.scatter_streams(tuple(jnp.array(t) for t in tabs), idxs,
                             vals, vws)
    want = pg._xla_scatter_streams(tabs, idxs, vals, vws)
    for s, (g, w_) in enumerate(zip(got, want)):
        assert np.array_equal(g, w_), s
    # the all-masked stream wrote nothing
    assert np.array_equal(got[2], tabs[2])


# --------------------------------------------------- probe-and-degrade


def test_resolve_use_fused_env_and_explicit(monkeypatch):
    monkeypatch.delenv("DINT_USE_FUSED", raising=False)
    assert pg.resolve_use_fused(None) is False        # default OFF
    monkeypatch.setenv("DINT_USE_FUSED", "0")
    assert pg.resolve_use_fused(None) is False
    monkeypatch.setenv("DINT_USE_FUSED", "1")
    assert pg.resolve_use_fused(False) is False       # explicit beats env
    # env on + a real probe at a tiny geometry: interpret mode passes,
    # so the resolver says fused (the stream-kernel probes are exercised
    # by every fused engine build below)
    assert pg.resolve_use_fused(None, lockv=(16, 16, 16, 18, 8)) is True


def test_probe_failure_raises_and_is_not_cached(monkeypatch):
    """A kernel that raises at probe time (the Mosaic-refusal shape)
    makes resolve_use_fused raise KernelRefused with the kernel's name
    and the compiler's text, and the failure is NOT cached as a verdict:
    restoring the kernel lets the same geometry probe clean."""
    real_lockv = pg.lock_validate
    monkeypatch.setattr(pg, "_probe_cache", set())    # isolate the cache

    def boom(*a, **k):
        raise RuntimeError("simulated Mosaic rejection")

    monkeypatch.setattr(pg, "lock_validate", boom)
    geom = (24, 24, 16, 18, 0)
    for _ in range(2):
        with pytest.raises(pg.KernelRefused,
                           match=r"'lockv'.*simulated Mosaic rejection"):
            pg.resolve_use_fused(True, lockv=geom)
    assert not pg._probe_cache
    monkeypatch.setattr(pg, "lock_validate", real_lockv)
    assert pg.resolve_use_fused(True, lockv=geom) is True
    # the stream kernels refuse the same way
    monkeypatch.setattr(pg, "scatter_streams", boom)
    with pytest.raises(pg.KernelRefused, match="'sstreams'"):
        pg.resolve_use_fused(True, scatters=((24, 4),))
    monkeypatch.setattr(pg, "gather_streams", boom)
    with pytest.raises(pg.KernelRefused, match="'gstreams'"):
        pg.resolve_use_fused(True, gathers=((24, 1),))


# ------------------------------------------------ engine parity (dense)


@functools.lru_cache(maxsize=None)
def _td_build(use_fused, use_pallas=False):
    # use_fused=None is only ever requested under DINT_USE_FUSED=1
    # (test_tatp_dense_fused_parity) — the env-plumbing leg of the pin
    from dint_tpu.engines import tatp_dense as td

    return td.build_pipelined_runner(
        N_SUB, w=W, val_words=VW, cohorts_per_block=CPB,
        use_pallas=use_pallas, use_fused=use_fused)


@functools.lru_cache(maxsize=None)
def _sb_build(use_fused, use_hotset=False, use_pallas=False):
    from dint_tpu.engines import smallbank_dense as sd

    return sd.build_pipelined_runner(
        N_ACC, w=W, cohorts_per_block=CPB, use_pallas=use_pallas,
        use_hotset=use_hotset, use_fused=use_fused)


def _run_td(use_fused, use_pallas=False, seed=0):
    from dint_tpu.engines import tatp_dense as td

    db = td.populate(np.random.default_rng(seed), N_SUB, val_words=VW)
    run, init, drain = _td_build(use_fused, use_pallas)
    carry = init(db)
    blocks = []
    for i in range(BLOCKS):
        carry, s = run(carry, jax.random.fold_in(KEY(seed), i))
        blocks.append(np.asarray(s))
    db2, tail = drain(carry)
    blocks.append(np.asarray(tail))
    return db2, np.concatenate(blocks, axis=0)


def _run_sb(use_fused, use_hotset=False, use_pallas=False, seed=0):
    from dint_tpu.engines import smallbank_dense as sd

    db = sd.create(N_ACC)
    run, init, drain = _sb_build(use_fused, use_hotset, use_pallas)
    carry = init(db)
    blocks = []
    for i in range(BLOCKS):
        carry, s = run(carry, jax.random.fold_in(KEY(seed), i))
        blocks.append(np.asarray(s))
    db2, tail = drain(carry)
    blocks.append(np.asarray(tail))
    return db2, np.concatenate(blocks, axis=0)


def test_tatp_dense_fused_parity(monkeypatch):
    """DINT_USE_FUSED=1 (env -> builder -> probe -> megakernels) is
    bit-identical to the unfused chain: final DenseDB and every stats
    block, drain included."""
    monkeypatch.setenv("DINT_USE_FUSED", "1")
    db_f, st_f = _run_td(None)          # env-resolved fused
    monkeypatch.delenv("DINT_USE_FUSED")
    db_u, st_u = _run_td(False)
    assert _trees_equal(db_f, db_u)
    assert np.array_equal(st_f, st_u)
    assert st_u.sum() > 0               # the pin exercised real traffic


def test_smallbank_dense_fused_parity():
    db_f, st_f = _run_sb(True)
    db_u, st_u = _run_sb(False)
    assert _trees_equal(db_f, db_u)
    assert np.array_equal(st_f, st_u)
    assert st_u.sum() > 0


# ----------------------------------------------- engine parity (sharded)


# the two sharded parities compile the full shard_map pipeline twice
# each; slow-marked to hold the 1-CPU tier-1 budget (round-10 rule) —
# the fused kernel mechanics and both dense-engine pins stay tier-1,
# and `pytest -m fused` / tools/hw_round12.sh still run these.
@pytest.mark.slow
def test_dense_sharded_fused_parity():
    from dint_tpu.parallel import dense_sharded as ds

    mesh = ds.make_mesh(4)
    n_glob = 4 * 200
    outs = []
    for fused in (True, False):
        run, init, drain = ds.build_sharded_pipelined_runner(
            mesh, 4, n_glob, w=32, val_words=4, cohorts_per_block=CPB,
            use_fused=fused)
        carry = init(ds.create_sharded(mesh, 4, n_glob, val_words=4,
                                       log_capacity=128))
        blocks = []
        for i in range(BLOCKS):
            carry, s = run(carry, jax.random.fold_in(KEY(2), i))
            blocks.append(np.asarray(s))
        state, tail = drain(carry)
        blocks.append(np.asarray(tail))
        outs.append((state, np.concatenate(blocks, axis=0)))
    (sf, stf), (su, stu) = outs
    assert _trees_equal(sf, su)
    assert np.array_equal(stf, stu)
    assert stu.sum() > 0


@pytest.mark.slow
def test_dense_sharded_sb_fused_parity():
    from dint_tpu.parallel import dense_sharded_sb as dsb

    mesh = dsb.make_mesh(4)
    n_glob = 4 * 128
    outs = []
    for fused in (True, False):
        run, init, drain = dsb.build_sharded_sb_runner(
            mesh, 4, n_glob, w=32, cohorts_per_block=CPB,
            use_fused=fused)
        carry = init(dsb.create_sharded_sb(mesh, 4, n_glob))
        blocks = []
        for i in range(BLOCKS):
            carry, s = run(carry, jax.random.fold_in(KEY(3), i))
            blocks.append(np.asarray(s))
        state, tail = drain(carry)
        blocks.append(np.asarray(tail))
        outs.append((state, np.concatenate(blocks, axis=0)))
    (sf, stf), (su, stu) = outs
    assert _trees_equal(sf, su)
    assert np.array_equal(stf, stu)
    assert stu.sum() > 0


# ------------------------------------------------- feature interactions


def test_smallbank_fused_hotset_pallas_stack_parity():
    """The whole stack at once — DINT_USE_FUSED x DINT_USE_HOTSET x
    DINT_USE_PALLAS all on — equals the all-off run bit for bit on
    every main-table field and every stats block (every layer is
    semantics-neutral by its own pins). The hot tier attaches VMEM
    mirror leaves the all-off bank never carries, so the comparison is
    by field name, skipping exactly the round-10 mirrors."""
    import dataclasses

    db_s, st_s = _run_sb(True, use_hotset=True, use_pallas=True)
    db_u, st_u = _run_sb(False)
    mirrors = {"hot_bal", "hot_x", "hot_s", "hot_n"}
    names = {f.name for f in dataclasses.fields(db_u)}
    assert mirrors < names                  # the skip-list stays honest
    for name in sorted(names - mirrors):   # `log` is a nested RepLog
        assert _trees_equal(getattr(db_s, name), getattr(db_u, name)), \
            name
    assert np.array_equal(st_s, st_u)


# ------------------------------------- the dintscope aliased diff gate


def _zero_row():
    return {"ms": 0.0, "slices": 0, "ms_per_step": None, "pct": 0.0,
            "bytes_per_step": None, "gbps": None}


def _fused_ab_artifacts():
    """A fused-vs-unfused A/B pair built from the checked-in fixture:
    A ran the unfused chain (fused waves unobserved), B ran the
    megakernels (constituents unobserved, each fused wave carrying
    exactly its constituents' time) — the equal-work case the aliased
    gate must pass."""
    base = attrib.report(FIXTURE, geometry=GEOM)
    a, b = copy.deepcopy(base), copy.deepcopy(base)
    dsts = sorted(set(attrib.WAVE_ALIASES.values()))
    for dst in dsts:
        a["waves"][dst] = _zero_row()
    for src in attrib.WAVE_ALIASES:
        b["waves"][src] = _zero_row()
    for dst in dsts:
        srcs = [s for s, d in attrib.WAVE_ALIASES.items() if d == dst]
        b["waves"][dst] = dict(
            _zero_row(),
            ms=round(sum(base["waves"][s]["ms"] for s in srcs), 6),
            slices=sum(base["waves"][s]["slices"] for s in srcs),
            ms_per_step=round(sum(base["waves"][s]["ms_per_step"]
                                  for s in srcs), 6),
            pct=round(sum(base["waves"][s]["pct"] for s in srcs), 3))
    return a, b


def test_aliased_fold_merges_constituents():
    a, b = _fused_ab_artifacts()
    d = attrib.diff_breakdowns(a, b)
    assert d["ok"], d["regressions"]
    # every fused wave folded, each listing its sorted constituents
    assert set(d["aliased"]) == set(attrib.WAVE_ALIASES.values())
    rows = {r["wave"]: r for r in d["rows"]}
    for src, dst in attrib.WAVE_ALIASES.items():
        assert src not in rows                  # merged away
        assert src in rows[dst]["includes"]
        assert rows[dst]["includes"] == sorted(
            s for s, t in attrib.WAVE_ALIASES.items() if t == dst)
    # folding conserves time: folded A's fused row == constituent sum
    lv = "dint.smallbank_dense.lock_validate"
    want = round(sum(
        attrib.report(FIXTURE, geometry=GEOM)["waves"][s]["ms_per_step"]
        for s, t in attrib.WAVE_ALIASES.items() if t == lv), 6)
    assert abs(rows[lv]["a_ms_per_step"] - want) < 1e-6
    # symmetric sides (unfused-vs-unfused, fused-vs-fused) never fold
    assert attrib.diff_breakdowns(a, a)["aliased"] == {}
    assert attrib.diff_breakdowns(b, b)["aliased"] == {}
    assert attrib.diff_breakdowns(a, b, alias=False)["aliased"] == {}


def test_fused_diff_cli_gate_names_regressed_wave(tmp_path):
    """Acceptance: the CLI gate folds the alias map, passes the
    equal-work fused A/B, and exits 1 NAMING the fused wave when its
    megakernel regresses past threshold — a regression --no-alias
    provably cannot see (the raw rows have no common observed wave)."""
    a, b = _fused_ab_artifacts()
    lv = "dint.smallbank_dense.lock_validate"
    b2 = copy.deepcopy(b)
    b2["waves"][lv]["ms"] = round(b2["waves"][lv]["ms"] * 1.6, 6)
    b2["waves"][lv]["ms_per_step"] = round(
        b2["waves"][lv]["ms_per_step"] * 1.6, 6)
    pa, pb, pb2 = (str(tmp_path / f"{n}.json") for n in ("a", "b", "b2"))
    for p, obj in ((pa, a), (pb, b), (pb2, b2)):
        with open(p, "w") as f:
            json.dump(obj, f)
    c = _cli(["diff", pa, pb])
    assert c.returncode == 0, (c.stdout, c.stderr)
    assert "aliased:" in c.stdout                # the fold is announced
    c = _cli(["diff", pa, pb2, "--json"])
    assert c.returncode == 1, (c.stdout, c.stderr)
    d = json.loads(c.stdout.strip().splitlines()[-1])
    assert any(r.get("wave") == lv for r in d["regressions"])
    assert d["aliased"][lv] == sorted(
        s for s, t in attrib.WAVE_ALIASES.items() if t == lv)
    c = _cli(["diff", pa, pb2])                  # human mode names it too
    assert c.returncode == 1
    assert lv in c.stdout
    # the raw-scope comparison hides it: A never observed the fused
    # wave, B2 never observed the constituents, so no row is comparable
    assert _cli(["diff", pa, pb2, "--no-alias"]).returncode == 0


# -------------------------------------------------- registry satellites


def test_fused_waves_registered():
    """The fused waves are first-class registry citizens and the alias
    map's endpoints all resolve (attrib asserts this at import; pin it
    explicitly so a registry edit fails here, not at import time)."""
    for eng in ("tatp_dense", "smallbank_dense"):
        for wv in ("lock_validate", "install_log"):
            assert waves.full_name(eng, wv) in waves.ALL_WAVES
            waves.scope(eng, wv)                 # no KeyError
    for src, dst in attrib.WAVE_ALIASES.items():
        assert src in waves.ALL_WAVES
        assert dst in waves.ALL_WAVES
        assert src.split(".")[1] == dst.split(".")[1]   # same engine
