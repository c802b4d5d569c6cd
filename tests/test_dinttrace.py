"""dinttrace: the per-transaction flight recorder (OBSERVABILITY.md).

The contract under test, per acceptance criteria:
  * at rate 1.0 the event stream RECONCILES with the dintmon counter
    plane exactly on every instrumented path (both dense engines, the
    sharded smallbank path, and the 2-D multihost mesh): lock events ==
    lock_requests, install events == install_writes, outcome splits ==
    txn_committed / ab_* — every sampled journey is complete;
  * the sampling mask is deterministic and monotone: the rate-0.25 event
    set is a strict subset of the rate-1.0 set (same txns on every
    shard, retry, and rate — what makes cross-shard joins exact);
  * tracing OFF (the default) changes no engine output bit;
  * ring overflow is keep-first and LOSS-COUNTED: head keeps counting,
    the excess drops, and the `trace_dropped` counter agrees;
  * the checked-in synthetic fixture does not drift from its generator,
    and the dintmon/dinttrace CLIs work end to end — including a joined
    cross-shard span tree (route -> lock -> vote -> install -> both
    replication hops) assembled from a real multihost_sb run.

Builders are cached at module scope (one compile per configuration),
same budget discipline as tests/test_dintmon.py.
"""
import functools
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from dint_tpu import monitor as M
from dint_tpu.monitor import txnevents as txe
from dint_tpu.monitor import txntrace as tt

pytestmark = pytest.mark.trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "dinttrace_events.jsonl")
KEY = jax.random.PRNGKey

# one shared tiny geometry -> one compile per configuration
N_SUB = 300
N_ACC = 400
W = 64
VW = 4
CPB = 2


def _cli(*argv):
    return subprocess.run(
        [sys.executable] + list(argv), capture_output=True, text=True,
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))


# ------------------------------------------------------- cached builders


@functools.lru_cache(maxsize=None)
def _td_build(trace=True, rate=1.0, cap=None, monitor=True):
    from dint_tpu.engines import tatp_dense as td

    return td.build_pipelined_runner(
        N_SUB, w=W, val_words=VW, cohorts_per_block=CPB, monitor=monitor,
        trace=trace, trace_rate=rate, trace_cap=cap)


@functools.lru_cache(maxsize=None)
def _sb_build(trace=True, rate=1.0, cap=None, monitor=True):
    from dint_tpu.engines import smallbank_dense as sd

    return sd.build_pipelined_runner(
        N_ACC, w=W, cohorts_per_block=CPB, monitor=monitor,
        trace=trace, trace_rate=rate, trace_cap=cap)


@functools.lru_cache(maxsize=None)
def _dsb_build():
    from dint_tpu.parallel import dense_sharded_sb as dsb

    mesh = dsb.make_mesh(4)
    runner = dsb.build_sharded_sb_runner(
        mesh, 4, 4 * 128, w=32, cohorts_per_block=2, monitor=True,
        trace=True)
    return runner, mesh


@functools.lru_cache(maxsize=None)
def _mhsb_build():
    from dint_tpu.parallel import multihost_sb as mh

    mesh = mh.make_mesh_2d(4, 2)
    runner = mh.build_multihost_sb_runner(
        mesh, 8 * 128, w=32, cohorts_per_block=2, monitor=True,
        trace=True)
    return runner, mesh


def _drive(runner, state, n_stats, *, trace=True, monitor=True, blocks=3,
           seed=0, path=None):
    """Run `blocks` dispatches + the drain, observing the ring after each
    (the ring zeroes at block entry, so each observe is self-contained).
    Returns (state_out, stats_total, counter_snapshot, TxnMonitor)."""
    run, init, drain = runner
    carry = init(state)
    tmon = txe.TxnMonitor(init.trace_cfg, path=path) if trace else None
    tot = np.zeros(n_stats, np.int64)
    for i in range(blocks):
        carry, s = run(carry, jax.random.fold_in(KEY(seed), i))
        tot += np.asarray(s, np.int64).sum(axis=0)
        if tmon is not None:
            tmon.observe(carry[-2] if monitor else carry[-1])
    out = drain(carry)
    tot += np.asarray(out[1], np.int64).sum(axis=0)
    rest = list(out[2:])
    if tmon is not None:
        tmon.observe(rest.pop(0))
        tmon.close()
    snap = M.snapshot(rest.pop(0)) if monitor else None
    return out[0], tot, snap, tmon


@functools.lru_cache(maxsize=None)
def _sb_full_drive():
    """The rate-1.0 smallbank drive, shared (read-only) by the
    reconciliation, subset, and bit-identity tests: one run, one compile."""
    from dint_tpu.engines import smallbank_dense as sd

    return _drive(_sb_build(), sd.create(N_ACC), sd.N_STATS, seed=1)


def _kind_counts(tmon):
    """(kind-name counts, outcome-cause counts) over every window."""
    kinds, outcomes = {}, {}
    for win in tmon.windows:
        for rec in win:
            for _w0, w1, _w2, _w3 in rec["events"]:
                kind, _wave, _shard, aux = txe.unpack_w1(w1)
                name = txe.KIND_NAMES[kind]
                kinds[name] = kinds.get(name, 0) + 1
                if kind == txe.EV_OUTCOME:
                    cause = txe.CAUSE_NAMES[aux]
                    outcomes[cause] = outcomes.get(cause, 0) + 1
    return kinds, outcomes


def _event_set(tmon):
    return {tuple(e) for win in tmon.windows for rec in win
            for e in rec["events"]}


# ------------------------------------------- full-rate reconciliation


def test_tatp_dense_full_rate_reconciles():
    from dint_tpu.engines import tatp_dense as td

    db = td.populate(np.random.default_rng(0), N_SUB, val_words=VW)
    _, tot, snap, tmon = _drive(_td_build(), db, td.N_STATS)
    kinds, outcomes = _kind_counts(tmon)
    assert kinds["lock"] == snap["lock_requests"] > 0
    assert kinds["validate"] == snap["validate_lanes"] > 0
    assert kinds["install"] == snap["install_writes"] > 0
    assert kinds["outcome"] == snap["txn_attempted"] \
        == tot[td.STAT_ATTEMPTED]
    assert outcomes.get("commit", 0) == snap["txn_committed"]
    assert outcomes.get("ab_lock", 0) == snap["ab_lock"]
    assert outcomes.get("ab_missing", 0) == snap["ab_missing"]
    assert outcomes.get("ab_validate", 0) == snap["ab_validate"]
    assert snap["trace_dropped"] == tmon.summary()["dropped"] == 0


def test_sb_dense_full_rate_reconciles():
    from dint_tpu.engines import smallbank_dense as sd

    _, tot, snap, tmon = _sb_full_drive()
    kinds, outcomes = _kind_counts(tmon)
    assert kinds["lock"] == snap["lock_requests"] > 0
    assert kinds["install"] == snap["install_writes"] > 0
    assert kinds["outcome"] == snap["txn_attempted"] \
        == tot[sd.STAT_ATTEMPTED]
    assert outcomes.get("commit", 0) == snap["txn_committed"]
    assert outcomes.get("ab_lock", 0) == snap["ab_lock"]
    assert outcomes.get("ab_logic", 0) == snap["ab_logic"]
    assert snap["trace_dropped"] == tmon.summary()["dropped"] == 0


def test_dense_sharded_sb_full_rate_reconciles():
    from dint_tpu.parallel import dense_sharded_sb as dsb

    runner, mesh = _dsb_build()
    _, tot, snap, tmon = _drive(
        runner, dsb.create_sharded_sb(mesh, 4, 4 * 128), dsb.N_STATS,
        seed=3)
    kinds, outcomes = _kind_counts(tmon)
    # single-host mesh: the route counters stay zero (ICI-only transport
    # predates the 2-D split), so ROUTE events tie to the lock requests
    # they carried — one lock-route hop per requested slot
    assert kinds["route"] == snap["lock_requests"] == kinds["lock"] > 0
    assert kinds["vote"] == snap["txn_attempted"] \
        == tot[dsb.STAT_ATTEMPTED]
    assert kinds["install"] == snap["install_writes"] > 0
    assert kinds["repl"] == snap["repl_push_hop1"] + snap["repl_push_hop2"]
    assert kinds["outcome"] == snap["txn_attempted"]
    assert outcomes.get("commit", 0) == snap["txn_committed"]
    assert outcomes.get("ab_lock", 0) == snap["ab_lock"]
    assert outcomes.get("ab_logic", 0) == snap["ab_logic"]
    assert snap["trace_dropped"] == tmon.summary()["dropped"] == 0


def test_multihost_sb_full_rate_reconciles(tmp_path):
    from dint_tpu.parallel import dense_sharded_sb as dsb
    from dint_tpu.parallel import multihost_sb as mh

    runner, mesh = _mhsb_build()
    path = str(tmp_path / "mhsb.jsonl")
    _, tot, snap, tmon = _drive(
        runner, mh.create_multihost_sb(mesh, 8 * 128), dsb.N_STATS,
        seed=5, path=path)
    kinds, outcomes = _kind_counts(tmon)
    # the route counters tally lock-route AND install-route lanes; ROUTE
    # events mark lock routes only, so the install writes subtract out
    assert kinds["route"] == snap["route_ici_lanes"] \
        + snap["route_dcn_lanes"] - snap["install_writes"]
    assert snap["route_dcn_lanes"] > 0          # 2-D mesh: DCN hops real
    assert kinds["lock"] == snap["lock_requests"] > 0
    assert kinds["vote"] == snap["txn_attempted"] \
        == tot[dsb.STAT_ATTEMPTED]
    assert kinds["install"] == snap["install_writes"] > 0
    assert kinds["repl"] == snap["repl_push_hop1"] + snap["repl_push_hop2"]
    assert outcomes.get("commit", 0) == snap["txn_committed"]
    assert outcomes.get("ab_lock", 0) == snap["ab_lock"]
    assert snap["trace_dropped"] == tmon.summary()["dropped"] == 0

    # acceptance demo: one committed cross-shard txn assembles into a
    # single joined span tree — route -> lock -> vote -> install -> both
    # replication hops -> outcome — via the CLI, from the JSONL stream
    meta, records = tt.read_trace(path)
    groups = tt.by_txn(tt.decode_records(meta, records))
    full = {txe.EV_ROUTE, txe.EV_LOCK, txe.EV_VOTE, txe.EV_INSTALL,
            txe.EV_REPL, txe.EV_OUTCOME}
    cands = [t for t, g in groups.items()
             if {e["kind"] for e in g} >= full
             and len({e["aux"] for e in g
                      if e["kind"] == txe.EV_REPL}) >= 2
             and len({e["shard"] for e in g}) >= 2
             and tt.span_tree(t, g)["outcome"] == "commit"]
    assert cands, "no committed cross-shard txn with full journey"
    r = _cli("tools/dinttrace.py", "show", path, str(cands[0]))
    assert r.returncode == 0, r.stderr
    for token in ("route", "granted", "vote", "install", "repl hop=",
                  "[commit]"):
        assert token in r.stdout, (token, r.stdout)


# ----------------------------------------- sampling mask + off-path


def test_quarter_rate_events_are_subset_of_full_rate():
    from dint_tpu.engines import smallbank_dense as sd

    _, tot_full, _, tm_full = _sb_full_drive()
    _, tot_q, _, tm_q = _drive(_sb_build(rate=0.25), sd.create(N_ACC),
                               sd.N_STATS, seed=1)
    assert tot_full.tolist() == tot_q.tolist()   # sampling never steers
    full, quarter = _event_set(tm_full), _event_set(tm_q)
    assert 0 < len(quarter) < len(full)
    assert quarter <= full
    # the mask is a pure function of the txn id: a txn is in or out WHOLE
    sampled = {e[0] for e in quarter}
    assert {e for e in full if e[0] in sampled} == quarter


def test_trace_off_is_bit_identical():
    """A/B on the trace flag alone (monitor on in both arms): the on-arm
    is the cached full-rate drive, the off-arm compiles once here."""
    from dint_tpu.engines import smallbank_dense as sd

    db_off, tot_off, _, _ = _drive(_sb_build(trace=False),
                                   sd.create(N_ACC), sd.N_STATS,
                                   trace=False, seed=1)
    db_on, tot_on, _, _ = _sb_full_drive()
    assert tot_off.tolist() == tot_on.tolist()
    for a, b in zip(jax.tree_util.tree_leaves(db_off),
                    jax.tree_util.tree_leaves(db_on)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------- overflow accounting


def test_ring_overflow_keeps_first_and_counts_losses():
    from dint_tpu.engines import smallbank_dense as sd

    _, _, snap, tmon = _drive(_sb_build(cap=16), sd.create(N_ACC),
                              sd.N_STATS, seed=1)
    s = tmon.summary()
    assert s["dropped"] > 0 and s["dropped_windows"]
    # device-side loss counter agrees with the host derivation exactly
    assert snap["trace_dropped"] == s["dropped"]
    for win in tmon.windows:
        for rec in win:
            assert len(rec["events"]) == min(rec["head"], 16)
            assert rec["dropped"] == max(0, rec["head"] - 16)


# ------------------------------------------------ fixture + CLI surface


def test_synth_fixture_has_not_drifted(tmp_path):
    fresh = str(tmp_path / "synth.jsonl")
    tt.synthesize_events(fresh)
    with open(fresh) as f, open(FIXTURE) as g:
        assert f.read() == g.read(), \
            "regenerate with `python tools/dinttrace.py synth`"


def test_dinttrace_cli_on_fixture():
    r = _cli("tools/dinttrace.py", "summarize", FIXTURE)
    assert r.returncode == 0 and "OVERFLOW" in r.stdout
    r = _cli("tools/dinttrace.py", "summarize", FIXTURE, "--json")
    s = json.loads(r.stdout)
    assert s["events"] == 14 and s["txns"] == 3 and s["dropped"] == 3

    r = _cli("tools/dinttrace.py", "show", FIXTURE, "101")
    assert r.returncode == 0
    for token in ("route", "granted", "install", "repl hop=1",
                  "repl hop=2", "[commit]"):
        assert token in r.stdout, (token, r.stdout)
    assert _cli("tools/dinttrace.py", "show", FIXTURE,
                "999").returncode == 1

    r = _cli("tools/dinttrace.py", "aborts", FIXTURE, "--by-cause",
             "--json")
    out = json.loads(r.stdout)
    assert out["aborted"] == 2
    assert set(out["by_cause"]) == {"ab_lock", "ab_validate"}

    r = _cli("tools/dinttrace.py", "slowest", FIXTURE, "--json")
    assert json.loads(r.stdout)["slowest"][0]["txn"] in (101, 103, 205)


def test_dinttrace_export_merges_on_own_pid(tmp_path):
    out = str(tmp_path / "spans.json")
    r = _cli("tools/dinttrace.py", "export", FIXTURE, "-o", out, "--json")
    assert r.returncode == 0
    trace = json.load(open(out))
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 14
    assert {e["pid"] for e in xs} == {tt.EXPORT_PID}


def test_dintmon_check_cli(tmp_path):
    good = {"counters": {
        "lock_requests": 10, "lock_granted": 7, "lock_rejected": 3,
        "lock_reject_held": 2, "lock_reject_arb": 1,
        "steps": 4}}
    p = str(tmp_path / "good.json")
    json.dump(good, open(p, "w"))
    r = _cli("tools/dintmon.py", "check", p)
    assert r.returncode == 0 and "dintmon check: ok" in r.stdout
    # the route identity must be SKIPPED when both route counters are 0
    r = _cli("tools/dintmon.py", "check", p, "--json")
    rows = {x["identity"]: x["status"]
            for x in json.loads(r.stdout)["identities"]}
    assert rows["route_ici_lanes + route_dcn_lanes == "
                "lock_requests + install_writes"] == "skipped"

    bad = {"counters": dict(good["counters"], lock_granted=9)}
    q = str(tmp_path / "bad.json")
    json.dump(bad, open(q, "w"))
    r = _cli("tools/dintmon.py", "check", q)
    assert r.returncode == 1
    assert "lock_requests == lock_granted + lock_rejected" in r.stdout

    null = str(tmp_path / "null.json")
    json.dump({"counters": None}, open(null, "w"))
    assert _cli("tools/dintmon.py", "check", null).returncode == 1
