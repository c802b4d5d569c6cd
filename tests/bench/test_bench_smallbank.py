"""The `smallbank24m` configuration's own files: the recorded chip trace
reduces to its known numbers, the bytes model gives hand-worked numbers
at the cell's width, the benchmark's reference and the program's copy of
it agree, the deployment's ``verify`` notices one doctored ring entry,
one doctored live balance and one doctored stat, and the traffic's shape
is the configuration's: a program that draws another fails the run."""
import glob
import json
import os

import jax
import numpy as np
import pytest

from benchmarks import bytes_model_smallbank as bms
from benchmarks import checks as ck
from benchmarks import part_times as pt
from benchmarks import run as bench_run
from benchmarks import trace_reduce as tr
from benchmarks.deployments import smallbank_dense_one_chip as dep_mod
from benchmarks.loops import closed_block
from benchmarks.references import smallbank as ref
from dint_tpu.testing import oracle

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures", "smallbank24m")
CELL = "smallbank24m-sat"
LOCK = "dint.smallbank_dense.lock"
LOCK_PARTS = ("lock_arb", "lock_held_read", "lock_grant", "lock_stamp")


def _manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _config() -> dict:
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "smallbank24m.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ the manifest


EIGHT = ("step_ms.sb", "device_idle_share.sb", "contention_abort_share.sb",
         "monitor_ms.sb", "unnamed_ms.sb", "lock_ms.sb", "lock_arb_ms.sb",
         "hbm_roofline_share.sb")
OWN_READERS = ("lock_ms.sb", "hbm_roofline_share.sb", "dispatch_gap_ms.sb",
               "logic_abort_share.sb")


def test_the_cell_and_its_metrics_are_in_the_manifest():
    manifest = _manifest()
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("smallbank24m", "sat", 1)
    config = next(c for c in manifest["configs"]
                  if c["name"] == "smallbank24m")
    assert config["reduced"] == [] == _config()["reduced"]
    assert config["source"] == _config()["source"]
    e2e = {m["name"] for m in manifest["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e >= {"committed_txn_per_s", "txn_latency_p50_ms", "setup_s"}
    mine = {m["name"]: m for m in manifest["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(mine) >= {*EIGHT, *OWN_READERS}
    for name in (*EIGHT, *OWN_READERS):
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "committed_txn_per_s"
    # by the variant rule where the quantity has a reader already
    for name in ("step_ms", "device_idle_share", "contention_abort_share",
                 "monitor_ms", "unnamed_ms", "lock_arb_ms"):
        assert bench_run.reader_path("layer_metrics", name + ".sb") \
            == os.path.join(REPO, "benchmarks", "layer_metrics",
                            name + ".py")
    for name in OWN_READERS:
        assert bench_run.reader_path("layer_metrics", name) \
            == os.path.join(REPO, "benchmarks", "layer_metrics",
                            name + ".py")


def test_pr29s_six_entries_are_where_pr29_put_them():
    """What ``test_bench_parts.py`` pinned of PR 29's six part metrics,
    by the position they have had since PR 29 and not by their distance
    from the end: the manifest is append-only, so an entry never moves
    and everything after the six came later (``conftest.py``)."""
    per_layer = _manifest()["per_layer"]
    quantities = {"val_scatter_ms": "kernels", "monitor_ms": "counter plane",
                  "unnamed_ms": "engine step"}
    six = per_layer[9:15]
    assert [m["name"] for m in six] == [
        q + v for q in quantities for v in (".tput", ".lat")]
    for m in six:
        quantity, variant = m["name"].rsplit(".", 1)
        assert m["layer"] == quantities[quantity]
        assert (m["unit"], m["better"], m["source"]) \
            == ("ms", "lower", "program_span")
        assert m["workloads"] == [
            {"tput": "tatp7m-sat", "lat": "tatp7m-lat"}[variant]]
        assert m["moves"] == {"tput": "committed_txn_per_s",
                              "lat": "txn_latency_p50_ms.lat"}[variant]
        assert bench_run.reader_path("layer_metrics", m["name"]) \
            == os.path.join(REPO, "benchmarks", "layer_metrics",
                            quantity + ".py")
    # this PR's entries follow them, at the end of the list as it was
    assert [m["name"] for m in per_layer[15:25]] == [*EIGHT, *OWN_READERS[2:]]
    assert not any(CELL in m["workloads"] for m in per_layer[:15])


def test_the_logic_abort_share_by_hand():
    """30 M attempted of which 3.84 M found no funds."""
    read = bench_run.load_reader("layer_metrics", "logic_abort_share.sb")
    totals = {"attempted": 30_000_000, "committed": 25_650_000,
              "ab_lock": 510_000, "ab_logic": 3_840_000}
    assert read({"totals": totals}) == pytest.approx(12.8)
    # a deployment without that outcome leaves the metric out
    del totals["ab_logic"]
    assert read({"totals": totals}) is None


# ------------------------------------------------- the recorded chip trace


def _fixture(suffix: str) -> dict:
    found = glob.glob(os.path.join(FIXTURES, "*." + suffix))
    assert len(found) == 1, f"one recorded *.{suffix} under {FIXTURES}"
    with open(found[0]) as f:
        return json.load(f)


def test_the_recorded_trace_reduces_to_its_known_numbers():
    fx = _fixture("trace.json")
    red, want = tr.reduce(fx["trace"]), fx["expected"]
    tr.require_device_work(red, want["n_devices"])
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    scope_s = red["devices"][0]["scope_s"]
    for scope, seconds in want["scope_s"].items():
        assert scope_s[scope] == pytest.approx(seconds, rel=1e-9)
    assert 0.9 * red["busy_s"] < sum(scope_s.values()) <= red["busy_s"]
    # the lock wave is the work of this cell
    assert max(scope_s, key=scope_s.get) == LOCK
    assert {"dint.smallbank_dense." + w for w in (
        "gen", "lock", "read", "compute", "install", "log_append")} \
        <= set(scope_s)
    assert len(tr.block_modules(red["devices"][0])) \
        == want["block_programs"]


def test_the_dispatch_gap_reader_by_hand():
    """Three block programs 3 ms and 5 ms apart, one other program: the
    arithmetic of tatp7m-sat's reader under this cell's name."""
    ms = 1e6
    dev = {"modules": [("block", 0.0, 100 * ms), ("drain", 320 * ms, ms),
                       ("block", 103 * ms, 100 * ms),
                       ("block", 208 * ms, 100 * ms)]}
    ctx = {"trace": {"devices": [dev], "window_s": 0.321}}
    sb, tput = (bench_run.load_reader("layer_metrics", "dispatch_gap_ms." + v)
                for v in ("sb", "tput"))
    assert sb(ctx) == tput(ctx) == 4.0
    # the recorded trace was cut to one block program: nothing to read
    one = {"trace": tr.reduce(_fixture("trace.json")["trace"])}
    assert sb(one) is None and sb({"trace": None}) is None


def test_the_recorded_trace_gives_its_known_parts():
    fx = _fixture("parts.json")
    got, want = pt.per_step(pt.fixture_ops(fx), fx["steps"]), fx["expected"]
    assert got["unnamed"] == pytest.approx(want["unnamed"], rel=1e-9)
    assert got["parts"] == pytest.approx(want["parts"], rel=1e-9)
    step = sum(v for row in got["by_wave"].values() for v in row.values())
    # lock_ms.sb >= lock_arb_ms.sb > 0, and the four parts are the wave
    lock = got["by_wave"][LOCK]
    lock_ms = sum(lock.values())
    assert lock_ms >= got["parts"]["lock_arb"] > 0
    assert set(LOCK_PARTS) <= set(lock)
    assert sum(lock[p] for p in LOCK_PARTS) \
        == pytest.approx(lock_ms, rel=0.01)
    # the largest wave, nearly half of the step
    assert lock_ms == max(sum(row.values())
                          for row in got["by_wave"].values())
    assert lock_ms > 0.4 * step
    assert {"log_build", "log_plan", "log_scatter"} \
        <= set(got["by_wave"]["dint.smallbank_dense.log_append"])
    assert {"sb_addr", "sb_ctx", "stats", "monitor"} \
        <= set(got["by_wave"][pt.NO_WAVE])
    assert 0 < got["parts"]["monitor"] < 0.1 * step
    assert got["unnamed"] < 0.1 * step


# --------------------------------------------------------- the bytes model


def test_step_bytes_at_the_cells_width_by_hand():
    """w = 8192 transactions of 3 lock lanes; 14,000 grants and 9,000
    installs a step: 24,576 lanes read two stamps (196,608 B) and a
    balance (98,304 B), a grant reads and writes a stamp (112,000 B), an
    install writes a word (36,000 B) and three 24-byte log entries
    (648,000 B)."""
    b = bms.step_bytes(8192, 3, 2, 3, lock_granted=14000, installs=9000)
    assert b == {"lock": 196608 + 112000, "read": 98304, "install": 36000,
                 "log_append": 648000, "total": 1090912}
    nothing = bms.step_bytes(8192, 3, 2, 3, lock_granted=0, installs=0)
    assert nothing["total"] == 3 * 98304     # dead lanes still read


# ------------------------------------------------------ the reference, twice


@pytest.mark.parametrize("max_slots", [1 << 25, 1 << 8],
                         ids=["exact_slots", "hashed_slots"])
def test_the_two_copies_of_the_reference_agree(max_slots):
    n, w = 600, 96
    rng = np.random.default_rng(7)
    a, b = (ref.SmallBankOracle(n, max_lock_slots=max_slots),
            oracle.SmallBankOracle(n, max_lock_slots=max_slots))
    assert a.n_slots == b.n_slots and a.hashed == (max_slots < 2 * n + 1)
    for _ in range(6):
        cohort = (rng.integers(0, 6, w), rng.integers(0, 40, w),
                  rng.integers(0, n, w), rng.integers(-60, 61, w))
        np.testing.assert_array_equal(a.step(*cohort), b.step(*cohort))
    a.drain(), b.drain()
    for x, y in zip(a.touched(), b.touched()):
        np.testing.assert_array_equal(x, y)
    assert a.log == b.log and len(a.log) > 0
    assert a.tally == b.tally and a.tally["s_shared"] > 0
    assert a.total_balance() == b.total_balance()
    assert ref.STAT_NAMES == oracle.SB_STAT_NAMES
    assert ref.LOCK_SETS == oracle.SB_LOCK_SETS


# ------------------------------------------------ verify notices a doctoring


def _bump(x, index):
    return x.at[index].add(1)


def _newest_entry(db) -> tuple:
    """(flat slot, row) of an entry of the newest install step: a row is
    written once a step, so that entry is the newest of its row."""
    packed, heads = np.asarray(db.log.entries), np.asarray(db.log.head)
    cap = db.log.capacity
    written = np.concatenate([lane * cap + np.arange(min(h, cap))
                              for lane, h in enumerate(heads)])
    slot = int(written[np.argmax(packed[written, 3])])
    first = packed[slot]
    return slot, int(first[0] >> 8) * db.n_accounts + int(first[2])


def _doctor_ring_entry(final):
    """The balance word of replica 1's copy of one entry."""
    db, tail, counters = final
    ew = db.log.entries.shape[1] // 3
    entries = _bump(db.log.entries,
                    (_newest_entry(db)[0], ew + ck.HDR_WORDS))
    return db.replace(log=db.log.replace(entries=entries)), tail, counters


def _doctor_live_balance(final):
    """The live balance of the row that entry names."""
    db, tail, counters = final
    return (db.replace(bal=_bump(db.bal, _newest_entry(db)[1])), tail,
            counters)


def _doctor_stat(final):
    """One more commit in the drain's stats row."""
    db, tail, counters = final
    return db, _bump(tail, (0, ref.STAT_NAMES.index("committed"))), counters


READ_BACK = [f"warmup.acked_writes_read_back_from_replica_{r}"
             for r in range(3)]
DOCTORED = {
    "undoctored": (lambda final: final, []),
    "ring_entry": (_doctor_ring_entry,
                   ["warmup.log_replicas_identical", READ_BACK[1]]),
    "live_balance": (_doctor_live_balance,
                     ["warmup.balance_conserved",
                      "warmup.touched_rows_equal_reference", *READ_BACK]),
    "stat": (_doctor_stat, ["warmup.stats_equal_reference"]),
}


def _verified_warmup(doctor) -> ck.Checks:
    """Two dispatches of the rehearsal's size, drained, doctored,
    verified as the warm-up."""
    traffic = bench_run.load_json(REPO, "benchmarks", "traffic", "sat.json")
    params = {**traffic, **traffic["rehearse"]}["params"]
    dep = dep_mod.build(_config(), params, 5, jax.devices()[:1],
                        lambda **kw: None, True)
    keys = bench_run.KeySchedule(5, 4)
    res = closed_block.run(dep, dep.start(), keys.__getitem__, 3600.0, 2,
                           lambda: None)
    totals = {n: int(v) for n, v in zip(dep.stat_names, res["totals"])}
    checks = ck.Checks(lambda **kw: None)
    dep.verify(doctor(res["final"]), checks, "warmup", totals,
               2 * dep.txns_per_dispatch)
    return checks


@pytest.mark.parametrize("case", DOCTORED)
def test_verify_fails_on_what_was_doctored_and_on_nothing_else(case):
    doctor, must_fail = DOCTORED[case]
    checks = _verified_warmup(doctor)
    assert checks.n >= 15
    assert sorted(checks.failed) == sorted(must_fail)


# ------------------------------------------- the traffic's shape is stated


def test_the_configuration_states_the_sources_shape():
    shape = _config()["traffic_shape"]
    assert shape["mix"] == [15, 15, 15, 25, 15, 15]
    assert [getattr(ref, n.upper()) for n in shape["mix_order"]] \
        == list(range(6))
    assert (shape["hot_frac"], shape["hot_prob"]) == (0.04, 0.9)
    assert int(_config()["sizes"]["n_accounts"] * shape["hot_frac"]) \
        == 960_000
    assert dep_mod.shape_args(_config()) == {
        "mix": (15, 15, 15, 25, 15, 15), "hot_frac": 0.04, "hot_prob": 0.9}


SHAPES = {      # drawn with -> (stated, holds)
    "as_stated": ({}, True),
    "mix_half_a_point_off": ({"mix": [15.5, 15, 15, 24.5, 15, 15]}, False),
    "hot_prob_88": ({"hot_prob": 0.88}, False),
    "hot_frac_5": ({"hot_frac": 0.05}, False),
}


@pytest.mark.parametrize("case", SHAPES)
def test_the_tally_holds_the_warmups_size_to_the_stated_shape(case):
    """262,144 transactions (the warm-up's 2 x 16 x 8,192) drawn from a
    shape, tallied against the configuration's."""
    other, holds = SHAPES[case]
    stated = _config()["traffic_shape"]
    drawn = {**stated, **other}
    n_accounts, n = 24_000_000, 262_144
    rng = np.random.default_rng(11)
    mix = np.asarray(drawn["mix"], float)
    ttype = rng.choice(6, n, p=mix / mix.sum())

    def draw():
        hot_n = int(n_accounts * drawn["hot_frac"])
        return np.where(rng.random(n) < drawn["hot_prob"],
                        rng.integers(0, hot_n, n),
                        rng.integers(0, n_accounts, n))

    tally = dep_mod.TrafficTally(n_accounts, stated)
    for piece in np.split(np.arange(n), 32):
        tally.add(ttype[piece], draw()[piece], draw()[piece])
    res = tally.result()
    assert res["txns"] == n and res["ok"] == holds


def test_verify_fails_when_the_program_draws_another_shape_than_stated(
        monkeypatch):
    """The program and the reference's cohorts both draw the doctored
    shape, so every comparison holds; the stated shape does not."""
    monkeypatch.setattr(dep_mod, "shape_args", lambda config: dict(
        mix=(40, 12, 12, 12, 12, 12), hot_frac=0.04, hot_prob=0.9))
    checks = _verified_warmup(lambda final: final)
    assert checks.failed == ["warmup.traffic_as_configured"]
