"""The `store24m` configuration's own files: its entries sit where this PR
appended them, the cell is held to the manifest and to its traffic file,
the bytes model gives hand-worked numbers at the cell's width, the
deployment's ``verify`` notices a torn value, a lost update, a version
off by one, a missing key and a wrong checksum (each by the checks that
should, and by no other), the traffic tally holds the warm-up's size to
YCSB's law, and the recorded chip trace reduces to its known numbers."""
import json
import os

import jax
import numpy as np
import pytest

from benchmarks import bytes_model, bytes_model_store as bms
from benchmarks import checks as ck
from benchmarks import part_times as pt
from benchmarks import run as bench_run
from benchmarks import trace_reduce as tr
from benchmarks.deployments import store_kv_one_chip as dep_mod
from benchmarks.loops import closed_block
from benchmarks.references import store as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures", "store24m")
CELL = "store-ycsb-b"
VARIANTS = ("step_ms.kv", "device_idle_share.kv",
            "contention_abort_share.kv", "monitor_ms.kv", "unnamed_ms.kv")
OWN_READERS = ("dispatch_gap_ms.kv", "probe_ms.kv", "install_ms.kv",
               "key_sort_ms.kv", "dup_key_share.kv",
               "hbm_roofline_share.kv")


def _manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _config() -> dict:
    return bench_run.load_json(REPO, "benchmarks", "configs",
                               "store24m.json")


def _traffic() -> dict:
    return bench_run.load_json(REPO, "benchmarks", "traffic", "ycsb-b.json")


# ------------------------------------------------------------ the manifest


def test_the_entries_are_where_this_pr_appended_them():
    m = _manifest()
    assert m["configs"][3]["name"] == "store24m"
    assert m["configs"][3]["reduced"] == [] == _config()["reduced"]
    assert m["configs"][3]["source"] == _config()["source"]
    assert len(m["configs"][3]["source"]) <= 200
    assert m["workloads"][4] == {
        "name": CELL, "config": "store24m", "traffic": "ycsb-b", "chips": 1,
        "why": m["workloads"][4]["why"]}
    for i, name in ((0, "committed_txn_per_s"), (1, "txn_latency_p50_ms")):
        assert m["end_to_end"][i]["name"] == name
        assert m["end_to_end"][i]["workloads"] == [
            "tatp7m-sat", "smallbank24m-sat", "tatp7m-x4-sat", CELL]
    # PR 37's twelve where PR 37 put them (its own test pins them to the
    # tail of the list, tests/conftest.py), then this PR's eleven
    assert [x["name"] for x in m["per_layer"][25:37]] == [
        q + ".x4" for q in (
            "step_ms", "device_idle_share", "contention_abort_share",
            "monitor_ms", "unnamed_ms", "replicate_ms",
            "collective_exposed_ms", "hbm_peak_gb", "dispatch_gap_ms",
            "bck_val_scatter_ms", "hbm_roofline_share",
            "ici_roofline_share")]
    assert all(x["workloads"] == ["tatp7m-x4-sat"]
               for x in m["per_layer"][25:37])
    assert m["per_layer"][24]["name"] == "logic_abort_share.sb"
    assert m["per_layer"][32]["source"] == "program_counter"
    assert m["workloads"][3]["name"] == "tatp7m-x4-sat"
    assert m["configs"][2]["name"] == "tatp7m-x4r3"
    mine = m["per_layer"][37:48]
    assert [x["name"] for x in mine] == [*VARIANTS, *OWN_READERS]
    assert not any(CELL in x["workloads"] for x in m["per_layer"][:37])
    for x in mine:
        assert x["workloads"] == [CELL]
        assert x["moves"] == "committed_txn_per_s"
    layers = {x["name"]: (x["layer"], x["source"], x["unit"]) for x in mine}
    assert layers["key_sort_ms.kv"] == ("kernels", "program_span", "ms")
    assert layers["hbm_roofline_share.kv"] == ("kernels", "device_trace",
                                               "%")
    assert layers["dup_key_share.kv"] == ("engine step", "program_counter",
                                          "%")
    assert layers["dispatch_gap_ms.kv"][0] == "dispatch"
    assert layers["monitor_ms.kv"][0] == "counter plane"
    assert layers["device_idle_share.kv"][0] == "device"
    for name in VARIANTS:       # by the variant rule: no file of their own
        assert bench_run.reader_path("layer_metrics", name) == os.path.join(
            REPO, "benchmarks", "layer_metrics",
            name.rsplit(".", 1)[0] + ".py")
    for name in OWN_READERS:
        assert bench_run.reader_path("layer_metrics", name) == os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py")


def test_the_configuration_states_the_sources_scale_and_claims_one_copy():
    c = _config()
    assert c["sizes"] == {"n_keys": 24_000_000, "val_words": 10, "slots": 4,
                          "n_buckets": 1 << 24, "populate_lanes": 65_536}
    assert c["architecture"] is None and c["chips"] == 1
    assert c["deployment"] == "store_kv_one_chip"
    text = " ".join(c["guarantees"])
    assert "no durability and no replica is claimed" in text
    assert "committed + not_exist == attempted" in text
    # the repo's sizing rule (clients/micro.make_store_table)
    assert c["sizes"]["n_buckets"] == 1 << int(np.ceil(np.log2(
        c["sizes"]["n_keys"] / 2)))
    for key in ("n_buckets", "populate_order", "traffic_source",
                "rank_is_key", "float32_generator",
                "update_writes_the_whole_record",
                "operation_as_transaction", "tiers_off"):
        assert c["assumed"][key]
    small = c["compare_small"]
    assert (small["n_keys"], small["w"], small["cohorts_per_block"],
            small["blocks"]) == (20_000, 256, 2, 4)


def test_the_traffic_file_is_ycsb_workload_b():
    t = _traffic()
    assert t["loop"] == "closed_block"
    assert t["params"] == {"w": 8192, "cohorts_per_block": 16, "read": 0.95,
                           "update": 0.05, "distribution": "zipfian",
                           "theta": 0.99}
    assert (t["warmup_dispatches"], t["trace_dispatches"]) == (2, 6)
    r = t["rehearse"]["params"]
    assert {k: r[k] for k in ("read", "update", "distribution", "theta")} \
        == {k: t["params"][k] for k in ("read", "update", "distribution",
                                        "theta")}
    assert dep_mod.runner_args(_config()["sizes"], t["params"]) == {
        "val_words": 10, "read_frac": 0.95, "theta": 0.99}
    with pytest.raises(ValueError, match="read \\+ update"):
        dep_mod.runner_args(_config()["sizes"], {**t["params"],
                                                 "update": 0.1})
    with pytest.raises(ValueError, match="no generator"):
        dep_mod.runner_args(_config()["sizes"], {**t["params"],
                                                 "distribution": "latest"})


def test_the_deployment_names_its_checks_for_the_route_they_read_by():
    assert dep_mod.GUARANTEE_CHECKS[:3] == (
        "acked_writes_read_back_from_engine_get",
        "acked_writes_read_back_from_table_rows",
        "acked_writes_read_back_from_every_live_entry")
    assert dep_mod.OUTCOMES == ("committed", "not_exist")
    assert dep_mod.FAULTS == ("spill", "retry", "magic_bad")
    assert dep_mod.CONTENTION == () and dep_mod.KVStore.depth == 1
    assert dep_mod.STAT_NAMES == ref.STAT_NAMES


def test_the_reference_imports_nothing_of_the_system():
    with open(os.path.join(REPO, "benchmarks", "references",
                           "store.py")) as f:
        text = f.read()
    assert "dint_tpu" not in text.split('"""', 2)[2]
    assert "import jax" not in text


# ---------------------------------------------------------- the yardstick


def test_step_bytes_by_hand_at_the_cells_width():
    # w = 8,192 operations: 7,782.4 GETs that hit, 409.6 updates a step
    b = bms.step_bytes(8192, 10, hit_gets=7782.4, updates=409.6)
    assert b["key_read"] == 8192 * 8 == 65_536
    assert b["get_read"] == pytest.approx(7782.4 * 44) == pytest.approx(
        342_425.6)
    assert b["update_write"] == pytest.approx(409.6 * 44)
    assert b["total"] == pytest.approx(425_984.0)
    # at 819 GB/s that is 0.52 us; over a 17 ms step: 0.0031 %
    share = bytes_model.roofline_share_pct(b["total"], 17e-3, "TPU v5 lite")
    assert share == pytest.approx(100 * 425_984 / 819e9 / 17e-3)
    assert 0.0030 < share < 0.0031


def test_the_readers_of_the_cells_own_metrics_by_hand():
    read = bench_run.load_reader("layer_metrics", "dup_key_share.kv")
    ctx = {"counters": {"store_dup_lanes": 2830 * 96},
           "totals": {"attempted": 8192 * 96}}
    assert read(ctx) == pytest.approx(100 * 2830 / 8192)
    assert read({"counters": {}, "totals": ctx["totals"]}) is None
    # off a trace (a rehearsal, or a program without the scopes): nothing
    for name in ("probe_ms.kv", "install_ms.kv", "dispatch_gap_ms.kv",
                 "hbm_roofline_share.kv"):
        assert bench_run.load_reader("layer_metrics", name)(
            {"trace": None}) is None
    red = {"window_s": 1.0, "devices": [{"scope_s": {
        "dint.store.probe": 0.32, "dint.store.install": 0.16}}]}
    ctx = {"trace": red, "steps": 32, "device": {"platform": "tpu"}}
    assert bench_run.load_reader("layer_metrics", "probe_ms.kv")(ctx) \
        == pytest.approx(10.0)
    assert bench_run.load_reader("layer_metrics", "install_ms.kv")(ctx) \
        == pytest.approx(5.0)
    red["devices"][0]["scope_s"].pop("dint.store.install")
    assert bench_run.load_reader("layer_metrics", "install_ms.kv")(ctx) \
        is None


# ------------------------------------------------- verify, on doctored state


def _entry_of(table, key: int) -> int:
    hit = np.nonzero(np.asarray(table.valid)
                     & (np.asarray(table.key_lo) == key))[0]
    assert len(hit) == 1
    return int(hit[0])


def _updated_key(table) -> int:
    return int(np.asarray(table.key_lo)[np.argmax(np.asarray(table.ver))])


def _fresh_key(table) -> int:
    ok = np.asarray(table.valid) & (np.asarray(table.ver) == 1)
    return int(np.asarray(table.key_lo)[np.nonzero(ok)[0][7]])


def _with(final, **arrays):
    table, tail, counters = final
    return table.replace(**arrays), tail, counters


def _torn_value(final, totals):
    """One word of the record of a key the warm-up updated."""
    t = final[0]
    e = _entry_of(t, _updated_key(t))
    return _with(final, val=t.val.at[e * t.val_words + 5].add(1)), totals


def _torn_fresh_value(final, totals):
    """One word of a record nothing has updated since the populate."""
    t = final[0]
    e = _entry_of(t, _fresh_key(t))
    return _with(final, val=t.val.at[e * t.val_words + 4].set(9)), totals


def _version_off_by_one(final, totals):
    t = final[0]
    e = _entry_of(t, _updated_key(t))
    return _with(final, ver=t.ver.at[e].add(1)), totals


def _lost_update(final, totals):
    """An updated key back as it was populated: the install never
    landed."""
    t = final[0]
    k = _updated_key(t)
    e = _entry_of(t, k)
    vw = t.val_words
    rec = np.array([k, ref.MAGIC] + [0] * (vw - 2), np.uint32)
    return _with(final, ver=t.ver.at[e].set(1),
                 val=t.val.at[e * vw:(e + 1) * vw].set(rec)), totals


def _misplaced_value(final, totals):
    """A fresh key's record under another fresh key's name."""
    t = final[0]
    e = _entry_of(t, _fresh_key(t))
    return _with(final, val=t.val.at[e * t.val_words].add(1)), totals


def _missing_key(final, totals):
    t = final[0]
    e = _entry_of(t, _fresh_key(t))
    return _with(final, valid=t.valid.at[e].set(False)), totals


def _wrong_checksum(final, totals):
    return final, {**totals, "val_sum": totals["val_sum"] + 1}


def _one_commit_more(final, totals):
    return final, {**totals, "committed": totals["committed"] + 1}


W = "warmup."
READ_BACK = [W + "acked_writes_read_back_from_" + r for r in dep_mod.ROUTES]
DOCTORED = {
    "undoctored": (lambda final, totals: (final, totals), []),
    "torn_value": (_torn_value,
                   [*READ_BACK, W + "touched_rows_equal_reference"]),
    "torn_fresh_value": (_torn_fresh_value, [READ_BACK[2]]),
    "misplaced_value": (_misplaced_value, [READ_BACK[2]]),
    "version_off_by_one": (_version_off_by_one,
                           [*READ_BACK, W + "touched_rows_equal_reference"]),
    "lost_update": (_lost_update,
                    [*READ_BACK, W + "touched_rows_equal_reference"]),
    "missing_key": (_missing_key, [W + "live_keys_unchanged"]),
    "wrong_checksum": (_wrong_checksum, [W + "stats_equal_reference"]),
    "one_commit_more": (_one_commit_more,
                        [W + "accounting_closes",
                         W + "monitor_reconciles_with_stats",
                         W + "stats_equal_reference"]),
}


def _verified_warmup(doctor, spilled: int = 0) -> ck.Checks:
    """Two dispatches of the rehearsal's size, drained, doctored,
    verified as the warm-up."""
    traffic = _traffic()
    params = {**traffic, **traffic["rehearse"]}["params"]
    dep = dep_mod.build(_config(), params, 5, jax.devices()[:1],
                        lambda **kw: None, True)
    dep.populate_spilled += spilled
    keys = bench_run.KeySchedule(5, 4)
    res = closed_block.run(dep, dep.start(), keys.__getitem__, 3600.0, 2,
                           lambda: None)
    totals = {n: int(v) for n, v in zip(dep.stat_names, res["totals"])}
    final, totals = doctor(res["final"], totals)
    checks = ck.Checks(lambda **kw: None)
    dep.verify(final, checks, "warmup", totals, 2 * dep.txns_per_dispatch)
    return checks


@pytest.mark.parametrize("case", DOCTORED)
def test_verify_fails_on_what_was_doctored_and_on_nothing_else(case):
    doctor, must_fail = DOCTORED[case]
    checks = _verified_warmup(doctor)
    # check_accounting's seven, then the deployment's own
    assert checks.n == 7 + len(dep_mod.GUARANTEE_CHECKS) + len(
        dep_mod.WARMUP_CHECKS)
    assert sorted(checks.failed) == sorted(must_fail)


def test_verify_fails_when_the_populate_spilled_a_key():
    checks = _verified_warmup(lambda final, totals: (final, totals),
                              spilled=1)
    assert checks.failed == ["warmup.populate_spilled_nothing"]


def test_the_warmup_only_checks_are_made_once():
    """``GUARANTEE_CHECKS`` names what both phases make; the comparison
    with the reference rides in the warm-up, which starts from the
    populated state."""
    traffic = _traffic()
    params = {**traffic, **traffic["rehearse"]}["params"]
    dep = dep_mod.build(_config(), params, 6, jax.devices()[:1],
                        lambda **kw: None, True)
    keys = bench_run.KeySchedule(6, 8)
    names = []
    carry = dep.start()
    for tag, first in (("warmup", 0), ("window", 2)):
        res = closed_block.run(dep, carry, lambda i: keys[first + i], 3600.0,
                               2, lambda: None)
        totals = {n: int(v) for n, v in zip(dep.stat_names, res["totals"])}
        checks = ck.Checks(lambda **kw: names.append(kw["check"]))
        dep.verify(res["final"], checks, tag, totals,
                   2 * dep.txns_per_dispatch)
        assert checks.ok, checks.failed
        carry = dep.restart(res["final"])
    for name in dep_mod.GUARANTEE_CHECKS:
        assert {f"warmup.{name}", f"window.{name}"} <= set(names)
    for name in dep_mod.WARMUP_CHECKS:
        assert f"warmup.{name}" in names and f"window.{name}" not in names
    assert sum("acked_writes_read_back_from" in n for n in names) == 6


def test_verify_fails_when_the_program_draws_another_mix_than_stated(
        monkeypatch):
    """Program and regenerated batches both draw 80 % reads, so every
    comparison holds; the traffic file's 95 % does not."""
    real = dep_mod.runner_args
    monkeypatch.setattr(dep_mod, "runner_args", lambda sizes, params: {
        **real(sizes, params), "read_frac": 0.80})
    checks = _verified_warmup(lambda final, totals: (final, totals))
    assert checks.failed == ["warmup.traffic_as_configured"]


# ------------------------------------------------- the traffic's law


def _ycsb_draws(rng, n_keys, theta, size):
    """YCSB's ZipfianGenerator in float64 numpy, ranks in [1, n]."""
    law = dep_mod.ZipfLaw(n_keys, theta)
    u = rng.random(size)
    alpha = 1.0 / (1.0 - theta)
    tail = 1 + np.floor(n_keys * (law.eta * u - law.eta + 1) ** alpha)
    return np.where(u * law.zetan < 1, 1, np.where(
        u * law.zetan < law.zeta2, 2, tail)).astype(np.int64)


def test_the_law_is_ycsbs_at_the_cells_size():
    law = dep_mod.ZipfLaw(24_000_000, 0.99)
    assert law.zetan == pytest.approx(19.10, abs=0.01)
    assert float(law.cdf(1)) == pytest.approx(0.05236, abs=2e-5)
    assert float(law.cdf(2) - law.cdf(1)) == pytest.approx(0.02636,
                                                           abs=2e-5)
    assert float(law.cdf(10) - law.cdf(2)) == pytest.approx(0.0844,
                                                            abs=2e-4)
    assert float(law.cdf(240_000)) == pytest.approx(0.7243, abs=2e-4)
    assert float(law.cdf(24_000_000)) == pytest.approx(1.0)
    mean, var = law.dup_lanes(8192)
    assert 2700 < mean < 2950 and 0 < var < 8192


DRAWN = {      # drawn with -> holds against (read 0.95, theta 0.99)
    "as_stated": ({}, True),
    "read_94": ({"read": 0.94}, False),
    "theta_095": ({"theta": 0.95}, False),
    "uniform_keys": ({"theta": None}, False),
}


@pytest.mark.parametrize("case", DRAWN)
def test_the_tally_holds_the_warmups_size_to_the_traffic_file(case):
    """262,144 lanes (the warm-up's 2 x 16 x 8,192) drawn in numpy from
    YCSB's generator, tallied against the traffic file."""
    other, holds = DRAWN[case]
    drawn = {"read": 0.95, "theta": 0.99, **other}
    n_keys, w, steps = 24_000_000, 8192, 32
    rng = np.random.default_rng(13)
    tally = dep_mod.TrafficTally(n_keys, w, 0.95, 0.99)
    dup = 0
    for _ in range(2):
        keys = (_ycsb_draws(rng, n_keys, drawn["theta"], (16, w))
                if drawn["theta"] else rng.integers(1, n_keys + 1, (16, w)))
        ops = np.where(rng.random((16, w)) < drawn["read"], ref.GET,
                       ref.SET)
        tally.add(ops, keys)
        dup += sum(w - len(np.unique(k)) for k in keys)
    res = tally.result(dup)
    assert res["lanes"] == w * steps and res["ok"] == holds
    if case == "as_stated":
        assert abs(res["dup_lanes_a_step"] - res["dup_lanes_stated"]) \
            < res["dup_band"] < 0.05 * res["dup_lanes_stated"]


def test_a_key_outside_the_key_space_fails_the_tally():
    tally = dep_mod.TrafficTally(1000, 64, 0.95, 0.99)
    rng = np.random.default_rng(3)
    keys = _ycsb_draws(rng, 1000, 0.99, (4, 64))
    keys[2, 5] = 1001
    tally.add(np.full((4, 64), ref.GET), keys)
    assert tally.result(0)["outside_key_space"] == 1
    assert not tally.result(0)["ok"]


# ------------------------------------------------- the recorded chip trace

PROBE, INSTALL = "dint.store.probe", "dint.store.install"


def _fixture(suffix: str) -> dict:
    path = os.path.join(FIXTURES, f"{CELL}.v5e.{suffix}")
    assert os.path.isfile(path), f"no recorded {path}"
    with open(path) as f:
        return json.load(f)


def test_the_recorded_trace_reduces_to_its_known_numbers():
    """One block program (16 steps) of the builder's traced run of PR 39
    on a TPU v5 lite: 18.4 ms a step, the install 10.1 of them."""
    fx = _fixture("trace.json")
    red, want = tr.reduce(fx["trace"]), fx["expected"]
    tr.require_device_work(red, want["n_devices"])
    assert want["n_devices"] == 1 == want["block_programs"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    scope_s = red["devices"][0]["scope_s"]
    for scope, seconds in want["scope_s"].items():
        assert scope_s[scope] == pytest.approx(seconds, rel=1e-9)
    # the store has two waves (the scan ones are off); the sorts, the
    # slot allocation and the replies lie outside them, in parts
    assert set(scope_s) == {PROBE, INSTALL}
    assert 0.75 * red["busy_s"] < sum(scope_s.values()) <= red["busy_s"]
    assert max(scope_s, key=scope_s.get) == INSTALL
    assert red["busy_s"] * 1e3 / 16 == pytest.approx(18.4207, abs=1e-3)
    assert scope_s[INSTALL] * 1e3 / 16 == pytest.approx(10.0874, abs=1e-3)
    assert scope_s[PROBE] * 1e3 / 16 == pytest.approx(4.8458, abs=1e-3)
    # cut to one block program: the dispatch gap has nothing to read
    gap = bench_run.load_reader("layer_metrics", "dispatch_gap_ms.kv")
    assert gap({"trace": red}) is None


def test_the_recorded_trace_gives_its_known_parts():
    fx = _fixture("parts.json")
    assert fx["steps"] == 16
    got, want = pt.per_step(pt.fixture_ops(fx), fx["steps"]), fx["expected"]
    assert got["unnamed"] == pytest.approx(want["unnamed"], rel=1e-9)
    assert got["parts"] == pytest.approx(want["parts"], rel=1e-9)
    step = sum(v for row in got["by_wave"].values() for v in row.values())
    assert step == pytest.approx(18.4207, abs=2e-3)
    assert set(got["by_wave"]) == {pt.NO_WAVE, PROBE, INSTALL}
    # every op under a wave carries one of its two parts
    assert set(got["by_wave"][PROBE]) == {"probe_keys", "probe_val"}
    assert set(got["by_wave"][INSTALL]) == {"kv_val_scatter",
                                            "kv_meta_scatter"}
    assert set(got["by_wave"][pt.NO_WAVE]) == {
        pt.UNNAMED, "block_pre", "store_gen", "key_sort", "slot_alloc",
        "reply_build", "stats", "monitor"}
    parts = got["parts"]
    # the value scatter is the heaviest part: 81,920 single words a step,
    # 95 % of them dropped lanes, at ~88 ns a word
    assert max(parts, key=parts.get) == "kv_val_scatter"
    assert parts["kv_val_scatter"] == pytest.approx(7.2003, abs=1e-3)
    assert parts["kv_meta_scatter"] == pytest.approx(2.8872, abs=1e-3)
    assert parts["probe_keys"] == pytest.approx(3.1273, abs=1e-3)
    assert parts["probe_val"] == pytest.approx(1.7185, abs=1e-3)
    assert parts["slot_alloc"] == pytest.approx(1.8499, abs=1e-3)
    assert parts["key_sort"] == pytest.approx(1.2850, abs=1e-3)
    assert parts["reply_build"] == pytest.approx(0.2760, abs=1e-3)
    assert got["unnamed"] == pytest.approx(0.0385, abs=1e-3)
    assert parts["monitor"] < 0.02 and parts["store_gen"] < 0.02
    # what XLA puts in on its own is next to nothing here: no whole-table
    # copy, the tables are updated in place
    assert got["unnamed"] < 0.01 * step
    ctx = {"parts": got}
    assert bench_run.load_reader("layer_metrics", "key_sort_ms.kv")(ctx) \
        == parts["key_sort"]
    assert bench_run.load_reader("layer_metrics", "monitor_ms.kv")(ctx) \
        == parts["monitor"]
    assert bench_run.load_reader("layer_metrics", "unnamed_ms.kv")(ctx) \
        == got["unnamed"]
