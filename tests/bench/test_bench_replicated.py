"""The `tatp7m-x4r3` configuration's own files: the manifest's new entries
by absolute position, the comparison that runs the four-device program
(it passes on several seeds, and three doctored programs each fail
exactly the checks they must), the benchmark's replication reference and
the program's copy of it agree, the two roofline byte counts by hand at
the cell's width, ``verify`` notices one doctored ring entry and one
doctored backup row, and the recorded chip trace reduces to the metrics
of the line it was cut from."""
import glob
import json
import os

import jax
import numpy as np
import pytest

from benchmarks import bytes_model, bytes_model_ici
from benchmarks import checks as ck
from benchmarks import part_times as pt
from benchmarks import run as bench_run
from benchmarks import trace_reduce as tr
from benchmarks.deployments import tatp_dense_replicated as dep_mod
from benchmarks.loops import closed_block
from benchmarks.references import replication as ref
from dint_tpu.parallel import dense_sharded as ds
from dint_tpu.testing import replication as own

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures", "tatp7m-x4r3")
CELL, CONFIG = "tatp7m-x4-sat", "tatp7m-x4r3"
REPLICATE = "dint.dense_sharded.replicate"
REPL_PARTS = ("repl_hop", "bck_meta_scatter", "bck_val_scatter",
              "bck_log_append")


def _manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _config() -> dict:
    with open(os.path.join(REPO, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------ the manifest


BY_VARIANT = ("step_ms", "device_idle_share", "contention_abort_share",
              "monitor_ms", "unnamed_ms")
OWN_READERS = ("replicate_ms", "collective_exposed_ms", "hbm_peak_gb",
               "dispatch_gap_ms", "bck_val_scatter_ms",
               "hbm_roofline_share", "ici_roofline_share")


def test_the_cell_and_its_twelve_metrics_are_at_the_end_of_the_manifest():
    manifest = _manifest()
    assert manifest["workloads"][3] == {
        "name": CELL, "config": CONFIG, "traffic": "sat", "chips": 4,
        "why": manifest["workloads"][3]["why"]}
    config = manifest["configs"][2]
    assert (config["name"], config["reduced"]) == (CONFIG, [])
    assert config["source"] == _config()["source"]
    assert _config()["reduced"] == [] and _config()["chips"] == 4
    for name in ("committed_txn_per_s", "txn_latency_p50_ms"):
        e2e = next(m for m in manifest["end_to_end"] if m["name"] == name)
        assert e2e["workloads"] == ["tatp7m-sat", "smallbank24m-sat", CELL]
    per_layer = manifest["per_layer"]
    assert [m["name"] for m in per_layer[25:]] == [
        q + ".x4" for q in (*BY_VARIANT, *OWN_READERS)]
    # the older 25 where PR 33 left them, none naming this cell
    assert per_layer[24]["name"] == "logic_abort_share.sb"
    assert [m["name"] for m in per_layer[15:25]][:3] == [
        "step_ms.sb", "device_idle_share.sb", "contention_abort_share.sb"]
    assert not any(CELL in m["workloads"] for m in per_layer[:25])
    for m in per_layer[25:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "committed_txn_per_s"
    assert per_layer[32]["source"] == "program_counter"    # hbm_peak_gb.x4
    layers = bench_run.reader_path
    for q in BY_VARIANT:        # by the variant rule, no file of its own
        assert layers("layer_metrics", q + ".x4") == os.path.join(
            REPO, "benchmarks", "layer_metrics", q + ".py")
    for q in OWN_READERS:
        assert layers("layer_metrics", q + ".x4") == os.path.join(
            REPO, "benchmarks", "layer_metrics", q + ".x4.py")


def test_the_configuration_differs_from_tatp7m_in_nothing_of_its_shape():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "tatp7m.json")) as f:
        one = json.load(f)
    mine = _config()
    assert mine["sizes"] == one["sizes"]
    assert mine["rehearse"] == one["rehearse"]
    assert mine["deployment"] == "tatp_dense_replicated"
    assert "use_pallas" not in mine["engine"]
    # every guarantee of the one-chip deployment, the log's made stronger
    assert len(mine["guarantees"]) == len(one["guarantees"]) == 4
    assert mine["guarantees"][0] == one["guarantees"][0]
    assert mine["guarantees"][2:] == one["guarantees"][2:]
    assert "two backup devices" in mine["guarantees"][1]
    small = mine["compare_small"]
    assert {k: small[k] for k in one["compare_small"]} \
        == one["compare_small"]
    # rings that cannot wrap: 8 steps of at most 2w writes, three streams
    assert small["log_lanes"] * small["log_capacity"] \
        >= 3 * (small["blocks"] * small["cohorts_per_block"] + 2) \
        * 2 * small["w"]


# --------------------------------- the comparison runs the sharded program


def _compared(seed: int) -> tuple:
    made = {}
    checks = ck.Checks(lambda **kw: made.setdefault(kw["check"], kw))
    dep_mod.compare_small(_config(), seed, checks)
    return checks, made


@pytest.mark.parametrize("seed", [5, 2147484029, 3000000017, 3700000101])
def test_the_four_device_program_equals_independent_code(seed,
                                                         monkeypatch):
    built = []
    real = ds.build_sharded_pipelined_runner
    monkeypatch.setattr(ds, "build_sharded_pipelined_runner",
                        lambda *a, **kw: built.append(a) or real(*a, **kw))
    checks, made = _compared(seed)
    assert checks.failed == [] and set(made) == set(dep_mod.COMPARE_CHECKS)
    # the mesh program, on four devices, at the stated small size
    (mesh, n, n_sub), = built
    assert (mesh.size, n, n_sub) == (4, 4, 20000)
    stats = made["compare.sharded_stats_equal_generic_engine"]
    assert stats["sharded"][0] == 4 * 256 * 2 * 4       # all attempted
    assert stats["sharded"][1] > 0 and stats["moved"] <= 1
    streams = made["compare.three_log_streams_equal_reference"]
    assert not streams["wrapped"] and min(streams["entries"]) > 100


def _drop_backup_install(real):
    def apply(state, inst, slot, *rest):
        out = real(state, inst, slot, *rest)
        return out.replace(bck_val=state.bck_val, bck_meta=state.bck_meta) \
            if slot == 1 else out
    return {"_apply_backup": apply}


def _drop_forwarded_append(real):
    def apply(state, inst, slot, *rest):
        out = real(state, inst, slot, *rest)
        return out.replace(db=state.db) if slot == 0 else out
    return {"_apply_backup": apply}


def _both_hops_by_one(real):
    perm = ds.ring_perm
    return {"ring_perm": lambda n, off: perm(n, 1)}


DOCTORED = {
    "a_backup_install_dropped": (_drop_backup_install, [
        "compare.backups_equal_reference"]),
    "a_forwarded_log_append_dropped": (_drop_forwarded_append, [
        "compare.three_log_streams_equal_reference",
        "compare.lost_device_recovered_from_stream_1"]),
    "both_hops_permuted_by_one": (_both_hops_by_one, [
        "compare.backups_equal_reference",
        "compare.three_log_streams_equal_reference",
        "compare.lost_device_recovered_from_stream_2"]),
}


@pytest.mark.parametrize("case", DOCTORED)
def test_a_doctored_program_fails_exactly_the_checks_it_must(case,
                                                             monkeypatch):
    doctor, must_fail = DOCTORED[case]
    ds.build_sharded_pipelined_runner.cache.clear()    # not in its key
    for name, fn in doctor(ds._apply_backup).items():
        monkeypatch.setattr(ds, name, fn)
    try:
        checks, _ = _compared(7)
    finally:
        ds.build_sharded_pipelined_runner.cache.clear()
    assert sorted(checks.failed) == sorted(must_fail)


# ------------------------------------------------------ the reference, twice


def test_the_two_copies_of_the_reference_agree():
    for n in (3, 4, 8):
        assert ref.placement(n) == own.placement(n)
        assert all(ref.carried(n, r) == own.carried(n, r)
                   for r in range(n))
    for copy in (ref, own):
        with pytest.raises(ValueError):
            copy.placement(2)
    assert ref.placement(4)[3] == {"backups": [(0, 0), (1, 1)],
                                   "streams": [(3, 0), (0, 4), (1, 4)]}
    assert ref.carried(4, 0) == [(0, 0), (3, 4), (2, 3)]
    rng = np.random.default_rng(3)
    table_rows, words = (7, 7, 28), 3
    meta = rng.integers(0, 8, sum(table_rows) + 1).astype(np.uint32)
    val = rng.integers(0, 99, (len(meta), words)).astype(np.uint32)
    stream, version = [], np.ones(len(meta), np.int64)
    base = np.cumsum([0, *table_rows[:-1]])
    for _ in range(200):            # rows written again and again
        table = int(rng.integers(0, 3))
        key = int(rng.integers(0, table_rows[table]))
        version[base[table] + key] += 1
        stream.append((table, key, int(rng.integers(0, 4) == 0),
                       int(version[base[table] + key]),
                       rng.integers(0, 99, words).astype(np.uint32)))
    for tag in (0, 3):
        a, b = (copy.replay(meta, val, table_rows, stream, tag)
                for copy in (ref, own))
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        assert (a[2][:, 1] == tag).all() and len(a[2]) == 200
    assert not np.array_equal(a[0], meta)       # and the inputs are kept
    with pytest.raises(ValueError):
        ref.replay(meta, val, table_rows, [(0, 7, 0, 2, val[0])], 0)
    with pytest.raises(ValueError):
        own.replay(meta, val, table_rows, [(0, 7, 0, 2, val[0])], 0)
    empty = own.replay(meta, val, table_rows, [], 0)
    np.testing.assert_array_equal(empty[0], meta)
    assert empty[2].shape == ref.replay(meta, val, table_rows, [],
                                        0)[2].shape == (0, 4 + words)


# -------------------------------------------------------- the byte counts


def test_the_hbm_bytes_of_a_replicated_step_by_hand():
    """w = 8192, K = 7 lanes, 10 value words, one log entry a local
    append, 1,500 installs and 1,800 lock requests a step and device, two
    forwarded records: gathers 458,752 + 229,376 B, locks 65,536 + 14,400
    + 65,536 B, install 1,500 x 44 B, log 1,500 x 56 B; a hop's record is
    16,384 lanes x 65 B = 1,064,960 B, read where it is sent and written
    where it arrives, and a backup applies 1,500 x (44 + 56) B of it."""
    b = bytes_model.step_bytes(8192, 7, 10, 1, installs=1500,
                               lock_requests=1800, n_backups=2)
    assert b["meta_gather"] == 458752 and b["magic_gather"] == 229376
    assert b["lock"] == 65536 + 14400 + 65536
    assert (b["install"], b["log_append"]) == (66000, 84000)
    assert b["replicate"] == 2 * (2 * 1064960 + 150000) == 4559840
    assert b["total"] == 5543440
    read = bench_run.load_reader("layer_metrics", "hbm_roofline_share.x4")
    ctx = {"trace": {"devices": [{}], "window_s": 1.0, "busy_s": 0.32},
           "geometry": {"w": 8192, "k": 7, "val_words": 10,
                        "log_replicas": 1, "n_backups": 2},
           "counters": {"install_writes": 1500 * 32,
                        "lock_requests": 1800 * 32},
           "steps": 8, "n_devices": 4, "device": {"kind": "TPU v5e"}}
    # 5,543,440 B at 819 GB/s is 6.7685 us of a 40 ms step
    assert read(ctx) == pytest.approx(100 * 5543440 / 819e9 / 0.04)
    assert read({**ctx, "trace": None}) is None


def test_the_ici_bytes_and_the_time_in_flight_by_hand():
    assert bytes_model.HOP_LANE_FIXED == 25     # a mask byte + six words
    assert bytes_model_ici.hop_bytes(8192, 10) == 16384 * 65 == 1064960
    assert bytes_model_ici.step_bytes(8192, 10) == 2129920
    # two transfers in flight together (0-125 and 5-155 ns), an op that
    # runs meanwhile, then a synchronous permute (500-540): 155 + 40
    ops = [["collective-permute-start.1", None, 0, 10],
           ["collective-permute-start.2", None, 5, 10],
           ["fusion.3", None, 20, 100],
           ["collective-permute-done.1", None, 120, 5],
           ["collective-permute-done.2", None, 125, 30],
           ["collective-permute.9", None, 500, 40],
           ["all-reduce.4", None, 600, 50]]
    assert bytes_model_ici.in_flight_ns(ops) == 195.0
    assert bytes_model_ici.in_flight_ns(ops[2:3]) == 0.0
    # the ops' own durations (95 ns) would read twice the share
    assert sum(o[3] for o in ops if "permute" in o[0]) == 95
    # 2,129,920 B at 200 GB/s is 10.65 us; in flight 100 us a step
    assert bytes_model_ici.roofline_share_pct(
        2129920, 100e-6, "TPU v5 lite") == pytest.approx(10.6496)
    read = bench_run.load_reader("layer_metrics", "ici_roofline_share.x4")
    assert read({"trace": None}) is None        # a run that was not traced


# ------------------------------------------------ verify notices a doctoring


def _newest_forwarded_entry(state, ring: int, tag: int) -> tuple:
    """(slot in the ring's flat entries, the entry) of the stream's entry
    with the highest version: the newest of its row."""
    entries = np.asarray(state.db.log.entries)[ring]
    mine = np.nonzero((entries[:, 1] == tag) & (entries[:, 3] > 0))[0]
    slot = int(mine[np.argmax(entries[mine, 3])])
    return slot, entries[slot]


def _doctor_ring_entry(dep, final):
    """A value word of ring 2's copy of device 1's newest entry."""
    state, tail, counters = final
    slot, _ = _newest_forwarded_entry(state, 2, 2)
    entries = state.db.log.entries.at[2, slot, ck.HDR_WORDS].add(1)
    log = state.db.log.replace(entries=entries)
    return state.replace(db=state.db.replace(log=log)), tail, counters


def _doctor_backup_row(dep, final):
    """A value word of the row that entry names, in device 1's first
    backup (slot 0 of device 2)."""
    state, tail, counters = final
    _, entry = _newest_forwarded_entry(state, 2, 2)
    row = int(ck.table_bases(ck.tatp_table_rows(dep.n_loc))[entry[0] >> 8]
              + entry[2])
    return (state.replace(bck_val=state.bck_val.at[2, row * dep.vw].add(1)),
            tail, counters)


DOCTORED_STATE = {
    "undoctored": (lambda dep, final: final, []),
    "ring_entry": (_doctor_ring_entry, [
        "warmup.device_1_acked_writes_read_back_from_ring_2",
        "warmup.stream_1_identical_in_three_rings"]),
    "backup_row": (_doctor_backup_row, [
        "warmup.backup_1_of_device_1_equals_primary"]),
}


@pytest.mark.parametrize("case", DOCTORED_STATE)
def test_verify_fails_on_what_was_doctored_and_on_nothing_else(case):
    doctor, must_fail = DOCTORED_STATE[case]
    traffic = bench_run.load_json(REPO, "benchmarks", "traffic", "sat.json")
    params = {**traffic, **traffic["rehearse"]}["params"]
    dep = dep_mod.build(_config(), params, 5, jax.devices()[:4],
                        lambda **kw: None, True)
    keys = bench_run.KeySchedule(5, 4)
    res = closed_block.run(dep, dep.start(), keys.__getitem__, 3600.0, 2,
                           lambda: None)
    totals = {n: int(v) for n, v in zip(dep.stat_names, res["totals"])}
    made = []
    checks = ck.Checks(lambda **kw: made.append(kw["check"]))
    dep.verify(doctor(dep, res["final"]), checks, "warmup", totals,
               2 * dep.txns_per_dispatch)
    assert sorted(checks.failed) == sorted(must_fail)
    # every guarantee has its named check: 12 read-backs on three
    # devices each, 8 backups, 4 streams, and the ten of the stats
    assert {"warmup." + n for n in dep_mod.GUARANTEE_CHECKS} <= set(made)
    assert len(dep_mod.GUARANTEE_CHECKS) == 3 + 2 + 12 + 8 + 4
    assert sum("acked_writes_read_back_from_ring" in n for n in made) == 12


def test_a_deployment_on_other_than_four_devices_is_refused():
    with pytest.raises(SystemExit, match="4 devices"):
        dep_mod.build(_config(), {"w": 8, "cohorts_per_block": 1}, 1,
                      jax.devices()[:2], lambda **kw: None, True)


# ------------------------------------------------- the recorded chip trace


def _fixture(suffix: str) -> dict:
    found = glob.glob(os.path.join(FIXTURES, "*." + suffix))
    assert len(found) == 1, f"one recorded *.{suffix} under {FIXTURES}"
    with open(found[0]) as f:
        return json.load(f)


def test_the_recorded_trace_reduces_to_the_metrics_of_its_line():
    fx = _fixture("trace.json")
    red, want = tr.reduce(fx["trace"]), fx["expected"]
    tr.require_device_work(red, 4)
    assert want["n_devices"] == 4
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    for dev in red["devices"]:
        # replication is the work of this cell, on every device
        assert max(dev["scope_s"], key=dev["scope_s"].get) == REPLICATE
        assert dev["collective_s"] > 0
    for scope, seconds in want["scope_s"].items():
        assert red["devices"][0]["scope_s"][scope] \
            == pytest.approx(seconds, rel=1e-9)
    # the readers, on the one block program (16 steps) the cut holds
    ctx = {"trace": red, "steps": want["steps"]}
    line = want["metrics"]
    for name in ("step_ms.x4", "device_idle_share.x4", "replicate_ms.x4",
                 "collective_exposed_ms.x4"):
        read = bench_run.load_reader("layer_metrics", name)
        assert read(ctx) == pytest.approx(line[name], rel=1e-9), name
    assert line["replicate_ms.x4"] > 0.5 * line["step_ms.x4"]
    # the interconnect's share from the same ops: under 100 %
    flights = [bytes_model_ici.in_flight_ns(d["ops"]) / 1e9
               for d in fx["trace"]["devices"]]
    assert all(f > 0 for f in flights)
    share = bytes_model_ici.roofline_share_pct(
        want["steps"] * bytes_model_ici.step_bytes(8192, 10),
        sum(flights) / 4, "TPU v5 lite")
    assert share == pytest.approx(line["ici_roofline_share.x4"], rel=1e-9)
    assert 0 < share < 100


def test_the_four_parts_sum_to_the_replicate_wave():
    fx = _fixture("parts.json")
    got = pt.per_step(pt.fixture_ops(fx), fx["steps"])
    want = fx["expected"]
    assert got["unnamed"] == pytest.approx(want["unnamed"], rel=1e-9)
    assert got["parts"] == pytest.approx(want["parts"], rel=1e-9)
    # every op under the wave lies under one of its four parts (a
    # backup's append books its ops to append_rep's own, inside
    # bck_log_append): booked by the OUTERMOST part, they are the wave
    by_part = dict.fromkeys(REPL_PARTS, 0.0)
    n = len(fx["devices"]) * fx["steps"] * 1e6
    for d in pt.fixture_ops(fx):
        for op, self_ns, _ in tr.self_times(d["ops"]):
            if pt.names_of(op[1])[0] != REPLICATE:
                continue
            # (XLA joins the names of ops it fused with ";")
            outer = {p for p in pt.PART.findall(op[1]) if p in REPL_PARTS}
            assert len(outer) == 1, op[1]
            by_part[outer.pop()] += self_ns / n
    wave = sum(got["by_wave"][REPLICATE].values())
    assert sum(by_part.values()) == pytest.approx(wave, rel=1e-9)
    assert wave == pytest.approx(want["metrics"]["replicate_ms.x4"],
                                 rel=1e-6)
    assert all(ms > 0 for ms in by_part.values())
    assert got["parts"]["bck_val_scatter"] \
        == pytest.approx(want["metrics"]["bck_val_scatter_ms.x4"], rel=1e-9)
    assert set(got["by_wave"][REPLICATE]) <= {
        *REPL_PARTS, "log_plan", "log_scatter"}
