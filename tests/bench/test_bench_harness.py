"""The harness off the chip: every cell's rehearsal runs every phase and
check at the configuration's tiny size, the last line has the contract's
keys and types, nothing that measures gives a result on a CPU, and a
configuration, a traffic mix, a loop kind and a per-layer metric are
added as new files plus manifest entries with no edit to a file that is
there."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import checks as ck
from benchmarks import run as bench_run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [c["name"] for c in MANIFEST["workloads"]]
FOUR_DEVICES = "--xla_force_host_platform_device_count=4"


def _lines(out: str) -> list:
    return [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]


def _metric_names(section: str, cell: str, source=None) -> set:
    return {m["name"] for m in MANIFEST[section]
            if cell in m.get("workloads", [cell])
            and source in (None, m["source"])}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_every_phase_and_check(capsys, cell, trace):
    rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 11),
                         "--seconds", "0.5", "--trace", str(trace),
                         "--rehearse"])
    lines = _lines(capsys.readouterr().out)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, last
    assert last["failed_checks"] == [] and last["failed"] == 0
    assert last["attempted"] > 0 and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert "busy_s" not in last["device"] and "breakdown" not in last
    # no time, rate or share of a device under a metric's name: only
    # what the program counts
    section = "per_layer" if trace else "end_to_end"
    assert set(last["metrics"]) <= _metric_names(section, cell,
                                                 "program_counter")
    assert bool(trace) == any(n.startswith("contention_abort_share")
                              for n in last["metrics"])
    if not trace:
        assert set(last["rehearsal_host_clock"]) == _metric_names(
            "end_to_end", cell)
    names = {ln["check"] for ln in lines if "check" in ln}
    # against independent code in the traced run, the one the driver
    # makes per cell; the guarantees in every run
    assert bool(trace) == ("compare.dense_stats_equal_generic_engine"
                           in names)
    assert bool(trace) == ("compare.recovered_from_replica_2" in names)
    for want in ("warmup.accounting_closes", "window.accounting_closes",
                 "window.attempted_equals_dispatched",
                 "window.monitor_reconciles_with_stats",
                 "window.lock_ledger_closes", "window.no_row_left_locked",
                 "window.ab_missing_in_analytic_band",
                 "window.nothing_compiled"):
        assert want in names, want
    assert sum("acked_writes_read_back_from" in n for n in names) >= 6
    stages = [ln["stage"] for ln in lines if "stage" in ln]
    assert stages == ["resolve", "jax", *["compare_small"][:trace],
                      "populate", "warmup", "window", "verify", "metrics",
                      "done"]
    assert any("peak_bytes_in_use" in ln for ln in lines)


@pytest.mark.parametrize("count", [1, 4])
def test_last_line_keys_and_types_on_a_faked_chip_run(count):
    checks = ck.Checks(lambda **kw: None)
    checks.add("x", True)
    res = {"totals": {"attempted": 100, "committed": 70, "ab_lock": 3,
                      "ab_missing": 26, "ab_validate": 1, "magic_bad": 0},
           "dispatched_txns": 100}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": count}
    metrics = {"setup_s": {"value": 21.5, "unit": "s"}}
    line = bench_run.result_line("tatp7m-sat", 7, checks, res, metrics,
                                 device, [5, 9][:count] + [None])
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 100 and "rehearsal" not in line
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": count,
                              "memory_peak_bytes": [5, 9][count > 1]}
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and isinstance(m["unit"], str)
    json.dumps(line)
    # a transaction with no lawful outcome, or a bad magic word, is failed
    res["totals"]["committed"] = 68
    res["totals"]["magic_bad"] = 1
    checks.add("y", False)
    line = bench_run.result_line("tatp7m-sat", 7, checks, res, metrics,
                                 device, [None])
    assert line["failed"] == 3 and line["correct"] is False
    assert line["failed_checks"] == ["y"]


def _bench(cwd, *argv, env=None, **kw):
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), *argv],
        cwd=cwd, env=env or dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600, **kw)


def test_no_tpu_means_non_zero_no_result_and_a_line_that_says_why():
    c = _bench(REPO, "--workload", CELLS[0], "--seed", "1", "--seconds",
               "1", "--trace", "0")
    assert c.returncode != 0
    last = _lines(c.stdout)[-1]
    assert "no TPU" in last["error"] and last["stage"] == "jax"
    assert "no TPU" in c.stderr
    for word in ('"metrics"', '"correct"', '"value"'):
        assert word not in c.stdout


def test_an_unknown_cell_is_refused_before_jax_starts():
    c = _bench(REPO, "--workload", "no-such-cell", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert c.returncode != 0 and '"metrics"' not in c.stdout
    assert "no cell 'no-such-cell'" in _lines(c.stdout)[-1]["error"]


def _copy_benchmark(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in MANIFEST["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: the system
    under test is not there."""
    _copy_benchmark(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    c = _bench(tmp_path, "--workload", CELLS[0], "--seed", "1", "--seconds",
               "1", "--trace", "0", "--rehearse",
               env=dict(env, JAX_PLATFORMS="cpu"))
    assert c.returncode != 0
    assert '"metrics"' not in c.stdout and '"correct"' not in c.stdout
    assert "dint_tpu" in _lines(c.stdout)[-1]["error"]


NEW_LOOP = '''\
"""A loop kind a later PR brings: single steps, as closed_step has them,
under another name."""
from benchmarks.loops import closed_step


def run(dep, carry, keys, seconds, max_dispatches, before_drain):
    return closed_step.run(dep, carry, keys, seconds, max_dispatches,
                           before_drain)
'''
NEW_METRIC = '''\
"""Counters: log entries appended per committed transaction."""


def read(ctx):
    return ctx["counters"]["log_appends"] / ctx["totals"]["committed"]
'''


def test_a_config_a_cell_a_loop_and_a_metric_are_added_as_new_files(
        tmp_path):
    """In a copy of the benchmark: the four-device configuration gets a
    cell with a new traffic mix on a new loop kind, and a new per-layer
    metric, by new files and manifest entries alone; the run picks all of
    it up on the virtual four-device mesh."""
    _copy_benchmark(tmp_path)
    os.symlink(os.path.join(REPO, "dint_tpu"), tmp_path / "dint_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks").rglob("*")
              if p.is_file()}
    bench = tmp_path / "benchmarks"
    cfg = json.loads((bench / "configs" / "tatp7m-x4.json").read_text())
    cfg.update(name="tatp-new-x4", source=cfg["source"] + " (copy)")
    (bench / "configs" / "tatp-new-x4.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "lat.json").read_text())
    traffic.update(name="pairs", loop="closed_pair")
    (bench / "traffic" / "pairs.json").write_text(json.dumps(traffic))
    (bench / "loops" / "closed_pair.py").write_text(NEW_LOOP)
    (bench / "layer_metrics" / "log_entries_per_commit.x4.py").write_text(
        NEW_METRIC)
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({
        "name": "tatp-new-x4", "source": cfg["source"],
        "file": "benchmarks/configs/tatp-new-x4.json", "reduced": [],
        "why": "a configuration a later PR adds"})
    manifest["workloads"].append({
        "name": "tatp-new-x4.pairs", "config": "tatp-new-x4",
        "traffic": "pairs", "chips": 4, "why": "a cell a later PR adds"})
    manifest["per_layer"].append({
        "name": "log_entries_per_commit.x4", "unit": "entries/txn",
        "better": "lower", "source": "program_counter",
        "layer": "multi-chip", "moves": "committed_txn_per_s",
        "workloads": ["tatp-new-x4.pairs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=FOUR_DEVICES)
    env.pop("PYTHONPATH", None)
    c = _bench(tmp_path, "--workload", "tatp-new-x4.pairs", "--seed", "5",
               "--seconds", "0.5", "--trace", "1", "--rehearse", env=env)
    assert c.returncode == 0, c.stdout[-2000:] + c.stderr[-2000:]
    lines = _lines(c.stdout)
    last = lines[-1]
    assert last["correct"] is True and last["device"]["count"] == 4
    assert last["workload"] == "tatp-new-x4.pairs"
    # three log entries (one per replica device) for every install
    assert set(last["metrics"]) == {"log_entries_per_commit.x4"}
    assert last["metrics"]["log_entries_per_commit.x4"]["value"] > 0
    names = {ln["check"] for ln in lines if "check" in ln}
    for want in ("window.replication_pushes_equal_installs",
                 "window.every_write_in_three_logs",
                 *(f"window.device_{d}_acked_writes_read_back_from_ring_"
                   f"{(d + o) % 4}" for d in range(4) for o in range(3)),
                 *(f"window.backup_{s}_of_device_{d}_equals_primary"
                   for s in (1, 2) for d in range(4))):
        assert want in names, want
    for p, body in before.items():
        assert p.read_bytes() == body, f"{p} was edited"


def test_a_failed_check_gives_a_result_that_says_which(capsys, monkeypatch):
    """A run whose check fails still ends in a result line: ``correct``
    false and the check's name, never a bare exit code."""
    real = ck.compare_readback

    def altered(plan, live_meta, live_val):
        live_val = live_val.copy()
        live_val[0, 0] ^= 1             # one replica disagrees with a row
        return real(plan, live_meta, live_val)

    monkeypatch.setattr(ck, "compare_readback", altered)
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "3", "--seconds",
                         "0.3", "--trace", "0", "--rehearse"])
    last = _lines(capsys.readouterr().out)[-1]
    assert rc == 0 and last["correct"] is False
    assert "window.acked_writes_read_back_from_replica_0" \
        in last["failed_checks"]


def test_an_exception_is_one_json_line_and_a_traceback(capsys, monkeypatch):
    def boom(*a, **kw):
        raise RuntimeError("the device went away")

    monkeypatch.setattr(ck, "compare_small", boom)
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "3", "--seconds",
                         "0.3", "--trace", "1", "--rehearse"])
    cap = capsys.readouterr()
    last = _lines(cap.out)[-1]
    assert rc == 1 and last == {
        "error": "RuntimeError: the device went away",
        "stage": "compare_small"}
    assert "Traceback" in cap.err and "compare_small" in cap.err
