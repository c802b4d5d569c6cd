"""The harness off the chip: every cell's rehearsal runs every phase and
check at the configuration's tiny size, the last line has the contract's
keys and types, nothing that measures gives a result on a CPU, and a
configuration, a traffic mix, a loop kind and a per-layer metric are
added as new files plus manifest entries with no edit to a file that is
there."""
import importlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import types

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


def _deployment_of(cell: str):
    """The deployment module of a cell of the manifest."""
    config = next(c["config"] for c in MANIFEST["workloads"]
                  if c["name"] == cell)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           config + ".json")) as f:
        kind = json.load(f)["deployment"]
    return importlib.import_module("benchmarks.deployments." + kind)


def _held_to_the_contract(lines: list, trace: int, guarantee_checks,
                          compare_checks) -> None:
    """What holds for a rehearsal of any deployment: the stage list, the
    harness's own checks in every phase, the read-back from the replicas,
    and the checks the deployment itself states."""
    last = lines[-1]
    assert last["correct"] is True, last
    assert last["failed_checks"] == [] and last["failed"] == 0
    assert last["attempted"] > 0 and last["rehearsal"] is True
    assert "busy_s" not in last["device"] and "breakdown" not in last
    assert bool(trace) == any(n.startswith("contention_abort_share")
                              for n in last["metrics"])
    names = {ln["check"] for ln in lines if "check" in ln}
    assert last["checks"] == sum("check" in ln for ln in lines)
    for want in ("warmup.accounting_closes", "window.accounting_closes",
                 "warmup.attempted_equals_dispatched",
                 "window.attempted_equals_dispatched",
                 "window.monitor_reconciles_with_stats",
                 "window.nothing_compiled"):
        assert want in names, want
    assert sum("acked_writes_read_back_from" in n for n in names) >= 6
    # the guarantees in every run; against independent code in the
    # traced run, the one the driver makes per cell
    assert guarantee_checks and compare_checks
    for name in guarantee_checks:
        assert {f"warmup.{name}", f"window.{name}"} <= names, name
    for name in compare_checks:
        assert bool(trace) == (name in names), name
    stages = [ln["stage"] for ln in lines if "stage" in ln]
    assert stages == ["resolve", "jax", *["compare_small"][:trace],
                      "populate", "warmup", "window", "verify", "metrics",
                      "done"]
    assert any("peak_bytes_in_use" in ln for ln in lines)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_every_phase_and_check(capsys, cell, trace):
    rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 11),
                         "--seconds", "0.5", "--trace", str(trace),
                         "--rehearse"])
    lines = _lines(capsys.readouterr().out)
    last = lines[-1]
    assert rc == 0, last
    dep = _deployment_of(cell)
    _held_to_the_contract(lines, trace, dep.GUARANTEE_CHECKS,
                          dep.COMPARE_CHECKS)
    assert last["device"]["platform"] == "cpu"
    # no time, rate or share of a device under a metric's name: only
    # what the program counts
    section = "per_layer" if trace else "end_to_end"
    assert set(last["metrics"]) <= _metric_names(section, cell,
                                                 "program_counter")
    if not trace:
        assert set(last["rehearsal_host_clock"]) == _metric_names(
            "end_to_end", cell)


@pytest.mark.parametrize("kind", ["tatp_dense_one_chip",
                                  "tatp_dense_sharded"])
def test_the_tatp_deployments_state_the_checks_this_file_listed(kind):
    """Before the tuples, the rehearsal test named these itself."""
    dep = importlib.import_module("benchmarks.deployments." + kind)
    assert set(dep.GUARANTEE_CHECKS) == {
        "lock_ledger_closes", "no_row_left_locked",
        "ab_missing_in_analytic_band"}
    assert set(dep.COMPARE_CHECKS) == {
        "compare.dense_stats_equal_generic_engine",
        "compare.recovered_from_replica_2"}


TATP_DEP = types.SimpleNamespace(
    outcomes=("committed", "ab_lock", "ab_missing", "ab_validate"),
    faults=("magic_bad",))
TATP_TOTALS = {"attempted": 100, "committed": 70, "ab_lock": 3,
               "ab_missing": 26, "ab_validate": 1, "magic_bad": 0}
# another engine's columns (engines/smallbank_dense.py): a logic abort is
# an outcome, the signed balance delta is neither outcome nor fault
BANK_DEP = types.SimpleNamespace(
    outcomes=("committed", "ab_lock", "ab_logic"), faults=("magic_bad",))
BANK_TOTALS = {"attempted": 100, "committed": 70, "ab_lock": 21,
               "ab_logic": 9, "magic_bad": 0, "bal_delta": -1234}


@pytest.mark.parametrize("dep,totals", [(TATP_DEP, TATP_TOTALS),
                                        (BANK_DEP, BANK_TOTALS)],
                         ids=["tatp_columns", "bank_columns"])
@pytest.mark.parametrize("count", [1, 4])
def test_last_line_keys_and_types_on_a_faked_chip_run(count, dep, totals):
    checks = ck.Checks(lambda **kw: None)
    checks.add("x", True)
    res = {"totals": dict(totals), "dispatched_txns": 100}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": count}
    metrics = {"setup_s": {"value": 21.5, "unit": "s"}}
    line = bench_run.result_line("tatp7m-sat", 7, checks, res, dep, metrics,
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
    line = bench_run.result_line("tatp7m-sat", 7, checks, res, dep, metrics,
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


NEW_DEPLOYMENT = '''\
"""Scaffolding of a test, not the supported SmallBank deployment: the
dense SmallBank engine behind the deployment contract, at a size a test
can hold (the whole balance array is fetched; a cell would gather
ring-sized on the device)."""
import jax
import numpy as np

from benchmarks import checks as ck
from dint_tpu import monitor, recovery
from dint_tpu.engines import smallbank_dense as sd
from dint_tpu.tables import log as logring

STAT_NAMES = ("attempted", "committed", "ab_lock", "ab_logic", "magic_bad",
              "bal_delta")
assert [sd.STAT_ATTEMPTED, sd.STAT_COMMITTED, sd.STAT_AB_LOCK,
        sd.STAT_AB_LOGIC, sd.STAT_MAGIC_BAD,
        sd.STAT_BAL_DELTA] == list(range(sd.N_STATS))
OUTCOMES = ("committed", "ab_lock", "ab_logic")
FAULTS = ("magic_bad",)
CONTENTION = ("ab_lock",)
COUNTER_PAIRS = (("txn_attempted", "attempted"),
                 ("txn_committed", "committed"), ("ab_lock", "ab_lock"),
                 ("ab_logic", "ab_logic"), ("magic_bad", "magic_bad"))
GUARANTEE_CHECKS = ("lock_ledger_closes", "balance_conserved",
                    "log_replicas_identical")
COMPARE_CHECKS = ("compare.run_changed_the_tables",
                  *(f"compare.recovered_from_replica_{r}" for r in range(3)))
ENGINE = dict(use_pallas=False, use_fused=False, use_hotset=False,
              trace=False)


def compare_small(config, seed, checks):
    """The balances rebuilt from each one of the three rings by
    recovery.py (host numpy, other code) against the engine's own."""
    size = config["compare_small"]
    n = size["n_accounts"]
    db0 = sd.create(n, log_capacity=1 << 12)
    fresh = jax.tree.map(np.array, db0)          # the runner donates db0
    run, init, drain = sd.build_pipelined_runner(
        n, w=size["w"], cohorts_per_block=size["cohorts_per_block"],
        **ENGINE)
    carry, key = init(db0), jax.random.PRNGKey(seed)
    for i in range(size["blocks"]):
        carry, _ = run(carry, jax.random.fold_in(key, i))
    db, _ = drain(carry)
    heads, live = np.asarray(db.log.head), np.asarray(db.bal)
    checks.add("compare.run_changed_the_tables",
               not np.array_equal(fresh.bal, live))
    for r in range(3):
        rec = recovery.recover_smallbank_dense(
            fresh, np.asarray(logring.replica_entries(db.log, r)), heads)
        checks.add(f"compare.recovered_from_replica_{r}",
                   np.array_equal(np.asarray(rec.bal), live))


class Bank:
    stat_names = STAT_NAMES
    outcomes = OUTCOMES
    faults = FAULTS
    contention = CONTENTION
    depth = 2
    n_devices = 1

    def __init__(self, sizes, params, emit):
        self.n = sizes["n_accounts"]
        w, cpb = params["w"], params["cohorts_per_block"]
        self.txns_per_dispatch = w * cpb
        self.steps_per_dispatch = cpb
        self._db = sd.create(self.n, log_lanes=sizes["log_lanes"],
                             log_capacity=sizes["log_capacity"])
        self._balance = int(np.asarray(sd.total_balance(self._db)))
        self.geometry = {"w": w, "table_bytes": int(self._db.bal.nbytes)}
        emit(phase="populate", n_accounts=self.n, **self.geometry)
        self._run, self._init, self._drain = sd.build_pipelined_runner(
            self.n, w=w, cohorts_per_block=cpb, monitor=True, **ENGINE)

    def start(self):
        db, self._db = self._db, None
        return self._init(db)

    def restart(self, final):
        return self._init(final[0])

    def dispatch(self, carry, key):
        return self._run(carry, key)

    def drain(self, carry):
        out = self._drain(carry)
        return out, np.asarray(out[1], np.int64)

    def verify(self, final, checks, tag, totals, dispatched):
        db, _, counters = final
        snap = monitor.snapshot(counters)
        ck.check_accounting(checks, tag, totals, snap, dispatched, OUTCOMES,
                            FAULTS, COUNTER_PAIRS)
        ck.check_lock_ledger(checks, tag, snap)
        balance = int(np.asarray(sd.total_balance(db)))
        moved, self._balance = balance - self._balance, balance
        checks.add(f"{tag}.balance_conserved",
                   moved % (1 << 32) == totals["bal_delta"] % (1 << 32),
                   moved=moved, bal_delta=totals["bal_delta"])
        heads = np.asarray(db.log.head)
        rings = [np.asarray(logring.replica_entries(db.log, r))
                 for r in range(3)]
        checks.add(f"{tag}.log_replicas_identical",
                   all(np.array_equal(rings[0], r) for r in rings[1:]))
        live = np.asarray(db.bal)
        for r, ring in enumerate(rings):
            # no version word in the table: the live balance is held to
            # the newest entry of its row wherever no later write can
            # have been wrapped over
            plan = ck.plan_readback(ring, heads, (self.n, self.n), sd.VW)
            held = plan["fresh"] | (not plan["wrapped"])
            differs = (live[plan["rows"]] != plan["val"][:, 0]) & held
            checks.add(f"{tag}.acked_writes_read_back_from_replica_{r}",
                       plan["in_range"] and bool(held.any())
                       and not differs.any()
                       and bool((plan["val"][:, 1] == sd.MAGIC).all()),
                       keys=len(held), held=int(held.sum()),
                       differs=int(differs.sum()), wrapped=plan["wrapped"])
        return snap


def build(config, params, seed, devices, emit, rehearse):
    return Bank(config["rehearse" if rehearse else "sizes"], params, emit)
'''
NEW_CONFIG = {
    "name": "bank-new", "deployment": "bank_dense", "chips": 1,
    "source": "a deployment of another engine that a later PR adds",
    "sizes": {"n_accounts": 20000, "log_lanes": 16, "log_capacity": 1024},
    "rehearse": {"n_accounts": 20000, "log_lanes": 16,
                 "log_capacity": 1024},
    "compare_small": {"n_accounts": 2000, "w": 128, "cohorts_per_block": 2,
                      "blocks": 3},
    "guarantees": ["2PL S/X no-wait locks", "every write in three logs"],
    "reduced": [], "assumed": {"size": "a test's"}}


@pytest.mark.parametrize("trace", [0, 1])
def test_a_deployment_of_another_engine_is_added_as_new_files(tmp_path,
                                                              trace):
    """In a copy of the benchmark: a deployment module over
    engines/smallbank_dense.py (other stat columns, no version word in
    the table, its own small comparison), its configuration, a cell on
    the traffic mix that is there and the metric entries it needs, by
    new files and manifest entries alone."""
    _copy_benchmark(tmp_path)
    os.symlink(os.path.join(REPO, "dint_tpu"), tmp_path / "dint_tpu")
    before = {p: p.read_bytes() for path in MANIFEST["paths"]
              for p in (tmp_path / path).rglob("*") if p.is_file()}
    bench = tmp_path / "benchmarks"
    (bench / "deployments" / "bank_dense.py").write_text(NEW_DEPLOYMENT)
    (bench / "configs" / "bank-new.json").write_text(json.dumps(NEW_CONFIG))
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({
        "name": "bank-new", "source": NEW_CONFIG["source"],
        "file": "benchmarks/configs/bank-new.json", "reduced": [],
        "why": "a configuration a later PR adds"})
    manifest["workloads"].append({
        "name": "bank-new-sat", "config": "bank-new", "traffic": "sat",
        "chips": 1, "why": "a cell a later PR adds"})
    manifest["end_to_end"].append({
        "name": "committed_txn_per_s.bank", "unit": "txn/s",
        "better": "higher", "bound": 0.05, "source": "host_clock",
        "workloads": ["bank-new-sat"]})
    manifest["per_layer"].append({
        "name": "contention_abort_share.bank", "unit": "%",
        "better": "lower", "source": "program_counter",
        "layer": "engine step", "moves": "committed_txn_per_s.bank",
        "workloads": ["bank-new-sat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    c = _bench(tmp_path, "--workload", "bank-new-sat", "--seed",
               str(2**31 + 5), "--seconds", "0.5", "--trace", str(trace),
               "--rehearse", env=env)
    assert c.returncode == 0, c.stdout[-2000:] + c.stderr[-2000:]
    lines = _lines(c.stdout)
    spec = importlib.util.spec_from_file_location(
        "bank_dense_as_written", bench / "deployments" / "bank_dense.py")
    dep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(dep)
    _held_to_the_contract(lines, trace, dep.GUARANTEE_CHECKS,
                          dep.COMPARE_CHECKS)
    last = lines[-1]
    assert last["workload"] == "bank-new-sat"
    if trace:
        # lock rejections over attempts, from the columns the module named
        totals = next(ln["totals"] for ln in lines if "totals" in ln)
        assert set(totals) == set(dep.STAT_NAMES)
        assert set(last["metrics"]) == {"contention_abort_share.bank"}
        assert last["metrics"]["contention_abort_share.bank"]["value"] \
            == 100.0 * totals["ab_lock"] / totals["attempted"] > 0
    else:
        assert set(last["rehearsal_host_clock"]) == {
            "committed_txn_per_s.bank", "setup_s"}
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
