"""chip_smoke.py off the chip: its rehearsal runs every phase and check
here at tiny sizes, and without the rehearsal argument nothing that
measures gives a result on a CPU."""
import json
import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

import chip_smoke
from dint_tpu import _runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rehearse(capsys, *argv):
    assert chip_smoke.main(["--rehearse", *argv]) == 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out           # a rehearsal never says so
    return [json.loads(ln) for ln in out.splitlines()]


def _passed(lines):
    checks = {ln["check"]: ln["passed"] for ln in lines if "check" in ln}
    assert checks and all(checks.values()), checks
    return set(checks)


def test_rehearsal_runs_every_check_of_the_default_phase(capsys):
    lines = _rehearse(capsys)
    names = _passed(lines)
    # the run's invariants, both comparisons, recovery at the run's size
    for want in ("tatp.accounting_closes", "tatp.no_row_left_locked",
                 "tatp.magic_bad_zero", "tatp.log_replicas_identical",
                 "tatp.log_entries_equal_monitor_installs",
                 "tatp.monitor_reconciles_with_stats",
                 "tatp.ab_missing_in_analytic_band",
                 "compare.dense_stats_equal_generic_engine",
                 *(f"durability_{tag}.recovered_from_replica_{r}"
                   for tag in ("cmp", "full") for r in range(3))):
        assert want in names, want
    sizes = next(ln for ln in lines if ln.get("phase") == "tatp")
    assert (sizes["n_sub"], sizes["w"]) == (20_000, 256)
    assert lines[-1] == {"rehearsal": "passed",
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 1}}


def test_rehearsal_of_the_four_device_phase(capsys):
    """`--chips 4` runs the sharded path and the one-chip run it is
    compared with, and no other phase."""
    lines = _rehearse(capsys, "--chips", "4")
    names = _passed(lines)
    for want in ("sharded.every_leaf_on_every_device",
                 "sharded.accounting_closes",
                 "sharded.replication_pushes_equal_installs",
                 "sharded.every_write_in_three_logs",
                 "sharded.committed_share_agrees_with_one_chip",
                 *(f"sharded.backup_{off}_of_device_{d}_equals_primary"
                   for off in (1, 2) for d in range(4)),
                 *(f"sharded.device_{d}_recovered_from_ring_{(d + o) % 4}"
                   for d in range(4) for o in range(3))):
        assert want in names, want
    assert not any(n.startswith(("compare.", "durability_")) for n in names)
    assert [ln["phase"] for ln in lines if "phase" in ln] \
        == ["sharded", "tatp"]
    assert lines[-1]["device"]["count"] == 4


@pytest.mark.parametrize("count", [1, 4])
def test_last_line_on_a_faked_device_record(count):
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert chip_smoke.result_line([tpu] * count, rehearse=False) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": count}}
    assert "ok" not in chip_smoke.result_line([tpu] * count, rehearse=True)


@pytest.mark.parametrize("program", ["chip_smoke.py", "bench.py", "exp.py",
                                     "tools/drive.py"])
def test_no_tpu_means_non_zero_and_no_result(program):
    """Every program that measures demands the chip: on a CPU it exits
    non-zero before it prints a result, a value or a rate — no fallback
    platform, no retry, no old artifact."""
    c = subprocess.run(
        [sys.executable, os.path.join(REPO, program)], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert c.returncode != 0, c.stdout[-400:]
    for word in ('"ok": true', '"value"', "txn_per_s", "goodput",
                 "ALL CHECKS PASSED"):
        assert word not in c.stdout, c.stdout[-400:]
    assert "no TPU" in c.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    c = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert c.returncode != 0 and c.stdout == ""


def test_compile_cache_helper(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and no path is set in code; without
    it the cache lives at the fixed in-checkout path. Either way the ops'
    names go into the cache key and their call stacks stay out of it
    (tests/test_parts.py says why)."""
    in_key = [("jax_compilation_cache_include_metadata_in_key", True),
              ("jax_traceback_in_locations_limit", 0)]
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: updates.append((name, val)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert _runtime.compile_cache_dir() == "/some/dir"
    assert _runtime.enable_compile_cache() == "/some/dir"
    assert updates == in_key
    updates.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert _runtime.compile_cache_dir() == fixed
    assert _runtime.enable_compile_cache() == fixed
    assert updates == [("jax_compilation_cache_dir", fixed)] + in_key
