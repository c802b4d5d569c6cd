"""dintplan: the static configuration planner + the fifth standing gate
(ISSUE 17 tentpole).

The acceptance pins, per ISSUE.md:
  * the knob registry is first-class: values, env semantics, and the
    target-variant mapping (use_hotset=True => the @hot twin) are
    declared once in analysis/plan.KNOBS and the lattice enumeration /
    pricing / domination pruning all read from it;
  * `dintplan check` exits 0 on the pinned PLAN.json with ZERO
    allowlist entries (the in-process gate below runs the FULL mode:
    fresh dintcost derivation per frontier row);
  * every plan_check ERROR is proven live by a mutated-fixture test —
    flipped priced ordering, dominated pin, unregistered knob/target,
    stale provenance, unjustified pin, env flag contradicting the plan
    without DINT_PLAN_OVERRIDE=1 — and each is silenceable by a scoped
    allowlist entry with a written reason, never by anything broader;
  * consumers resolve knobs through plan.resolve_for: the plan's pinned
    config wins, env flags are consulted ONLY under
    DINT_PLAN_OVERRIDE=1, and a missing plan degrades to plain env
    resolution with meta["source"] None (artifacts record "plan": null,
    never a silent default).

The serve-plane integration (ServeEngine plan priors, the hot_frac
rebuild at drain boundaries, plan-resolved == hand-config bit identity)
is pinned in tests/test_dintserve.py next to the engines it exercises.
"""
import copy
import json
import os
import subprocess

import pytest

from dint_tpu import analysis
from dint_tpu.analysis import allowlist as al
from dint_tpu.analysis import plan as P
from dint_tpu.analysis import targets as T
from dint_tpu.analysis.passes import plan_check as pc

pytestmark = pytest.mark.plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PLAN_PATH = os.path.join(REPO, "PLAN.json")

# the fixture target every mutated-document finding anchors to; the
# dintlint every-pass parametrization silences `fixture/plan_check`
ANCHOR = "fixture/plan_check"

_DOC = None


def _doc() -> dict:
    """A fresh deep copy of the pinned PLAN.json (loaded once)."""
    global _DOC
    if _DOC is None:
        _DOC = P.load_plan(PLAN_PATH)
    return copy.deepcopy(_DOC)


def _check(doc, environ=None, static=True):
    return pc.check_plan(doc, ANCHOR, static=static,
                         environ={} if environ is None else environ)


def codes(findings):
    return {f.code for f in findings}


# ------------------------------------------------------- knob registry


def test_knob_registry_declares_target_variants():
    """Satellite (1): the registry is the single source of knob ->
    target-variant truth: use_hotset=True maps to the @hot twin,
    hierarchical=False to @flat, and the planned knobs span the lattice."""
    assert P.KNOBS["use_hotset"].token == "hot"
    assert P.KNOBS["use_hotset"].token_when is True
    assert P.KNOBS["hierarchical"].token == "flat"
    assert P.KNOBS["hierarchical"].token_when is False
    wl = P._WORKLOADS_BY_NAME["tatp_uniform"]
    assert P.target_name(wl, {"use_hotset": True}) == "tatp_dense/block@hot"
    assert P.target_name(wl, {"use_hotset": False}) == "tatp_dense/block"
    ms = P._WORKLOADS_BY_NAME["multihost_serve"]
    assert P.target_name(ms, {"hierarchical": False, "overlap": True}) \
        == "multihost_sb/serve@overlap+flat"  # canonical token order
    mh = P._WORKLOADS_BY_NAME["multihost_4x2"]
    assert P.target_name(mh, {"hierarchical": False}) \
        == "multihost_sb/block@flat"
    assert P.target_name(mh, {"hierarchical": True}) == "multihost_sb/block"


def test_enumerate_candidates_flags_infeasible_combos():
    """The lattice is exhaustive over each workload's planned knobs and
    an unregistered combination (overlap over the flat transport) is
    marked infeasible, never silently priced."""
    wl = P._WORKLOADS_BY_NAME["multihost_serve"]
    cands = P.enumerate_candidates(wl)
    assert len(cands) == 2 ** len(wl.knobs)
    by_target = {c["target"]: c for c in cands}
    assert by_target["multihost_sb/serve"]["feasible"]
    assert by_target["multihost_sb/serve@overlap"]["feasible"]
    flat_overlap = [c for c in cands
                    if c["knobs"].get("overlap")
                    and not c["knobs"].get("hierarchical")]
    assert flat_overlap and not any(c["feasible"] for c in flat_overlap)
    # every feasible candidate names a registered target
    for c in cands:
        assert c["feasible"] == (c["target"] in T.TARGETS)


def test_resolve_knobs_env_semantics():
    """The registry replicates each consumer's exact env semantics:
    flag01 (set-and-not-0) vs flag1 (exactly "1")."""
    r = P.resolve_knobs({})
    assert r["use_hotset"] is False and r["monitor"] is False
    assert P.resolve_knobs({"DINT_USE_HOTSET": "0"})["use_hotset"] is False
    assert P.resolve_knobs({"DINT_USE_HOTSET": ""})["use_hotset"] is False
    assert P.resolve_knobs({"DINT_USE_HOTSET": "2"})["use_hotset"] is True
    assert P.resolve_knobs({"DINT_MONITOR": "1"})["monitor"] is True
    assert P.resolve_knobs({"DINT_MONITOR": "2"})["monitor"] is False


def test_env_knob_signature_canonicalizes():
    """Satellite (2): the memo-key signature engines/_memo.py folds into
    builder identity canonicalizes unset == "" == "0" for the flag
    knobs."""
    base = P.env_knob_signature({})
    assert base == P.env_knob_signature({"DINT_USE_HOTSET": "0"})
    assert base == P.env_knob_signature({"DINT_USE_HOTSET": ""})
    assert base != P.env_knob_signature({"DINT_USE_HOTSET": "1"})
    names = [n for n, _ in base]
    assert "use_hotset" in names and "trace" in names
    assert "monitor" not in names        # not part of compiled identity


def test_memo_routes_through_shared_signature(monkeypatch):
    """engines/_memo.py derives its env fingerprint from the SAME
    registry resolution — flipping a build-identity flag changes the
    memo key, flipping an equivalent spelling does not."""
    from dint_tpu.engines import _memo
    monkeypatch.delenv("DINT_USE_HOTSET", raising=False)
    k0 = _memo._env_signature()
    monkeypatch.setenv("DINT_USE_HOTSET", "0")
    assert _memo._env_signature() == k0
    monkeypatch.setenv("DINT_USE_HOTSET", "1")
    assert _memo._env_signature() != k0


# --------------------------------------------------- the pinned artifact


def test_pinned_plan_is_schema_versioned_and_clean():
    """The checked-in PLAN.json parses at the current schema, carries
    full provenance, and the static gate finds NOTHING wrong with it."""
    doc = _doc()
    assert doc["schema"] == P.SCHEMA
    prov = doc["provenance"]
    assert prov["knobs_hash"] == P.knobs_hash()
    assert prov["calibration_hash"] == P.calibration_hash()
    assert prov["cost_model_hash"] == P.frontier_hash(doc["frontier"])
    assert _check(doc) == []


def test_pinned_plan_covers_every_declared_workload():
    doc = _doc()
    assert set(doc["workloads"]) == {w.name for w in P.WORKLOADS}
    for wname, entry in doc["workloads"].items():
        assert entry["target"] in T.TARGETS
        assert entry["predicted_target"] in T.TARGETS
        # every pinned != predicted divergence carries a written reason
        for o in entry["overrides"]:
            assert o["reason"].strip()


def test_consumer_maps_resolve_to_declared_workloads():
    """bench/exp/serve look their workload up via these maps — every
    value must be a declared, pinned workload."""
    doc = _doc()
    for m in (P.BLOCK_WORKLOADS, P.SERVE_WORKLOADS):
        for engine, wname in m.items():
            assert wname in doc["workloads"], (engine, wname)
            assert doc["workloads"][wname]["engine"] == engine


def test_serve_priors_pinned_in_plan():
    """Serve workloads carry ServiceModel capacity priors: the width
    menu with per-width capacity, the knee, and the hot_frac prior the
    engine rebuilds toward (None for TATP — no hot tier)."""
    from dint_tpu.clients import workloads as wl
    from dint_tpu.serve.controller import ControllerCfg
    doc = _doc()
    sb = doc["workloads"]["smallbank_serve"]["serve"]
    tatp = doc["workloads"]["tatp_serve"]["serve"]
    assert sb["hot_frac"] == wl.SB_HOT_FRAC
    assert tatp["hot_frac"] is None
    cfg = ControllerCfg()
    for priors in (sb, tatp):
        assert sorted(int(w) for w in priors["widths"]) == list(cfg.widths)
        caps = {int(w): v["capacity_lanes_per_s"]
                for w, v in priors["widths"].items()}
        assert priors["knee_width"] == max(caps, key=caps.get)
    mesh = doc["workloads"]["multihost_serve"]["serve"]
    assert mesh["lanes_scale"] == 8


# ------------------------------------------------- mutated-fixture gate
#
# Each plan_check ERROR code proven live on a surgically mutated copy of
# the real pinned document (provenance hashes are EXPECTED to co-fire on
# frontier edits — the assertion is that the named code fires).


def broken_plan_findings():
    """The canonical broken plan fixture (swapped frontier ranks =>
    flipped-ordering), also imported by test_dintlint's every-pass
    liveness parametrization. Findings anchor to fixture/plan_check."""
    doc = _doc()
    rows = [r for r in doc["frontier"]
            if r["workload"] == "multihost_serve" and not r["dominated"]]
    assert len(rows) >= 2
    rows[0]["rank"], rows[1]["rank"] = rows[1]["rank"], rows[0]["rank"]
    return _check(doc)


def _mutate(code):
    doc = _doc()
    if code == "flipped-ordering":
        rows = [r for r in doc["frontier"]
                if r["workload"] == "multihost_serve"
                and not r["dominated"]]
        rows[0]["rank"], rows[1]["rank"] = rows[1]["rank"], rows[0]["rank"]
        return _check(doc)
    if code == "dominated-pin":
        entry = doc["workloads"]["tatp_uniform"]
        rows = [r for r in doc["frontier"]
                if r["workload"] == "tatp_uniform"]
        pin = next(r for r in rows if r["target"] == entry["target"])
        other = next(r for r in rows if r is not pin)
        for k in ("bytes_per_step", "dispatches_per_step",
                  "footprint_bytes"):
            pin[k] = other[k] + 1       # strictly worse on all three
        return _check(doc)
    if code == "unregistered-target":
        doc["workloads"]["tatp_uniform"]["target"] = "tatp_dense/nope"
        return _check(doc)
    if code == "unregistered-knob":
        doc["workloads"]["tatp_uniform"]["pinned"]["warp_speed"] = True
        return _check(doc)
    if code == "unknown-workload":
        doc["workloads"]["mystery"] = copy.deepcopy(
            doc["workloads"]["tatp_uniform"])
        return _check(doc)
    if code == "stale-provenance":
        doc["provenance"]["calibration_hash"] = "0" * 16
        return _check(doc)
    if code == "unjustified-pin":
        entry = doc["workloads"]["tatp_uniform"]
        entry["pinned"]["use_hotset"] = not entry["predicted"]["use_hotset"]
        return _check(doc)
    if code == "env-override":
        return _check(doc, environ={"DINT_USE_HOTSET": "1"})
    if code == "malformed-plan":
        del doc["frontier"]
        return _check(doc)
    raise AssertionError(code)


@pytest.mark.parametrize("code", [
    "flipped-ordering", "dominated-pin", "unregistered-target",
    "unregistered-knob", "unknown-workload", "stale-provenance",
    "unjustified-pin", "env-override", "malformed-plan"])
def test_each_check_fires_and_is_allowlist_silenceable(code, tmp_path):
    """Acceptance contract: each plan_check ERROR is proven live by a
    mutated fixture AND silenceable by a scoped entry with a written
    reason — never by anything broader."""
    findings = _mutate(code)
    errs = {f.code for f in findings if f.severity == "error"}
    assert code in errs, f"{code} fixture did not fire: " \
        + str([str(f) for f in findings])

    path = tmp_path / "allow.json"
    path.write_text(json.dumps([
        {"pass": "plan_check", "code": code, "target": ANCHOR,
         "reason": "test fixture: mutation is constructed on purpose"}]))
    fs = al.apply(_mutate(code), al.load(str(path)), check_unused=False)
    assert not any(f.severity == "error" and not f.suppressed
                   and f.code == code for f in fs)
    assert any(f.suppressed for f in fs)


def test_mutated_price_flips_ordering_and_provenance():
    """Editing a recorded price re-ranks the workload under the decision
    rule AND breaks the frontier digest — a doctored row cannot survive
    either check."""
    doc = _doc()
    rows = [r for r in doc["frontier"]
            if r["workload"] == "multihost_serve" and not r["dominated"]]
    best = next(r for r in rows if r["rank"] == 0)
    best["dcn_bytes_per_step"] = 1e12      # push the pick off rank 0
    fs = _check(doc)
    assert "flipped-ordering" in codes(fs)
    assert "stale-provenance" in codes(fs)


def test_missing_and_unreadable_plan(tmp_path):
    plan, fs = pc.load_plan_findings(ANCHOR, path=tmp_path / "none.json")
    assert plan is None and codes(fs) == {"missing-plan"}

    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    plan, fs = pc.load_plan_findings(ANCHOR, path=bad)
    assert plan is None and codes(fs) == {"malformed-plan"}

    wrong = tmp_path / "wrong.json"
    wrong.write_text(json.dumps({"schema": P.SCHEMA + 1}))
    with pytest.raises(ValueError):
        P.load_plan(wrong)


def test_env_override_flag_acknowledges_contradiction():
    """DINT_PLAN_OVERRIDE=1 is the ONLY way an ambient flag may
    contradict the plan — with it the gate is silent, without it every
    contradicting workload is named."""
    doc = _doc()
    fs = _check(doc, environ={"DINT_USE_HOTSET": "1"})
    hit = [f for f in fs if f.code == "env-override"]
    assert hit and all("DINT_USE_HOTSET" in f.message for f in hit)
    assert _check(doc, environ={"DINT_USE_HOTSET": "1",
                                "DINT_PLAN_OVERRIDE": "1"}) == []
    # contradictions() names (workload, knob, pinned, env value)
    cons = P.contradictions(doc, {"DINT_USE_HOTSET": "1"})
    assert ("tatp_uniform", "use_hotset", False, True) in cons
    assert P.contradictions(doc, {}) == []


def test_priced_drift_fires_in_full_mode():
    """Full mode re-derives each frontier row with dintcost: a doctored
    price that kept its rank is still caught. Frontier reduced to the
    one rank-0 row so the fresh derivation traces a single target."""
    doc = _doc()
    row = next(r for r in doc["frontier"]
               if r["workload"] == "tatp_uniform" and r["rank"] == 0)
    doc["frontier"] = [row]
    row["bytes_per_step"] += 64.0
    fs = _check(doc, static=False)
    assert "priced-drift" in codes(fs)
    drift = next(f for f in fs if f.code == "priced-drift")
    assert "bytes_per_step" in drift.message


# ------------------------------------------------------- consumer resolve


def test_resolve_for_plan_pins_beat_env():
    """Without DINT_PLAN_OVERRIDE the plan's pinned knobs win outright;
    with it, only explicitly-SET contradicting flags flip, and meta
    records exactly which."""
    doc = _doc()
    knobs, meta = P.resolve_for("tatp_uniform",
                                environ={"DINT_USE_HOTSET": "1"}, plan=doc)
    assert knobs["use_hotset"] is False and meta["overridden"] == []
    assert meta["source"] and meta["hash"] == \
        doc["provenance"]["cost_model_hash"]

    knobs, meta = P.resolve_for(
        "tatp_uniform", plan=doc,
        environ={"DINT_USE_HOTSET": "1", "DINT_PLAN_OVERRIDE": "1"})
    assert knobs["use_hotset"] is True
    assert meta["overridden"] == ["use_hotset"]
    # an UNSET flag never flips a pin, even under the override
    knobs, meta = P.resolve_for("tatp_uniform", plan=doc,
                                environ={"DINT_PLAN_OVERRIDE": "1"})
    assert knobs["use_hotset"] is False and meta["overridden"] == []


def test_resolve_for_without_plan_falls_back_to_env(monkeypatch,
                                                    tmp_path):
    monkeypatch.setenv(P.ENV_PLAN_PATH, str(tmp_path / "none.json"))
    knobs, meta = P.resolve_for("tatp_uniform",
                                environ={"DINT_USE_HOTSET": "1"})
    assert meta == {"source": None, "hash": None, "overridden": []}
    assert knobs["use_hotset"] is True         # plain env resolution
    assert set(knobs) == set(
        P._WORKLOADS_BY_NAME["tatp_uniform"].knobs)


# ------------------------------------------------------------ tier-1 gate


def _dintplan_main():
    """tools/dintplan.py main() in-process: the full-mode gate reuses
    this process's TraceCache instead of re-tracing ~28 targets."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_dintplan_cli", os.path.join(REPO, "tools", "dintplan.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


def test_dintplan_check_full_gate_in_process(monkeypatch, capsys):
    """THE acceptance gate: `dintplan check` (FULL mode — fresh dintcost
    derivation per frontier row) exits 0 on the pinned PLAN.json with
    zero plan_check allowlist entries."""
    # setenv (not delenv): cmd_check writes these vars, and monkeypatch
    # only restores what it touched — register the restore up front
    monkeypatch.setenv(P.ENV_PLAN_STATIC, "0")
    monkeypatch.delenv(P.ENV_PLAN_PATH, raising=False)
    for k in P.KNOBS.values():               # a clean ambient env
        if k.env:
            monkeypatch.delenv(k.env, raising=False)
    main = _dintplan_main()
    assert main(["check", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["metric"] == "dintplan" and payload["ok"] is True
    assert payload["static"] is False and payload["n_errors"] == 0
    assert payload["n_suppressed"] == 0      # ZERO allowlist entries
    allow = json.load(open(os.path.join(REPO, "tools",
                                        "dintlint_allow.json")))
    assert not [e for e in allow if e["pass"] == "plan_check"]


def test_plan_check_anchors_to_one_target(monkeypatch):
    """The whole-plan findings land exactly once: on the anchor target,
    [] everywhere else — `dintlint --all` cannot double-report."""
    monkeypatch.delenv(P.ENV_PLAN_ANCHOR, raising=False)
    monkeypatch.delenv(P.ENV_PLAN_STATIC, raising=False)
    fs = analysis.run(targets=[P.DEFAULT_ANCHOR], passes=["plan_check"])
    assert not analysis.has_errors(fs)
    other = next(n for n in sorted(T.TARGETS) if n != P.DEFAULT_ANCHOR)
    assert analysis.run(targets=[other], passes=["plan_check"]) == []


def test_dintplan_check_mutated_plan_fails(tmp_path, monkeypatch,
                                           capsys):
    """CLI exit discipline on a broken artifact: a plan whose recorded
    ordering was flipped fails `check --static` with exit 1 and names
    flipped-ordering."""
    doc = _doc()
    rows = [r for r in doc["frontier"]
            if r["workload"] == "multihost_serve" and not r["dominated"]]
    rows[0]["rank"], rows[1]["rank"] = rows[1]["rank"], rows[0]["rank"]
    path = tmp_path / "broken_plan.json"
    path.write_text(json.dumps(doc))
    monkeypatch.setenv(P.ENV_PLAN_STATIC, "1")   # cmd_check writes this
    monkeypatch.setenv(P.ENV_PLAN_PATH, str(path))
    main = _dintplan_main()
    rc = main(["check", "--static", "--plan", str(path)])
    out = capsys.readouterr().out
    assert rc == 1 and "flipped-ordering" in out


def test_dintplan_cli_describe_and_sarif(tmp_path, capsys, monkeypatch):
    """Satellite (1): `describe` lists the registry with target
    mappings; `check --sarif` writes SARIF 2.1.0 through the shared
    exporter. In-process main() (warm TraceCache) — the subprocess
    surface is covered by the mutated-plan CLI test's sibling tools."""
    monkeypatch.setenv(P.ENV_PLAN_STATIC, "1")   # cmd_check writes it
    main = _dintplan_main()
    assert main(["describe", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["metric"] == "dintplan"
    assert payload["knobs"]["use_hotset"]["token"] == "hot"
    assert payload["knobs"]["use_hotset"]["env"] == "DINT_USE_HOTSET"
    assert "tatp_uniform" in payload["workloads"]
    assert payload["decision_rule"]

    sarif_path = tmp_path / "plan.sarif"
    assert main(["check", "--static", "--sarif", str(sarif_path),
                 "--json"]) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["ok"] is True and payload["static"] is True
    sarif = json.loads(sarif_path.read_text())
    assert sarif["version"] == "2.1.0"
    assert sarif["runs"][0]["tool"]["driver"]["name"] == "dintplan"


def test_bench_and_exp_route_through_resolve_for():
    """bench.py / exp.py resolve their builder knobs from the plan via
    the shared helpers — the wiring exists and names real workloads."""
    import bench
    import exp
    knobs, meta = bench._plan_resolve("tatp_uniform")
    assert meta is not None and meta["overridden"] == []
    assert set(knobs) >= {"use_hotset"}
    assert exp._plan_knobs("smallbank_skewed").keys() == \
        set(P._WORKLOADS_BY_NAME["smallbank_skewed"].knobs)
    m = exp._plan_meta()
    assert m and m["hash"] == _doc()["provenance"]["cost_model_hash"]


def test_bench_plan_escape_hatch(monkeypatch):
    """DINT_BENCH_PLAN=0: bench falls back to env knobs and records
    "plan": null — disabled is explicit, never silent."""
    import bench
    monkeypatch.setenv("DINT_BENCH_PLAN", "0")
    knobs, meta = bench._plan_resolve("tatp_uniform")
    assert knobs == {} and meta is None


# ----------------------------------------------------- tools/dintgate.sh


def test_dintgate_orchestration_smoke(tmp_path):
    """Satellite: tools/dintgate.sh is ONE entry point for the seven
    standing gates. The smoke pins the orchestration — eight
    invocations (dintcal contributes check AND the journal audit) in
    order through $PYTHON, the allowlist-rot dry-runs riding the three
    matrix gates, dintplan full by default / static under --quick, the
    six finding gates' SARIF logs merged into one multi-run document,
    the per-stage wall-clock timings JSON line, a failing gate named
    WITHOUT stopping the others — against a millisecond stub; each real
    gate has its own in-depth tests (and the full script runs in CI
    proper)."""
    import stat
    import subprocess
    import textwrap

    calls = tmp_path / "calls.log"
    stub = tmp_path / "fakepy"
    stub.write_text(textwrap.dedent("""\
        #!/bin/sh
        # dintgate's SARIF merge runs "$PY - out in..." — that one is
        # real work, hand it to the actual interpreter
        if [ "$1" = "-" ]; then exec python "$@"; fi
        echo "$*" >> "$CALLS"
        tool=$(basename "$1" .py)
        out=""; prev=""
        for a in "$@"; do
            [ "$prev" = "--sarif" ] && out="$a"
            prev="$a"
        done
        [ -n "$out" ] && printf \\
          '{"version":"2.1.0","runs":[{"tool":{"driver":{"name":"%s"}},"results":[]}]}' \\
          "$tool" > "$out"
        [ "$tool" = dintdur ] && [ "${FAIL_DUR:-0}" = 1 ] && exit 1
        exit 0
        """))
    stub.chmod(stub.stat().st_mode | stat.S_IEXEC)
    script = os.path.join(REPO, "tools", "dintgate.sh")
    env = dict(os.environ, PYTHON=str(stub), CALLS=str(calls))

    merged = tmp_path / "gate.sarif"
    timings = tmp_path / "timings.json"
    r = subprocess.run(["bash", script, "--sarif", str(merged),
                        "--timings", str(timings)],
                       capture_output=True, text=True, env=env,
                       timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "all 7 gates ok" in r.stdout

    lines = calls.read_text().splitlines()
    assert [ln.split()[0].rsplit("/", 1)[-1] for ln in lines] == \
        ["dintlint.py", "dintcost.py", "dintdur.py", "dintplan.py",
         "dintmon.py", "dintcal.py", "dintcal.py", "dintmut.py"]
    # the three matrix gates carry the allowlist-rot dry-run
    assert "--prune-allowlist --check" in lines[0]
    assert "check --prune-allowlist --check" in lines[1]
    assert "check --prune-allowlist --check" in lines[2]
    assert "--static" not in lines[3]        # default: the FULL gate
    assert lines[4].endswith("tests/fixtures/dintmon_counters.json")
    assert os.path.exists(os.path.join(
        REPO, "tests", "fixtures", "dintmon_counters.json"))
    assert "check" in lines[5] and "--sarif" in lines[5]
    assert lines[6].endswith("tests/fixtures/dintcal_journal.jsonl")
    assert os.path.exists(os.path.join(
        REPO, "tests", "fixtures", "dintcal_journal.jsonl"))
    assert "check --quick" in lines[7]       # the dintmut sampled tier

    doc = json.loads(merged.read_text())
    assert doc["version"] == "2.1.0"
    assert sorted(r_["tool"]["driver"]["name"] for r_ in doc["runs"]) \
        == ["dintcal", "dintcost", "dintdur", "dintlint", "dintmut",
            "dintplan"]

    # the per-stage wall-clock block: one JSON line, mirrored to --timings
    tline = next(ln for ln in r.stdout.splitlines()
                 if ln.startswith('{"metric": "dintgate"'))
    tdoc = json.loads(tline)
    assert tdoc == json.loads(timings.read_text())
    assert [s["gate"] for s in tdoc["stages"]] == \
        ["dintlint", "dintcost", "dintdur", "dintplan", "dintmon",
         "dintcal", "dintcal-audit", "dintmut"]
    assert all(s["ok"] is True and s["wall_s"] >= 0
               for s in tdoc["stages"])
    assert tdoc["quick"] is False and tdoc["total_s"] > 0

    # --quick keeps the planner gate static
    calls.write_text("")
    r = subprocess.run(["bash", script, "--quick"], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0
    assert "--static" in calls.read_text().splitlines()[3]

    # one failing gate fails the run BY NAME, the rest still execute
    calls.write_text("")
    r = subprocess.run(["bash", script], capture_output=True, text=True,
                       env=dict(env, FAIL_DUR="1"), timeout=120)
    assert r.returncode == 1
    assert "dintgate: FAIL" in r.stdout and "dintdur" in r.stdout
    assert len(calls.read_text().splitlines()) == 8   # no fail-fast
    tdoc = json.loads(next(ln for ln in r.stdout.splitlines()
                           if ln.startswith('{"metric": "dintgate"')))
    assert [s["gate"] for s in tdoc["stages"]
            if s["ok"] is False] == ["dintdur"]

    # unknown flags are a usage error; --help documents the contract
    assert subprocess.run(["bash", script, "--frobnicate"],
                          capture_output=True, timeout=120).returncode == 2
    h = subprocess.run(["bash", script, "--help"], capture_output=True,
                       text=True, timeout=120)
    assert h.returncode == 0 and "dintplan check" in h.stdout
