"""dintcal: the calibration & prediction-audit plane (ISSUE 18).

The acceptance pins, per ISSUE.md:
  * `dintcal fit` on the checked-in evidence fixture reproduces the
    pinned CALIB.json coefficients bit-for-bit (the closed-form least
    squares is deterministic pure-python arithmetic);
  * `dintcal check` exits 1 NAMING the drifted wave or coefficient on
    injected drift, 0 on the clean fixture;
  * the controller decision journal is a pure function of (schedule,
    seed) under VirtualClock — two runs give byte-identical journals —
    and its shed entries reconcile exactly with the dintmon
    serve_shed_lanes counter;
  * `dintcal audit` replays every recorded width/shed decision
    through the pure policy functions; a hand-tampered decision fails
    the audit naming the entry and block;
  * the calib_check pass fails closed on hand-edited coefficients
    (unfit-model), broken provenance and unregistered waves.

Fixtures regenerate with `python tools/dintcal.py synth`.
"""
import copy
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from dint_tpu.monitor import calib as CAL
from dint_tpu.serve import (ControllerCfg, ServeEngine, ServiceModel,
                            VirtualClock, WidthController,
                            constant_schedule)

pytestmark = pytest.mark.serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EVIDENCE_FIXTURE = os.path.join(REPO, "tests", "fixtures",
                                "dintcal_evidence.json")
JOURNAL_FIXTURE = os.path.join(REPO, "tests", "fixtures",
                               "dintcal_journal.jsonl")
CALIB_PINNED = os.path.join(REPO, "CALIB.json")

REGEN = "regenerate them: python tools/dintcal.py synth"


def _cli_main():
    """The tools/dintcal.py entry point, loaded in-process (argv-driven,
    same exit codes as the subprocess — without a fresh jax import per
    invocation)."""
    spec = importlib.util.spec_from_file_location(
        "dintcal_cli", os.path.join(REPO, "tools", "dintcal.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


# ------------------------------------------------------- closed-form fit


def test_fit_closed_form_exact_on_linear_samples():
    """Samples exactly on a line recover its coefficients exactly (the
    normal equations are pure float arithmetic, rounded to 6 dp)."""
    m = ServiceModel(base_us=200.0, per_lane_ns=25.0)
    samples = [[w, m.service_us(w)] for w in (64, 256, 1024, 4096)]
    fit = CAL.fit_service_model(samples)
    assert fit["base_us"] == 200.0
    assert fit["per_lane_ns"] == 25.0
    assert fit["rms_us"] == 0.0 and fit["max_abs_us"] == 0.0
    assert fit["n"] == 4 and fit["widths"] == [64, 256, 1024, 4096]


def test_fit_requires_two_distinct_widths():
    """One width cannot separate the floor from the slope."""
    with pytest.raises(ValueError, match="distinct widths"):
        CAL.fit_service_model([[256, 160.0], [256, 161.0]])
    with pytest.raises(ValueError, match="distinct widths"):
        CAL.fit_service_model([])


def test_implied_gbps_is_the_reconciliation_unit():
    # 1 MB in 1 ms == 1 GB/s
    assert CAL.implied_gbps(1.0, 1e6) == pytest.approx(1.0)


# ------------------------------------------------- fixtures: drift guard


def test_evidence_fixture_matches_fresh_synth():
    """The checked-in evidence fixture must equal a fresh deterministic
    synthesis — any drift means the synthesizer (or the wave formulas it
    prices with) changed without re-pinning the fixture."""
    with open(EVIDENCE_FIXTURE) as fh:
        pinned = json.load(fh)
    assert pinned == CAL.synthesize_evidence(), (
        "tests/fixtures/dintcal_evidence.json drifted from "
        f"synthesize_evidence() — {REGEN}")


def test_journal_fixture_matches_fresh_synth():
    pinned = CAL.load_journal(JOURNAL_FIXTURE)
    assert pinned == CAL.synthesize_journal(), (
        "tests/fixtures/dintcal_journal.jsonl drifted from "
        f"synthesize_journal() — {REGEN}")


def test_pinned_calib_reproduced_bit_for_bit_from_evidence_fixture():
    """THE fit acceptance pin: refitting the checked-in evidence
    reproduces the pinned CALIB.json exactly — coefficients, wave
    table, provenance hashes, every field."""
    ev = CAL.load_evidence(EVIDENCE_FIXTURE)
    refit = CAL.fit_calib(ev, source="tests/fixtures/dintcal_evidence.json")
    with open(CALIB_PINNED) as fh:
        pinned = json.load(fh)
    assert refit == pinned, (
        "CALIB.json drifted from the evidence fixture — re-pin: "
        "python tools/dintcal.py fit tests/fixtures/dintcal_evidence.json"
        " -o CALIB.json")
    # and the provenance discipline holds on its face
    assert pinned["provenance"]["calib_hash"] == CAL.calib_hash(pinned)
    assert pinned["provenance"]["evidence_hash"] == CAL._digest(ev)


def test_journal_fixture_audits_clean():
    assert CAL.audit_journal(CAL.load_journal(JOURNAL_FIXTURE)) == []


def test_journal_jsonl_roundtrip(tmp_path):
    doc = CAL.synthesize_journal()
    p = tmp_path / "j.jsonl"
    CAL.dump_journal_jsonl(doc, p)
    assert CAL.load_journal(p) == doc
    # header carries the schema + the cfg the auditor replays under
    head = json.loads(p.read_text().splitlines()[0])
    assert head["kind"] == "dintcal_journal"
    assert head["schema"] == 1
    assert tuple(head["cfg"]["widths"]) == ControllerCfg().widths


# ------------------------------------------------------ evidence gather


def test_gather_evidence_deep_walks_artifact_shapes():
    """bench/exp artifacts are nested dicts/lists: controller snapshots
    (service_samples), dintscope breakdown blocks, and serve counter
    dicts are all folded in wherever they appear."""
    snap = {"service_samples": {"n": 3, "samples": [[16, 150.7],
                                                   [64, 152.6]]}}
    art = {
        "metric": "x", "extra": [
            {"controller": snap},
            {"kind": "dintscope_breakdown",
             "waves": {"dint.tatp_dense.lock":
                       {"ms_per_step": 0.01, "bytes_per_step": 1536,
                        "gbps": 0.15},
                       "dint.tatp_dense.arb": {"ms_per_step": 0.02}}},
        ],
        "counters": {"serve_shed_lanes": 7, "other": 1},
    }
    ev = CAL.gather_evidence([art, {"counters": {"serve_shed_lanes": 2}}],
                             sources=["a.json", "b.json"])
    assert ev["samples"] == [[16, 150.7], [64, 152.6]]
    assert ev["waves"]["dint.tatp_dense.lock"]["bytes_per_step"] == 1536
    assert "dint.tatp_dense.arb" in ev["waves"]   # compute-only kept
    assert ev["counters"] == {"serve_shed_lanes": 9}
    assert ev["sources"] == ["a.json", "b.json"]
    # gathering is purely structural: same input, same hash
    assert CAL._digest(ev) == CAL._digest(
        CAL.gather_evidence([art, {"counters": {"serve_shed_lanes": 2}}],
                            sources=["a.json", "b.json"]))


# -------------------------------------------------- tolerance-band check


def test_check_calib_clean_then_names_drift():
    calib = CAL.load_calib(CALIB_PINNED)
    ev = CAL.load_evidence(EVIDENCE_FIXTURE)
    assert CAL.check_calib(calib, ev) == []

    bad = copy.deepcopy(ev)
    bad["samples"] = [[w, us * 1.2] for w, us in bad["samples"]]
    drifts = CAL.check_calib(calib, bad)
    assert {d["name"] for d in drifts} == {"base_us", "per_lane_ns"}
    assert all(d["what"] == "coefficient" for d in drifts)

    bad = copy.deepcopy(ev)
    wave = "dint.tatp_dense.lock"
    bad["waves"][wave]["ms_per_step"] *= 2       # half the implied GB/s
    drifts = CAL.check_calib(calib, bad)
    assert [d["name"] for d in drifts] == [wave]
    assert wave in drifts[0]["message"]

    # within-band noise does NOT drift (tolerance is the contract)
    ok = copy.deepcopy(ev)
    ok["samples"] = [[w, us * 1.01] for w, us in ok["samples"]]
    assert CAL.check_calib(calib, ok) == []


# ------------------------------------------------------------ the audit


def test_audit_names_tampered_decisions():
    doc = CAL.synthesize_journal()
    kinds = [e["kind"] for e in doc["entries"]]
    iw, ish = kinds.index("width"), kinds.index("shed")

    t = copy.deepcopy(doc)
    t["entries"][iw]["decision"]["width"] = 99999
    v = CAL.audit_journal(t)
    assert len(v) == 1 and v[0]["index"] == iw
    assert f"block {doc['entries'][iw]['block']}" in v[0]["message"]

    t = copy.deepcopy(doc)
    t["entries"][ish]["decision"]["shed"] += 1
    v = CAL.audit_journal(t)
    assert len(v) == 1 and v[0]["index"] == ish and v[0]["kind"] == "shed"

    t = copy.deepcopy(doc)
    t["entries"][iw]["kind"] = "mystery"
    assert "unknown journal entry kind" in \
        CAL.audit_journal(t)[0]["message"]

    with pytest.raises(ValueError, match="dintcal_journal"):
        CAL.audit_journal({"kind": "nope"})
    with pytest.raises(ValueError, match="schema"):
        CAL.audit_journal({"kind": "dintcal_journal", "schema": 99})


# ----------------------------------- the engine journal (the producer)

# geometry shared with tests/test_dintserve.py so every jit here is a
# process-wide cache hit
N_ACC = 400
W = 64
CPB = 2


def _overload_engine(seed=0):
    eng = ServeEngine("smallbank_dense", N_ACC,
                      cfg=ControllerCfg(widths=(16, W)),
                      cohorts_per_block=CPB, clock=VirtualClock(),
                      monitor=True, seed=seed)
    eng.run(constant_schedule(800_000.0, 0.01))
    eng.close()
    return eng


def test_engine_journal_deterministic_reconciled_and_audits_clean():
    """The tentpole pins in one trajectory: (a) same (schedule, seed)
    under VirtualClock => BYTE-identical journal; (b) the journal's shed
    entries reconcile exactly with the host shed tally AND the dintmon
    serve_shed_lanes counter; (c) every recorded decision replays
    bit-for-bit through the pure policy functions; (d) the journal rides
    the snapshot (and therefore every bench/exp serve artifact)."""
    a, b = _overload_engine(), _overload_engine()
    doc_a, doc_b = a.ctl.journal_doc(), b.ctl.journal_doc()
    assert json.dumps(doc_a, sort_keys=True) == \
        json.dumps(doc_b, sort_keys=True)

    rep = a.snapshot()
    entries = doc_a["entries"]
    assert {e["kind"] for e in entries} >= {"width", "shed"}
    shed_logged = sum(e["decision"]["shed"] for e in entries
                      if e["kind"] == "shed")
    assert shed_logged == rep["shed"] > 0
    assert shed_logged == rep["counters"]["serve_shed_lanes"]

    assert CAL.audit_journal(doc_a) == []
    # the recorded width decisions ARE the switch trajectory: every
    # switch block appears as a journaled width entry changing width
    switched = [(e["block"], e["decision"]["width"]) for e in entries
                if e["kind"] == "width" and e["switched"]]
    assert switched == [tuple(s) for s in rep["controller"]["switches"]]

    # (d) the journal + the fit-feeding samples ride the snapshot
    assert rep["controller"]["journal"] == entries
    ss = rep["controller"]["service_samples"]
    assert ss["n"] >= len(ss["samples"]) > 0

    # journal meta pins the exact policy the auditor replays under
    assert doc_a["schema"] == 1
    assert doc_a["model"] == {"base_us": a.model.base_us,
                              "per_lane_ns": a.model.per_lane_ns}


def test_controller_journal_matches_policy_reevaluations():
    """Width entries land exactly on the policy re-evaluations (block 0,
    then every block once the hysteresis window has elapsed), replay
    clean, and the fit-sample buffer keeps the FIRST SAMPLE_CAP
    observations while counting all of them."""
    cfg = ControllerCfg()
    ctl = WidthController(cfg, ServiceModel())
    for _ in range(3 * cfg.hysteresis_blocks):
        w = ctl.width()
        ctl.observe_rate(1000.0)
        ctl.observe_service(w, 160.0)
    n_width = sum(e["kind"] == "width" for e in ctl.journal)
    assert n_width == 1 + 2 * cfg.hysteresis_blocks
    assert CAL.audit_journal(ctl.journal_doc()) == []
    ctl2 = WidthController(cfg, ServiceModel())
    for i in range(600):
        ctl2.observe_service(256, 160.0 + i)
    assert ctl2.samples_seen == 600
    assert len(ctl2.samples) == 512     # SAMPLE_CAP, keep-first
    assert ctl2.samples[0] == [256, 160.0]


# -------------------------------------------------- the calib_check pass


def _pass_check(calib):
    from dint_tpu.analysis.passes import calib_check as CC
    return CC.check_calib_doc(calib, "fixture/calib_check",
                              source_dir=REPO)


def broken_calib_findings():
    """The canonical broken calibration fixture (hand-edited coefficient
    => unfit-model + stale-provenance), also imported by test_dintlint's
    every-pass liveness parametrization. Findings anchor to
    fixture/calib_check."""
    doc = CAL.load_calib(CALIB_PINNED)
    doc["model"]["base_us"] += 1.0      # the hand edit the gate exists for
    return _pass_check(doc)


def test_calib_check_clean_on_pinned_artifacts():
    calib = CAL.load_calib(CALIB_PINNED)
    assert _pass_check(calib) == []


def test_calib_check_broken_fixture_fires():
    codes = {f.code for f in broken_calib_findings()}
    assert codes == {"unfit-model", "stale-provenance"}


@pytest.mark.parametrize("mutate,code", [
    (lambda d: d.pop("fit"), "malformed-calib"),
    (lambda d: d["model"].__setitem__("per_lane_ns", float("nan")),
     "malformed-calib"),
    (lambda d: d["provenance"].__setitem__("calib_hash", "0" * 16),
     "stale-provenance"),
    (lambda d: d["samples"].__setitem__(0, [d["samples"][0][0],
                                            d["samples"][0][1] + 5.0]),
     "unfit-model"),
    (lambda d: d["waves"].__setitem__("dint.tatp_dense.nope",
                                     {"ms_per_step": 1.0,
                                      "bytes_per_step": 1.0,
                                      "gbps": 1e-9}),
     "unregistered-wave"),
    (lambda d: d["waves"]["dint.tatp_dense.lock"].__setitem__(
        "gbps", 12345.0), "unregistered-wave"),
])
def test_calib_check_codes_fire(mutate, code):
    doc = CAL.load_calib(CALIB_PINNED)
    mutate(doc)
    if code != "stale-provenance":      # keep the hash consistent so the
        doc["provenance"]["calib_hash"] = CAL.calib_hash(doc)  # code under
    findings = _pass_check(doc)         # test is the one that fires
    assert code in {f.code for f in findings}, \
        [f.code for f in findings]


def test_calib_check_anchoring_and_opt_in(monkeypatch, tmp_path):
    """The registered pass lands whole-artifact findings exactly once
    (the anchor target) and returns [] when calibration is not in use
    (no CALIB.json)."""
    from dint_tpu import analysis
    from dint_tpu.analysis.passes.calib_check import ANCHOR, calib_check

    class _T:                           # a trace stub off-anchor
        name = "smallbank_dense/block"
    assert calib_check(_T()) == []

    class _A:
        name = ANCHOR
    assert calib_check(_A()) == []      # the pinned CALIB.json is clean

    # opt-out world: no calib anywhere
    monkeypatch.setenv(CAL.ENV_CALIB_PATH, str(tmp_path / "none.json"))
    assert calib_check(_A()) == []
    assert not analysis.has_errors([])


# -------------------------------------------------------- the resolver


def test_resolve_service_model_prefers_calib_and_says_so(monkeypatch,
                                                         tmp_path):
    model, meta = CAL.resolve_service_model()      # the pinned CALIB.json
    calib = CAL.load_calib(CALIB_PINNED)
    assert meta["source"] == "calib"
    assert meta["hash"] == calib["provenance"]["calib_hash"]
    assert (model.base_us, model.per_lane_ns) == \
        (calib["model"]["base_us"], calib["model"]["per_lane_ns"])

    monkeypatch.setenv(CAL.ENV_CALIB_PATH, str(tmp_path / "absent.json"))
    model, meta = CAL.resolve_service_model()
    assert meta == {"source": "defaults", "path": None, "hash": None}
    assert (model.base_us, model.per_lane_ns) == (150.0, 40.0)

    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    model, meta = CAL.resolve_service_model(bad)   # soft-fail, never raise
    assert meta["source"] == "defaults"


# ----------------------------------------------------------------- CLI


def test_cli_fit_reproduces_pinned_calib(tmp_path, capsys):
    main = _cli_main()
    out = tmp_path / "CALIB.json"
    rc = main(["fit", EVIDENCE_FIXTURE, "-o", str(out),
               "--source", "tests/fixtures/dintcal_evidence.json",
               "--json"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    with open(CALIB_PINNED) as fh:
        pinned = json.load(fh)
    assert rep["model"] == pinned["model"]
    assert rep["provenance"] == pinned["provenance"]
    assert json.loads(out.read_text()) == pinned


def test_cli_audit_exit_codes(tmp_path, capsys):
    main = _cli_main()
    assert main(["audit", JOURNAL_FIXTURE]) == 0
    capsys.readouterr()

    lines = open(JOURNAL_FIXTURE).read().splitlines()
    e = json.loads(lines[1])
    assert e["kind"] == "width"
    e["decision"]["width"] = 99999      # the hand tamper
    lines[1] = json.dumps(e, sort_keys=True)
    bad = tmp_path / "tampered.jsonl"
    bad.write_text("\n".join(lines) + "\n")
    rc = main(["audit", str(bad), "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["n_violations"] == 1
    assert f"block {e['block']}" in out["violations"][0]["message"]


def test_cli_synth_regenerates_checked_in_fixtures(tmp_path, capsys):
    """File-level drift guard: `dintcal synth` into a scratch dir
    reproduces the checked-in fixture FILES byte-for-byte."""
    main = _cli_main()
    ev, jn = tmp_path / "e.json", tmp_path / "j.jsonl"
    assert main(["synth", "--out-evidence", str(ev),
                 "--out-journal", str(jn)]) == 0
    capsys.readouterr()
    assert ev.read_text() == open(EVIDENCE_FIXTURE).read(), REGEN
    assert jn.read_text() == open(JOURNAL_FIXTURE).read(), REGEN


def test_cli_propose_emits_repin_recipe(tmp_path, capsys):
    main = _cli_main()
    out = tmp_path / "CALIB.proposed.json"
    rc = main(["propose", "--evidence", EVIDENCE_FIXTURE,
               "-o", str(out), "--json"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["delta"]["base_us"]["pinned"] == \
        rep["delta"]["base_us"]["proposed"]     # clean evidence: no move
    assert rep["repin"] == f"mv {out} CALIB.json"
    proposed = json.loads(out.read_text())
    with open(CALIB_PINNED) as fh:
        assert proposed["model"] == json.load(fh)["model"]


def test_cli_check_clean_then_drift_names_offender(tmp_path, capsys):
    """THE check acceptance pin: rc 0 on the clean fixture; rc 1 on
    injected drift, NAMING the wave and the coefficient."""
    main = _cli_main()
    assert main(["check"]) == 0
    capsys.readouterr()

    ev = CAL.load_evidence(EVIDENCE_FIXTURE)
    bad = copy.deepcopy(ev)
    wave = "dint.tatp_dense.install"
    bad["waves"][wave]["ms_per_step"] *= 3
    bad["samples"] = [[w, us * 1.3] for w, us in bad["samples"]]
    bpath = tmp_path / "drifted_evidence.json"
    bpath.write_text(json.dumps(bad))
    rc = main(["check", "--evidence", str(bpath), "--json"])
    rep = json.loads(capsys.readouterr().out)
    assert rc == 1 and not rep["ok"]
    sites = {f["site"] for f in rep["findings"]
             if f["code"] == "evidence-drift"}
    assert f"wave:{wave}" in sites
    assert {"coefficient:base_us", "coefficient:per_lane_ns"} <= sites


def test_cli_describe_reports_resolver_source(capsys):
    main = _cli_main()
    assert main(["describe", "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["resolved_model"]["source"] == "calib"
    assert rep["calib_schema"] == CAL.CALIB_SCHEMA


# --------------------------------------------- dintserve CLI integration


def _serve_cli(*args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dintserve.py"),
         *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=REPO)


def _serve_main():
    """tools/dintserve.py main(), loaded in-process (simulate is pure
    controller math — no engine, no fresh jax import per invocation)."""
    spec = importlib.util.spec_from_file_location(
        "dintserve_cli", os.path.join(REPO, "tools", "dintserve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


def _simulate(main, capsys, monkeypatch, *extra):
    monkeypatch.setattr(sys, "argv",
                        ["dintserve", "simulate", "--rate", "20000000",
                         "--window", "0.004", "--json", *extra])
    assert main() == 0
    return json.loads(capsys.readouterr().out)


def test_dintserve_simulate_reports_model_source(capsys, monkeypatch):
    """Satellite: simulated capacity claims are attributable — the
    simulate report names the ServiceModel source (CALIB.json here;
    explicit flags report source=flags)."""
    main = _serve_main()
    rep = _simulate(main, capsys, monkeypatch)
    calib = CAL.load_calib(CALIB_PINNED)
    assert rep["model"]["source"] == "calib"
    assert rep["model"]["hash"] == calib["provenance"]["calib_hash"]
    assert rep["model"]["base_us"] == calib["model"]["base_us"]
    rep_b = _simulate(main, capsys, monkeypatch, "--model-base-us", "150")
    assert rep_b["model"]["source"] == "flags"
    assert rep_b["model"]["hash"] is None


@pytest.mark.slow
def test_dintserve_run_streams_auditable_journal(tmp_path):
    """Satellite: `dintserve run --journal PATH` streams the decision
    journal as JSONL, and `dintcal audit` replays it clean."""
    jpath = tmp_path / "journal.jsonl"
    c = _serve_cli("run", "--engine", "smallbank_dense", "--size",
                   str(N_ACC), "--rate", "800000", "--window", "0.01",
                   "--widths", f"16,{W}", "--cpb", str(CPB), "--virtual",
                   "--no-gate", "--json", "--journal", str(jpath))
    assert c.returncode == 0, c.stderr
    doc = CAL.load_journal(jpath)
    assert doc["entries"]
    assert CAL.audit_journal(doc) == []
    rep = json.loads(c.stdout.strip().splitlines()[-1])
    assert rep["controller"]["journal"] == doc["entries"]
