"""dintmut: the mutation-coverage plane proven on the pinned artifact.

Covers the acceptance contract of the mutation gate:
  * the operator registry and the quick sample are deterministic
    (hashes and draws reproduce bit-for-bit),
  * mutant discovery on a live trace reproduces the pinned cell ids and
    every discovered mutant builds a walkable ClosedJaxpr,
  * the pinned MUTCOV.json attributes >= 1 kill to every operator's
    expected pass family and to every required gate family,
  * survivor triage = an allowlist entry pinned to the CELL ID; a
    mis-scoped entry suppresses nothing,
  * every drift class (edited cells, edited summary, forged quick
    sample, missing/mis-schemaed artifact) fails closed with a
    regeneration hint,
  * the ring-family cells stay cross-referenced against the ONE standing
    durability/no-ring-truncation allowlist entry,
  * the CLI round-trips (report/check/describe, --json payloads, exit
    discipline) — in-process, sharing the TraceCache.

The full-matrix re-execution (every mutant re-run, bit-for-bit against
the pinned rows) is the slow tier; tier-1 re-executes one pinned
quick-sample cell on the anchor target.
"""
import copy
import json
import os

import pytest

from dint_tpu import analysis
from dint_tpu.analysis import allowlist as al
from dint_tpu.analysis import mutate as M
from dint_tpu.analysis import targets as T
from dint_tpu.analysis.passes import mut_check as MC

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MUTCOV_PINNED = os.path.join(REPO, "MUTCOV.json")
ANCHOR = "tatp_dense/block"

pytestmark = pytest.mark.mut

_DOC = None


def _doc() -> dict:
    """A fresh deep copy of the pinned MUTCOV.json (loaded once)."""
    global _DOC
    if _DOC is None:
        _DOC = M.load_mutcov(MUTCOV_PINNED)
    return copy.deepcopy(_DOC)


def _repin(doc: dict) -> dict:
    """Re-derive summary/quick/provenance after a cell edit, so ONLY the
    policy checks see the edit (provenance/summary checks stay green)."""
    doc["summary"] = M._summary(doc["cells"])
    doc["quick"] = {"seed": M.QUICK_SEED,
                    "cells": M.quick_sample(doc["cells"], M.QUICK_SEED)}
    doc["provenance"] = {"registry": M.registry_hash(),
                         "matrix": M.matrix_hash(),
                         "cells": M._digest(doc["cells"])}
    return doc


def codes(findings):
    return {f.code for f in findings}


# ------------------------------------------------------------ determinism


def test_registry_and_matrix_hashes_are_deterministic():
    assert M.registry_hash() == M.registry_hash()
    assert M.matrix_hash() == M.matrix_hash()
    # the digest is order-insensitive over dict keys (sort_keys pinned)
    assert M._digest({"a": 1, "b": 2}) == M._digest({"b": 2, "a": 1})
    assert M._digest([1, 2]) != M._digest([2, 1])


def test_quick_sample_is_deterministic_and_pinned():
    doc = _doc()
    cells = doc["cells"]
    seed = doc["quick"]["seed"]
    draw1 = M.quick_sample(cells, seed)
    draw2 = M.quick_sample(cells, seed)
    assert draw1 == draw2 == doc["quick"]["cells"]
    # one representative per operator, all real cell ids
    ids = {c["id"] for c in cells}
    assert set(draw1) <= ids
    assert len({i.split("|")[1] for i in draw1}) == len(draw1)


def test_discovery_reproduces_the_pinned_anchor_cells():
    """Mutant discovery on a live trace is deterministic and matches the
    pinned matrix: same cell ids, same sites, same notes."""
    trace = T.get_trace(ANCHOR)
    ops = _doc()["targets"][ANCHOR]["operators"]
    muts1 = M.discover(trace, ops)
    muts2 = M.discover(trace, ops)
    assert [m.cell_id for m in muts1] == [m.cell_id for m in muts2]
    assert [(m.site, m.note) for m in muts1] \
        == [(m.site, m.note) for m in muts2]
    pinned = [(c["id"], c["site"], c["note"]) for c in _doc()["cells"]
              if c["target"] == ANCHOR]
    assert [(m.cell_id, m.site, m.note) for m in muts1] == pinned


def test_cell_sites_carry_no_line_number(monkeypatch):
    """A cell's site is 'file.py:function': the pinned cells name no
    line, and a blank line inserted above every mutated function (a
    frame stub offsetting each line by one) leaves the discovered cells
    and their hash as they were."""
    import re
    # '' where the eqn recorded no source (a top-level pjit's donation)
    site = re.compile(r"^([\w./-]+\.py:[A-Za-z_<][\w.<>]*)?$")
    assert all(site.match(c["site"]) for c in _doc()["cells"]), \
        [c["site"] for c in _doc()["cells"] if not site.match(c["site"])]

    trace = T.get_trace(ANCHOR)
    ops = _doc()["targets"][ANCHOR]["operators"]

    def cells():
        return [(m.cell_id, m.site, m.note) for m in M.discover(trace, ops)]

    before = cells()
    assert before and all(site.match(s) for _, s, _ in before)
    real = M.user_frame_of

    def shifted(eqn):
        frame = real(eqn)
        return frame and (frame[0], frame[1] + 1, frame[2])
    monkeypatch.setattr(M, "user_frame_of", shifted)
    assert cells() == before
    assert M._digest(cells()) == M._digest(before)


def test_every_discovered_mutant_builds():
    """Each mutant rewrite produces a ClosedJaxpr the passes can walk —
    the corruption is structural, never a crash of the mutator itself."""
    import jax._src.core as jcore
    trace = T.get_trace(ANCHOR)
    muts = M.discover(trace, _doc()["targets"][ANCHOR]["operators"])
    assert muts, "anchor target produced no mutants"
    for m in muts:
        mutated = m.build(trace.closed_jaxpr)
        assert isinstance(mutated, jcore.ClosedJaxpr)
        # the rewrite returned a NEW object; the cached trace is intact
        assert mutated is not trace.closed_jaxpr


# ------------------------------------------------- pinned-evidence policy


def test_pinned_matrix_clears_the_policy_bar():
    """The committed MUTCOV.json is itself gate-clean: kill rate over
    floor, no dormant operator, every required family attributed."""
    doc = _doc()
    s = doc["summary"]
    assert s["kill_rate"] >= doc["kill_rate_floor"]
    assert s["n_cells"] == len(doc["cells"])
    fs = MC.check_mutcov(doc, ANCHOR)
    # survivors are the only permitted errors, and each one is triaged
    # by a site-pinned entry in the shared repo allowlist
    assert codes(fs) <= {"survivor"}
    entries = al.load(os.path.join(REPO, "tools", "dintlint_allow.json"))
    fs = al.apply(fs, entries, check_unused=False)
    assert not analysis.has_errors(fs)


def test_every_operator_kills_within_its_expected_family():
    """>= 1 kill per operator, attributed to a pass that operator's
    registry entry declares it expects — the per-operator kill proof."""
    doc = _doc()
    by_op: dict[str, list[dict]] = {}
    for c in doc["cells"]:
        by_op.setdefault(c["operator"], []).append(c)
    assert set(by_op) == set(M.OPERATORS), "matrix lost an operator"
    for name, cells in by_op.items():
        killed = [c for c in cells if c["verdict"] == "killed"]
        assert killed, f"operator {name} killed nothing"
        expect = {e.split("/", 1)[0] for e in M.OPERATORS[name].expect}
        for c in killed:
            kpass = c["killer"].split("/", 1)[0]
            assert kpass in expect, \
                f"{c['id']}: killer {c['killer']} outside {expect}"


def test_required_families_each_attribute_a_kill():
    killers = set(_doc()["summary"]["killer_passes"])
    assert "protocol" in killers
    assert "durability" in killers
    assert "cost_budget" in killers
    assert killers & MC._CORE_PASSES, "no core dintlint pass kills"


# ------------------------------------------------------- survivor triage


def test_survivor_triage_is_pinned_to_the_cell_id(tmp_path):
    """A survivor is one ERROR whose site is the cell id; only an
    allowlist entry pinned to that exact cell suppresses it."""
    doc = _repin(_doc())
    survivors = [c for c in doc["cells"] if c["verdict"] == "survived"]
    assert survivors, "pinned matrix lost its documented survivors"
    cid = survivors[0]["id"]
    fs = MC.check_mutcov(doc, ANCHOR)
    mine = [f for f in fs if f.code == "survivor" and f.site == cid]
    assert len(mine) == 1

    scoped = [{"pass": "mut_check", "code": "survivor", "site": cid,
               "reason": "documented non-goal (test)"}]
    fs = al.apply(MC.check_mutcov(doc, ANCHOR), scoped,
                  check_unused=False)
    assert not any(f.site == cid and not f.suppressed for f in fs
                   if f.code == "survivor")

    elsewhere = [{"pass": "mut_check", "code": "survivor",
                  "site": "some/other|cell|9", "reason": "mis-scoped"}]
    fs = al.apply(MC.check_mutcov(doc, ANCHOR), elsewhere,
                  check_unused=False)
    assert any(f.site == cid and not f.suppressed for f in fs)


def test_untriaged_survivor_fails_the_gate():
    """Flipping a killed cell to survived (and re-pinning hashes so only
    policy sees it) leaves an unsuppressed survivor ERROR."""
    doc = _doc()
    victim = next(c for c in doc["cells"] if c["verdict"] == "killed")
    victim["verdict"], victim["killer"] = "survived", None
    victim["new_errors"] = []
    _repin(doc)
    fs = MC.check_mutcov(doc, ANCHOR)
    assert any(f.code == "survivor" and f.site == victim["id"]
               for f in fs)
    entries = al.load(os.path.join(REPO, "tools", "dintlint_allow.json"))
    fs = al.apply(fs, entries, check_unused=False)
    assert analysis.has_errors(fs)   # the repo triage does not cover it


# ------------------------------------------------------------ drift guard


def test_edited_cells_trip_stale_provenance_with_regen_hint():
    doc = _doc()
    doc["cells"][0]["verdict"] = "survived"
    fs = MC.check_mutcov(doc, ANCHOR)
    stale = [f for f in fs if f.code == "stale-provenance"]
    assert any(f.site == "cells" for f in stale)
    assert all("dintmut.py run" in f.suggestion for f in stale)


def test_edited_summary_trips_summary_drift():
    doc = _doc()
    doc["summary"]["kill_rate"] = 1.0
    doc["summary"]["n_survived"] = 0
    fs = MC.check_mutcov(doc, ANCHOR)
    assert any(f.code == "summary-drift" and f.site == "summary"
               for f in fs)


def test_forged_quick_sample_trips_summary_drift():
    doc = _doc()
    doc["quick"]["cells"] = doc["quick"]["cells"][:-1]
    fs = MC.check_mutcov(doc, ANCHOR)
    assert any(f.code == "summary-drift" and f.site == "quick"
               for f in fs)


def test_kill_rate_floor_and_dormant_operator_fire():
    doc = _doc()
    for c in doc["cells"]:
        if c["operator"] == "drop-eqn":
            c["verdict"], c["killer"] = "survived", None
            c["new_errors"] = []
    _repin(doc)
    fs = MC.check_mutcov(doc, ANCHOR)
    assert "kill-rate-floor" in codes(fs)        # 10/34 flipped

    doc = _doc()
    doc["cells"] = [c for c in doc["cells"]
                    if c["operator"] != "drop-donation"]
    _repin(doc)
    fs = MC.check_mutcov(doc, ANCHOR)
    assert any(f.code == "operator-dormant" and f.site == "drop-donation"
               for f in fs)


def test_attribution_gap_fires_when_a_family_stops_killing():
    doc = _doc()
    for c in doc["cells"]:
        if c["killer"] and c["killer"].startswith("cost_budget/"):
            c["killer"] = "protocol/unlocked-install"
    _repin(doc)
    fs = MC.check_mutcov(doc, ANCHOR)
    assert any(f.code == "attribution-gap" and f.site == "cost_budget"
               for f in fs)


def test_missing_and_mis_schemaed_artifacts_fail_closed(tmp_path):
    doc, fs = MC.load_mutcov_findings(ANCHOR, str(tmp_path / "no.json"))
    assert doc is None and codes(fs) == {"missing-mutcov"}
    assert "dintmut.py run" in fs[0].suggestion

    bad = tmp_path / "old.json"
    old = _doc()
    old["schema"] = M.SCHEMA + 1
    bad.write_text(json.dumps(old))
    with pytest.raises(ValueError, match="dintmut.py run"):
        M.load_mutcov(str(bad))
    doc, fs = MC.load_mutcov_findings(ANCHOR, str(bad))
    assert doc is None and codes(fs) == {"malformed-mutcov"}


def test_structure_findings_short_circuit():
    doc = _doc()
    del doc["summary"]
    del doc["cells"][0]["killer"]
    fs = MC.check_mutcov(doc, ANCHOR)
    assert codes(fs) == {"malformed-mutcov"}     # nothing else piles on


# ------------------------------------------------------------ ring hygiene


def test_ring_cells_cite_the_standing_truncation_entry(tmp_path):
    doc = _doc()
    ring = [c for c in doc["cells"] if c["operator"] == "ring-shrink"]
    assert ring, "matrix lost its ring-shrink cells"
    for c in ring:
        assert MC._RING_ENTRY in c["suppressed"]

    # a ring cell that stops recording the suppression = drift
    ring[0]["suppressed"] = [s for s in ring[0]["suppressed"]
                             if s != MC._RING_ENTRY]
    _repin(doc)
    fs = MC.check_mutcov(doc, ANCHOR)
    assert any(f.code == "ring-triage-drift" and f.site == ring[0]["id"]
               for f in fs)

    # the standing entry vanishing from the allowlist = drift too
    bare = tmp_path / "allow.json"
    bare.write_text(json.dumps([]))
    fs = MC.check_mutcov(_doc(), ANCHOR, allow_path=str(bare))
    assert any(f.code == "ring-triage-drift"
               and f.site == MC._RING_ENTRY for f in fs)


# --------------------------------------------------- re-execution tiers


def test_quick_cell_reexecutes_bit_for_bit():
    """Tier-1 re-execution: the anchor's pinned quick-sample cell re-runs
    and reproduces its pinned row exactly (the dintgate --quick tier runs
    the whole sample; one target keeps this inside the tier-1 budget)."""
    doc = _doc()
    ids = [i for i in doc["quick"]["cells"]
           if i.split("|")[0] == ANCHOR]
    assert ids, "quick sample no longer covers the anchor"
    fresh = M.run_cells(ids)
    pinned = {c["id"]: c for c in doc["cells"]}
    for cell in fresh:
        want = pinned[cell["id"]]
        for k in ("verdict", "killer", "site", "note", "new_errors",
                  "suppressed"):
            assert cell[k] == want[k], (cell["id"], k)


@pytest.mark.slow
def test_full_matrix_reproduces_pinned_rows():
    """The slow tier: every mutant re-executes and the whole document
    (cells, summary, quick draw, provenance) reproduces bit-for-bit."""
    fresh = M.run_matrix()
    pinned = _doc()
    assert fresh["cells"] == pinned["cells"]
    assert fresh["summary"] == pinned["summary"]
    assert fresh["quick"] == pinned["quick"]
    assert fresh["provenance"] == pinned["provenance"]


# ------------------------------------------------------------------- CLI


def _dintmut_main():
    """Load tools/dintmut.py as a module so main() runs in-process and
    shares this process's TraceCache (no subprocess re-tracing)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_dintmut_cli", os.path.join(REPO, "tools", "dintmut.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


def test_cli_report_round_trip(capsys):
    main = _dintmut_main()
    assert main(["report", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["metric"] == "mutation-coverage"
    assert payload["mode"] == "report" and payload["ok"] is True
    assert payload["summary"] == _doc()["summary"]
    assert payload["quick"] == _doc()["quick"]

    assert main(["report"]) == 0
    out = capsys.readouterr().out
    assert "killed" in out and "quick sample" in out
    for cid in (c["id"] for c in _doc()["cells"]
                if c["verdict"] == "survived"):
        assert cid in out                       # survivors always shown


def test_cli_describe_lists_every_operator(capsys):
    main = _dintmut_main()
    assert main(["describe"]) == 0
    out = capsys.readouterr().out
    for name in M.OPERATORS:
        assert name in out
    assert "survivor" in out and "kill-rate-floor" in out


def test_cli_check_quick_passes_on_pinned_artifact(capsys):
    """`dintmut check --quick` (the dintgate tier): static policy gate +
    the pinned deterministic sample re-executed, exit 0 on this tree."""
    main = _dintmut_main()
    assert main(["check", "--quick", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["metric"] == "mutation-coverage"
    assert payload["mode"] == "quick" and payload["ok"] is True
    for k in ("schema", "targets", "allowlist", "n_findings", "n_errors",
              "n_suppressed", "stale_allowlist", "mutcov", "findings"):
        assert k in payload
    # the two documented survivors ride through as SUPPRESSED findings
    assert payload["n_errors"] == 0
    assert payload["n_suppressed"] >= 2


def test_cli_check_fails_on_stale_artifact(tmp_path, capsys, monkeypatch):
    doc = _doc()
    doc["cells"][0]["verdict"] = "survived"
    path = tmp_path / "MUTCOV.json"
    path.write_text(json.dumps(doc))
    monkeypatch.setenv(M.ENV_MUTCOV, str(path))
    main = _dintmut_main()
    assert main(["check", "--quick"]) == 1
    out = capsys.readouterr().out
    assert "stale-provenance" in out


def test_cli_report_missing_artifact_exits_2(tmp_path, capsys,
                                             monkeypatch):
    monkeypatch.setenv(M.ENV_MUTCOV, str(tmp_path / "nope.json"))
    main = _dintmut_main()
    assert main(["report"]) == 2
    assert "dintmut:" in capsys.readouterr().err


# ------------------------------------------------------- lint integration


def broken_mutcov_findings():
    """The canonical broken mutation fixture (a killed cell hand-flipped
    to survived => stale-provenance + survivor), also imported by
    test_dintlint's every-pass liveness parametrization. Findings anchor
    to fixture/mut_check."""
    doc = _doc()
    victim = next(c for c in doc["cells"] if c["verdict"] == "killed")
    victim["verdict"], victim["killer"] = "survived", None
    return MC.check_mutcov(doc, "fixture/mut_check")


def test_mut_check_broken_fixture_fires():
    fs = broken_mutcov_findings()
    assert "stale-provenance" in codes(fs)
    assert "survivor" in codes(fs)


def test_mut_check_anchors_to_one_target(monkeypatch):
    """The pass lands its whole-artifact findings exactly once: on the
    anchor target, [] everywhere else."""
    from dint_tpu.analysis.core import TargetTrace
    off = TargetTrace("smallbank_dense/block", None)
    assert MC.mut_check(off) == []
    monkeypatch.setenv(MC.ENV_MUT_ANCHOR, "smallbank_dense/block")
    assert MC._anchor() == "smallbank_dense/block"
