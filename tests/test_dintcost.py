"""dintcost: the static cost model and its CI gate.

Liveness: mutated mini-engine fixtures — an extra scatter
dispatch, a doubled gather width, a dropped donation — prove each
cost_budget check fires (naming the offending wave/target) and is
silenceable by a scoped allowlist entry. Soundness: the full target
matrix reconciles against every declared waves.py formula and stays
inside its registered budgets with ZERO cost_budget allowlist entries.
The geometry pins
at the bottom keep the budget ledger's formula variables honest against
the engine modules' real constants.
"""
import contextlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

import dint_tpu.parallel  # noqa: F401 — installs the jax.shard_map shim
from dint_tpu import analysis
from dint_tpu.analysis import allowlist as al
from dint_tpu.analysis import core, cost
from dint_tpu.analysis import targets as T
from dint_tpu.monitor import waves

pytestmark = pytest.mark.cost

S = jax.ShapeDtypeStruct
U32 = jnp.uint32
I32 = jnp.int32

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALLOW = os.path.join(REPO, "tools", "dintlint_allow.json")

# ------------------------------------------------- mini-engine fixtures
#
# One table, one wave-scoped gather whose traffic equals the registered
# magic_gather formula EXACTLY at this geometry (so the clean fixture
# reconciles at ratio 1.0), one unattributed install scatter, donated
# table. Budgets are calibrated from the clean fixture's own derived
# model, then each mutation regresses exactly one number.

WAVE = "dint.tatp_dense.magic_gather"
GEOM = dict(w=8, k=4, vw=2)
DECL = waves.wave_bytes(WAVE, **GEOM)          # = w*k*4 = 128 B
NE = DECL // 4                                  # gather elements
N = 512


def _mini_step(wide=False, extra=False, donate=True):
    ne = NE * (2 if wide else 1)

    def raw(tab, idx, vals):
        with jax.named_scope(WAVE):
            got = tab[idx]                      # ne rows * 4 B
        s = got.sum(dtype=U32)
        tab2 = tab.at[idx[:NE]].set(vals + s, mode="drop",
                                    unique_indices=True)
        if extra:                               # the extra dispatch
            tab2 = tab2.at[idx[:NE]].set(vals ^ s, mode="drop",
                                         unique_indices=True)
        return tab2

    fn = jax.jit(raw, donate_argnums=(0,)) if donate else jax.jit(raw)
    return fn, (S((N,), U32), S((ne,), I32), S((NE,), U32))


@contextlib.contextmanager
def _registered(name, fn, args, meta):
    """Temporarily add a fixture target (+ cost meta) to the registry so
    the real analysis.run plumbing — pass, dedup, allowlist — applies."""
    T.TARGETS[name] = lambda: core.trace_target(name, fn, args)
    T.TARGET_DOCS[name] = "dintcost test fixture"
    T.TARGET_PROTOCOL[name] = ()
    if meta is not None:
        T.TARGET_COST[name] = meta
    try:
        yield
    finally:
        for d in (T.TARGETS, T.TARGET_DOCS, T.TARGET_PROTOCOL,
                  T.TARGET_COST):
            d.pop(name, None)


def _meta(budget):
    return {"steps": 1.0, "geom": dict(GEOM), "wave_expect": {},
            "budget": budget}


def _clean_numbers():
    """Derive the clean fixture once: its numbers calibrate every
    mutated fixture's budget."""
    fn, args = _mini_step()
    model = cost.derive(core.trace_target("fixture_cost/_probe", fn, args),
                        steps=1.0, geom=GEOM)
    return model.dispatches_per_step, model.bytes_per_step, \
        model.footprint_bytes


def _run(name, allowlist_entries=None):
    return analysis.run(targets=[name], passes=["cost_budget"],
                        allowlist_entries=allowlist_entries)


def _err_codes(findings):
    return {f.code for f in findings
            if f.severity == "error" and not f.suppressed}


def test_clean_mini_engine_passes_gate():
    disp, nbytes, fp = _clean_numbers()
    fn, args = _mini_step()
    name = "fixture_cost/clean"
    with _registered(name, fn, args, _meta(
            {"dispatches": disp, "bytes": nbytes, "footprint": fp})):
        fs = _run(name)
        assert not _err_codes(fs), [str(f) for f in fs]
        # and the wave reconciles at exactly the declared formula
        model = cost.model_for(name)
        checks = cost.reconcile_for(name, model)
        assert [c.wave for c in checks] == [WAVE]
        assert checks[0].ratio == pytest.approx(1.0)


def test_extra_scatter_fires_dispatch_budget_and_is_silenceable():
    disp, _, fp = _clean_numbers()
    fn, args = _mini_step(extra=True)
    name = "fixture_cost/extra-dispatch"
    meta = _meta({"dispatches": disp, "bytes": None, "footprint": fp})
    with _registered(name, fn, args, meta):
        fs = _run(name)
        assert _err_codes(fs) == {"over-dispatch-budget"}, \
            [str(f) for f in fs]
        hit = [f for f in fs if f.code == "over-dispatch-budget"]
        assert hit[0].target == name       # the offender is named
        fs2 = _run(name, allowlist_entries=[
            {"pass": "cost_budget", "code": "over-dispatch-budget",
             "target": name, "reason": "fixture: regression on purpose"}])
        assert not analysis.has_errors(fs2)
        assert any(f.suppressed for f in fs2)


def test_doubled_gather_fires_formula_and_bytes_budget():
    disp, nbytes, fp = _clean_numbers()
    fn, args = _mini_step(wide=True)
    name = "fixture_cost/wide-gather"
    # footprint unbudgeted: the wider idx input grows live state too, and
    # this test isolates the byte checks
    meta = _meta({"dispatches": disp, "bytes": nbytes, "footprint": None})
    with _registered(name, fn, args, meta):
        fs = _run(name)
        assert _err_codes(fs) == {"formula-mismatch", "over-bytes-budget"}
        mism = [f for f in fs if f.code == "formula-mismatch"]
        assert mism[0].site == WAVE        # the offending WAVE is named
        assert "2.00" in mism[0].message   # derived = 2x declared
        fs2 = _run(name, allowlist_entries=[
            {"pass": "cost_budget", "code": "formula-mismatch",
             "target": name, "reason": "fixture: doubled on purpose"},
            {"pass": "cost_budget", "code": "over-bytes-budget",
             "target": name, "reason": "fixture: doubled on purpose"}])
        assert not analysis.has_errors(fs2)


def test_dropped_donation_fires_footprint_budget():
    disp, nbytes, fp = _clean_numbers()
    fn, args = _mini_step(donate=False)
    name = "fixture_cost/no-donate"
    meta = _meta({"dispatches": disp, "bytes": nbytes, "footprint": fp})
    with _registered(name, fn, args, meta):
        fs = _run(name)
        assert _err_codes(fs) == {"over-footprint-budget"}, \
            [str(f) for f in fs]
        # dropping donate_argnums re-allocates the table: ~doubled state
        model = cost.model_for(name)
        assert model.footprint_bytes >= fp + N * 4
        fs2 = _run(name, allowlist_entries=[
            {"pass": "cost_budget", "code": "over-footprint-budget",
             "target": name, "reason": "fixture: donation dropped"}])
        assert not analysis.has_errors(fs2)


# ------------------------------------------------------ full-matrix gate


def test_cost_gate_full_matrix_clean_with_zero_allowlist_entries():
    """Acceptance: `dintcost check --all` semantics — the cost_budget
    pass over every registered target, repo allowlist applied, zero
    unsuppressed errors AND zero cost_budget suppressions in the file."""
    findings = analysis.run(
        passes=["cost_budget"],
        allowlist_path=ALLOW if os.path.exists(ALLOW) else None)
    errors = [str(f) for f in findings
              if f.severity == "error" and not f.suppressed]
    assert not errors, "dintcost gate failed:\n" + "\n".join(errors)
    entries = al.load(ALLOW) if os.path.exists(ALLOW) else []
    assert not [e for e in entries if e["pass"] == "cost_budget"], \
        "the dintcost gate must hold without allowlist exceptions"


def test_reconciliation_full_matrix():
    """Every declared waves.py formula a target exercises agrees with
    the derived bytes within tolerance — the hand ledger cannot rot."""
    covered = 0
    for name in sorted(T.TARGET_COST):
        try:
            model = cost.model_for(name)
        except T.SkipTarget:
            continue
        assert not model.error, (name, model.error)
        for c in cost.reconcile_for(name, model):
            assert c.ok, (name, c.wave, c.derived, c.declared,
                          round(c.ratio, 3))
            covered += 1
    assert covered >= 60      # the matrix exercises the formula ledger


def test_wave_registry_complete():
    """Satellite contract: every registered wave has a bytes formula or
    an explicit compute-only / unmodeled doc marker — no silently
    unaccounted wave can enter the registry."""
    for n in waves.ALL_WAVES:
        doc = waves.WAVE_DOCS[n].lower()
        assert (waves.WAVE_BYTES[n] is not None
                or "compute-only" in doc or "unmodeled" in doc), \
            (n, "needs a bytes formula or a compute-only/unmodeled marker")


def test_budget_geometry_pins_engine_constants():
    """The ledger's formula variables against the engine modules' real
    constants — a drifted K/L/VW would silently skew every budget."""
    from dint_tpu.engines import smallbank_pipeline, tatp_pipeline
    assert T._TD_GEOM["k"] == tatp_pipeline.K
    assert T._TD_GEOM["w"] == T._W and T._TD_GEOM["vw"] == T._VW
    assert T._SB_GEOM["l"] == smallbank_pipeline.L
    assert T._SB_GEOM["vw"] == smallbank_pipeline.VW
    assert T._DS_GEOM["d"] == T._MESH_SHARDS
    assert T._DSB_GEOM["d"] == T._MESH_SHARDS
    # every registered target has a complete cost declaration
    assert sorted(T.TARGET_COST) == sorted(T.TARGETS)
    for name, meta in T.TARGET_COST.items():
        assert meta["budget"]["dispatches"] is not None, name
        assert meta["budget"]["footprint"] is not None, name


# --------------------------------------------------------------- the CLI
#
# main() runs in-process (same importlib pattern as the dintlint prune
# test) so the CLI tests reuse this process's TraceCache instead of
# paying a fresh jax import + trace per subprocess — the exit-code and
# JSON-line contract is identical either way.


def _dintcost_main():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_dintcost_cli", os.path.join(REPO, "tools", "dintcost.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


def test_cli_report_check_and_diff(tmp_path, capsys):
    """One CLI round-trip: report -o artifact + --json schema, check
    exit 0, and diff catching an injected regression by name."""
    main = _dintcost_main()
    art = tmp_path / "cost.json"
    assert main(["report", "tatp_dense/block", "tatp_dense/block@hot",
                 "--json", "-o", str(art)]) == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["metric"] == "dintcost"
    assert isinstance(payload["schema"], int)
    e = payload["targets"]["tatp_dense/block@hot"]
    for k in ("bytes_per_step", "dispatches_per_step", "footprint_bytes",
              "waves", "reconcile", "budget", "ledger_bytes"):
        assert k in e
    assert all(c["ok"] for c in e["reconcile"])

    assert main(["check", "--target", "tatp_dense/block@hot",
                 "--json"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["ok"] is True

    mutated = json.loads(art.read_text())
    t = mutated["targets"]["tatp_dense/block"]
    t["dispatches_per_step"] += 1
    wave = "dint.tatp_dense.install"
    t["waves"][wave]["bytes_per_step"] *= 2
    mut = tmp_path / "mutated.json"
    mut.write_text(json.dumps(mutated))
    assert main(["diff", str(art), str(mut), "--json"]) == 1
    d = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    kinds = {(r["kind"], r.get("wave")) for r in d["regressions"]}
    assert ("dispatches", None) in kinds
    assert ("wave-bytes", wave) in kinds
    # and A vs A is clean
    assert main(["diff", str(art), str(art)]) == 0
    capsys.readouterr()


def test_cli_unknown_target_exits_2(capsys):
    main = _dintcost_main()
    with pytest.raises(SystemExit) as exc:
        main(["report", "nope/bad"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "unknown" in err and "tatp_dense/block" in err


# ------------------------------------------- hierarchical route (2-D mesh)


def test_hier_route_strictly_fewer_dcn_bytes_everywhere():
    """Round-14 tentpole, statically: at EVERY calibrated 2-D geometry
    the hierarchical (ici-then-dcn) route moves strictly fewer bytes
    over the dcn axis than its flat tuple-axis twin — the whole reason
    the transport restructure exists. 1-D targets carry no dcn bytes at
    all (the axis split only prices the 2-D mesh)."""
    pairs = 0
    for name, twin in sorted(T.TARGET_FLAT_TWIN.items()):
        mh_, mf_ = cost.model_for(name), cost.model_for(twin)
        assert not mh_.error and not mf_.error, (name, mh_.error)
        assert mh_.dcn_bytes_per_step < mf_.dcn_bytes_per_step, \
            (name, mh_.dcn_bytes_per_step, twin, mf_.dcn_bytes_per_step)
        assert mh_.dcn_bytes_per_step > 0
        assert mh_.axis_bytes_per_step()["ici"] > 0
        pairs += 1
    assert pairs >= 3         # block, block@mon, block@h3
    assert cost.model_for("dense_sharded_sb/block").dcn_bytes_per_step == 0


def test_hier_dominance_finding_fires_when_hier_regresses(monkeypatch):
    """Liveness for the hier-dcn-dominance gate: point a target at
    itself as its own flat twin — equal dcn bytes is NOT strict
    dominance, so the error must fire and name the twin."""
    from types import SimpleNamespace

    from dint_tpu.analysis.passes import cost_budget as cb

    name = "multihost_sb/block@flat"
    model = cost.model_for(name)
    monkeypatch.setitem(T.TARGET_FLAT_TWIN, name, name)
    fs = cb._hier_dominance_findings(SimpleNamespace(name=name), model)
    assert [f.code for f in fs] == ["hier-dcn-dominance"]
    assert fs[0].severity == "error" and fs[0].site == name


# --------------------------------------- double-buffered route (round 18)


def test_overlap_twins_parity_and_priced_footprint_everywhere():
    """Round-18 tentpole, statically: at every calibrated overlap pair
    the double-buffered serve route moves NO MORE dcn-axis link bytes
    per step than the unoverlapped twin it is supposed to hide under,
    and its footprint exceeds the twin's by exactly the priced prefetch
    double buffer (targets.OVERLAP_FOOTPRINT) — the in-flight cohort is
    the ONLY extra state the overlap may hold."""
    pairs = 0
    for name, twin in sorted(T.TARGET_OVERLAP_TWIN.items()):
        mo, mt = cost.model_for(name), cost.model_for(twin)
        assert not mo.error and not mt.error, (name, mo.error, mt.error)
        assert mo.dcn_bytes_per_step <= mt.dcn_bytes_per_step, \
            (name, mo.dcn_bytes_per_step, twin, mt.dcn_bytes_per_step)
        allowance = cost.eval_budget_bytes(T.OVERLAP_FOOTPRINT,
                                           mo.geom, 0.0)
        assert allowance and allowance > 0, (name, mo.geom)
        extra = mo.footprint_bytes - mt.footprint_bytes
        assert 0 < extra <= allowance, (name, extra, allowance)
        pairs += 1
    assert pairs >= 2         # serve@overlap, serve@overlap+mon


def test_overlap_findings_fire_on_regression(monkeypatch):
    """Liveness for the round-18 overlap gates: (a) pointing a target
    whose route moves MORE dcn bytes at a cheaper twin must fire
    overlap-dcn-parity; (b) a target carrying state past the priced
    double buffer must fire overlap-footprint. Both name the twin."""
    from types import SimpleNamespace

    from dint_tpu.analysis.passes import cost_budget as cb

    # (a) the flat serve lowering moves MORE dcn bytes than the
    # hierarchical serve target — parity must fire
    name = "multihost_sb/serve@flat"
    model = cost.model_for(name)
    monkeypatch.setitem(T.TARGET_OVERLAP_TWIN, name, "multihost_sb/serve")
    fs = cb._overlap_findings(SimpleNamespace(name=name), model)
    assert "overlap-dcn-parity" in [f.code for f in fs]
    assert all(f.severity == "error" and f.site == "multihost_sb/serve"
               for f in fs)

    # (b) the trace variant carries event-ring state far past the
    # priced prefetch buffer — footprint must fire
    name2 = "multihost_sb/block@trace"
    model2 = cost.model_for(name2)
    monkeypatch.setitem(T.TARGET_OVERLAP_TWIN, name2, "multihost_sb/block")
    fs2 = cb._overlap_findings(SimpleNamespace(name=name2), model2)
    assert "overlap-footprint" in [f.code for f in fs2]


def test_prune_check_is_a_gate_scoped_dry_run(tmp_path, capsys):
    """The stale-entry contract, shared verbatim with dintlint and
    dintdur: `check --prune-allowlist --check` is a DRY RUN that fails
    (exit 1) on a stale cost_budget entry without touching the file;
    without --check the stale entry is dropped — but ONLY entries
    scoped to this gate's pass. Wildcard-pass entries and entries for
    other passes belong to dintlint's full-suite prune and survive."""
    main = _dintcost_main()
    entries = json.loads(
        open(os.path.join(REPO, "tools", "dintlint_allow.json")).read())
    n_repo = len(entries)
    entries += [
        {"pass": "cost_budget", "code": "no-such-code",
         "reason": "stale on purpose"},
        {"pass": "*", "code": "no-such-code",
         "reason": "wildcard: only dintlint may judge this"},
    ]
    path = tmp_path / "allow.json"
    path.write_text(json.dumps(entries))
    before = path.read_text()

    # dry run: exit 1, file NOT rewritten, offender named
    assert main(["check", "--prune-allowlist", "--check",
                 "--allowlist", str(path)]) == 1
    assert path.read_text() == before
    out = capsys.readouterr().out
    assert "NOT rewritten" in out
    assert "cost_budget/no-such-code" in out

    # real prune: exit 0, ONLY the gate-scoped stale entry dropped
    assert main(["check", "--prune-allowlist",
                 "--allowlist", str(path)]) == 0
    capsys.readouterr()
    pruned = json.loads(path.read_text())
    assert len(pruned) == n_repo + 1
    assert not any(e["pass"] == "cost_budget" for e in pruned)
    assert any(e["pass"] == "*" and e["code"] == "no-such-code"
               for e in pruned)          # dintlint's problem, kept

    # usage discipline: --check only modifies --prune-allowlist, and
    # the prune needs the gate's full matrix (no --target)
    with pytest.raises(SystemExit):
        main(["check", "--all", "--check"])
    with pytest.raises(SystemExit):
        main(["check", "--prune-allowlist", "--target",
              "tatp_dense/block", "--allowlist", str(path)])


def test_every_scan_target_beats_point_probes_per_row():
    """The round-20 dintscan bandwidth claim, statically: every @scan
    target's dint.store.scan wave must deliver reply rows STRICTLY
    cheaper (HBM bytes/row) than its point twin's dint.store.probe
    wave prices a probed reply (bytes/probe) — the same inequality the
    standing scan-bytes-dominance gate enforces, pinned here with the
    actual numbers so a silent geometry drift is loud."""
    pairs = 0
    for name, twin in sorted(T.TARGET_SCAN_TWIN.items()):
        try:
            ms, mt = cost.model_for(name), cost.model_for(twin)
        except T.SkipTarget:
            continue
        assert not ms.error and not mt.error, (name, ms.error, mt.error)
        geom = ms.geom or {}
        w, sl = float(geom["w"]), float(geom["sl"])
        scan_b = ms.wave_bytes_per_step().get("dint.store.scan", 0.0)
        probe_b = mt.wave_bytes_per_step().get("dint.store.probe", 0.0)
        assert scan_b > 0 and probe_b > 0, (name, scan_b, twin, probe_b)
        per_row, per_probe = scan_b / (w * sl), probe_b / w
        assert per_row < per_probe, (name, per_row, twin, per_probe)
        pairs += 1
    assert pairs >= 2     # block@scan, serve@scan
