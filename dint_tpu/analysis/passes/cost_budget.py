"""dintcost gate: the derived cost model vs. ledger, budgets, dominance.

dintlint proves the hot paths are safe; this pass proves they are not
QUIETLY GETTING SLOWER. analysis/cost.py derives per-target bytes/step,
dispatches/step and persistent footprint from the traced jaxpr; this
pass fails closed on three checks (ANALYSIS.md "Static cost model"):

  formula-mismatch        a wave's derived bytes left the tolerance band
                          around its waves.py declared formula (after the
                          target's registered wave_expect adjustment) —
                          the hand ledger and the code disagree, one of
                          them rotted
  over-dispatch-budget    more memory-op dispatches per step than the
                          target's registered budget: an extra
                          gather/scatter slipped into the chain
  over-bytes-budget       derived bytes/step above the budget formula
                          (typically "1.25*ledger"): doubled traffic
  over-footprint-budget   donation-aware live state grew past budget: a
                          dropped donate_argnums doubles a table
  hier-dcn-dominance      a hierarchical 2-D mesh target no longer
                          schedules STRICTLY fewer DCN-axis link bytes
                          per step than its flat tuple-axis collective
                          twin (targets.TARGET_FLAT_TWIN) — the whole
                          point of routing ici-then-dcn; checked at
                          every calibrated 2-D geometry, no allowlist
                          entries tolerated
  overlap-dcn-parity      a double-buffered serve target schedules MORE
                          DCN-axis link bytes per step than its
                          unoverlapped twin (targets.TARGET_OVERLAP_TWIN)
                          — overlap exists to HIDE the exchange under the
                          lock wave, never to inflate it (round 18)
  overlap-footprint       the overlapped carry grew past the twin's
                          footprint plus the priced prefetch double
                          buffer (targets.OVERLAP_FOOTPRINT): the
                          in-flight cohort buffer is the ONLY extra state
                          the overlap is allowed to hold
  scan-bytes-dominance    an @scan store target's sequential slab no
                          longer derives STRICTLY fewer HBM bytes per
                          reply row (dint.store.scan / (w*sl)) than its
                          point twin pays per probe reply
                          (dint.store.probe / w, targets.
                          TARGET_SCAN_TWIN) — rows must arrive cheaper
                          than probes, the dintscan bandwidth claim
                          (round 20); no allowlist entries tolerated

Every finding names the offending wave/target in `site` and is
silenceable through the shared dintlint allowlist with a reviewed
reason. Budgets live in targets.TARGET_COST — the calibration ledger at
the bottom of analysis/targets.py; recalibrating a number is a reviewed
diff of that table, never an edit to this pass.
"""
from __future__ import annotations

from .. import cost
from ..core import (Finding, SEV_ERROR, SEV_WARNING, TargetTrace,
                    register_pass)


def _budget_findings(trace: TargetTrace, meta: dict,
                     model: cost.CostModel) -> list[Finding]:
    out: list[Finding] = []
    bud = meta.get("budget") or {}
    disp = model.dispatches_per_step
    nbytes = model.bytes_per_step

    b_disp = bud.get("dispatches")
    if b_disp is not None and disp > float(b_disp) + 1e-9:
        out.append(Finding(
            "cost_budget", "over-dispatch-budget", SEV_ERROR, trace.name,
            f"{disp:g} memory-op dispatches/step, budget {b_disp:g}: an "
            "extra gather/scatter/collective entered the chain",
            site="(per-step)",
            suggestion="fuse the new op into an existing wave or "
                       "recalibrate the budget in targets.TARGET_COST "
                       "with the regression justified in the PR"))

    ledger = cost.ledger_bytes(model, meta.get("wave_expect"))
    b_bytes = cost.eval_budget_bytes(bud.get("bytes"), model.geom, ledger)
    if b_bytes is not None and nbytes > b_bytes + 1e-6:
        out.append(Finding(
            "cost_budget", "over-bytes-budget", SEV_ERROR, trace.name,
            f"{nbytes:g} derived HBM bytes/step, budget {b_bytes:g} "
            f"(formula {bud.get('bytes')!r}, ledger {ledger:g}): row "
            "traffic grew past the declared ledger band",
            site="(per-step)",
            suggestion="find the widened gather/scatter with "
                       "`tools/dintcost.py report <target>`"))

    b_fp = bud.get("footprint")
    if b_fp is not None and model.footprint_bytes > int(b_fp):
        out.append(Finding(
            "cost_budget", "over-footprint-budget", SEV_ERROR, trace.name,
            f"{model.footprint_bytes} B persistent footprint, budget "
            f"{b_fp} B: an output buffer no longer reuses a donated "
            "input (dropped donate_argnums?)",
            site="(footprint)",
            suggestion="restore the donation (aliasing pass docs) or "
                       "recalibrate with the new allocation justified"))
    return out


def _reconcile_findings(trace: TargetTrace, meta: dict,
                        model: cost.CostModel) -> list[Finding]:
    out: list[Finding] = []
    for c in cost.reconcile(model, wave_expect=meta.get("wave_expect"),
                            tol_overrides=meta.get("tol")):
        if c.ok:
            continue
        exp = f" (wave_expect {c.expect!r} applied)" if c.expect else ""
        mem = "" if c.members == (c.wave,) else \
            f" [folded: {', '.join(c.members)}]"
        out.append(Finding(
            "cost_budget", "formula-mismatch", SEV_ERROR, trace.name,
            f"derived {c.derived:g} B/step vs declared "
            f"{c.declared:g} B/step{exp} (ratio {c.ratio:.2f}, tolerance "
            f"{c.tol:g}){mem}: the waves.py formula and the traced code "
            "disagree — one of them rotted",
            site=c.wave,
            suggestion="fix the formula in monitor/waves.py if the code "
                       "is right, or the code if the ledger is; document "
                       "a real layout deviation as wave_expect in "
                       "targets.TARGET_COST"))
    return out


def _hier_dominance_findings(trace: TargetTrace,
                             model: cost.CostModel) -> list[Finding]:
    from .. import targets as T
    twin = T.TARGET_FLAT_TWIN.get(trace.name)
    if not twin or twin not in T.TARGETS:
        return []
    try:
        twin_model = cost.model_for(twin)
    except Exception:  # noqa: BLE001 — twin untraceable here (topology)
        return []
    if twin_model.error:
        return []
    hier, flat = model.dcn_bytes_per_step, twin_model.dcn_bytes_per_step
    if hier >= flat:
        return [Finding(
            "cost_budget", "hier-dcn-dominance", SEV_ERROR, trace.name,
            f"{hier:g} DCN-axis link bytes/step vs flat twin {twin} at "
            f"{flat:g}: the hierarchical (ici-then-dcn) route no longer "
            "moves strictly fewer bytes over the slow axis — the "
            "transport restructure lost its reason to exist",
            site=twin,
            suggestion="a collective fell back onto the dcn (or tuple) "
                       "axis — diff the per-wave ici_bytes/dcn_bytes "
                       f"blocks of `tools/dintcost.py report {trace.name} "
                       f"{twin} --json`")]
    return []


def _overlap_findings(trace: TargetTrace,
                      model: cost.CostModel) -> list[Finding]:
    from .. import targets as T
    twin = T.TARGET_OVERLAP_TWIN.get(trace.name)
    if not twin or twin not in T.TARGETS:
        return []
    try:
        twin_model = cost.model_for(twin)
    except Exception:  # noqa: BLE001 — twin untraceable here (topology)
        return []
    if twin_model.error:
        return []
    out: list[Finding] = []
    dcn, dcn_t = model.dcn_bytes_per_step, twin_model.dcn_bytes_per_step
    if dcn > dcn_t:
        out.append(Finding(
            "cost_budget", "overlap-dcn-parity", SEV_ERROR, trace.name,
            f"{dcn:g} DCN-axis link bytes/step vs unoverlapped twin "
            f"{twin} at {dcn_t:g}: the double-buffered route moves MORE "
            "bytes over the slow axis than the route it is supposed to "
            "hide — prefetch duplicated an exchange",
            site=twin,
            suggestion="the prefetched buckets must be CONSUMED next "
                       "step, never re-exchanged — diff the per-wave "
                       "dcn_bytes blocks of `tools/dintcost.py report "
                       f"{trace.name} {twin} --json`"))
    allowance = cost.eval_budget_bytes(T.OVERLAP_FOOTPRINT, model.geom,
                                       0.0) or 0.0
    fp, fp_t = model.footprint_bytes, twin_model.footprint_bytes
    if fp > fp_t + allowance:
        out.append(Finding(
            "cost_budget", "overlap-footprint", SEV_ERROR, trace.name,
            f"{fp} B persistent footprint vs twin {twin} at {fp_t} B + "
            f"{allowance:g} B priced double buffer "
            f"(targets.OVERLAP_FOOTPRINT): the overlap carry holds more "
            "than the one in-flight cohort it is allowed",
            site=twin,
            suggestion="the prefetch state is (key, occ, routed op/row "
                       "buckets) and nothing else — find the extra leaf "
                       f"with `tools/dintcost.py report {trace.name} "
                       f"{twin}`"))
    return out


def _scan_dominance_findings(trace: TargetTrace,
                             model: cost.CostModel) -> list[Finding]:
    from .. import targets as T
    twin = getattr(T, "TARGET_SCAN_TWIN", {}).get(trace.name)
    if not twin or twin not in T.TARGETS:
        return []
    try:
        twin_model = cost.model_for(twin)
    except Exception:  # noqa: BLE001 — twin untraceable here (topology)
        return []
    if twin_model.error:
        return []
    geom = model.geom or {}
    w, sl = float(geom.get("w", 0)), float(geom.get("sl", 0))
    if w <= 0 or sl <= 0:
        return []
    scan_b = model.wave_bytes_per_step().get("dint.store.scan", 0.0)
    probe_b = twin_model.wave_bytes_per_step().get("dint.store.probe",
                                                   0.0)
    per_row, per_probe = scan_b / (w * sl), probe_b / w
    if scan_b <= 0.0 or per_row >= per_probe:
        return [Finding(
            "cost_budget", "scan-bytes-dominance", SEV_ERROR, trace.name,
            f"{per_row:g} HBM bytes per reply row (dint.store.scan "
            f"{scan_b:g} B/step over w*sl={w * sl:g} rows) vs the point "
            f"twin {twin} at {per_probe:g} bytes per probe reply "
            f"(dint.store.probe {probe_b:g} B/step over w={w:g} lanes): "
            "sequential rows must arrive STRICTLY cheaper than point "
            "probes — the dintscan bandwidth claim",
            site=twin,
            suggestion="the slab widened (check the sl+dc window and "
                       "row stride) or the scan wave lost its scope — "
                       f"diff `tools/dintcost.py report {trace.name} "
                       f"{twin} --json`")]
    return []


@register_pass("cost_budget")
def cost_budget(trace: TargetTrace) -> list[Finding]:
    """Derives the target's static cost model and enforces ledger
    reconciliation, registered budgets and the twin dominance checks."""
    from .. import targets as T
    meta = T.TARGET_COST.get(trace.name)
    if meta is None:
        return [Finding(
            "cost_budget", "no-budget", SEV_WARNING, trace.name,
            "registered target has no TARGET_COST entry: its cost is "
            "unbudgeted and regressions are invisible to CI",
            suggestion="calibrate with `tools/dintcost.py report "
                       f"{trace.name}` and add a _cost(...) row to the "
                       "ledger in analysis/targets.py")]
    model = cost.model_for(trace.name, trace)
    if model.error:
        return [Finding(
            "cost_budget", "derivation-failed", SEV_ERROR, trace.name,
            f"cost derivation failed: {model.error}")]
    out = _reconcile_findings(trace, meta, model)
    out += _budget_findings(trace, meta, model)
    out += _hier_dominance_findings(trace, model)
    out += _overlap_findings(trace, model)
    out += _scan_dominance_findings(trace, model)
    return out
