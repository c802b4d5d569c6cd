"""dintplan CLI: the static configuration planner + the fifth CI gate.

The knob matrix (`use_hotset`, `hierarchical`, `overlap`, serve widths) stops being operator folklore:
`plan` enumerates the feasible (engine x geometry x skew x mesh)
candidate lattice from the first-class knob registry
(analysis/plan.KNOBS), prices every candidate through the dintcost
CostModel + the ServiceModel capacity priors, prunes
statically-dominated points and pins the result as a schema-versioned
PLAN.json with provenance hashes. `check` is the standing gate: the
pinned plan must agree with the knob registry, the calibration ledger
and the priced frontier, and ambient DINT_* flags may not contradict it
without DINT_PLAN_OVERRIDE=1 (passes/plan_check.py).

Usage:
    python tools/dintplan.py plan [-o PLAN.json] [--json]
        [--calib CALIB.json]                    # re-pin from evidence
    python tools/dintplan.py check                       # the CI gate
        [--static] [--plan PATH]
        [--allowlist tools/dintlint_allow.json] [--json]
    python tools/dintplan.py check --sarif out.sarif     # SARIF 2.1.0
    python tools/dintplan.py describe [--json]           # knob registry

`check` runs ONLY the plan_check pass of the dintlint suite (same
allowlist, same exit discipline) — `tools/dintlint.py --all` includes it
too, in STATIC form (no matrix tracing rides every lint run). `check`
here is the FULL gate: it re-derives every frontier price fresh
(~30 s on CPU, memoized). `--static` skips that derivation: provenance
hashes still pin the calibration ledger and the recorded prices
bit-for-bit, so a recalibration or registry edit fails fast even in the
cheap mode. `plan` traces the full priced lattice (~30 s on CPU).

Exit codes: 0 ok; 1 = gate failure (offenders are named); 2 usage.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the shared gate harness pins XLA_FLAGS (8-device virtual CPU) and
# JAX_PLATFORMS before any backend initializes — see analysis/cli.py
from dint_tpu.analysis import cli  # noqa: E402
from dint_tpu import analysis  # noqa: E402
from dint_tpu.analysis import plan as P  # noqa: E402

DEFAULT_ALLOWLIST = cli.DEFAULT_ALLOWLIST

# bumped when keys of the --json payload change shape
JSON_SCHEMA = 1


def cmd_plan(args, ap) -> int:
    if args.calib:
        # re-pin from evidence: serve_priors resolves its ServiceModel
        # through monitor/calib.resolve_service_model, which honours
        # this override (the dintcal `propose` -> `plan --calib` loop)
        os.environ["DINT_CALIB_PATH"] = args.calib
    plan = P.build_plan()
    out = args.out or P.plan_path()
    path = P.save_plan(plan, out)
    if args.json:
        print(json.dumps({
            "metric": "dintplan", "schema": JSON_SCHEMA, "mode": "plan",
            "out": str(path), "provenance": plan["provenance"],
            "workloads": {w: {"target": e["target"],
                              "predicted_target": e["predicted_target"],
                              "overrides": [o["knob"]
                                            for o in e["overrides"]]}
                          for w, e in plan["workloads"].items()},
            "n_frontier": len(plan["frontier"])}), flush=True)
        return 0
    print(f"wrote {path} (schema {plan['schema']}, "
          f"{len(plan['frontier'])} priced candidates, "
          f"{len(plan['workloads'])} workloads)")
    for wname, e in sorted(plan["workloads"].items()):
        mark = "" if e["target"] == e["predicted_target"] else \
            "  [overridden: " + ", ".join(o["knob"]
                                          for o in e["overrides"]) + "]"
        print(f"  {wname:20s} pinned {e['target']:40s} "
              f"predicted {e['predicted_target']}{mark}")
    print("provenance: " + " ".join(f"{k}={v}" for k, v in
                                    sorted(plan["provenance"].items())))
    return 0


def cmd_check(args, ap) -> int:
    if args.plan:
        os.environ[P.ENV_PLAN_PATH] = args.plan
    # the embedded pass defaults to static (cheap) — dintplan check is
    # the FULL gate, so force full mode unless --static asked for cheap
    os.environ[P.ENV_PLAN_STATIC] = "1" if args.static else "0"
    allowlist = cli.resolve_allowlist(args.allowlist)
    anchor = os.environ.get(P.ENV_PLAN_ANCHOR, P.DEFAULT_ANCHOR)
    findings = analysis.run(targets=[anchor], passes=["plan_check"],
                            allowlist_path=allowlist)
    failed = analysis.has_errors(findings)
    if args.sarif:
        cli.write_sarif(findings, ap.prog, args.sarif)
    if args.json:
        print(json.dumps({
            "metric": "dintplan", "schema": JSON_SCHEMA, "mode": "check",
            "plan": str(P.plan_path()), "static": bool(args.static),
            "anchor": anchor, "allowlist": allowlist,
            "n_findings": len(findings),
            "n_errors": cli.count_errors(findings),
            "n_suppressed": cli.count_suppressed(findings),
            "ok": not failed,
            "findings": [f.to_dict() for f in findings]}), flush=True)
    else:
        for f in findings:
            print(f)
        mode = "static" if args.static else "full"
        print(f"dintplan ({mode}): {len(findings)} finding(s), "
              f"{cli.count_errors(findings)} error(s) -> "
              f"{'FAIL' if failed else 'ok'}", flush=True)
    return 1 if failed else 0


def cmd_describe(args, ap) -> int:
    if args.json:
        print(json.dumps({
            "metric": "dintplan", "schema": JSON_SCHEMA,
            "mode": "describe",
            "decision_rule": P.DECISION_RULE,
            "plan_path": str(P.plan_path()),
            "knobs": {k.name: k.to_dict() for k in P.KNOBS.values()},
            "workloads": {w.name: w.to_dict() for w in P.WORKLOADS}},
            ), flush=True)
        return 0
    print(f"dintplan knob registry ({len(P.KNOBS)} knobs, "
          f"{len(P.WORKLOADS)} workloads)")
    print(f"decision rule: {P.DECISION_RULE}")
    print(f"pinned plan:   {P.plan_path()}\n")
    for k in P.KNOBS.values():
        tok = (f"=> @{k.token} when {k.token_when!r}" if k.token
               else "(no target variant)")
        bits = []
        if k.planned:
            bits.append("planned")
        if k.build_identity:
            bits.append("memo-key")
        tag = f" [{', '.join(bits)}]" if bits else ""
        print(f"  {k.name:16s} env={k.env or '-':22s} "
              f"default={k.default!r:6} {tok}{tag}")
        print(f"  {'':16s} engines: {', '.join(k.engines)}")
        print(f"  {'':16s} {k.doc}")
    print("\nworkloads (engine x geometry x skew x mesh):")
    for w in P.WORKLOADS:
        mesh = w.mesh or "single-device"
        print(f"  {w.name:20s} {w.engine}/{w.base:8s} mesh={mesh:8s} "
              f"skew={w.skew:10s} knobs: "
              + (", ".join(w.knobs) or "(none)"))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="dintplan", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("plan",
                       help="enumerate, price, prune and pin PLAN.json")
    p.add_argument("-o", "--out", default=None,
                   help="output path (default: the pinned "
                        "<repo>/PLAN.json, or $DINT_PLAN_PATH)")
    p.add_argument("--calib", metavar="CALIB.json", default=None,
                   help="price serve priors with this dintcal "
                        "calibration (sets DINT_CALIB_PATH for the "
                        "build) — the evidence-driven re-pin route")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_plan)

    p = sub.add_parser("check",
                       help="the CI gate: run the plan_check pass with "
                            "the dintlint allowlist")
    p.add_argument("--static", action="store_true",
                   help="skip the fresh dintcost derivation (registry + "
                        "provenance + ordering checks only; no matrix "
                        "tracing)")
    p.add_argument("--plan", default=None,
                   help="check this plan file instead of the pinned one")
    p.add_argument("--allowlist", default=None,
                   help="allowlist JSON path (default: "
                        "tools/dintlint_allow.json when present)")
    p.add_argument("--sarif", metavar="PATH", default=None,
                   help="also write the findings as SARIF 2.1.0 "
                        "('-' for stdout) — same exporter dintlint uses")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_check)

    p = sub.add_parser("describe",
                       help="print the knob registry with per-knob "
                            "target mappings and the workload lattice")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_describe)

    args = ap.parse_args(argv)
    return cli.guard("dintplan", args.fn, args, ap)


if __name__ == "__main__":
    sys.exit(main())
