"""dintcal CLI: the calibration & prediction-audit plane + its gate.

Measured evidence closes the loop back into the model: `gather`
normalizes bench/exp artifacts (dintscope breakdown blocks, dintmon
counter snapshots, serve controller trajectories) into one evidence
document, `fit` pins ServiceModel coefficients from it as a
schema-versioned CALIB.json (provenance-hashed),
`check` is the standing drift gate — tolerance-banded evidence
reconciliation PLUS the static calib_check pass — and `audit` replays a
controller decision journal through the pure policy functions,
verifying every recorded width/shed decision bit-for-bit.
`propose` emits the recalibration from evidence, to be pinned as
CALIB.json (the serving plane's default service-time prior).

Usage:
    python tools/dintcal.py gather ART [ART...] -o evidence.json
    python tools/dintcal.py fit EVIDENCE [-o CALIB.json] [--json]
    python tools/dintcal.py check                        # the CI gate
        [--calib PATH] [--evidence PATH] [--allowlist PATH]
        [--sarif out.sarif] [--json]
    python tools/dintcal.py audit JOURNAL [--json]
    python tools/dintcal.py propose [--calib PATH] [--evidence PATH]
        [-o CALIB.proposed.json] [--json]
    python tools/dintcal.py describe [--json]
    python tools/dintcal.py synth [--json]               # fixtures

`check` exits 1 naming the drifted wave or coefficient; `audit` exits 1
naming the entry (index + block) whose recorded decision the replay does
not reproduce. Exit codes: 0 ok; 1 = gate failure; 2 usage.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the shared gate harness pins XLA_FLAGS (8-device virtual CPU) and
# JAX_PLATFORMS before any backend initializes — see analysis/cli.py
from dint_tpu.analysis import cli  # noqa: E402
from dint_tpu.monitor import calib as CAL  # noqa: E402

DEFAULT_ALLOWLIST = cli.DEFAULT_ALLOWLIST

# bumped when keys of the --json payload change shape
JSON_SCHEMA = 1

FIXTURE_EVIDENCE = "tests/fixtures/dintcal_evidence.json"
FIXTURE_JOURNAL = "tests/fixtures/dintcal_journal.jsonl"


def _load_json(path):
    with open(path) as fh:
        return json.load(fh)


def cmd_gather(args, ap) -> int:
    docs = [_load_json(p) for p in args.artifacts]
    ev = CAL.gather_evidence(docs, sources=args.artifacts)
    Path(args.out).write_text(json.dumps(ev, indent=1, sort_keys=True)
                              + "\n")
    summary = {"metric": "dintcal", "schema": JSON_SCHEMA,
               "mode": "gather", "out": args.out,
               "n_sources": len(args.artifacts),
               "n_samples": len(ev["samples"]),
               "n_waves": len(ev["waves"]),
               "counters": ev["counters"]}
    if args.json:
        print(json.dumps(summary), flush=True)
    else:
        print(f"wrote {args.out}: {summary['n_samples']} service "
              f"samples, {summary['n_waves']} wave rows from "
              f"{len(args.artifacts)} artifact(s)")
    return 0


def cmd_fit(args, ap) -> int:
    ev = CAL.load_evidence(args.evidence)
    calib = CAL.fit_calib(ev, source=args.source or args.evidence)
    out = Path(args.out) if args.out else CAL.calib_path()
    CAL.save_calib(calib, out)
    if args.json:
        print(json.dumps({
            "metric": "dintcal", "schema": JSON_SCHEMA, "mode": "fit",
            "out": str(out), "model": calib["model"],
            "prior": calib["prior"], "fit": calib["fit"],
            "n_waves": len(calib["waves"]),
            "provenance": calib["provenance"]}), flush=True)
        return 0
    m, p = calib["model"], calib["prior"]
    print(f"wrote {out} (schema {calib['schema']}, "
          f"{calib['fit']['n']} samples at widths "
          f"{calib['fit']['widths']}, {len(calib['waves'])} waves)")
    print(f"  base_us     {m['base_us']:>12.6f}  (prior {p['base_us']})")
    print(f"  per_lane_ns {m['per_lane_ns']:>12.6f}  "
          f"(prior {p['per_lane_ns']})")
    print(f"  rms_us {calib['fit']['rms_us']}  "
          f"max_abs_us {calib['fit']['max_abs_us']}")
    print("provenance: " + " ".join(
        f"{k}={v}" for k, v in sorted(calib["provenance"].items())))
    return 0


def _resolve_evidence(args, calib, cpath):
    """--evidence wins; else the calib's recorded source, resolved
    relative to the calib file (how the pinned fixture is addressed)."""
    if args.evidence:
        return CAL.load_evidence(args.evidence), args.evidence
    src = (calib or {}).get("source")
    if not src:
        return None, None
    spath = Path(src)
    if not spath.is_absolute():
        spath = Path(cpath).parent / spath
    try:
        return CAL.load_evidence(spath), str(spath)
    except (OSError, ValueError):
        return None, str(spath)


def cmd_check(args, ap) -> int:
    from dint_tpu import analysis
    from dint_tpu.analysis import allowlist as AL
    from dint_tpu.analysis.core import Finding, SEV_ERROR
    from dint_tpu.analysis.passes.calib_check import ANCHOR as anchor

    cpath = Path(args.calib) if args.calib else CAL.calib_path()
    if args.calib:
        os.environ[CAL.ENV_CALIB_PATH] = args.calib
    allowlist = cli.resolve_allowlist(args.allowlist)

    # half 1: the static calib_check pass (provenance, refit equality,
    # wave registry) under the dintlint allowlist
    findings = analysis.run(targets=[anchor], passes=["calib_check"],
                            allowlist_path=allowlist)

    # half 2: tolerance-banded drift of the pinned fit against evidence
    drift: list[dict] = []
    evidence_path = None
    try:
        calib = CAL.load_calib(cpath)
    except FileNotFoundError:
        calib = None
        findings.append(Finding(
            "calib_check", "missing-calib", SEV_ERROR, anchor,
            f"no calibration at {cpath}: nothing pins the ServiceModel "
            "coefficients to evidence",
            site=str(cpath),
            suggestion="fit one with `python tools/dintcal.py fit "
                       "<evidence> -o CALIB.json`"))
    except (OSError, ValueError):
        calib = None            # malformed-calib already landed via pass
    if calib is not None:
        ev, evidence_path = _resolve_evidence(args, calib, cpath)
        if ev is not None:
            drift = CAL.check_calib(calib, ev)
            for d in drift:
                findings.append(Finding(
                    "calib_check", "evidence-drift", SEV_ERROR, anchor,
                    d["message"], site=f"{d['what']}:{d['name']}",
                    suggestion="recalibrate with `python tools/"
                               "dintcal.py propose` and pin the "
                               "proposal as CALIB.json"))
    if allowlist:
        # drift findings are appended after analysis.run applied the
        # allowlist — give them the same suppression chance (no unused-
        # entry hygiene here; the pass run already did it)
        AL.apply(findings[-len(drift):] if drift else [],
                 AL.load(allowlist), check_unused=False)

    failed = analysis.has_errors(findings)
    if args.sarif:
        cli.write_sarif(findings, ap.prog, args.sarif)
    if args.json:
        print(json.dumps({
            "metric": "dintcal", "schema": JSON_SCHEMA, "mode": "check",
            "calib": str(cpath), "evidence": evidence_path,
            "anchor": anchor, "allowlist": allowlist,
            "n_findings": len(findings),
            "n_errors": cli.count_errors(findings),
            "n_drift": len(drift), "ok": not failed,
            "findings": [f.to_dict() for f in findings]}), flush=True)
    else:
        for f in findings:
            print(f)
        print(f"dintcal check: {len(findings)} finding(s), "
              f"{cli.count_errors(findings)} error(s), "
              f"{len(drift)} drift(s) -> "
              f"{'FAIL' if failed else 'ok'}", flush=True)
    return 1 if failed else 0


def cmd_audit(args, ap) -> int:
    doc = CAL.load_journal(args.journal)
    violations = CAL.audit_journal(doc)
    n = len(doc.get("entries", []))
    if args.json:
        print(json.dumps({
            "metric": "dintcal", "schema": JSON_SCHEMA, "mode": "audit",
            "journal": args.journal, "n_entries": n,
            "n_violations": len(violations),
            "ok": not violations, "violations": violations}), flush=True)
    else:
        for v in violations:
            print(f"dintcal audit: {v['message']}")
        print(f"dintcal audit: {n} entries replayed, "
              f"{len(violations)} violation(s) -> "
              f"{'FAIL' if violations else 'ok'}", flush=True)
    return 1 if violations else 0


def cmd_propose(args, ap) -> int:
    cpath = Path(args.calib) if args.calib else CAL.calib_path()
    try:
        calib = CAL.load_calib(cpath)
    except (OSError, ValueError):
        calib = None
    ev, evidence_path = _resolve_evidence(args, calib, cpath)
    if ev is None:
        print("dintcal propose: no evidence (pass --evidence, or pin a "
              "calib whose source is readable)", file=sys.stderr)
        return 2
    proposed = CAL.fit_calib(ev, source=evidence_path)
    out = args.out or "CALIB.proposed.json"
    CAL.save_calib(proposed, out)
    delta = None
    if calib is not None:
        delta = {c: {"pinned": calib["model"][c],
                     "proposed": proposed["model"][c]}
                 for c in ("base_us", "per_lane_ns")}
    if args.json:
        print(json.dumps({
            "metric": "dintcal", "schema": JSON_SCHEMA,
            "mode": "propose", "out": str(out),
            "evidence": evidence_path, "model": proposed["model"],
            "delta": delta, "provenance": proposed["provenance"],
            "repin": f"mv {out} CALIB.json"}),
            flush=True)
        return 0
    print(f"wrote {out} from {evidence_path}")
    for c in ("base_us", "per_lane_ns"):
        was = f" (pinned {calib['model'][c]})" if calib else ""
        print(f"  {c:12s} {proposed['model'][c]}{was}")
    print(f"pin it with: mv {out} CALIB.json")
    return 0


def cmd_describe(args, ap) -> int:
    model, meta = CAL.resolve_service_model()
    if args.json:
        print(json.dumps({
            "metric": "dintcal", "schema": JSON_SCHEMA,
            "mode": "describe",
            "calib_path": str(CAL.calib_path()),
            "evidence_schema": CAL.EVIDENCE_SCHEMA,
            "calib_schema": CAL.CALIB_SCHEMA,
            "tolerance": CAL.DEFAULT_TOLERANCE,
            "resolved_model": {"base_us": model.base_us,
                               "per_lane_ns": model.per_lane_ns,
                               **meta}}), flush=True)
        return 0
    print(f"dintcal: evidence schema {CAL.EVIDENCE_SCHEMA}, calib "
          f"schema {CAL.CALIB_SCHEMA}")
    print(f"pinned calib:  {CAL.calib_path()} "
          f"(override ${CAL.ENV_CALIB_PATH})")
    print(f"tolerance:     {CAL.DEFAULT_TOLERANCE}")
    print(f"resolved ServiceModel: base_us={model.base_us} "
          f"per_lane_ns={model.per_lane_ns} source={meta['source'].upper()}"
          + (f" hash={meta['hash']}" if meta["hash"] else ""))
    return 0


def cmd_synth(args, ap) -> int:
    ev = CAL.synthesize_evidence()
    jn = CAL.synthesize_journal()
    ev_out = args.out_evidence or FIXTURE_EVIDENCE
    jn_out = args.out_journal or FIXTURE_JOURNAL
    Path(ev_out).write_text(json.dumps(ev, indent=1, sort_keys=True)
                            + "\n")
    CAL.dump_journal_jsonl(jn, jn_out)
    if args.json:
        print(json.dumps({
            "metric": "dintcal", "schema": JSON_SCHEMA, "mode": "synth",
            "evidence": ev_out, "journal": jn_out,
            "n_samples": len(ev["samples"]),
            "n_entries": len(jn["entries"])}), flush=True)
    else:
        print(f"wrote {ev_out} ({len(ev['samples'])} samples, "
              f"{len(ev['waves'])} waves) and {jn_out} "
              f"({len(jn['entries'])} entries)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="dintcal", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("gather", help="normalize bench/exp artifacts "
                                      "into one evidence document")
    p.add_argument("artifacts", nargs="+")
    p.add_argument("-o", "--out", required=True)
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_gather)

    p = sub.add_parser("fit", help="fit ServiceModel coefficients from "
                                   "evidence and pin CALIB.json")
    p.add_argument("evidence")
    p.add_argument("-o", "--out", default=None,
                   help="output path (default: the pinned "
                        "<repo>/CALIB.json, or $DINT_CALIB_PATH)")
    p.add_argument("--source", default=None,
                   help="source string to record (default: the "
                        "evidence path as given)")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_fit)

    p = sub.add_parser("check",
                       help="the CI gate: calib_check pass + tolerance-"
                            "banded evidence drift (names the drifted "
                            "wave or coefficient)")
    p.add_argument("--calib", default=None,
                   help="check this calib file instead of the pinned "
                        "one")
    p.add_argument("--evidence", default=None,
                   help="reconcile against this evidence (default: the "
                        "calib's recorded source)")
    p.add_argument("--allowlist", default=None,
                   help="allowlist JSON path (default: "
                        "tools/dintlint_allow.json when present)")
    p.add_argument("--sarif", metavar="PATH", default=None,
                   help="also write the findings as SARIF 2.1.0 "
                        "('-' for stdout) — same exporter dintlint uses")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_check)

    p = sub.add_parser("audit",
                       help="replay a decision journal through the pure "
                            "policy functions; every recorded decision "
                            "must reproduce bit-for-bit")
    p.add_argument("journal")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_audit)

    p = sub.add_parser("propose",
                       help="emit a recalibration from evidence, to "
                            "pin as CALIB.json")
    p.add_argument("--calib", default=None)
    p.add_argument("--evidence", default=None)
    p.add_argument("-o", "--out", default=None,
                   help="proposed calib path "
                        "(default: CALIB.proposed.json)")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_propose)

    p = sub.add_parser("describe", help="schemas, paths, tolerance and "
                                        "the resolved ServiceModel")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_describe)

    p = sub.add_parser("synth",
                       help="regenerate the deterministic evidence + "
                            "journal fixtures")
    p.add_argument("--out-evidence", default=None)
    p.add_argument("--out-journal", default=None)
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_synth)

    args = ap.parse_args(argv)
    return cli.guard("dintcal", args.fn, args, ap)


if __name__ == "__main__":
    sys.exit(main())
