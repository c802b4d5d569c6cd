"""dintplan: the static configuration planner behind PLAN.json.

DINT's design point is that the SYSTEM decides what lives in the fast
tier (the kernel cache admits and evicts on its own — PAPER.md); our
reproduction grew an operator-driven knob matrix instead: `use_hotset`,
`hierarchical`, `overlap`, the serve width menu, a per-round manual
decision rule buried in PERF.md. This module is
the static half of closing that loop. It declares the knob space as a
first-class registry (`KNOBS` — each knob knows its env var, its legal
values, the engines it applies to and the registered target variant it
maps to), enumerates the feasible (engine x geometry x skew x mesh)
candidate lattice (`WORKLOADS` x knob values, filtered against
analysis/targets.py — a knob combination with no registered target is
infeasible by construction, never silently priced), prices every
candidate through the dintcost `CostModel` (bytes, dispatches,
footprint, per-axis link bytes) plus the `ServiceModel` capacity priors,
prunes statically-dominated points, and pins the result as a
schema-versioned `PLAN.json` artifact with provenance hashes.

One decision rule, stated once (recorded verbatim in the plan):

  dominated  a candidate is pruned iff some candidate in the SAME
             workload is strictly better on HBM bytes/step AND
             dispatches/step AND footprint — all three, strictly
             (ISSUE 17's rule; ties survive)
  choose     lexicographic minimize (dcn_bytes_per_step,
             dispatches_per_step, bytes_per_step, footprint_bytes)
             over the undominated frontier — the slow axis first
             (round 14), then the dispatch chain (round 3's "op count
             is cost"), bytes and footprint as tiebreaks

The chosen config is the plan's `predicted` pick. The plan additionally
carries a `pinned` config per workload — what production actually runs —
and when pinned != predicted, an explicit per-knob override with a
written reason (`MEASURED_OVERRIDES`, quoting the PERF.md round). The
honest cases are structural: the static model prices SCHEDULED work, so
the hot tier (whose win is locality, invisible to a bytes ledger)
prices as a regression. passes/plan_check.py fails CI when the pinned
plan drifts from this module's view of the world; bench.py / exp.py /
the serving plane resolve their knob defaults FROM the plan
(`resolve_for`), with env flags demoted to an explicit
`DINT_PLAN_OVERRIDE=1` escape hatch.

`resolve_knobs()` is also the single point of env-knob truth: it
replicates, exactly, the resolution semantics of
ops/hotset.env_use_hotset, tables/run.env_use_scan, monitor/txnevents
trace_enabled/trace_rate and the bench DINT_MONITOR gate, and
engines/_memo.py folds `env_knob_signature()` (the canonicalized
resolution, not raw strings) into its builder memo keys — the memo key,
the builder and the plan checker can no longer disagree on what a flag
means.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from pathlib import Path

SCHEMA = 1

ENV_PLAN_PATH = "DINT_PLAN_PATH"          # override the pinned plan file
ENV_PLAN_OVERRIDE = "DINT_PLAN_OVERRIDE"  # "1": env flags beat the plan
ENV_PLAN_STATIC = "DINT_PLAN_STATIC"      # "1": plan_check skips tracing
ENV_PLAN_ANCHOR = "DINT_PLAN_ANCHOR"      # plan_check's reporting target

# the one registered target plan_check anchors its findings to (the
# whole-plan checks are global, not per-target; anchoring them to the
# cheapest always-traceable target keeps the pass inside the standard
# analysis.run harness)
DEFAULT_ANCHOR = "tatp_dense/block"

DECISION_RULE = (
    "choose = lexicographic min (dcn_bytes_per_step, dispatches_per_step, "
    "bytes_per_step, footprint_bytes) over the undominated frontier; "
    "dominated = strictly worse than some same-workload candidate on "
    "bytes AND dispatches AND footprint")


def plan_path() -> Path:
    """The pinned plan location: $DINT_PLAN_PATH or <repo>/PLAN.json."""
    env = os.environ.get(ENV_PLAN_PATH)
    if env:
        return Path(env)
    return Path(__file__).resolve().parents[2] / "PLAN.json"


def override_active(environ=None) -> bool:
    env = os.environ if environ is None else environ
    return env.get(ENV_PLAN_OVERRIDE, "0") == "1"


# ------------------------------------------------------ the knob registry
#
# Every ambient configuration flag the engines/bench/serve planes consult,
# declared ONCE: env var, resolution semantics (`kind`), legal values, the
# engines it applies to, and the registered target variant token it maps
# to (use_hotset=True => the "@hot" target). `planned` knobs span the
# priced lattice; the rest (observability and debug knobs) are registered
# so resolution and memo keys cover them, but the planner holds them at
# their default — tracing and counters are priced by their OWN calibrated
# @mon/@trace targets, not chosen by the planner.

# token order inside registered names ("@hot+mon", "@overlap+mon",
# "@h3+flat"): rank sorts tokens into the registry's canonical spelling
_TOKEN_RANK = {"hot": 0, "h3": 1, "overlap": 2, "scan": 3,
               "mon": 4, "flat": 5, "trace": 6}

_DENSE = ("tatp_dense", "smallbank_dense")
_SHARDED = ("dense_sharded", "dense_sharded_sb")
_MESH = ("multihost_sb",)


@dataclasses.dataclass(frozen=True)
class Knob:
    """One ambient configuration knob, declared once."""
    name: str                     # canonical name ("use_hotset")
    env: str | None               # env var; None = CLI/constructor only
    kind: str                     # resolution semantics, see _resolve_one
    default: object
    values: tuple                 # legal values (floats: observed range)
    engines: tuple[str, ...]      # registry engine prefixes it applies to
    token: str | None = None      # target variant token it maps to
    token_when: object = True     # knob value that turns the token ON
    planned: bool = False         # spans the priced lattice
    build_identity: bool = False  # part of the compiled-program identity
    doc: str = ""

    def to_dict(self) -> dict:
        return {
            "name": self.name, "env": self.env, "kind": self.kind,
            "default": self.default, "values": list(self.values),
            "engines": list(self.engines), "token": self.token,
            "token_when": self.token_when, "planned": self.planned,
            "build_identity": self.build_identity, "doc": self.doc,
        }


_KNOB_LIST = (
    Knob("use_hotset", "DINT_USE_HOTSET", "flag01", False, (False, True),
         _DENSE + ("dense_sharded_sb",), token="hot", planned=True,
         build_identity=True,
         doc="keep the round-10 hot-prefix mirror (write-through on "
             "install)"),
    Knob("hierarchical", None, "bool", True, (False, True),
         _MESH, token="flat", token_when=False, planned=True,
         doc="decompose cross-host collectives ici-then-dcn (round 14) "
             "instead of one flat tuple-axis exchange; False = the "
             "@flat twin"),
    Knob("overlap", None, "bool", False, (False, True),
         _MESH, token="overlap", planned=True,
         doc="double-buffer the DCN exchange under the lock wave "
             "(round 18 serve plane)"),
    Knob("use_scan", "DINT_USE_SCAN", "flag01", False, (False, True),
         ("store",), token="scan", planned=False, build_identity=True,
         doc="thread the round-20 ordered-run snapshot + delta overlay "
             "through the store step (Op.SCAN range replies via the "
             "sequential slab); not planned — default-off until the "
             "round-20 hw A/B shows the GB/s win (PERF.md decision "
             "rule), priced by the calibrated @scan targets"),
    Knob("monitor", "DINT_MONITOR", "flag1", False, (False, True),
         _DENSE + _SHARDED + _MESH, token="mon",
         doc="thread the dintmon counter plane through the carry; "
             "priced by the calibrated @mon targets, not planned"),
    Knob("trace", "DINT_TRACE", "flag1", False, (False, True),
         _DENSE + _SHARDED + _MESH, token="trace", build_identity=True,
         doc="arm the dinttrace flight recorder ring; priced by the "
             "@trace targets, not planned"),
    Knob("trace_rate", "DINT_TRACE_RATE", "float", 1.0, (0.0, 1.0),
         _DENSE + _SHARDED + _MESH, build_identity=True,
         doc="dinttrace sampling rate (txnevents.trace_rate)"),
    Knob("trace_cap", "DINT_TRACE_CAP", "raw", None, (None,),
         _DENSE + _SHARDED + _MESH, build_identity=True,
         doc="reserved trace-ring capacity override (memo-key only; no "
             "consumer yet)"),
    Knob("hot_frac", "DINT_BENCH_HOT_FRAC", "optfloat", None,
         (None, 1 / 64, 0.5), ("smallbank_dense", "dense_sharded_sb",
                               "multihost_sb"),
         doc="hot-set fraction; None = workloads.SB_HOT_FRAC. The serve "
             "plane re-pins it from recommend_hot_frac at width-switch "
             "drain boundaries"),
)

KNOBS: dict[str, Knob] = {k.name: k for k in _KNOB_LIST}


def _resolve_one(knob: Knob, environ) -> object:
    """One knob's env resolution — replicating the consumer's exact
    semantics (hotset.env_use_hotset, txnevents.trace_enabled/rate,
    bench's DINT_MONITOR gate). THE single point of env-knob truth."""
    if knob.env is None:
        return knob.default
    raw = environ.get(knob.env)
    if knob.kind == "flag01":       # set-and-not-"0"/"": hot/scan
        return (raw or "0") not in ("", "0")
    if knob.kind == "flag1":        # exactly "1": DINT_MONITOR, DINT_TRACE
        return (raw or "0") == "1"
    if knob.kind == "float":
        try:
            return float(raw) if raw is not None else float(knob.default)
        except ValueError:
            return float(knob.default)
    if knob.kind == "optfloat":
        try:
            return float(raw) if raw is not None else knob.default
        except ValueError:
            return knob.default
    return raw                      # "raw" / "bool": no env semantics


def resolve_knobs(environ=None) -> dict[str, object]:
    """Resolve EVERY registered knob from the environment (explicit
    mapping for tests; default os.environ). Knobs without an env var
    resolve to their default."""
    env = os.environ if environ is None else environ
    return {k.name: _resolve_one(k, env) for k in _KNOB_LIST}


def env_knob_signature(environ=None) -> tuple:
    """The canonical compiled-program-identity snapshot engines/_memo.py
    folds into builder memo keys: (name, resolved value) for every
    build_identity knob. Canonicalized resolution — not raw strings — so
    unset, "" and "0" (all meaning False to the builders) share one memo
    entry."""
    env = os.environ if environ is None else environ
    return tuple((k.name, _resolve_one(k, env))
                 for k in _KNOB_LIST if k.build_identity)


# ------------------------------------------------------ workload lattice


@dataclasses.dataclass(frozen=True)
class Workload:
    """One (engine x geometry x skew x mesh) point the planner prices."""
    name: str
    engine: str                       # registry engine prefix
    base: str                         # "block" | "serve"
    knobs: tuple[str, ...]            # planned knobs that vary here
    base_tokens: tuple[str, ...] = () # geometry tokens ("h3")
    mesh: str = ""                    # "" | "d=4" | "4x2" | "3x2"
    skew: str = "uniform"
    serve: bool = False               # attach ServiceModel priors
    lanes_scale: int = 1              # mesh serve: hosts x chips
    doc: str = ""

    def to_dict(self) -> dict:
        return {"name": self.name, "engine": self.engine,
                "base": self.base, "knobs": list(self.knobs),
                "base_tokens": list(self.base_tokens), "mesh": self.mesh,
                "skew": self.skew, "serve": self.serve,
                "lanes_scale": self.lanes_scale, "doc": self.doc}


WORKLOADS: tuple[Workload, ...] = (
    Workload("tatp_uniform", "tatp_dense", "block",
             ("use_hotset",),
             doc="single-device TATP, uniform subscriber draw"),
    Workload("smallbank_skewed", "smallbank_dense", "block",
             ("use_hotset",), skew="hot-90/4",
             doc="single-device SmallBank, 90% of txns on the 4% hot "
                 "prefix (clients/workloads.py)"),
    Workload("tatp_sharded", "dense_sharded", "block",
             (), mesh="d=4",
             doc="4-shard ICI TATP (parallel/dense_sharded)"),
    Workload("smallbank_sharded", "dense_sharded_sb", "block",
             ("use_hotset",), mesh="d=4", skew="hot-90/4",
             doc="4-shard ICI SmallBank"),
    Workload("multihost_4x2", "multihost_sb", "block",
             ("hierarchical",), mesh="4x2", skew="hot-90/4",
             doc="4 hosts x 2 chips, 2-D (dcn x ici) mesh, hierarchical "
                 "vs flat cross-host transport (round 14)"),
    Workload("multihost_3x2", "multihost_sb", "block",
             ("hierarchical",), base_tokens=("h3",), mesh="3x2",
             skew="hot-90/4",
             doc="3 hosts x 2 chips: the non-power-of-two host count"),
    Workload("multihost_serve", "multihost_sb", "serve",
             ("hierarchical", "overlap"), mesh="4x2", skew="hot-90/4",
             serve=True, lanes_scale=8,
             doc="mesh serving plane (round 18): DCN exchange overlapped "
                 "under the lock wave vs not"),
    Workload("smallbank_serve", "smallbank_dense", "serve",
             (), skew="hot-90/4", serve=True,
             doc="single-device serving plane (round 17); no planned "
                 "knob varies — pinned for the width/hot_frac priors"),
    Workload("tatp_serve", "tatp_dense", "serve",
             (), serve=True,
             doc="single-device TATP serving plane; pinned for the "
                 "width priors (no hot tier)"),
)

_WORKLOADS_BY_NAME = {w.name: w for w in WORKLOADS}

# consumer lookup: which workload an entry point resolves its knobs from
# (bench/exp block runs vs the serving planes)
BLOCK_WORKLOADS = {
    "tatp_dense": "tatp_uniform",
    "smallbank_dense": "smallbank_skewed",
    "dense_sharded": "tatp_sharded",
    "dense_sharded_sb": "smallbank_sharded",
    "multihost_sb": "multihost_4x2",
}
SERVE_WORKLOADS = {
    "tatp_dense": "tatp_serve",
    "smallbank_dense": "smallbank_serve",
    "multihost_sb": "multihost_serve",
}


def target_name(workload: Workload, values: dict[str, object]) -> str:
    """The registered target a knob assignment maps to:
    engine/base[@tok+tok...] with tokens in the registry's canonical
    rank order."""
    tokens = list(workload.base_tokens)
    for kname in workload.knobs:
        knob = KNOBS[kname]
        if knob.token and values.get(kname) == knob.token_when:
            tokens.append(knob.token)
    tokens.sort(key=lambda t: _TOKEN_RANK.get(t, 99))
    suffix = ("@" + "+".join(tokens)) if tokens else ""
    return f"{workload.engine}/{workload.base}{suffix}"


def enumerate_candidates(workload: Workload) -> list[dict]:
    """The workload's full knob lattice: every assignment of its planned
    knobs, each mapped to a target name and marked feasible iff that
    target is registered (an unregistered combination is structurally
    infeasible, never silently priced)."""
    from . import targets as T
    assigns: list[dict] = [{}]
    for kname in workload.knobs:
        knob = KNOBS[kname]
        assigns = [dict(a, **{kname: v}) for a in assigns
                   for v in knob.values]
    out = []
    for a in assigns:
        name = target_name(workload, a)
        out.append({"knobs": a, "target": name,
                    "feasible": name in T.TARGETS})
    return out


def pinned_knobs(workload: Workload) -> dict[str, object]:
    """What production runs today: every planned knob at its registered
    default (env flags all unset)."""
    return {k: KNOBS[k].default for k in workload.knobs}


# pinned != predicted needs a WRITTEN reason quoting the measured story
# (PERF.md) — the plan records these verbatim so `dintplan check` can
# demand that every divergence is acknowledged, not drifted into.
MEASURED_OVERRIDES: dict[str, str] = {
    "use_hotset": (
        "PERF.md round 10: the hot tier prices as MORE scheduled work "
        "(write-through double-pass) — its win is locality, which "
        "a static bytes ledger cannot see; opt-in until measured"),
    "overlap": (
        "PERF.md round 18: overlap exists to HIDE the exchange under "
        "the lock wave — wall-clock only; statically it adds the "
        "double-buffer footprint, so the planner correctly never picks "
        "it. Opt-in (--overlap) pending the hardware A/B"),
}


# ------------------------------------------------------ pricing + choice


def _price_target(name: str) -> dict:
    """One candidate's static price (traces the target on first use;
    memoized process-wide via cost.model_for)."""
    from . import cost
    model = cost.model_for(name)
    if model.error:
        raise RuntimeError(f"{name}: cost derivation failed: {model.error}")
    axis = model.axis_bytes_per_step()
    return {
        "dispatches_per_step": round(model.dispatches_per_step, 3),
        "bytes_per_step": round(model.bytes_per_step, 2),
        "footprint_bytes": int(model.footprint_bytes),
        "ici_bytes_per_step": round(axis.get("ici", 0.0), 2),
        "dcn_bytes_per_step": round(axis.get("dcn", 0.0), 2),
    }


def decision_key(row: dict) -> tuple:
    """The lexicographic choice key (DECISION_RULE, stated once)."""
    return (row["dcn_bytes_per_step"], row["dispatches_per_step"],
            row["bytes_per_step"], row["footprint_bytes"])


def dominates(a: dict, b: dict) -> bool:
    """True iff candidate `a` is strictly better than `b` on bytes AND
    dispatches AND footprint (the prune rule; ties do NOT dominate)."""
    return (a["bytes_per_step"] < b["bytes_per_step"]
            and a["dispatches_per_step"] < b["dispatches_per_step"]
            and a["footprint_bytes"] < b["footprint_bytes"])


def rank_rows(rows: list[dict]) -> None:
    """In place: mark dominated rows (`dominated_by` = the cheapest
    dominator) and rank the survivors by the decision key (rank 0 = the
    predicted pick). Deterministic: ties broken by target name."""
    for row in rows:
        doms = [o for o in rows if o is not row and dominates(o, row)]
        if doms:
            best = min(doms, key=lambda o: (decision_key(o), o["target"]))
            row["dominated"] = True
            row["dominated_by"] = best["target"]
        else:
            row["dominated"] = False
            row["dominated_by"] = None
    frontier = sorted((r for r in rows if not r["dominated"]),
                      key=lambda r: (decision_key(r), r["target"]))
    for i, row in enumerate(frontier):
        row["rank"] = i
    for row in rows:
        if row["dominated"]:
            row["rank"] = None


def serve_priors(workload: Workload) -> dict:
    """ServiceModel capacity priors for a serve workload: the width menu
    with per-width service time, capacity and admissible backlog, the
    knee, and the hot_frac prior the engine rebuilds toward. The model
    comes from THE resolver (monitor/calib.resolve_service_model):
    pinned CALIB.json coefficients when present, ServiceModel defaults
    otherwise — and the row records which (source + hash), so a plan's
    capacity claims are attributable to their coefficient source
    (ISSUE 18 fix: this used to instantiate ServiceModel()
    unconditionally)."""
    from ..monitor.calib import resolve_service_model
    from ..serve.controller import ControllerCfg, max_backlog
    cfg = ControllerCfg()
    model, model_meta = resolve_service_model()
    widths = {}
    best_cap, knee = -1.0, cfg.widths[-1]
    for w in cfg.widths:
        s_us = model.service_us(w)
        cap = w / (s_us * 1e-6)
        if cap > best_cap:
            best_cap, knee = cap, w
        widths[str(w)] = {
            "service_us": round(s_us, 3),
            "capacity_lanes_per_s": round(cap, 1),
            "max_backlog": max_backlog(w, s_us, cfg),
        }
    hot_frac = None
    if "smallbank" in workload.engine or workload.engine == "multihost_sb":
        from ..clients import workloads as wl
        hot_frac = wl.SB_HOT_FRAC
    return {
        "widths": widths,
        "knee_width": knee,
        "slo_us": cfg.slo_us,
        "lanes_scale": workload.lanes_scale,
        "hot_frac": hot_frac,
        "model": {"base_us": model.base_us,
                  "per_lane_ns": model.per_lane_ns,
                  "source": model_meta["source"],
                  "hash": model_meta["hash"]},
    }


# ------------------------------------------------------------ provenance


def _digest(obj) -> str:
    blob = json.dumps(obj, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def knobs_hash() -> str:
    """Digest of the knob registry + workload lattice + decision rule —
    a plan generated against a different planner is stale."""
    return _digest({"knobs": [k.to_dict() for k in _KNOB_LIST],
                    "workloads": [w.to_dict() for w in WORKLOADS],
                    "rule": DECISION_RULE})


def calibration_hash() -> str:
    """Digest of targets.TARGET_COST (the calibration ledger): any
    recalibration invalidates the pinned plan's prices. Recomputable
    without tracing — plan_check's static mode leans on this."""
    from . import targets as T
    return _digest(T.TARGET_COST)


def frontier_hash(rows: list[dict]) -> str:
    return _digest(sorted(rows, key=lambda r: (r["workload"],
                                               r["target"])))


# --------------------------------------------------------- plan building


def build_plan() -> dict:
    """Enumerate, price, prune and choose: the full PLAN.json document.
    Traces every feasible candidate (memoized; ~25 targets) — run under
    the 8-device virtual CPU topology (tools/dintplan.py does)."""
    frontier: list[dict] = []
    workloads: dict[str, dict] = {}
    for wl in WORKLOADS:
        cands = enumerate_candidates(wl)
        rows = []
        for c in cands:
            if not c["feasible"]:
                continue
            row = {"workload": wl.name, "target": c["target"],
                   "knobs": c["knobs"]}
            row.update(_price_target(c["target"]))
            rows.append(row)
        if not rows:
            raise RuntimeError(f"{wl.name}: no feasible candidate")
        rank_rows(rows)
        frontier.extend(rows)
        predicted = min((r for r in rows if not r["dominated"]),
                        key=lambda r: (decision_key(r), r["target"]))
        pinned = pinned_knobs(wl)
        pinned_target = target_name(wl, pinned)
        overrides = []
        for kname in wl.knobs:
            if pinned[kname] != predicted["knobs"][kname]:
                overrides.append({
                    "knob": kname,
                    "pinned": pinned[kname],
                    "predicted": predicted["knobs"][kname],
                    "reason": MEASURED_OVERRIDES[kname],
                })
        entry = {
            "engine": wl.engine, "base": wl.base, "mesh": wl.mesh,
            "skew": wl.skew,
            "pinned": pinned,
            "target": pinned_target,
            "predicted": predicted["knobs"],
            "predicted_target": predicted["target"],
            "overrides": overrides,
            "infeasible": sorted(c["target"] for c in cands
                                 if not c["feasible"]),
            "serve": serve_priors(wl) if wl.serve else None,
        }
        workloads[wl.name] = entry
    return {
        "schema": SCHEMA,
        "decision_rule": DECISION_RULE,
        "provenance": {
            "knobs_hash": knobs_hash(),
            "calibration_hash": calibration_hash(),
            "cost_model_hash": frontier_hash(frontier),
        },
        "workloads": workloads,
        "frontier": sorted(frontier,
                           key=lambda r: (r["workload"], r["target"])),
    }


def save_plan(plan: dict, path: Path | None = None) -> Path:
    path = Path(path) if path else plan_path()
    path.write_text(json.dumps(plan, indent=1, sort_keys=True) + "\n")
    return path


def load_plan(path: Path | None = None) -> dict:
    """Parse the pinned plan. Raises FileNotFoundError / ValueError —
    callers that want soft-fail use resolve_for."""
    path = Path(path) if path else plan_path()
    plan = json.loads(path.read_text())
    if not isinstance(plan, dict) or plan.get("schema") != SCHEMA:
        raise ValueError(f"{path}: not a schema-{SCHEMA} PLAN.json")
    return plan


# ------------------------------------------------------ consumer resolve


def resolve_for(workload: str, environ=None,
                plan: dict | None = None) -> tuple[dict, dict]:
    """The consumer entry point (bench.py, exp.py, serve/engine.py,
    tools/dintserve.py): `(knobs, meta)` for one workload.

    knobs start from the plan's pinned config; a knob's env flag is
    consulted ONLY under DINT_PLAN_OVERRIDE=1 (meta records which knobs
    the override changed). Without a readable plan, knobs fall back to
    plain env resolution and meta["source"] is None — artifacts record
    `"plan": null`, never a silent default."""
    env = os.environ if environ is None else environ
    if plan is None:
        try:
            plan = load_plan()
        except (OSError, ValueError):
            plan = None
    resolved = resolve_knobs(env)
    if plan is None or workload not in plan.get("workloads", {}):
        wl = _WORKLOADS_BY_NAME.get(workload)
        knobs = ({k: resolved[k] for k in wl.knobs} if wl
                 else dict(resolved))
        return knobs, {"source": None, "hash": None, "overridden": []}
    entry = plan["workloads"][workload]
    knobs = dict(entry["pinned"])
    overridden = []
    if override_active(env):
        for kname in list(knobs):
            knob = KNOBS.get(kname)
            if knob is None or knob.env is None:
                continue
            if env.get(knob.env) is not None \
                    and resolved[kname] != knobs[kname]:
                knobs[kname] = resolved[kname]
                overridden.append(kname)
    meta = {"source": str(plan_path()),
            "hash": plan.get("provenance", {}).get("cost_model_hash"),
            "overridden": overridden}
    return knobs, meta


def contradictions(plan: dict, environ=None) -> list[tuple[str, str,
                                                           object, object]]:
    """Env flags that are SET and contradict a workload's pinned knob:
    [(workload, knob, pinned, env_value)]. plan_check ERRORs on these
    unless DINT_PLAN_OVERRIDE=1 — silent env drift is exactly what the
    plan exists to end."""
    env = os.environ if environ is None else environ
    resolved = resolve_knobs(env)
    out = []
    for wname, entry in sorted(plan.get("workloads", {}).items()):
        for kname, pinned in sorted(entry.get("pinned", {}).items()):
            knob = KNOBS.get(kname)
            if knob is None or knob.env is None:
                continue
            if env.get(knob.env) is None:
                continue
            if resolved[kname] != pinned:
                out.append((wname, kname, pinned, resolved[kname]))
    return out
