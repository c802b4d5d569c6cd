"""dintmut engine: machine-generated jaxpr mutants prove the gates bite.

Every standing gate (dintlint/dintproof, dintcost, dintdur) claims it
would catch a specific engine-corruption class — an install nobody
locked, a dropped replication hop, an unbounded ring, a doubled gather.
Until this module those claims were backed by hand-written mini-fixtures
(tests/test_dintlint.py); the REAL engines were never corrupted. dintmut
closes that gap the way mutation testing does for unit suites: it takes
the traced jaxpr of a registered target (riding targets.TRACE_CACHE —
mutants are pure jaxpr rewrites, nothing is ever executed), applies one
semantic corruption from a first-class operator registry, re-runs the
full structural pass matrix on the mutant, and attributes the kill to
the specific pass/code that fired. The verdict matrix is pinned as a
schema-versioned MUTCOV.json under the PLAN.json provenance-hash
discipline; passes/mut_check.py is the standing gate over that artifact
(kill-rate floor, survivor triage, killer-family coverage).

Operator registry (OPERATORS):

  drop-eqn        delete one protocol-bearing eqn: a scatter-max/min
                  (the lock arbitration), a ppermute (a replication
                  hop), or a log-append scatter — the fact it seeded
                  never flows, so the dependent gate must fire
                  (unlocked-install / quorum-fanout / wal-order).
  weaken-scatter  scatter-max -> overwrite scatter (arbitration loses
                  its reducer, ARB/LOCK_WIN never seed), or flip an
                  install's unique_indices certification to False
                  (scatter_race's nonunique ladder).
  mask-swap       replace an install scatter's index operand with a
                  fresh unconstrained var: the write mask no longer
                  descends from the lock grant / validate compare
                  (unlocked-install, unvalidated-install).
  axis-swap       reroute a dcn-axis replication ppermute onto the ici
                  axis (replicas land in one host fault domain), or
                  collapse a perm so every source keeps < 2 distinct
                  destinations (quorum-fanout).
  widen-gather    double the leading dim of the largest table gather's
                  output: derived HBM bytes blow the waves.py ledger
                  band / bytes budget (formula-mismatch,
                  over-bytes-budget).
  drop-donation   clear donated_invars on a top-level pjit: the
                  persistent footprint loses its donation discount
                  (over-footprint-budget).
  ring-shrink     shrink a log ring root to 2 slots: the statically
                  counted appends/trace overflow it (unbounded-ring).

A mutant never executes; it only needs to be *walkable* by the dataflow
and cost analyzers, so edits are free to leave dangling vars (a dropped
eqn's consumers simply lose its facts — exactly the corruption the gates
key on) and stale reducer params on a swapped scatter primitive.

Kill attribution: the mutant runs MUT_PASSES (every structural pass —
the artifact-anchored plan_check/calib_check/mut_check are excluded:
they check pinned documents, not jaxprs) under the shared allowlist;
`new_errors` is the mutant's unsuppressed ERROR (pass, code) set minus
the base trace's, `killed` means it is non-empty, and `killer` is the
first new error matching the operator's declared expectation (else the
lexicographic first). Suppressed codes are recorded per cell so the
standing `durability/no-ring-truncation` allowlist entry stays
machine-cross-referenced against the ring operators (mut_check's
ring-triage-drift check).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import random
from pathlib import Path
from typing import Callable

import jax._src.core as jcore
from jax._src.lax import slicing as _lsl

from . import dataflow as df
from .core import Finding, PASSES, SEV_ERROR, TargetTrace, site_of

SCHEMA = 1
ARTIFACT = "MUTCOV.json"
ENV_MUTCOV = "DINT_MUTCOV"          # artifact path override (tests)
QUICK_SEED = 20260807               # pinned quick-sample seed
KILL_RATE_FLOOR = 0.90              # standing ERROR below this
MAX_SITES = 2                       # per (target, operator) cell cap

# the pass matrix mutants re-run: every structural pass; the
# artifact-anchored checks (plan_check/calib_check/mut_check) verify
# pinned documents, not jaxprs, and would fire identically on mutants
_ANCHORED = {"plan_check", "calib_check", "mut_check"}


def mut_passes() -> list[str]:
    return sorted(p for p in PASSES if p not in _ANCHORED)


# ------------------------------------------------------ addressed walker
#
# An address names one jaxpr inside a ClosedJaxpr as a tuple of steps
# (eqn_idx, param_key, tuple_idx|None) descending through param
# sub-jaxprs; () is the top jaxpr. Rewrites rebuild every eqn on the
# path with `.replace(...)` — shared structure in TRACE_CACHE is never
# mutated in place.


def _param_subjaxprs(eqn):
    """(param_key, tuple_idx|None, sub_jaxpr, wrapper) for every jaxpr
    nested in the eqn's params (pjit/scan jaxpr, cond branches, while
    cond/body, shard_map body, pallas kernel, custom_*)."""
    out = []
    for k, v in sorted(eqn.params.items()):
        if isinstance(v, (jcore.Jaxpr, jcore.ClosedJaxpr)):
            out.append((k, None, v))
        elif isinstance(v, (tuple, list)):
            for i, w in enumerate(v):
                if isinstance(w, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                    out.append((k, i, w))
    return out


def _inner(obj) -> jcore.Jaxpr:
    return obj.jaxpr if isinstance(obj, jcore.ClosedJaxpr) else obj


def walk_addressed(jaxpr: jcore.Jaxpr, prefix=(), in_pallas=False):
    """Yield (addr, jaxpr, eqn_idx, eqn, in_pallas) for every eqn; addr
    addresses the ENCLOSING jaxpr (the rewrite unit)."""
    for i, eqn in enumerate(jaxpr.eqns):
        yield prefix, jaxpr, i, eqn, in_pallas
        sub_pl = in_pallas or eqn.primitive.name == "pallas_call"
        for k, ti, obj in _param_subjaxprs(eqn):
            yield from walk_addressed(_inner(obj),
                                      prefix + ((i, k, ti),), sub_pl)


def _rewrap(obj, new_jaxpr):
    if isinstance(obj, jcore.ClosedJaxpr):
        return jcore.ClosedJaxpr(new_jaxpr, obj.consts)
    return new_jaxpr


def _rebuild(jaxpr: jcore.Jaxpr, addr, edit) -> jcore.Jaxpr:
    if not addr:
        return edit(jaxpr)
    (i, k, ti), rest = addr[0], addr[1:]
    eqn = jaxpr.eqns[i]
    v = eqn.params[k]
    if ti is None:
        new_v = _rewrap(v, _rebuild(_inner(v), rest, edit))
    else:
        seq = list(v)
        seq[ti] = _rewrap(seq[ti], _rebuild(_inner(seq[ti]), rest, edit))
        new_v = tuple(seq) if isinstance(v, tuple) else seq
    params = dict(eqn.params)
    params[k] = new_v
    eqns = list(jaxpr.eqns)
    eqns[i] = eqn.replace(params=params)
    return jaxpr.replace(eqns=eqns)


def rewrite_at(closed: jcore.ClosedJaxpr, addr,
               edit: Callable[[jcore.Jaxpr], jcore.Jaxpr]
               ) -> jcore.ClosedJaxpr:
    """Apply `edit` to the jaxpr at `addr`, rebuilding the spine; the
    input ClosedJaxpr (and everything it shares with the trace cache) is
    left untouched."""
    return jcore.ClosedJaxpr(_rebuild(closed.jaxpr, addr, edit),
                             closed.consts)


# ------------------------------------------------------ jaxpr-edit bricks


def _drop_eqns(idxs):
    idxs = sorted(idxs, reverse=True)

    def edit(jaxpr):
        eqns = list(jaxpr.eqns)
        for i in idxs:
            del eqns[i]
        return jaxpr.replace(eqns=eqns)
    return edit


def _replace_eqn(i: int, fn):
    def edit(jaxpr):
        eqns = list(jaxpr.eqns)
        eqns[i] = fn(eqns[i])
        return jaxpr.replace(eqns=eqns)
    return edit


def _set_param(i: int, key: str, value):
    def fn(eqn):
        params = dict(eqn.params)
        params[key] = value
        return eqn.replace(params=params)
    return _replace_eqn(i, fn)


def _subst_var(old, new):
    """Substitute a jaxpr-input var everywhere in one jaxpr (invars,
    constvars, every eqn's invars, outvars) — the ring-shrink edit."""
    def sw(v):
        return new if v is old else v

    def edit(jaxpr):
        eqns = [e.replace(invars=[sw(v) for v in e.invars])
                if any(v is old for v in e.invars) else e
                for e in jaxpr.eqns]
        return jaxpr.replace(
            eqns=eqns,
            invars=[sw(v) for v in jaxpr.invars],
            constvars=[sw(v) for v in jaxpr.constvars],
            outvars=[sw(v) for v in jaxpr.outvars])
    return edit


def _fresh_var(aval) -> jcore.Var:
    return jcore.Var(aval)


def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * int(aval.dtype.itemsize)
    except Exception:               # noqa: BLE001 — abstract dims
        return 0


# ------------------------------------------------------ operator registry


@dataclasses.dataclass
class Mutant:
    """One (target, operator, site) cell, pre-edit."""
    target: str
    operator: str
    index: int                      # ordinal within (target, operator)
    site: str                       # source provenance of the edited eqn
    note: str                       # which edit variant was applied
    addr: tuple                     # address of the enclosing jaxpr
    edit: Callable                  # Jaxpr -> Jaxpr

    @property
    def cell_id(self) -> str:
        return f"{self.target}|{self.operator}|{self.index}"

    def build(self, closed: jcore.ClosedJaxpr) -> jcore.ClosedJaxpr:
        return rewrite_at(closed, self.addr, self.edit)


@dataclasses.dataclass(frozen=True)
class MutOp:
    """One registered mutation operator."""
    name: str
    doc: str
    expect: tuple[str, ...]         # "pass/code" kill expectations, ranked
    find: Callable                  # (trace, flow) -> list[(addr, i, eqn,
    #                                                        note, edit)]


def _local_root(jaxpr: jcore.Jaxpr, upto: int, var):
    """dataflow._operand_root against THIS jaxpr's defs: walk a scatter
    operand back through scatter/reinterpret eqns to the var no eqn here
    defines (the enclosing jaxpr's input — the persistent array)."""
    defs = {}
    for eqn in jaxpr.eqns[:upto]:
        for ov in eqn.outvars:
            defs[ov] = eqn
    for _ in range(256):
        if isinstance(var, jcore.Literal):
            return None
        eqn = defs.get(var)
        if eqn is None:
            return var
        if eqn.primitive.name in df._SCATTER_FAMILY \
                or eqn.primitive.name in df._STATE_SHAPE_OPS:
            var = eqn.invars[0]
            continue
        return var
    return var


def _install_sites(flow: df.Dataflow) -> set[str]:
    """Source sites of the overwrite installs the protocol pass governs."""
    return {r.site for r in flow.scatters
            if r.prim == "scatter" and r.is_state and not r.in_pallas}


def _log_sites(flow: df.Dataflow) -> dict[str, object]:
    """site -> root for the log-append scatters."""
    return {r.site: r.root for r in flow.log_appends()}


def _find_drop_eqn(trace, flow):
    """One candidate per protocol-bearing eqn kind: the lock-arbitration
    GROUP (every scatter-max/min in the first jaxpr that holds one —
    multi-table engines arbitrate per table, and dropping one of a pair
    leaves the merged win mask tainted by the other), the first
    ppermute, the first log-append scatter."""
    logs = _log_sites(flow)
    picked: dict[str, tuple] = {}
    groups = {"lock-arb": (None, [], None), "ppermute": (None, [], None)}
    for addr, jaxpr, i, eqn, in_pl in walk_addressed(trace.jaxpr):
        if in_pl:
            continue
        prim = eqn.primitive.name
        gk = ("lock-arb" if prim in df._SCATTER_ARB
              else "ppermute" if prim == "ppermute" else None)
        if gk:
            gaddr, gidxs, geqn = groups[gk]
            if gaddr is None:
                gaddr, geqn = addr, eqn
            if addr == gaddr:
                gidxs.append(i)
            groups[gk] = (gaddr, gidxs, geqn)
            continue
        if prim == "scatter" and site_of(eqn) in logs \
                and "log-append" not in picked:
            picked["log-append"] = (addr, i, eqn,
                                    "drop log-append (scatter)",
                                    _drop_eqns([i]))
    out = []
    for gk in ("lock-arb", "ppermute"):
        gaddr, gidxs, geqn = groups[gk]
        if gidxs:
            out.append((gaddr, gidxs[0], geqn,
                        f"drop {len(gidxs)} {gk} eqn(s)",
                        _drop_eqns(gidxs)))
    if "log-append" in picked:
        out.append(picked["log-append"])
    return out


def _find_weaken_scatter(trace, flow):
    """scatter-max -> overwrite on the first lock arbitration; flip the
    certification bit on the first unique-certified install."""
    installs = _install_sites(flow)
    out, seen = [], set()
    for addr, jaxpr, i, eqn, in_pl in walk_addressed(trace.jaxpr):
        if in_pl:
            continue
        prim = eqn.primitive.name
        if prim in df._SCATTER_ARB and "arb->overwrite" not in seen:
            seen.add("arb->overwrite")
            out.append((addr, i, eqn, f"{prim} -> overwrite scatter",
                        _replace_eqn(i, lambda e: e.replace(
                            primitive=_lsl.scatter_p))))
        elif (prim == "scatter" and site_of(eqn) in installs
                and eqn.params.get("unique_indices")
                and "unique-flip" not in seen):
            seen.add("unique-flip")
            out.append((addr, i, eqn, "unique_indices=True -> False",
                        _set_param(i, "unique_indices", False)))
    return out


def _find_mask_swap(trace, flow):
    """Replace an install's index AND update operands with fresh
    unconstrained vars: the written mask/values no longer descend from
    the lock grant or the validate compare (write_facts goes empty —
    the dataflow pass must see an install nobody certified). Swapping
    only the indices is not enough: engines bake the win mask into the
    update via where(win, new, old), so update_facts alone keeps the
    install certified."""
    installs = _install_sites(flow)
    out = []
    for addr, jaxpr, i, eqn, in_pl in walk_addressed(trace.jaxpr):
        if in_pl or eqn.primitive.name != "scatter":
            continue
        if site_of(eqn) not in installs or len(eqn.invars) < 3:
            continue
        if any(isinstance(v, jcore.Literal) for v in eqn.invars[1:3]):
            continue
        news = [_fresh_var(eqn.invars[1].aval),
                _fresh_var(eqn.invars[2].aval)]

        def fn(eqn, news=news):
            invars = list(eqn.invars)
            invars[1:3] = news
            return eqn.replace(invars=invars)
        out.append((addr, i, eqn, "indices+updates -> unconstrained vars",
                    _replace_eqn(i, fn)))
        if len(out) >= MAX_SITES:
            break
    return out


def _perm_axis(eqn) -> str:
    ax = eqn.params.get("axis_name", eqn.params.get("axes", ""))
    if isinstance(ax, (tuple, list)):
        ax = ",".join(str(a) for a in ax)
    return str(ax)


def _collapse_perms(idxs):
    """Rewrite every named ppermute's perm to the single +1 neighbor:
    each source keeps exactly one destination ACROSS the whole hop
    group (quorum-fanout unions destinations over all live perms, so
    collapsing one hop of a redundant pair changes nothing)."""
    def fn(eqn):
        perm = tuple(eqn.params.get("perm") or ())
        n = len(perm)
        params = dict(eqn.params)
        params["perm"] = tuple((int(s), (int(s) + 1) % n) for s, _ in perm)
        return eqn.replace(params=params)

    def edit(jaxpr):
        eqns = list(jaxpr.eqns)
        for i in idxs:
            eqns[i] = fn(eqns[i])
        return jaxpr.replace(eqns=eqns)
    return edit


def _find_axis_swap(trace, flow):
    """Reroute a dcn replication hop onto the ici axis, or collapse
    every hop's perm to one shared +1 destination per source."""
    out, seen = [], set()
    mesh_axes = tuple(getattr(trace, "mesh_axes", ()) or ())
    grp_addr, grp_idxs, grp_eqn = None, [], None
    for addr, jaxpr, i, eqn, in_pl in walk_addressed(trace.jaxpr):
        if in_pl or eqn.primitive.name != "ppermute":
            continue
        perm = tuple(eqn.params.get("perm") or ())
        if not perm or all(int(s) == int(d) for s, d in perm):
            continue
        ax = _perm_axis(eqn)
        if "dcn" in ax and "dcn->ici" not in seen and len(mesh_axes) >= 2:
            seen.add("dcn->ici")
            ici = next((a for a in mesh_axes if "dcn" not in str(a)),
                       mesh_axes[-1])
            out.append((addr, i, eqn, f"axis {ax!r} -> {str(ici)!r}",
                        _set_param(i, "axis_name", str(ici))))
        if grp_addr is None:
            grp_addr, grp_eqn = addr, eqn
        if addr == grp_addr:
            grp_idxs.append(i)
    if grp_idxs:
        out.append((grp_addr, grp_idxs[0], grp_eqn,
                    f"{len(grp_idxs)} perm(s) -> single +1 destination",
                    _collapse_perms(grp_idxs)))
    return out


def _find_widen_gather(trace, flow):
    """Double the leading output dim of the largest gather out of
    persistent state (the table-row read that dominates its wave's byte
    ledger; a gather out of a temporary, as a chunk of the compacted
    install makes of its 2w-wide operands, is no table read and the
    ledger does not price it)."""
    best = None
    for addr, jaxpr, i, eqn, in_pl in walk_addressed(trace.jaxpr):
        if in_pl or eqn.primitive.name != "gather" or not eqn.outvars:
            continue
        if eqn.invars[0] not in flow.state_vars:
            continue
        aval = eqn.outvars[0].aval
        shape = tuple(getattr(aval, "shape", ()))
        if not shape:
            continue
        nb = _aval_bytes(aval)
        if best is None or nb > best[0]:
            best = (nb, addr, i, eqn)
    if best is None:
        return []
    _, addr, i, eqn = best
    aval = eqn.outvars[0].aval
    wide = aval.update(shape=(2 * aval.shape[0],) + tuple(aval.shape[1:]))
    new = _fresh_var(wide)

    def fn(eqn, new=new):
        outvars = list(eqn.outvars)
        outvars[0] = new
        return eqn.replace(outvars=outvars)
    return [(addr, i, eqn,
             f"gather out {tuple(aval.shape)} -> {tuple(wide.shape)}",
             _replace_eqn(i, fn))]


def _find_drop_donation(trace, flow):
    """Clear donated_invars on the top-level donated pjit (the one
    cost._footprint credits the donation discount to)."""
    out = []
    for i, eqn in enumerate(trace.jaxpr.eqns):
        if eqn.primitive.name != "jit":
            continue
        don = tuple(eqn.params.get("donated_invars") or ())
        if not any(don):
            continue
        out.append(((), i, eqn, f"cleared {sum(don)} donated invars",
                    _set_param(i, "donated_invars",
                               (False,) * len(don))))
        if len(out) >= 1:
            break
    return out


def _find_ring_shrink(trace, flow):
    """Shrink the log ring array feeding an append to 2 slots (in
    the append's ENCLOSING jaxpr — the ring root there is the scan-body
    carry var, resolved exactly like dataflow's _operand_root)."""
    logs = _log_sites(flow)
    out, done = [], set()
    for addr, jaxpr, i, eqn, in_pl in walk_addressed(trace.jaxpr):
        if in_pl or eqn.primitive.name != "scatter":
            continue
        if site_of(eqn) not in logs:
            continue
        root = _local_root(jaxpr, i, eqn.invars[0])
        if root is None or id(root) in done:
            continue
        shape = tuple(getattr(root.aval, "shape", ()))
        if len(shape) == 3:
            small = (1, 2) + shape[2:]
        elif len(shape) == 2:
            small = (2,) + shape[1:]
        else:
            continue
        done.add(id(root))
        new = _fresh_var(root.aval.update(shape=small))
        out.append((addr, i, eqn, f"ring {shape} -> {small} (2 slots)",
                    _subst_var(root, new)))
        if len(out) >= MAX_SITES:
            break
    return out


OPERATORS: dict[str, MutOp] = {op.name: op for op in [
    MutOp("drop-eqn",
          "delete a lock-arbitration / ppermute / log-append eqn",
          ("protocol/unlocked-install", "durability/quorum-fanout",
           "protocol/no-replication-push", "durability/wal-order",
           "protocol/no-writer-election"),
          _find_drop_eqn),
    MutOp("weaken-scatter",
          "scatter-max -> overwrite; flip unique_indices certification",
          ("scatter_race/nonunique-scatter", "protocol/unlocked-install",
           "protocol/uncertified-install", "protocol/no-writer-election"),
          _find_weaken_scatter),
    MutOp("mask-swap",
          "replace an install mask/index input with an unconstrained var",
          ("protocol/unlocked-install", "protocol/unvalidated-install",
           "protocol/unelected-install"),
          _find_mask_swap),
    MutOp("axis-swap",
          "ppermute dcn -> ici; collapse a perm's destinations",
          ("durability/quorum-fanout",),
          _find_axis_swap),
    MutOp("widen-gather",
          "double a table gather's output rows to blow the byte ledger",
          ("cost_budget/formula-mismatch", "cost_budget/over-bytes-budget"),
          _find_widen_gather),
    MutOp("drop-donation",
          "clear donated_invars on the top-level pjit",
          ("cost_budget/over-footprint-budget",),
          _find_drop_donation),
    MutOp("ring-shrink",
          "shrink a log ring to 2 slots",
          ("durability/unbounded-ring",),
          _find_ring_shrink),
]}


def discover(trace: TargetTrace, operators) -> list[Mutant]:
    """Enumerate the mutant cells for one target, deterministically (walk
    order x registry order), capped at MAX_SITES per operator."""
    if trace.jaxpr is None:
        return []
    flow = df.analyze(trace)
    out: list[Mutant] = []
    for opname in operators:
        op = OPERATORS[opname]
        for idx, (addr, i, eqn, note, edit) in enumerate(
                op.find(trace, flow)[:MAX_SITES]):
            out.append(Mutant(trace.name, opname, idx, site_of(eqn),
                              note, addr, edit))
    return out


# --------------------------------------------------------- mutant running


def _run_passes(trace: TargetTrace, passes, entries) -> list[Finding]:
    """Run the structural pass matrix on one (possibly mutant) trace; a
    pass crash on a corrupted jaxpr is itself a loud detection and is
    recorded as a synthetic `<pass>/pass-crash` ERROR."""
    from . import allowlist as al
    findings: list[Finding] = []
    for pname in passes:
        try:
            findings.extend(PASSES[pname](trace))
        except Exception as e:      # noqa: BLE001 — crash = detection
            findings.append(Finding(
                pname, "pass-crash", SEV_ERROR, trace.name,
                f"pass crashed on this jaxpr: {type(e).__name__}: {e}"))
    al.apply(findings, entries, check_unused=False)
    return findings


def _error_set(findings) -> set[tuple[str, str]]:
    return {(f.pass_name, f.code) for f in findings
            if f.severity == SEV_ERROR and not f.suppressed}


def _suppressed_set(findings) -> set[tuple[str, str]]:
    return {(f.pass_name, f.code) for f in findings if f.suppressed}


def _load_entries():
    from . import allowlist as al
    from .cli import DEFAULT_ALLOWLIST
    if os.path.exists(DEFAULT_ALLOWLIST):
        return al.load(DEFAULT_ALLOWLIST)
    return []


class MutRunner:
    """Shared state for a matrix run: the pass list, the allowlist, and
    the per-target baseline error sets (computed once per target)."""

    def __init__(self, passes=None, entries=None):
        self.passes = list(passes) if passes else mut_passes()
        self.entries = entries if entries is not None else _load_entries()
        self._baseline: dict[str, set] = {}

    def baseline(self, trace: TargetTrace) -> set[tuple[str, str]]:
        got = self._baseline.get(trace.name)
        if got is None:
            got = _error_set(_run_passes(trace, self.passes, self.entries))
            self._baseline[trace.name] = got
        return got

    def run_cell(self, trace: TargetTrace, mut: Mutant, expect) -> dict:
        """Build + analyze one mutant; returns the MUTCOV cell record."""
        mtrace = TargetTrace(trace.name, mut.build(trace.closed_jaxpr),
                             mesh_axes=trace.mesh_axes,
                             protocol=trace.protocol)
        findings = _run_passes(mtrace, self.passes, self.entries)
        new = sorted(f"{p}/{c}" for p, c
                     in _error_set(findings) - self.baseline(trace))
        killer = ""
        if new:
            killer = next((e for e in expect if e in new), new[0])
        return {
            "id": mut.cell_id,
            "target": mut.target,
            "operator": mut.operator,
            "site": mut.site,
            "note": mut.note,
            "verdict": "killed" if new else "survived",
            "killer": killer,
            "new_errors": new,
            "suppressed": sorted(f"{p}/{c}" for p, c
                                 in _suppressed_set(findings)),
        }


# ----------------------------------------------------- MUTCOV.json pinning


def _digest(obj) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True, default=str).encode()
    ).hexdigest()[:16]


def registry_hash() -> str:
    """Pins the operator registry + pass matrix + policy knobs: any edit
    to what dintmut mutates or how kills are judged must re-pin."""
    return _digest({
        "schema": SCHEMA,
        "floor": KILL_RATE_FLOOR,
        "max_sites": MAX_SITES,
        "passes": mut_passes(),
        "operators": {name: {"doc": op.doc, "expect": list(op.expect)}
                      for name, op in OPERATORS.items()},
    })


def matrix_hash() -> str:
    """Pins the target matrix (names + protocol flags + operator sets)."""
    from . import targets as T
    return _digest({
        name: {"protocol": list(T.TARGET_PROTOCOL.get(name, ())),
               "operators": list(ops)}
        for name, ops in T.MUT_TARGETS.items()})


def _summary(cells: list[dict]) -> dict:
    by_op: dict[str, dict] = {}
    killers: dict[str, int] = {}
    for c in cells:
        rec = by_op.setdefault(c["operator"], {"cells": 0, "killed": 0})
        rec["cells"] += 1
        if c["verdict"] == "killed":
            rec["killed"] += 1
            kp = c["killer"].split("/", 1)[0]
            killers[kp] = killers.get(kp, 0) + 1
    n_killed = sum(r["killed"] for r in by_op.values())
    return {
        "n_cells": len(cells),
        "n_killed": n_killed,
        "n_survived": len(cells) - n_killed,
        "kill_rate": round(n_killed / len(cells), 4) if cells else 0.0,
        "by_operator": {k: by_op[k] for k in sorted(by_op)},
        "killer_passes": {k: killers[k] for k in sorted(killers)},
    }


def quick_sample(cells: list[dict], seed: int = QUICK_SEED) -> list[str]:
    """One deterministically sampled cell per operator (the dintgate
    quick gate re-executes these bit-for-bit)."""
    rnd = random.Random(seed)
    out = []
    by_op: dict[str, list[str]] = {}
    for c in cells:
        by_op.setdefault(c["operator"], []).append(c["id"])
    for op in sorted(by_op):
        ids = sorted(by_op[op])
        out.append(ids[rnd.randrange(len(ids))])
    return out


def run_matrix(targets=None, progress=None) -> dict:
    """Execute the full (target x operator x site) matrix and assemble
    the MUTCOV document (unpinned — callers save_mutcov to pin it)."""
    from . import targets as T
    matrix = dict(T.MUT_TARGETS)
    if targets is not None:
        matrix = {k: v for k, v in matrix.items() if k in set(targets)}
    runner = MutRunner()
    cells: list[dict] = []
    skipped: list[str] = []
    for tname in sorted(matrix):
        try:
            trace = T.get_trace(tname)
        except T.SkipTarget:
            skipped.append(tname)
            continue
        if trace.jaxpr is None:
            skipped.append(tname)
            continue
        for mut in discover(trace, matrix[tname]):
            if progress:
                progress(mut)
            cells.append(runner.run_cell(
                trace, mut, OPERATORS[mut.operator].expect))
    doc = {
        "schema": SCHEMA,
        "kill_rate_floor": KILL_RATE_FLOOR,
        "passes": runner.passes,
        "operators": {name: {"doc": op.doc, "expect": list(op.expect)}
                      for name, op in sorted(OPERATORS.items())},
        "targets": {name: {"protocol":
                           list(T.TARGET_PROTOCOL.get(name, ())),
                           "operators": list(matrix[name])}
                    for name in sorted(matrix)},
        "skipped": skipped,
        "cells": cells,
        "summary": _summary(cells),
        "quick": {"seed": QUICK_SEED, "cells": quick_sample(cells)},
        "provenance": {"registry": registry_hash(),
                       "matrix": matrix_hash(),
                       "cells": _digest(cells)},
    }
    return doc


def run_cells(cell_ids, passes=None) -> list[dict]:
    """Re-execute specific pinned cells (the quick gate): rediscover the
    named targets' mutants and run exactly the requested ids. Unknown
    ids come back as verdict 'missing-cell' — registry/code drift."""
    from . import targets as T
    wanted = list(cell_ids)
    by_target: dict[str, list[str]] = {}
    for cid in wanted:
        by_target.setdefault(cid.split("|", 1)[0], []).append(cid)
    runner = MutRunner(passes=passes)
    got: dict[str, dict] = {}
    for tname, ids in sorted(by_target.items()):
        if tname not in T.MUT_TARGETS:
            continue
        try:
            trace = T.get_trace(tname)
        except T.SkipTarget:
            continue
        if trace.jaxpr is None:
            continue
        muts = {m.cell_id: m
                for m in discover(trace, T.MUT_TARGETS[tname])}
        for cid in ids:
            if cid in muts:
                got[cid] = runner.run_cell(
                    trace, muts[cid], OPERATORS[muts[cid].operator].expect)
    return [got.get(cid, {"id": cid, "verdict": "missing-cell"})
            for cid in wanted]


def mutcov_path() -> Path:
    env = os.environ.get(ENV_MUTCOV)
    if env:
        return Path(env)
    return Path(__file__).resolve().parents[2] / ARTIFACT


def save_mutcov(doc: dict, path=None) -> Path:
    p = Path(path) if path else mutcov_path()
    p.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    return p


def load_mutcov(path=None) -> dict:
    p = Path(path) if path else mutcov_path()
    with open(p) as fh:
        doc = json.load(fh)
    if doc.get("schema") != SCHEMA:
        raise ValueError(
            f"{p}: MUTCOV schema {doc.get('schema')!r} != {SCHEMA} — "
            "regenerate with `python tools/dintmut.py run`")
    return doc
