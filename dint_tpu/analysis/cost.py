"""dintcost derivation: the static cost model behind passes/cost_budget.

dintlint proves the hot paths are *safe* and dintproof that they are
*sequenced*; neither says what they COST. The reference stack argues its
design from a per-RPC bytes-and-round-trips ledger measured at the NIC
driver; our port has that ledger twice — hand-declared formulas in
monitor/waves.py and dintscope timings that need a TPU — and the entire
hardware A/B backlog waits on chip time. This module derives
the third copy FROM THE JAXPR, so an extra dispatch, a doubled gather or
a silently dropped donation becomes a deterministic CPU-only CI failure.

Per registered target (analysis/targets.py, trace-once cache) we walk the
traced jaxpr — through pjit / scan / while / cond / shard_map, the same
traversal discipline as analysis/dataflow.py — and derive three numbers:

* **Logical HBM bytes per step.** Every `gather` whose operand descends
  from persistent state counts its output bytes (random row reads);
  every scatter-family eqn over state counts its update bytes (row
  writes); `ppermute`/`all_to_all` count their operand bytes once (the
  ICI move — the same convention the waves.py formulas use); a Pallas
  kernel counts the rows it produces (_pallas_bytes).
  Elementwise/VPU traffic is deliberately NOT modeled — formulas and
  derivation both measure the random-access row traffic that dominates
  the engines (PERF.md round 3), not XLA padding or fusion residue.
* **Dispatch count per step.** One per counted gather/scatter site, one
  per collective, one per `pallas_call` — the length of the dependency
  chain of non-fusable memory ops.
* **Persistent footprint.** Input bytes of the jitted step plus every
  output buffer NOT matched (shape+dtype) to a donated input — the
  donation-aware live-state size. Dropping a `donate_argnums` doubles
  it, which is precisely the regression this catches.
* **Per-axis link bytes** (round 14). Each collective additionally
  carries an interconnect attribution: the axis class it is priced on
  ("dcn" when any of its mesh axis names contains ``dcn``, else "ici")
  and its link bytes — the traffic the collective schedules on that
  axis. An untiled `all_to_all` prices (n-1)/n of its operand (the self
  shard never moves; n = the split dimension's size, which for untiled
  a2a IS the axis size); `ppermute` prices its full operand. The slow
  axis is deliberately conservative: a flat collective over a tuple
  axis that includes "dcn" schedules its WHOLE exchange at DCN speed —
  the static model cannot see a transport-level decomposition that the
  program did not express — so an explicit hierarchical (ici, then dcn)
  decomposition is exactly what moves bytes off the priced slow axis.
  `Access.bytes` keeps the original whole-operand convention, so every
  calibrated budget and waves.py reconciliation is unchanged; the
  per-axis figures are a parallel ledger gated by the
  hier-dcn-dominance check in passes/cost_budget.py.

Scan bodies multiply their costs by the trace's `length` (the registered
targets trace one block = `_BLK` cohorts) and the model divides by the
target's declared steps-per-trace, so everything is reported per engine
step. `cond` branches contribute their most expensive branch (the rebase
branch is costed, not averaged away). Wave attribution rides
`jax.named_scope`: the dintscope annotations survive tracing in each
eqn's `source_info.name_stack`, so the same names that key measured time
(monitor/attrib.py) key the derived bytes — dintscope measures what
dintcost predicts.

Models are memoized per TargetTrace (`model_for`), like dataflow, so the
36-target matrix derives once per process.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Iterable

import jax._src.core as jcore

from ..monitor import waves
from ..monitor.attrib import WAVE_ALIASES
from .core import TargetTrace, site_of

# formula-vs-derived reconciliation band: |derived/declared - 1| <= tol.
# The default covers the registry's coarsest hand estimate (the ~20 B
# log-entry header vs the real HDR_WORDS=4 -> 16 B: ratio 0.89).
DEFAULT_TOL = 0.25

_WAVE_RE = re.compile(r"dint\.[A-Za-z0-9_]+\.[A-Za-z0-9_]+")

_SCATTER_FAMILY = frozenset({"scatter", "scatter-add", "scatter-mul",
                             "scatter-min", "scatter-max"})
_COLLECTIVES = frozenset({"ppermute", "all_to_all"})
# call-like primitives whose single sub-jaxpr maps invars/outvars 1:1
_CALL_PRIMS = frozenset({"jit", "closed_call", "core_call", "remat",
                         "remat2", "checkpoint", "custom_jvp_call",
                         "custom_vjp_call", "custom_vjp_call_jaxpr",
                         "shard_map", "custom_partitioning"})


def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * int(aval.dtype.itemsize)
    except Exception:               # noqa: BLE001 — abstract token et al.
        return 0


def _aval_size(v) -> int:
    try:
        return int(v.aval.size)
    except Exception:               # noqa: BLE001
        return 0


def wave_of(eqn) -> str | None:
    """The innermost registered dint.<engine>.<wave> scope on an eqn's
    name stack, or None — jax.named_scope survives tracing verbatim, so
    the dintscope names ARE the cost model's attribution keys."""
    try:
        stack = str(eqn.source_info.name_stack)
    except Exception:               # noqa: BLE001
        return None
    hits = _WAVE_RE.findall(stack)
    return hits[-1] if hits else None


@dataclasses.dataclass
class Access:
    """One counted memory operation (already scan-multiplied)."""
    kind: str           # "gather" | "scatter" | "collective" | "pallas"
    prim: str
    wave: str | None    # full dint.<engine>.<wave> name, or None
    bytes: float        # logical bytes for the whole trace
    dispatches: float   # dispatch count for the whole trace
    site: str = ""
    path: str = ""
    axis: str = ""      # collectives only: "ici" | "dcn" (slowest axis)
    link_bytes: float = 0.0  # collectives only: bytes priced on `axis`


def collective_axis(eqn) -> str:
    """The axis class a collective is priced on: "dcn" when ANY of its
    mesh axis names contains "dcn", else "ici" (the flat 1-D "shard"
    axis is ICI-class). A tuple axis spanning both is priced "dcn" —
    one indivisible exchange runs at the speed of its slowest link."""
    ax = eqn.params.get("axis_name")
    names = ax if isinstance(ax, (tuple, list)) else (ax,)
    return "dcn" if any("dcn" in str(a) for a in names) else "ici"


def _collective_link(eqn, nb: float) -> tuple[str, float]:
    """(axis, link_bytes) for a collective eqn. Untiled all_to_all keeps
    its self shard local, so (n-1)/n of the operand crosses the axis —
    and for untiled a2a the split dimension's size IS the axis size, so
    n reads straight off the operand aval (no mesh needed at this
    layer). ppermute moves its whole operand."""
    axis = collective_axis(eqn)
    if eqn.primitive.name == "all_to_all" and \
            not eqn.params.get("tiled", False):
        try:
            split = int(eqn.params.get("split_axis"))
            n = int(eqn.invars[0].aval.shape[split])
        except Exception:           # noqa: BLE001 — unknown layout
            n = 0
        if n > 1:
            return axis, nb * (n - 1) / n
    return axis, nb


@dataclasses.dataclass
class CostModel:
    """The derived per-target cost model (all `*_per_step` figures are
    normalized by the registered steps-per-trace)."""
    target: str
    steps: float
    geom: dict
    accesses: list[Access]
    footprint_bytes: int
    input_bytes: int
    donated_bytes: int
    error: str = ""

    @property
    def bytes_per_step(self) -> float:
        return sum(a.bytes for a in self.accesses) / self.steps

    @property
    def dispatches_per_step(self) -> float:
        return sum(a.dispatches for a in self.accesses) / self.steps

    def wave_bytes_per_step(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for a in self.accesses:
            key = a.wave or "(unattributed)"
            out[key] = out.get(key, 0.0) + a.bytes / self.steps
        return out

    def wave_dispatches_per_step(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for a in self.accesses:
            key = a.wave or "(unattributed)"
            out[key] = out.get(key, 0.0) + a.dispatches / self.steps
        return out

    def axis_bytes_per_step(self) -> dict[str, float]:
        """Per-axis interconnect link bytes/step ({"ici": x, "dcn": y});
        HBM gathers/scatters carry no axis and are excluded."""
        out = {"ici": 0.0, "dcn": 0.0}
        for a in self.accesses:
            if a.axis:
                out[a.axis] = out.get(a.axis, 0.0) \
                    + a.link_bytes / self.steps
        return out

    @property
    def dcn_bytes_per_step(self) -> float:
        return self.axis_bytes_per_step().get("dcn", 0.0)

    def wave_axis_bytes_per_step(self) -> dict[str, dict[str, float]]:
        out: dict[str, dict[str, float]] = {}
        for a in self.accesses:
            if not a.axis:
                continue
            key = a.wave or "(unattributed)"
            per = out.setdefault(key, {"ici": 0.0, "dcn": 0.0})
            per[a.axis] = per.get(a.axis, 0.0) + a.link_bytes / self.steps
        return out

    def to_dict(self) -> dict:
        per_axis = self.wave_axis_bytes_per_step()
        tot_axis = self.axis_bytes_per_step()
        return {
            "target": self.target,
            "steps": self.steps,
            "geom": dict(self.geom),
            "bytes_per_step": round(self.bytes_per_step, 2),
            "dispatches_per_step": round(self.dispatches_per_step, 3),
            "ici_bytes_per_step": round(tot_axis.get("ici", 0.0), 2),
            "dcn_bytes_per_step": round(tot_axis.get("dcn", 0.0), 2),
            "footprint_bytes": self.footprint_bytes,
            "input_bytes": self.input_bytes,
            "donated_bytes": self.donated_bytes,
            "waves": {
                w: {"bytes_per_step": round(b, 2),
                    "dispatches_per_step": round(
                        self.wave_dispatches_per_step().get(w, 0.0), 3),
                    "ici_bytes_per_step": round(
                        per_axis.get(w, {}).get("ici", 0.0), 2),
                    "dcn_bytes_per_step": round(
                        per_axis.get(w, {}).get("dcn", 0.0), 2)}
                for w, b in sorted(self.wave_bytes_per_step().items())},
            "error": self.error,
        }


# ----------------------------------------------------- kernel byte rule


def _pallas_bytes(eqn) -> float:
    """A Pallas kernel moves its traffic inside one dispatch; the jaxpr
    only shows the call. Non-aliased outputs are rows the kernel
    produced; aliased outputs are in-place updates whose row traffic the
    call does not show."""
    aliases = dict(eqn.params.get("input_output_aliases") or {})
    aliased_out = set(int(v) for v in aliases.values())
    return float(sum(_aval_bytes(o.aval)
                     for i, o in enumerate(eqn.outvars)
                     if i not in aliased_out))


# ------------------------------------------------------------ the walker


class _CostWalker:
    """One derivation pass: propagates an is-persistent-state bit through
    the jaxpr (seeded on the top-level inputs, flowing through scatters,
    carries and size-preserving ops — a boolean shadow of dataflow.py's
    STATE fact) and records counted accesses with scan multipliers."""

    def __init__(self):
        self.accesses: list[Access] = []

    # -- state environment helpers ---------------------------------------

    @staticmethod
    def _read(env: dict, v) -> bool:
        if isinstance(v, jcore.Literal):
            return False
        return env.get(v, False)

    def run(self, jaxpr: jcore.Jaxpr, in_state: list[bool], mult: float,
            record: bool, path: tuple[str, ...] = (),
            wave_ctx: str | None = None) -> list[bool]:
        env: dict = {}
        for var, st in zip(jaxpr.invars, in_state):
            env[var] = bool(st)
        for var in jaxpr.constvars:
            env[var] = False
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env, mult, record, path, wave_ctx)
        return [self._read(env, v) for v in jaxpr.outvars]

    # -- recording -------------------------------------------------------

    def _rec(self, eqn, kind: str, nbytes: float, mult: float,
             record: bool, path, wave_ctx, dispatches: float = 1.0,
             axis: str = "", link_bytes: float = 0.0):
        if not record or mult <= 0:
            return
        self.accesses.append(Access(
            kind=kind, prim=eqn.primitive.name,
            wave=wave_of(eqn) or wave_ctx,
            bytes=nbytes * mult, dispatches=dispatches * mult,
            site=site_of(eqn), path="/".join(path),
            axis=axis, link_bytes=link_bytes * mult))

    # -- eqn dispatch ----------------------------------------------------

    def _eqn(self, eqn, env, mult, record, path, wave_ctx):
        prim = eqn.primitive.name
        ins = [self._read(env, v) for v in eqn.invars]
        # an eqn with its own scope re-anchors attribution for everything
        # nested below it (jit boundaries reset the traced name stack, so
        # a jitted kernel's pallas_call inherits the CALLER's wave)
        wave_ctx = wave_of(eqn) or wave_ctx

        if prim == "scan":
            outs = self._scan(eqn, ins, mult, record, path, wave_ctx)
        elif prim == "while":
            outs = self._while(eqn, ins, mult, record, path, wave_ctx)
        elif prim == "cond":
            outs = self._cond(eqn, ins, mult, record, path, wave_ctx)
        elif prim == "pallas_call":
            outs = self._pallas(eqn, ins, mult, record, path, wave_ctx)
        elif prim in _CALL_PRIMS:
            outs = self._call(eqn, ins, mult, record, path, wave_ctx)
        elif prim == "gather":
            if ins[0]:
                nb = _aval_bytes(eqn.outvars[0].aval)
                self._rec(eqn, "gather", float(nb), mult, record, path,
                          wave_ctx)
            outs = [False for _ in eqn.outvars]
        elif prim in _SCATTER_FAMILY:
            if ins[0]:
                upd = eqn.invars[2] if len(eqn.invars) > 2 else None
                nb = _aval_bytes(upd.aval) if upd is not None else 0
                self._rec(eqn, "scatter", float(nb), mult, record, path,
                          wave_ctx)
            outs = [ins[0] for _ in eqn.outvars]
        elif prim in _COLLECTIVES:
            nb = sum(_aval_bytes(v.aval) for v in eqn.invars
                     if not isinstance(v, jcore.Literal))
            axis, link = _collective_link(eqn, float(nb))
            self._rec(eqn, "collective", float(nb), mult, record, path,
                      wave_ctx, axis=axis, link_bytes=link)
            outs = list(ins[:len(eqn.outvars)]) + \
                [False] * max(0, len(eqn.outvars) - len(ins))
        elif prim == "dynamic_update_slice":
            outs = [ins[0] for _ in eqn.outvars]
        else:
            # default: state flows through any op that preserves a state
            # operand's element count (elementwise, select, convert,
            # transpose, reshape, squeeze, copy, optimization_barrier);
            # reductions and broadcasts drop it.
            outs = []
            for ov in eqn.outvars:
                osz = _aval_size(ov)
                outs.append(any(
                    st and _aval_size(iv) == osz and osz > 0
                    for st, iv in zip(ins, eqn.invars)))
        for ov, st in zip(eqn.outvars, outs):
            env[ov] = bool(st)

    # -- structured control flow -----------------------------------------

    @staticmethod
    def _first_sub(eqn, key: str):
        v = eqn.params.get(key)
        if isinstance(v, jcore.ClosedJaxpr):
            return v.jaxpr
        return v

    def _call(self, eqn, ins, mult, record, path, wave_ctx):
        sub = self._first_sub(eqn, "jaxpr")
        if sub is None or len(sub.invars) != len(eqn.invars):
            return [any(ins) for _ in eqn.outvars]
        outs = self.run(sub, ins, mult, record,
                        path + (eqn.primitive.name,), wave_ctx)
        if len(outs) != len(eqn.outvars):
            return [any(ins) for _ in eqn.outvars]
        return outs

    def _scan(self, eqn, ins, mult, record, path, wave_ctx):
        sub = self._first_sub(eqn, "jaxpr")
        if sub is None:
            return [any(ins) for _ in eqn.outvars]
        nc = int(eqn.params.get("num_consts", 0))
        ncar = int(eqn.params.get("num_carry", 0))
        length = int(eqn.params.get("length", 1))
        consts, carry, xs = ins[:nc], ins[nc:nc + ncar], ins[nc + ncar:]
        for _ in range(8):              # carry fixpoint (propagation only)
            outs = self.run(sub, consts + carry + xs, 0, False)
            new_carry = [a or b for a, b in zip(carry, outs[:ncar])]
            if new_carry == carry:
                break
            carry = new_carry
        outs = self.run(sub, consts + carry + xs, mult * length, record,
                        path + ("scan",), wave_ctx)
        carry_out = [a or b for a, b in zip(carry, outs[:ncar])]
        return carry_out + list(outs[ncar:])

    def _while(self, eqn, ins, mult, record, path, wave_ctx):
        body = self._first_sub(eqn, "body_jaxpr")
        if body is None:
            return [any(ins) for _ in eqn.outvars]
        nc = int(eqn.params.get("body_nconsts", 0))
        cond_nc = int(eqn.params.get("cond_nconsts", 0))
        consts = ins[cond_nc:cond_nc + nc]
        carry = ins[cond_nc + nc:]
        for _ in range(8):
            outs = self.run(body, consts + carry, 0, False)
            new_carry = [a or b for a, b in zip(carry, outs)]
            if new_carry == carry:
                break
            carry = new_carry
        # trip count is data-dependent: cost one iteration (the engines
        # only use while for bounded search loops, never for table waves)
        outs = self.run(body, consts + carry, mult, record,
                        path + ("while",), wave_ctx)
        return [a or b for a, b in zip(carry, outs)]

    def _cond(self, eqn, ins, mult, record, path, wave_ctx):
        branches = eqn.params.get("branches") or ()
        subs = [b.jaxpr if isinstance(b, jcore.ClosedJaxpr) else b
                for b in branches]
        if not subs:
            return [any(ins) for _ in eqn.outvars]
        opins = ins[1:]                 # drop the predicate
        merged = None
        best: list[Access] = []
        best_bytes = -1.0
        for sub in subs:
            if len(sub.invars) != len(opins):
                return [any(ins) for _ in eqn.outvars]
            saved = self.accesses
            self.accesses = []
            outs = self.run(sub, opins, mult, record, path + ("cond",),
                            wave_ctx)
            branch_acc = self.accesses
            self.accesses = saved
            b = sum(a.bytes for a in branch_acc)
            if b > best_bytes:
                best_bytes, best = b, branch_acc
            merged = outs if merged is None else \
                [a or b2 for a, b2 in zip(merged, outs)]
        # a cond costs its most expensive branch (the rebase pass is
        # costed as if taken — budgets are ceilings, not averages)
        self.accesses.extend(best)
        return merged or [any(ins) for _ in eqn.outvars]

    def _pallas(self, eqn, ins, mult, record, path, wave_ctx):
        self._rec(eqn, "pallas", _pallas_bytes(eqn), mult, record, path,
                  wave_ctx)
        aliases = dict(eqn.params.get("input_output_aliases") or {})
        outs = [False] * len(eqn.outvars)
        for in_idx, out_idx in aliases.items():
            ii, oi = int(in_idx), int(out_idx)
            if 0 <= ii < len(ins) and 0 <= oi < len(outs):
                outs[oi] = ins[ii]
        return outs


# ----------------------------------------------------------- footprint


def _footprint(jaxpr: jcore.Jaxpr) -> tuple[int, int, int]:
    """(footprint, input, donated) bytes for the traced step. Donation
    comes from the outermost pjit eqn's `donated_invars`; every output
    buffer is greedily matched (shape+dtype) against the donated pool —
    matched outputs reuse their input buffer, unmatched ones are new
    allocations the step keeps live."""
    best = None
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "jit":
            continue
        don = eqn.params.get("donated_invars")
        if not don or not any(don):
            continue
        size = sum(_aval_bytes(v.aval) for v in eqn.invars)
        if best is None or size > best[0]:
            best = (size, eqn, don)
    if best is None:
        in_b = sum(_aval_bytes(v.aval) for v in jaxpr.invars)
        out_b = sum(_aval_bytes(v.aval) for v in jaxpr.outvars)
        return in_b + out_b, in_b, 0
    _, eqn, don = best
    in_b = sum(_aval_bytes(v.aval) for v in eqn.invars)
    donated = [(v.aval.shape, str(v.aval.dtype), _aval_bytes(v.aval))
               for v, d in zip(eqn.invars, don) if d]
    don_b = sum(b for _, _, b in donated)
    pool: dict[tuple, int] = {}
    for shape, dt, _ in donated:
        pool[(shape, dt)] = pool.get((shape, dt), 0) + 1
    extra = 0
    for ov in eqn.outvars:
        key = (ov.aval.shape, str(ov.aval.dtype))
        if pool.get(key, 0) > 0:
            pool[key] -= 1              # in-place reuse of a donated buffer
        else:
            extra += _aval_bytes(ov.aval)
    return in_b + extra, in_b, don_b


# ----------------------------------------------------------- derivation


def derive(trace: TargetTrace, *, steps: float = 1.0,
           geom: dict | None = None) -> CostModel:
    """Walk one traced target into a CostModel (use `model_for` for the
    registered, memoized path)."""
    geom = dict(geom or {})
    if trace.jaxpr is None:
        return CostModel(trace.name, steps, geom, [], 0, 0, 0,
                         error=f"trace failed: {trace.trace_error!r}")
    walker = _CostWalker()
    jaxpr = trace.jaxpr
    walker.run(jaxpr, [True] * len(jaxpr.invars), 1.0, True)
    fp, in_b, don_b = _footprint(jaxpr)
    return CostModel(trace.name, max(steps, 1e-9), geom, walker.accesses,
                     fp, in_b, don_b)


def model_for(name: str, trace: TargetTrace | None = None) -> CostModel:
    """The memoized cost model of a registered target (per-trace cache,
    like dataflow.analyze: the matrix derives once per process)."""
    from . import targets as T
    if trace is None:
        trace = T.get_trace(name)
    cached = getattr(trace, "_cost_model", None)
    if cached is not None:
        return cached
    meta = T.TARGET_COST.get(name, {})
    model = derive(trace, steps=meta.get("steps", 1.0),
                   geom=meta.get("geom", {}))
    trace._cost_model = model
    return model


# ------------------------------------------------------- reconciliation


@dataclasses.dataclass
class WaveCheck:
    """One wave's derived-vs-declared comparison (after alias-group
    folding and wave_expect adjustment)."""
    wave: str                   # the formula-bearing wave name
    members: tuple[str, ...]    # observed waves folded into it
    derived: float              # bytes/step
    declared: float             # expectation at the target's geometry
    tol: float
    expect: object = None       # applied wave_expect override, if any

    @property
    def ratio(self) -> float:
        return self.derived / self.declared if self.declared else 0.0

    @property
    def ok(self) -> bool:
        return abs(self.ratio - 1.0) <= self.tol


def _apply_expect(declared: float, expect, geom: dict) -> float:
    """A wave_expect value adjusts the registry formula for ONE target's
    documented layout deviation: a number scales it (hot double-pass =
    2.0), a string REPLACES it with a geometry formula evaluated at the
    target's geom (sharded 1-replica local log)."""
    if expect is None:
        return declared
    if isinstance(expect, (int, float)):
        return declared * float(expect)
    scope = {k: v for k, v in geom.items() if v is not None}
    try:
        return float(eval(str(expect), {"__builtins__": {}}, scope))  # noqa: S307
    except Exception:               # noqa: BLE001 — bad override = no change
        return declared


def reconcile(model: CostModel,
              wave_expect: dict[str, object] | None = None,
              tol_overrides: dict[str, float] | None = None,
              default_tol: float = DEFAULT_TOL) -> list[WaveCheck]:
    """Compare the derived per-wave bytes against every declared waves.py
    formula the target exercises. A wave that another scope takes over
    on one route folds into its successor first (attrib.WAVE_ALIASES —
    the same folding dintscope uses for its A/Bs), so it reconciles
    against the group formula. `wave_expect` carries the target's
    declared layout deviations from the base formula (targets.py cost=):
    derived is compared against the ADJUSTED expectation."""
    tols = tol_overrides or {}
    expects = wave_expect or {}
    per_wave = model.wave_bytes_per_step()
    observed = {w for w in per_wave if w != "(unattributed)"}
    groups: dict[str, set[str]] = {}
    consumed: set[str] = set()
    for w in observed:
        if w in WAVE_ALIASES and WAVE_ALIASES[w] in observed:
            succ = WAVE_ALIASES[w]
            groups.setdefault(succ, {succ}).add(w)
            consumed.add(w)
    checks: list[WaveCheck] = []
    for w in sorted(observed):
        if w in consumed:
            continue
        members = tuple(sorted(groups.get(w, {w})))
        declared = waves.wave_bytes(w, **model.geom)
        if declared is None:
            continue                    # compute-only / unmodeled wave
        exp = expects.get(w)
        adj = _apply_expect(float(declared), exp, model.geom)
        derived = sum(per_wave.get(m, 0.0) for m in members)
        checks.append(WaveCheck(
            wave=w, members=members, derived=derived, declared=adj,
            tol=tols.get(w, default_tol), expect=exp))
    return checks


def reconcile_for(name: str, model: CostModel | None = None
                  ) -> list[WaveCheck]:
    """reconcile() with the target's registered cost meta applied."""
    from . import targets as T
    if model is None:
        model = model_for(name)
    meta = T.TARGET_COST.get(name, {})
    return reconcile(model,
                     wave_expect=meta.get("wave_expect"),
                     tol_overrides=meta.get("tol"))


# ------------------------------------------------------------- budgets


def eval_budget_bytes(formula, geom: dict, ledger: float) -> float | None:
    """Evaluate a bytes-budget geometry formula. Variables: the target's
    geom (w, k, l, vw, d, ...) plus `ledger` = the summed waves.py
    formulas of every formula-backed wave the derivation observed — so
    "1.25*ledger" means "at most 25% above what the declared ledger says
    these waves should move"."""
    if formula is None:
        return None
    if isinstance(formula, (int, float)):
        return float(formula)
    scope = {k: v for k, v in geom.items() if v is not None}
    scope["ledger"] = ledger
    try:
        return float(eval(formula, {"__builtins__": {}}, scope))  # noqa: S307
    except Exception:               # noqa: BLE001 — bad formula = no budget
        return None


def ledger_bytes(model: CostModel,
                 wave_expect: dict[str, object] | None = None) -> float:
    """The declared-ledger total for the waves this model observed (after
    wave_expect adjustment): the budget formulas' `ledger` variable."""
    return float(sum(c.declared
                     for c in reconcile(model, wave_expect=wave_expect)))


def iter_models(names: Iterable[str]) -> Iterable[CostModel]:
    for n in names:
        yield model_for(n)
