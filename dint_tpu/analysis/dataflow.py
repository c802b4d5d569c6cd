"""dintproof dataflow: forward protocol-fact propagation over traced jaxprs.

dintlint's original passes (PR 2) are *local*: each looks at one eqn plus
a backward def slice. The protocol invariants the engines' correctness
argument actually rests on — FaSST-style OCC's "install only what you
locked AND validated" and 2PL's "every abort path releases its locks"
(FaSST, OSDI'16; engines/tatp_dense.py "Scatter discipline") — are
*interprocedural dataflow* properties: the lock grant computed at wave 1
of step t gates the install scatter at wave 3 of step t+2, two scan
iterations later. This module is the taint layer underneath
passes/protocol.py: a forward fact propagation over the whole traced
jaxpr, flowing through `pjit`/`shard_map`/`scan`/`while`/`cond`
sub-jaxprs, with scan/while carries iterated to a fixpoint so facts flow
around the pipeline loop exactly like the cohort contexts they model.

Facts (a small powerset lattice, may-analysis: a fact on a value means
"some contributing definition carries it"):

  provenance facts (computed first; the protocol seeds condition on them)
    STATE       the value IS persistent carry state (a table buffer).
                Seeded on every top-level jaxpr input; propagated only
                through scatter outputs, shape-preserving reinterpret
                ops, and size-preserving indexing (the shard_map body's
                `x[0]` squeeze) — a gather *from* state is a read, not
                the state.
    TBL_READ    gathered out of persistent state (a table read).
    ARB         produced by scatter-max/min (arbitration machinery);
                KILLED at overwrite-scatter outputs, so the character of
                an array tracks its last write: the step-stamped `arb`
                array stays ARB around the carry loop while a version
                table that was merely index-masked by a grant does not.
    SORTED      derived from `lax.sort` — the segment machinery whose
                head/last masks make generic-engine scatters one-writer
                by construction (same evidence ladder as scatter_race).

  protocol facts (computed second, against the converged provenance)
    LOCK_WIN    data-dependent on winning lock arbitration. Seeded at
                eq/ne compares with an ARB-carrying input (the batched-
                CAS grant compare `arb' == packed` / `first_x[slot] ==
                lane` / the expiring-stamp held test).
    VALIDATED   data-dependent on an OCC stamp-equality check. Seeded at
                eq/ne compares where an input carries TBL_READ, no input
                carries ARB (that is lock arbitration, not validation),
                and neither side is a literal/constant (`vvB != vv1`
                against the execute-time read seeds; `x == 0` exists
                tests and `magic != MAGIC` integrity tests do not).
    STAMP       derived from the scalar step counter packed into a lock
                word. Seeded at left-shifts of a rank-0 traced scalar
                (`step << K_ARB`) and at broadcasts of a rank-0 unsigned
                scalar rooted in a jaxpr-level scalar input
                (`x_step.at[...].set(t)`). Random-bit shift chains
                (threefry) are rank>0 and never seed.
    ABORT_MASK  a transaction-level abort aggregate. Seeded at
                `reduce_or` over LOCK_WIN/VALIDATED-carrying lanes —
                `lock_rejected = (active & ~granted).any(1)`,
                `changed = bad.any(1)` — the point where per-lane
                protocol outcomes become a per-txn abort decision.
    REPL_PUSHED crossed an ICI replication hop. Seeded at every
                `ppermute` output (the CommitBck/CommitLog fan-out).

  durability facts (dintdur, passes/durability.py; ANALYSIS.md
  "Durability facts & passes"):
    LOG_SLOT    (provenance) a ring slot id computed by the log-append
                machinery. Seeded at `rem` eqns whose source site lies in
                tables/log.py — the `pos % capacity` of `append`/
                `plan_rep` — so any scatter whose INDICES carry LOG_SLOT
                is a log append (append/append_rep) or the
                forwarded-backup route (_apply_backup).
    LOGGED      (protocol) written by a log-append scatter: seeded at
                scatter eqns whose index operand carries LOG_SLOT. The
                wal-order check pairs these appends against the
                commit-visible installs by their shared lane-mask facts.
    TRUNCATED   (protocol) a ring watermark advance: seeded at the `min`
                clamp of tables/log.advance_watermark. A durable target
                whose trace appends but never reaches a TRUNCATED seed
                has an unbounded ring (the ROADMAP log-truncation item).
  DURABLE is derived, not propagated: a LOGGED root is durable once the
  recorded ppermute perms prove >= 2 distinct non-self destinations per
  source (Dataflow.quorum_dests / durable_roots below) — the replica-
  quorum placement the quorum-fanout check enforces.

Why two phases: seed conditions like "TBL_READ without ARB" are not
monotone, so running them during the carry fixpoint would let an
under-resolved round-1 fact (the arb array before its scatter-max loops
back) plant a spurious VALIDATED that the join can never retract —
exactly the false negative that would let a validate-dropped engine slip
through. Provenance transfers ARE monotone, so phase 1 converges to the
least fixpoint; phase 2's seeds then read frozen provenance and its own
transfers are monotone in the protocol facts. Sites (seeds, scatters,
collectives) are recorded only on phase 2's final converged pass.

The result (`Dataflow`) is an inventory the protocol pass consumes:
per-scatter fact summaries with operand roots (which persistent array a
scatter chain writes), seed sites and ppermute sites. `analyze()` memoizes per TargetTrace, so the full target
matrix pays one dataflow per trace however many checks read it.
"""
from __future__ import annotations

import dataclasses

import jax._src.core as jcore

from .core import TargetTrace, site_of

# ------------------------------------------------------------------ facts

LOCK_WIN = "LOCK_WIN"
VALIDATED = "VALIDATED"
STAMP = "STAMP"
ABORT_MASK = "ABORT_MASK"
REPL_PUSHED = "REPL_PUSHED"
STATE = "STATE"
TBL_READ = "TBL_READ"
ARB = "ARB"
SORTED = "SORTED"
LOG_SLOT = "LOG_SLOT"
LOGGED = "LOGGED"
TRUNCATED = "TRUNCATED"

PROTOCOL_FACTS = (LOCK_WIN, VALIDATED, STAMP, ABORT_MASK, REPL_PUSHED,
                  LOGGED, TRUNCATED)
PROVENANCE_FACTS = (STATE, TBL_READ, ARB, SORTED, LOG_SLOT)
ALL_FACTS = PROTOCOL_FACTS + PROVENANCE_FACTS

# source anchor for the durability seeds: the slot math of append/plan_rep
# and the watermark clamp of advance_watermark both live here
_LOG_MODULE = "tables/log.py"

_SCATTER_ARB = frozenset({"scatter-max", "scatter-min"})
_SCATTER_FAMILY = frozenset({"scatter", "scatter-add", "scatter-mul",
                             "scatter-max", "scatter-min"})
_GATHERS = frozenset({"gather", "dynamic_slice", "slice"})
# pure reinterpretations of the same buffer: STATE flows through
_STATE_SHAPE_OPS = frozenset({"reshape", "squeeze", "transpose",
                              "convert_element_type"})
_CMP = frozenset({"eq", "ne"})
# call-like prims whose single sub-jaxpr maps invars/outvars positionally
_CALL_PRIMS = frozenset({"jit", "closed_call", "core_call", "remat",
                         "checkpoint", "custom_jvp_call",
                         "custom_vjp_call", "custom_vjp_call_jaxpr",
                         "custom_jvp_call_jaxpr"})

_MAX_ROUNDS = 12       # fixpoint cap; the lattice is 9 facts so any
#                        carry chain stabilizes far earlier
_EMPTY: frozenset = frozenset()


# ---------------------------------------------------------------- records


@dataclasses.dataclass
class SeedSite:
    """One eqn that introduced a protocol fact (reported provenance)."""
    fact: str
    prim: str
    site: str
    path: tuple[str, ...]


@dataclasses.dataclass
class ScatterRec:
    """One scatter-family eqn with its fact summary.

    ``root`` identifies WHICH persistent array the scatter chain writes:
    the operand walked backward through scatter/reshape-family eqns to
    its first non-derived var (a jaxpr input / constvar). Scatters in
    the same jaxpr sharing a root write the same state array — how the
    protocol pass groups a lock table's acquire and release sites.

    ``idx_rows``/``trips`` size the write statically for the dintdur
    ring-bound check: idx_rows is the index batch width (masked lanes
    included — an upper bound on rows written per dispatch) and trips the
    product of enclosing scan lengths, so idx_rows * trips bounds the
    rows this site writes per trace.
    """
    prim: str
    site: str
    path: tuple[str, ...]
    in_pallas: bool
    is_state: bool                 # operand carries STATE
    operand_facts: frozenset
    index_facts: frozenset
    update_facts: frozenset
    root: object                   # Var | None (None = fresh array)
    idx_nonconst: bool             # indices are a traced (non-const) value
    idx_rows: int = 0              # index batch width (0 = unknown)
    trips: float = 1.0             # product of enclosing scan lengths
    unique_indices: bool = False   # the eqn's uniqueness certification

    @property
    def write_facts(self) -> frozenset:
        return self.index_facts | self.update_facts


@dataclasses.dataclass
class PermRec:
    """One `ppermute` with its static permutation (perms are Python tuples
    in the eqn params, so quorum placement is statically evaluable)."""
    perm: tuple                    # ((src, dst), ...)
    axis: str                      # axis_name, "" if undeclared
    site: str
    path: tuple[str, ...]

    @property
    def identity(self) -> bool:
        return all(int(s) == int(d) for s, d in self.perm)


@dataclasses.dataclass
class Dataflow:
    """Analysis result for one TargetTrace (memoized on the trace)."""
    seeds: list[SeedSite]
    scatters: list[ScatterRec]
    ppermutes: list[SeedSite]          # fact == REPL_PUSHED sites
    perms: list[PermRec] = dataclasses.field(default_factory=list)
    # the vars that ARE persistent state (phase-1 STATE provenance): a
    # gather out of one is a table read, out of anything else it is not
    state_vars: frozenset = frozenset()

    def seeded(self, fact: str) -> list[SeedSite]:
        return [s for s in self.seeds if s.fact == fact]

    def log_appends(self) -> list[ScatterRec]:
        """Scatters whose indices descend from the log slot math — the
        LOGGED sites."""
        return [r for r in self.scatters if LOG_SLOT in r.index_facts]

    def quorum_dests(self) -> dict[int, set[int]]:
        """Per-source destination sets, unioned over every recorded
        non-identity perm (self-sends excluded): the static replica
        placement of the CommitBck/CommitLog fan-out."""
        dests: dict[int, set[int]] = {}
        for rec in self.perms:
            if rec.identity:
                continue
            for s, d in rec.perm:
                dests.setdefault(int(s), set())
                if int(d) != int(s):
                    dests[int(s)].add(int(d))
        return dests

    def durable_roots(self) -> set[int]:
        """ids of LOGGED roots that are DURABLE: the trace both appends to
        them and pushes >= 2 distinct-destination replication hops, so a
        single fault domain cannot hold every copy."""
        dests = self.quorum_dests()
        if not dests or min(len(v) for v in dests.values()) < 2:
            return set()
        return {id(r.root) for r in self.log_appends()
                if r.root is not None}


# --------------------------------------------------------------- analyzer


def _sub_jaxpr(obj):
    if isinstance(obj, jcore.ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, jcore.Jaxpr):
        return obj
    return None


def _aval_size(aval) -> int:
    try:
        n = 1
        for d in aval.shape:
            n *= int(d)
        return n
    except Exception:               # noqa: BLE001 — dynamic/abstract dims
        return -1


class _Analyzer:
    def __init__(self, trace: TargetTrace):
        self.trace = trace
        self.env: dict = {}                 # Var -> frozenset (this phase)
        self.prov: dict = {}                # Var -> frozenset (phase 1)
        self.const_vars: set = set()        # Vars bound to constants
        self.protocol_phase = False
        self._suspend = 0                   # >0: inside a fixpoint round
        self._seeds: dict = {}              # (fact, id(eqn)) -> SeedSite
        self._scatters: dict = {}           # id(eqn) -> ScatterRec
        self._ppermutes: dict = {}
        self._perms: dict = {}              # id(eqn) -> PermRec
        self._mult = 1.0                    # product of enclosing scan trips

    # -- env helpers ------------------------------------------------------

    def facts(self, atom) -> frozenset:
        if isinstance(atom, jcore.Literal):
            return _EMPTY
        return self.env.get(atom, _EMPTY)

    def pfacts(self, atom) -> frozenset:
        """Converged provenance facts (phase 2 reads phase 1's result;
        during phase 1 the current env IS the provenance)."""
        if isinstance(atom, jcore.Literal):
            return _EMPTY
        if self.protocol_phase:
            return self.prov.get(atom, _EMPTY)
        return self.env.get(atom, _EMPTY)

    def allfacts(self, atom) -> frozenset:
        return self.facts(atom) | (self.prov.get(atom, _EMPTY)
                                   if not isinstance(atom, jcore.Literal)
                                   else _EMPTY)

    def bind(self, var, fs):
        """Assignment semantics: each fixpoint round recomputes body facts
        from scratch; only loop carries join across rounds."""
        if not isinstance(var, jcore.Literal):
            self.env[var] = frozenset(fs)

    def is_const(self, atom) -> bool:
        return isinstance(atom, jcore.Literal) or atom in self.const_vars

    @property
    def recording(self) -> bool:
        return self.protocol_phase and self._suspend == 0

    # -- entry ------------------------------------------------------------

    def run(self) -> Dataflow:
        jaxpr = self.trace.jaxpr
        if jaxpr is not None:
            # phase 1: provenance (monotone) to fixpoint
            self._phase(jaxpr, protocol=False, top_facts={STATE})
            self.prov = self.env
            # phase 2: protocol facts against frozen provenance
            self.env = {}
            self._phase(jaxpr, protocol=True, top_facts=_EMPTY)
        return Dataflow(
            seeds=list(self._seeds.values()),
            scatters=list(self._scatters.values()),
            ppermutes=list(self._ppermutes.values()),
            perms=list(self._perms.values()),
            state_vars=frozenset(v for v, fs in self.prov.items()
                                 if STATE in fs))

    def _phase(self, jaxpr, protocol: bool, top_facts):
        self.protocol_phase = protocol
        for v in jaxpr.invars:
            self.bind(v, top_facts)
        for v in jaxpr.constvars:
            self.const_vars.add(v)
            self.bind(v, _EMPTY)
        self.flow(jaxpr, (), False)

    # -- jaxpr walk -------------------------------------------------------

    def flow(self, jaxpr: jcore.Jaxpr, path, in_pallas: bool):
        """One forward pass over `jaxpr` (invars/constvars already bound);
        SSA order makes a single sweep complete for straight-line code,
        and the loop handlers below iterate their bodies to fixpoints."""
        defs = {}
        for eqn in jaxpr.eqns:
            self.eqn_transfer(eqn, jaxpr, defs, path, in_pallas)
            for ov in eqn.outvars:
                defs[ov] = eqn

    def _bind_sub(self, sub: jcore.Jaxpr, in_atom_facts):
        for cv in sub.constvars:
            self.const_vars.add(cv)
            self.bind(cv, _EMPTY)
        for sv, fs in zip(sub.invars, in_atom_facts):
            self.bind(sv, fs)

    def eqn_transfer(self, eqn, jaxpr, defs, path, in_pallas):
        prim = eqn.primitive.name
        if prim == "scan":
            return self._scan(eqn, path, in_pallas)
        if prim == "while":
            return self._while(eqn, path, in_pallas)
        if prim == "cond":
            return self._cond(eqn, path, in_pallas)
        if prim == "shard_map":
            sub = _sub_jaxpr(eqn.params.get("jaxpr"))
            if sub is not None and len(sub.invars) == len(eqn.invars):
                return self._call(eqn, sub, path + (prim,), in_pallas)
        if prim == "pallas_call":
            return self._pallas_call(eqn)
        if prim in _CALL_PRIMS:
            sub = _sub_jaxpr(eqn.params.get("jaxpr")
                             or eqn.params.get("call_jaxpr"))
            if (sub is not None and len(sub.invars) == len(eqn.invars)
                    and len(sub.outvars) == len(eqn.outvars)):
                return self._call(eqn, sub, path + (prim,), in_pallas)
        # unknown prim owning a sub-jaxpr with matching arity: map it too
        for v in eqn.params.values():
            sub = _sub_jaxpr(v)
            if (sub is not None and len(sub.invars) == len(eqn.invars)
                    and len(sub.outvars) == len(eqn.outvars)):
                return self._call(eqn, sub, path + (prim,), in_pallas)
        return self._local(eqn, jaxpr, defs, path, in_pallas)

    # -- structured control flow -----------------------------------------

    def _call(self, eqn, sub, path, in_pallas):
        self._bind_sub(sub, [self.facts(a) for a in eqn.invars])
        self.flow(sub, path, in_pallas)
        for ov, sv in zip(eqn.outvars, sub.outvars):
            self.bind(ov, self.facts(sv))

    def _fixpoint(self, one_pass, carry):
        """Join loop-carried facts across rounds until stable, then run
        the converged recording pass. Returns the final body outputs."""
        self._suspend += 1
        try:
            for _ in range(_MAX_ROUNDS):
                outs = one_pass()
                changed = False
                for i in range(len(carry)):
                    new = outs[i] - carry[i]
                    if new:
                        carry[i] |= new
                        changed = True
                if not changed:
                    break
        finally:
            self._suspend -= 1
        return one_pass()

    def _scan(self, eqn, path, in_pallas):
        body = _sub_jaxpr(eqn.params["jaxpr"])
        nc = eqn.params.get("num_consts", 0)
        ncar = eqn.params.get("num_carry", 0)
        consts = [self.facts(a) for a in eqn.invars[:nc]]
        carry = [set(self.facts(a)) for a in eqn.invars[nc:nc + ncar]]
        xs = [self.facts(a) for a in eqn.invars[nc + ncar:]]

        def one_pass():
            self._bind_sub(body, consts + [frozenset(c) for c in carry]
                           + xs)
            self.flow(body, path + ("scan",), in_pallas)
            return [self.facts(v) for v in body.outvars]

        # scatters recorded inside the body write once per trip: scale
        # their static row bound by the scan length (dintdur ring bound)
        mult = self._mult
        try:
            self._mult = mult * float(eqn.params.get("length", 1) or 1)
            outs = self._fixpoint(one_pass, carry)
        finally:
            self._mult = mult
        for ov, fs in zip(eqn.outvars, outs):
            self.bind(ov, fs)

    def _while(self, eqn, path, in_pallas):
        cond = _sub_jaxpr(eqn.params["cond_jaxpr"])
        body = _sub_jaxpr(eqn.params["body_jaxpr"])
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        cconsts = [self.facts(a) for a in eqn.invars[:cn]]
        bconsts = [self.facts(a) for a in eqn.invars[cn:cn + bn]]
        carry = [set(self.facts(a)) for a in eqn.invars[cn + bn:]]

        def one_pass():
            self._bind_sub(body, bconsts + [frozenset(c) for c in carry])
            self.flow(body, path + ("while",), in_pallas)
            return [self.facts(v) for v in body.outvars]

        outs = self._fixpoint(one_pass, carry)
        self._bind_sub(cond, cconsts + [frozenset(c) for c in carry])
        self.flow(cond, path + ("while",), in_pallas)
        for ov, fs in zip(eqn.outvars, outs):
            self.bind(ov, fs)

    def _cond(self, eqn, path, in_pallas):
        branches = eqn.params["branches"]
        ops = [self.facts(a) for a in eqn.invars[1:]]
        merged = [set() for _ in eqn.outvars]
        for br in branches:
            sub = _sub_jaxpr(br)
            self._bind_sub(sub, ops)
            self.flow(sub, path + ("cond",), in_pallas)
            for i, sv in enumerate(sub.outvars):
                merged[i] |= self.facts(sv)
        for ov, fs in zip(eqn.outvars, merged):
            self.bind(ov, fs)

    # -- pallas -----------------------------------------------------------

    def _pallas_call(self, eqn):
        merged = set()
        for a in eqn.invars:
            merged |= self.facts(a)
        merged.discard(STATE)
        aliases = dict(eqn.params.get("input_output_aliases") or {})
        state_in = [STATE in self.pfacts(a) for a in eqn.invars]
        if not self.protocol_phase:
            # a kernel reading table state is a fused gather: its outputs
            # are table reads on the same terms as an XLA gather
            if any(state_in):
                merged.add(TBL_READ)
            if aliases:
                # an aliased kernel is an in-place overwrite install: it
                # kills the arb character of the buffer exactly like an
                # XLA overwrite scatter — otherwise ARB picked up from a
                # grant-derived mask would ride the installed table
                # around the carry and turn the next validate compare
                # into a spurious LOCK_WIN seed
                merged.discard(ARB)
        for oi, ov in enumerate(eqn.outvars):
            fs = set(merged)
            if not self.protocol_phase:
                for ii, out_idx in aliases.items():
                    if int(out_idx) == oi and 0 <= int(ii) < len(state_in) \
                            and state_in[int(ii)]:
                        fs.add(STATE)  # in-place update of the state buf
            self.bind(ov, fs)

    # -- local transfer ---------------------------------------------------

    def _seed(self, fact, eqn, path):
        if self.recording:
            self._seeds[(fact, id(eqn))] = SeedSite(
                fact, eqn.primitive.name, site_of(eqn), path)

    def _operand_root(self, var, defs):
        """Walk a scatter operand back through scatter/reinterpret eqns to
        the persistent array it updates (a var no eqn here defines)."""
        for _ in range(256):
            if isinstance(var, jcore.Literal):
                return None
            eqn = defs.get(var)
            if eqn is None:
                return var
            if eqn.primitive.name in _SCATTER_FAMILY \
                    or eqn.primitive.name in _STATE_SHAPE_OPS:
                var = eqn.invars[0]
                continue
            return var
        return var

    def _scalar_invar_rooted(self, var, jaxpr, defs) -> bool:
        """True if `var`'s backward slice reaches a rank-0 input of the
        enclosing jaxpr (the step counter riding the carry)."""
        frontier, seen = [var], set()
        invars = set(jaxpr.invars)
        while frontier and len(seen) < 2000:
            v = frontier.pop()
            if isinstance(v, jcore.Literal) or v in seen:
                continue
            seen.add(v)
            if v in invars and getattr(v.aval, "shape", None) == ():
                return True
            eqn = defs.get(v)
            if eqn is not None:
                frontier.extend(eqn.invars)
        return False

    def _local(self, eqn, jaxpr, defs, path, in_pallas):
        prim = eqn.primitive.name
        ins = eqn.invars
        base = set()
        for a in ins:
            base |= self.facts(a)
        extra = set()

        if not self.protocol_phase:
            base.discard(STATE)
            if prim == "sort":
                extra.add(SORTED)
            elif prim == "rem":
                # the slot math of tables/log.append / plan_rep: anything
                # this feeds (the flat row ids) is log-append indexing. Monotone (site test is constant), so
                # safe inside the phase-1 fixpoint.
                if _LOG_MODULE in site_of(eqn):
                    extra.add(LOG_SLOT)
            elif prim in _GATHERS:
                op_f = self.facts(ins[0])
                if STATE in op_f:
                    extra.add(TBL_READ)
                    # size-preserving indexing (the shard_map body's x[0])
                    # is a view of the same buffer, not a table read
                    if _aval_size(ins[0].aval) \
                            == _aval_size(eqn.outvars[0].aval):
                        extra.add(STATE)
            elif prim in _STATE_SHAPE_OPS:
                if STATE in self.facts(ins[0]):
                    extra.add(STATE)
            elif prim == "broadcast_in_dim":
                if STATE in self.facts(ins[0]) and _aval_size(ins[0].aval) \
                        == _aval_size(eqn.outvars[0].aval):
                    extra.add(STATE)
            if prim in _SCATTER_FAMILY:
                if prim in _SCATTER_ARB:
                    extra.add(ARB)
                if prim == "scatter":
                    base.discard(ARB)  # overwrite kills the arb character
                if STATE in self.facts(ins[0]):
                    extra.add(STATE)
        else:
            pin = set()
            for a in ins:
                pin |= self.pfacts(a)
            if prim in _CMP:
                if ARB in pin:
                    extra.add(LOCK_WIN)
                    self._seed(LOCK_WIN, eqn, path)
                elif TBL_READ in pin and len(ins) == 2 \
                        and not any(self.is_const(a) for a in ins):
                    extra.add(VALIDATED)
                    self._seed(VALIDATED, eqn, path)
            elif prim == "reduce_or":
                if base & {LOCK_WIN, VALIDATED}:
                    extra.add(ABORT_MASK)
                    self._seed(ABORT_MASK, eqn, path)
            elif prim == "ppermute":
                extra.add(REPL_PUSHED)
                if self.recording:
                    self._ppermutes[id(eqn)] = SeedSite(
                        REPL_PUSHED, prim, site_of(eqn), path)
                    perm = eqn.params.get("perm")
                    if perm:
                        ax = eqn.params.get("axis_name",
                                            eqn.params.get("axes", ""))
                        if isinstance(ax, (tuple, list)):
                            ax = ",".join(str(a) for a in ax)
                        self._perms[id(eqn)] = PermRec(
                            perm=tuple((int(s), int(d)) for s, d in perm),
                            axis=str(ax), site=site_of(eqn), path=path)
            elif prim == "min":
                # the watermark clamp of tables/log.advance_watermark —
                # the only truncation anchor the rings expose
                if _LOG_MODULE in site_of(eqn):
                    extra.add(TRUNCATED)
                    self._seed(TRUNCATED, eqn, path)
            elif prim == "shift_left":
                op0 = ins[0]
                if not self.is_const(op0) \
                        and getattr(op0.aval, "shape", None) == ():
                    extra.add(STAMP)
                    self._seed(STAMP, eqn, path)
            elif prim == "broadcast_in_dim":
                op0 = ins[0]
                if not isinstance(op0, jcore.Literal) \
                        and not self.is_const(op0) \
                        and getattr(op0.aval, "shape", None) == () \
                        and "uint" in str(getattr(op0.aval, "dtype", "")) \
                        and self._scalar_invar_rooted(op0, jaxpr, defs):
                    extra.add(STAMP)
                    self._seed(STAMP, eqn, path)
            if prim in _SCATTER_FAMILY:
                idx = ins[1] if len(ins) > 1 else None
                if prim == "scatter" and idx is not None \
                        and LOG_SLOT in self.pfacts(idx):
                    extra.add(LOGGED)
                    self._seed(LOGGED, eqn, path)
                if self.recording:
                    upd = ins[2] if len(ins) > 2 else None
                    rows = 0
                    if idx is not None:
                        shp = getattr(idx.aval, "shape", ())
                        rows = int(shp[0]) if shp else 1
                    self._scatters[id(eqn)] = ScatterRec(
                        prim=prim, site=site_of(eqn), path=path,
                        in_pallas=in_pallas,
                        is_state=STATE in self.pfacts(ins[0]),
                        operand_facts=frozenset(self.allfacts(ins[0])),
                        index_facts=frozenset(self.allfacts(idx)
                                              if idx is not None else ()),
                        update_facts=frozenset(self.allfacts(upd)
                                               if upd is not None else ()),
                        root=self._operand_root(ins[0], defs),
                        idx_nonconst=(idx is not None
                                      and not self.is_const(idx)),
                        idx_rows=rows, trips=self._mult,
                        unique_indices=bool(
                            eqn.params.get("unique_indices")))

        out = frozenset(base | extra)
        for ov in eqn.outvars:
            self.bind(ov, out)


# -------------------------------------------------------------------- API


def analyze(trace: TargetTrace) -> Dataflow:
    """Run (or fetch the memoized) dataflow for a traced target."""
    cached = getattr(trace, "_dataflow", None)
    if cached is not None:
        return cached
    flow = _Analyzer(trace).run()
    trace._dataflow = flow
    return flow
