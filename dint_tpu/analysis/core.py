"""dintlint core: jaxpr tracing, walking, and the pass/finding machinery.

The engines' correctness argument is stated in docstrings (one writer per
row, expiring stamps, in-place donated buffers, pure jitted hot paths) but
until this package nothing *checked* those invariants — a refactor that
drops a `unique_indices`, reads a donated buffer after the in-place kernel,
or sneaks a host callback into the step only fails probabilistically at
runtime, on hardware, where chip time is scarce. dintlint runs the
checks statically on CPU: every registered step function (analysis/targets)
is traced to a jaxpr with abstract values and walked by a registry of
passes (analysis/passes), each encoding one invariant as an eqn-level
predicate. Findings carry severity + provenance (primitive, source line,
enclosing-jaxpr path) and feed the tools/dintlint.py CLI and the tier-1
gate in tests/test_dintlint.py.

Design notes:

* A *target* is anything traceable: the registry hands us a thunk that
  builds a function + example args at tiny geometry (tracing is
  shape-polymorphic in cost — the jaxpr of a w=64 step is the same eqn
  stream as the w=8192 one, minus the shapes).
* Tracing failures are findings, not crashes: a function that cannot be
  traced with abstract values is exactly a function that forces
  recompilation / host sync per call, which is what the purity pass
  exists to flag (`TargetTrace.trace_error`).
* Walking recurses through every sub-jaxpr (pjit, scan, cond, while,
  shard_map, pallas_call, custom_*), tracking context: the path of
  enclosing primitives, the innermost shard_map mesh, and whether we are
  inside a Pallas kernel body (whose Mosaic-level primitives most
  table-discipline passes must skip).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator

import jax
import jax._src.core as jcore
from jax._src import source_info_util
from jax._src.interpreters import partial_eval as _pe

SEV_ERROR = "error"
SEV_WARNING = "warning"
SEV_INFO = "info"
_SEV_ORDER = {SEV_ERROR: 0, SEV_WARNING: 1, SEV_INFO: 2}


@dataclasses.dataclass
class Finding:
    """One structured lint finding (the CLI's unit of report)."""
    pass_name: str      # registered pass (e.g. "scatter_race")
    code: str           # stable slug within the pass (e.g. "nonunique-set")
    severity: str       # SEV_ERROR | SEV_WARNING | SEV_INFO
    target: str         # registered target name (e.g. "tatp_dense/block")
    message: str        # human sentence: invariant + why it is at risk
    primitive: str = "" # offending eqn's primitive name ("" = whole-target)
    site: str = ""      # user-code provenance "file.py:line" (best effort)
    path: str = ""      # enclosing-jaxpr path (e.g. "pjit/scan/shard_map")
    suggestion: str = ""  # suggested fix
    allowed_by: str = ""  # reason string of the allowlist entry, if matched
    count: int = 1        # identical findings merged (same site, many eqns)

    @property
    def suppressed(self) -> bool:
        return bool(self.allowed_by)

    def sort_key(self):
        return (_SEV_ORDER.get(self.severity, 3), self.target,
                self.pass_name, self.code, self.site)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["suppressed"] = self.suppressed
        return d

    def __str__(self):
        where = f" [{self.site}]" if self.site else ""
        if self.count > 1:
            where += f" x{self.count}"
        prim = f" ({self.primitive})" if self.primitive else ""
        sup = f"  -- allowed: {self.allowed_by}" if self.suppressed else ""
        fix = f"\n      fix: {self.suggestion}" if self.suggestion else ""
        return (f"{self.severity.upper():7s} {self.target} "
                f"{self.pass_name}/{self.code}{prim}{where}: "
                f"{self.message}{sup}{fix}")


# --------------------------------------------------------------- tracing


@dataclasses.dataclass
class TargetTrace:
    """A traced target: the closed jaxpr (or the trace failure) + metadata
    the passes key on: declared mesh axes for the sharded paths, and the
    protocol flags the dataflow pass gates on (passes/protocol.py) —
    "certified" (the engine closes the lock/validate/install loop inside
    the trace), "occ" (installs must also descend from the validate
    compare), "replicated" (ICI replication must push and land), "drain"
    (boundary cohorts: only the abort-unlock witness applies), "server"
    (protocol sequencing lives in the client, outside the trace)."""
    name: str
    closed_jaxpr: jcore.ClosedJaxpr | None
    trace_error: BaseException | None = None
    mesh_axes: tuple[str, ...] = ()   # axes the target DECLARES it runs on
    protocol: tuple[str, ...] = ("certified",)

    @property
    def jaxpr(self) -> jcore.Jaxpr | None:
        return None if self.closed_jaxpr is None else self.closed_jaxpr.jaxpr


def trace_target(name: str, fn: Callable, args, *, mesh_axes=(),
                 protocol: tuple[str, ...] = ("certified",),
                 ) -> TargetTrace:
    """Trace `fn(*args)` to a jaxpr with abstract values; a trace failure
    (concretization, host sync, data-dependent Python branching) is
    captured as `trace_error` for the purity pass instead of raised."""
    # jit-wrapped ufuncs (jnp.mod, jnp.remainder, ...) stage through
    # jit's trace cache (`pe.trace_to_jaxpr`), which caches the inner
    # jaxpr BY AVALS and keeps the source_info of the FIRST caller.  If
    # an engine ran (or another target traced) earlier in this process,
    # our eqns inherit that caller's file:line and every site_of-keyed
    # fact (LOG_SLOT, TRUNCATED, ...) mis-seeds.  Clearing that cache
    # before each target trace makes provenance order-independent;
    # re-staging is milliseconds, and — unlike jax.clear_caches() — the
    # compiled executable caches survive, so engines running later in
    # the same process (the test suite) do not recompile.
    _pe.trace_to_jaxpr.cache_clear()
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:          # noqa: BLE001 — any trace failure is data
        return TargetTrace(name, None, trace_error=e,
                           mesh_axes=tuple(mesh_axes),
                           protocol=tuple(protocol))
    return TargetTrace(name, closed, mesh_axes=tuple(mesh_axes),
                       protocol=tuple(protocol))


class TraceCache:
    """Trace-once cache: every pass of every `analysis.run()` call in a
    process shares ONE jaxpr per target (tracing a dense multi-chip
    runner costs ~1 s; the matrix cost must scale with targets, not
    targets x passes x runs). Records per-target build seconds so the
    CLI's `--time` report can show where the wall time went."""

    def __init__(self):
        self._traces: dict[str, TargetTrace] = {}
        self.seconds: dict[str, float] = {}   # trace-build time (misses)
        self.hits = 0
        self.misses = 0

    def __contains__(self, name: str) -> bool:
        return name in self._traces

    def get(self, name: str, builder: Callable[[], TargetTrace]
            ) -> TargetTrace:
        hit = self._traces.get(name)
        if hit is not None:
            self.hits += 1
            return hit
        self.misses += 1
        t0 = time.perf_counter()
        trace = builder()
        self.seconds[name] = time.perf_counter() - t0
        self._traces[name] = trace
        return trace

    def clear(self):
        self._traces.clear()
        self.seconds.clear()
        self.hits = self.misses = 0


# --------------------------------------------------------------- walking


@dataclasses.dataclass
class EqnCtx:
    """One eqn in context: the owning jaxpr + index (so passes can look at
    later eqns for liveness questions), the enclosing-primitive path, the
    innermost shard_map mesh, and the in-Pallas-kernel flag."""
    eqn: jcore.JaxprEqn
    jaxpr: jcore.Jaxpr
    index: int
    path: tuple[str, ...] = ()
    mesh: object | None = None           # innermost shard_map Mesh
    in_pallas_kernel: bool = False

    @property
    def prim(self) -> str:
        return self.eqn.primitive.name


def _sub_jaxprs(params: dict) -> list[jcore.Jaxpr]:
    """Every jaxpr nested in an eqn's params (pjit/scan jaxpr, cond
    branches, while cond/body, shard_map body, pallas kernel, custom_*)."""
    out = []
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for w in vals:
            if isinstance(w, jcore.Jaxpr):
                out.append(w)
            elif isinstance(w, jcore.ClosedJaxpr):
                out.append(w.jaxpr)
    return out


def walk(trace: TargetTrace) -> Iterator[EqnCtx]:
    """Depth-first walk of every eqn in the trace, sub-jaxprs included."""
    if trace.jaxpr is None:
        return
    stack = [(trace.jaxpr, (), None, False)]
    while stack:
        jaxpr, path, mesh, in_pl = stack.pop()
        for i, eqn in enumerate(jaxpr.eqns):
            name = eqn.primitive.name
            yield EqnCtx(eqn, jaxpr, i, path, mesh, in_pl)
            sub_mesh = mesh
            if name == "shard_map":
                sub_mesh = eqn.params.get("mesh", mesh)
            sub_pl = in_pl or name == "pallas_call"
            for sub in _sub_jaxprs(eqn.params):
                stack.append((sub, path + (name,), sub_mesh, sub_pl))


def site_of(eqn: jcore.JaxprEqn) -> str:
    """Best-effort user-code 'file.py:line' for an eqn (the deepest frame
    outside jax itself); '' when source info was not recorded."""
    try:
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is None:
            return ""
        fname = frame.file_name
        if "/analysis/" in fname:
            return ""   # the harness's own trace call, not user provenance
        for marker in ("/dint_tpu/", "/tests/", "/tools/"):
            if marker in fname:
                fname = fname[fname.index(marker) + 1:]
                break
        return f"{fname}:{frame.start_line}"
    except Exception:               # noqa: BLE001 — provenance is best-effort
        return ""


def def_var(jaxpr: jcore.Jaxpr, var, upto: int) -> jcore.JaxprEqn | None:
    """The eqn (within eqns [0, upto)) that defines `var`, or None for
    literals / jaxpr inputs / constvars."""
    if isinstance(var, jcore.Literal):
        return None
    for eqn in jaxpr.eqns[:upto]:
        for ov in eqn.outvars:
            if ov is var:
                return eqn
    return None


def def_chain_prims(jaxpr: jcore.Jaxpr, var, upto: int,
                    stop: frozenset[str] = frozenset()) -> set[str]:
    """Primitive names in the backward def slice of `var` within `jaxpr`
    (eqns [0, upto)). Stops at jaxpr boundaries: an invar/constvar
    contributes nothing (callers pass evidence via scatter params instead).

    `stop` names primitives whose INPUTS are not traversed (the eqn itself
    is still recorded): passes use it to cut the slice at range-limiting
    ops — a value that just went through `and` with a mask or `rem` no
    longer carries its producers' magnitude, so e.g. a left shift upstream
    of a mask is not stamp-layout evidence.

    This is the provenance oracle of the scatter-race pass (indices whose
    slice contains a `sort` come from the segment machinery,
    ops/segments.sort_batch, whose head/last masks make the scatter
    one-writer by construction) and of the u64 pass's drift rules.
    """
    if isinstance(var, jcore.Literal):
        return set()
    defs: dict = {}
    for i, eqn in enumerate(jaxpr.eqns[:upto]):
        for ov in eqn.outvars:
            defs[ov] = eqn
    seen: set = set()
    prims: set[str] = set()
    frontier = [var]
    while frontier:
        v = frontier.pop()
        if isinstance(v, jcore.Literal) or v in seen:
            continue
        seen.add(v)
        eqn = defs.get(v)
        if eqn is None:
            continue
        prims.add(eqn.primitive.name)
        if eqn.primitive.name in stop:
            continue
        # recurse into sub-jaxpr outputs too (a scan/pjit that produced the
        # index still names its own internal prims)
        for sub in _sub_jaxprs(eqn.params):
            for ie in sub.eqns:
                prims.add(ie.primitive.name)
        frontier.extend(v2 for v2 in eqn.invars
                        if not isinstance(v2, jcore.Literal))
    return prims


def used_after(jaxpr: jcore.Jaxpr, var, after: int) -> str:
    """If `var` is read by any eqn after index `after` (or escapes as a
    jaxpr output), return a description of the first use; else ''. The
    liveness primitive behind the use-after-donate checks."""
    if isinstance(var, jcore.Literal):
        return ""
    for j in range(after + 1, len(jaxpr.eqns)):
        eqn = jaxpr.eqns[j]
        for iv in eqn.invars:
            if iv is var:
                return f"read by `{eqn.primitive.name}` at {site_of(eqn)}"
    for ov in jaxpr.outvars:
        if ov is var:
            return "escapes as a jaxpr output"
    return ""


# ------------------------------------------------------------ SARIF export

# Minimal SARIF 2.1.0 (the schema slice documented in ANALYSIS.md): one
# run, one rule per pass/code pair, one result per finding; allowlisted
# findings ride along as suppressions so SARIF viewers grey them out
# instead of dropping them.
_SARIF_LEVEL = {SEV_ERROR: "error", SEV_WARNING: "warning", SEV_INFO: "note"}


def to_sarif(findings: list[Finding], tool_name: str) -> dict:
    """Serialize findings as a SARIF 2.1.0 log (shared by the dintlint
    and dintdur CLIs' --sarif flags)."""
    rules: dict[str, dict] = {}
    results = []
    for f in findings:
        rule_id = f"{f.pass_name}/{f.code}"
        rules.setdefault(rule_id, {
            "id": rule_id,
            "shortDescription": {"text": PASS_DOCS.get(f.pass_name,
                                                       f.pass_name)},
        })
        result = {
            "ruleId": rule_id,
            "level": _SARIF_LEVEL.get(f.severity, "none"),
            "message": {"text": f.message + (
                f"\nfix: {f.suggestion}" if f.suggestion else "")},
            "properties": {"target": f.target, "primitive": f.primitive,
                           "path": f.path, "count": f.count},
        }
        if f.site:
            uri, _, line = f.site.rpartition(":")
            region = {}
            if line.isdigit():
                region["startLine"] = int(line)
            else:
                uri = f.site
            loc = {"physicalLocation": {
                "artifactLocation": {"uri": uri or f.site}}}
            if region:
                loc["physicalLocation"]["region"] = region
            result["locations"] = [loc]
        if f.suppressed:
            result["suppressions"] = [{"kind": "external",
                                       "justification": f.allowed_by}]
        results.append(result)
    return {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                    "sarif-spec/master/Schemata/sarif-schema-2.1.0.json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {"name": tool_name,
                                "rules": sorted(rules.values(),
                                                key=lambda r: r["id"])}},
            "results": results,
        }],
    }


# ---------------------------------------------------------- pass registry

PASSES: dict[str, Callable[[TargetTrace], list[Finding]]] = {}
PASS_DOCS: dict[str, str] = {}


def register_pass(name: str):
    """Register `fn(trace: TargetTrace) -> list[Finding]` under `name`."""
    def deco(fn):
        PASSES[name] = fn
        PASS_DOCS[name] = (fn.__doc__ or "").strip().splitlines()[0]
        return fn
    return deco
