"""Hot-path purity pass: a step must be one pure device program.

The throughput model (PERF.md) prices a step as ONE device dispatch; any
host round-trip inside it — a callback, a debug print, a Python-level
branch on device data — either blocks the dispatch queue per step or
forces a retrace/recompile per distinct shape. The reference has the same
rule in harsher form: its hot path lives inside an eBPF verifier-approved
kernel where a host call is structurally impossible.

Two detection layers:

  * trace-time: a target that cannot be traced with abstract values at
    all (ConcretizationTypeError / TracerBoolConversionError) is exactly a
    function with data-dependent Python control flow or an implicit
    device->host transfer (`float(x)`, `if x.sum():`, `np.asarray(x)`) —
    reported as ERROR `untraceable` with the original exception text.
  * eqn scan: callback-class primitives inside the jaxpr —
    `pure_callback` / `io_callback` / unbatched `custom_partitioning`
    callbacks -> ERROR (host round-trip per step);
    `debug_print` / `debug_callback` (jax.debug.print /
    jax.debug.callback; both reported under the `debug_callback` code)
    -> WARNING (tolerable while debugging, never in the benchmarked path);
    `infeed` / `outfeed` -> ERROR.
"""
from __future__ import annotations

from ..core import (Finding, SEV_ERROR, SEV_WARNING, TargetTrace,
                    register_pass, site_of, walk)

_HOST_SYNC = {"pure_callback": SEV_ERROR,
              "io_callback": SEV_ERROR,
              "infeed": SEV_ERROR,
              "outfeed": SEV_ERROR,
              "debug_callback": SEV_WARNING,
              "debug_print": SEV_WARNING}


@register_pass("purity")
def purity(trace: TargetTrace) -> list[Finding]:
    """Detects host transfers, callbacks, and shape-branching that break
    the one-dispatch-per-step model."""
    out: list[Finding] = []
    if trace.trace_error is not None:
        msg = f"{type(trace.trace_error).__name__}: {trace.trace_error}"
        out.append(Finding(
            "purity", "untraceable", SEV_ERROR, trace.name,
            "step function cannot be traced with abstract values — it "
            "branches in Python on device data or forces an implicit "
            "device->host transfer, which means a host sync and/or a "
            f"recompile per call in the hot path. Trace error: {msg[:500]}",
            suggestion="replace Python control flow on traced values with "
                       "lax.cond/lax.select; keep shapes static; move "
                       "host-side decisions outside the jitted step"))
        return out
    for ctx in walk(trace):
        sev = _HOST_SYNC.get(ctx.prim)
        if sev is None:
            continue
        debug = ctx.prim in ("debug_callback", "debug_print")
        what = "debug print/callback" if debug else "host callback"
        out.append(Finding(
            "purity", "debug_callback" if debug else ctx.prim, sev,
            trace.name,
            f"{what} `{ctx.prim}` inside the jitted step: the device "
            "program stalls on a host round-trip every step",
            primitive=ctx.prim, site=site_of(ctx.eqn),
            path="/".join(ctx.path),
            suggestion="compute the value on device and return it in the "
                       "step's outputs (stats lanes), or gate the debug "
                       "aid out of production builds"))
    return out
