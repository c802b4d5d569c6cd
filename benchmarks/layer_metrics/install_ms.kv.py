"""Device trace: ms per engine step of the ops under the scope
``dint.store.install`` (the scatters of valid, version, key_hi and key_lo, w lanes
each, and of the w x VW value words), mean over
devices. None where the trace has no such scope."""
from benchmarks import trace_reduce

SCOPE = "dint.store.install"


def read(ctx):
    tr = trace_reduce.traced(ctx)
    if not tr or any(SCOPE not in d["scope_s"] for d in tr["devices"]):
        return None
    return trace_reduce.mean_over_devices(tr, "scope_s", SCOPE) * 1e3 \
        / ctx["steps"]
