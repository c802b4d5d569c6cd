"""Device trace: ms per engine step of the ops under the scope
``dint.dense_sharded_sb.replicate`` (two ppermute hops of the applied
installs, the two backup scatters, the two forwarded full-width log
appends), mean over devices. None where the trace has no such scope."""
from benchmarks import trace_reduce

SCOPE = "dint.dense_sharded_sb.replicate"


def read(ctx):
    tr = trace_reduce.traced(ctx)
    if not tr or any(SCOPE not in d["scope_s"] for d in tr["devices"]):
        return None
    return trace_reduce.mean_over_devices(tr, "scope_s", SCOPE) * 1e3 \
        / ctx["steps"]
