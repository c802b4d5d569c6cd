"""Device trace: ms per engine step of the ops under the scope
``dint.dense_sharded_sb.arbitrate`` (the owner's side of a lock request:
two shard-table-wide arrays filled and scatter-min'ed, the held-stamp
reads, the grants, the stamp writes and the fused balance read, over the
D x cap slots of its inbox), mean over devices. None where the trace has
no such scope."""
from benchmarks import trace_reduce

SCOPE = "dint.dense_sharded_sb.arbitrate"


def read(ctx):
    tr = trace_reduce.traced(ctx)
    if not tr or any(SCOPE not in d["scope_s"] for d in tr["devices"]):
        return None
    return trace_reduce.mean_over_devices(tr, "scope_s", SCOPE) * 1e3 \
        / ctx["steps"]
