"""Device trace: time per step of the ops under the scope
``dint.dense_sharded.replicate`` (two ppermute hops of the install
record, the backup installs, the forwarded log appends), mean over
devices."""
from benchmarks import trace_reduce


def read(ctx):
    tr = trace_reduce.traced(ctx)
    return tr and trace_reduce.mean_over_devices(
        tr, "scope_s", "dint.dense_sharded.replicate") * 1e3 / ctx["steps"]
