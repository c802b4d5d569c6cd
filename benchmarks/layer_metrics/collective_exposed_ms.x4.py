"""Device trace: per step, the part of the collective ops
(collective-permute and the stats' all-reduce) during which no other op
runs on that device, mean over devices."""
from benchmarks import trace_reduce


def read(ctx):
    tr = trace_reduce.traced(ctx)
    return tr and trace_reduce.mean_over_devices(
        tr, "collective_exposed_s") * 1e3 / ctx["steps"]
