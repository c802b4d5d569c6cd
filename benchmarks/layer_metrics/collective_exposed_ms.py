"""Device trace: per step, the part of the collective ops (all-to-all,
collective-permute, the stats' all-reduce) during which no other op runs
on that device, mean over devices. The quantity's own file: a variant
without one of its own (``.sbx4``: nine all-to-alls and ten
collective-permutes a step) resolves here."""
from benchmarks import trace_reduce


def read(ctx):
    tr = trace_reduce.traced(ctx)
    return tr and trace_reduce.mean_over_devices(
        tr, "collective_exposed_s") * 1e3 / ctx["steps"]
