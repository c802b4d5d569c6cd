"""Program span on the device trace: ms per engine step of the ops under
``part.monitor``: what the step runs only because the counter plane is
threaded (``monitor=True``: ~20 reductions, one scatter-add, one
scatter-max), mean over devices. The device time of those ops, not an
A/B against ``monitor=False``: the extra carry leaf and any fusion the
plane prevents are not in it. None where the trace has no parts."""
from benchmarks import part_times


def read(ctx):
    return part_times.part_ms(ctx, "monitor")
