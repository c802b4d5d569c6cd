"""Program span on the device trace: ms per engine step of the ops under
``part.lock_arb`` (smallbank_dense's lock wave: two slot-table-wide
arrays filled and scatter-min'ed with the lane index over the step's
exclusive and shared requests), mean over devices. None where the trace
has no parts."""
from benchmarks import part_times


def read(ctx):
    return part_times.part_ms(ctx, "lock_arb")
