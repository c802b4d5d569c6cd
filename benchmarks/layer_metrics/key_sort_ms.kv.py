"""Program span on the device trace: ms per engine step of the ops under
``part.key_sort`` (the store step's same-key serialisation: the 3-key sort
of the batch, the segment sums, maxes and cummax that resolve a key's
lanes in arrival order, the writer election and the unsorts back to lane
order), mean over devices. None where the trace has no parts."""
from benchmarks import part_times


def read(ctx):
    return part_times.part_ms(ctx, "key_sort")
