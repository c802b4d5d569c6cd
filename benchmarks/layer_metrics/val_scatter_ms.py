"""Program span on the device trace: ms per engine step of the ops under
``part.val_scatter`` (tatp_dense's install wave: the unique-index scatter
of 2w x VW single value words into the 1-D val array, with its flat
index), mean over devices. None where the trace has no parts."""
from benchmarks import part_times


def read(ctx):
    return part_times.part_ms(ctx, "val_scatter")
