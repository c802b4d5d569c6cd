"""Program span on the device trace: ms per engine step of the ops under
``part.bck_val_scatter`` (dense_sharded's ``replicate`` wave: per hop, the
unique-index scatter of all 2w x VW single value words of the forwarded
install record into the backup slot, with its flat index; two hops a
step), mean over devices. None where the trace has no parts."""
from benchmarks import part_times


def read(ctx):
    return part_times.part_ms(ctx, "bck_val_scatter")
