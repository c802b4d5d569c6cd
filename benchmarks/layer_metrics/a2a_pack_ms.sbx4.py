"""Program span on the device trace: ms per engine step of the ops under
``part.a2a_pack`` (dense_sharded_sb's ``_route``: seven unique-index
scatters of the 3w lanes of a cohort into D buckets of ``cap`` slots, two
fields of lock requests under ``route`` and five of installs under
``install_route``), mean over devices. None where the trace has no
parts."""
from benchmarks import part_times


def read(ctx):
    return part_times.part_ms(ctx, "a2a_pack")
