"""Device trace: median idle gap on the device between consecutive block
programs (end of one execution to the start of the next), first device.
The quantity's own file, so that a new cell's variant resolves here by
the variant rule (the older cells each carry a copy of this arithmetic
under their own names)."""
import statistics

from benchmarks import trace_reduce


def read(ctx):
    tr = trace_reduce.traced(ctx)
    if not tr:
        return None
    runs = trace_reduce.block_modules(tr["devices"][0])
    gaps = [max(b[0] - a[1], 0.0) for a, b in zip(runs, runs[1:])]
    return statistics.median(gaps) / 1e6 if gaps else None
