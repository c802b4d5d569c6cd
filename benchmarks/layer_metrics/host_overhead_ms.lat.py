"""The benchmark's own host spans against the device trace: per step, the
time from the dispatch call to the fetch's end (TraceAnnotations
``bench.dispatch`` and ``bench.fetch``) less the time the step's program
ran on the device; the median over the traced steps."""
import statistics

from benchmarks import trace_reduce


def read(ctx):
    tr = trace_reduce.traced(ctx)
    if not tr:
        return None
    runs = trace_reduce.block_modules(tr["devices"][0])
    starts = [s for n, s, _ in tr["host"] if n == "bench.dispatch"]
    ends = [s + d for n, s, d in tr["host"] if n == "bench.fetch"]
    over = [(e - s) - (r[1] - r[0]) for s, e, r in zip(starts, ends, runs)]
    return statistics.median(over) / 1e6 if over else None
