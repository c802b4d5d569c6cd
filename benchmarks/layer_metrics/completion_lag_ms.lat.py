"""Device trace against host span: device program ended -> stats on the
host, the end of the dispatch's ``XLA Modules`` event on device 0 to the
end of its ``bench.fetch``, on the clock ``launch_lag_ms.lat`` is on (per
step the two add up to what ``host_overhead_ms.lat`` takes the median of,
whatever the clock); the median over the traced dispatches
(``benchmarks/host_path.py``)."""
from benchmarks import host_path


def read(ctx):
    return host_path.median_ms(ctx, "completion_lag")
