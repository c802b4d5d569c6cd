"""Host spans on the profiler's trace: the jit call of one dispatch, the
benchmark's annotation ``bench.dispatch`` from its start to its end (the
flatten of the carry, the key's upload, handing the launch to the
runtime's launch thread, wrapping the outputs); the median over the
traced dispatches (``benchmarks/host_path.py``). None where the trace has
no device plane or nothing to join."""
from benchmarks import host_path


def read(ctx):
    return host_path.median_ms(ctx, "call")
