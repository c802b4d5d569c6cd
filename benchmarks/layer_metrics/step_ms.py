"""Device trace: busy time (union of op intervals) of the traced window
per engine step, mean over devices."""
from benchmarks.trace_reduce import busy_ms_per_step as read  # noqa: F401
