"""Device trace of the steady window: 1 - busy / window, in percent,
mean over devices."""
from benchmarks.trace_reduce import idle_share_pct as read  # noqa: F401
