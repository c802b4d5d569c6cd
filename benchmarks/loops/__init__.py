"""One file per loop kind. A cell names its kind (``"loop"`` in
benchmarks/traffic/<traffic>.json) and run.py imports
``benchmarks.loops.<kind>`` and calls

  run(dep, carry, key_of, seconds, max_dispatches, before_drain) -> dict

which dispatches from an empty pipeline (``key_of(i)`` is dispatch i's
key) until ``seconds`` have passed or ``max_dispatches`` were made (the
traced run), calls ``before_drain()``, drains, and returns

  final, totals (int64 per stat, window and drain), t0, t1, dispatches,
  latency_s (one sample per dispatch), spans {name: [[start, end], ...]}

on ``time.perf_counter``. The window is [t0, t1]: first dispatch to the
drain's stats on the host, so every transaction dispatched in it has its
outcome counted in it. Every reading of the clock that ends a span comes
after ``block_until_ready`` AND a fetch of the values (PERF.md's
protocol)."""
import collections
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation


def fetch(stats) -> np.ndarray:
    jax.block_until_ready(stats)
    return np.asarray(stats).astype(np.int64)


def closed(dep, carry, key_of, seconds: float, max_dispatches,
           before_drain, fetch_lag: int) -> dict:
    """The closed loops' common body: dispatch i, then fetch the stats of
    dispatch i - ``fetch_lag`` (0: a coordinator that waits for its
    reply; 1: the next block is queued before the last one's stats are
    read). Returns the result dict without ``latency_s`` and ``spans``,
    plus the clock readings ``d`` (dispatch called), ``q`` (dispatch
    returned) and ``f`` (stats on the host), one per dispatch."""
    clock = time.perf_counter
    total = np.zeros(len(dep.stat_names), np.int64)
    d, q, f = [], [], []
    pending = collections.deque()

    def fetch_oldest():
        nonlocal total
        with TraceAnnotation("bench.fetch"):
            arr = fetch(pending.popleft())
        f.append(clock())
        total += arr.sum(axis=0)

    i = 0
    t0 = clock()
    while True:
        now = clock()
        if now - t0 >= seconds or i == max_dispatches:
            break
        d.append(now)
        with TraceAnnotation("bench.dispatch"):
            carry, stats = dep.dispatch(carry, key_of(i))
        q.append(clock())
        pending.append(stats)
        if len(pending) > fetch_lag:
            fetch_oldest()
        i += 1
    while pending:
        fetch_oldest()
    before_drain()
    final, dstats = dep.drain(carry)
    total += dstats.sum(axis=0)
    t1 = clock()
    return {"final": final, "totals": total, "t0": t0, "t1": t1,
            "dispatches": i, "d": np.asarray(d), "q": np.asarray(q),
            "f": np.asarray(f)}
