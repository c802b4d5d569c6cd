"""Closed loop of single steps (stats.run_latency_window's loop, on
perf_counter): every step's stats are fetched before the next dispatch,
as a coordinator waits for its reply. The cohort dispatched at call j has
its outcome in the stats of call j + depth - 1."""
from __future__ import annotations

import numpy as np

from benchmarks.loops import closed


def run(dep, carry, key_of, seconds: float, max_dispatches, before_drain):
    res = closed(dep, carry, key_of, seconds, max_dispatches, before_drain,
                 fetch_lag=0)
    d, q, f = res.pop("d"), res.pop("q"), res.pop("f")
    # the last depth-1 cohorts get their outcome from the drain
    lag = dep.depth - 1
    done = np.r_[f[lag:], np.full(min(lag, len(d)), res["t1"])]
    res["latency_s"] = done - d
    res["spans"] = {"dispatch": np.c_[d, q], "fetch": np.c_[q, f]}
    return res
