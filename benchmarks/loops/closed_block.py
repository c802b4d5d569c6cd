"""Closed loop at saturation (stats.run_window's loop, on perf_counter):
block i is dispatched before block i-1's stats are fetched, so the device
always has the next block queued and the host's reduction overlaps it."""
from __future__ import annotations

import numpy as np

from benchmarks.loops import closed


def run(dep, carry, key_of, seconds: float, max_dispatches, before_drain):
    res = closed(dep, carry, key_of, seconds, max_dispatches, before_drain,
                 fetch_lag=1)
    d, q, f = res.pop("d"), res.pop("q"), res.pop("f")
    # a transaction waits from its block's dispatch call to the moment
    # that block's stats are on the host; block j's fetch follows block
    # j+1's dispatch (the last one follows nothing)
    res["latency_s"] = f - d
    res["spans"] = {"dispatch": np.c_[d, q],
                    "fetch": np.c_[np.r_[q[1:], q[-1:]], f]}
    return res
