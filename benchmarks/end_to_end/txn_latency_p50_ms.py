"""Median, over all dispatches of the window, of the time from the host
call that dispatches a transaction's cohort to the moment its outcome is
on the host (the loop kind says which fetch that is)."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx["loop"]["latency_s"], 50)) * 1e3
