"""95th percentile of the samples txn_latency_p50_ms takes the median
of; listed only for cells whose window holds thousands of them."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx["loop"]["latency_s"], 95)) * 1e3
