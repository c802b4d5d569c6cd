"""``memory_stats()["peak_bytes_in_use"]`` after the window and its
checks, the largest over the devices, in GB. The quantity's own file: a
variant without one of its own resolves here (``.sbx4``: the state is
0.27 GB a device; the peak is device 0's, where ``create_sharded_sb``
builds the state before it spreads it)."""


def read(ctx):
    peaks = [p for p in ctx["peak_bytes_in_use"] if p is not None]
    return max(peaks) / 1e9 if peaks else None
