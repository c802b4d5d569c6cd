"""``memory_stats()["peak_bytes_in_use"]`` after the window and its
checks, the largest over the devices, in GB: the sharded block program's
temporaries are what brings a 5.3 GB shard near the chip's 16 GB."""


def read(ctx):
    peaks = [p for p in ctx["peak_bytes_in_use"] if p is not None]
    return max(peaks) / 1e9 if peaks else None
