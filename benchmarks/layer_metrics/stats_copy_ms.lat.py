"""The runtime's own event on the host plane: self time a dispatch of
``np.asarray(jax.Array)`` on the line ``python3`` after the device
program ended: the stats' copy to the host (``ToLiteral``, the
device-to-host transfer's issue, the wait for its ``Done``, the
delinearize; those run on other lines, so this line's wait for them is
its own), the largest runtime event after the run; mean over the traced
dispatches (``benchmarks/host_path.py``). None, with a note, where the
trace has no such event."""
from benchmarks import host_path


def read(ctx):
    return host_path.event_ms(ctx, "python3/np.asarray(jax.Array)", "after")
