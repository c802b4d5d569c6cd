"""The runtime's own event on the host plane: self time a dispatch of
``DeferredTpuAllocator::Allocate`` on the line ``main`` before the device
program began: the device buffers a launch allocates (the key's, the
outputs', the tuple index table's), the largest runtime event before the
run beneath the jit call itself (``PjitFunction(block)``, which
``dispatch_call_ms.lat`` reads whole); mean over the traced dispatches
(``benchmarks/host_path.py``). None, with a note, where the trace has no
such event."""
from benchmarks import host_path


def read(ctx):
    return host_path.event_ms(ctx, "main/DeferredTpuAllocator::Allocate",
                              "before")
