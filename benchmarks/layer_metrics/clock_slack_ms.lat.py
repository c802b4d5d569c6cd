"""How far the device's clock could still be shifted against the host's
with no causal order broken in any traced dispatch: what
``launch_lag_ms.lat`` and ``completion_lag_ms.lat`` can have swapped
(each is within half of it). Narrowed by the runtime's host events that
carry the ``run_id`` of the device's ``XLA Modules`` event:
``DoEnqueueProgram`` starts before the run begins, ``CompleteCallbacks``
after it ended; from the two annotations alone where those are not
recorded (``benchmarks/host_path.py``, which prints which pair set it)."""
from benchmarks import host_path


def read(ctx):
    found = host_path.read(ctx)
    return found and found["clock_slack"] / 1e6
