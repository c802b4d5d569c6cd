"""Host span against the device trace: call begun -> device program
begun, the start of ``bench.dispatch`` to the start of the same
dispatch's ``XLA Modules`` event on device 0, the device's clock set by
the runtime's ``run_id`` pairs (``DoEnqueueProgram`` before the run,
``CompleteCallbacks`` after it) and good to half of ``clock_slack_ms.lat``;
the median over the traced dispatches (``benchmarks/host_path.py``)."""
from benchmarks import host_path


def read(ctx):
    return host_path.median_ms(ctx, "launch_lag")
