"""Bytes one SmallBank step must move (benchmarks/bytes_model_smallbank.py
on the cell's shapes and the run's own grant and install counts) over the
HBM peak, over the step's measured device time (step_ms.sb), in percent.
Bound by bytes."""
from benchmarks import bytes_model, bytes_model_smallbank, trace_reduce


def read(ctx):
    tr = trace_reduce.traced(ctx)
    if not tr:
        return None
    g, c = ctx["geometry"], ctx["counters"]
    device_steps = ctx["steps"] * ctx["n_devices"]
    need = bytes_model_smallbank.step_bytes(
        g["w"], g["l"], g["val_words"], g["log_replicas"],
        lock_granted=c["lock_granted"] / device_steps,
        installs=c["install_writes"] / device_steps)["total"]
    return bytes_model.roofline_share_pct(
        need, tr["busy_s"] / ctx["steps"], ctx["device"]["kind"])
