"""Bytes one sharded SmallBank step must move through one device's HBM
(benchmarks/bytes_model_smallbank.py's rules on the lanes a device
sources and the run's own grant and install counts a device: both stamp
tables and the balance at every lane, a stamp read and written a grant,
a balance word an install; then what replication adds: the same word
into two backup slots and three log entries an install, the owner's and
the two forwarded ones a device appends) over the HBM peak, over the
step's measured device time (step_ms.sbx4), in percent. The exchange's
buckets, the arbitration arrays and the full-width appends are this
implementation's, not the protocol's: they are not in it. Bound by
bytes."""
from benchmarks import bytes_model, bytes_model_smallbank, trace_reduce


def read(ctx):
    tr = trace_reduce.traced(ctx)
    if not tr:
        return None
    g, c = ctx["geometry"], ctx["counters"]
    device_steps = ctx["steps"] * ctx["n_devices"]
    installs = c["install_writes"] / device_steps
    need = bytes_model_smallbank.step_bytes(
        g["w"], g["l"], g["val_words"], g["log_replicas"],
        lock_granted=c["lock_granted"] / device_steps,
        installs=installs)["total"] \
        + g["n_backups"] * installs * bytes_model.WORD
    return bytes_model.roofline_share_pct(
        need, tr["busy_s"] / ctx["steps"], ctx["device"]["kind"])
