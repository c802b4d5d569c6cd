"""Bytes one replicated step must move through one device's HBM
(benchmarks/bytes_model.py on the cell's shapes and the run's own install
and lock counts: one log entry a local append, two forwarded install
records sent, received and applied to a backup slot and to the ring)
over the HBM peak, over the step's measured device time (step_ms.x4), in
percent. The arithmetic of ``hbm_roofline_share.tput``. Bound by
bytes."""
from benchmarks import bytes_model, trace_reduce


def read(ctx):
    tr = trace_reduce.traced(ctx)
    if not tr:
        return None
    g, c = ctx["geometry"], ctx["counters"]
    device_steps = ctx["steps"] * ctx["n_devices"]
    need = bytes_model.step_bytes(
        g["w"], g["k"], g["val_words"], g["log_replicas"],
        installs=c["install_writes"] / device_steps,
        lock_requests=c["lock_requests"] / device_steps,
        n_backups=g["n_backups"])["total"]
    return bytes_model.roofline_share_pct(
        need, tr["busy_s"] / ctx["steps"], ctx["device"]["kind"])
