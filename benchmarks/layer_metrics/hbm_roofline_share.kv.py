"""Bytes one KV step must move (benchmarks/bytes_model_store.py on the
cell's shapes and the run's own counts of hit GETs and updates) over the
HBM peak, over the step's measured device time (step_ms.kv), in percent.
Bound by bytes. No kernel of its own comes with the cell, so the step's
share is the roofline share it reports."""
from benchmarks import bytes_model, bytes_model_store, trace_reduce


def read(ctx):
    tr = trace_reduce.traced(ctx)
    if not tr:
        return None
    g, t = ctx["geometry"], ctx["totals"]
    device_steps = ctx["steps"] * ctx["n_devices"]
    need = bytes_model_store.step_bytes(
        g["w"], g["val_words"],
        hit_gets=(t["gets"] - t["not_exist"]) / device_steps,
        updates=t["updates"] / device_steps)["total"]
    return bytes_model.roofline_share_pct(
        need, tr["busy_s"] / ctx["steps"], ctx["device"]["kind"])
