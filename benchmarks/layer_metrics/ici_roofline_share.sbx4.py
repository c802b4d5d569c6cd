"""Bytes one device sends to others in a step
(benchmarks/bytes_model_ici_sb.py: three quarters of each of the nine
all_to_all arrays, the whole of each of the ten ppermuted ones, by their
shapes and dtypes) over the interconnect's peak (peaks.json
``ici_bits_per_s``), over the time an all-to-all or a collective-permute
was in flight on the device (the union of the transfers' intervals; mean
over devices), in percent. Bound by bytes. None where the trace shows
neither."""
import json

from benchmarks import bytes_model_ici_sb as model
from benchmarks import part_times, trace_reduce


def read(ctx):
    path = part_times.newest_xplane() if trace_reduce.traced(ctx) else None
    if path is None:
        return None
    try:
        flights = [model.in_flight_ns(d["ops"]) / 1e9
                   for d in part_times.load_ops(path)]
    except Exception as e:  # noqa: BLE001 — the run keeps its result line
        print(json.dumps({"ici": f"not read: {type(e).__name__}: {e}",
                          "xplane": path}), flush=True)
        return None
    flights = [f for f in flights if f > 0]
    if not flights:
        return None
    g = ctx["geometry"]
    return model.roofline_share_pct(
        ctx["steps"] * model.step_bytes(g["w"], g["l"],
                                        ctx["n_devices"])["total"],
        sum(flights) / len(flights), ctx["device"]["kind"])
