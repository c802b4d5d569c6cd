"""Bytes the two replication hops of a step send from one device
(benchmarks/bytes_model_ici.py: 2 hops x 2w lanes x (25 B + the value
words)) over the interconnect's peak (peaks.json ``ici_bits_per_s``),
over the time a collective-permute was in flight on the device (from each
transfer's ``-start`` to the end of its ``-done``, the union; mean over
devices), in percent. Bound by bytes. None where the trace shows no
collective-permute."""
import json

from benchmarks import bytes_model_ici, part_times, trace_reduce


def read(ctx):
    path = part_times.newest_xplane() if trace_reduce.traced(ctx) else None
    if path is None:
        return None
    try:
        flights = [bytes_model_ici.in_flight_ns(d["ops"]) / 1e9
                   for d in part_times.load_ops(path)]
    except Exception as e:  # noqa: BLE001 — the run keeps its result line
        print(json.dumps({"ici": f"not read: {type(e).__name__}: {e}",
                          "xplane": path}), flush=True)
        return None
    flights = [f for f in flights if f > 0]
    if not flights:
        return None
    g = ctx["geometry"]
    return bytes_model_ici.roofline_share_pct(
        ctx["steps"] * bytes_model_ici.step_bytes(g["w"], g["val_words"]),
        sum(flights) / len(flights), ctx["device"]["kind"])
