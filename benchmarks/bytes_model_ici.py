"""Bytes a replicated step sends over the chip-to-chip interconnect, and
the time its transfers are in flight, from the cell's shapes and the
trace's collective-permute ops.

A hop ships the whole 2w-lane install record (``bytes_model``'s
``HOP_LANE_FIXED`` bytes of mask, row id, meta, table, key, delete flag
and version a lane, plus the value words), masked lanes too: a
``ppermute`` moves arrays, not the live lanes of them. Two hops a step
(to d + 1 and to d + 2), each sent once by every device."""
from __future__ import annotations

from benchmarks import bytes_model, trace_reduce

HOPS = 2
START, DONE, WHOLE = ("collective-permute-start", "collective-permute-done",
                      "collective-permute")


def hop_bytes(w: int, val_words: int) -> int:
    """What one device sends in one hop."""
    return 2 * w * (bytes_model.HOP_LANE_FIXED
                    + val_words * bytes_model.WORD)


def step_bytes(w: int, val_words: int) -> int:
    """What one device sends in one step."""
    return HOPS * hop_bytes(w, val_words)


def in_flight_ns(ops) -> float:
    """Length of the union of the intervals in which a collective-permute
    is in flight on one device. ``ops``: [name, _, start_ns, dur_ns] of
    its ``XLA Ops`` line. An asynchronous transfer runs from the start of
    ``collective-permute-start.<n>`` to the end of the next
    ``collective-permute-done.<n>`` (paired by their number: XLA does not
    complete them in the order it issued them); other ops run in between,
    which is why the ops' own durations would under-count the time and
    over-count the share. A synchronous ``collective-permute`` is its own
    interval. A start the cut left without its done counts for nothing."""
    opened, spans = {}, []
    for name, _, start, dur in sorted(ops, key=lambda o: o[2]):
        if name.startswith(START):
            opened.setdefault(name[len(START):], []).append(start)
        elif name.startswith(DONE):
            begun = opened.get(name[len(DONE):])
            if begun:
                spans.append((begun.pop(0), start + dur))
        elif name.startswith(WHOLE):
            spans.append((start, start + dur))
    return trace_reduce.union_ns(spans)


def roofline_share_pct(sent_bytes: float, in_flight_s: float,
                       device_kind: str) -> float:
    """The least time the bytes need at the interconnect's peak, over the
    time the transfers were in flight, in percent."""
    peak = bytes_model.load_peaks(device_kind)["ici_bits_per_s"] / 8
    return 100.0 * sent_bytes / peak / in_flight_s
