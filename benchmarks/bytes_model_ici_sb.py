"""Bytes a sharded SmallBank step sends over the chip-to-chip interconnect,
and the time its transfers are in flight, from the cell's shapes and the
trace's all-to-all and collective-permute ops.

parallel/dense_sharded_sb.py exchanges whole arrays of ``D x cap`` slots
(``cap = 2 x ceil(w x L / D)`` a destination), masked lanes too, as
``bytes_model_ici.py`` counts a hop's record: a collective moves arrays,
not the live lanes of them. A step makes

* nine ``all_to_all``s: the requests (op and local row, i32 each), the
  replies (the grant, a bool, and the balance, i32), the installs (mask,
  local row, balance, table and account, i32 each). The bucket a device
  keeps for itself does not leave it: (D - 1) / D of each array does;
* two ``ppermute`` hops of the applied installs to d + 1 and d + 2: the
  mask (bool) and row, balance, table and account (i32 each), whole."""
from __future__ import annotations

from benchmarks import bytes_model_ici, trace_reduce
from benchmarks.references.smallbank_sharded import bucket_cap

WORD, FLAG = 4, 1
A2A_SLOT_BYTES = (2 * WORD) + (FLAG + WORD) + (5 * WORD)
HOP_SLOT_BYTES = FLAG + 4 * WORD
HOPS = 2
A2A = "all-to-all"
PERMUTE = "collective-permute"


def step_bytes(w: int, l: int, d: int) -> dict:
    """What one device sends to others in one step."""
    cap = bucket_cap(w, d, l)
    out = {"all_to_all": (d - 1) * cap * A2A_SLOT_BYTES,
           "ppermute": HOPS * d * cap * HOP_SLOT_BYTES}
    out["total"] = sum(out.values())
    return out


def in_flight_ns(ops) -> float:
    """Length of the union of the intervals in which an all-to-all or a
    collective-permute is in flight on one device. ``ops``: [name, _,
    start_ns, dur_ns] of its ``XLA Ops`` line. An asynchronous transfer
    runs from the start of ``<kind>-start.<n>`` to the end of the
    ``<kind>-done.<n>`` of its number (``bytes_model_ici.in_flight_ns``'s
    pairing, for both kinds); a synchronous one is its own interval."""
    opened, spans = {}, []
    for name, _, start, dur in sorted(ops, key=lambda o: o[2]):
        for kind in (A2A, PERMUTE):
            if name.startswith(kind + "-start"):
                opened.setdefault(kind + name[len(kind + "-start"):],
                                  []).append(start)
            elif name.startswith(kind + "-done"):
                begun = opened.get(kind + name[len(kind + "-done"):])
                if begun:
                    spans.append((begun.pop(0), start + dur))
            elif name.startswith(kind):
                spans.append((start, start + dur))
            else:
                continue
            break
    return trace_reduce.union_ns(spans)


roofline_share_pct = bytes_model_ici.roofline_share_pct
