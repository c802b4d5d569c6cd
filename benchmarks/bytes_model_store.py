"""Bytes one KV store step must move through HBM, by lanes and by the
run's own counts, whatever implements it.

What a step of GETs and whole-record updates over a table in HBM needs,
and no more: every operation reads the key it names out of the table (8
bytes: a store that does not compare the key cannot tell a hit from a
collision); a GET that hits reads the value and its version; an update
writes the value and the version. No bucket, slot, sort or tile
granularity: that this implementation reads both candidate buckets' four
slots a lane (2 x 4 x 9 B), sorts the batch three times and scatters every
lane, live or not, is its own, and shows in ``probe_ms.kv``,
``key_sort_ms.kv`` and ``install_ms.kv``. So the share this gives is a
floor on how far the step is from the bandwidth bound, never above it
(benchmarks/bytes_model.py has the peaks)."""
from __future__ import annotations

from benchmarks.bytes_model import WORD

KEY_BYTES = 2 * WORD        # key_hi, key_lo


def step_bytes(w: int, val_words: int, hit_gets: float,
               updates: float) -> dict:
    """Per-kind bytes of one step on one device. ``w`` operations;
    ``hit_gets`` and ``updates`` per step, from the run's stats."""
    record = (val_words + 1) * WORD            # value words + version
    out = {"key_read": w * KEY_BYTES,
           "get_read": hit_gets * record,
           "update_write": updates * record}
    out["total"] = sum(out.values())
    return out
