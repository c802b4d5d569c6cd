"""Bytes one SmallBank engine step must move through HBM, by lanes and by
the run's own counters, whatever implements them.

What strict 2PL over hashed S/X lock slots needs of a step, and no more:
every lock lane reads its slot's exclusive stamp, its shared stamp and
its row's balance, a 4-byte word each, whether the lane is live or not
(dead lanes read the sentinel); a granted lock reads and writes one
stamp; an installed balance writes one word; a log entry is written
three times. The arbitration among a step's requests is not in it: the
two slot-table-wide arrays this implementation fills and scatter-mins
(2 x 134 MB at 2^25 slots) are its own, and what they cost shows in
``lock_arb_ms.sb``. No cache line or tile granularity is assumed, so the
share this gives is a floor on how far the step is from the bandwidth
bound, never above it (benchmarks/bytes_model.py has the peaks)."""
from __future__ import annotations

from benchmarks.bytes_model import LOG_HDR_WORDS, WORD


def step_bytes(w: int, l: int, val_words: int, log_replicas: int,
               lock_granted: float, installs: float) -> dict:
    """Per-wave bytes of one step on one device. ``w`` transactions of
    ``l`` lock lanes each; ``lock_granted`` and ``installs`` per step,
    from the counter plane."""
    entry = (LOG_HDR_WORDS + val_words) * WORD
    out = {
        # both stamp tables at every lane, then a read and a write of one
        # stamp for every grant
        "lock": 2 * w * l * WORD + lock_granted * 2 * WORD,
        "read": w * l * WORD,
        "install": installs * WORD,
        "log_append": installs * log_replicas * entry,
    }
    out["total"] = sum(out.values())
    return out
