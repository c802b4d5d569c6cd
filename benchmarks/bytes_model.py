"""Bytes one engine step must move through HBM, from the cell's shapes.

Every gather lane reads one 4-byte word whether its op is real or a NOP
(NOP lanes read the sentinel row), so gathers are counted by lane. A
masked scatter lane is dropped and moves nothing, so installs, log
appends and lock stamps are counted by the run's own counters
(``installs`` and ``lock_requests`` per step and device). No cache line
or tile granularity is assumed: this is the least the algorithm needs,
so the roofline share it gives is a floor on how far the step is from
the bandwidth bound, never above it."""
from __future__ import annotations

import json
import os

WORD = 4
LOG_HDR_WORDS = 4        # flags, key_hi, key_lo, ver (tables/log.py layout)
# one lane of the Installs record a replication hop ships: wmask (1 byte),
# rows, meta, tbl, key, is_del, ver (4 bytes each) + the value words
HOP_LANE_FIXED = 1 + 6 * WORD


def step_bytes(w: int, k: int, val_words: int, log_replicas: int,
               installs: float, lock_requests: float,
               n_backups: int = 0) -> dict:
    """Per-wave bytes of one step on one device. ``log_replicas`` is how
    many entries one local append writes (3 packed on one chip, 1 per
    device when the copies live on three devices); ``n_backups`` is how
    many forwarded install records this device applies per step (0 on one
    chip, 2 on the replicated mesh)."""
    entry = (LOG_HDR_WORDS + val_words) * WORD
    row = (val_words + 1) * WORD                     # value words + meta
    out = {
        # validate lanes of the cohort one step behind + read lanes of the
        # new cohort, one meta word each
        "meta_gather": 2 * w * k * WORD,
        # the magic word of every lane of the new cohort
        "magic_gather": w * k * WORD,
        # old stamp of both write slots, a read-modify-write per request,
        # and the gather-back that names the winner
        "lock": 2 * w * WORD + lock_requests * 2 * WORD + 2 * w * WORD,
        "install": installs * row,
        "log_append": installs * log_replicas * entry,
    }
    if n_backups:
        hop = 2 * w * (HOP_LANE_FIXED + val_words * WORD)
        # each hop's record is read once where it is sent and written once
        # where it arrives; a backup applies it to its copy and its ring
        out["replicate"] = n_backups * (2 * hop + installs * (row + entry))
    out["total"] = sum(out.values())
    return out


def load_peaks(device_kind: str) -> dict:
    """This device's published peaks. An unknown kind is an error: a
    share of a guessed peak is not a measurement."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {path}; known: {sorted(table)}")
    return table[device_kind]


def roofline_share_pct(total_bytes: float, step_s: float,
                       device_kind: str) -> float:
    """The least time the bytes need at the HBM peak, over the step's
    measured time, in percent. Bound by bytes: the step does no
    arithmetic worth counting."""
    return 100.0 * total_bytes / load_peaks(device_kind)["hbm_bytes_per_s"] \
        / step_s
