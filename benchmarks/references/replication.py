"""The plain reference for primary-backup replication with three log
copies: numpy and Python, nothing of ``dint_tpu``.

Written from the source's commit path, ``tatp/caladan/
client_ebpf_shard.cc:779-900`` (a committing client sends CommitLog to
three servers, :779-810, then CommitBck to the two backups, :812-860, and
CommitPrim to the primary) and the backup server's handling of COMMIT_BCK
and COMMIT_LOG, ``tatp/ebpf/shard_kern.c:659-939`` (store the value and
the version the message carries, or drop the row; append {table, key,
version, value} to the local log). Departures, each what the system under
test states of itself (``configs/tatp7m-x4r3.json`` ``assumed``):

* servers are devices of one ring: device d's two backups are on d + 1
  and d + 2, and those two keep its log stream beside their own (the
  source's three servers back each other up in the same way; with four
  there is a fourth that holds nothing of d's);
* a deleted row keeps its slot: the exists bit of its meta word is
  cleared and the version it was deleted at, and the words the install
  carried, stay (the source frees the hash-table entry); tables are flat
  arrays laid end to end, not hash tables;
* an entry carries the stream's tag (0 on the primary's own ring, d + 1
  where it was forwarded) in its ``key_hi`` word, so that one ring can
  hold three streams apart; the source keeps one log per server and no
  tag."""
from __future__ import annotations

import numpy as np

N_BACKUPS = 2       # CommitBck x2
HDR_WORDS = 4       # is_delete | table << 8, tag, key, version


def placement(n: int) -> dict:
    """Who holds what of device d, for n devices in a ring: ``backups``
    [(holder, slot)] (slot s of device d + s + 1) and ``streams``
    [(ring, tag)] (its own ring under tag 0, rings d + 1 and d + 2 under
    tag d + 1). Three different devices each, so n >= 3."""
    if n < N_BACKUPS + 1:
        raise ValueError(f"{N_BACKUPS + 1} fault domains need as many "
                         f"devices, not {n}")
    return {d: {"backups": [((d + s + 1) % n, s)
                            for s in range(N_BACKUPS)],
                "streams": [(d, 0)] + [((d + h) % n, d + 1)
                                       for h in range(1, N_BACKUPS + 1)]}
            for d in range(n)}


def carried(n: int, ring: int) -> list:
    """[(source device, tag)] of the streams ring ``ring`` carries: its
    own, then those of the devices one and two before it."""
    where = placement(n)
    return [(d, tag) for d in ((ring - h) % n for h in range(N_BACKUPS + 1))
            for r, tag in where[d]["streams"] if r == ring]


def replay(meta: np.ndarray, val: np.ndarray, table_rows, stream,
           tag: int) -> tuple:
    """A partition's tables after an ordered stream of acknowledged
    installs, and the log entries a replica that took the stream must
    hold, in the stream's order.

    meta [rows] u32 (version << 1 | exists) and val [rows, words] u32: the
    tables laid end to end, ``table_rows`` rows each; ``stream``: (table,
    key, is delete, version, value words) in the order acknowledged.
    Returns (meta, val, entries [n, HDR_WORDS + words] u32)."""
    meta, val = meta.copy(), val.copy()
    base = np.cumsum([0, *table_rows[:-1]])
    entries = np.zeros((len(stream), HDR_WORDS + val.shape[1]), np.uint32)
    for i, (table, key, is_delete, version, words) in enumerate(stream):
        if not 0 <= key < table_rows[table]:
            raise ValueError(f"key {key} outside table {table}")
        row = base[table] + key
        meta[row] = (int(version) << 1) | (0 if is_delete else 1)
        val[row] = words
        entries[i, :HDR_WORDS] = (int(bool(is_delete)) | (table << 8), tag,
                                  key, version)
        entries[i, HDR_WORDS:] = words
    return meta, val, entries
