"""Primary-backup replication with three log copies, sequentially: the
program's own copy of the benchmark's plain reference
(benchmarks/references/replication.py decides ``correct``; this one
serves tests/, which must not lean on the benchmark's files to judge the
program). Same three functions, same answers (tests/bench/
test_bench_replicated.py holds the two equal: change both), other code:
the tables take each row's LAST install of the stream in one vectorised
assignment where the benchmark's copy walks the stream.

Device d of n in a ring: backups in slot s of device d + s + 1, its log
stream in its own ring under tag 0 and in rings d + 1, d + 2 under tag
d + 1 (parallel/dense_sharded.py; the reference's CommitBck x2 +
CommitLog x3, client_ebpf_shard.cc:779-900)."""
from __future__ import annotations

import numpy as np

N_BACKUPS = 2
HDR_WORDS = 4       # is_delete | table << 8, tag, key, version


def placement(n: int) -> dict:
    if n < N_BACKUPS + 1:
        raise ValueError(f"{N_BACKUPS + 1} fault domains need as many "
                         f"devices, not {n}")
    out = {}
    for d in range(n):
        holders = [(d + h) % n for h in range(1, N_BACKUPS + 1)]
        out[d] = {"backups": [(h, s) for s, h in enumerate(holders)],
                  "streams": [(d, 0)] + [(h, d + 1) for h in holders]}
    return out


def carried(n: int, ring: int) -> list:
    placement(n)        # the same refusal
    return [(ring, 0)] + [((ring - h) % n, (ring - h) % n + 1)
                          for h in range(1, N_BACKUPS + 1)]


def replay(meta: np.ndarray, val: np.ndarray, table_rows, stream,
           tag: int) -> tuple:
    """(meta, val, entries) after ``stream`` = [(table, key, is delete,
    version, value words)], in order; see the benchmark's copy."""
    meta, val = meta.copy(), val.copy()
    words = val.shape[1]
    if not len(stream):
        return meta, val, np.zeros((0, HDR_WORDS + words), np.uint32)
    table, key, is_del, ver = (np.array([s[i] for s in stream], np.int64)
                               for i in range(4))
    vals = np.array([s[4] for s in stream], np.uint32).reshape(-1, words)
    sizes = np.asarray(table_rows, np.int64)
    if ((key < 0) | (key >= sizes[table])).any():
        raise ValueError("key outside its table")
    rows = (np.cumsum(sizes) - sizes)[table] + key
    # the last install of each row: the first of the reversed stream
    urows, first_rev = np.unique(rows[::-1], return_index=True)
    last = len(rows) - 1 - first_rev
    meta[urows] = ((ver[last] << 1) | (is_del[last] == 0)).astype(np.uint32)
    val[urows] = vals[last]
    head = np.stack([(is_del != 0) | (table << 8), np.full_like(key, tag),
                     key, ver], axis=1).astype(np.uint32)
    return meta, val, np.concatenate([head, vals], axis=1)
