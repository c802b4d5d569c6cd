"""Write-set compaction: a masked table op issues its live lanes only.

Measured on v5e (PERF.md §6, PR 30): a scatter index routed out of bounds
under ``mode="drop"`` costs what a live one costs (81 ns a value word, 87 a
meta word, 132 a log row, landed or dropped), and TATP's mix leaves ~9 % of
the 2w write slots live. So a mask is compacted before it reaches a
scatter: each lane's turn among the live ones (``live_ranks``: one cumsum),
then the scatter runs over fixed chunks of the live lanes, as many as the
live count needs (``for_chunks``: a ``while_loop`` whose trip count the
step itself observes: 0 trips when nothing is live, the whole width when
everything is). All of it vector work. Two users: the install and the log
append of dense TATP under the write mask (PR 30; ``tables/log.
append_rep_live``), and its lock wave under the mask of active write
slots (PR 34: ~11 % of the 2w; the stamp gather and the winner read-back
are chunked with the scatter-max, since a gather lane on the sentinel row
costs what a live one costs, and a chunk's verdicts go back to lane space
through ``lanes_mask``). Tried on the chip and left
(PERF.md §6, PR 30): one sort of the lane ids, 0.11 ms a step faster in
``tatp7m-sat``, but the protocol proofs read a sort as the generic
engines' segment evidence (analysis/dataflow.py SORTED) and would have
passed a step whose lock arbitration was gone; log2(R) rounds of shifted
selects, which slowed the lock wave's three ops by 0.38 ms. Never a
scatter of lane ids (the R indices being saved) nor ``jnp.nonzero(size=)``
(a duplicate-index scatter-add: those serialize).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

I32 = jnp.int32


def chunk_lanes(r: int) -> int:
    """Lanes a chunk issues, from the mask's width alone: a thirty-second
    of it, at least 128 (the lane width of a vector register), at most all
    of it. TATP's 2w = 16,384 slots hold ~1,430 live (sigma ~45): three
    chunks of 512 a step. Swept on the chip at 2w = 16,384 (PERF.md §6,
    PR 30): 128 / 256 / 512 / 2048 lanes gave 6.63 / 6.47 / 6.41 / 6.96 ms
    a step; a trip costs its lanes and a chunk x R compare, so small
    chunks waste fewer lanes and pay more trips."""
    return min(r, max(128, r // 32))


def live_ranks(mask):
    """(ranks i32 [R], n_live i32): the running count of live lanes. The
    j-th live lane (from 0) is the first whose count reaches j + 1."""
    ranks = jnp.cumsum(mask, dtype=I32)
    return ranks, ranks[-1]


def for_chunks(ranks, n_live, chunk: int, body, carry):
    """``carry = body(carry, lanes, ok)`` over the live lanes in lane
    order, ``chunk`` of them a trip: ``lanes`` i32 [chunk] are lane ids
    (the chunk's positions compared with every rank: chunk x R compares,
    no gather), ``ok`` marks the positions below ``n_live`` (a body routes
    the others out of bounds). Returns (carry, trips);
    trips = ceil(n_live / chunk)."""
    last = ranks.shape[0] - 1
    pos = jnp.arange(chunk, dtype=I32)

    def more(state):
        return state[0] * chunk < n_live

    def one(state):
        i, carry = state
        at = i * chunk + pos
        lanes = jnp.searchsorted(ranks, at + 1, method="compare_all")
        return i + 1, body(carry, jnp.minimum(lanes.astype(I32), last),
                           at < n_live)

    trips, carry = jax.lax.while_loop(more, one, (jnp.asarray(0, I32), carry))
    return carry, trips


def lanes_mask(lanes, on, r: int):
    """bool [r]: the lanes of a chunk that ``on`` marks, back in lane space
    (``lanes`` i32 [chunk] as a ``for_chunks`` body gets them): a chunk x r
    compare OR-ed over the chunk, what the search costs. No scatter of
    lane ids, no r-lane gather out of a compact buffer."""
    return ((lanes[:, None] == jnp.arange(r, dtype=I32))
            & on[:, None]).any(axis=0)


def varying_like(x, ref):
    """``x`` marked varying over the mesh axes ``ref`` varies over and it
    does not (shard_map's types; ``x`` itself outside one): a loop's first
    carry, born of constants, has to close over what the body makes of it
    out of a sharded table."""
    missing = tuple(jax.typeof(ref).vma - jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x
