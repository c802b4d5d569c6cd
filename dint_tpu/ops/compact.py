"""Write-set compaction: a masked table op issues its live lanes only.

Measured on v5e (PERF.md §6, PR 30): a scatter index routed out of bounds
under ``mode="drop"`` costs what a live one costs (81 ns a value word, 87 a
meta word, 132 a log row, landed or dropped), and TATP's mix leaves ~9 % of
the 2w write slots live. So a mask is compacted before it reaches a
scatter: each lane's turn among the live ones (``live_ranks``: one cumsum),
then the scatter runs over fixed chunks of the live lanes, as many as the
live count needs (``for_chunks``: a ``while_loop`` whose trip count the
step itself observes: 0 trips when nothing is live, the whole width when
everything is). All of it vector work. Five users: the install and the
log append of dense TATP under the write mask (PR 30; ``tables/log.
append_rep`` under a ``Ranked`` mask), its lock wave under the mask of
active write slots
(PR 34: ~11 % of the 2w; the stamp gather and the winner read-back are
chunked with the scatter-max, since a gather lane on the sentinel row
costs what a live one costs, and a chunk's verdicts go back to lane space
through ``lanes_mask``), the backups' apply of ``parallel/
dense_sharded`` under the forwarded record's write mask (PR 38: the
RECEIVER ranks the 2w lanes that arrive, once a hop, for the install into
its backup slot and the append into its ring; the record itself crosses
the mesh at full width), and the KV store's install under the mask of a
step's elected writers (PR 40, ``engines/store._install_live``: ~5 % of
the lanes under YCSB-B; one loop for value words and versions, a second,
idle under a GET / SET mix, for the words that change only when a slot is
allocated or freed), and the three ring appends of ``parallel/
dense_sharded_sb`` under an inbox's install mask (PR 44: ~17 % of the
D x cap slots). That one is different: the mask is D segments of ``cap``
slots, each live in a PREFIX (a source fills a destination's bucket in
arrival order, the all_to_all moves whole buckets), so ``prefixed`` counts
the segments and a chunk's lanes follow from the D counts, D compares a
position: no running count over the lanes and no chunk x R search, which
at R = 49,152 would cost what a third of the dropped rows do. Tried on
the chip and left
(PERF.md §6, PR 30): one sort of the lane ids, 0.11 ms a step faster in
``tatp7m-sat``, but the protocol proofs read a sort as the generic
engines' segment evidence (analysis/dataflow.py SORTED) and would have
passed a step whose lock arbitration was gone; log2(R) rounds of shifted
selects, which slowed the lock wave's three ops by 0.38 ms. Never a
scatter of lane ids (the R indices being saved) nor ``jnp.nonzero(size=)``
(a duplicate-index scatter-add: those serialize).

A third way, where neither pays (PERF.md §6, PR 36): the v5e compiler sorts
a 1-D scatter's (index, value) pairs itself, and then issues a lane at
18-24 ns, not 90, once the scatter issues about one index per 1,630 table
words. So a scatter whose mask is half live (``smallbank_dense``'s
install: compaction's lane search would cost what it saves) issues
``sorted_scatter_lanes`` lanes, the added ones out of bounds: the sort is
the compiler's, in no jaxpr, and the proofs read what they read. The same
density decides, for a second user, which form one algorithm takes
(``compiler_sorts``; PR 40): the KV store's ``step`` keeps its full-width
scatters where they are that dense already (the populate: 65,536 all-live
lanes into 2^26 entries, 0.90 us a key) and compacts them where they are
not (the serve block: 8,192 lanes, 95 % of them dead).
"""
from __future__ import annotations

import flax.struct
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


# The v5e compiler was seen to sort a 1-D scatter at 1,616 table words an
# index and not at 1,674 (AOT, PR 36; tests/test_chip_compile.py pins
# it): a margin under the first.
SORTED_SCATTER_WORDS_PER_LANE = 1536


def compiler_sorts(table_words: int, lanes: int) -> bool:
    """Whether a 1-D scatter of ``lanes`` indices into ``table_words``
    words is dense enough, as it stands, that the compiler sorts its
    indices first."""
    return table_words <= lanes * SORTED_SCATTER_WORDS_PER_LANE


def sorted_scatter_lanes(table_words: int, lanes: int) -> int:
    """Lanes a 1-D scatter of ``lanes`` indices into ``table_words`` words
    issues so that the compiler sorts its indices first: ``lanes`` where
    it is that dense already; else the least multiple of 128 with at most
    SORTED_SCATTER_WORDS_PER_LANE words a lane (48,000,001 words at 24,576
    lanes: 31,360); and ``lanes``, no fill, where that would more than
    double them (a 512-lane chunk into a 70 M-word table is not this
    case). The caller routes the added lanes out of bounds."""
    if compiler_sorts(table_words, lanes):
        return lanes
    dense = -(-table_words // (SORTED_SCATTER_WORDS_PER_LANE * 128)) * 128
    return dense if dense <= 2 * lanes else lanes


def filled(x, lanes: int, value):
    """``x`` [R] with ``lanes - R`` lanes of ``value`` appended (none to
    add: ``x`` itself, no equation)."""
    fill = lanes - x.shape[0]
    if not fill:
        return x
    return jnp.concatenate([x, jnp.full((fill,), value, x.dtype)])


def live_ranks(mask):
    """(ranks i32 [R], n_live i32): the running count of live lanes. The
    j-th live lane (from 0) is the first whose count reaches j + 1."""
    ranks = jnp.cumsum(mask, dtype=I32)
    return ranks, ranks[-1]


def _ranked_lane(ranks, at):
    return jnp.searchsorted(ranks, at + 1, method="compare_all")


def _chunk_loop(lane_of, r: int, n_live, chunk: int, body, carry):
    """The loop of ``for_chunks`` over a mask of ``r`` lanes whose j-th
    live lane (from 0) is ``lane_of(j)``."""
    last = r - 1
    pos = jnp.arange(chunk, dtype=I32)

    def more(state):
        return state[0] * chunk < n_live

    def one(state):
        i, carry = state
        at = i * chunk + pos
        lanes = lane_of(at)
        return i + 1, body(carry, jnp.minimum(lanes.astype(I32), last),
                           at < n_live)

    trips, carry = jax.lax.while_loop(more, one, (jnp.asarray(0, I32), carry))
    return carry, trips


def for_chunks(ranks, n_live, chunk: int, body, carry):
    """``carry = body(carry, lanes, ok)`` over the live lanes in lane
    order, ``chunk`` of them a trip: ``lanes`` i32 [chunk] are lane ids
    (the chunk's positions compared with every rank: chunk x R compares,
    no gather), ``ok`` marks the positions below ``n_live`` (a body routes
    the others out of bounds). Returns (carry, trips);
    trips = ceil(n_live / chunk)."""
    return _chunk_loop(lambda at: _ranked_lane(ranks, at), ranks.shape[0],
                       n_live, chunk, body, carry)


class Live:
    """A mask together with what enumerates its live lanes, made once and
    handed to a masked op IN PLACE of the plain mask: the op then issues
    the live lanes only, ``chunk`` of them a trip (tables/log.append_rep).
    Two forms, by what the maker knows of the mask: ``Ranked`` (nothing:
    the running count, a search a chunk) and ``Prefixed`` (equal segments,
    each live in a prefix: no search). Both have ``mask`` bool [R],
    ``n_live`` i32 and ``lane_of(at)``, the lane of the at-th live one."""

    @property
    def chunk(self) -> int:
        return chunk_lanes(self.mask.shape[0])

    @property
    def trips(self):
        """The trips ``for_chunks`` makes: ceil(n_live / chunk)."""
        return (self.n_live + (self.chunk - 1)) // self.chunk

    def for_chunks(self, body, carry):
        """``for_chunks`` of the module over this mask's live lanes."""
        return _chunk_loop(self.lane_of, self.mask.shape[0], self.n_live,
                           self.chunk, body, carry)


@flax.struct.dataclass
class Ranked(Live):
    """``Ranked(mask, *live_ranks(mask))``: any mask; a chunk's lanes by
    search (chunk x R compares a trip, R x n_live in all)."""
    mask: jax.Array      # bool [R]
    ranks: jax.Array     # i32 [R]
    n_live: jax.Array    # i32

    def lane_of(self, at):
        return _ranked_lane(self.ranks, at)


@flax.struct.dataclass
class Prefixed(Live):
    """``prefixed(mask, S)``: a mask of S equal segments, each live in a
    PREFIX (mask[s * cap + p] == (p < counts[s])): what a bucket exchange
    delivers when every source fills a destination's bucket in arrival
    order (parallel/dense_sharded_sb._route). The j-th live lane is then
    j plus the dead tails of the segments that end at or before j: S
    compares a position, no search. A live lane outside its segment's
    prefix would never be issued: the maker holds the invariant."""
    mask: jax.Array      # bool [S * cap]
    counts: jax.Array    # i32 [S]: live lanes of each segment
    ends: jax.Array      # i32 [S]: their running sum

    @property
    def n_live(self):
        return self.ends[-1]

    def lane_of(self, at):
        cap = self.mask.shape[0] // self.counts.shape[0]
        passed = self.ends[None, :] <= at[:, None]
        return at + jnp.where(passed, cap - self.counts[None, :],
                              0).sum(axis=1)


def prefixed(mask, segments: int) -> Prefixed:
    counts = mask.reshape(segments, -1).sum(axis=1, dtype=I32)
    return Prefixed(mask=mask, counts=counts, ends=jnp.cumsum(counts))


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
