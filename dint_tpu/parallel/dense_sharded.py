"""Multi-chip dense TATP: partitioned subscribers + ICI replication.

Scales the flagship dense engine (engines/tatp_dense.py) across a device
mesh the way the reference scales across its 3 servers — but re-partitioned
TPU-first. The reference shards each table independently by `key % 3`
(tatp/caladan/client_ebpf_shard.cc:636-641), so one transaction's messages
fan out to several servers and the client pays multi-server RTTs. Every
TATP table, however, is keyed by the subscriber id (sf_idx = s_id*4+t,
cf_key = s_id*12+..., tatp/caladan/tatp.h:28), so partitioning by
SUBSCRIBER makes every transaction device-local by construction — the
cross-device traffic that remains is exactly the replication the reference
pays too:

  * device d runs the full fused 3-wave pipeline on its local subscriber
    range (its own on-device workload generator, locks, OCC validation);
  * each step's install record (engines/tatp_dense.Installs) is forwarded
    to devices d+1 and d+2 with `ppermute` over ICI — the reference's
    CommitBck x2 (client_ebpf_shard.cc:812-860) — and applied there to
    backup tables;
  * the receivers ALSO append the forwarded records to their own log
    rings, so every write lands in 3 devices' logs — the reference's
    CommitLog x3 (:779-810), now real cross-device replicated logging
    (the single-chip engine's RepLog packs 3 replica entries locally
    instead);
  * per-step stats are `psum`med across the mesh — batched 2PC vote
    collection.

Backup tables use the tight interleaved 1-D layout ([rows * VW] words)
rather than the primary's padded [rows, VW]: XLA pads trailing dims to 128
lanes, and at the reference's 7M-subscriber scale the backup copies are
what pushes per-device HBM over the edge (SURVEY.md §6; two backup ranges
per device). Backups hold val + ver:exists only — locks are volatile
primary-side state, exactly like the reference's backup servers.

Runs under one jitted shard_map block, jit(shard_map(scan(step))). Between
dispatches a device holds its ShardState as the scan carries it: every
table, ring and backup leaf is a global [D * N, ...] array sharded over
axis 0 (a device's [N, ...]), with NO stacked axis, so the block's
parameters and results are the scan's own carry buffers. (A stacked
[1, N] parameter is tiled otherwise than the scan's [N] carry: squeezing
and unsqueezing it copied every table at the block's entry and exit, 5 ms
of a 12.9 ms step on four chips, PERF.md PR 42.) `create_sharded`, the
state `init` takes and the state `drain` returns are stacked [D, N, ...];
`init` and `drain` convert. A flat leaf's global length passes 2^31 at 7 M
subscribers: it is only ever touched inside a shard_map.

Tested on the virtual 8-device CPU mesh and exercised by
__graft_entry__.dryrun_multichip.
"""
from __future__ import annotations

import functools

import flax.struct
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..engines import tatp_dense as td
from ..engines._memo import memoize_builder, refuse_retired
from ..monitor import counters as mon
from ..monitor import waves
from ..ops import compact
from ..tables import log as logring
from .sharded import (SHARD_AXIS, make_mesh, pcast_varying,   # noqa: F401 (re-exported)
                      stack_on_mesh)

I32 = jnp.int32
U32 = jnp.uint32

N_BCK = 2      # backup copies per row range (reference: 3 replicas total)


@flax.struct.dataclass
class ShardState:
    """One device's slice: a full single-chip DenseDB for its subscriber
    range + tight backup copies of the two predecessor devices' ranges
    (slot 0 = device d-1's rows, slot 1 = d-2's)."""
    db: td.DenseDB
    bck_val: jax.Array    # u32 [N_BCK * n1_loc * VW]  interleaved words
    bck_meta: jax.Array   # u32 [N_BCK * n1_loc]       ver<<1 | exists


def n_sub_local(n_sub_global: int, n_shards: int) -> int:
    return (n_sub_global + n_shards - 1) // n_shards


def ring_perm(n: int, off: int) -> list:
    """The replication hop: device i sends to device i+off around the
    ring (CommitBck x2, client_ebpf_shard.cc:812-860)."""
    return [(i, (i + off) % n) for i in range(n)]


def populate_local(seed: int, part, n_loc: int, val_words: int, pull,
                   **kw) -> ShardState:
    """What every device runs ON ITSELF inside the create functions'
    shard_map: populate partition `part` with the single-chip rules
    (td.populate_device — reference populate,
    client_ebpf_shard.cc:96-341), then fetch the two predecessors'
    populated tables as the backup copies through `pull(x, off)`, the
    same ppermute hop the installs ride. db.val / db.meta end in the
    all-zero sentinel row, which is each backup slot's padding row."""
    # log_replicas=1: the 3 log copies live on 3 devices here (forwarded
    # installs are appended by each receiver), not packed per-slot
    db = td.populate_device(
        jax.random.fold_in(jax.random.PRNGKey(seed), part), n_loc,
        val_words=val_words, log_replicas=1, **kw)
    return ShardState(
        db=db,
        bck_val=jnp.concatenate([pull(db.val, off) for off in (1, 2)]),
        bck_meta=jnp.concatenate([pull(db.meta, off) for off in (1, 2)]))


def create_sharded(mesh: Mesh, n_shards: int, n_sub_global: int,
                   val_words: int = 10, seed: int = 0,
                   **kw) -> ShardState:
    """Stacked per-device state sharded over the mesh (leading axis =
    device). One shard_map program: each shard is populated on its own
    device and the backups arrive over the ring, so nothing global is
    ever materialised on one chip (7 M subscribers is ~18.6 GB of primary
    + 2 backups against 16 GB of HBM)."""
    n_loc = n_sub_local(n_sub_global, n_shards)

    def pull(x, off):
        return jax.lax.ppermute(x, SHARD_AXIS, ring_perm(n_shards, off))

    def local():
        one = populate_local(seed, jax.lax.axis_index(SHARD_AXIS), n_loc,
                             val_words, pull, **kw)
        return jax.tree.map(lambda x: x[None], one)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(),
                                 out_specs=P(SHARD_AXIS)))()


def _with_step(state: ShardState, step) -> ShardState:
    return state.replace(db=state.db.replace(step=step))


def _map_tables(f, state: ShardState) -> ShardState:
    """``f`` over every leaf of a ShardState but `db.step`, the one
    per-device scalar: [D] (a device's [1]) in both forms of the carry."""
    return _with_step(jax.tree.map(f, _with_step(state, None)),
                      state.db.step)


def _apply_backup(state: ShardState, inst: td.Installs, slot: int,
                  n1: int, val_words: int, src_dev,
                  chunks: list | None = None):
    """Install a forwarded record into backup copy `slot` + log it locally
    (the backup server's COMMIT_BCK + COMMIT_LOG handling,
    tatp/ebpf/shard_kern.c:659-939). Entries log key_hi = the SOURCE
    device: rows are source-local ids, and a log that mixes 3 devices'
    entries must stay separable for cross-device recovery
    (recovery.recover_tatp_dense with key_hi_filter).

    The record arrives at full width (2w lanes, ~9 % of them live under
    TATP's mix) and a dropped scatter lane costs what a live one costs,
    so the RECEIVER ranks its lanes once a hop (ops/compact.py) and the
    install and the append issue the live ones, C = chunk_lanes(2w) lanes
    a chunk, as the primary does with its own (tatp_dense.pipe_step).
    ``chunks``: a list that gets the install loop's trip count (the
    counter plane's bck_chunks)."""
    base = slot * n1
    oob = N_BCK * n1
    with waves.part("dense_sharded", "bck_compact"):
        ranks, n_live = compact.live_ranks(inst.wmask)

        def install_chunk(tabs, lanes, ok):
            meta, val = tabs
            rows_c = jnp.where(ok, base + inst.rows[lanes], oob)
            meta_c, val_c = inst.meta[lanes], inst.val[lanes]
            with waves.part("dense_sharded", "bck_meta_scatter"):
                meta = meta.at[rows_c].set(meta_c, mode="drop",
                                           unique_indices=True)
            with waves.part("dense_sharded", "bck_val_scatter"):
                # a position past the live count rides the oob row:
                # oob*val_words is already past the end
                flat = (rows_c[:, None] * val_words
                        + jnp.arange(val_words, dtype=I32)).reshape(-1)
                val = val.at[flat].set(val_c.reshape(-1), mode="drop",
                                       unique_indices=True)
            return meta, val

        (meta, val), trips = compact.for_chunks(
            ranks, n_live, compact.chunk_lanes(inst.wmask.shape[0]),
            install_chunk, (state.bck_meta, state.bck_val))
        if chunks is not None:
            chunks.append(trips)
    with waves.part("dense_sharded", "bck_log_append"):
        # 1-based so "own entry" (key_hi == 0, written by pipe_step's
        # local append) can never collide with "forwarded from device 0"
        src = jnp.broadcast_to(src_dev.astype(U32) + U32(1),
                               inst.key.shape)
        log = logring.append_rep_live(state.db.log, ranks, n_live,
                                      inst.wmask, inst.tbl, inst.is_del,
                                      src, inst.key, inst.ver, inst.val)
    return state.replace(bck_val=val, bck_meta=meta,
                         db=state.db.replace(log=log))


@memoize_builder
def build_sharded_pipelined_runner(mesh: Mesh, n_shards: int,
                                   n_sub_global: int, w: int = 4096,
                                   val_words: int = 10,
                                   cohorts_per_block: int = 8, mix=None,
                                   use_pallas=None, use_fused=None,
                                   monitor: bool = False):
    """jit(shard_map(scan(step)))) over the carry. Same contract shape
    as the single-chip runner: returns (run, init, drain) where
      run(carry, key) -> (carry', stats [cohorts_per_block, N_STATS]
                          psummed across the mesh)
      init(state)     -> carry with two bootstrap cohorts per device;
                         consumes the stacked ``state``
      drain(carry)    -> (state, stats [2, N_STATS]) flushing pipelines;
                         ``state`` stacked again, fit for another init

    The carry is opaque to callers. `carry[0]` is the ShardState with no
    stacked axis (module docstring): a leaf is [D * N, ...] sharded over
    axis 0, `db.step` [D]. The contexts and the counters (`carry[1:]`,
    kilobytes) stay stacked [D, ...]. `init` and `drain` convert between
    the forms LEAF BY LEAF, one donated copy program a leaf shape: the
    relayout cannot alias, so one program over the whole state would hold
    it twice (10.6 GB a device at 7 M subscribers) where a leaf at a time
    peaks at the state plus `bck_val` (8.4 GB).

    ``monitor``: thread the dintmon counter plane PER DEVICE — the carry
    grows a trailing stacked monitor.Counters (buf [D, N_COUNTERS]; each
    device bumps its own slice inside shard_map, with the replication
    hops counted at the receiving device) and drain returns (state,
    stats, counters). Flow counters sum across the device axis to the
    psummed stats totals (monitor.snapshot does that reduction); off
    (default) = contract and jaxpr unchanged."""
    assert 2 * w <= (1 << td.K_ARB), f"w={w} exceeds the arb slot field"
    refuse_retired(use_pallas=use_pallas, use_fused=use_fused)
    n_loc = n_sub_local(n_sub_global, n_shards)
    n1 = td.n_rows(n_loc) + 1
    kw = dict(w=w, n_sub=n_loc, val_words=val_words)

    def local_step(state, c1, c2, key, cnt, gen_new=True):
        dev = jax.lax.axis_index(SHARD_AXIS)
        out = td.pipe_step(
            state.db, c1, c2, jax.random.fold_in(key, dev), mix=mix,
            gen_new=gen_new, emit_installs=True, counters=cnt, **kw)
        if cnt is not None:
            db, new_ctx, c1, stats, inst, cnt = out
        else:
            db, new_ctx, c1, stats, inst = out
        state = state.replace(db=db)
        # constants born inside the body (attempted, ab_validate=0) are
        # unvarying over the mesh axis; mark them varying so the scan
        # carry types close under shard_map (identity on older jax)
        new_ctx, c1 = jax.tree.map(
            lambda x: pcast_varying(x, SHARD_AXIS), (new_ctx, c1))
        # CommitBck + CommitLog fan-out: forward installs to d+1, d+2.
        # MACHINE-CHECKED (dintlint protocol pass): the backup/log writes
        # in _apply_backup must consume the PPERMUTED record (fwd), not
        # the local one — commit-after-replication fails the gate if the
        # hop's payload is dropped on the floor.
        with waves.scope("dense_sharded", "replicate"):
            for off in (1, 2):
                with waves.part("dense_sharded", "repl_hop"):
                    fwd = jax.tree.map(functools.partial(
                        jax.lax.ppermute, axis_name=SHARD_AXIS,
                        perm=ring_perm(n_shards, off)), inst)
                    src_dev = (dev - off) % n_shards
                trips = []
                state = _apply_backup(state, fwd, off - 1, n1, val_words,
                                      src_dev, trips)
                if cnt is not None:
                    # replication pushes, and the chunks their install
                    # took, counted where they are APPLIED (the receiving
                    # backup — the reference's CommitBck handler)
                    hop = (mon.CTR_REPL_PUSH_HOP1 if off == 1
                           else mon.CTR_REPL_PUSH_HOP2)
                    with waves.part("dense_sharded", "repl_hop"):
                        cnt = mon.bump(cnt, {
                            hop: fwd.wmask.sum(dtype=jnp.int32),
                            mon.CTR_BCK_CHUNKS: trips[0]})
        return state, new_ctx, c1, jax.lax.psum(stats, SHARD_AXIS), cnt

    def scan_fn(carry, key, gen_new=True):
        state, c1, c2 = carry[:3]
        cnt = carry[3] if monitor else None
        state, new_ctx, c1, stats, cnt = local_step(state, c1, c2, key,
                                                    cnt, gen_new)
        out = (state, new_ctx, c1) + ((cnt,) if monitor else ())
        return out, stats

    def sq(tree):
        return jax.tree.map(lambda x: x[0], tree)

    def unsq(tree):
        return jax.tree.map(lambda x: x[None], tree)

    def enter(args):
        """A device's carry as the scan takes it: the ShardState's leaves
        as they come (`db.step` [1] as the scalar it is), the small
        stacked rest squeezed."""
        state = args[0]
        return (_with_step(state, state.db.step[0]),) + tuple(
            sq(a) for a in args[1:])

    def leave_state(state):
        return _with_step(state, state.db.step[None])

    def block_local(*args):
        key = args[-1]
        carry0 = enter(args[:-1])
        state0 = carry0[0]
        db = jax.lax.cond(state0.db.step >= jnp.uint32(td.REBASE_AT),
                          td.rebase_stamps, lambda d: d, state0.db)
        keys = jax.random.split(key, cohorts_per_block)
        carry, stats = jax.lax.scan(
            scan_fn, (state0.replace(db=db),) + carry0[1:], keys)
        return (leave_state(carry[0]),) + tuple(
            unsq(x) for x in carry[1:]) + (stats,)

    def drain_local(*args):
        key = args[-1]
        carry = enter(args[:-1])
        carry, s1 = scan_fn(carry, key, gen_new=False)
        carry, s2 = scan_fn(carry, jax.random.fold_in(key, 1),
                            gen_new=False)
        out = (leave_state(carry[0]),) + (
            (unsq(carry[3]),) if monitor else ())
        return out + (jnp.stack([s1, s2]),)

    n_carry = 4 if monitor else 3
    spec = (P(SHARD_AXIS),) * n_carry + (P(),)
    block = jax.shard_map(block_local, mesh=mesh, in_specs=spec,
                          out_specs=(P(SHARD_AXIS),) * n_carry + (P(),))
    drain_m = jax.shard_map(
        drain_local, mesh=mesh, in_specs=spec,
        out_specs=(P(SHARD_AXIS),) * (2 if monitor else 1) + (P(),))

    donate = tuple(range(n_carry))
    jit_block = jax.jit(block, donate_argnums=donate)
    jit_drain = jax.jit(drain_m, donate_argnums=donate)

    def relayout(local):
        copy = jax.jit(jax.shard_map(local, mesh=mesh,
                                     in_specs=P(SHARD_AXIS),
                                     out_specs=P(SHARD_AXIS)),
                       donate_argnums=0)

        def one_leaf(x):
            # a leaf at a time in earnest (my chip runs, PR 42: without
            # these lines the allocator's peak is twice the state, 10.6
            # GB; the wait alone left it there). The runtime allocates a
            # result when its program is enqueued: wait for this leaf's
            # before the next. And the copy aliases nothing, so the
            # donation frees nothing by itself: the argument lives as
            # long as a reference to it, and the caller's pytree holds
            # one until every leaf is through.
            y = jax.block_until_ready(copy(x))
            if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
                x.delete()
            return y

        return one_leaf

    drop_axis = relayout(lambda x: x[0])        # [D, N, ...] -> [D * N, ...]
    add_axis = relayout(lambda x: x[None])      # and back

    def run(carry, key):
        out = jit_block(*carry, key)
        return out[:-1], out[-1]

    def init(state):
        fresh = (td.empty_ctx(w), td.empty_ctx(w)) + (
            (mon.create(),) if monitor else ())
        return (_map_tables(drop_axis, state),) + stack_on_mesh(mesh, fresh)

    def drain(carry):
        out = jit_drain(*carry, jax.random.PRNGKey(0))
        state = _map_tables(add_axis, out[0])
        if monitor:
            return state, out[2], out[1]
        return state, out[1]

    return run, init, drain
