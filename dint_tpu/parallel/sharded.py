"""Multi-chip sharding: partitioned keyspaces + device-side replication.

TPU re-expression of the reference's distribution machinery
(SURVEY.md §2.3): static hash sharding of the keyspace across 3 servers
(`shard = key % 3`, tatp/caladan/client_ebpf_shard.cc:636-641) and
primary-backup replication (every record on 3 servers; primary = key % n,
backups +1, +2; CommitLog -> all, CommitBck -> backups, CommitPrim ->
primary).

Here the "servers" are TPU devices on a `jax.sharding.Mesh` axis:

  * the keyspace is partitioned owner = key % n_shards; each device's engine
    state holds 3 *roles* of each of its dense rows — role 0 = rows it owns
    (primary), roles 1, 2 = replicas of devices d-1, d-2 — via the local
    index remap (key // n) * 3 + role. Sparse (hash) tables keep global keys
    and just size for 3/n of the keyspace.
  * clients route primary ops to the owner (host pre-bucketing, exactly like
    the reference client's per-shard batches).
  * replication happens ON DEVICE: after the primary step, commit records
    are forwarded to the +1/+2 neighbors with `ppermute` over ICI and applied
    there as backup installs — replacing the reference's client-driven
    CommitBck fan-out RTTs.
  * the per-step committed count is `psum`med across the mesh — the batched
    equivalent of 2PC vote collection.

Everything runs under `shard_map` over one jitted step; tested on a virtual
8-device CPU mesh (tests/conftest.py) and dry-run by the driver via
__graft_entry__.dryrun_multichip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engines import smallbank, tatp
from ..engines._memo import memoize_builder
from ..engines.types import Batch, Op, Replies
from ..ops import segments

I32 = jnp.int32
U32 = jnp.uint32

N_ROLES = 3
SHARD_AXIS = "shard"


def pcast_varying(x, *axes):
    """`jax.lax.pcast(x, axis, to="varying")` for each axis the value is
    not already varying over — needed under shard_map's varying-manual-
    axes typing when constants born inside the body must close a scan
    carry."""
    vma = jax.typeof(x).vma
    for ax in axes:
        if ax not in vma:
            x = jax.lax.pcast(x, ax, to="varying")
    return x

# engine registry: step fn + how many leading table ids are dense (and so
# need the device-local row remap). Any engine whose step is a pure
# (state, Batch) -> (state, Replies) over dense-indexed tables can shard.
ENGINES = {
    "tatp": (tatp.step, tatp.N_DENSE),
    "smallbank": (smallbank.step, 2),     # SAVINGS, CHECKING
}


def make_mesh(n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (SHARD_AXIS,))


def local_rows(n_global: int, n_shards: int) -> int:
    """Dense rows per device: 3 roles x ceil(n_global / n_shards)."""
    return N_ROLES * ((n_global + n_shards - 1) // n_shards)


def local_dense_key(global_key, n_shards: int, role: int):
    """Global dense key -> device-local row for the given replica role."""
    return (global_key // n_shards) * N_ROLES + role


_PRIM_TO_BCK = {Op.COMMIT_PRIM: Op.COMMIT_BCK, Op.INSERT_PRIM: Op.INSERT_BCK,
                Op.DELETE_PRIM: Op.DELETE_BCK}


def _as_backup_ops(op):
    out = jnp.full_like(op, Op.NOP)
    for src, dst in _PRIM_TO_BCK.items():
        out = jnp.where(op == src, dst, out)
    return out


def _remap_dense_keys(batch: Batch, n_shards: int, role: int,
                      n_dense: int) -> Batch:
    """Remap dense-table keys in a batch to this device's local rows."""
    is_dense = batch.table < n_dense
    lk = local_dense_key(batch.key_lo.astype(I32), n_shards, role)
    return batch.replace(key_lo=jnp.where(is_dense, lk.astype(U32), batch.key_lo))


def replicated_step(shard, batch: Batch, *, n_shards: int,
                    step_fn=tatp.step, n_dense: int = tatp.N_DENSE):
    """One multi-chip engine step, called inside shard_map.

    `batch` holds this device's primary-routed requests with GLOBAL keys.
    Builds one combined batch of [3w] lanes — primary lanes (role 0) plus
    the commit records ppermuted in from the two devices we back up
    (roles 1, 2) — and applies tatp.step ONCE. Safe to fuse because the
    three role views touch disjoint state: dense rows are disjoint by the
    role remap, and backup CF keys are owned by other devices (owner =
    key % n), so no (table, key) group spans roles. Psums the commit vote.
    Returns (shard', replies, global_committed).

    A single step instead of three keeps compile time ~1/3 of the unrolled
    form (the whole 5-table engine is traced once, not per role).
    """
    is_prim = ((batch.op == Op.COMMIT_PRIM) | (batch.op == Op.INSERT_PRIM)
               | (batch.op == Op.DELETE_PRIM))
    bck_op = _as_backup_ops(batch.op)
    parts = [_remap_dense_keys(batch, n_shards, 0, n_dense)]
    for off in (1, 2):
        perm = [(i, (i + off) % n_shards) for i in range(n_shards)]
        pp = functools.partial(jax.lax.ppermute, axis_name=SHARD_AXIS, perm=perm)
        fwd = Batch(op=pp(bck_op), table=pp(batch.table),
                    key_hi=pp(batch.key_hi), key_lo=pp(batch.key_lo),
                    val=pp(batch.val), ver=pp(batch.ver))
        # received records came from the device `off` behind us -> role `off`
        parts.append(_remap_dense_keys(fwd, n_shards, off, n_dense))

    combined = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)
    shard, rep = step_fn(shard, combined)
    replies = jax.tree.map(lambda x: x[: batch.width], rep)

    committed = jax.lax.psum(is_prim.sum().astype(I32), SHARD_AXIS)
    return shard, replies, committed


@memoize_builder
def build_sharded_step(mesh: Mesh, n_shards: int, engine: str = "tatp"):
    """jit(shard_map(replicated_step)) over stacked per-device state.

    State/batch arrays carry a leading [n_shards] device axis sharded over
    the mesh; inside shard_map each device sees its own [1, ...] block.
    `engine` picks the step fn + dense-table count from ENGINES.
    """
    step_fn, n_dense = ENGINES[engine]

    def squeeze(tree):
        return jax.tree.map(lambda x: x[0], tree)

    def unsqueeze(tree):
        return jax.tree.map(lambda x: x[None], tree)

    def local_fn(shard_blk, batch_blk):
        shard, replies, committed = replicated_step(
            squeeze(shard_blk), squeeze(batch_blk), n_shards=n_shards,
            step_fn=step_fn, n_dense=n_dense)
        return unsqueeze(shard), unsqueeze(replies), committed[None]

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                       out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)))
    return jax.jit(fn)


def stack_on_mesh(mesh: Mesh, tree):
    """One copy of `tree` per device of `mesh`: every leaf grows one
    leading axis per mesh axis and is sharded one slice per device. The
    broadcast is compiled with `out_shardings`, so each device fills its
    own slice and the stacked array never exists whole on one chip."""
    lead = mesh.devices.shape
    stack = jax.jit(
        lambda t: jax.tree.map(
            lambda x: jnp.broadcast_to(x, lead + x.shape), t),
        out_shardings=NamedSharding(mesh, P(*mesh.axis_names)))
    return stack(tree)


def create_sharded_state(mesh: Mesh, n_shards: int, n_subscribers: int,
                         val_words: int = 10, **kw) -> tatp.Shard:
    """Stacked per-device TATP state, device-local table sizes, sharded
    over the mesh (leading axis = device)."""
    rows = local_rows(n_subscribers + 1, n_shards)
    return stack_on_mesh(mesh,
                         tatp.create(rows - 1, val_words=val_words, **kw))


def create_sharded_smallbank(mesh: Mesh, n_shards: int, n_accounts: int,
                             val_words: int = 2, **kw) -> smallbank.Shard:
    """Stacked per-device SmallBank state (reference shards its 3 servers
    identically, smallbank/caladan/client_ebpf_shard.cc:287-289)."""
    rows = local_rows(n_accounts, n_shards)
    return stack_on_mesh(mesh,
                         smallbank.create(rows, val_words=val_words, **kw))


def route_batches(ops, tbls, keys, vals, vers, n_shards: int, width: int,
                  val_words: int):
    """Host-side: bucket flat request arrays by owner = key % n_shards into
    stacked [n_shards, width] Batches (the reference client's per-shard
    batch grouping, smallbank/caladan/client_ebpf_shard.cc:287-289).

    Skewed batches SPILL instead of crashing: requests beyond `width` for a
    device carry over into further waves (the reference client likewise
    retries over multiple RTTs rather than dying). Returns
    (waves: list of stacked Batch, owner [n]); every request appears in
    exactly one wave, at most `width` per device per wave."""
    from ..engines.types import make_batch

    owner = (np.asarray(keys, np.int64) % n_shards)
    per_dev = [np.nonzero(owner == d)[0] for d in range(n_shards)]
    n_waves = max(1, max((len(i) + width - 1) // width for i in per_dev))
    waves = []
    for wv in range(n_waves):
        parts = []
        for d in range(n_shards):
            idx = per_dev[d][wv * width:(wv + 1) * width]
            parts.append(make_batch(
                ops[idx], keys[idx].astype(np.uint64),
                vals[idx] if vals is not None else None,
                vers=vers[idx] if vers is not None else None,
                tables=tbls[idx], width=width, val_words=val_words))
        waves.append(jax.tree.map(lambda *xs: jnp.stack(xs), *parts))
    return waves, owner
