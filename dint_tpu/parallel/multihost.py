"""Multi-host dense TATP: DCN-aware replication over a (host, chip) mesh.

The reference's deployment is 3 SERVER MACHINES, each holding every
record once (primary for key%3==id, backup for the rest) — a machine
failure therefore loses at most one replica of any row
(smallbank/caladan/proto.h:62-66 ip_list; SURVEY.md §7 item 9). The 1-D
sharded runner (parallel/dense_sharded.py) reproduces the replication
math but places all 3 replicas on chips of ONE host — correct on a
single-host mesh, but its fault domains are chips, not machines.

This module is the multi-host design: a 2-D mesh with explicit axes

    DCN_AXIS ("dcn")  — hosts, connected over the data-center network;
    ICI_AXIS ("ici")  — chips within a host, connected over ICI.

Device (h, c) is primary for its own subscriber range (partition id
h * n_ici + c): transactions are device-local by construction, exactly
like dense_sharded (every TATP table keys by subscriber id,
tatp/caladan/tatp.h:28). The ONLY cross-device traffic is replication —
each step's install record is ppermuted to hosts h+1 and h+2 AT THE SAME
ICI COORDINATE (axis_name="dcn"), so:

  * the 3 replicas of every row live on 3 DIFFERENT HOSTS — the
    reference's fault-domain guarantee (CommitBck x2 + CommitLog x3,
    client_ebpf_shard.cc:779-860);
  * the expensive DCN hop carries only install records (~w x (VW+4)
    words per step), while everything bandwidth-hungry — table state,
    locks, workload generation, OCC validation — stays chip-local;
  * XLA lowers the "dcn" ppermute to cross-host collectives when the
    mesh spans real hosts (jax.distributed), and to ICI/in-memory
    permutes on a single-host or virtual mesh: the PROGRAM is identical,
    only the transport changes. Placement rule: the mesh's major axis
    must enumerate hosts so "dcn" is the slow axis (the scaling-book
    mesh recipe).

Host failure recovery: device (h, c)'s range rebuilds from its populate
snapshot + the log of surviving host (h+1, c) or (h+2, c), filtered by
the source tag (recovery.recover_tatp_dense key_hi_filter) — the
cross-HOST analogue of the cross-device story tested for dense_sharded.

Requires n_hosts >= 3 (with 2 hosts the +2 forward would alias the
source itself and double-log).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..engines import tatp_dense as td
from ..engines._memo import memoize_builder
from ..tables import log as logring
from .dense_sharded import (ShardState, _apply_backup, n_sub_local,
                            populate_local, ring_perm)
from .sharded import pcast_varying, stack_on_mesh

I32 = jnp.int32
U32 = jnp.uint32

DCN_AXIS = "dcn"
ICI_AXIS = "ici"


def mesh_shape_from_env(default: str = "4x2",
                        env: str = "DINT_BENCH_MESH") -> tuple[int, int]:
    """The bench/exp mesh-geometry knob: DINT_BENCH_MESH="HxC" (e.g.
    "3x2" = 3 hosts x 2 chips). Bench artifacts record the parsed shape
    next to n_shards so 2-D measurements are distinguishable from 1-D
    runs (which record mesh: null)."""
    import os
    spec = os.environ.get(env) or default
    try:
        h, c = (int(p) for p in spec.lower().replace("*", "x").split("x"))
    except ValueError as e:
        raise ValueError(f"{env}={spec!r}: expected 'HxC', e.g. '4x2'") \
            from e
    return h, c


def make_mesh_2d(n_hosts: int, chips_per_host: int) -> Mesh:
    """(host, chip) mesh. jax.devices() enumerates host-major under
    jax.distributed (process 0's chips first), so reshaping to
    [n_hosts, chips_per_host] puts the DCN boundary on the major axis —
    on a single-process virtual mesh this still validates program
    structure, with "dcn" hops degrading to local permutes."""
    devs = jax.devices()
    need = n_hosts * chips_per_host
    if len(devs) < need:
        raise ValueError(f"mesh {n_hosts}x{chips_per_host} needs {need} "
                         f"devices, have {len(devs)}")
    return Mesh(np.array(devs[:need]).reshape(n_hosts, chips_per_host),
                (DCN_AXIS, ICI_AXIS))


def create_multihost(mesh: Mesh, n_sub_global: int, val_words: int = 10,
                     seed: int = 0, **kw) -> ShardState:
    """Stacked per-device state [H, C, ...]: device (h, c) populates its
    own primary range on itself (the same partition streams as the 1-D
    dense_sharded.create_sharded), backup copies arrive from hosts h-1,
    h-2 at the same chip coordinate (a ppermute over the HOST axis)."""
    n_hosts, n_ici = mesh.devices.shape
    if n_hosts < 3:
        raise ValueError("multihost replication needs >= 3 hosts "
                         "(reference topology: 3 server machines)")
    n_loc = n_sub_local(n_sub_global, n_hosts * n_ici)

    def pull(x, off):       # host h gets host h-off's copy, same chip
        return jax.lax.ppermute(x, DCN_AXIS, ring_perm(n_hosts, off))

    def local():
        part = (jax.lax.axis_index(DCN_AXIS) * n_ici
                + jax.lax.axis_index(ICI_AXIS))
        one = populate_local(seed, part, n_loc, val_words, pull, **kw)
        return jax.tree.map(lambda x: x[None, None], one)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(),
                                 out_specs=P(DCN_AXIS, ICI_AXIS)))()


@memoize_builder
def build_multihost_runner(mesh: Mesh, n_sub_global: int, w: int = 4096,
                           val_words: int = 10,
                           cohorts_per_block: int = 8, mix=None):
    """jit(shard_map(scan(step))) over the 2-D mesh; same (run, init,
    drain) contract as dense_sharded.build_sharded_pipelined_runner, with
    the replication permute pinned to the DCN axis."""
    assert 2 * w <= (1 << td.K_ARB), f"w={w} exceeds the arb slot field"
    n_hosts, n_ici = mesh.devices.shape
    if n_hosts < 3:
        raise ValueError(
            f"n_hosts={n_hosts}: the replication permute pushes backups "
            "to hosts h+1 and h+2 along the dcn axis; with fewer than 3 "
            "hosts the +2 hop aliases the source host, so one failure "
            "would take a primary AND its second backup together")
    n_parts = n_hosts * n_ici
    n_loc = n_sub_local(n_sub_global, n_parts)
    n1 = td.n_rows(n_loc) + 1
    kw = dict(w=w, n_sub=n_loc, val_words=val_words)

    def local_step(state, c1, c2, key, gen_new=True):
        h = jax.lax.axis_index(DCN_AXIS)
        c = jax.lax.axis_index(ICI_AXIS)
        dev = h * n_ici + c               # global partition id
        db, new_ctx, c1, stats, inst = td.pipe_step(
            state.db, c1, c2, jax.random.fold_in(key, dev), mix=mix,
            gen_new=gen_new, emit_installs=True, **kw)
        state = state.replace(db=db)

        new_ctx, c1 = jax.tree.map(
            lambda x: pcast_varying(x, DCN_AXIS, ICI_AXIS), (new_ctx, c1))
        # CommitBck + CommitLog fan-out: forward installs to hosts h+1,
        # h+2 at the same chip — the only DCN traffic in the program
        for off in (1, 2):
            fwd = jax.tree.map(functools.partial(
                jax.lax.ppermute, axis_name=DCN_AXIS,
                perm=ring_perm(n_hosts, off)), inst)
            src_dev = ((h - off) % n_hosts) * n_ici + c
            state = _apply_backup(state, fwd, off - 1, n1, val_words,
                                  src_dev)
        return state, new_ctx, c1, jax.lax.psum(
            jax.lax.psum(stats, DCN_AXIS), ICI_AXIS)

    def scan_fn(carry, key, gen_new=True):
        state, c1, c2 = carry
        state, new_ctx, c1, stats = local_step(state, c1, c2, key, gen_new)
        return (state, new_ctx, c1), stats

    def sq(tree):
        return jax.tree.map(lambda x: x[0, 0], tree)

    def unsq(tree):
        return jax.tree.map(lambda x: x[None, None], tree)

    def block_local(state_blk, c1_blk, c2_blk, key):
        state0 = sq(state_blk)
        db = jax.lax.cond(state0.db.step >= jnp.uint32(td.REBASE_AT),
                          td.rebase_stamps, lambda d: d, state0.db)
        keys = jax.random.split(key, cohorts_per_block)
        carry, stats = jax.lax.scan(
            scan_fn, (state0.replace(db=db), sq(c1_blk), sq(c2_blk)), keys)
        state, c1, c2 = carry
        return unsq(state), unsq(c1), unsq(c2), stats

    def drain_local(state_blk, c1_blk, c2_blk, key):
        carry = (sq(state_blk), sq(c1_blk), sq(c2_blk))
        carry, s1 = scan_fn(carry, key, gen_new=False)
        carry, s2 = scan_fn(carry, jax.random.fold_in(key, 1),
                            gen_new=False)
        state, _, _ = carry
        return unsq(state), jnp.stack([s1, s2])

    grid = P(DCN_AXIS, ICI_AXIS)
    spec = (grid, grid, grid, P())
    block = jax.shard_map(block_local, mesh=mesh, in_specs=spec,
                          out_specs=(grid, grid, grid, P()))
    drain_m = jax.shard_map(drain_local, mesh=mesh, in_specs=spec,
                            out_specs=(grid, P()))

    jit_block = jax.jit(block, donate_argnums=(0, 1, 2))
    jit_drain = jax.jit(drain_m, donate_argnums=(0, 1, 2))

    def run(carry, key):
        state, c1, c2 = carry
        state, c1, c2, stats = jit_block(state, c1, c2, key)
        return (state, c1, c2), stats

    def init(state):
        return (state,) + stack_on_mesh(
            mesh, (td.empty_ctx(w), td.empty_ctx(w)))

    def drain(carry):
        state, c1, c2 = carry
        return jit_drain(state, c1, c2, jax.random.PRNGKey(0))

    return run, init, drain
