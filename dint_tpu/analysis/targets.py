"""dintlint target registry: every engine/sharded step function we lint.

A target is a named thunk that builds ONE hot-path function plus example
arguments at tiny geometry and hands both to `core.trace_target`. State is
constructed ABSTRACTLY (`jax.eval_shape` around the real builders, so the
shapes can never drift from production code) and tracing uses abstract
values only — no buffers, no device programs, the whole registry runs on
CPU in seconds. The jaxpr of a w=16 step is the same eqn stream as the
production w=8192 one.

Coverage contract (ANALYSIS.md): every production entry point that bench.py
or exp.py can dispatch appears here — both dense engines, the dense
pipeline drain, both generic fused pipelines, the generic replicated
shard step, and both dense multi-chip runners. The ``@mon`` variants
re-register every dintmon-instrumented step with the counter plane
threaded (OBSERVABILITY.md): the counter scatter-adds must themselves
pass scatter_race.

Mesh targets need >= `_MESH_SHARDS` devices; the dintlint CLI forces an
8-device virtual CPU topology exactly like tests/conftest.py, and targets
raise `SkipTarget` (reported, never fatal) when the topology cannot host
them.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .core import TargetTrace, TraceCache, trace_target

U32 = jnp.uint32

_MESH_SHARDS = 4
# tiny-geometry knobs shared by all builders: shapes don't change the eqn
# stream, only trace time and memory
_N_SUB = 32
_N_ACCT = 64
_W = 16
_BLK = 2
_VW = 4
_LOGCAP = 128

TARGETS: dict[str, Callable[[], TargetTrace]] = {}
TARGET_DOCS: dict[str, str] = {}
# static cost meta per target (analysis/cost.py; enforced fail-closed by
# passes/cost_budget.py — see the budget ledger at the bottom of this
# module and ANALYSIS.md "Static cost model"):
#   steps        engine steps per trace (block/drain targets trace _BLK)
#   geom         geometry vars for waves.py formulas + budget formulas
#   wave_expect  documented per-target layout deviations from the base
#                formula (number = scale, string = replacement formula)
#   budget       {"dispatches": int, "bytes": formula|int, "footprint":
#                 int} — per-step ceilings
TARGET_COST: dict[str, dict] = {}
# protocol flags per target (core.TargetTrace.protocol; gates the checks
# in passes/protocol.py): "certified" = the engine closes the
# lock/validate/install loop inside the trace; "occ" = installs must
# also descend from the validate compare; "replicated" = ICI replication
# must push AND land; "drain" = installs boundary cohorts certified in
# the block trace (only abort-implies-unlock applies); "server" = the
# client owns protocol sequencing (clients/tatp_client.py), so only
# replication is checkable in-trace.
TARGET_PROTOCOL: dict[str, tuple[str, ...]] = {}


class SkipTarget(Exception):
    """Raised by a builder whose prerequisites (device count) are absent."""


def register_target(name: str, doc: str,
                    protocol: tuple[str, ...] = ("certified",),
                    cost: dict | None = None):
    def deco(fn):
        TARGETS[name] = fn
        TARGET_DOCS[name] = doc
        TARGET_PROTOCOL[name] = tuple(protocol)
        if cost is not None:
            TARGET_COST[name] = dict(cost)
        return fn
    return deco


def _abstract(thunk):
    """Run a state builder under eval_shape: the production constructor
    defines the shapes, but no buffer is allocated and no device program
    runs (device_put inside the builders becomes a no-op on tracers)."""
    return jax.eval_shape(thunk)


def _key_aval():
    return jax.ShapeDtypeStruct((2,), jnp.uint32)


def _occ_aval():
    # dintserve per-cohort occupancy / shed vectors: one i32 per step of
    # the block scan (engines' serve=True run signature)
    return jax.ShapeDtypeStruct((_BLK,), jnp.int32)


def _mesh(n: int):
    if len(jax.devices()) < n:
        raise SkipTarget(
            f"needs {n} devices, have {len(jax.devices())} — run under "
            "an 8-device virtual CPU topology (tools/dintlint.py does)")
    from ..parallel.sharded import make_mesh
    return make_mesh(n)


def _mesh2d(n_hosts: int, n_ici: int):
    if len(jax.devices()) < n_hosts * n_ici:
        raise SkipTarget(
            f"needs {n_hosts * n_ici} devices for a {n_hosts}x{n_ici} "
            f"mesh, have {len(jax.devices())} — run under an 8-device "
            "virtual CPU topology (tools/dintlint.py does)")
    from ..parallel.multihost import make_mesh_2d
    return make_mesh_2d(n_hosts, n_ici)


# hierarchical 2-D targets -> their flat-collective twin on the SAME
# mesh: passes/cost_budget.py fails hier-dcn-dominance unless the
# hierarchical route derives STRICTLY fewer DCN-axis link bytes than
# the flat lowering at every calibrated geometry (ISSUE 11's gate)
TARGET_FLAT_TWIN: dict[str, str] = {}

# double-buffered (overlap=True) serve targets -> their unoverlapped
# twin on the SAME mesh/width: passes/cost_budget.py fails
# overlap-dcn-parity unless the overlapped route schedules NO MORE
# DCN-axis link bytes per step than the twin (overlap must hide the
# exchange under the lock wave, not inflate it), and overlap-footprint
# unless the overlapped carry grows by at most the priced double buffer
# (OVERLAP_FOOTPRINT below) over the twin's footprint (round-18 gate).
TARGET_OVERLAP_TWIN: dict[str, str] = {}

# the in-flight prefetch buffer the overlap path carries per device:
# routed op + row-loc bucket planes (2 x i32[d*cap] with
# cap = 2*ceil(w*l/d)) plus the replayed source (key u32[2] + occ i32,
# 12 B); global bytes = d x per-device
OVERLAP_FOOTPRINT = "d*(8*d*(2*((w*l+d-1)//d)) + 12)"


# ------------------------------------------------------------ dense TATP


def _tatp_dense(name: str, monitor: bool = False,
                trace: bool = False,
                serve: bool = False) -> TargetTrace:
    from ..engines import tatp_dense as td
    from .. import monitor as mn
    from ..monitor import txnevents as txe
    run, init, _ = td.build_pipelined_runner(_N_SUB, w=_W, val_words=_VW,
                                             cohorts_per_block=_BLK,
                                             monitor=monitor, trace=trace,
                                             serve=serve)
    carry = _abstract(
        lambda: (td.create(_N_SUB, val_words=_VW,
                           log_capacity=_LOGCAP),
                 td.empty_ctx(_W), td.empty_ctx(_W))
        + ((txe.create_ring(init.trace_cfg.cap),) if trace else ())
        + ((mn.create(),) if monitor else ()))
    args = (carry, _key_aval())
    if serve:
        args += (_occ_aval(), _occ_aval())
    return trace_target(name, run, args)


@register_target("tatp_dense/block",
                 "flagship dense TATP fused 3-wave pipeline",
                 protocol=('certified', 'occ'))
def _t_tatp_dense() -> TargetTrace:
    return _tatp_dense("tatp_dense/block")


@register_target("tatp_dense/block@mon",
                 "dense TATP with the dintmon counter plane threaded",
                 protocol=('certified', 'occ'))
def _t_tatp_dense_mon() -> TargetTrace:
    return _tatp_dense("tatp_dense/block@mon", monitor=True)


@register_target("tatp_dense/drain",
                 "dense TATP pipeline drain (gen_new=False tail steps)",
                 protocol=('drain',))
def _t_tatp_dense_drain() -> TargetTrace:
    from ..engines import tatp_dense as td
    drain = td.build_pipelined_runner(_N_SUB, w=_W, val_words=_VW,
                                      cohorts_per_block=_BLK)[2]
    carry = _abstract(lambda: (td.create(_N_SUB, val_words=_VW,
                                         log_capacity=_LOGCAP),
                               td.empty_ctx(_W), td.empty_ctx(_W)))
    return trace_target("tatp_dense/drain", drain, (carry,))


# ------------------------------------------------------- dense SmallBank


def _sb_dense(name: str, monitor: bool = False,
              trace: bool = False,
              serve: bool = False) -> TargetTrace:
    from ..engines import smallbank_dense as sd
    run, init, _ = sd.build_pipelined_runner(_N_ACCT, w=_W,
                                             cohorts_per_block=_BLK,
                                             monitor=monitor, trace=trace,
                                             serve=serve)
    carry = _abstract(lambda: init(sd.create(_N_ACCT,
                                             log_capacity=_LOGCAP)))
    args = (carry, _key_aval())
    if serve:
        args += (_occ_aval(), _occ_aval())
    return trace_target(name, run, args)


@register_target("smallbank_dense/block",
                 "dense SmallBank fused 2-wave pipeline",
                 protocol=('certified',))
def _t_sb_dense() -> TargetTrace:
    return _sb_dense("smallbank_dense/block")


@register_target("smallbank_dense/block@mon",
                 "dense SmallBank with the dintmon counter plane threaded",
                 protocol=('certified',))
def _t_sb_dense_mon() -> TargetTrace:
    return _sb_dense("smallbank_dense/block@mon", monitor=True)


# ---------------------------------------------------- generic pipelines


def _tatp_pipeline(name: str, monitor: bool = False) -> TargetTrace:
    from ..engines import tatp
    from ..engines import tatp_pipeline as tp
    run, init, _ = tp.build_pipelined_runner(_N_SUB, w=_W, val_words=_VW,
                                             cohorts_per_block=_BLK,
                                             monitor=monitor)
    # same shapes as tatp_client.populate_shards (N_SHARDS identical
    # replicas of tatp.create's geometry), no host-numpy population cost
    carry = _abstract(lambda: init(tp.stack_shards(
        [tatp.create(_N_SUB, val_words=_VW, cf_buckets=256,
                     cf_lock_slots=256) for _ in range(tp.N_SHARDS)])))
    return trace_target(name, run, (carry, _key_aval()))


@register_target("tatp_pipeline/block",
                 "generic (sort-based) fused TATP pipeline",
                 protocol=('certified', 'occ'))
def _t_tatp_pipeline() -> TargetTrace:
    return _tatp_pipeline("tatp_pipeline/block")


@register_target("tatp_pipeline/block@mon",
                 "generic TATP pipeline with the counter plane threaded",
                 protocol=('certified', 'occ'))
def _t_tatp_pipeline_mon() -> TargetTrace:
    return _tatp_pipeline("tatp_pipeline/block@mon", monitor=True)


def _sb_pipeline(name: str, monitor: bool = False) -> TargetTrace:
    from ..engines import smallbank_pipeline as sp
    from .. import monitor as mn
    run = sp.build_runner(_N_ACCT, w=_W, cohorts_per_block=_BLK,
                          monitor=monitor)
    stacked = _abstract(lambda: sp.create_stacked(_N_ACCT))
    carry = (stacked, _abstract(mn.create)) if monitor else stacked
    return trace_target(name, run, (carry, _key_aval()))


@register_target("smallbank_pipeline/block",
                 "generic (sort-based) fused SmallBank pipeline",
                 protocol=('certified',))
def _t_sb_pipeline() -> TargetTrace:
    return _sb_pipeline("smallbank_pipeline/block")


@register_target("smallbank_pipeline/block@mon",
                 "generic SmallBank pipeline with the counter plane",
                 protocol=('certified',))
def _t_sb_pipeline_mon() -> TargetTrace:
    return _sb_pipeline("smallbank_pipeline/block@mon", monitor=True)


# ------------------------------------------------------- generic sharded


def _generic_sharded(name: str, engine: str) -> TargetTrace:
    from ..engines.types import Op
    from ..parallel import sharded
    mesh = _mesh(_MESH_SHARDS)
    if engine == "tatp":
        from ..engines import tatp
        state = _abstract(lambda: sharded.create_sharded_state(
            mesh, _MESH_SHARDS, _N_SUB, val_words=_VW, cf_buckets=256,
            cf_lock_slots=256))
        tbl = tatp.SUBSCRIBER
        vw = _VW
    else:
        from ..engines import smallbank
        state = _abstract(lambda: sharded.create_sharded_smallbank(
            mesh, _MESH_SHARDS, _N_ACCT, val_words=2))
        tbl = smallbank.SAVINGS
        vw = 2
    step = sharded.build_sharded_step(mesh, _MESH_SHARDS, engine=engine)
    m = 8
    keys = np.arange(1, m + 1, dtype=np.int64)
    ops = np.full(m, Op.OCC_LOCK, np.int32)
    tbls = np.full(m, tbl, np.int32)
    (batch,), _ = sharded.route_batches(ops, tbls, keys, None, None,
                                        _MESH_SHARDS, m, vw)
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
        batch)
    return trace_target(name, step, (state, batch),
                        mesh_axes=(sharded.SHARD_AXIS,))


@register_target("sharded/tatp",
                 "generic replicated TATP shard step (3-role shard_map)",
                 protocol=('server', 'replicated'))
def _t_sharded_tatp() -> TargetTrace:
    return _generic_sharded("sharded/tatp", "tatp")


@register_target("sharded/smallbank",
                 "generic replicated SmallBank shard step",
                 protocol=('server', 'replicated'))
def _t_sharded_sb() -> TargetTrace:
    return _generic_sharded("sharded/smallbank", "smallbank")


# --------------------------------------------------- dense multi-chip


def _dense_sharded(name: str, monitor: bool = False) -> TargetTrace:
    from ..parallel import dense_sharded as ds
    mesh = _mesh(_MESH_SHARDS)
    run, init, _ = ds.build_sharded_pipelined_runner(
        mesh, _MESH_SHARDS, _N_SUB * _MESH_SHARDS, w=_W, val_words=_VW,
        cohorts_per_block=_BLK, monitor=monitor)
    carry = _abstract(lambda: init(ds.create_sharded(
        mesh, _MESH_SHARDS, _N_SUB * _MESH_SHARDS, val_words=_VW,
        log_capacity=_LOGCAP)))
    return trace_target(name, run, (carry, _key_aval()),
                        mesh_axes=(ds.SHARD_AXIS,))


@register_target("dense_sharded/block",
                 "multi-chip dense TATP: shard_map pipeline + CommitBck "
                 "ppermute fan-out",
                 protocol=('certified', 'occ', 'replicated'))
def _t_dense_sharded() -> TargetTrace:
    return _dense_sharded("dense_sharded/block")


@register_target("dense_sharded/block@mon",
                 "multi-chip dense TATP with per-device counter planes",
                 protocol=('certified', 'occ', 'replicated'))
def _t_dense_sharded_mon() -> TargetTrace:
    return _dense_sharded("dense_sharded/block@mon", monitor=True)


def _dense_sharded_sb(name: str, monitor: bool = False,
                      trace: bool = False) -> TargetTrace:
    from ..parallel import dense_sharded_sb as dsb
    mesh = _mesh(_MESH_SHARDS)
    run, init, _ = dsb.build_sharded_sb_runner(
        mesh, _MESH_SHARDS, _N_ACCT * _MESH_SHARDS, w=_W,
        cohorts_per_block=_BLK, monitor=monitor, trace=trace)
    carry = _abstract(lambda: init(dsb.create_sharded_sb(
        mesh, _MESH_SHARDS, _N_ACCT * _MESH_SHARDS)))
    return trace_target(name, run, (carry, _key_aval()),
                        mesh_axes=(dsb.AXIS,))


@register_target("dense_sharded_sb/block",
                 "multi-chip dense SmallBank: owner-routed shard_map step",
                 protocol=('certified', 'replicated'))
def _t_dense_sharded_sb() -> TargetTrace:
    return _dense_sharded_sb("dense_sharded_sb/block")


@register_target("dense_sharded_sb/block@mon",
                 "multi-chip dense SmallBank with per-device counter "
                 "planes",
                 protocol=('certified', 'replicated'))
def _t_dense_sharded_sb_mon() -> TargetTrace:
    return _dense_sharded_sb("dense_sharded_sb/block@mon", monitor=True)


# ------------------------------------------- round-14 2-D (dcn x ici)
# The multi-host cross-shard SmallBank step (parallel/multihost_sb.py)
# and the existing multi-host TATP runner (parallel/multihost.py), both
# over explicit (dcn, ici) mesh axes. The @flat twins lower the SAME
# step with flat tuple-axis all_to_all collectives; cost_budget's
# hier-dcn-dominance check (TARGET_FLAT_TWIN above) proves the
# hierarchical route schedules strictly fewer bytes on the DCN axis.
# Two calibrated geometries: 4x2 (the conftest topology's widest
# >=3-host mesh) and 3x2 (the reference's 3-machine deployment shape).


def _multihost_sb(name: str, n_hosts: int, n_ici: int,
                  hierarchical: bool = True,
                  monitor: bool = False,
                  trace: bool = False,
                  serve: bool = False,
                  overlap: bool = False) -> TargetTrace:
    from ..parallel import multihost_sb as mhs
    mesh = _mesh2d(n_hosts, n_ici)
    d = n_hosts * n_ici
    run, init, _ = mhs.build_multihost_sb_runner(
        mesh, _N_ACCT * d, w=_W, cohorts_per_block=_BLK,
        hierarchical=hierarchical, monitor=monitor, trace=trace,
        serve=serve, overlap=overlap)
    carry = _abstract(lambda: init(mhs.create_multihost_sb(
        mesh, _N_ACCT * d)))
    args = (carry, _key_aval())
    if serve:
        # mesh serve signature: per-(host, chip, cohort) occ/shed arrays
        a = jax.ShapeDtypeStruct((n_hosts, n_ici, _BLK), jnp.int32)
        args += (a, a)
    return trace_target(name, run, args,
                        mesh_axes=(mhs.DCN_AXIS, mhs.ICI_AXIS))


@register_target("multihost_sb/block",
                 "2-D multi-host cross-shard SmallBank: hierarchical "
                 "(ici-then-dcn) routing, host fault-domain replication",
                 protocol=('certified', 'replicated'))
def _t_multihost_sb() -> TargetTrace:
    return _multihost_sb("multihost_sb/block", 4, 2)


@register_target("multihost_sb/block@flat",
                 "2-D multi-host SmallBank lowered with flat tuple-axis "
                 "all_to_all (the hier-dcn-dominance baseline twin)",
                 protocol=('certified', 'replicated'))
def _t_multihost_sb_flat() -> TargetTrace:
    return _multihost_sb("multihost_sb/block@flat", 4, 2,
                         hierarchical=False)


@register_target("multihost_sb/block@mon",
                 "2-D multi-host SmallBank with the per-device counter "
                 "plane (incl. the route_ici/route_dcn per-axis split)",
                 protocol=('certified', 'replicated'))
def _t_multihost_sb_mon() -> TargetTrace:
    return _multihost_sb("multihost_sb/block@mon", 4, 2, monitor=True)


@register_target("multihost_sb/block@h3",
                 "2-D multi-host SmallBank at the reference's 3-machine "
                 "shape (3x2 mesh), hierarchical routing",
                 protocol=('certified', 'replicated'))
def _t_multihost_sb_h3() -> TargetTrace:
    return _multihost_sb("multihost_sb/block@h3", 3, 2)


@register_target("multihost_sb/block@h3+flat",
                 "3x2 multi-host SmallBank with flat tuple-axis "
                 "collectives (dominance twin of @h3)",
                 protocol=('certified', 'replicated'))
def _t_multihost_sb_h3_flat() -> TargetTrace:
    return _multihost_sb("multihost_sb/block@h3+flat", 3, 2,
                         hierarchical=False)


TARGET_FLAT_TWIN.update({
    "multihost_sb/block": "multihost_sb/block@flat",
    "multihost_sb/block@mon": "multihost_sb/block@flat",
    "multihost_sb/block@h3": "multihost_sb/block@h3+flat",
})


@register_target("multihost/block",
                 "2-D multi-host dense TATP: device-local pipeline + "
                 "dcn-axis CommitBck/CommitLog fan-out (host fault "
                 "domains)",
                 protocol=('certified', 'occ', 'replicated'))
def _t_multihost() -> TargetTrace:
    from ..parallel import multihost as mhost
    mesh = _mesh2d(4, 2)
    run, init, _ = mhost.build_multihost_runner(
        mesh, _N_SUB * 8, w=_W, val_words=_VW, cohorts_per_block=_BLK)
    carry = _abstract(lambda: init(mhost.create_multihost(
        mesh, _N_SUB * 8, val_words=_VW, log_capacity=_LOGCAP)))
    return trace_target("multihost/block", run, (carry, _key_aval()),
                        mesh_axes=(mhost.DCN_AXIS, mhost.ICI_AXIS))


# ------------------------------------------------ flight recorder (@trace)
# The dinttrace event ring (monitor/txnevents.py) threaded through each
# instrumented engine at full sampling rate. The ring update is a single
# provably-unique-index scatter-add (unselected lanes spill to distinct
# OOB rows dropped by mode="drop"), so the variants pass certification,
# OCC, and replication checks unchanged; dintcost prices the ring
# traffic through the per-family "trace" wave rows in monitor/waves.py.


@register_target("tatp_dense/block@trace",
                 "dense TATP with the dinttrace flight-recorder ring "
                 "(lock/validate/install/outcome events, full rate)",
                 protocol=('certified', 'occ'))
def _t_tatp_dense_trace() -> TargetTrace:
    return _tatp_dense("tatp_dense/block@trace", trace=True)


@register_target("smallbank_dense/block@trace",
                 "dense SmallBank with the dinttrace flight-recorder "
                 "ring (lock/install/outcome events, full rate)",
                 protocol=('certified',))
def _t_sb_dense_trace() -> TargetTrace:
    return _sb_dense("smallbank_dense/block@trace", trace=True)


@register_target("dense_sharded_sb/block@trace",
                 "multi-chip dense SmallBank with the dinttrace ring: "
                 "txn ids ride the lock/install routes so owner-side "
                 "events join into cross-shard span trees",
                 protocol=('certified', 'replicated'))
def _t_dense_sharded_sb_trace() -> TargetTrace:
    return _dense_sharded_sb("dense_sharded_sb/block@trace", trace=True)


@register_target("multihost_sb/block@trace",
                 "2-D multi-host SmallBank with the dinttrace ring: "
                 "route events carry the dcn-hop tag, replication "
                 "events land on both fault-domain hops",
                 protocol=('certified', 'replicated'))
def _t_multihost_sb_trace() -> TargetTrace:
    return _multihost_sb("multihost_sb/block@trace", 4, 2, trace=True)


# --------------------------------------------- dintserve serving plane
# The serve-mode blocks (round 17): the same dense pipelines with the
# variable-occupancy mask + serve counter bumps. Registered from day one
# so every standing gate — purity (dintlint), conservation (dintproof),
# durability (dintdur, via the family loop below), and the static cost
# ledger (dintcost rows at the bottom) — prices the serving path exactly
# like the closed-loop path it masks.


@register_target("tatp_dense/serve",
                 "dense TATP serve-mode block: variable-occupancy mask "
                 "over the fused 3-wave pipeline (dintserve steady state)",
                 protocol=('certified', 'occ'))
def _t_tatp_dense_serve() -> TargetTrace:
    return _tatp_dense("tatp_dense/serve", serve=True)


@register_target("tatp_dense/serve@mon",
                 "dense TATP serve-mode block with the counter plane: "
                 "occupancy/padded/shed lanes land on the device ledger",
                 protocol=('certified', 'occ'))
def _t_tatp_dense_serve_mon() -> TargetTrace:
    return _tatp_dense("tatp_dense/serve@mon", monitor=True, serve=True)


@register_target("smallbank_dense/serve",
                 "dense SmallBank serve-mode block: variable-occupancy "
                 "lock-slot mask over the 2-wave pipeline",
                 protocol=('certified',))
def _t_sb_dense_serve() -> TargetTrace:
    return _sb_dense("smallbank_dense/serve", serve=True)


@register_target("smallbank_dense/serve@mon",
                 "dense SmallBank serve-mode block with the counter "
                 "plane: occupancy/padded/shed lanes on the ledger",
                 protocol=('certified',))
def _t_sb_dense_serve_mon() -> TargetTrace:
    return _sb_dense("smallbank_dense/serve@mon", monitor=True, serve=True)


# --------------------------------------- dintmesh serving plane (round 18)
# The mesh-wide serve-mode blocks: the round-14 2-D cross-shard step in
# the serve=True cohort form (per-(host, chip, cohort) occupancy mask +
# serve counter bumps) that serve/mesh.py's MeshServeEngine drives. The
# @overlap variants serve through the double-buffered route (cohort
# i+1's exchange issued under cohort i's owner waves); they keep the
# full protocol flags because the runner pins them bit-identical to the
# unoverlapped route, and cost_budget's overlap-dcn-parity /
# overlap-footprint checks (TARGET_OVERLAP_TWIN above) price exactly
# what the overlap costs BEFORE any hardware run.


@register_target("multihost_sb/serve",
                 "2-D mesh serve-mode block: variable-occupancy mask "
                 "over the hierarchical cross-shard step (dintmesh "
                 "steady state)",
                 protocol=('certified', 'replicated'))
def _t_multihost_sb_serve() -> TargetTrace:
    return _multihost_sb("multihost_sb/serve", 4, 2, serve=True)


@register_target("multihost_sb/serve@flat",
                 "2-D mesh serve-mode block lowered with flat tuple-axis "
                 "all_to_all (dominance twin of the serve family)",
                 protocol=('certified', 'replicated'))
def _t_multihost_sb_serve_flat() -> TargetTrace:
    return _multihost_sb("multihost_sb/serve@flat", 4, 2,
                         hierarchical=False, serve=True)


@register_target("multihost_sb/serve@mon",
                 "2-D mesh serve-mode block with the counter plane: "
                 "occupancy/padded/shed lanes + the per-axis route split "
                 "on every device ledger",
                 protocol=('certified', 'replicated'))
def _t_multihost_sb_serve_mon() -> TargetTrace:
    return _multihost_sb("multihost_sb/serve@mon", 4, 2, monitor=True,
                         serve=True)


@register_target("multihost_sb/serve@overlap",
                 "2-D mesh serve-mode block with the double-buffered "
                 "route: cohort i+1's ici-then-dcn exchange issued under "
                 "cohort i's owner waves (bit-identical pin vs @serve)",
                 protocol=('certified', 'replicated'))
def _t_multihost_sb_serve_overlap() -> TargetTrace:
    return _multihost_sb("multihost_sb/serve@overlap", 4, 2, serve=True,
                         overlap=True)


@register_target("multihost_sb/serve@overlap+mon",
                 "double-buffered mesh serve block with the counter "
                 "plane (route_prefetch_lanes lands on the ledger)",
                 protocol=('certified', 'replicated'))
def _t_multihost_sb_serve_overlap_mon() -> TargetTrace:
    return _multihost_sb("multihost_sb/serve@overlap+mon", 4, 2,
                         monitor=True, serve=True, overlap=True)


TARGET_FLAT_TWIN.update({
    "multihost_sb/serve": "multihost_sb/serve@flat",
    "multihost_sb/serve@mon": "multihost_sb/serve@flat",
    "multihost_sb/serve@overlap": "multihost_sb/serve@flat",
})

TARGET_OVERLAP_TWIN.update({
    "multihost_sb/serve@overlap": "multihost_sb/serve",
    "multihost_sb/serve@overlap+mon": "multihost_sb/serve@mon",
})


# ------------------------------------------------- durability (dintdur)
# Every engine family that owns replicated log rings declares 'durable':
# passes/durability.py then proves log-before-visible ordering, replica
# quorum placement, and ring bounds on its trace. The generic pipelines
# and sharded/* servers keep no local rings (the reference's log server
# is a separate role there), so they stay un-flagged. The loop keeps the
# flag in lockstep with future variants of the same families.

_DURABLE_FAMILIES = ("tatp_dense/", "smallbank_dense/", "dense_sharded/",
                     "dense_sharded_sb/", "multihost_sb/", "multihost/")

for _name in list(TARGET_PROTOCOL):
    if _name.startswith(_DURABLE_FAMILIES):
        TARGET_PROTOCOL[_name] = TARGET_PROTOCOL[_name] + ("durable",)
del _name


# ---------------------------------------------- recovery replay targets
# The traceable jnp twins of recovery.py's numpy paths (same winner-per-
# row rule; recovery.py module docstring). Registered so dintdur's
# replay-coverage check can statically compare what the engines install
# against what replay reconstructs, and which log columns replay reads
# against the entry layout the engines populate. The 'replay' flag gates
# the replay-side checks in passes/durability.py.

# engine target -> its replay twin: durability proves the twin's
# entries-derived outputs cover every table class the engine installs
REPLAY_TWINS: dict[str, str] = {
    "tatp_dense/block": "recovery/tatp_dense",
    "smallbank_dense/block": "recovery/smallbank_dense",
}
# entry-layout spec per replay target: `val_words` is the populated
# value-word count (columns [HDR, HDR+val_words) of the ring; anything
# past that is never written by the engines — the overread arm)
REPLAY_SPECS: dict[str, dict] = {
    "recovery/tatp_dense": dict(val_words=_VW),
    "recovery/smallbank_dense": dict(val_words=2),
    "recovery/sb_shard": dict(val_words=2),
}


def _ring_avals(lanes: int, capacity: int, val_words: int):
    from ..tables.log import HDR_WORDS
    return (jax.ShapeDtypeStruct((lanes, capacity,
                                  HDR_WORDS + val_words), U32),
            jax.ShapeDtypeStruct((lanes,), U32))


@register_target("recovery/tatp_dense",
                 "traceable replay twin of recovery.recover_tatp_dense: "
                 "rebuild val+meta from one surviving replica ring",
                 protocol=('replay',))
def _t_recovery_tatp() -> TargetTrace:
    from .. import recovery
    from ..engines import tatp_dense as td
    db0 = _abstract(lambda: td.create(_N_SUB, val_words=_VW,
                                      log_capacity=_LOGCAP))
    entries, heads = _ring_avals(db0.log.lanes, db0.log.capacity, _VW)
    return trace_target("recovery/tatp_dense",
                        recovery.replay_tatp_dense, (db0, entries, heads))


@register_target("recovery/smallbank_dense",
                 "traceable replay twin of recovery."
                 "recover_smallbank_dense: balances + resumed step",
                 protocol=('replay',))
def _t_recovery_sb() -> TargetTrace:
    from .. import recovery
    from ..engines import smallbank_dense as sd
    db0 = _abstract(lambda: sd.create(_N_ACCT, log_capacity=_LOGCAP))
    entries, heads = _ring_avals(db0.log.lanes, db0.log.capacity, 2)
    return trace_target("recovery/smallbank_dense",
                        recovery.replay_smallbank_dense,
                        (db0, entries, heads))


@register_target("recovery/sb_shard",
                 "traceable replay twin of recovery.recover_sb_shard: a "
                 "dead device's primary balance range from any one ring",
                 protocol=('replay',))
def _t_recovery_sb_shard() -> TargetTrace:
    import functools

    from .. import recovery
    from ..parallel.dense_sharded_sb import m1_local
    bal0 = jax.ShapeDtypeStruct(
        (m1_local(_N_ACCT * _MESH_SHARDS, _MESH_SHARDS),), U32)
    entries, heads = _ring_avals(16, _LOGCAP, 2)
    fn = functools.partial(recovery.replay_sb_shard, dead=1,
                           n_shards=_MESH_SHARDS)
    return trace_target("recovery/sb_shard", fn, (bal0, entries, heads))


# -------------------------------------------------- static cost budgets
#
# The dintcost ledger (analysis/cost.py, gated by passes/cost_budget.py).
# Geometry mirrors the tiny-trace knobs above and pins the engine
# constants the waves.py formulas assume (tatp_pipeline.READ_LANES = 2,
# smallbank_pipeline.L = 3 / .VW = 2 — tests/test_dintcost.py
# cross-checks them against the engine modules). Budgets are ceilings
# calibrated once against the derivation at this geometry: dispatches
# and footprint are exact (ANY extra dispatch or dropped donation
# regresses them), bytes allow 25% over the declared waves.py ledger —
# the same band reconciliation uses. Recalibrate with
# `python tools/dintcost.py report <target>` and justify the diff in
# the PR; silence a reviewed exception via the dintlint allowlist.

_TD_GEOM = dict(w=_W, r=2, vw=_VW)
_SB_GEOM = dict(w=_W, l=3, vw=2)
_DS_GEOM = dict(w=_W, r=2, vw=_VW, d=_MESH_SHARDS)
_DSB_GEOM = dict(w=_W, l=3, vw=2, d=_MESH_SHARDS)

# wave_expect: documented layout deviations from the base formula.
#
# The sharded dense runner keeps ONE local log replica (the other two
# ride the CommitBck/Log hops accounted under replicate), and
# replicate's two ppermute hops each move the wL balance rows plus a
# log append the hand formula counts once.
_DS_EXPECT = {"dint.tatp_dense.log_append": "2*w*(20 + 4*vw)",
              "dint.dense_sharded.replicate": 1.75}
# 2-D mesh geometries (parallel/multihost_sb.py): d is the GLOBAL
# device count n_hosts*n_ici — the per-step lane math is identical to
# dense_sharded_sb at the same d, only the transport differs.
_MHSB_GEOM = dict(w=_W, l=3, vw=2, d=8, h=4)
_MHSB_GEOM_H3 = dict(w=_W, l=3, vw=2, d=6, h=3)
# The @flat twins run ONE tuple-axis exchange where the hierarchical
# formulas count two stages: route/reply halve exactly, install_route
# falls back to dense_sharded_sb's single-exchange formula.
_MHSB_FLAT = {
    "dint.multihost_sb.route": 0.5,
    "dint.multihost_sb.reply": 0.5,
    "dint.multihost_sb.install_route":
        "2*w*l*8 + 2*w*l*4 + w*l*3*(20 + 4*vw)"}
# The @trace variants route the txn id alongside key+op, widening each
# lock-route slot from 8 to 12 bytes; install_route's and replicate's
# extra txn-id field stays inside the base formulas' 25% band.
_DSB_TRACE = {"dint.dense_sharded_sb.route": "2*w*l*12"}
_MHSB_TRACE = {"dint.multihost_sb.route": "2*2*w*l*12"}
# The 2-D TATP runner appends only the LOCAL log copy inside the
# log_append wave (same deviation _DS_EXPECT documents for the 1-D
# dense_sharded runner); its replication collectives pre-date wave
# scoping and surface as (unattributed), hence the absolute bytes
# budget on its row below.
_MH_EXPECT = {"dint.tatp_dense.log_append": "2*w*(20 + 4*vw)"}


# Every @mon footprint below includes the round-20 counter-plane growth:
# the scan_requests/scan_rows/scan_delta_hits rows widen the device
# Counters leaf by 12 B per device (3 x u32), +12 B single-chip, +12*d
# on the sharded/mesh targets — a fleet-wide recalibration, not a leak.
# PR 30's install_chunks row: +4 B per device in the same way; PR 34's
# lock_chunks row and PR 38's bck_chunks row: +4 B per device again.
# The resize that took six constant or hot-set rows out: -24 B a device.
def _cost(geom, dispatches, footprint, *, steps=float(_BLK),
          bytes_budget="1.25*ledger", wave_expect=None):
    return dict(steps=float(steps), geom=dict(geom),
                wave_expect=dict(wave_expect or {}),
                budget=dict(dispatches=dispatches, bytes=bytes_budget,
                            footprint=footprint))


TARGET_COST.update({
    # dense TATP. PR 30's write-set compaction (ops/compact.py) adds 3
    # to every install + log: a chunk's gathers of row ids, meta words and ring
    # slots out of the 2w-wide operands (the value and entry rows' gathers
    # read temporaries, which the walker does not price). One trip
    # of a chunk loop is priced, at a geometry where a chunk is all 2w
    # slots: the scatters' bytes are the parent's
    "tatp_dense/block": _cost(_TD_GEOM, 12, 216844),
    "tatp_dense/block@mon": _cost(_TD_GEOM, 14, 217020),
    "tatp_dense/drain": _cost(_TD_GEOM, 12, 216836),
    # dintserve serve-mode blocks: dispatches/step identical to the
    # closed-loop rows above (the occupancy mask fuses into the gen
    # wave), footprint +16 B (@mon +28 B) for the occ/shed step inputs
    "tatp_dense/serve": _cost(_TD_GEOM, 12, 216860),
    "tatp_dense/serve@mon": _cost(_TD_GEOM, 14, 217036),
    # dense SmallBank
    "smallbank_dense/block": _cost(_SB_GEOM, 8, 150984),
    "smallbank_dense/block@mon": _cost(_SB_GEOM, 10, 151160),
    "smallbank_dense/serve": _cost(_SB_GEOM, 8, 151000),
    "smallbank_dense/serve@mon": _cost(_SB_GEOM, 10, 151176),
    # generic pipelines: sort-bound, no formula-backed waves -> absolute
    # bytes ceilings instead of a ledger multiple
    "tatp_pipeline/block": _cost(_TD_GEOM, 50, 1610736022,
                                 bytes_budget=256000),
    "tatp_pipeline/block@mon": _cost(_TD_GEOM, 51, 1610736198,
                                     bytes_budget=256000),
    "smallbank_pipeline/block": _cost(_SB_GEOM, 36, 1207967480,
                                      bytes_budget=72000),
    "smallbank_pipeline/block@mon": _cost(_SB_GEOM, 37, 1207967656,
                                          bytes_budget=72000),
    # generic replicated shard step: one engine step per trace
    "sharded/tatp": _cost(_DS_GEOM, 62, 4295279296, steps=1.0,
                          bytes_budget=12000),
    "sharded/smallbank": _cost(_DSB_GEOM, 30, 3221242768, steps=1.0,
                               bytes_budget=4000),
    # dense multi-chip TATP. PR 38's compacted backup apply adds 3 a hop
    # (6 a step; the same 6 in multihost/block below): a chunk's gathers
    # of row ids, meta words and ring slots out of the forwarded 2w-wide
    # record, priced as one trip at a geometry where a chunk is all 2w
    "dense_sharded/block": _cost(_DS_GEOM, 42, 459240,
                                 wave_expect=_DS_EXPECT),
    "dense_sharded/block@mon": _cost(_DS_GEOM, 46, 459944,
                                     wave_expect=_DS_EXPECT),
    # dense multi-chip SmallBank
    "dense_sharded_sb/block": _cost(_DSB_GEOM, 33, 100676560),
    "dense_sharded_sb/block@mon": _cost(_DSB_GEOM, 37, 100677264),
    # 2-D (dcn x ici) SmallBank: the hierarchical route pays +9
    # dispatches/step (each exchange runs ici + dcn stages) to move
    # strictly fewer DCN-axis link bytes than its flat twin — the
    # hier-dcn-dominance check in passes/cost_budget.py enforces that
    # trade at BOTH calibrated geometries via TARGET_FLAT_TWIN
    "multihost_sb/block": _cost(_MHSB_GEOM, 42, 201353056),
    "multihost_sb/block@flat": _cost(_MHSB_GEOM, 33, 201353056,
                                     wave_expect=_MHSB_FLAT),
    "multihost_sb/block@mon": _cost(_MHSB_GEOM, 46, 201354464),
    "multihost_sb/block@h3": _cost(_MHSB_GEOM_H3, 42, 151014808),
    "multihost_sb/block@h3+flat": _cost(_MHSB_GEOM_H3, 33, 151014808,
                                        wave_expect=_MHSB_FLAT),
    # dintmesh serve-mode blocks (round 18): dispatches/step match the
    # closed-loop rows (the occupancy mask fuses into gen), footprint
    # +128 B for the [h, d/h, steps] occ/shed inputs; @overlap carries
    # the priced double buffer (OVERLAP_FOOTPRINT = 6240 B at this
    # geometry) and moves the SAME link bytes one step early — the
    # overlap-dcn-parity / overlap-footprint checks pin both statically
    "multihost_sb/serve": _cost(_MHSB_GEOM, 42, 201353184),
    "multihost_sb/serve@flat": _cost(_MHSB_GEOM, 33, 201353184,
                                     wave_expect=_MHSB_FLAT),
    "multihost_sb/serve@mon": _cost(_MHSB_GEOM, 47, 201354592),
    "multihost_sb/serve@overlap": _cost(_MHSB_GEOM, 44, 201359424),
    "multihost_sb/serve@overlap+mon": _cost(_MHSB_GEOM, 50, 201360832),
    # 2-D TATP (parallel/multihost.py, flat tuple-axis collectives):
    # replication traffic pre-dates wave scoping -> absolute bytes
    # ceiling like the pipeline targets, not a ledger multiple
    "multihost/block": _cost(dict(w=_W, r=2, vw=_VW, d=8, h=4), 42,
                             918424, bytes_budget=11000,
                             wave_expect=_MH_EXPECT),
    # dinttrace flight-recorder variants: the ring scatter-add adds one
    # dispatch per step plus the txn-id route fields (per-family "trace"
    # wave rows in monitor/waves.py price the 16 B x candidate-lane
    # update operand); footprint grows by the per-device ring buffers
    "tatp_dense/block@trace": _cost(_TD_GEOM, 13, 221968),
    "smallbank_dense/block@trace": _cost(_SB_GEOM, 9, 154572),
    "dense_sharded_sb/block@trace": _cost(_DSB_GEOM, 38, 100735968,
                                          wave_expect=_DSB_TRACE),
    "multihost_sb/block@trace": _cost(_MHSB_GEOM, 49, 201471872,
                                      wave_expect=_MHSB_TRACE),
    # recovery replay twins (cold path, one invocation per fault — the
    # budget exists so replay cannot silently grow a per-entry dispatch
    # loop): no waves.py formulas, absolute bytes ceilings like the
    # pipeline targets
    "recovery/tatp_dense": _cost(dict(w=_W, r=2, vw=_VW), 2, 493848,
                                 steps=1.0, bytes_budget=51200),
    "recovery/smallbank_dense": _cost(dict(w=_W, l=3, vw=2), 1, 349392,
                                      steps=1.0, bytes_budget=10240),
    "recovery/sb_shard": _cost(dict(w=_W, l=3, vw=2, d=_MESH_SHARDS), 1,
                               50248, steps=1.0, bytes_budget=10240),
})


# --------------------------------------- dintscan store serving (round 20)
# The KV store engine as a serve family: point GET/SET batches plus the
# @scan variants threading the ordered-run snapshot + delta overlay
# (Op.SCAN answered by the sequential slab, dint.store.scan). protocol
# is ('server', 'elected'): the store executes client-driven ops — no
# in-trace lock/validate loop to certify; instead the 'elected' flag
# pins the lock-free discipline itself (protocol pass, round 20): the
# segment writer election must exist, every install must descend from
# it, and every install must certify unique_indices — the three checks
# that make dintmut's store/block@scan cells killable.

_ST_NB = 16            # 16 buckets x 4 slots = 64 entries (= run cap)
_ST_SMAX = 8           # scan_max: reply slab rows per lane
_ST_DCAP = 8           # delta overlay capacity (window = 2 x sl rows)
# lg = run locate rounds = bit_length(cap=64) = 7, ld = overlay locate
# rounds = bit_length(dc=8) = 4 (tables/run.locate_bits)
_ST_GEOM = dict(w=_W, vw=_VW, sl=_ST_SMAX, dc=_ST_DCAP, lg=7, ld=4)


# 2^14 buckets x 4 slots = 4,096 entries a lane of the block's 16: past
# the density at which a full-width scatter is sorted, so this block's
# install is the compacted one (engines/store.install_is_compacted), as
# the store-ycsb-b cell's is; the five targets above trace 16 buckets and
# the full-width install. Abstract: nothing table-sized is made
_ST_NB_COMPACT = 1 << 14


def _store_runner(name: str, use_scan: bool, monitor: bool = False,
                  serve: bool = False, n_buckets: int = _ST_NB
                  ) -> TargetTrace:
    from ..engines import store
    from ..tables import kv
    run, init, _ = store.build_serve_runner(
        _N_ACCT, w=_W, cohorts_per_block=_BLK, val_words=_VW,
        scan_frac=0.5 if use_scan else 0.0, max_scan_len=_ST_SMAX,
        scan_max=_ST_SMAX, delta_cap=_ST_DCAP, use_scan=use_scan,
        monitor=monitor, serve=serve)
    carry = _abstract(lambda: init(kv.create(n_buckets, val_words=_VW)))
    args = (carry, _key_aval())
    if serve:
        args += (_occ_aval(), _occ_aval())
    return trace_target(name, run, args)


@register_target("store/block",
                 "KV store block, point ops only (GET/SET mix): the "
                 "packet-at-a-time baseline the scan route must beat",
                 protocol=('server', 'elected'))
def _t_store_block() -> TargetTrace:
    return _store_runner("store/block", use_scan=False)


@register_target("store/block@compact",
                 "KV store block, point ops, at a table sparse enough "
                 "that the install issues the elected writers in chunks "
                 "(ops/compact.py): the in-loop scatters must still "
                 "descend from the election and certify unique indices",
                 protocol=('server', 'elected'))
def _t_store_block_compact() -> TargetTrace:
    return _store_runner("store/block@compact", use_scan=False,
                         n_buckets=_ST_NB_COMPACT)


@register_target("store/block@scan",
                 "KV store block with the ordered-run scan path: locate "
                 "+ sequential slab + run∪delta merge",
                 protocol=('server', 'elected'))
def _t_store_block_scan() -> TargetTrace:
    return _store_runner("store/block@scan", use_scan=True)


@register_target("store/serve@scan",
                 "KV store serve-mode block: variable-occupancy mask "
                 "over the scan-enabled step (dintserve steady state)",
                 protocol=('server', 'elected'))
def _t_store_serve_scan() -> TargetTrace:
    return _store_runner("store/serve@scan", use_scan=True, serve=True)


@register_target("store/serve@scan+mon",
                 "KV store serve-mode block with the counter plane: "
                 "scan_requests/scan_rows/scan_delta_hits on the ledger",
                 protocol=('server', 'elected'))
def _t_store_serve_scan_mon() -> TargetTrace:
    return _store_runner("store/serve@scan+mon", use_scan=True,
                         serve=True, monitor=True)


@register_target("store/rebuild@scan",
                 "drain-boundary merge-compact: delta overlay folded "
                 "back into the dense sorted run (dint.store.run_rebuild)",
                 # no 'elected': this trace is the maintenance compact
                 # alone — no step loop, so no election/installs to pin
                 protocol=('server',))
def _t_store_rebuild() -> TargetTrace:
    from ..engines import store
    from ..tables import kv
    from ..tables import run as run_mod
    table = _abstract(lambda: kv.create(_ST_NB, val_words=_VW))
    runv = _abstract(lambda: run_mod.from_table(
        kv.create(_ST_NB, val_words=_VW), delta_cap=_ST_DCAP))
    return trace_target("store/rebuild@scan", jax.jit(store.rebuild_run),
                        (table, runv))


# @scan targets -> their point-op twin: passes/cost_budget.py fails
# scan-bytes-dominance unless the sequential slab derives STRICTLY
# fewer HBM bytes per REPLY ROW (dint.store.scan bytes / (w*sl)) than
# the point route pays per reply (dint.store.probe bytes / w) — rows
# must arrive cheaper than probes, the dintscan bandwidth claim
TARGET_SCAN_TWIN: dict[str, str] = {
    "store/block@scan": "store/block",
    "store/serve@scan": "store/block",
}

# round-20 dintscan store cost rows. probe/install bytes are hash-
# layout-dependent (unmodeled, attribution-only waves) -> absolute
# bytes ceilings like the pipeline targets, ~5% over the calibrated
# trace. The modeled pair reconciles at this geometry: scan =
# w*2*sl*(12+4*vw) = 7168 B/step (7,552 derived: the horizon reads and the
# tombstone window ride along), scan_locate = w*(lg+ld)*8 = 1408 B/step
# (zero wave_expect entries, zero allowlist entries — ISSUE 20's
# acceptance). The run_rebuild wave bills once per BLOCK (the drain
# boundary), attribution-only. The mon row
# pays +1 dispatch and +32 B/step for the counter scatter-add.
TARGET_COST.update({
    "store/block": _cost(_ST_GEOM, 15, 2072, bytes_budget=2200),
    # the compacted install (PR 40): one trip of each chunk loop is
    # priced, at a geometry where a chunk is all w lanes, so dispatches
    # and bytes are store/block's (a chunk's gathers out of lane space
    # read temporaries, which the walker does not price). The footprint
    # is the 2^14-bucket table's
    "store/block@compact": _cost(_ST_GEOM, 15, 2031704,
                                 bytes_budget=2200),
    # PR 45, the windowed scan and the one-pass rebuild, re-derived from
    # the new program (budgets ~0.4 % over the walker's reading): per step
    # 58 memory ops and 11,920 B, of which the scan's (the run and overlay
    # lower bounds, 11 rounds of two gathers; the two windows' 13 + 13
    # slices and horizon reads; the merge's takes) 7,552 + 1,408 B and
    # the rebuild's 896, billed once a block of two steps: the overlay's
    # insertion points and hit reads, and the events' rows scattered into
    # the fresh run (its shift passes are selects of shifted copies, no
    # memory op the walker prices). The footprint: the scan stats columns
    # and the next key number the YCSB-E generator carries, less the
    # overlay's arrival stamps. The rebuild alone: 4 memory ops, 1,792 B
    "store/block@scan": _cost(_ST_GEOM, 58, 4157, bytes_budget=11970),
    "store/serve@scan": _cost(_ST_GEOM, 58, 4173, bytes_budget=11970),
    "store/serve@scan+mon": _cost(_ST_GEOM, 59.5, 4349,
                                  bytes_budget=12045),
    "store/rebuild@scan": _cost(_ST_GEOM, 4, 6050, steps=1.0,
                                bytes_budget=1800),
})


# ------------------------------------------------- mutation-target matrix

# The dintmut matrix (analysis/mutate.py): which targets get corrupted,
# and with which operators. One representative per engine family — the
# operator set per target reflects what the engine actually contains
# (e.g. axis-swap needs live ppermutes, ring-shrink needs the durable
# log ring, drop-donation needs a top-level donated pjit) so
# "no sites found" stays a loud mut_check error (operator-dormant), not
# an expected blank. Kept here (not in mutate.py) because mutability is
# a property of the TARGET: adding an engine family means deciding which
# corruption classes apply to it, exactly like TARGET_PROTOCOL.
MUT_TARGETS: dict[str, tuple[str, ...]] = {
    # single-chip certified+occ TATP: the lock/validate/install loop,
    # the donated pjit, and the durable log ring are all in one trace
    "tatp_dense/block": ("drop-eqn", "weaken-scatter", "mask-swap",
                         "widen-gather", "drop-donation", "ring-shrink"),
    # single-chip certified SmallBank (no occ validate): same fabric,
    # different protocol flags — proves kills do not depend on occ
    "smallbank_dense/block": ("drop-eqn", "weaken-scatter", "mask-swap",
                              "widen-gather", "ring-shrink"),
    # 4-way replicated+occ shard_map TATP: replication hops exist, so
    # the ppermute operators come into play
    "dense_sharded/block": ("drop-eqn", "mask-swap", "axis-swap",
                            "ring-shrink"),
    # replicated SmallBank shards: the weaken/widen operators against a
    # sharded byte ledger
    "dense_sharded_sb/block": ("drop-eqn", "weaken-scatter", "axis-swap",
                               "widen-gather"),
    # 2-D (dcn x ici) mesh: the only target where dcn->ici rerouting is
    # expressible — the axis-swap dcn variant lives here
    "multihost_sb/block": ("drop-eqn", "axis-swap", "ring-shrink"),
    # round-20 scan-enabled store: no lock ring / replication, but the
    # writer-election scatters, the scan merge masks and the slab
    # gathers are all corruptible — the gate matrix must prove the
    # oracle pins and the cost ledger actually catch them
    "store/block@scan": ("drop-eqn", "weaken-scatter", "mask-swap",
                         "widen-gather"),
}


# ----------------------------------------------------------------- API

# trace-once cache shared by every pass in every analysis.run() of the
# process (core.TraceCache records per-target build seconds for --time)
TRACE_CACHE = TraceCache()


def get_trace(name: str) -> TargetTrace:
    """Build + trace a registered target (traced once per process; every
    pass and every run() shares the cached jaxpr)."""
    trace = TRACE_CACHE.get(name, TARGETS[name])
    trace.protocol = TARGET_PROTOCOL.get(name, trace.protocol)
    return trace
