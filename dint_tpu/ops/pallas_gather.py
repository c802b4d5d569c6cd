"""Pallas/Mosaic DMA-ring kernels for batched random access over the
HBM-resident dense tables.

PERF.md "Where the remaining 2.5x lives": the dense engines' step cost is
pinned to a short serialized chain of random-access HBM ops (gathers /
scatter-max / gather-back) at ~0.6-0.9 ms per 16-32k random indices each —
XLA emits one device op per access with no way to overlap a chain that is
data-dependent. The reference collapses its per-request path into ONE fused
in-kernel pass at the NIC (tatp/ebpf/shard_kern.c); this module is the TPU
analogue: kernels that walk K random rows with a ring of NSLOTS outstanding
row DMAs (HBM latency hiding inside one kernel launch) instead of N chained
XLA gather ops.

Two kernel families, both production entry points behind `DINT_USE_PALLAS`
(env) / `use_pallas=` (engine kwarg):

* `gather_rows(tab, idx, vw)` — the wave-1/validate/magic reads: K rows of
  `vw` u32 words from a tight interleaved 1-D table (row r's words at
  [r*vw, (r+1)*vw), the engines/tatp_dense.DenseDB.val layout). Indices are
  prefetched to SMEM (PrefetchScalarGridSpec), the kernel keeps NSLOTS row
  DMAs in flight. Semantics == `tab[(idx[:,None]*vw + arange(vw)).ravel()]`
  bit for bit (pinned in tests/test_pallas_ops.py); indices MUST be
  in-bounds — the engines clamp masked lanes onto the sentinel row, and
  unlike XLA's clipping gather a Pallas DMA from an out-of-range offset is
  undefined.

* `lock_arbitrate(arb, rows, active, step, k_arb)` — the fused
  gather -> stamp-compare -> scatter-max lock path of engines/tatp_dense:
  ONE kernel pass replaces the 3-op chain (arb gather, masked scatter-max
  of `(step << k_arb) | (M-1-lane)`, winner gather-back). The kernel walks
  the M write-slot lanes in order doing a read-modify-write per lane:
  first ACTIVE lane on a free row wins the stamp, later lanes observe
  either the in-batch stamp (step field == step) or the previous step's
  stamp (== step-1) and reject. That sequential rule is EXACTLY the XLA
  scatter-max outcome (max of the packed stamps == smallest lane index,
  proof in tests/test_pallas_ops.py::test_lock_arbitrate_matches_xla): the
  arb array and grant vector are bit-identical to the XLA path. The arb
  input is donated (input_output_aliases), so the 0.6 GB array is updated
  in place. Hardware hazard discipline: reads run NSLOTS ahead of the
  RMW point, a write DMA is force-waited when its slot is reused (lag
  NSLOTS), and an SMEM window of the last 2*NSLOTS granted rows catches
  the only writes a prefetched read can miss — so in-batch duplicates
  arbitrate correctly even with the ring fully in flight.

Round 10 adds the HOT-SET family (dintcache): the TPU-native analogue of
DINT's kernel/user split across the MEMORY hierarchy — HBM is "userspace",
VMEM is "XDP". The engines keep a compact physical mirror of the hot index
prefix (a few MiB; engines/smallbank_dense.attach_hotset) that installs
write through to, so there is no coherence protocol, just a partition:

* `gather_rows_hot(tab, mirror, idx, midx, vw)` — bulk-DMAs the whole
  mirror into VMEM once per invocation (~10 µs sequential at a few MiB),
  then serves lanes with `midx >= 0` by VMEM-local row copies while lanes
  with `midx < 0` walk the HBM DMA ring exactly like `gather_rows`.
  Semantics: `out[i] = mirror[midx[i]] if midx[i] >= 0 else tab[idx[i]]`
  (rows of vw words) — bit-identical to the plain gather whenever the
  mirror invariant `mirror[m] == tab[row_of(m)]` holds, which the engines'
  write-through installs maintain by construction.

* `scatter_rows_hot(tab, mirror, idx, midx, mask, vals, vw)` — the fused
  install/scatter variant: one kernel writes each masked lane's row into
  the HBM table AND (for `midx >= 0` lanes) into the mirror, replacing the
  XLA double scatter of the write-through path. Masked-out lanes write
  nothing (no OOB-sentinel traffic); indices among masked lanes must be
  unique — the same one-X-writer-per-row contract the engines' XLA
  `unique_indices=True` scatters already certify.

* `lock_arbitrate(..., hot_n=H)` — the fused lock pass with the arb
  array's `[0, H)` prefix cached in VMEM for the duration of the pass:
  hot lanes' RMW DMAs are VMEM-local, the prefix is bulk-copied back at
  the end, and the ring/hazard discipline is UNCHANGED (hot and cold
  lanes use the same slot ring, only the copy endpoints differ) so the
  first-lane-wins equivalence proof carries over verbatim. hot_n=0 (the
  default) is the round-6 kernel.

Round 12 adds the MEGAKERNEL family (`DINT_USE_FUSED` env / `use_fused=`
kwarg, default off): each fuses a PAIR of adjacent engine waves into one
dispatch, shortening the step's dependent-dispatch chain from ~6 to ~4.
`lock_validate` composes the arb RMW (`_arb_rmw`, hot_n prefix included)
with the OCC validate read and the next cohort's fresh meta read;
`gather_streams`/`scatter_streams` run N independent gather/masked-
scatter rings back-to-back in one launch (the install table write, its
mirror write-through, and the replication-log append = `install_log`).
Every stream is the round-6/10 ring verbatim — only dispatch boundaries
are removed — so outputs stay bit-identical to the unfused path
(tests/test_fused_ops.py) and `resolve_use_fused()` carries the same
refusal contract below.

Refusal contract (ISSUE 25): a kernel that was ASKED FOR never degrades
in silence. `resolve_use_pallas()` / `resolve_use_fused()` / the
`*_kernels_available()` probes compile + run the requested kernels at the
caller's real lane geometry (tiny tables — the failure modes are
construct/SMEM-budget level, not table-size level) and check them against
their XLA forms; a Mosaic refusal or a mismatch RAISES `KernelRefused`
naming the kernel and carrying the compiler's text, so nobody runs the
XLA route believing a kernel served it. The XLA forms (`hot_gather`'s
index-compare partition, `scan_slab`, the unfused dispatches) stay as
what a caller gets when it does NOT ask. Only successful probes are
cached, per (backend, interpret, kernel, geometry): a rebuild never
re-compiles a probe that passed, and a refusal is raised every time. On
CPU every kernel runs under `interpret=True` (the Mosaic pipeline never
runs), which is what makes the layer tier-1-testable without hardware —
and why only a compile for the chip (tests/test_chip_compile.py) says
whether Mosaic accepts a kernel. On TPU v5e it refuses every kernel in
this file at the row widths the engines use: a row slice of a 1-D HBM
table must be a multiple of 1024 words, of a 2-D view a multiple of 128
(PERF.md "Round 25").
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32 = jnp.int32
U32 = jnp.uint32

NSLOTS = 16      # outstanding row DMAs in the gather ring
RMW_SLOTS = 8    # outstanding read DMAs in the lock RMW ring
WIN = 2 * RMW_SLOTS   # recent-grant window: covers every write a read
#                       prefetched RMW_SLOTS ahead can race (see module doc)

def _out(shape, *operands) -> jax.ShapeDtypeStruct:
    """u32 `out_shape` entry that varies over every mesh axis any operand
    varies over: inside `jax.shard_map(check_vma=True)` a pallas_call
    must say so itself (outside one the set is empty)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, U32, vma=vma)


def use_interpret() -> bool:
    """interpret=True off-TPU (CPU tier-1 tests, virtual meshes); the env
    override exists so hardware debugging can force either mode."""
    env = os.environ.get("DINT_PALLAS_INTERPRET")
    if env is not None:
        return env != "0"
    return jax.default_backend() != "tpu"


def shard_map_check_vma(runs_kernels: bool) -> bool:
    """`check_vma` for a `jax.shard_map` whose body may run these kernels.
    Compiled kernels type-check (`_out` declares their outputs varying);
    JAX's Pallas HLO interpreter drops the varying-manual-axes types
    inside its own grid loop and says to pass check_vma=False, so only a
    body that runs kernels in interpret mode turns the check off."""
    return not (runs_kernels and use_interpret())


def env_use_pallas() -> bool:
    return os.environ.get("DINT_USE_PALLAS", "0") not in ("", "0")


def env_use_hotset() -> bool:
    return os.environ.get("DINT_USE_HOTSET", "0") not in ("", "0")


def env_use_fused() -> bool:
    return os.environ.get("DINT_USE_FUSED", "0") not in ("", "0")


def env_use_scan() -> bool:
    return os.environ.get("DINT_USE_SCAN", "0") not in ("", "0")


def resolve_use_scan(explicit: bool | None = None) -> bool:
    """Engine-builder gate for the dintscan ordered-run scan path:
    explicit kwarg wins, else the DINT_USE_SCAN env. No kernel probe here
    — the scan path has a pure-XLA slab gather (scan_slab); whether the
    streaming DMA kernel serves it rides the engine's use_pallas
    resolution (scan_kernels_available)."""
    if explicit is None:
        return env_use_scan()
    return bool(explicit)


def resolve_use_hotset(explicit: bool | None = None) -> bool:
    """Engine-builder gate for the hot-set partition: explicit kwarg wins,
    else the DINT_USE_HOTSET env. No kernel probe here — the partition has
    a pure-XLA form (hot_gather); whether the VMEM kernels serve it is
    resolved separately (resolve_use_pallas + hot_kernels_available)."""
    if explicit is None:
        return env_use_hotset()
    return bool(explicit)


# ------------------------------------------------------------- row gather


def _gather_kernel(vw: int, nslots: int, idx_ref, tab_ref, out_ref, sem):
    """idx_ref: SMEM [K] i32 row ids (prefetched); tab_ref: ANY [N*vw] u32;
    out_ref: ANY [K*vw] u32; sem: DMA sems [nslots]. Ring of nslots
    outstanding one-row HBM->HBM copies (validated against XLA's gather in
    interpret mode AND at K=256/N=10k geometry by tools/profile_pallas_hbm)."""
    k = idx_ref.shape[0]

    def copy(i):
        r = idx_ref[i]
        return pltpu.make_async_copy(
            tab_ref.at[pl.ds(r * vw, vw)],
            out_ref.at[pl.ds(i * vw, vw)],
            sem.at[jax.lax.rem(i, nslots)])

    def prime(i, _):
        copy(i).start()
        return 0

    jax.lax.fori_loop(0, min(nslots, k), prime, 0)

    def body(i, _):
        copy(i).wait()               # slot free again

        def issue(_):
            copy(i + nslots).start()
            return 0

        jax.lax.cond(i + nslots < k, issue, lambda _: 0, 0)
        return 0

    jax.lax.fori_loop(0, k, body, 0)


@functools.partial(jax.jit, static_argnums=(2, 3))
def gather_rows(tab, idx, vw: int = 1, interpret: bool | None = None):
    """K random rows of `vw` u32 words from the flat table `tab`
    (row r at [r*vw, (r+1)*vw)). Returns u32 [K*vw] — bit-identical to
    `tab[(idx[:,None]*vw + arange(vw)).reshape(-1)]` for in-bounds idx.
    `vw=1` covers the meta/arb/bal/stamp single-word gathers; callers that
    need one word at an offset inside wider rows pass pre-scaled flat word
    indices with vw=1 (e.g. the magic check's `rows*VW + 1`)."""
    if interpret is None:
        interpret = use_interpret()
    k = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((NSLOTS,))],
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, vw, NSLOTS),
        name="gather_rows",
        grid_spec=grid_spec,
        out_shape=_out((k * vw,), idx, tab),
        interpret=bool(interpret),
    )(idx.astype(I32), tab)


# ------------------------------------------------- hot-set row gather


def _gather_hot_kernel(vw: int, nslots: int, idx_ref, midx_ref, tab_ref,
                       mir_ref, out_ref, mir_vmem, load_sem, sem):
    """gather_rows with a VMEM-resident mirror: one bulk HBM->VMEM copy of
    the whole mirror up front, then the usual ring of nslots outstanding
    row copies — hot lanes (midx >= 0) copy VMEM-locally from the mirror,
    cold lanes DMA from the HBM table. Hot and cold lanes share the slot
    ring (same semaphore, same row size), so the round-6 ring discipline
    is unchanged."""
    k = idx_ref.shape[0]
    load = pltpu.make_async_copy(mir_ref, mir_vmem, load_sem)
    load.start()
    load.wait()

    def cold(i):
        return pltpu.make_async_copy(
            tab_ref.at[pl.ds(idx_ref[i] * vw, vw)],
            out_ref.at[pl.ds(i * vw, vw)],
            sem.at[jax.lax.rem(i, nslots)])

    def hot(i):
        return pltpu.make_async_copy(
            mir_vmem.at[pl.ds(midx_ref[i] * vw, vw)],
            out_ref.at[pl.ds(i * vw, vw)],
            sem.at[jax.lax.rem(i, nslots)])

    def start(i):
        @pl.when(midx_ref[i] >= 0)
        def _():
            hot(i).start()

        @pl.when(midx_ref[i] < 0)
        def _():
            cold(i).start()

    def wait(i):
        @pl.when(midx_ref[i] >= 0)
        def _():
            hot(i).wait()

        @pl.when(midx_ref[i] < 0)
        def _():
            cold(i).wait()

    def prime(i, _):
        start(i)
        return 0

    jax.lax.fori_loop(0, min(nslots, k), prime, 0)

    def body(i, _):
        wait(i)

        @pl.when(i + nslots < k)
        def _():
            start(i + nslots)

        return 0

    jax.lax.fori_loop(0, k, body, 0)


@functools.partial(jax.jit, static_argnums=(4, 5))
def gather_rows_hot(tab, mirror, idx, midx, vw: int = 1,
                    interpret: bool | None = None):
    """Partitioned row gather: `out[i] = mirror[midx[i]*vw +: vw]` when
    `midx[i] >= 0`, else `tab[idx[i]*vw +: vw]`. Bit-identical to
    `gather_rows(tab, idx, vw)` whenever the mirror mirrors the table
    (the engines' write-through invariant). Cold-lane idx must be
    in-bounds (same sentinel-clamp contract as gather_rows); hot-lane
    midx must address the mirror."""
    if interpret is None:
        interpret = use_interpret()
    k = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((mirror.shape[0],), U32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((NSLOTS,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_gather_hot_kernel, vw, NSLOTS),
        name="gather_rows_hot",
        grid_spec=grid_spec,
        out_shape=_out((k * vw,), idx, midx, tab, mirror),
        interpret=bool(interpret),
    )(idx.astype(I32), midx.astype(I32), tab, mirror)


def _xla_hot_gather(tab, mirror, idx, midx, vw: int):
    """The XLA partition: index-compare + small-array gather. Same
    semantics as the kernel; what use_hotset without use_pallas runs, and
    the probe's ground truth."""
    flat_c = (idx[:, None] * vw + jnp.arange(vw, dtype=I32)).reshape(-1)
    mc = jnp.maximum(midx, 0)
    flat_h = (mc[:, None] * vw + jnp.arange(vw, dtype=I32)).reshape(-1)
    hot = jnp.repeat(midx >= 0, vw)
    return jnp.where(hot, mirror[flat_h], tab[flat_c])


def hot_gather(tab, mirror, idx, midx, vw: int = 1,
               use_pallas: bool = False):
    """Engine entry point for the partitioned gather: the VMEM kernel when
    the builder resolved pallas, the index-compare XLA partition
    otherwise. Returns u32 [K*vw]."""
    if use_pallas:
        return gather_rows_hot(tab, mirror, idx.astype(I32),
                               midx.astype(I32), vw)
    return _xla_hot_gather(tab, mirror, idx.astype(I32),
                           midx.astype(I32), vw)


# --------------------------------------------- dintscan sequential slabs


def _scan_kernel(vw: int, lg: int, nslots: int, off_ref, order_ref,
                 hi_ref, lo_ref, ver_ref, val_ref,
                 ohi_ref, olo_ref, over_ref, oval_ref, sem):
    """Sequential-slab gather over the ordered run: per lane, FOUR
    contiguous-row DMAs (key_hi/key_lo/ver of `lg` rows + their `lg*vw`
    val words) land the window [off, off+lg) into the lane's reply slab.
    Lanes are walked in ascending-offset order (order_ref, prefetched) so
    consecutive DMAs touch adjacent HBM — the sequential-bandwidth shape
    the sorted layout exists for — while slabs land at each lane's
    ORIGINAL index, keeping outputs order-independent and bit-identical
    to the XLA slab gather. The ring keeps nslots lanes (4 DMAs each) in
    flight; off must be in-bounds ([0, cap-lg], the engine's clamped
    locate offsets) — a Pallas DMA from an out-of-range offset is
    undefined, unlike XLA's clipping gather."""
    k = off_ref.shape[0]

    def copies(j):
        lane = order_ref[j]
        base = off_ref[lane]
        s = jax.lax.rem(j, nslots)
        return (
            pltpu.make_async_copy(hi_ref.at[pl.ds(base, lg)],
                                  ohi_ref.at[pl.ds(lane * lg, lg)],
                                  sem.at[s, 0]),
            pltpu.make_async_copy(lo_ref.at[pl.ds(base, lg)],
                                  olo_ref.at[pl.ds(lane * lg, lg)],
                                  sem.at[s, 1]),
            pltpu.make_async_copy(ver_ref.at[pl.ds(base, lg)],
                                  over_ref.at[pl.ds(lane * lg, lg)],
                                  sem.at[s, 2]),
            pltpu.make_async_copy(val_ref.at[pl.ds(base * vw, lg * vw)],
                                  oval_ref.at[pl.ds(lane * lg * vw, lg * vw)],
                                  sem.at[s, 3]),
        )

    def start(j):
        for c in copies(j):
            c.start()

    def wait(j):
        for c in copies(j):
            c.wait()

    def prime(j, _):
        start(j)
        return 0

    jax.lax.fori_loop(0, min(nslots, k), prime, 0)

    def body(j, _):
        wait(j)

        @pl.when(j + nslots < k)
        def _():
            start(j + nslots)

        return 0

    jax.lax.fori_loop(0, k, body, 0)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def scan_rows(run_hi, run_lo, run_ver, run_val, off, order, lg: int,
              vw: int, interpret: bool | None = None):
    """K sequential windows of `lg` rows from the ordered run's flat
    arrays. `order` is the lane walk order (ascending off); returns
    (hi, lo, ver, val) slabs of shapes [K*lg] / [K*lg*vw], bit-identical
    to the XLA slab gather for in-bounds off."""
    if interpret is None:
        interpret = use_interpret()
    k = off.shape[0]
    ops = (off, order, run_hi, run_lo, run_ver, run_val)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
        scratch_shapes=[pltpu.SemaphoreType.DMA((NSLOTS, 4))],
    )
    return pl.pallas_call(
        functools.partial(_scan_kernel, vw, lg, NSLOTS),
        name="scan_rows",
        grid_spec=grid_spec,
        out_shape=[_out((k * n,), *ops) for n in (lg, lg, lg, lg * vw)],
        interpret=bool(interpret),
    )(off.astype(I32), order.astype(I32), *ops[2:])


def _xla_scan_slab(run_hi, run_lo, run_ver, run_val, off, lg: int, vw: int):
    """The XLA form: per-lane dynamic-slice-shaped gathers of the same
    contiguous windows (random-gather issue rate where the kernel
    streams). What use_scan without use_pallas runs, and the probe's
    ground truth."""
    idx = off[:, None] + jnp.arange(lg, dtype=I32)[None, :]
    widx = (idx * vw)[:, :, None] + jnp.arange(vw, dtype=I32)[None, None, :]
    return (run_hi[idx], run_lo[idx], run_ver[idx],
            run_val[widx])


def scan_slab(run_hi, run_lo, run_ver, run_val, off, lg: int, vw: int,
              use_pallas: bool = False):
    """Engine entry point for the dintscan window gather: the streaming
    DMA kernel when the builder resolved pallas, the XLA slab gather
    otherwise. Returns (hi, lo, ver [K, lg], val [K, lg, vw])."""
    off = off.astype(I32)
    k = off.shape[0]
    if use_pallas:
        order = jnp.argsort(off)
        hi, lo, ver, val = scan_rows(run_hi, run_lo, run_ver, run_val,
                                     off, order, lg, vw)
        return (hi.reshape(k, lg), lo.reshape(k, lg), ver.reshape(k, lg),
                val.reshape(k, lg, vw))
    return _xla_scan_slab(run_hi, run_lo, run_ver, run_val, off, lg, vw)


# ---------------------------------------------- hot-set fused install


def _scatter_hot_kernel(vw: int, nslots: int, idx_ref, midx_ref, msk_ref,
                        vals_ref, tab_in, mir_in, tab_out, mir_out,
                        tlane, mlane, tsem, msem):
    """Fused write-through install: per masked lane, one row DMA into the
    HBM table and (when midx >= 0) one into the mirror. Unmasked lanes
    issue nothing (no OOB-sentinel traffic). Per-slot SMEM trackers
    record WHICH lane's copy occupies a ring slot so reuse force-waits
    exactly the copies that were started. In-flight writes never collide:
    indices among masked lanes are unique (the engines' one-X-writer-
    per-row certification, the same contract their unique_indices=True
    XLA scatters declare)."""
    k = idx_ref.shape[0]

    def t_copy(i):
        return pltpu.make_async_copy(
            vals_ref.at[pl.ds(i * vw, vw)],
            tab_out.at[pl.ds(idx_ref[i] * vw, vw)],
            tsem.at[jax.lax.rem(i, nslots)])

    def m_copy(i):
        return pltpu.make_async_copy(
            vals_ref.at[pl.ds(i * vw, vw)],
            mir_out.at[pl.ds(midx_ref[i] * vw, vw)],
            msem.at[jax.lax.rem(i, nslots)])

    def init(s, _):
        tlane[s] = I32(-1)
        mlane[s] = I32(-1)
        return 0

    jax.lax.fori_loop(0, nslots, init, 0)

    def body(i, _):
        s = jax.lax.rem(i, nslots)

        @pl.when(tlane[s] >= 0)
        def _():
            t_copy(tlane[s]).wait()

        tlane[s] = I32(-1)

        @pl.when(mlane[s] >= 0)
        def _():
            m_copy(mlane[s]).wait()

        mlane[s] = I32(-1)

        @pl.when(msk_ref[i] != 0)
        def _():
            t_copy(i).start()
            tlane[s] = i

            @pl.when(midx_ref[i] >= 0)
            def _():
                m_copy(i).start()
                mlane[s] = i

        return 0

    jax.lax.fori_loop(0, k, body, 0)

    def drain(s, _):
        @pl.when(tlane[s] >= 0)
        def _():
            t_copy(tlane[s]).wait()

        @pl.when(mlane[s] >= 0)
        def _():
            m_copy(mlane[s]).wait()

        return 0

    jax.lax.fori_loop(0, nslots, drain, 0)


@functools.partial(jax.jit, static_argnums=(6, 7), donate_argnums=(0, 1))
def scatter_rows_hot(tab, mirror, idx, midx, mask, vals, vw: int = 1,
                     interpret: bool | None = None):
    """Fused install: for every lane with mask != 0, write vals row i into
    `tab[idx[i]*vw +: vw]` AND, when `midx[i] >= 0`, into
    `mirror[midx[i]*vw +: vw]`. Returns (tab', mirror'), both updated in
    place (donated). Indices among masked lanes must be unique."""
    if interpret is None:
        interpret = use_interpret()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[
            pltpu.SMEM((NSLOTS,), I32),     # tlane: lane holding tab slot
            pltpu.SMEM((NSLOTS,), I32),     # mlane: lane holding mir slot
            pltpu.SemaphoreType.DMA((NSLOTS,)),
            pltpu.SemaphoreType.DMA((NSLOTS,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_scatter_hot_kernel, vw, NSLOTS),
        name="scatter_rows_hot",
        grid_spec=grid_spec,
        out_shape=tuple(_out(t.shape, idx, midx, mask, vals, tab, mirror)
                        for t in (tab, mirror)),
        # operands 4/5 (post scalar-prefetch: vals, tab, mirror) -> in-place
        input_output_aliases={4: 0, 5: 1},
        interpret=bool(interpret),
    )(idx.astype(I32), midx.astype(I32), mask.astype(I32), vals, tab,
      mirror)


def hot_scatter(tab, mirror, idx, midx, mask, vals, vw: int = 1,
                use_pallas: bool = False):
    """Engine entry point for the write-through install: the fused kernel
    when the builder resolved pallas, the XLA double scatter otherwise
    (both 1-D unique-index fast paths). Returns (tab', mirror')."""
    if use_pallas:
        return scatter_rows_hot(tab, mirror, idx, midx, mask, vals, vw)
    n_tab = tab.shape[0] // vw
    n_mir = mirror.shape[0] // vw
    widx = jnp.where(mask != 0, idx, n_tab)
    wflat = (widx[:, None] * vw + jnp.arange(vw, dtype=I32)).reshape(-1)
    tab = tab.at[wflat].set(vals, mode="drop", unique_indices=True)
    hmask = (mask != 0) & (midx >= 0)
    hidx = jnp.where(hmask, midx, n_mir)
    hflat = (hidx[:, None] * vw + jnp.arange(vw, dtype=I32)).reshape(-1)
    mirror = mirror.at[hflat].set(vals, mode="drop", unique_indices=True)
    return tab, mirror


# ------------------------------------------------------- fused lock pass


def _arb_rmw(k_arb: int, hot_n: int, rows_ref, act_ref, t, arb_out,
             rbuf, wbuf, gbuf, win_row, hot_vmem, rsem, wsem, hsem):
    """Sequential first-lane-wins RMW over M lock lanes — the fused form of
    gather -> stamp-compare -> scatter-max (bit-equivalence argument in the
    module docstring). Grants accumulate in the SMEM ``gbuf``; the caller
    DMAs them out (lock_arbitrate's trailing copy) or keeps composing
    (lock_validate). This is the WHOLE arbitration pass — hot-prefix
    load/store, ring init, prime, body, drain — factored so the megakernel
    reuses it verbatim and the round-6 equivalence proof carries over
    unchanged.

    ``hot_n`` > 0 additionally caches the arb prefix [0, hot_n) in VMEM
    for the whole pass: lanes on hot rows RMW against the VMEM copy
    (VMEM-local DMAs — no HBM latency on the 90% of a skewed batch), cold
    lanes against HBM, and the prefix is bulk-copied back at the end. Hot
    and cold rows are DISJOINT index sets, both lane classes run the SAME
    slot ring / force-wait / grant-window discipline (only the copy
    endpoints differ), so the round-6 hazard argument — every write older
    than the ring depth has landed, the SMEM window catches the rest —
    holds verbatim."""
    m = rows_ref.shape[0]

    if hot_n > 0:
        load = pltpu.make_async_copy(arb_out.at[pl.ds(0, hot_n)],
                                     hot_vmem, hsem)
        load.start()
        load.wait()

    def _rd(i, ref):
        return pltpu.make_async_copy(
            ref.at[pl.ds(rows_ref[i], 1)],
            rbuf.at[pl.ds(jax.lax.rem(i, RMW_SLOTS), 1)],
            rsem.at[jax.lax.rem(i, RMW_SLOTS)])

    def _wr(i, ref):
        return pltpu.make_async_copy(
            wbuf.at[pl.ds(jax.lax.rem(i, RMW_SLOTS), 1)],
            ref.at[pl.ds(rows_ref[i], 1)],
            wsem.at[jax.lax.rem(i, RMW_SLOTS)])

    def _route(i, mk, verb):
        """Issue (verb='start') or retire (verb='wait') lane i's copy
        against its row's endpoint: the VMEM prefix for hot rows, the HBM
        array for cold. Descriptors are identical in size/semaphore, so
        the ring discipline does not see the split."""
        if hot_n == 0:
            getattr(mk(i, arb_out), verb)()
            return

        @pl.when(rows_ref[i] < hot_n)
        def _():
            getattr(mk(i, hot_vmem), verb)()

        @pl.when(rows_ref[i] >= hot_n)
        def _():
            getattr(mk(i, arb_out), verb)()

    def read_start(i):
        _route(i, _rd, "start")

    def read_wait(i):
        _route(i, _rd, "wait")

    def write_start(i):
        _route(i, _wr, "start")

    def write_wait(i):
        _route(i, _wr, "wait")

    def init_win(i, _):
        win_row[i] = I32(-1)
        return 0

    jax.lax.fori_loop(0, WIN, init_win, 0)

    def init_wbuf(i, _):
        # wbuf doubles as the per-slot write-in-flight flag: packed stamps
        # are never 0 (step >= 2), so nonzero == a write DMA to force-wait
        wbuf[i] = U32(0)
        return 0

    jax.lax.fori_loop(0, RMW_SLOTS, init_wbuf, 0)

    def prime(i, _):
        read_start(i)
        return 0

    jax.lax.fori_loop(0, min(RMW_SLOTS, m), prime, 0)

    def body(i, _):
        s = jax.lax.rem(i, RMW_SLOTS)
        # a write DMA still in flight on this slot belongs to lane
        # i - RMW_SLOTS: force-wait it so (a) wbuf[s] is reusable and
        # (b) every write older than the ring depth has LANDED before the
        # reads issued this iteration (the hazard-window invariant)
        @pl.when(jnp.logical_and(i >= RMW_SLOTS,
                                 wbuf[jax.lax.rem(i, RMW_SLOTS)] != U32(0)))
        def _():
            write_wait(i - RMW_SLOTS)

        wbuf[s] = U32(0)

        read_wait(i)
        old = rbuf[s]
        r = rows_ref[i]

        # writes a ring-prefetched read can have missed are exactly the
        # last WIN lanes' grants — scan the SMEM window for this row
        def scan(j, hit):
            return jnp.logical_or(hit, win_row[j] == r)

        taken_win = jax.lax.fori_loop(0, WIN, scan, False)

        stamp = old >> k_arb
        held = stamp == t - U32(1)              # stamped by the previous step
        taken = jnp.logical_or(stamp == t, taken_win)   # in-batch winner
        grant = jnp.logical_and(act_ref[i] != 0,
                                jnp.logical_not(jnp.logical_or(held, taken)))

        gbuf[i] = jax.lax.select(grant, U32(1), U32(0))
        win_row[jax.lax.rem(i, WIN)] = jax.lax.select(grant, r, I32(-1))

        @pl.when(grant)
        def _():
            inv = U32(m - 1) - i.astype(U32)    # == XLA's inverted slot
            wbuf[s] = (t << k_arb) | inv
            write_start(i)

        @pl.when(i + RMW_SLOTS < m)
        def _():
            read_start(i + RMW_SLOTS)

        return 0

    jax.lax.fori_loop(0, m, body, 0)

    def drain(j, _):
        i = m - min(RMW_SLOTS, m) + j

        @pl.when(wbuf[jax.lax.rem(i, RMW_SLOTS)] != U32(0))
        def _():
            write_wait(i)

        return 0

    jax.lax.fori_loop(0, min(RMW_SLOTS, m), drain, 0)

    if hot_n > 0:
        # every hot write has retired (drain above), so the VMEM prefix is
        # the final state of rows [0, hot_n): one bulk copy back in place
        store = pltpu.make_async_copy(hot_vmem, arb_out.at[pl.ds(0, hot_n)],
                                      hsem)
        store.start()
        store.wait()


def _arbitrate_kernel(k_arb: int, hot_n: int, rows_ref, act_ref, t_ref,
                      arb_in, arb_out, grant_out, rbuf, wbuf, gbuf,
                      win_row, hot_vmem, rsem, wsem, gsem, hsem):
    """The standalone lock pass: the RMW core plus one trailing DMA that
    carries the SMEM grant bits out. arb_in/arb_out alias (in-place update
    of the HBM array)."""
    _arb_rmw(k_arb, hot_n, rows_ref, act_ref, t_ref[0], arb_out, rbuf,
             wbuf, gbuf, win_row, hot_vmem, rsem, wsem, hsem)
    out = pltpu.make_async_copy(gbuf, grant_out, gsem)
    out.start()
    out.wait()


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def lock_arbitrate(arb, rows, active, step, k_arb: int,
                   interpret: bool | None = None, hot_n: int = 0):
    """Fused lock pass over the step-stamped arb array (engines/tatp_dense
    layout: `step << k_arb | inverted_slot`). Returns (arb', grant u32[M])
    bit-identical to the XLA chain

        old  = arb[rows]; held = (old >> k_arb) == step - 1
        cand = active & ~held
        arb' = arb.at[where(cand, rows, oob)].max((step << k_arb)
                                                  | (M-1 - lane), "drop")
        grant = cand & (arb'[rows] == packed)

    for in-bounds rows (masked lanes must carry active=False and a valid
    sentinel row id, exactly what pipe_step already does). The arb buffer
    is donated and updated in place.

    ``hot_n`` (static) > 0 caches the arb prefix [0, hot_n) in VMEM for
    the pass (the dintcache hot tier — module docstring); outputs stay
    bit-identical, only the DMA endpoints of hot lanes change."""
    if interpret is None:
        interpret = use_interpret()
    m = rows.shape[0]
    assert 0 <= hot_n <= arb.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[
            pltpu.SMEM((RMW_SLOTS,), U32),    # rbuf: in-flight read words
            pltpu.SMEM((RMW_SLOTS,), U32),    # wbuf: in-flight write words
            pltpu.SMEM((m,), U32),            # gbuf: per-lane grant bits
            pltpu.SMEM((WIN,), I32),          # win_row: recent granted rows
            pltpu.VMEM((max(hot_n, 1),), U32),  # hot arb prefix residency
            pltpu.SemaphoreType.DMA((RMW_SLOTS,)),
            pltpu.SemaphoreType.DMA((RMW_SLOTS,)),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    arb2, grant = pl.pallas_call(
        functools.partial(_arbitrate_kernel, k_arb, hot_n),
        name="lock_arbitrate",
        grid_spec=grid_spec,
        out_shape=tuple(_out(shp, rows, active, step, arb)
                        for shp in (arb.shape, (m,))),
        # operand 3 (post scalar-prefetch) -> output 0: in-place arb update
        input_output_aliases={3: 0},
        interpret=bool(interpret),
    )(rows.astype(I32), active.astype(I32),
      step.reshape(1).astype(U32), arb)
    return arb2, grant


# ------------------------------------------------- round-12 megakernels
#
# Two fusions that each swallow a PAIR of adjacent waves of the engine
# step (PERF.md round 12), shortening the dependency chain from ~6
# dispatches to ~4:
#
# * lock_validate — the lock-arbitration RMW (_arb_rmw, including its
#   hot_n VMEM prefix residency) composed with the OCC validate read and
#   the next cohort's fresh meta read in ONE dispatch. meta and arb are
#   disjoint arrays, so phase order inside the kernel cannot change any
#   output and the round-6 first-lane-wins proof carries verbatim.
#
# * gather_streams / scatter_streams — N independent row-gather /
#   masked-row-scatter rings run back-to-back inside one dispatch (the
#   install table write, its mirror write-through, and the replication-
#   log append become one kernel: install_log). Each stream is the
#   round-6/round-10 single-target ring verbatim; only the dispatch
#   boundary between them is removed. Streams must target DISJOINT
#   arrays; indices < 0 are masked lanes (no traffic); masked-in indices
#   per stream must be unique — the engines' one-writer-per-row
#   certification, identical to their unique_indices=True XLA scatters.


def _lock_validate_kernel(k_arb: int, hot_n: int, vidx_ref, vv1_ref,
                          ridx_ref, rows_ref, act_ref, t_ref, meta_in,
                          arb_in, arb_out, grant_out, vbad_out, rmeta_out,
                          rbuf, wbuf, gbuf, win_row, hot_vmem, vrbuf, vb,
                          rsem, wsem, gsem, hsem, vsem, vbsem, msem):
    """The lock+validate megakernel: (1) ring-gather each validate lane's
    packed meta word into SMEM and compare against the expected version
    (vb[i] = word != vv1[i]); (2) ring-gather the next cohort's fresh
    meta words straight to HBM (_gather_kernel verbatim); (3) run the
    arbitration RMW (_arb_rmw verbatim); (4) DMA the grant bits and
    validate verdicts out. meta_in and arb_out are disjoint arrays, so
    the phases commute with the unfused two-dispatch schedule bit for
    bit."""
    v = vidx_ref.shape[0]
    t = t_ref[0]

    def vcopy(i):
        return pltpu.make_async_copy(
            meta_in.at[pl.ds(vidx_ref[i], 1)],
            vrbuf.at[pl.ds(jax.lax.rem(i, RMW_SLOTS), 1)],
            vsem.at[jax.lax.rem(i, RMW_SLOTS)])

    def vprime(i, _):
        vcopy(i).start()
        return 0

    jax.lax.fori_loop(0, min(RMW_SLOTS, v), vprime, 0)

    def vbody(i, _):
        vcopy(i).wait()
        word = vrbuf[jax.lax.rem(i, RMW_SLOTS)]
        vb[i] = jax.lax.select(word != vv1_ref[i], U32(1), U32(0))

        # the slot's word was consumed above, so reuse is hazard-free
        @pl.when(i + RMW_SLOTS < v)
        def _():
            vcopy(i + RMW_SLOTS).start()

        return 0

    jax.lax.fori_loop(0, v, vbody, 0)

    _gather_kernel(1, NSLOTS, ridx_ref, meta_in, rmeta_out, msem)

    _arb_rmw(k_arb, hot_n, rows_ref, act_ref, t, arb_out, rbuf, wbuf,
             gbuf, win_row, hot_vmem, rsem, wsem, hsem)

    gout = pltpu.make_async_copy(gbuf, grant_out, gsem)
    gout.start()
    gout.wait()
    vout = pltpu.make_async_copy(vb, vbad_out, vbsem)
    vout.start()
    vout.wait()


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def lock_validate(arb, meta, vidx, vv1, ridx, rows, active, step,
                  k_arb: int, interpret: bool | None = None,
                  hot_n: int = 0):
    """Fused lock+validate pass. Returns (arb', grant u32[M], vbad u32[V],
    rmeta u32[R]) where (arb', grant) are bit-identical to
    `lock_arbitrate(arb, rows, active, step, k_arb, hot_n=hot_n)`,
    `vbad[i] = (meta[vidx[i]] != vv1[i])` (the OCC validate verdict; the
    engine masks it with is_read afterwards exactly as it masked the
    unfused compare), and `rmeta = meta[ridx]` (the next cohort's version
    seeds, == gather_rows(meta, ridx, 1)). All indices must be in-bounds
    (sentinel-clamped by the engines, same contract as gather_rows). The
    arb buffer is donated and updated in place."""
    if interpret is None:
        interpret = use_interpret()
    m = rows.shape[0]
    v = vidx.shape[0]
    r = ridx.shape[0]
    assert 0 <= hot_n <= arb.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[
            pltpu.SMEM((RMW_SLOTS,), U32),    # rbuf: in-flight read words
            pltpu.SMEM((RMW_SLOTS,), U32),    # wbuf: in-flight write words
            pltpu.SMEM((m,), U32),            # gbuf: per-lane grant bits
            pltpu.SMEM((WIN,), I32),          # win_row: recent granted rows
            pltpu.VMEM((max(hot_n, 1),), U32),  # hot arb prefix residency
            pltpu.SMEM((RMW_SLOTS,), U32),    # vrbuf: in-flight meta words
            pltpu.SMEM((v,), U32),            # vb: per-lane validate bits
            pltpu.SemaphoreType.DMA((RMW_SLOTS,)),   # rsem
            pltpu.SemaphoreType.DMA((RMW_SLOTS,)),   # wsem
            pltpu.SemaphoreType.DMA(()),             # gsem
            pltpu.SemaphoreType.DMA(()),             # hsem
            pltpu.SemaphoreType.DMA((RMW_SLOTS,)),   # vsem
            pltpu.SemaphoreType.DMA(()),             # vbsem
            pltpu.SemaphoreType.DMA((NSLOTS,)),      # msem (rmeta ring)
        ],
    )
    arb2, grant, vbad, rmeta = pl.pallas_call(
        functools.partial(_lock_validate_kernel, k_arb, hot_n),
        name="lock_validate",
        grid_spec=grid_spec,
        out_shape=tuple(
            _out(shp, vidx, vv1, ridx, rows, active, step, meta, arb)
            for shp in (arb.shape, (m,), (v,), (r,))),
        # operand 7 (post scalar-prefetch: meta, arb) -> output 0
        input_output_aliases={7: 0},
        interpret=bool(interpret),
    )(vidx.astype(I32), vv1.astype(U32), ridx.astype(I32),
      rows.astype(I32), active.astype(I32), step.reshape(1).astype(U32),
      meta, arb)
    return arb2, grant, vbad, rmeta


def _gather_streams_kernel(vws: tuple, nslots: int, *refs):
    s_n = len(vws)
    idxs = refs[:s_n]
    tabs = refs[s_n:2 * s_n]
    outs = refs[2 * s_n:3 * s_n]
    sems = refs[3 * s_n:]
    for s in range(s_n):
        _gather_kernel(vws[s], nslots, idxs[s], tabs[s], outs[s], sems[s])


@functools.partial(jax.jit, static_argnums=(2, 3))
def gather_streams(tabs, idxs, vws: tuple, interpret: bool | None = None):
    """N independent row gathers in ONE dispatch: stream s gathers
    `idxs[s]` rows of `vws[s]` u32 words from `tabs[s]` — each stream is
    _gather_kernel verbatim, so per-stream semantics equal
    `gather_rows(tabs[s], idxs[s], vws[s])` bit for bit. Returns a tuple
    of u32 [K_s * vws[s]] arrays."""
    if interpret is None:
        interpret = use_interpret()
    tabs = tuple(tabs)
    idxs = tuple(i.astype(I32) for i in idxs)
    s_n = len(vws)
    assert len(tabs) == len(idxs) == s_n
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=s_n,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * s_n,
        out_specs=tuple(pl.BlockSpec(memory_space=pl.ANY)
                        for _ in range(s_n)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((NSLOTS,))
                        for _ in range(s_n)],
    )
    return pl.pallas_call(
        functools.partial(_gather_streams_kernel, tuple(vws), NSLOTS),
        name="gather_streams",
        grid_spec=grid_spec,
        out_shape=tuple(
            _out((idxs[s].shape[0] * vws[s],), *idxs, *tabs)
            for s in range(s_n)),
        interpret=bool(interpret),
    )(*idxs, *tabs)


def _xla_gather_streams(tabs, idxs, vws):
    """XLA form of gather_streams (per-stream flat gathers) — the probe
    ground truth and the shape the unfused engine paths already emit."""
    outs = []
    for tab, idx, vw in zip(tabs, idxs, vws):
        idx = idx.astype(I32)
        flat = (idx[:, None] * vw + jnp.arange(vw, dtype=I32)).reshape(-1)
        outs.append(tab[flat])
    return tuple(outs)


def _scatter_one_stream(vw: int, nslots: int, idx_ref, vals_ref, out_ref,
                        trk, sem):
    """One masked row-scatter ring (idx < 0 = masked lane, no traffic):
    the scatter_rows_hot single-target discipline — a per-slot SMEM
    tracker records WHICH lane's copy occupies a ring slot so reuse
    force-waits exactly the copies that were started."""
    k = idx_ref.shape[0]

    def cp(i):
        return pltpu.make_async_copy(
            vals_ref.at[pl.ds(i * vw, vw)],
            out_ref.at[pl.ds(idx_ref[i] * vw, vw)],
            sem.at[jax.lax.rem(i, nslots)])

    def init(s, _):
        trk[s] = I32(-1)
        return 0

    jax.lax.fori_loop(0, nslots, init, 0)

    def body(i, _):
        s = jax.lax.rem(i, nslots)

        @pl.when(trk[s] >= 0)
        def _():
            cp(trk[s]).wait()

        trk[s] = I32(-1)

        @pl.when(idx_ref[i] >= 0)
        def _():
            cp(i).start()
            trk[s] = i

        return 0

    jax.lax.fori_loop(0, k, body, 0)

    def drain(s, _):
        @pl.when(trk[s] >= 0)
        def _():
            cp(trk[s]).wait()

        return 0

    jax.lax.fori_loop(0, nslots, drain, 0)


def _scatter_streams_kernel(vws: tuple, nslots: int, *refs):
    s_n = len(vws)
    idxs = refs[:s_n]
    vals = refs[s_n:2 * s_n]
    # refs[2*s_n : 3*s_n] are the aliased table INPUTS — never read; the
    # in-place targets are the aliased outputs
    outs = refs[3 * s_n:4 * s_n]
    trks = refs[4 * s_n:5 * s_n]
    sems = refs[5 * s_n:]
    for s in range(s_n):
        _scatter_one_stream(vws[s], nslots, idxs[s], vals[s], outs[s],
                            trks[s], sems[s])


@functools.partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0,))
def scatter_streams(tabs, idxs, vals, vws: tuple,
                    interpret: bool | None = None):
    """N independent masked row scatters in ONE dispatch (the install_log
    megakernel): stream s writes `vals[s]` row i into
    `tabs[s][idxs[s][i]*vw +: vw]` for every lane with `idxs[s][i] >= 0`;
    lanes with idx < 0 write nothing. Streams must target DISJOINT
    arrays; masked-in indices per stream must be unique (the engines'
    one-writer-per-row certification). Every table is donated and updated
    in place; returns the updated tuple, bit-identical per stream to the
    engines' `tab.at[flat].set(vals, mode="drop", unique_indices=True)`
    with the mask folded onto an OOB row."""
    if interpret is None:
        interpret = use_interpret()
    tabs = tuple(tabs)
    idxs = tuple(i.astype(I32) for i in idxs)
    vals = tuple(vals)
    s_n = len(vws)
    assert len(tabs) == len(idxs) == len(vals) == s_n
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=s_n,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (2 * s_n),
        out_specs=tuple(pl.BlockSpec(memory_space=pl.ANY)
                        for _ in range(s_n)),
        scratch_shapes=(
            [pltpu.SMEM((NSLOTS,), I32) for _ in range(s_n)]
            + [pltpu.SemaphoreType.DMA((NSLOTS,)) for _ in range(s_n)]),
    )
    return pl.pallas_call(
        functools.partial(_scatter_streams_kernel, tuple(vws), NSLOTS),
        name="scatter_streams",
        grid_spec=grid_spec,
        out_shape=tuple(_out(t.shape, *idxs, *vals, *tabs) for t in tabs),
        # operands 2S+s (post scalar-prefetch: vals x S, tabs x S) -> s
        input_output_aliases={2 * s_n + s: s for s in range(s_n)},
        interpret=bool(interpret),
    )(*idxs, *vals, *tabs)


def _xla_scatter_streams(tabs, idxs, vals, vws):
    """XLA form of scatter_streams: per-stream 1-D unique-index drop
    scatters with masked lanes folded onto the OOB row — exactly the
    shape the unfused engine installs already emit."""
    outs = []
    for tab, idx, val, vw in zip(tabs, idxs, vals, vws):
        idx = idx.astype(I32)
        n = tab.shape[0] // vw
        widx = jnp.where(idx >= 0, idx, n)
        wflat = (widx[:, None] * vw + jnp.arange(vw, dtype=I32)).reshape(-1)
        outs.append(tab.at[wflat].set(val.astype(U32), mode="drop",
                                      unique_indices=True))
    return tuple(outs)


# ------------------------------------------------------- probe plumbing


class KernelRefused(RuntimeError):
    """A Pallas kernel the caller asked for does not compile, or does not
    match its XLA form, on this backend."""


def _probe_key(kernel: str, *geom) -> tuple:
    return (kernel, jax.default_backend(), use_interpret()) + geom


# probes that PASSED, keyed (kernel, backend, interpret, geometry...): a
# builder rebuild that reuses one kernel's geometry never re-compiles that
# kernel's probe. A refusal is never cached: it raises every time
_probe_cache: set[tuple] = set()


def _probed(key, probe) -> bool:
    if key in _probe_cache:
        return True
    try:
        probe()
    except Exception as e:  # Mosaic refusal / SMEM overflow / mismatch
        raise KernelRefused(
            f"pallas kernel {key[0]!r} (geometry {key[3:]}) was asked for "
            f"and is refused on backend {key[1]!r}: {e}") from e
    _probe_cache.add(key)
    return True


def _probe_gather(n_idx: int) -> bool:
    def probe():
        n = 64
        tab = jnp.arange(n * 4, dtype=U32)
        idx = (jnp.arange(n_idx, dtype=I32) * 7) % n
        got = gather_rows(tab, idx, 4)
        want = jnp.take(tab.reshape(n, 4), idx, axis=0).reshape(-1)
        if not bool(jnp.array_equal(got, want)):
            raise RuntimeError("gather_rows output != XLA gather")

    return _probed(_probe_key("gather", n_idx), probe)


def _probe_lock(m_lock: int, k_arb: int, hot_n: int = 0) -> bool:
    def probe():
        n = 64
        arb = jnp.zeros((n + 1,), U32)
        rows = (jnp.arange(m_lock, dtype=I32) * 3) % n
        act = jnp.ones((m_lock,), bool)
        arb2, grant = lock_arbitrate(arb, rows, act, jnp.asarray(2, U32),
                                     k_arb, hot_n=hot_n)
        jax.block_until_ready((arb2, grant))

    return _probed(_probe_key("lock", m_lock, k_arb, hot_n), probe)


def _probe_hot(n_idx: int, vw: int = 1) -> bool:
    """Compile + run the hot-set gather AND fused-install kernels at the
    caller's lane geometry with a tiny mirror, checking both against
    their XLA partitions. The mirror size does not change the eqn stream
    (it only scales the one bulk DMA), so lane geometry is the probe
    axis, like the plain gather."""
    def probe():
        n, h = 64, 16
        tab = jnp.arange(n * vw, dtype=U32)
        mirror = tab[:h * vw]
        idx = (jnp.arange(n_idx, dtype=I32) * 7) % n
        midx = jnp.where(idx < h, idx, -1)
        got = gather_rows_hot(tab, mirror, idx, midx, vw)
        want = _xla_hot_gather(tab, mirror, idx, midx, vw)
        if not bool(jnp.array_equal(got, want)):
            raise RuntimeError("gather_rows_hot output != XLA partition")
        # masked writers must be unique rows: mask the first min(n, k)
        # lanes, one row each, straddling the hot boundary
        lane = jnp.arange(n_idx, dtype=I32)
        uniq = (lane < n) & ((lane % 3) == 0)
        rows = jax.lax.rem(lane, I32(n))
        vals = jnp.arange(n_idx * vw, dtype=U32)
        hmidx = jnp.where(rows < h, rows, -1)
        t_p, m_p = scatter_rows_hot(jnp.array(tab), jnp.array(mirror),
                                    rows, hmidx, uniq, vals, vw)
        t_x, m_x = hot_scatter(jnp.array(tab), jnp.array(mirror), rows,
                               hmidx, uniq, vals, vw, use_pallas=False)
        if not (bool(jnp.array_equal(t_p, t_x))
                and bool(jnp.array_equal(m_p, m_x))):
            raise RuntimeError("scatter_rows_hot output != XLA partition")

    return _probed(_probe_key("hot", n_idx, vw), probe)


def _probe_scan(n_idx: int, lg: int, vw: int) -> bool:
    """Compile + run scan_rows at the caller's lane geometry over a tiny
    run and check it bit-for-bit against the XLA slab gather."""
    def probe():
        n = max(lg + 8, 64)
        hi = jnp.arange(n, dtype=U32)
        lo = hi * U32(3)
        ver = hi + U32(100)
        val = jnp.arange(n * vw, dtype=U32)
        off = ((jnp.arange(n_idx, dtype=I32) * 7) % (n - lg))
        order = jnp.argsort(off)
        got = scan_rows(hi, lo, ver, val, off, order, lg, vw)
        want = _xla_scan_slab(hi, lo, ver, val, off, lg, vw)
        k = n_idx
        got = (got[0].reshape(k, lg), got[1].reshape(k, lg),
               got[2].reshape(k, lg), got[3].reshape(k, lg, vw))
        for g, w in zip(got, want):
            if not bool(jnp.array_equal(g, w)):
                raise RuntimeError("scan_rows output != XLA slab gather")

    return _probed(_probe_key("scan", n_idx, lg, vw), probe)


def scan_kernels_available(n_idx: int = 512, lg: int = 16,
                           vw: int = 4) -> bool:
    """Probe the dintscan streaming slab kernel: True, or KernelRefused
    (same contract as kernels_available)."""
    return _probe_scan(n_idx, lg, vw)


def kernels_available(n_idx: int = 512, m_lock: int | None = 64,
                      k_arb: int = 18) -> bool:
    """Compile AND run the requested kernels at the caller's lane geometry
    (small tables — SMEM budget scales with lane count, not table bytes),
    checking the gather against jnp.take. Returns True, or raises
    KernelRefused naming the kernel that failed. Each kernel's passing
    probe is cached independently per (backend, interpret, geometry): one
    small compile per kernel per runner configuration, once per
    process."""
    _probe_gather(n_idx)
    if m_lock is not None:
        _probe_lock(m_lock, k_arb)
    return True


def hot_kernels_available(n_idx: int = 512, vw: int = 1,
                          m_lock: int | None = None, k_arb: int = 18,
                          hot_n: int = 16) -> bool:
    """Probe the hot-set kernel family (gather + fused install, plus the
    hot-prefix lock pass when m_lock is given): True, or KernelRefused."""
    _probe_hot(n_idx, vw)
    if m_lock is not None:
        _probe_lock(m_lock, k_arb, hot_n=min(hot_n, 16))
    return True


def resolve_use_pallas(explicit: bool | None = None, *, n_idx: int = 512,
                       m_lock: int | None = 64, k_arb: int = 18) -> bool:
    """Engine-builder entry point: explicit kwarg wins, else the
    DINT_USE_PALLAS env; when requested, the probe runs at the builder's
    real lane geometry and a Mosaic refusal raises KernelRefused — the
    caller asked for the kernels and does not get the XLA route in their
    place."""
    if explicit is None:
        explicit = env_use_pallas()
    if not explicit:
        return False
    return kernels_available(n_idx=n_idx, m_lock=m_lock, k_arb=k_arb)


# ------------------------------------------- round-12 megakernel probes


def _probe_lockv(n_val: int, n_read: int, m_lock: int, k_arb: int,
                 hot_n: int = 0) -> bool:
    """Compile + run lock_validate at the caller's lane geometry and check
    it against the COMPOSITION it replaces: lock_arbitrate (itself proven
    against the XLA chain) + the direct meta gathers/compares."""
    def probe():
        n = 64
        meta = ((jnp.arange(n, dtype=U32) * U32(7)) << 1) | U32(1)
        arb = jnp.zeros((n + 1,), U32)
        vidx = (jnp.arange(n_val, dtype=I32) * 5) % n
        vv1 = jnp.where(jnp.arange(n_val) % 3 == 0,
                        meta[vidx], meta[vidx] + U32(2))
        ridx = (jnp.arange(n_read, dtype=I32) * 7) % n
        rows = (jnp.arange(m_lock, dtype=I32) * 3) % n
        act = jnp.arange(m_lock) % 2 == 0
        t = jnp.asarray(2, U32)
        arb2, grant, vbad, rmeta = lock_validate(
            arb, meta, vidx, vv1, ridx, rows, act, t, k_arb, hot_n=hot_n)
        arb_u, grant_u = lock_arbitrate(jnp.array(arb), rows, act, t,
                                        k_arb, hot_n=hot_n)
        vbad_u = (meta[vidx] != vv1).astype(U32)
        rmeta_u = meta[ridx]
        if not (bool(jnp.array_equal(arb2, arb_u))
                and bool(jnp.array_equal(grant, grant_u))
                and bool(jnp.array_equal(vbad, vbad_u))
                and bool(jnp.array_equal(rmeta, rmeta_u))):
            raise RuntimeError("lock_validate output != unfused pair")

    return _probed(_probe_key("lockv", n_val, n_read, m_lock, k_arb,
                              hot_n), probe)


def _probe_gather_streams(geoms: tuple) -> bool:
    """geoms: tuple of (k, vw) per stream — the caller's real lane
    geometry (small tables; failure modes are construct-level)."""
    def probe():
        n = 64
        tabs, idxs = [], []
        for si, (k, vw) in enumerate(geoms):
            tabs.append(jnp.arange(n * vw, dtype=U32) * U32(si + 1))
            idxs.append((jnp.arange(k, dtype=I32) * (5 + si)) % n)
        vws = tuple(vw for _, vw in geoms)
        got = gather_streams(tuple(tabs), tuple(idxs), vws)
        want = _xla_gather_streams(tabs, idxs, vws)
        for g, w_ in zip(got, want):
            if not bool(jnp.array_equal(g, w_)):
                raise RuntimeError("gather_streams != XLA gathers")

    return _probed(_probe_key("gstreams", geoms), probe)


def _probe_scatter_streams(geoms: tuple) -> bool:
    """geoms: tuple of (k, vw) per stream. Masked-in rows are unique per
    stream (the engines' contract); masked lanes carry idx = -1."""
    def probe():
        n = 64
        tabs, idxs, vals = [], [], []
        for si, (k, vw) in enumerate(geoms):
            tabs.append(jnp.arange(n * vw, dtype=U32))
            lane = jnp.arange(k, dtype=I32)
            uniq = (lane < n) & (lane % (2 + si % 2) == 0)
            idxs.append(jnp.where(uniq, lane % n, -1))
            vals.append(jnp.arange(k * vw, dtype=U32) + U32(si))
        vws = tuple(vw for _, vw in geoms)
        got = scatter_streams(tuple(jnp.array(tb) for tb in tabs),
                              tuple(idxs), tuple(vals), vws)
        want = _xla_scatter_streams(tabs, idxs, vals, vws)
        for g, w_ in zip(got, want):
            if not bool(jnp.array_equal(g, w_)):
                raise RuntimeError("scatter_streams != XLA scatters")

    return _probed(_probe_key("sstreams", geoms), probe)


def fused_kernels_available(*, lockv=None, gathers=None,
                            scatters=None) -> bool:
    """Probe the round-12 megakernels. ``lockv`` is (n_val, n_read,
    m_lock, k_arb, hot_n) or None; ``gathers`` / ``scatters`` are tuples
    of per-stream (k, vw) geometry or None. True, or KernelRefused; same
    per-(backend, interpret, geometry) cache as kernels_available."""
    if lockv is not None:
        n_val, n_read, m_lock, k_arb, hot_n = lockv
        _probe_lockv(n_val, n_read, m_lock, k_arb, hot_n=min(hot_n, 16))
    if gathers:
        _probe_gather_streams(tuple(gathers))
    if scatters:
        _probe_scatter_streams(tuple(scatters))
    return True


def resolve_use_fused(explicit: bool | None = None, *, lockv=None,
                      gathers=None, scatters=None) -> bool:
    """Engine-builder gate for the fused wave pairs: explicit kwarg wins,
    else the DINT_USE_FUSED env (default off — PERF.md round-12 decision
    rule); when requested, every megakernel the engine would dispatch is
    probed at its real geometry and any refusal raises KernelRefused."""
    if explicit is None:
        explicit = env_use_fused()
    if not explicit:
        return False
    return fused_kernels_available(lockv=lockv, gathers=gathers,
                                   scatters=scatters)
