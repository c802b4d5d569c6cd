"""Sort-free dense TATP engine: the TPU-first fast path.

The generic engine (engines/tatp.py) resolves intra-batch conflicts with
64-bit sorts + segmented reductions over EVERY lane x 3 vmapped shard
replicas — protocol-faithful but ~200x off the reference's throughput
(tatp/ebpf/shard_kern.c:111-197 does one hash + one CAS per packet). This
module is the redesign that removes the sort entirely, exploiting three
structural facts the reference cannot:

1. **Every TATP table is dense-indexable.** SUBSCRIBER/SEC_SUBSCRIBER/
   ACCESS_INFO/SPECIAL_FACILITY index by s_id directly (tatp/caladan/
   tatp.h:28), and even CALL_FORWARDING's composite key
   ``s_id*12 + (sf_type-1)*3 + start_time/8`` is bounded by 12*(n_sub+1),
   so the "sparse" table is a dense array plus an `exists` bit. The
   reference hashes it (tatp/ebpf/shard_kern.c:61-108) only because its
   kvs.h is generic; no bloom filter is needed when lookups are exact.
   All 5 tables live in ONE flat row-id space:
   rows [0,p1) sub | [p1,2p1) sec | [2p1,6p1) ai | [6p1,10p1) sf |
   [10p1,22p1) cf, with row N as the gather/scatter sentinel for NOP lanes.

2. **The 3 servers' lock tables partition by key.** Locks for key k are
   only ever taken at server k%3 (tatp/caladan/client_ebpf_shard.cc:
   636-641), so the union of the 3 per-server lock arrays is one exact
   per-row lock bit — no routing, no hash conflation (exact locks also
   remove the reference's false REJECT_LOCK on hash collisions, the
   ablation its lock_kern.c instrumentation exists to measure).

3. **Replicas are bit-identical by construction.** Every certified write
   applies at primary + both backups (client_ebpf_shard.cc:779-900), so
   the single-chip engine stores table content ONCE and keeps the
   replication physical where it matters for recovery: the log x3
   (tables/log.RepLog packs 3 replica entries per slot). The multi-chip
   path (parallel/sharded.py) places real per-device replicas; a
   single-chip emulation holding 3 bit-identical copies in one HBM adds
   no fidelity — it only triples memory (measured: XLA tiles [N, 3, VW]
   u32 to 2 KB/row, 4.5 GB for the bench's 2.2M rows).

Per-row metadata packs into ONE u32 word (`meta`):

    bits [31:1] = ver   (monotonic: commit/insert/delete all bump it, so
                         OCC validate is an equality compare with no
                         delete/reinsert ABA window)
    bit  0      = exists

`meta` IS the value OCC validation compares — reads never observe locks,
exactly the reference's verify stage (client_ebpf_shard.cc:765-768),
because locks live in a SEPARATE step-stamped arbitration array (`arb`):

    arb[row] = step_granted << K_ARB | (2w-1 - winning_slot)

Every lock in the 3-stage pipeline has a FIXED lifetime — granted in
wave 1 of step t, released in wave 3 of step t+2 (commit, insert,
delete, and abort all release then) — so releases need no scatter at
all: a row is held iff ``(arb >> K_ARB) == step - 1``, and stamps from
step-2 or older have simply expired (the same expiring-stamp design as
smallbank_dense's S/X tables). This removes BOTH wave-3 release lanes
and the wave-1 grant scatter from the meta dependency chain: the table
chain is install-scatter -> gather (2 random ops) and the lock chain is
gather -> scatter-max -> gather (3 random ops) on an INDEPENDENT array,
so XLA overlaps them — measured on v5e, the serialized 5-op meta chain
was the step's critical path (PERF.md round 3).

Conflict resolution per fused step (replacing ops/segments.sort_batch):
  * commits: X-certified one-writer-per-row -> direct scatter.
  * lock acquires: first-slot-wins via scatter-MAX of the packed
    (step, inverted slot) stamp — the batched equivalent of the
    reference's CAS loop (shard_kern.c:251-297). Candidates targeting a
    HELD row (stamp == step-1) are masked out of the scatter, so a
    stream of rejected attempts cannot re-stamp (livelock) a hot row.
    Arbitration runs in [w, 2] write-slot space (2 lock slots per txn),
    measured 2x cheaper than arbitrating all [w, K] lanes.
  * reads/validates: pure gathers.

Scatter discipline (TPU, all measured on v5e): every scatter is 1-D or
row-major on axis 0 with ``unique_indices=True`` and masked lanes routed
OUT OF BOUNDS under ``mode="drop"`` — duplicate-index and multi-dim-index
scatters serialize, and uniqueness is guaranteed by certification (one
X-lock holder per row). Row N is a never-written sentinel that NOP lanes
gather from; OOB gather indices clip onto it. A dropped index costs what a
live one costs (PR 28's trace: the value scatter took 13.31 ms with no live
slot and 13.33 with ~1,430; 81 ns a value word, 87 a meta word, 132 a log
row, landed or dropped), so a mask is compacted before it reaches a
scatter: the install and the log append issue the live write slots, C lanes
a chunk (ops/compact.py), not all 2w of which TATP's mix leaves 9 % live.

The 3-stage software pipeline (wave 1 of cohort t + validate of t-1 +
commit of t-2 fused into ONE device program) is inherited from
engines/tatp_pipeline.py, which remains the semantics reference; its
gen_cohort (txn mix, NURand, lane layout) is reused verbatim.

Memory: ~22*(n_sub+1) rows; val dominates at N*VW u32 words in a tight
interleaved 1-D layout (40 B/row at VW=10 — see DenseDB.val). At the
reference's full n_sub=7e6 (tatp/caladan/tatp.h:28) that is ~6.2 GB val
+ 0.6 GB meta + the log — single-chip HBM, populated on device
(populate_device). The multi-chip shard path (parallel/dense_sharded.py)
multiplies throughput, not feasibility.
"""
from __future__ import annotations

import functools

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from ._memo import memoize_builder, refuse_retired
from ..monitor import counters as mon
from ..monitor import txnevents as txe
from ..monitor import waves
from ..ops import compact
from ..tables import log as logring
from . import tatp
from .types import Op, Reply
from .tatp_pipeline import (K, MAGIC, N_SHARDS, READ_LANES, classify_wave1,
                            gen_cohort)
from .tatp_pipeline import (STAT_ATTEMPTED, STAT_COMMITTED, STAT_AB_LOCK,     # noqa: F401 (re-exported)
                            STAT_AB_MISSING, STAT_AB_VALIDATE, STAT_MAGIC_BAD,
                            N_STATS)

I32 = jnp.int32
U32 = jnp.uint32

# arb stamp layout: step << K_ARB | (2w-1 - slot). Supports w <= 2^17 and
# 2^(32-K_ARB) = 16384 steps between rebases (build_pipelined_runner
# rebases the stamps when step approaches the limit).
K_ARB = 18
REBASE_AT = (1 << (32 - K_ARB)) - 4096


def _bases(p1: int) -> np.ndarray:
    """Flat row-id base per table id (tatp.SUBSCRIBER..tatp.CALL_FORWARDING)."""
    return np.cumsum([0, p1, p1, 4 * p1, 4 * p1]).astype(np.int32)


def n_rows(n_sub: int) -> int:
    return 22 * (n_sub + 1)


@flax.struct.dataclass
class DenseDB:
    """All 5 TATP tables + locks + logs in flat dense arrays (row N is the
    sentinel every NOP/padded lane gathers from; it is never written).

    ``val`` is a tight interleaved 1-D word array (row r's words at
    [r*VW, (r+1)*VW)) — NOT [N+1, VW]: XLA tiles a trailing dim of 10 to
    128 lanes (512 B/row), which put the reference's 7M-subscriber scale
    (tatp/caladan/tatp.h:28, 154M rows) at 79 GB. The 1-D layout is the
    same one the multi-chip backups always used
    (parallel/dense_sharded.ShardState) and costs 40 B/row: ~6.2 GB at
    7M subscribers, single-chip HBM."""
    val: jax.Array      # u32 [(N+1) * VW] interleaved; word0 payload, word1 magic
    meta: jax.Array     # u32 [N+1]      ver<<1 | exists
    arb: jax.Array      # u32 [N+1]      step-stamped lock arbitration word
    step: jax.Array     # u32 scalar, monotonic (starts at 2: stamp 0 is
                        #   "never held", so step-1 must never be 0)
    log: logring.RepLog   # 3 replica entries packed per slot (log x3)
    val_words: int = flax.struct.field(pytree_node=False, default=10)

    @property
    def n_sub(self):
        return self.meta.shape[0] // 22 - 1

    @property
    def val2d(self):
        """[..., N+1, VW] view for tests / recovery / oracles (materializes
        a tiled copy on device — NOT the hot path)."""
        return self.val.reshape(self.val.shape[:-1]
                                + (-1, self.val_words))

    # convenience views (tests / recovery / oracles — not the hot path)
    @property
    def ver(self):
        return self.meta >> 1

    @property
    def exists(self):
        return (self.meta & 1) != 0

    @property
    def locked(self):
        """Rows X-held RIGHT NOW: stamped by the previous step (stamps
        from step-2 and older have expired). Works on stacked
        [..., N+1] state too."""
        return (self.arb >> K_ARB) == (self.step[..., None] - 1)


def create(n_sub: int, val_words: int = 10, log_lanes: int = 16,
           log_capacity: int = 1 << 16,
           log_replicas: int = N_SHARDS) -> DenseDB:
    """``log_replicas``: the single-chip engine packs the log x3 locally;
    the multi-chip path (parallel/dense_sharded.py) passes 1 because the
    3 copies live on 3 devices there.

    ``log_capacity`` bounds the recovery window: the ring wraps like the
    reference's (ls_kern.c:72-73) and recover_* refuses a wrapped ring —
    at bench throughput the 1M-entry default wraps within ~1 s; pass a
    larger capacity when recovery artifacts are wanted."""
    n1 = n_rows(n_sub) + 1
    # flat word indices (row * VW + j) are computed in i32 on device
    assert n1 * val_words < (1 << 31), \
        f"n_sub={n_sub} x val_words={val_words} overflows i32 row*VW indices"
    return DenseDB(
        val=jnp.zeros((n1 * val_words,), U32),
        meta=jnp.zeros((n1,), U32),
        arb=jnp.zeros((n1,), U32),
        step=jnp.asarray(2, U32),
        log=logring.create_rep(log_lanes, log_capacity, val_words,
                               replicas=log_replicas),
        val_words=val_words,
    )


def populate(rng: np.random.Generator, n_sub: int, val_words: int = 10,
             **kw) -> DenseDB:
    """Same population as clients/tatp_client.populate_shards (reference
    populate: tatp/caladan/client_ebpf_shard.cc:96-341): all subscribers
    present, ai/sf types present w.p. 0.625 (>=1 each), CF rows on 25% of
    present sf rows per start_time; val word0 = row payload, word1 = magic
    (tatp/caladan/tatp.h:67-72)."""
    p1 = n_sub + 1
    db = create(n_sub, val_words=val_words, **kw)
    n1 = n_rows(n_sub) + 1
    base = _bases(p1)

    val = np.zeros((n1, val_words), np.uint32)
    meta = np.zeros(n1, np.uint32)

    def put(rows, payload):
        val[rows, 0] = payload.astype(np.uint32)
        val[rows, 1] = MAGIC
        meta[rows] = (1 << 1) | 1             # ver 1, exists

    s_ids = np.arange(1, p1)
    put(base[tatp.SUBSCRIBER] + s_ids, s_ids)
    put(base[tatp.SEC_SUBSCRIBER] + s_ids, s_ids)

    ai_present = rng.random((p1, 4)) < 0.625
    sf_present = rng.random((p1, 4)) < 0.625
    ai_present[0] = sf_present[0] = False
    ai_present[1:][ai_present[1:].sum(1) == 0, 0] = True
    sf_present[1:][sf_present[1:].sum(1) == 0, 0] = True
    ai_idx = np.nonzero(ai_present.reshape(-1))[0]
    sf_idx = np.nonzero(sf_present.reshape(-1))[0]
    put(base[tatp.ACCESS_INFO] + ai_idx, ai_idx)
    put(base[tatp.SPECIAL_FACILITY] + sf_idx, sf_idx)

    sfi, sft = np.nonzero(sf_present)
    cf_keys = []
    for st in (0, 8, 16):
        mask = rng.random(len(sfi)) < 0.25
        cf_keys.append(np.asarray(tatp.cf_key(sfi[mask], sft[mask] + 1, st)))
    cf_keys = np.unique(np.concatenate(cf_keys)).astype(np.int64)
    put(base[tatp.CALL_FORWARDING] + cf_keys, cf_keys)

    return db.replace(val=jnp.asarray(val.reshape(-1)),
                      meta=jnp.asarray(meta))


def populate_device(key, n_sub: int, val_words: int = 10, **kw) -> DenseDB:
    """On-device populate for reference-scale tables: same population RULES
    as `populate` (all subscribers present; ai/sf types present w.p. 0.625
    with >=1 each; CF on 25% of present sf rows per start_time —
    tatp/caladan/client_ebpf_shard.cc:96-341) drawn from the device RNG, so
    the 6+ GB val array at n_sub=7e6 is generated in HBM instead of being
    built in host numpy and copied to the device. Not bit-identical to
    the numpy path (different RNG stream); distribution-identical, which is
    what the abort-class expectations depend on."""
    p1 = n_sub + 1
    db = create(n_sub, val_words=val_words, **kw)
    n1 = n_rows(n_sub) + 1
    base = jnp.asarray(_bases(p1))

    @jax.jit
    def build(key):
        # every draw/temp here is deliberately 1-D: a (p1, 4) or (p1, 4, 3)
        # draw pads its minor dim up to 128 lanes under TPU tiling — at
        # p1=7e6 the (p1,4,3) bernoulli padded 42.7x to 13.35 GB and OOMed
        # the 16 GB chip AT COMPILE TIME (measured, round 5). Flat layouts
        # pad 1.0x; per-subscriber reductions use strided slices instead
        # of a trailing axis.
        k_ai, k_sf, k_cf = jax.random.split(key, 3)
        sub_e = jnp.arange(p1, dtype=I32) >= 1                  # [p1]

        def present(k):
            pr = jax.random.bernoulli(k, 0.625, (p1 * 4,))      # idx=s*4+t
            any4 = pr[0::4] | pr[1::4] | pr[2::4] | pr[3::4]
            pr = pr.at[0::4].set(pr[0::4] | ~any4)              # >=1 each
            # s = idx//4: 1-D gather instead of a [p1,4] broadcast
            return pr & sub_e[jnp.arange(p1 * 4, dtype=I32) // 4]

        ai_p = present(k_ai)                                    # [4*p1]
        sf_p = present(k_sf)
        # cf rows flat [12*p1]: idx = s*12 + (sf_type-1)*3 + start_time/8,
        # exactly tatp.cf_key's layout; idx//3 is the covering sf element
        cf_p = sf_p[jnp.arange(p1 * 12, dtype=I32) // 3] \
            & jax.random.bernoulli(k_cf, 0.25, (p1 * 12,))
        exists = jnp.concatenate([
            sub_e, sub_e, ai_p, sf_p, cf_p,
            jnp.zeros((1,), bool)])                             # [n1]
        meta = jnp.where(exists, U32((1 << 1) | 1), U32(0))

        # payload = index within the row's table region (populate's `put`);
        # 5 scalar compares instead of searchsorted's vmapped while loop
        rows = jnp.arange(n1, dtype=I32)
        region = sum((rows >= base[i]).astype(I32) for i in range(1, 5))
        payload = (rows - base[region]).astype(U32)
        val = jnp.zeros((n1 * val_words,), U32)
        idx = jnp.where(exists, rows, n1) * val_words   # absent -> dropped
        val = val.at[idx].set(payload, mode="drop", unique_indices=True)
        val = val.at[idx + 1].set(U32(MAGIC), mode="drop",
                                  unique_indices=True)
        return val, meta

    val, meta = build(key)
    return db.replace(val=val, meta=meta)


# ---------------------------------------------------------------- pipeline


@flax.struct.dataclass
class DenseCtx:
    """An in-flight cohort between pipeline stages (cf. tatp_pipeline.PipeCtx
    — row ids and versions are captured once at wave 1). Bootstrap cohorts
    have attempted == 0 and all-False masks."""
    rows: jax.Array       # i32 [w, R] flat row ids of the read lanes
    #                       (R = READ_LANES; sentinel for NOP lanes)
    is_read: jax.Array    # bool [w, R] OCC_READ lanes
    vv1: jax.Array        # u32 [w, R] meta (ver<<1|exists) at wave 1
    alive: jax.Array      # bool [w]
    ro_commit: jax.Array  # bool [w]
    granted: jax.Array    # bool [w, 2]
    ws_rows: jax.Array    # i32 [w, 2] write-slot row ids (sentinel if inactive)
    ws_vv: jax.Array      # u32 [w, 2] write-slot ver:exists at wave 1
    ws_tbl: jax.Array     # i32 [w, 2]
    ws_key: jax.Array     # i32 [w, 2] (logged key)
    ws_kind: jax.Array    # i32 [w, 2] 0 commit / 1 insert / 2 delete
    ws_active: jax.Array  # bool [w, 2]
    attempted: jax.Array  # i32 scalar
    ab_lock: jax.Array    # i32 scalar
    ab_missing: jax.Array # i32 scalar
    ab_validate: jax.Array  # i32 scalar
    magic_bad: jax.Array  # i32 scalar


def empty_ctx(w: int) -> DenseCtx:
    def z(shape, dt):
        return jnp.asarray(np.zeros(shape, dt))

    return DenseCtx(
        rows=z((w, READ_LANES), np.int32),
        is_read=z((w, READ_LANES), bool),
        vv1=z((w, READ_LANES), np.uint32), alive=z((w,), bool),
        ro_commit=z((w,), bool), granted=z((w, 2), bool),
        ws_rows=z((w, 2), np.int32), ws_vv=z((w, 2), np.uint32),
        ws_tbl=z((w, 2), np.int32),
        ws_key=z((w, 2), np.int32), ws_kind=z((w, 2), np.int32),
        ws_active=z((w, 2), bool),
        attempted=z((), np.int32), ab_lock=z((), np.int32),
        ab_missing=z((), np.int32), ab_validate=z((), np.int32),
        magic_bad=z((), np.int32))


def _stats_of(c: DenseCtx):
    return jnp.stack([
        c.attempted, (c.ro_commit | c.alive).sum(dtype=I32),
        c.ab_lock, c.ab_missing, c.ab_validate, c.magic_bad])


@flax.struct.dataclass
class Installs:
    """Wave-3 install record of one step: what a backup replica must apply
    (parallel/dense_sharded.py ppermutes this to the +1/+2 devices — the
    reference's CommitBck x2 + CommitLog fan-out,
    client_ebpf_shard.cc:779-900). Rows are the emitting device's local
    ids; wmask marks real writes (releases are lock-only and stay local)."""
    wmask: jax.Array     # bool [2w]
    rows: jax.Array      # i32 [2w]
    meta: jax.Array      # u32 [2w]  new ver<<1|exists
    val: jax.Array       # u32 [2w, VW]
    tbl: jax.Array       # i32 [2w]  (for the log)
    key: jax.Array       # u32 [2w]
    is_del: jax.Array    # i32 [2w]
    ver: jax.Array       # u32 [2w]


def pipe_step(db: DenseDB, c1: DenseCtx, c2: DenseCtx, key, *, w: int,
              n_sub: int, val_words: int, gen_new: bool = True, mix=None,
              emit_installs: bool = False, check_magic: bool = True,
              occupancy: jax.Array | None = None,
              shed: jax.Array | None = None,
              counters: mon.Counters | None = None,
              ring: txe.TxnRing | None = None,
              tcfg: txe.TraceCfg | None = None):
    """One fused device step: commit wave of c2, validate wave of c1, and
    read+lock wave of a NEW cohort — ordered commits -> reads -> locks per
    row exactly like the generic engine's phase order (engines/tatp.
    _dense_step), so cohort t-2's installs are visible to t-1's validation
    and this step's reads, and its unlocks free rows for this step's lock
    acquires. Returns (db', new_ctx, c1', stats-of-c2), plus the Installs
    record when ``emit_installs`` (static) is set.

    ``occupancy``/``shed`` (device i32 scalars, or None = off): the
    dintserve variable-occupancy plane. Lanes >= occupancy of the freshly
    generated cohort are forced to no-ops (ops -> NOP, write slots
    deactivated) BEFORE wave 1, so a partially filled serving cohort
    certifies exactly the admitted prefix and ``attempted`` counts only
    real admissions; the value is a traced scalar, so ONE compiled step
    serves every occupancy at this width. ``shed`` mirrors the host-side
    SLO-shed tally onto the device ledger (counted like trace_dropped).
    At occupancy == w the masks are all-true and outputs are
    bit-identical to the closed-loop path (pinned in
    tests/test_dintserve.py). None (the default) adds nothing.

    ``counters`` (a monitor.Counters, or None = off): the device-resident
    counter plane. When threaded, the step bumps the dintmon registry
    in-step (txn outcomes from c2's completing stats, lock arbitration
    won-vs-lost for the new cohort, validate lanes/failures for c1,
    install/log counts, ring high-water) with
    unique-index scatter-adds and returns the updated Counters appended
    to the result tuple. None (the default) threads no counter state and
    leaves the jaxpr untouched.

    ``ring``/``tcfg`` (monitor.txnevents): the dinttrace flight-recorder
    plane — the new cohort's lock verdicts and wave-1 outcomes, c1's
    validate verdicts and wave-2 outcomes, and c2's landing installs for
    the deterministically sampled txn-id subset, ONE scatter-add per
    step. The updated TxnRing is appended LAST (after Counters and the
    Installs record); None (default) adds nothing to the jaxpr."""
    p1 = n_sub + 1
    n1 = n_rows(n_sub) + 1
    sent = n1 - 1     # sentinel row: gathered by NOP lanes, never written
    oob = n1          # scatter index for masked lanes under mode="drop"
    base = jnp.asarray(_bases(p1))
    with waves.part("tatp_dense", "step_frame"):
        kg, kv3 = jax.random.split(key)
    t = db.step

    # ---- wave 3 of c2: install + log --------------------------------------
    # the meta scatter covers ONLY real writes: lock releases are implicit —
    # c2's stamps (from step t-2) expire this step, which is exactly when
    # COMMIT/INSERT/DELETE_PRIM and ABORT release the row lock in the
    # reference (shard_kern.c:338-476). Uniqueness: one X-holder per row,
    # and a txn's two slots target different tables.
    # MACHINE-CHECKED (dintlint protocol pass, ANALYSIS.md): wmask must
    # stay data-dependent on c2.alive — the chain grant -> alive ->
    # ~changed -> wmask is what proves lock-dominates-write and
    # validate-before-install; severing it fails the tier-1 gate.
    with waves.scope("tatp_dense", "install"):
        with waves.part("tatp_dense", "install_build"):
            do_write = c2.ws_active & c2.alive[:, None]             # [w, 2]
            wmask = do_write.reshape(-1)
            wkind = c2.ws_kind.reshape(-1)
            newex = (wkind != 2) & wmask
            vv = c2.ws_vv.reshape(-1)   # wave-1 meta (ver<<1|exists): the row
            #                             was X-held since, so still current
            meta_new = (((vv >> 1) + 1) << 1) | newex.astype(U32)
            wrows = jnp.where(wmask, c2.ws_rows.reshape(-1), oob)   # [2w]
            payload = jax.random.randint(kv3, (w, 2), 0, 1 << 16, dtype=I32)
            newval = jnp.zeros((w, 2, val_words), U32)
            newval = newval.at[:, :, 0].set(payload.astype(U32))
            newval = newval.at[:, :, 1].set(
                jnp.where(do_write & (c2.ws_kind != 2), U32(MAGIC), U32(0)))
            newval = newval.reshape(-1, val_words)
            newval = jnp.where((wkind == 2)[:, None], U32(0),
                               newval)                      # delete zeroes
            newver = (vv >> 1) + 1
            flags_del = (wkind == 2).astype(I32)
            log_tbl = c2.ws_tbl.reshape(-1)
            log_key = c2.ws_key.reshape(-1).astype(U32)
            zero_hi = jnp.zeros_like(log_key)
        # the 2w slots are ~9 % live (TATP writes 0.22 slots a txn) and
        # a dropped index costs what a live one costs: the slots are
        # ranked once, for the scatters of this step (meta and val
        # here, the log's below), which issue the live ones C lanes a
        # chunk, as many chunks as this step's live count needs
        with waves.part("tatp_dense", "ws_compact"):
            ranks, n_live = compact.live_ranks(wmask)

            def install_chunk(tabs, lanes, ok):
                meta, val = tabs
                rows_c = jnp.where(ok, wrows[lanes], oob)
                meta_c, val_c = meta_new[lanes], newval[lanes]
                with waves.part("tatp_dense", "meta_scatter"):
                    meta = meta.at[rows_c].set(meta_c, mode="drop",
                                               unique_indices=True)
                # interleaved-1-D install: row r's words live at
                # [r*VW, (r+1)*VW); a position past the live count
                # rides the oob row, which lands at n1*VW >= len and
                # drops (the same chunked discipline as the backups'
                # install, parallel/dense_sharded._apply_backup)
                with waves.part("tatp_dense", "val_scatter"):
                    wflat = (rows_c[:, None] * val_words
                             + jnp.arange(val_words, dtype=I32)
                             ).reshape(-1)
                    val = val.at[wflat].set(val_c.reshape(-1),
                                            mode="drop",
                                            unique_indices=True)
                return meta, val

            (meta, val), chunks = compact.for_chunks(
                ranks, n_live, compact.chunk_lanes(2 * w), install_chunk,
                (db.meta, db.val))

    with waves.scope("tatp_dense", "log_append"):
        logs = logring.append_rep_live(
            db.log, ranks, n_live, wmask, log_tbl, flags_del, zero_hi,
            log_key, newver, newval)

    # ---- wave 1: new cohort read + lock -----------------------------------
    if gen_new:
        with waves.scope("tatp_dense", "gen"):
            ttype, ops, tbl, kk, ws = gen_cohort(kg, w, n_sub, mix=mix)
        ws_active, ws_lane, ws_tbl, ws_key, ws_kind = ws
    else:
        ttype = jnp.zeros((w,), I32)
        ops = jnp.zeros((w, K), I32)
        tbl = jnp.zeros((w, K), I32)
        kk = jnp.zeros((w, K), I32)
        ws_active = jnp.zeros((w, 2), bool)
        ws_lane = jnp.zeros((w, 2), I32)
        ws_tbl = jnp.zeros((w, 2), I32)
        ws_key = jnp.zeros((w, 2), I32)
        ws_kind = jnp.zeros((w, 2), I32)

    if occupancy is not None:
        # serving-plane occupancy mask: the cohort is generated full-width
        # (RNG stream identical to the closed-loop path) and the lanes past
        # the admitted occupancy are erased before any wave sees them —
        # NOP lanes gather the sentinel and their write slots never enter
        # arbitration, so a padded lane is provably traffic-free
        with waves.scope("tatp_dense", "serve"):
            occ = jnp.asarray(occupancy, I32)
            lane_ok = jnp.arange(w, dtype=I32) < occ
            ops = jnp.where(lane_ok[:, None], ops, Op.NOP)
            ws_active = ws_active & lane_ok[:, None]

    # the gathers issue the read lanes alone: no OCC_READ sits in a lane
    # >= READ_LANES, and a write slot's row is one of its txn's read rows
    # (gen_cohort), so its wave-1 meta is that read lane's (ws_pick)
    with waves.part("tatp_dense", "addr"):
        r_ops = ops[:, :READ_LANES]
        used = r_ops != Op.NOP
        rows = jnp.where(used, base[tbl[:, :READ_LANES]]
                         + kk[:, :READ_LANES], sent)                # [w, R]
        is_read = r_ops == Op.OCC_READ
        ws_rows = jnp.where(ws_active, base[ws_tbl] + ws_key,
                            sent)                                   # [w, 2]

    # ONE fused meta gather serves wave 2 (c1's validate re-read) AND
    # wave 1 (the new cohort's reads). Both gathers depend on the same
    # install scatter and on nothing else of each other, so XLA could
    # overlap their DMAs (PERF.md round-3 finding 3) — the fusion still
    # halves per-op launch/descriptor overhead on ops measured at
    # 0.6-0.9 ms per 16-32k random indices
    with waves.scope("tatp_dense", "meta_gather"):
        gidx = jnp.concatenate([c1.rows.reshape(-1), rows.reshape(-1)])
        g = meta[gidx]
        vvB = g[: w * READ_LANES].reshape(w, READ_LANES)    # [w, R]
        rmeta = g[w * READ_LANES:].reshape(w, READ_LANES)   # [w, R]
    with waves.part("tatp_dense", "validate"):
        bad = c1.is_read & (vvB != c1.vv1)

    # ---- wave 2 of c1: validate read-set version compare ------------------
    with waves.part("tatp_dense", "validate"):
        changed = bad.any(axis=1)
        if counters is not None or ring is not None:
            # lanes of surviving RW txns checked / failed — the same lane set
            # the generic pipeline re-reads (_validate_lanes), so the parity
            # counters are engine-independent. The flight recorder needs the
            # per-lane masks (and c1's PRE-verdict alive) for its VALIDATE
            # and wave-2 OUTCOME events, captured before the replace below.
            with waves.part("tatp_dense", "monitor"):
                v_alive = c1.alive[:, None]
                v_lanes = (c1.is_read & v_alive).sum(dtype=I32)
                v_failed = (bad & v_alive).sum(dtype=I32)
                val_mask = (c1.is_read & v_alive).reshape(-1)       # [wR]
                val_bad = (bad & v_alive).reshape(-1)               # [wR]
                c1_alive_pre = c1.alive
        c1 = c1.replace(alive=c1.alive & ~changed,
                        ab_validate=(c1.alive & changed).sum(dtype=I32))

    with waves.part("tatp_dense", "classify"):
        vv1 = rmeta                 # ver<<1|exists — locks live elsewhere
        rex = (rmeta & 1) != 0
    if check_magic:
        # the magic-parity oracle costs one [w,R] single-word gather over
        # the 6.2 GB val array per step; check_magic=False is an A/B
        # measurement knob (DINT_BENCH_CHECK_MAGIC=0) quantifying it —
        # the default keeps the reference's every-read integrity check
        with waves.scope("tatp_dense", "magic_gather"):
            midx = (rows * val_words + 1).reshape(-1)
            rmagic = val[midx].reshape(w, READ_LANES)
            magic_bad = jnp.sum(is_read & rex & (rmagic != MAGIC),
                                dtype=I32)
    else:
        magic_bad = jnp.asarray(0, I32)

    # lock arbitration in [w, 2] write-slot space: first slot wins per row
    # (batched CAS, tatp/ebpf/shard_kern.c:251-297); losers and held rows
    # REJECT. The whole chain — stamp gather, masked scatter-max, winner
    # gather-back — runs on the arb array, INDEPENDENT of the meta/val
    # install chain. held = stamped by the previous step; c2's stamps
    # (t-2) expired this step, matching the wave-3 release timing above.
    # Candidates for held rows are masked OUT of the scatter so rejected
    # attempts cannot keep a hot row stamped (no livelock).
    # The three table ops issue the ACTIVE slots only
    # (TATP's mix leaves ~11 % of the 2w active, and a dropped scatter
    # index, like a sentinel gather lane, costs what a live one costs):
    # the slots are ranked once and run C lanes a chunk, in two loops,
    # because the winner of a row is known only when every chunk's
    # scatter-max has landed. The first loop reads the held stamps from
    # the arb it carries, not from db.arb (closing over db.arb as well
    # would keep a second copy of the table alive): a row held at the
    # start of the step (stamp t-1) has every candidate masked out in
    # every chunk, so its word never changes within the step; a row not
    # held can only have gained a stamp t from an earlier chunk, and
    # (x >> K_ARB) == t - 1 is as false for that as for what it had. So
    # held, cand and arb are those of the full-width chain, and a held
    # row's word (stamp t-1) never equals a packed word (stamp t): the
    # read-back needs no `cand &`. A cohort that writes in every slot pays
    # 2w / C trips of each loop: the trade the install took (PR 30).
    with waves.part("tatp_dense", "ws_pick"):
        # the meta of the read lane whose row the slot names; an inactive
        # slot names the sentinel, whose meta (never written) is 0
        ws_vv = jnp.zeros((w, 2), U32)
        for j in reversed(range(READ_LANES)):
            ws_vv = jnp.where(ws_rows == rows[:, j:j + 1],
                              rmeta[:, j:j + 1], ws_vv)
    with waves.scope("tatp_dense", "lock"):
        flat_ws = ws_rows.reshape(-1)
        active = ws_active.reshape(-1)
        with waves.part("tatp_dense", "lock_compact"):
            a_ranks, n_act = compact.live_ranks(active)
            a_chunk = compact.chunk_lanes(2 * w)

            def packed(lanes):
                # the slot's own id, so the first slot still wins
                return ((t << K_ARB)
                        | (U32(2 * w - 1) - lanes.astype(U32)))

            def stamp_chunk(state, lanes, ok):
                arb, n_held, held = state
                rows_c = flat_ws[lanes]
                with waves.part("tatp_dense", "lock_read"):
                    held_c = ok & ((arb[rows_c] >> K_ARB) == t - 1)
                with waves.part("tatp_dense", "lock_scatter_max"):
                    arb = arb.at[
                        jnp.where(ok & ~held_c, rows_c, oob)].max(
                        packed(lanes), mode="drop")
                if ring is not None:
                    # per lane only for the flight recorder
                    held = held | compact.lanes_mask(
                        lanes, held_c, 2 * w)
                return arb, n_held + held_c.sum(dtype=I32), held

            def grant_chunk(grant, lanes, ok):
                rows_c = flat_ws[lanes]
                with waves.part("tatp_dense", "lock_readback"):
                    won_c = ok & (arb[rows_c] == packed(lanes))
                return grant | compact.lanes_mask(lanes, won_c, 2 * w)

            # (a drain's cohort is constants, its arb a shard's)
            no_lanes = compact.varying_like(
                jnp.zeros_like(active), db.arb)
            (arb, n_held, held), lock_chunks = compact.for_chunks(
                a_ranks, n_act, a_chunk, stamp_chunk,
                (db.arb,
                 compact.varying_like(jnp.zeros_like(n_act), db.arb),
                 None if ring is None else no_lanes))
            grant, _ = compact.for_chunks(
                a_ranks, n_act, a_chunk, grant_chunk, no_lanes)
            grant = grant.reshape(w, 2)

    with waves.part("tatp_dense", "classify"):
        # reply types: reads from the gather; write-slot GRANT/REJECT direct
        rt = jnp.where(is_read & used,
                       jnp.where(rex, Reply.VAL, Reply.NOT_EXIST), Reply.NONE)
        ws_rt = jnp.where(grant, Reply.GRANT,
                          jnp.where(ws_active, Reply.REJECT, Reply.NONE))

        # ---- wave-1 outcome: shared per-txn-type rules --------------------
        is_ro, rw, granted, lock_rejected, missing = classify_wave1(
            ttype, rt, ops, ws_active, ws_lane, ws_rt=ws_rt)

        new_ctx = DenseCtx(
            rows=rows, is_read=is_read & used, vv1=vv1,
            alive=rw & ~lock_rejected & ~missing,
            ro_commit=is_ro & ~missing, granted=granted,
            ws_rows=ws_rows, ws_vv=ws_vv,
            ws_tbl=ws_tbl, ws_key=ws_key, ws_kind=ws_kind,
            ws_active=ws_active,
            attempted=(occ if occupancy is not None
                       else jnp.asarray(w if gen_new else 0, I32)),
            ab_lock=(rw & lock_rejected).sum(dtype=I32),
            ab_missing=((rw & ~lock_rejected & missing)
                        | (is_ro & missing)).sum(dtype=I32),
            ab_validate=jnp.asarray(0, I32),
            magic_bad=magic_bad)

    with waves.part("tatp_dense", "step_frame"):
        db = db.replace(val=val, meta=meta, arb=arb, step=t + 1, log=logs)
    if counters is not None:
        with waves.part("tatp_dense", "monitor"):
            # a grant is an active slot on a row not held, so the two
            # kinds of rejection are what is left of the requests
            n_req = active.sum(dtype=I32)
            n_grant = (active & grant.reshape(-1)).sum(dtype=I32)
            serve_ctrs = {}
            if occupancy is not None:
                serve_ctrs = {
                    mon.CTR_SERVE_OCC_LANES: occ,
                    mon.CTR_SERVE_PAD_LANES: jnp.asarray(w, I32) - occ,
                    mon.CTR_SERVE_SHED_LANES:
                        jnp.asarray(0 if shed is None else shed, I32),
                }
            counters = mon.bump(counters, {
                **serve_ctrs,
                mon.CTR_STEPS: 1,
                mon.CTR_TXN_ATTEMPTED: c2.attempted,
                mon.CTR_TXN_COMMITTED:
                    (c2.ro_commit | c2.alive).sum(dtype=I32),
                mon.CTR_AB_LOCK: c2.ab_lock,
                mon.CTR_AB_MISSING: c2.ab_missing,
                mon.CTR_AB_VALIDATE: c2.ab_validate,
                mon.CTR_MAGIC_BAD: c2.magic_bad,
                mon.CTR_LOCK_REQUESTS: n_req,
                mon.CTR_LOCK_GRANTED: n_grant,
                mon.CTR_LOCK_REJECTED: n_req - n_grant,
                mon.CTR_LOCK_REJECT_HELD: n_held,
                mon.CTR_LOCK_REJECT_ARB: n_req - n_grant - n_held,
                mon.CTR_VALIDATE_LANES: v_lanes,
                mon.CTR_VALIDATE_FAILED: v_failed,
                mon.CTR_INSTALL_WRITES: wmask.sum(dtype=I32),
                mon.CTR_LOG_APPENDS: wmask.sum(dtype=I32),
                mon.CTR_INSTALL_CHUNKS: chunks,
                mon.CTR_LOCK_CHUNKS: lock_chunks,
            })
            counters = mon.gauge_max(
                counters, {mon.CTR_RING_HWM: logs.head.max()})
    extra = ()
    if ring is not None:
        # dinttrace: the txn id is recomputable per cohort — gen_step*w +
        # lane (c1 generated at t-1, c2 at t-2), so the assembler joins a
        # txn's lock, validate, install, and outcome events without any
        # id traveling through the carry. The OUTCOME masks mirror the
        # counter formulas above exactly (ro commits + lock/missing
        # aborts classify at wave 1; rw commits + validate aborts at
        # wave 2), so full-rate event counts reconcile with the ledger.
        with waves.scope("tatp_dense", "trace"):
            tu = jnp.asarray(t).astype(U32)
            lane_w = jnp.arange(w, dtype=U32)
            txn_new = tu * U32(w) + lane_w
            txn_c1 = (tu - U32(1)) * U32(w) + lane_w
            txn_c2 = (tu - U32(2)) * U32(w) + lane_w
            grant_l = grant.reshape(-1)
            lock_aux = (jnp.where(grant_l, txe.LOCK_GRANTED, 0)
                        | jnp.where(held, txe.LOCK_HELD, 0))
            miss_m = (rw & ~lock_rejected & missing) | (is_ro & missing)
            out1_mask = (rw & lock_rejected) | miss_m | new_ctx.ro_commit
            out1_cause = jnp.where(
                rw & lock_rejected, txe.CAUSE_LOCK,
                jnp.where(miss_m, txe.CAUSE_MISSING, txe.CAUSE_COMMIT))
            out2_cause = jnp.where(changed, txe.CAUSE_VALIDATE,
                                   txe.CAUSE_COMMIT)
            groups = (
                txe.ev(active, jnp.repeat(txn_new, 2), txe.EV_LOCK,
                       waves.full_name("tatp_dense", "lock"),
                       aux=lock_aux, step=tu),
                txe.ev(val_mask, jnp.repeat(txn_c1, READ_LANES),
                       txe.EV_VALIDATE,
                       waves.full_name("tatp_dense", "meta_gather"),
                       aux=val_bad, step=tu),
                txe.ev(wmask, jnp.repeat(txn_c2, 2), txe.EV_INSTALL,
                       waves.full_name("tatp_dense", "install"),
                       step=tu),
                txe.ev(out1_mask, txn_new, txe.EV_OUTCOME,
                       waves.full_name("tatp_dense", "lock"),
                       aux=out1_cause, step=tu),
                txe.ev(c1_alive_pre, txn_c1, txe.EV_OUTCOME,
                       waves.full_name("tatp_dense", "meta_gather"),
                       aux=out2_cause, step=tu),
            )
            ring, counters = txe.emit(ring, tcfg, groups, counters)
        extra = (ring,)
    with waves.part("tatp_dense", "stats"):
        stats = _stats_of(c2)
    if emit_installs:
        inst = Installs(
            wmask=wmask, rows=c2.ws_rows.reshape(-1),
            meta=jnp.where(wmask, meta_new, U32(0)),
            val=newval, tbl=log_tbl, key=log_key,
            is_del=flags_del, ver=newver)
        if counters is not None:
            return (db, new_ctx, c1, stats, inst, counters) + extra
        return (db, new_ctx, c1, stats, inst) + extra
    if counters is not None:
        return (db, new_ctx, c1, stats, counters) + extra
    return (db, new_ctx, c1, stats) + extra


def rebase_stamps(db: DenseDB) -> DenseDB:
    """Rebase arb stamps so the step field never overflows its u32 budget:
    live stamps (step-1 -> 2, step-2 -> 1) are kept, everything older is
    zeroed, and the step counter restarts at 3. One full elementwise pass,
    run once per ~16k steps."""
    with waves.scope("tatp_dense", "rebase"):
        t = db.step
        ts = db.arb >> K_ARB
        keep = ts + 2 >= t
        new_ts = jnp.where(keep, ts - (t - 3), 0)
        arb = jnp.where(keep, (new_ts << K_ARB)
                        | (db.arb & U32((1 << K_ARB) - 1)), U32(0))
        # t*0+3 (not a fresh constant) so the step keeps its varying-axis
        # type under shard_map's lax.cond (dense_sharded.block_local)
        return db.replace(arb=arb, step=t * U32(0) + U32(3))


@memoize_builder
def build_pipelined_runner(n_sub: int, w: int = 8192, val_words: int = 10,
                           cohorts_per_block: int = 8, mix=None,
                           check_magic: bool = True, use_pallas=None,
                           use_fused=None, monitor: bool = False,
                           trace: bool = False, trace_rate: float = 1.0,
                           trace_cap=None, serve: bool = False):
    """jit(scan(pipe_step)) over carry (db, c1, c2); same contract as
    tatp_pipeline.build_pipelined_runner: returns (run, init, drain).

    ``serve``: the dintserve variable-occupancy mode. run's signature
    becomes ``run(carry, key, occ, shed)`` with occ/shed i32
    [cohorts_per_block] arrays scanned alongside the step keys — each
    step masks lanes >= occ[i] to no-ops and mirrors shed[i] onto the
    device ledger (pipe_step's occupancy/shed). Carry layout, init, and
    drain are unchanged, so the serving engine reuses the closed-loop
    drain verbatim.

    ``monitor``: thread the dintmon counter plane through the carry. The
    carry grows a trailing monitor.Counters leaf (init creates it; read
    it between dispatches with monitor.snapshot(carry[-1])) and drain
    returns (db, stats, counters). Off (default) = contract and jaxpr
    unchanged, outputs bit-identical.

    ``trace`` / ``trace_rate`` / ``trace_cap``: the dinttrace flight
    recorder (off by default). When on, the
    carry gains a monitor.txnevents.TxnRing leaf BEFORE the counters leaf
    (so counters stay carry[-1]); each block starts from a zeroed ring and
    the caller drains it between dispatches with monitor.txnevents
    .TxnMonitor.observe. ``trace_cap`` defaults to one full block of
    candidates (w*(READ_LANES+6) per step) so nothing drops at rate 1.0; the
    resolved txnevents.TraceCfg hangs off ``init.trace_cfg``. Off =
    engine outputs bit-identical, not one extra jaxpr eqn."""
    assert 2 * w <= (1 << K_ARB), f"w={w} exceeds the arb slot field"
    refuse_retired(use_pallas=use_pallas, use_fused=use_fused)
    kw = dict(w=w, n_sub=n_sub, val_words=val_words,
              check_magic=check_magic)
    trace_on = bool(trace)
    tcfg = None
    if trace_on:
        # candidates/step: LOCK [2w] + VALIDATE [wR] + INSTALL [2w] +
        # OUTCOME x2 [2w] — default cap holds a full block at rate 1.0
        n_step = w * (READ_LANES + 6)
        cap = int(trace_cap) if trace_cap else n_step * cohorts_per_block
        tcfg = txe.TraceCfg(rate=float(trace_rate), cap=cap,
                            wave=waves.full_name("tatp_dense", "trace"))

    def step_mon(db, c1, c2, key, cnt, ring, **skw):
        """pipe_step with counters/ring or None, normalized to a fixed
        6-arity (db, new_ctx, c1, stats, cnt, ring)."""
        out = pipe_step(db, c1, c2, key, counters=cnt, ring=ring,
                        tcfg=tcfg, **skw)
        i = 4
        cnt = out[i] if cnt is not None else None
        i += 1 if cnt is not None else 0
        ring = out[i] if ring is not None else None
        return out[0], out[1], out[2], out[3], cnt, ring

    def scan_fn(carry, x):
        key, occ, shed = x if serve else (x, None, None)
        db, c1, c2 = carry[:3]
        ring = carry[3] if trace_on else None
        cnt = carry[-1] if monitor else None
        db, new_ctx, c1, stats, cnt, ring = step_mon(
            db, c1, c2, key, cnt, ring, mix=mix,
            occupancy=occ, shed=shed, **kw)
        out = ((db, new_ctx, c1) + ((ring,) if trace_on else ())
               + ((cnt,) if monitor else ()))
        return out, stats

    def _pre(carry, key):
        with waves.part("tatp_dense", "block_pre"):
            db = jax.lax.cond(carry[0].step >= U32(REBASE_AT),
                              rebase_stamps, lambda d: d, carry[0])
            carry = (db,) + carry[1:]
            if trace_on:     # each drained window is self-contained
                carry = carry[:3] + (txe.reset(carry[3]),) + carry[4:]
            return carry, jax.random.split(key, cohorts_per_block)

    if serve:
        def block(carry, key, occ, shed):
            carry, keys = _pre(carry, key)
            return jax.lax.scan(scan_fn, carry, (keys, occ, shed))
    else:
        def block(carry, key):
            carry, keys = _pre(carry, key)
            return jax.lax.scan(scan_fn, carry, keys)

    def init(db):
        base = (db, empty_ctx(w), empty_ctx(w))
        return (base + ((txe.create_ring(tcfg.cap),) if trace_on else ())
                + ((mon.create(),) if monitor else ()))

    init.trace_cfg = tcfg

    @functools.partial(jax.jit, donate_argnums=0)
    def drain(carry):
        db, c1, c2 = carry[:3]
        ring = txe.reset(carry[3]) if trace_on else None
        cnt = carry[-1] if monitor else None
        key = jax.random.PRNGKey(0)
        db, _, c1, s1, cnt, ring = step_mon(db, c1, c2, key, cnt, ring,
                                            gen_new=False, **kw)
        db, _, _, s2, cnt, ring = step_mon(db, empty_ctx(w), c1, key, cnt,
                                           ring, gen_new=False, **kw)
        stats = jnp.stack([s1, s2])
        return ((db, stats) + ((ring,) if trace_on else ())
                + ((cnt,) if monitor else ()))

    return jax.jit(block, donate_argnums=0), init, drain
