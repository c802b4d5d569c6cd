"""dinttrace event plane: the device-resident per-transaction flight
recorder.

dintmon counts and dintscope times; this plane NARRATES — it records the
journey of individual sampled transactions through the waves so "why did
THIS txn abort three times before committing" has an answer (the
per-request visibility the reference's Caladan clients get for free by
tracking every outstanding request in userspace, and the raw material of
FaSST-style abort-by-cause analysis). The design is the `Counters` plane
generalized from one u32 per name to one 16-byte record per event:

* **A per-device event ring rides the carry.** `TxnRing` is a flat u32
  buffer of `cap` fixed-width records plus a monotonic `head`, donated
  with the engine state exactly like `Counters.buf`. At every step each
  instrumented engine concatenates its candidate event lanes (one group
  per wave — lock verdicts, validate verdicts, installs, 2PC votes,
  replication hops, outcome classifications) and lands the sampled
  subset with ONE `scatter-add` of compile-time-unique indices: no
  `io_callback`, no host sync, and the scatter-add family is exempt from
  every table-discipline pass by construction (protocol/_installs,
  durability/_wal_order, and replay coverage all govern overwrite
  `scatter` only — the same carve-out the counter bumps ride).

* **Deterministic sampling.** A lane is recorded iff
  ``murmur_mix(txn_id) & 0xFFFF < round(rate * 65536)`` — a pure
  function of the txn id, so the SAME transactions are sampled on every
  shard, every retry, and every rate: the rate-0.25 event set is a
  strict subset of the rate-1.0 set (thresholds are monotone in rate),
  which is what makes cross-shard joins and A/B reconciliation exact.

* **Keep-first overflow, loss-counted.** The ring is zeroed at each
  window (block) boundary inside the jitted block; within a window the
  first `cap` sampled events are kept and the excess is DROPPED (never
  wrapped over recorded events — a wrap would tear records and break
  the scatter's uniqueness). `head` keeps counting past `cap`, so the
  host always knows exactly how many events were lost, and monitored
  runs bump the `trace_dropped` counter on-device with the same number.

* **Drained at window boundaries.** `TxnMonitor` mirrors the round-11
  counter drain: fetch the ring after each dispatched block, optionally
  `defer=True` double-buffered (on-device copy now, host materialize
  next window) so the drain never serializes the dispatch stream.
  Events go to JSONL as `{"type": "txnevents", ...}` records that
  `monitor/txntrace.py` joins into per-transaction span trees.

Record layout (4 u32 words, schema 1):

    w0  txn id      engine-defined, stable across waves/retries/shards
    w1  bits 31..24 event kind (EV_*)
        bits 23..16 wave ordinal (index into waves.ALL_WAVES)
        bits 15..8  shard/device ordinal (0 on single-device engines)
        bits  7..0  aux payload: verdict bits / abort cause / hop / dest
    w2  step        db.step at emission (the engine's wave clock)
    w3  lane        flat lane index within the emitting wave

Off means off: builders thread `ring=None` and not one extra eqn enters
the jaxpr — engine outputs are bit-identical (pinned in
tests/test_dinttrace.py), the same contract the counter plane keeps.
"""
from __future__ import annotations

import dataclasses
import json
import os

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from . import counters as ctr
from . import waves

SCHEMA = 1
WORDS = 4          # u32 words per event record

# ------------------------------------------------------------ event kinds
# Append-only: kind codes are baked into checked-in fixtures/artifacts.
EV_ROUTE = 1       # request left its source lane for an owner shard
EV_LOCK = 2        # lock arbitration verdict at the owner
EV_VALIDATE = 3    # OCC read-set re-check verdict
EV_VOTE = 4        # 2PC vote the source derives from its grant replies
EV_INSTALL = 5     # certified write landed in the primary table
EV_REPL = 6        # install record applied at a +off backup shard
EV_OUTCOME = 7     # final classification of the attempt (aux = cause)

KIND_NAMES: dict[int, str] = {
    EV_ROUTE: "route", EV_LOCK: "lock", EV_VALIDATE: "validate",
    EV_VOTE: "vote", EV_INSTALL: "install", EV_REPL: "repl",
    EV_OUTCOME: "outcome",
}

# EV_OUTCOME aux payload: the dintmon abort classes, one code per ab_*
CAUSE_COMMIT = 0
CAUSE_LOCK = 1     # ab_lock
CAUSE_MISSING = 2  # ab_missing
CAUSE_VALIDATE = 3  # ab_validate
CAUSE_LOGIC = 4    # ab_logic

CAUSE_NAMES: dict[int, str] = {
    CAUSE_COMMIT: "commit", CAUSE_LOCK: "ab_lock",
    CAUSE_MISSING: "ab_missing", CAUSE_VALIDATE: "ab_validate",
    CAUSE_LOGIC: "ab_logic",
}

# EV_LOCK aux verdict bits
LOCK_GRANTED = 0x1
LOCK_HELD = 0x2    # rejected because the slot was held (vs lost the arb)

# EV_ROUTE aux bit: the hop crossed the DCN axis (2-D meshes only)
ROUTE_DCN = 0x40

U32 = jnp.uint32


@flax.struct.dataclass
class TxnRing:
    """Per-device event ring: `cap` 4-word records + a monotonic head
    (total sampled events generated this window, INCLUDING dropped)."""
    buf: jax.Array     # u32 [cap * WORDS]
    head: jax.Array    # u32 scalar


@dataclasses.dataclass(frozen=True)
class TraceCfg:
    """Static trace configuration a builder closes over (never traced)."""
    rate: float        # sampling rate in [0, 1]
    cap: int           # ring capacity in records
    wave: str = ""     # full scope name of the engine's trace wave

    @property
    def thresh(self) -> int:
        """16-bit sampling threshold; monotone in rate, so lower-rate
        event sets are strict subsets of higher-rate ones."""
        return max(0, min(65536, round(float(self.rate) * 65536)))


def trace_enabled(flag: bool | None = None) -> bool:
    """Builders' gate: explicit `trace=` wins, else DINT_TRACE=1."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("DINT_TRACE", "0") == "1"


def trace_rate(rate: float | None = None) -> float:
    """Explicit `trace_rate=` wins, else DINT_TRACE_RATE (default 1.0)."""
    if rate is not None:
        return float(rate)
    return float(os.environ.get("DINT_TRACE_RATE", "1.0"))


def create_ring(cap: int) -> TxnRing:
    # fresh numpy backing so the buffer is never aliased with another
    # donated leaf (same rule as counters.create)
    return TxnRing(buf=jnp.asarray(np.zeros(cap * WORDS, np.uint32)),
                   head=jnp.asarray(np.uint32(0)))


def reset(ring: TxnRing | None) -> TxnRing | None:
    """Zero the ring at a window boundary (called INSIDE the jitted block,
    so each drained ring is self-contained); None passes through."""
    if ring is None:
        return None
    return TxnRing(buf=ring.buf * jnp.uint32(0),
                   head=ring.head * jnp.uint32(0))


def sample_mask(txn: jax.Array, thresh: int) -> jax.Array:
    """murmur3 finalizer over the txn id -> bottom 16 bits vs thresh."""
    x = txn.astype(U32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return (x & jnp.uint32(0xFFFF)) < jnp.uint32(thresh)


def ev(mask: jax.Array, txn: jax.Array, kind: int, wave_name: str, *,
       shard=0, aux=0, step=0, lane=None):
    """One candidate event group: `mask` [n] selects lanes, everything
    else broadcasts to [n]. `wave_name` must be a registered
    waves.ALL_WAVES entry — the ordinal baked into w1 is its index."""
    n = int(mask.shape[0])
    wave_ord = waves.ALL_WAVES.index(wave_name)

    def b(v):
        return jnp.broadcast_to(jnp.asarray(v).astype(U32), (n,))

    if lane is None:
        lane = jnp.arange(n, dtype=U32)
    return (mask, b(txn), b(kind), b(wave_ord), b(shard), b(aux),
            b(step), b(lane))


def emit(ring: TxnRing, cfg: TraceCfg, groups, counters=None):
    """Land one step's candidate events: concatenate the groups, sample
    by txn id, and scatter-add the packed records at head+rank with ONE
    unique-index scatter (keep-first: candidates past `cap` fall into
    per-lane out-of-bounds slots and drop). Returns (ring, counters) —
    counters gains the window's `trace_dropped` delta when threaded."""
    mask = jnp.concatenate([g[0] for g in groups])
    txn, kind, wave_ord, shard, aux, step, lane = (
        jnp.concatenate([g[i] for g in groups]) for i in range(1, 8))
    samp = mask & sample_mask(txn, cfg.thresh)
    s32 = samp.astype(U32)
    pos = jnp.cumsum(s32) - s32                       # exclusive rank
    n_new = s32.sum()
    cap = jnp.uint32(cfg.cap)
    row = ring.head + pos
    n = int(mask.shape[0])
    # every unselected/overflowed lane gets a DISTINCT out-of-bounds row
    # (cap + lane ordinal): mode="drop" discards them and the index
    # operand stays duplicate-free — unique_indices is a fact, as in
    # counters._static_update
    spill = cap + jnp.arange(n, dtype=U32)
    row = jnp.where(samp & (row < cap), row, spill)
    w1 = ((kind << 24) | ((wave_ord & jnp.uint32(0xFF)) << 16)
          | ((shard & jnp.uint32(0xFF)) << 8) | (aux & jnp.uint32(0xFF)))
    vals = jnp.stack([txn, w1, step, lane], axis=1)   # [n, WORDS]
    idx = (row[:, None] * jnp.uint32(WORDS)
           + jnp.arange(WORDS, dtype=U32)[None, :]).reshape(-1)
    buf = ring.buf.at[idx].add(vals.reshape(-1), mode="drop",
                               unique_indices=True)
    head = ring.head + n_new
    # events lost this step = growth of max(head, cap) beyond cap
    dropped = (jnp.maximum(head, cap) - jnp.maximum(ring.head, cap))
    counters = ctr.bump(counters, {ctr.CTR_TRACE_DROPPED: dropped})
    return TxnRing(buf=buf, head=head), counters


# ------------------------------------------------------------- host side


def decode(buf, head, cap: int) -> np.ndarray:
    """Recorded events of one drained ring, in append order: a u32
    [n, WORDS] array with n = min(head, cap) (keep-first overflow)."""
    n = int(min(int(head), int(cap)))
    arr = np.asarray(buf, np.uint32).reshape(-1)[:n * WORDS]
    return arr.reshape(n, WORDS)


def dropped_of(head, cap: int) -> int:
    return max(0, int(head) - int(cap))


def unpack_w1(w1: int) -> tuple[int, int, int, int]:
    """w1 -> (kind, wave ordinal, shard, aux)."""
    w1 = int(w1)
    return ((w1 >> 24) & 0xFF, (w1 >> 16) & 0xFF, (w1 >> 8) & 0xFF,
            w1 & 0xFF)


class TxnMonitor:
    """Drives the event-ring drain at window boundaries, mirroring
    monitor.trace.Monitor for the counter plane: fetch each block's ring
    (a TxnRing carry leaf, possibly with stacked per-device leaves),
    decode it, and append one `txnevents` JSONL record per device.

    ``defer=True`` is the round-11 double-buffer: the buf/head are
    copied on-device into fresh (never-donated) arrays and materialized
    on the NEXT observe/flush, so the drain does not serialize the
    dispatch stream. Mandatory copy for the same reason as the counter
    plane: the carry's own ring leaf is donated into the next dispatch.
    """

    def __init__(self, cfg: TraceCfg, path: str | None = None,
                 meta: dict | None = None):
        self.cfg = cfg
        self.windows: list[list[dict]] = []   # per window: records/device
        self._f = open(path, "w") if path else None
        self._window = 0
        self._pending = None
        self.total_events = 0
        self.total_dropped = 0
        rec = {"type": "txnmeta", "schema": SCHEMA,
               "rate": float(cfg.rate), "cap": int(cfg.cap),
               "waves": list(waves.ALL_WAVES)}
        rec.update(meta or {})
        self.meta = rec
        self._write(rec)

    def _write(self, rec: dict):
        if self._f is not None:
            self._f.write(json.dumps(rec) + "\n")
            self._f.flush()

    @staticmethod
    def _leaves(ring: TxnRing):
        """Split a (possibly device-stacked) ring into per-device
        (buf, head) numpy pairs."""
        buf = np.asarray(ring.buf)
        head = np.asarray(ring.head)
        bufs = buf.reshape(-1, buf.shape[-1]) if buf.ndim > 1 else buf[None]
        heads = head.reshape(-1) if head.ndim > 0 else head[None]
        assert len(bufs) == len(heads)
        return list(zip(bufs, heads))

    def observe(self, ring: TxnRing, *, defer: bool = False):
        """Drain one window's ring. Returns the records of the completed
        window (the PREVIOUS one under ``defer``; None when pending)."""
        out = None
        if self._pending is not None:
            out = self._process(self._pending)
            self._pending = None
        if defer:
            buf = jnp.asarray(ring.buf) + jnp.uint32(0)   # fresh copies
            head = jnp.asarray(ring.head) + jnp.uint32(0)
            for leaf in (buf, head):
                try:
                    leaf.copy_to_host_async()
                except Exception:   # noqa: BLE001 — best-effort prefetch
                    pass
            self._pending = TxnRing(buf=buf, head=head)
            return out
        recs = self._process(ring)
        return recs if out is None else recs

    def flush(self):
        """Materialize a deferred window, if any."""
        if self._pending is None:
            return None
        out = self._process(self._pending)
        self._pending = None
        return out

    def _process(self, ring: TxnRing) -> list[dict]:
        recs = []
        for dev, (buf, head) in enumerate(self._leaves(ring)):
            events = decode(buf, head, self.cfg.cap)
            dropped = dropped_of(head, self.cfg.cap)
            rec = {"type": "txnevents", "window": self._window,
                   "device": dev, "head": int(head),
                   "cap": int(self.cfg.cap), "dropped": dropped,
                   "events": events.astype(np.int64).tolist()}
            self._write(rec)
            recs.append(rec)
            self.total_events += len(events)
            self.total_dropped += dropped
        self.windows.append(recs)
        self._window += 1
        return recs

    def summary(self) -> dict:
        """The `"dinttrace"` artifact block bench.py/exp.py embed."""
        drop_windows = sorted({r["window"] for w in self.windows
                               for r in w if r["dropped"]})
        return {"schema": SCHEMA, "rate": float(self.cfg.rate),
                "cap": int(self.cfg.cap), "windows": self._window,
                "events": int(self.total_events),
                "dropped": int(self.total_dropped),
                "dropped_windows": drop_windows}

    def close(self):
        if self._f is not None and not self._f.closed:
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.flush()
        self.close()
