"""Device-resident counter plane: the fixed registry + the Counters pytree.

The reference's servers account for every hot-path event in per-CPU BPF
map counters (grant/reject in lock_kern.c, per-cause aborts in the
clients, ring heads in ls_kern.c) that userspace reads asynchronously.
Here the "map" is one flat u32 device array threaded through the engine
carry; engines bump slices of it in-step and the host fetches it at
window boundaries. Three rules keep it honest:

* **Fixed registry.** Counter IDs are module constants into ONE flat
  array; names, kinds, and order are schema — artifacts and JSONL events
  key on the names, so adding a counter means appending here (never
  reordering) and documenting it in OBSERVABILITY.md. A removal
  renumbers the counters after it and recompiles every ``monitor=True``
  program, so removals wait for one resize that takes them together.
* **Deterministic increments.** Every update is an elementwise add of
  reduced scalars via one `scatter-add`/`scatter-max` whose indices are a
  static, sorted, duplicate-free Python tuple — `unique_indices=True` is
  provably true, so the dintlint scatter_race pass accepts the counter
  plane on the same terms as the table installs.
* **u32 with wrap-safe draining.** Flow counters are monotonic mod 2^32;
  the host computes window deltas in uint32 arithmetic (exact under a
  single wrap) and accumulates totals in int64 (`delta`). Gauges
  (`RING_HWM`) are scatter-MAX high-water marks: a window reports the
  current value, not a difference.

Counters never leave the device mid-step and are never read back inside
jit (no `io_callback`): the purity pass stays clean and monitoring
changes no engine output — with `monitor=False` (the default) the
builders thread no counter state at all and the jaxpr is untouched.
"""
from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

I32 = jnp.int32
U32 = jnp.uint32

FLOW = "flow"      # monotonic accumulator (wrap-safe window deltas sum)
GAUGE = "gauge"    # high-water mark (windows report the current value)

# --------------------------------------------------------------- registry
# (name, kind, doc). Append; remove only in a batched resize (module
# docstring) — indices are schema. The docs are what
# `tools/dintmon.py summarize --describe` and OBSERVABILITY.md print.
_REGISTRY: tuple[tuple[str, str, str], ...] = (
    ("steps", FLOW,
     "fused pipeline steps executed (scan iterations, drains included)"),
    ("txn_attempted", FLOW,
     "transactions dispatched, counted when their cohort completes — "
     "reconciles with stats[STAT_ATTEMPTED]"),
    ("txn_committed", FLOW,
     "transactions committed — reconciles with stats[STAT_COMMITTED]"),
    ("ab_lock", FLOW,
     "aborts: write-set lock rejected (no-wait 2PL loss)"),
    ("ab_missing", FLOW,
     "aborts: required row absent / insert-exists (TATP semantics)"),
    ("ab_validate", FLOW,
     "aborts: OCC read-set version changed between read and validate"),
    ("ab_logic", FLOW,
     "aborts: SmallBank balance-logic failure (insufficient funds)"),
    ("magic_bad", FLOW,
     "integrity: VAL replies whose magic word mismatched"),
    ("lock_requests", FLOW,
     "lock lanes that requested a grant (active write slots)"),
    ("lock_granted", FLOW, "lock lanes granted"),
    ("lock_rejected", FLOW,
     "lock lanes rejected = reject_held + reject_arb where the split is "
     "observable (dense engines); generic engines bump only this total"),
    ("lock_reject_held", FLOW,
     "lock lanes rejected because the row/slot was stamped by an "
     "in-flight cohort (cross-cohort conflict)"),
    ("lock_reject_arb", FLOW,
     "lock lanes that lost intra-batch first-wins arbitration"),
    ("validate_lanes", FLOW,
     "read-set lanes of surviving RW transactions re-checked at wave 2"),
    ("validate_failed", FLOW,
     "validate lanes whose version compare failed"),
    ("install_writes", FLOW,
     "rows installed at the commit wave (commit/insert/delete lanes). "
     "A second owner since PR 40: the KV store's runner (engines/store.py "
     "build_serve_runner), where its install is compacted "
     "(install_is_compacted), bumps it with the step's elected writers "
     "that install a record (one a key written: updates and inserts that "
     "found a slot), the lanes its first chunk loop issues; at the "
     "shapes that keep the full-width install it bumps neither this nor "
     "install_chunks"),
    ("log_appends", FLOW,
     "log entries appended (one per logical install; replicas not "
     "multiplied)"),
    ("repl_push_hop1", FLOW,
     "install records applied from the +1 ppermute hop (CommitBck)"),
    ("repl_push_hop2", FLOW,
     "install records applied from the +2 ppermute hop (CommitBck)"),
    ("route_overflow", FLOW,
     "all_to_all destination-bucket overflow lanes (sharded SmallBank)"),
    ("ring_hwm", GAUGE,
     "log-ring high-water mark: max monotonic lane head observed "
     "(occupancy = min(ring_hwm, capacity))"),
    ("route_ici_lanes", FLOW,
     "routed lanes (lock requests + installs) whose owner lives on the "
     "SAME host: the exchange crosses only the ICI axis (2-D sharded "
     "SmallBank; route_ici_lanes + route_dcn_lanes = lock_requests + "
     "install_writes)"),
    ("route_dcn_lanes", FLOW,
     "routed lanes (lock requests + installs) whose owner lives on "
     "ANOTHER host: the exchange pays the DCN hop (2-D sharded "
     "SmallBank)"),
    ("trace_dropped", FLOW,
     "dinttrace events lost to ring overflow: sampled events generated "
     "after the per-window event ring filled (keep-first semantics — "
     "the ring never wraps over recorded events, the excess is dropped "
     "and counted here; 0 whenever the ring is sized for the window)"),
    ("serve_occupancy_lanes", FLOW,
     "dintserve: lanes carrying real admitted transactions in variable-"
     "occupancy serving cohorts (occupancy rides the batch as a device "
     "scalar; serve_occupancy_lanes + serve_padded_lanes = width x "
     "serving steps — the padding-waste reconciliation identity)"),
    ("serve_padded_lanes", FLOW,
     "dintserve: lanes past occupancy masked to no-ops (padding waste "
     "paid to keep one pre-compiled width hot; see "
     "serve_occupancy_lanes for the reconciliation identity)"),
    ("serve_shed_lanes", FLOW,
     "dintserve: admissions shed by the SLO controller before dispatch, "
     "mirrored onto the device ledger like trace_dropped (host tally == "
     "device counter — the graceful-degradation audit trail)"),
    ("route_prefetch_lanes", FLOW,
     "valid lock-request lanes whose routed buckets were exchanged one "
     "step EARLY by the double-buffered mesh serve path (overlap=True): "
     "the DCN all_to_all of cohort i+1 issued under cohort i's owner "
     "waves. Summed over devices and a full run+drain it equals "
     "lock_requests — every prefetched lane is arbitrated exactly once; "
     "0 on unoverlapped routes"),
    ("scan_requests", FLOW,
     "dintscan: Op.SCAN lanes served by the store engine's ordered-run "
     "path (stale-run RETRY lanes included — they consumed a request "
     "slot even though they returned zero rows)"),
    ("scan_rows", FLOW,
     "dintscan: rows returned across all scan replies (sum of per-lane "
     "counts; scan_rows <= scan_requests x scan_max by construction, "
     "with equality iff every scan ran to its full requested length)"),
    ("scan_delta_hits", FLOW,
     "dintscan: scan reply rows served from the write-through delta "
     "overlay rather than the sorted run (scan_delta_hits <= scan_rows; "
     "0 in the step right after a drain-boundary rebuild — the overlay "
     "freshness diagnostic)"),
    ("install_chunks", FLOW,
     "write-set compaction (ops/compact.py): chunk trips of the dense "
     "TATP install loop, C = chunk_lanes(2w) lanes a trip. "
     "install_chunks == sum over steps of ceil(install_writes / C): one a "
     "step under TATP's mix, none for a step with nothing to write; "
     "install_writes / (C x install_chunks) is the fill share of the "
     "indices the scatters issue. The KV "
     "store's compacted install (PR 40) is the second owner: the trips "
     "of its first loop (value words and versions), C = chunk_lanes(w), "
     "with the same identity and fill share; its second loop (valid and "
     "key words, for inserts and deletes only) is not counted, and makes "
     "no trip under a GET / SET mix over resident keys. The sharded "
     "SmallBank (PR 44) is the third: the trips of the owner's ring "
     "append over its inbox, C = chunk_lanes(D x cap), same identity and "
     "fill share (its install into the primary stays one full-width "
     "scatter)"),
    ("lock_chunks", FLOW,
     "lock-wave compaction (ops/compact.py): chunk trips of the dense "
     "TATP lock wave's first loop (the second makes as many), C = "
     "chunk_lanes(2w) lanes a trip. lock_chunks == sum over steps of "
     "ceil(lock_requests / C); lock_requests / (C x lock_chunks) is the "
     "fill share of the lanes the stamp gather, the scatter-max and the "
     "winner read-back issue. The sharded SmallBank's owner arbitration "
     "is the second owner: the trips of its first loop over the live "
     "requests of its inbox (the other two make as many), counted at the "
     "owner, C = compact.sorted_chunk_lanes(m1, D x cap), from the "
     "table's words and not from the inbox's width so that the compiler "
     "still sorts each chunk's scatters (7,936 at 24 M accounts over "
     "four devices); same identity and fill share"),
    ("bck_chunks", FLOW,
     "backup-apply compaction (ops/compact.py): chunk trips of the "
     "install loop of parallel/dense_sharded._apply_backup, both hops, "
     "counted at the receiving device, C = chunk_lanes(2w) lanes a trip "
     "(the forwarded append makes as many). bck_chunks == sum over steps "
     "and hops of ceil(the hop's live lanes / C); (repl_push_hop1 + "
     "repl_push_hop2) / (C x bck_chunks) is the fill share of the lanes "
     "the backups' scatters issue. Summed over the mesh it is 2 x "
     "install_chunks (a receiver makes its sender's trips). 0 off the "
     "mesh. The sharded SmallBank (PR 44) is the second owner: the trips "
     "of its two forwarded ring appends, at the receiving device, with "
     "the same two identities"),
    ("store_gets", FLOW,
     "KV store (engines/store.py build_serve_runner): admitted GET lanes "
     "— reconciles with the runner's stats column `gets`"),
    ("store_updates", FLOW,
     "KV store: admitted SET lanes (an update writes the whole record) — "
     "reconciles with the stats column `updates`; store_gets + "
     "store_updates + scan_requests == txn_attempted"),
    ("store_not_exist", FLOW,
     "KV store: lanes answered NOT_EXIST (a GET of an absent key): a "
     "lawful outcome, txn_committed + store_not_exist == txn_attempted "
     "where nothing spills; 0 where the table holds the whole key space"),
    ("store_spill", FLOW,
     "KV store: install lanes answered SPILL (both candidate buckets "
     "full: the key is in no table). A fault of a deployment without a "
     "host overflow store"),
    ("store_dup_lanes", FLOW,
     "KV store: admitted lanes whose key an earlier lane of the same "
     "step carries (admitted lanes less their distinct keys, counted by "
     "a sort of its own beside the engine's): the skew's pressure on the "
     "same-key serialisation (ops/segments.py). ~2,830 of 8,192 under "
     "Zipfian 0.99 over 24 M keys, ~1.4 under uniform draws"),
    ("xshard_txns", FLOW,
     "sharded SmallBank (parallel/dense_sharded_sb.py): generated "
     "transactions whose lock set names rows of more than one owner "
     "device, counted at the SOURCE device in the step that routes their "
     "requests (a drain generates none). Under the reference's mix and "
     "owner = account % 4: AMALGAMATE + SEND_PAYMENT (40 %), three "
     "quarters of them on two owners, ~30 % of txn_attempted"),
    ("remote_lock_lanes", FLOW,
     "sharded SmallBank: generated lock requests whose owner is not the "
     "source device (the lanes a lock grant and a fused read cross the "
     "interconnect for), counted at the SOURCE with xshard_txns; bucket "
     "overflow apart it is the share (D - 1) / D of lock_requests, ~75 % "
     "on four devices"),
    ("scan_window_rows", FLOW,
     "dintscan (PR 45): rows the scan lanes' two windows gathered (scan "
     "lanes x (WR + WD), tables/run.py window_widths), so that scan_rows "
     "/ scan_window_rows is the share of the gathered rows a scan "
     "returned"),
    ("store_inserts", FLOW,
     "the KV store (PR 45): INSERT lanes acknowledged (ACK): the keys a "
     "step added, where every insert names a new key (YCSB-E's "
     "insertion sequence); equals the stats column `inserts` when "
     "nothing spilled"),
    ("run_rows", GAUGE,
     "dintscan (PR 45): live rows of the ordered run after the latest "
     "drain-boundary rebuild (its high-water mark: rows only grow under "
     "inserts); == the table's live entries after a drain"),
)

ALL_NAMES: tuple[str, ...] = tuple(n for n, _, _ in _REGISTRY)
COUNTER_KINDS: dict[str, str] = {n: k for n, k, _ in _REGISTRY}
COUNTER_DOCS: dict[str, str] = {n: d for n, _, d in _REGISTRY}
COUNTER_INDEX: dict[str, int] = {n: i for i, n in enumerate(ALL_NAMES)}
N_COUNTERS = len(_REGISTRY)
FLOW_NAMES = tuple(n for n, k, _ in _REGISTRY if k == FLOW)
GAUGE_NAMES = tuple(n for n, k, _ in _REGISTRY if k == GAUGE)

CTR_STEPS = COUNTER_INDEX["steps"]
CTR_TXN_ATTEMPTED = COUNTER_INDEX["txn_attempted"]
CTR_TXN_COMMITTED = COUNTER_INDEX["txn_committed"]
CTR_AB_LOCK = COUNTER_INDEX["ab_lock"]
CTR_AB_MISSING = COUNTER_INDEX["ab_missing"]
CTR_AB_VALIDATE = COUNTER_INDEX["ab_validate"]
CTR_AB_LOGIC = COUNTER_INDEX["ab_logic"]
CTR_MAGIC_BAD = COUNTER_INDEX["magic_bad"]
CTR_LOCK_REQUESTS = COUNTER_INDEX["lock_requests"]
CTR_LOCK_GRANTED = COUNTER_INDEX["lock_granted"]
CTR_LOCK_REJECTED = COUNTER_INDEX["lock_rejected"]
CTR_LOCK_REJECT_HELD = COUNTER_INDEX["lock_reject_held"]
CTR_LOCK_REJECT_ARB = COUNTER_INDEX["lock_reject_arb"]
CTR_VALIDATE_LANES = COUNTER_INDEX["validate_lanes"]
CTR_VALIDATE_FAILED = COUNTER_INDEX["validate_failed"]
CTR_INSTALL_WRITES = COUNTER_INDEX["install_writes"]
CTR_LOG_APPENDS = COUNTER_INDEX["log_appends"]
CTR_REPL_PUSH_HOP1 = COUNTER_INDEX["repl_push_hop1"]
CTR_REPL_PUSH_HOP2 = COUNTER_INDEX["repl_push_hop2"]
CTR_ROUTE_OVERFLOW = COUNTER_INDEX["route_overflow"]
CTR_RING_HWM = COUNTER_INDEX["ring_hwm"]
CTR_ROUTE_ICI_LANES = COUNTER_INDEX["route_ici_lanes"]
CTR_ROUTE_DCN_LANES = COUNTER_INDEX["route_dcn_lanes"]
CTR_TRACE_DROPPED = COUNTER_INDEX["trace_dropped"]
CTR_SERVE_OCC_LANES = COUNTER_INDEX["serve_occupancy_lanes"]
CTR_SERVE_PAD_LANES = COUNTER_INDEX["serve_padded_lanes"]
CTR_SERVE_SHED_LANES = COUNTER_INDEX["serve_shed_lanes"]
CTR_ROUTE_PREFETCH_LANES = COUNTER_INDEX["route_prefetch_lanes"]
CTR_SCAN_REQUESTS = COUNTER_INDEX["scan_requests"]
CTR_SCAN_ROWS = COUNTER_INDEX["scan_rows"]
CTR_SCAN_DELTA_HITS = COUNTER_INDEX["scan_delta_hits"]
CTR_INSTALL_CHUNKS = COUNTER_INDEX["install_chunks"]
CTR_LOCK_CHUNKS = COUNTER_INDEX["lock_chunks"]
CTR_BCK_CHUNKS = COUNTER_INDEX["bck_chunks"]
CTR_STORE_GETS = COUNTER_INDEX["store_gets"]
CTR_STORE_UPDATES = COUNTER_INDEX["store_updates"]
CTR_STORE_NOT_EXIST = COUNTER_INDEX["store_not_exist"]
CTR_STORE_SPILL = COUNTER_INDEX["store_spill"]
CTR_STORE_DUP_LANES = COUNTER_INDEX["store_dup_lanes"]
CTR_XSHARD_TXNS = COUNTER_INDEX["xshard_txns"]
CTR_REMOTE_LOCK_LANES = COUNTER_INDEX["remote_lock_lanes"]
CTR_SCAN_WINDOW_ROWS = COUNTER_INDEX["scan_window_rows"]
CTR_STORE_INSERTS = COUNTER_INDEX["store_inserts"]
CTR_RUN_ROWS = COUNTER_INDEX["run_rows"]

# the subset defined with IDENTICAL semantics by the dense engines and
# the generic sort-based pipelines: on the parity workloads
# (tests/test_tatp_dense.py's dense-vs-generic configuration) these must
# be bit-identical across engine families. Engine-local counters
# (held/arb reject split, ring gauge, dispatch/backend accounting,
# replication hops) are excluded by design — the generic engines either
# cannot observe them or implement the machinery differently.
PARITY_NAMES: tuple[str, ...] = (
    "txn_attempted", "txn_committed", "ab_lock", "ab_missing",
    "ab_validate", "ab_logic", "magic_bad", "lock_requests",
    "lock_granted", "lock_rejected", "validate_lanes", "validate_failed",
    "install_writes", "log_appends",
)


@flax.struct.dataclass
class Counters:
    """The device-resident counter plane: one flat u32 vector, a pytree
    leaf that rides the engine carry (donated with it, updated in place
    in HBM)."""
    buf: jax.Array     # u32 [N_COUNTERS]


def create() -> Counters:
    # fresh numpy backing so the buffer is never aliased with another
    # donated leaf (same rule as the engines' empty_ctx)
    return Counters(buf=jnp.asarray(np.zeros(N_COUNTERS, np.uint32)))


def _static_update(c: Counters, updates: dict[int, jax.Array], *,
                   reduce: str) -> Counters:
    """One scatter over a static sorted duplicate-free index tuple.

    `updates` keys are the CTR_* module constants (Python ints), so the
    index operand is a compile-time constant with provably unique
    entries — `unique_indices=True` is a fact, not a promise."""
    if not updates:
        return c
    idx = tuple(sorted(updates))
    assert len(idx) == len(updates)
    vals = jnp.stack([jnp.asarray(updates[i]).astype(U32) for i in idx])
    at = c.buf.at[jnp.asarray(idx, I32)]
    if reduce == "add":
        buf = at.add(vals, unique_indices=True)
    else:
        buf = at.max(vals, unique_indices=True)
    return c.replace(buf=buf)


def bump(c: Counters | None, updates: dict[int, jax.Array]):
    """Add reduced scalars to flow counters; None passes through (so call
    sites stay one-liners on both the monitored and unmonitored paths)."""
    if c is None:
        return None
    return _static_update(c, updates, reduce="add")


def gauge_max(c: Counters | None, updates: dict[int, jax.Array]):
    """Raise gauge counters to new high-water marks (scatter-max)."""
    if c is None:
        return None
    return _static_update(c, updates, reduce="max")


def counters_enabled(monitor: bool) -> Counters | None:
    """The builders' one-line gate: a Counters to thread, or None (the
    default) in which case no counter state enters the jaxpr at all."""
    return create() if monitor else None


# ------------------------------------------------------------- host side


def snapshot(counters) -> dict[str, int]:
    """Fetch a Counters (or raw buf / stacked [D, N] per-device bufs) to a
    {name: int} dict; stacked device axes are summed for flow counters and
    maxed for gauges (the cross-shard reading of a high-water mark)."""
    buf = counters.buf if isinstance(counters, Counters) else counters
    arr = np.asarray(buf)
    if arr.ndim == 1:
        arr = arr[None]
    arr = arr.reshape(-1, N_COUNTERS).astype(np.uint64)
    out = {}
    for name, i in COUNTER_INDEX.items():
        col = arr[:, i]
        out[name] = int(col.max() if COUNTER_KINDS[name] == GAUGE
                        else col.sum())
    return out


def delta(cur: dict[str, int], prev: dict[str, int] | None) -> dict[str, int]:
    """Window delta between two snapshots: flow counters subtract in
    uint32 (exact under a single wrap per window per device); gauges
    report the current value."""
    out = {}
    for name in ALL_NAMES:
        c = cur.get(name, 0)
        if COUNTER_KINDS[name] == GAUGE:
            out[name] = int(c)
        elif prev is None:
            out[name] = int(c)
        else:
            out[name] = int(np.uint32(c) - np.uint32(prev.get(name, 0)))
    return out


def zeros_dict() -> dict[str, int]:
    return {name: 0 for name in ALL_NAMES}
