"""dintscope wave-name registry: the timing half's schema.

dintmon made the engines auditable by COUNT; this registry is the anchor
for auditing them by TIME. Every wave of every hot path is wrapped in a
``jax.named_scope("dint.<engine>.<wave>")`` annotation (the `scope`
helper below), so the wave identity survives jit into XLA op metadata and
shows up verbatim in `jax.profiler` Chrome/Perfetto traces —
`monitor/attrib.py` then attributes device time back to these names and
`tools/dintscope.py diff` gates regressions per wave. The reference gets
the same attribution for free from per-program eBPF counters and perf
annotations on named kernels; on TPU the name stack is the only identity
that survives fusion, so it is schema:

* **Append-only.** Wave names are keyed on by breakdown artifacts, the
  regression gate's thresholds, and the checked-in trace fixture —
  renaming or removing one silently un-gates it. Add waves by appending a
  row here and wrapping the new code region (recipe in OBSERVABILITY.md);
  regenerate the fixture with `python tools/dintscope.py synth`.
* **Semantics-neutral.** `jax.named_scope` only pushes the name stack —
  it adds no jaxpr equations, so engine outputs are bit-identical with
  or without the scopes (pinned in tests/test_dintscope.py) and the
  dintlint/dintproof target matrix is unaffected. There is no switch:
  the scopes cost nothing, and every device metric of the benchmark
  reads them.
* **Bytes formulas are declared, not measured.** Each wave may carry an
  expected-bytes-per-step formula (a string evaluated against the run's
  geometry: w, r, l, vw, d, ...), the same hand accounting PERF.md's
  closing ledger was built from — attribution divides measured time into
  it to report effective HBM bandwidth per wave, which is how "this wave
  is dispatch-bound, not bandwidth-bound" becomes machine-readable.
  Formulas are estimates of logical bytes moved (random-access row
  traffic; they ignore XLA padding/tiling) and `None` marks compute-only
  waves.

**Parts** are the level below the waves: ``jax.named_scope("part.<name>")``
(the `part` helper) around the pieces of a wave, and around what a step
does outside every wave. A part's name is deliberately not of the form
``dint.<engine>.<wave>``: benchmarks/trace_reduce.py takes the FIRST such
name on an op's stack and analysis/cost.py the LAST, so a nested ``dint.``
name would move dintcost's per-wave budgets and every artifact pinned on
them. A ``part.`` name moves neither; benchmarks/part_times.py reads the
last one on the stack. Waves stay the schema; parts may change with the
code (registered, but not append-only, and no artifact keys on them but
the benchmark's per-layer metrics named in `_PARTS`).
"""
from __future__ import annotations

PREFIX = "dint"

# ------------------------------------------------------------ the registry
# (engine, wave, doc, bytes-per-step formula | None). APPEND ONLY.
# Formula variables: w = cohort width, r = TATP read lanes per txn,
# l = SmallBank lock lanes per txn, vw = val words, d = mesh devices.
# Log-entry estimate: ~20 B header + 4*vw payload, x3 replicas.
_REGISTRY: tuple[tuple[str, str, str, str | None], ...] = (
    # --- dense TATP (engines/tatp_dense.py): 3-wave fused step ---------
    ("tatp_dense", "gen",
     "on-device cohort generation (txn mix, NURand, lane layout) — "
     "compute-only", None),
    ("tatp_dense", "install",
     "wave-3 install: meta + interleaved-val scatters of cohort t-2's "
     "certified writes, the live ones of the 2w write slots, C lanes a "
     "chunk (ops/compact.py). Priced at all 2w slots live, which is what "
     "a write-only mix issues, plus a chunk's gathers of row ids and "
     "meta words", "2*w*(4 + 4*vw) + 2*w*8"),
    ("tatp_dense", "log_append",
     "log x3 append of cohort t-2's installs (RepLog packed entries; the "
     "live ones, C lanes a chunk, priced at all 2w)",
     "2*w*3*(20 + 4*vw)"),
    ("tatp_dense", "meta_gather",
     "fused meta gather serving c1's validate re-read AND the new "
     "cohort's reads (2wR random lanes over the meta array: the R read "
     "lanes of a txn)",
     "2*w*r*4"),
    ("tatp_dense", "magic_gather",
     "magic-word integrity gather over the val array (wR random "
     "single-word lanes; absent when check_magic=False)", "w*r*4"),
    ("tatp_dense", "lock",
     "lock arbitration on the arb array: stamp gather + masked "
     "scatter-max + winner gather-back (the active ones of the 2w write "
     "slots, C lanes a chunk, priced at all 2w active)", "3*2*w*4"),
    ("tatp_dense", "rebase",
     "arb stamp rebase (full elementwise pass, once per ~16k steps — "
     "amortizes to noise; bytes unmodeled: streaming elementwise, not "
     "row traffic)", None),
    # --- dense SmallBank (engines/smallbank_dense.py): 2-wave step -----
    ("smallbank_dense", "gen",
     "on-device cohort generation (mix + hot-set skew) — compute-only",
     None),
    ("smallbank_dense", "lock",
     "no-wait S/X arbitration: held-stamp gathers + per-slot "
     "scatter-mins + grant stamp installs (wL lanes)", "5*w*l*4"),
    ("smallbank_dense", "read",
     "fused balance gather (wL random single-word lanes)", "w*l*4"),
    ("smallbank_dense", "compute",
     "shared per-txn balance logic (compute_phase) — compute-only", None),
    ("smallbank_dense", "install",
     "wave-2 balance install scatter of cohort t-1 (wL rows, plus the "
     "hot-mirror write-through when the dintcache tier is on)",
     "w*l*4"),
    ("smallbank_dense", "log_append",
     "log x3 append of cohort t-1's installs", "w*l*3*(20 + 4*vw)"),
    # --- generic TATP pipeline (engines/tatp_pipeline.py) --------------
    ("tatp_pipeline", "gen",
     "cohort generation (shared gen_cohort) — compute-only", None),
    ("tatp_pipeline", "assemble",
     "combined 12w-lane batch assembly (wave-1 + validate + wave-3 "
     "slices) — compute-only", None),
    ("tatp_pipeline", "engine_step",
     "vmapped sort-based engine step over the 3 stacked shard replicas "
     "(the sorts + segmented reductions + table ops; bytes unmodeled: "
     "sort-bound, no closed-form row-traffic formula)", None),
    ("tatp_pipeline", "classify",
     "per-wave outcome classification + stats emission — compute-only",
     None),
    # --- generic SmallBank pipeline (engines/smallbank_pipeline.py) ----
    ("smallbank_pipeline", "gen",
     "cohort generation + lock-slot layout — compute-only", None),
    ("smallbank_pipeline", "wave1",
     "fused lock+read at owners: vmapped engine step over the 3 stacked "
     "replicas (bytes unmodeled: sort-bound)", None),
    ("smallbank_pipeline", "compute",
     "shared per-txn balance logic (compute_phase) — compute-only", None),
    ("smallbank_pipeline", "wave2",
     "log x3 + prim/bck install + release: second vmapped engine step "
     "(bytes unmodeled: sort-bound)", None),
    # --- multi-chip dense TATP (parallel/dense_sharded.py); the local
    # --- step re-uses the tatp_dense wave scopes ------------------------
    ("dense_sharded", "replicate",
     "CommitBck x2 + CommitLog fan-out: ppermute the install record to "
     "devices +1/+2 and apply to backup tables + local logs (2 hops x "
     "2w records of meta+val plus a log append each; the receiver issues "
     "the live ones, C lanes a chunk, priced at all 2w)",
     "2*(2*w*(4 + 4*vw) + 2*w*(20 + 4*vw))"),
    # --- multi-chip dense SmallBank (parallel/dense_sharded_sb.py) -----
    ("dense_sharded_sb", "gen",
     "per-device cohort generation over the global keyspace — "
     "compute-only", None),
    ("dense_sharded_sb", "route",
     "wave-1 request routing: per-owner compaction + all_to_all "
     "exchange of lock/read requests (wL lanes of key+op)", "2*w*l*8"),
    # NOTE (dintcost audit): the owner-side formulas below were amended
    # when analysis/cost.py started deriving the same numbers from the
    # jaxpr — the originals pre-dated the 2x routed-slot capacity (the
    # factor route's own formula already carried) and install_route's
    # formula omitted the install + CommitLog bytes its doc always
    # described. Names are append-only; formulas are declared estimates
    # and reconciliation exists precisely so they cannot rot.
    ("dense_sharded_sb", "arbitrate",
     "owner-side no-wait S/X arbitration + fused balance read over the "
     "2wL routed request slots (5 passes, like the dense lock wave)",
     "5*2*w*l*4"),
    ("dense_sharded_sb", "reply",
     "grant/balance replies all_to_all back to sources + outcome "
     "classification + compute_phase (grant byte + balance word per "
     "lane)", "w*l*(2 + 8)"),
    ("dense_sharded_sb", "install_route",
     "wave-2 install routing to owners (all_to_all over the 2wL slots) "
     "+ primary balance install + the owner's CommitLog x3 append",
     "2*w*l*8 + 2*w*l*4 + w*l*3*(20 + 4*vw)"),
    ("dense_sharded_sb", "replicate",
     "backup fan-out: ppermute applied installs to owner+1/+2, apply to "
     "backup copies + append local logs (2 hops x wL balance rows + a "
     "log append each)", "2*(w*l*4 + w*l*3*(20 + 4*vw))"),
    # --- 2-D multi-host SmallBank (parallel/multihost_sb.py): the same
    # --- cross-shard step over the (dcn x ici) mesh. Hierarchical
    # --- routing runs each exchange TWICE (ici stage + host-aggregated
    # --- dcn stage over the full 2wL bucket array), so the collective
    # --- terms double vs dense_sharded_sb; the @flat twins replace them
    # --- back via wave_expect in targets.TARGET_COST ------------------
    ("multihost_sb", "gen",
     "per-device cohort generation over the global keyspace — "
     "compute-only", None),
    ("multihost_sb", "route",
     "wave-1 request routing: per-owner compaction + hierarchical "
     "(ici-then-dcn) all_to_all of lock/read requests (2 exchange "
     "stages x 2wL slots of key+op)", "2*2*w*l*8"),
    ("multihost_sb", "arbitrate",
     "owner-side no-wait S/X arbitration + fused balance read over the "
     "2wL routed request slots (5 passes, like dense_sharded_sb)",
     "5*2*w*l*4"),
    ("multihost_sb", "reply",
     "grant/balance replies hierarchically back to sources + outcome "
     "classification + compute_phase (2 stages x grant byte + balance "
     "word per lane)", "2*w*l*(2 + 8)"),
    ("multihost_sb", "install_route",
     "wave-2 install routing to owners (2 exchange stages over the 2wL "
     "slots) + primary balance install + the owner's CommitLog append",
     "2*(2*w*l*8 + 2*w*l*4) + w*l*3*(20 + 4*vw)"),
    ("multihost_sb", "replicate",
     "host fault-domain fan-out: ppermute applied installs to hosts "
     "h+1/h+2 at the same chip (axis=dcn), apply to backup copies + "
     "append local logs (2 hops x wL balance rows + a log append each)",
     "2*(w*l*4 + w*l*3*(20 + 4*vw))"),
    # --- dinttrace flight recorder (monitor/txnevents.py): one
    # --- concatenated 16-byte-record scatter-add into the per-device
    # --- event ring per step, covering every instrumented wave of the
    # --- engine. Formula = 16 B x candidate event lanes per step
    # --- (sampling masks lanes out of the scatter but the update
    # --- operand — what dintcost prices — stays full-width) ------------
    ("tatp_dense", "trace",
     "flight-recorder event scatter: LOCK (2w) + VALIDATE (wR) + "
     "INSTALL (2w) + OUTCOME x2 (2w) candidate records per step",
     "16*(w*(r+6))"),
    ("smallbank_dense", "trace",
     "flight-recorder event scatter: LOCK (wL) + INSTALL (wL) + "
     "OUTCOME (w) candidate records per step", "16*(w*(2*l+1))"),
    ("dense_sharded_sb", "trace",
     "flight-recorder event scatter: ROUTE (wL) + owner LOCK (2wL) + "
     "VOTE (w) + owner INSTALL (2wL) + REPL x2 hops (4wL) + OUTCOME "
     "(w) candidate records per step", "16*(9*w*l + 2*w)"),
    ("multihost_sb", "trace",
     "flight-recorder event scatter: ROUTE (wL) + owner LOCK (2wL) + "
     "VOTE (w) + owner INSTALL (2wL) + REPL x2 hops (4wL) + OUTCOME "
     "(w) candidate records per step", "16*(9*w*l + 2*w)"),
    # --- dintserve variable-occupancy serving (dint_tpu/serve): the
    # --- lane mask + padding/shed accounting applied before gen hands
    # --- the cohort to the waves above. Compute-only: the mask is an
    # --- elementwise compare against a device scalar, no row traffic ----
    ("tatp_dense", "serve",
     "serving-plane occupancy mask: lanes past the cohort's admitted "
     "occupancy forced to no-ops + serve counter bumps — compute-only",
     None),
    ("smallbank_dense", "serve",
     "serving-plane occupancy mask: lock slots past the cohort's "
     "admitted occupancy zeroed + serve counter bumps — compute-only",
     None),
    # --- dintmesh (round 18): the 2-D mesh as one open-loop service.
    # --- serve is the same compute-only admission mask as the dense
    # --- engines; route_prefetch is the double-buffered route — the SAME
    # --- 2wL bucket exchange as `route`, issued one step EARLY so the
    # --- host-aggregated DCN all_to_all of cohort i+1 rides under cohort
    # --- i's arbitrate/reply waves (an overlap regression shows up as
    # --- this wave's wall-clock time growing back toward `route`'s) -----
    ("multihost_sb", "serve",
     "mesh serving-plane occupancy mask: lock slots past the cohort's "
     "per-device admitted occupancy zeroed + serve counter bumps — "
     "compute-only", None),
    ("multihost_sb", "route_prefetch",
     "double-buffered lock/read routing: cohort i+1's 2wL bucket "
     "exchange (ICI then host-aggregated DCN, same bytes as route) "
     "issued under cohort i's owner waves", "2*2*w*l*8"),
    # --- dintscan (round 20): the store KV engine's waves. probe/install
    # --- bytes are hash-layout-dependent (two-choice bucket walks,
    # --- slot-scan gathers) — unmodeled, attribution-only. The scan pair
    # --- IS modeled: locate is 2 u32 point gathers per lane per binary-
    # --- search round (lg = ceil(log2 cap)); scan is the sequential slab
    # --- — ROWS x ROW-BYTES (sl+dc window rows of 12+4vw B each), NOT
    # --- lanes x point-gather bytes: that rows-not-probes shape is the
    # --- scan's bandwidth claim, CI-gated by cost_budget's
    # --- scan-dominance check ------------------------------------------
    ("store", "probe",
     "two-choice bucket probe: key compare over both candidate buckets' "
     "slots + hit val/ver gathers — bytes hash-layout-dependent, "
     "unmodeled", None),
    ("store", "install",
     "writer-election install/delete scatters (valid/key/val/ver) — "
     "bytes hash-layout-dependent, unmodeled", None),
    ("store", "scan_locate",
     "each scan lane's lower bound in the run and in the overlay: "
     "branchless meta binary searches, 2 u32 point gathers per lane per "
     "round over lg run rounds and ld overlay rounds (PR 45)",
     "w*(lg+ld)*8"),
    ("store", "scan",
     "two contiguous windows a lane, sl run rows and sl overlay rows of "
     "(key_hi,key_lo,ver,val[vw]) = 12+4vw B/row (PR 45: the window "
     "no longer grows with the overlay), then their merge",
     "w*2*sl*(12+4*vw)"),
    ("store", "delta_append",
     "write-through overlay append + latest-wins re-sort of the dc-row "
     "delta — sort-bound, bytes unmodeled", None),
    ("store", "run_rebuild",
     "drain-boundary fold of the overlay into the run in one pass (PR "
     "45): the overlay's insertion points by binary search, then the "
     "run's rows moved a block at a time into a fresh run — every live "
     "row read and written once (bytes unmodeled here: the byte model is "
     "benchmarks/bytes_model_scan.py); from_table instead where the "
     "overlay went stale", None),
)


def full_name(engine: str, wave: str) -> str:
    return f"{PREFIX}.{engine}.{wave}"


ALL_WAVES: tuple[str, ...] = tuple(
    full_name(e, wv) for e, wv, _, _ in _REGISTRY)
WAVE_DOCS: dict[str, str] = {
    full_name(e, wv): doc for e, wv, doc, _ in _REGISTRY}
WAVE_BYTES: dict[str, str | None] = {
    full_name(e, wv): f for e, wv, _, f in _REGISTRY}
ENGINES: tuple[str, ...] = tuple(dict.fromkeys(e for e, _, _, _ in _REGISTRY))
WAVES_BY_ENGINE: dict[str, tuple[str, ...]] = {
    eng: tuple(full_name(e, wv) for e, wv, _, _ in _REGISTRY if e == eng)
    for eng in ENGINES}
N_WAVES = len(ALL_WAVES)
assert N_WAVES == len(set(ALL_WAVES)), "duplicate wave name in registry"


def wave_bytes(name: str, **geometry) -> int | None:
    """Evaluate a wave's expected-bytes-per-step formula against run
    geometry (w=, r=, l=, vw=, d=, lg=, ld=, sl=, dc=...). Returns None for compute-only
    waves and for formulas whose variables the caller did not supply —
    attribution then reports time without a bandwidth figure instead of
    inventing one."""
    formula = WAVE_BYTES.get(name)
    if formula is None:
        return None
    try:
        v = eval(formula, {"__builtins__": {}},   # noqa: S307 — registry
                 {k: v for k, v in geometry.items() if v is not None})
    except NameError:
        return None
    try:
        return int(v)
    except (TypeError, ValueError):
        return None


def scope(engine: str, wave: str):
    """`jax.named_scope("dint.<engine>.<wave>")` for a REGISTERED wave —
    annotating an unregistered name raises at trace time, so the registry
    and the annotations cannot drift apart."""
    name = full_name(engine, wave)
    if name not in WAVE_DOCS:
        raise KeyError(
            f"wave {name!r} is not in the dintscope registry "
            "(monitor/waves.py); append it there first")
    import jax

    return jax.named_scope(name)


# --------------------------------------------------------------- the parts
PART_PREFIX = "part"
_DENSE = ("tatp_dense", "smallbank_dense")
# the engine-neutral parts: each engine whose runner steps a block opens
# them in its own step
_STEPPED = _DENSE + ("store", "dense_sharded_sb")

# (owner, wave | None, part, doc). The owner is the engine, or the shared
# module ("log" = tables/log.py), whose code opens the part, or a tuple of
# engines where the part is engine-neutral and each of them opens it in
# its own step (`monitor`, `stats`, `block_pre`); the wave is
# the one it lies under in that owner's step, None for what a step does
# outside every wave. append_rep's parts also run under
# `dense_sharded.replicate`, where a backup appends, inside that wave's
# own `bck_log_append`. The innermost part on an op's name stack is the
# one its time is booked to (benchmarks/part_times.py).
_PARTS: tuple[tuple[str | tuple[str, ...], str | None, str, str], ...] = (
    # --- dense TATP (engines/tatp_dense.py) -----------------------------
    ("tatp_dense", "install", "install_build",
     "masks, new meta words, payload draw and the [2w, VW] new rows"),
    ("tatp_dense", "install", "meta_scatter",
     "unique-index scatter of the live slots' meta words, C lanes a "
     "chunk"),
    ("tatp_dense", "install", "val_scatter",
     "unique-index scatter of the live slots' C x VW single value words "
     "a chunk into the 1-D val array, with its flat index "
     "(val_scatter_ms.* reads this)"),
    ("tatp_dense", "lock", "lock_read",
     "gather of the active write slots' arb stamps, C lanes a chunk, + "
     "the held compare"),
    ("tatp_dense", "lock", "lock_scatter_max",
     "packed stamps + masked scatter-max of a chunk's candidates into "
     "arb"),
    ("tatp_dense", "lock", "lock_readback",
     "winner gather-back of a chunk's slots (second loop) + the grant "
     "compare"),
    ("tatp_dense", None, "step_frame",
     "the step's frame: its key split and the step counter's increment"),
    ("tatp_dense", None, "addr",
     "wave-1 addressing: used lanes, table base + key, read mask"),
    ("tatp_dense", None, "validate",
     "wave 2: c1's version compare, its reductions, the new alive mask"),
    ("tatp_dense", None, "ws_pick",
     "the write slots' version pick: each slot takes the meta word of "
     "the read lane that names its row ([w, 2] selects over [w, R])"),
    ("tatp_dense", None, "classify",
     "reply types, classify_wave1 and the new cohort's context"),
    (_STEPPED, None, "monitor",
     "everything the step does only because the counter plane (or the "
     "flight recorder) is threaded: the reductions, the scatter-add, "
     "the gauge max (monitor_ms.* reads this)"),
    (_STEPPED, None, "stats",
     "the completing cohort's stats vector"),
    (_STEPPED, None, "block_pre",
     "block prologue: per-step key split, the stamp-rebase cond, the "
     "flight recorder's ring reset"),
    # --- tables/log.py append_rep, under whichever wave calls it --------
    ("log", "log_append", "log_plan",
     "lane / rank / slot plan and the replica-packed entry rows"),
    ("log", "log_append", "log_scatter",
     "unique-index row scatter of the live entries into the rings, C "
     "lanes a chunk, each chunk's lanes and its gathers out of lane "
     "space (under a plain mask: all R at once) + the head advance"),
    # --- write-set compaction (ops/compact.py), appended in PR 30 -------
    ("tatp_dense", "install", "ws_compact",
     "the running count of live write slots (one 2w-element cumsum), "
     "the chunk loop of the install, each chunk's lane search (C x 2w "
     "compares) and its gathers of row ids, meta words and value rows "
     "out of the 2w-wide operands"),
    # --- dense SmallBank (engines/smallbank_dense.py) -------------------
    ("smallbank_dense", "lock", "lock_arb",
     "the arbitration proper: two fresh slot-table-wide arrays filled "
     "and scatter-min'ed with the lane index over the X and the S "
     "requests (lock_arb_ms.* reads this)"),
    ("smallbank_dense", "lock", "lock_held_read",
     "gathers of the wL lanes' X and S stamps + the held compares"),
    ("smallbank_dense", "lock", "lock_grant",
     "gathers back out of the two arbitration arrays, the grant masks "
     "and the transactions' lock verdicts"),
    ("smallbank_dense", "lock", "lock_stamp",
     "the two unique-index stamp scatters of the granted lanes (and the "
     "hot mirror's write-through)"),
    ("smallbank_dense", "log_append", "log_build",
     "the [wL, VW] value rows {balance, magic} and the step-index "
     "version column handed to append_rep"),
    ("smallbank_dense", None, "sb_addr",
     "the step's key split, the TRANSACT_SAVING amount draw, flat rows, "
     "lock slots (identity or multiply-shift) and the lane masks"),
    ("smallbank_dense", None, "sb_ctx",
     "the new cohort's context (its outcome sums) and the step counter's "
     "increment"),
    # --- lock-wave compaction (ops/compact.py), appended in PR 34 -------
    ("tatp_dense", "lock", "lock_compact",
     "the running count of active write slots (one 2w-element cumsum), "
     "the wave's two chunk loops, each chunk's lane search (C x 2w "
     "compares) and gather of row ids, the held count, and the winners' "
     "way back to lane space (C x 2w compares, OR-ed over the chunk)"),
    # --- replication over the mesh (parallel/dense_sharded.py), appended
    # --- with the cell tatp7m-x4-sat (PR 37): every equation under
    # --- `replicate` carries one of these (bck_compact: PR 38) ----------
    ("dense_sharded", "replicate", "repl_hop",
     "one hop's ppermutes of the 8-leaf install record to device d + off, "
     "the sender's index, and the counts of what arrived "
     "(repl_push_hop<off>) and of the chunks its install took "
     "(bck_chunks)"),
    ("dense_sharded", "replicate", "bck_meta_scatter",
     "the backup slot's row ids and the unique-index scatter of a chunk "
     "of the live lanes' meta words into it, C lanes a chunk"),
    ("dense_sharded", "replicate", "bck_val_scatter",
     "the flat index and the unique-index scatter of a chunk of the live "
     "lanes' C x VW single value words into the backup slot "
     "(bck_val_scatter_ms.* reads this)"),
    ("dense_sharded", "replicate", "bck_log_append",
     "the forwarded stream's tag and append_rep_live of a chunk of the "
     "live lanes at a time into this device's ring (its log_plan, at "
     "full width, and log_scatter lie inside)"),
    ("dense_sharded", "replicate", "bck_compact",
     "the receiver's running count of the forwarded record's live lanes "
     "(one 2w-element cumsum a hop, shared by the install and the "
     "append), the install's chunk loop, each chunk's lane search (C x "
     "2w compares) and its gathers of row ids, meta words and value rows "
     "out of the 2w-wide record"),
    # --- the KV store (engines/store.py step + build_serve_runner),
    # --- appended with the cell store-ycsb-b (PR 39): every equation of
    # --- the point block carries a wave or one of these ------------------
    ("store", None, "store_gen",
     "on-device cohort generation: the op mix, the key draw (YCSB's "
     "Zipfian in closed form, or the hot-prefix skew) and the [w, VW] "
     "values an update writes"),
    ("store", None, "key_sort",
     "the same-key serialisation: sort_batch (one 3-key lax.sort of w "
     "lanes), the segment sums / maxes / cummax that resolve a key's "
     "lanes in arrival order, the writer election, and the unsorts back "
     "to lane order (key_sort_ms.* reads this)"),
    ("store", None, "slot_alloc",
     "slot allocation for inserts, phases B and B2: two more sorts (by "
     "destination and by alternate bucket), the [w, S] `valid` gathers, "
     "the n_buckets-wide `taken` fill + scatter-add"),
    ("store", None, "reply_build",
     "reply types, values and versions in sorted space, and the spill "
     "fix-up of a key's whole segment"),
    ("store", "probe", "probe_keys",
     "the bucket hash and the [w, S] key_hi / key_lo / valid gathers of "
     "both candidate buckets, with the match and the free counts"),
    ("store", "probe", "probe_val",
     "the hit entry's VW value words and its version, gathered"),
    ("store", "install", "kv_val_scatter",
     "unique-index scatter of the writers' single value words into the "
     "1-D val array, with its flat index: w x VW at full width, C x VW a "
     "chunk where the install is compacted"),
    ("store", "install", "kv_meta_scatter",
     "the entry index and the unique-index scatters of valid, version, "
     "key_hi and key_lo: w lanes each at full width, C a chunk where the "
     "install is compacted"),
    ("store", "install", "kv_compact",
     "the compacted install (PR 40; where engines/store."
     "install_is_compacted says so): the running counts of the elected "
     "writers and of the lanes that allocate or free a slot (two "
     "w-element cumsums), the two chunk loops, each chunk's lane search "
     "(C x w compares) and its gathers of entry indices, versions, value "
     "rows and key words out of lane space; kv_val_scatter and "
     "kv_meta_scatter, C lanes a chunk, lie inside its loops"),
    # --- the ordered run's scan and rebuild (tables/run.py), appended
    # --- with the cell store-ycsb-e (PR 45) ------------------------------
    ("store", "scan", "scan_window",
     "the two windows a scan lane reads: sl contiguous run rows and sl "
     "overlay rows from its lower bounds (dynamic_slice-shaped gathers "
     "of key_hi, key_lo, version and the VW value words), and the "
     "horizon key past each"),
    ("store", "scan", "scan_merge",
     "the merge of a lane's two windows: the [w, sl, sl] shadow compare, "
     "the start-key and horizon tests, one sort of 2 sl rows a lane, the "
     "first slen rows' versions and value words taken, the counts"),
    ("store", "run_rebuild", "run_merge_rank",
     "the overlay's insertion points in the run (a binary search a "
     "live entry), each entry's own row in the new run, and the prefix "
     "count of the inserts before every run row"),
    ("store", "run_rebuild", "run_merge_move",
     "the rebuild's shift passes: the rows moved left past the deletes "
     "before them (only where the overlay deletes), then right past the "
     "inserts before them, log2(delta_cap) selects of each array and its "
     "copy shifted by 2^k (the value words a plane at a time), PAD past "
     "the new end, the entries' own rows scattered into place"),
    ("store", "run_rebuild", "run_snapshot",
     "from_table where the overlay went stale: the table's live entries "
     "compacted, sorted by key, their versions and value rows gathered"),
    # --- sharded SmallBank (parallel/dense_sharded_sb.py), appended with
    # --- the cell smallbank24m-x4-sat (PR 43): every equation of its block
    # --- carries a wave or a part. A part whose wave is None here and
    # --- whose doc names two waves is opened under both (the same code
    # --- serves the lock requests and the installs) ----------------------
    ("dense_sharded_sb", None, "sbx_frame",
     "the step's frame: the device's index, its key fold and split, the "
     "TRANSACT_SAVING amount draw, a drain's empty cohort, the new "
     "cohort's context (its outcome sums, cast varying) and the state's "
     "reassembly with the step counter's increment"),
    ("dense_sharded_sb", None, "sbx_carry",
     "the stacked [D, ...] carry: every leaf's `x[0]` at the block's "
     "entry and `x[None]` at its exit (the form PR 42 took out of "
     "dense_sharded: on v5e a whole-table reduce in, a zero fill and a "
     "one-trip update loop out)"),
    ("dense_sharded_sb", None, "route_addr",
     "under route and install_route: a lane's owner (account % D), its "
     "local row, the active / valid masks and the fields to exchange"),
    ("dense_sharded_sb", None, "a2a_rank",
     "under route and install_route: `_positions`, a lane's arrival rank "
     "at its owner (a [wL, D] one-hot, its exclusive cumsum and the "
     "take_along_axis)"),
    ("dense_sharded_sb", None, "a2a_pack",
     "under route and install_route: `_route`, the bucket index and one "
     "unique-index scatter a field of the wL lanes into D buckets of "
     "`cap` slots (2 fields of requests, 5 of installs; a2a_pack_ms.* "
     "reads this)"),
    ("dense_sharded_sb", "route", "a2a_requests",
     "the all_to_alls of the lock + read requests (op, local row)"),
    ("dense_sharded_sb", None, "owner_addr",
     "the owner's view of what arrived: its inbox's D segment counts and "
     "their running ends (compact.prefixed: every segment is live in a "
     "prefix)"),
    ("dense_sharded_sb", "arbitrate", "owner_arb",
     "the arbitration proper: two fresh shard-table-wide arrays filled "
     "and scatter-min'ed with the arrival number over the X and the S "
     "requests, C live slots a trip of the first loop"),
    ("dense_sharded_sb", "arbitrate", "owner_held_read",
     "gathers of the live slots' X and S stamps + the held compares, C a "
     "trip of the third loop (an S stamp of this step read back as the "
     "held flag its row's first S read)"),
    ("dense_sharded_sb", "arbitrate", "owner_grant",
     "gathers back out of the two arbitration arrays (second loop) and "
     "the grant masks (third loop), C live slots a trip"),
    ("dense_sharded_sb", "arbitrate", "owner_stamp",
     "the two unique-index stamp scatters of the granted slots, C a trip "
     "of the third loop (and the hot mirror's write-through)"),
    ("dense_sharded_sb", "arbitrate", "owner_bal_read",
     "the fused balance gather of the live slots, masked by the grant, C "
     "a trip of the third loop"),
    ("dense_sharded_sb", "reply", "a2a_replies",
     "the all_to_alls of the replies (grant bit, balance)"),
    ("dense_sharded_sb", "reply", "reply_unpack",
     "the source's gathers of its lanes' replies out of the returned "
     "buckets"),
    ("dense_sharded_sb", "reply", "reply_classify",
     "lock verdicts per transaction, compute_phase, the write mask and "
     "the balance delta"),
    ("dense_sharded_sb", "install_route", "a2a_installs",
     "the all_to_alls of the committed writes (mask, local row, balance, "
     "table, account)"),
    ("dense_sharded_sb", "install_route", "owner_install",
     "the owner's unique-index scatter of the arrived balances into its "
     "primary table"),
    ("dense_sharded_sb", "install_route", "owner_log_append",
     "the [D x cap, VW] value rows {balance, magic}, the inbox's segment "
     "counts (compact.prefixed: its install mask is D segments of cap "
     "slots, each live in a prefix, so no lane search) and append_rep "
     "into the owner's ring, tag 0: log_plan at full width, log_scatter "
     "the live rows, C a trip of its chunk loop, lie inside "
     "(install_chunks counts the trips)"),
    ("dense_sharded_sb", "replicate", "sb_repl_hop",
     "one hop's five ppermutes of the applied installs (mask, row, "
     "balance, table, account) to device d + off, the sender's index and "
     "the count of what arrived (repl_push_hop<off>)"),
    ("dense_sharded_sb", "replicate", "sb_bck_scatter",
     "the backup slot's row ids and the unique-index scatter of the "
     "forwarded balances into it, D x cap lanes"),
    ("dense_sharded_sb", "replicate", "sb_bck_log_append",
     "the forwarded stream's tag (source + 1), the {balance, magic} rows, "
     "the forwarded mask's segment counts and append_rep into this "
     "device's ring: log_plan at full width, log_scatter the live rows, C "
     "a trip, lie inside (bck_chunks counts both hops' trips)"),
    # --- the sharded SmallBank owner's compacted arbitration -----------
    ("dense_sharded_sb", "arbitrate", "owner_compact",
     "the inbox's live slots in live order (D dense slices a field: op, "
     "row), the X / S masks and rows with the sentinel past the live "
     "count (and the hot mirror's index), the three chunk loops, C = "
     "compact.sorted_chunk_lanes(m1, D x cap) positions a trip, each "
     "trip's slices, and the verdicts' way back to slot layout (D dense "
     "slices each); the other arbitrate parts lie inside its loops "
     "(lock_chunks counts the first loop's trips)"),
)

# keyed on the part's name alone: the scope is `part.<name>`, so two
# owners could not tell a shared name apart in a trace; a name that
# several engines open is one row with all of them as its owners
PART_OWNERS: dict[str, tuple[str, ...]] = {
    p: (o,) if isinstance(o, str) else o for o, _, p, _ in _PARTS}
assert len(PART_OWNERS) == len(_PARTS), "duplicate part in registry"


def part_name(name: str) -> str:
    return f"{PART_PREFIX}.{name}"


def part(owner: str, name: str):
    """`jax.named_scope("part.<name>")` for a REGISTERED part of `owner`;
    an unregistered one raises at trace time, as `scope` does."""
    if owner not in PART_OWNERS.get(name, ()):
        raise KeyError(
            f"part {name!r} of {owner!r} is not in the part registry "
            "(monitor/waves.py _PARTS); add it there first")
    import jax

    return jax.named_scope(part_name(name))
