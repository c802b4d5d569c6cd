"""Protocol pass: dataflow proofs of the engines' transaction invariants.

The eBPF verifier gives the reference structural guarantees before a
handler may run (DINT, NSDI'24); the jitted steps' equivalents — the
FaSST-style OCC contract "install only what you locked AND validated"
and 2PL's "every abort path releases its locks" (FaSST, OSDI'16) — were
docstring claims until this pass. It consumes the forward fact
propagation in analysis/dataflow.py (LOCK_WIN / VALIDATED / STAMP /
ABORT_MASK / REPL_PUSHED, flowed through pjit/shard_map/scan carries to
a fixpoint) and enforces four ERROR-severity checks, gated by the
per-target protocol flags declared in analysis/targets.py:

  lock-dominance       ["certified"]  every overwrite scatter into
      persistent table state (KV words, version/meta words, lock/stamp
      words, log entries) must have indices or updates data-dependent on
      LOCK_WIN — the write mask descends from a lock grant. Scatters
      whose masks descend from the segment machinery (SORTED) pass on
      the same evidence ladder as scatter_race: sorted-segment
      representatives are one-writer/serialized by construction and the
      generic engines' closed forms certify inside the sort.

  validate-before-install ["occ"]     on OCC paths the same scatters
      must also carry VALIDATED: the install mask descends from the
      read-set version compare. 2PL engines (smallbank_*) and
      client-driven servers (sharded/*) don't declare the flag.

  abort-implies-unlock ["certified" or "drain"]  if the trace produces
      an ABORT_MASK, every lock array that receives grants (a state
      scatter whose write facts carry LOCK_WIN) must also release:
      (a) expiring stamps — some scatter on that array stamps the step
          counter into it (updates carry STAMP; the dense engines'
          step-stamp design, where release is stamp expiry); or
      (b) a release write — a scatter on the same array whose write
          facts carry ABORT_MASK (the generic engines' combined
          release+acquire value `locked' = held & ~unlock | grant`,
          where unlock descends from the abort ops); or
      (c) two distinct scatter sites on the array (explicit
          acquire-wave + release-wave engines: the release mask
          `granted`, covering commits AND aborts, legitimately does not
          depend on the abort bit — the second site is the witness).
      An engine that "returns early past the unlock wave" has a single
      grant-masked, unstamped, abort-independent scatter site and fails
      all three.

  commit-after-replication ["replicated"]  multi-chip paths must push
      install records over ICI and land them: at least one ppermute in
      the trace, and at least one scatter into persistent state whose
      write facts carry REPL_PUSHED (the backup-apply / forwarded-log
      writes). The committed-outcome stats ride the same carry those
      writes update, so a path that drops the push or discards the
      pushed payload fails deterministically.

  writer-election      ["elected"]  lock-free server engines (the round-
      20 KV store) arbitrate concurrent writers with the segment
      machinery instead of a lock table: duplicate keys sort into
      segments and a segment reduction (scatter-max over sorted seg_ids
      — engines/store.step's last-writer-wins `seg_max_where`) elects
      exactly one winner per key. Three ERROR checks pin that
      discipline: (a) `no-writer-election` — the trace must contain at
      least one non-pallas scatter-max/min whose indices carry SORTED
      (deleting or overwrite-weakening the reduction removes the only
      arbitration between duplicate writers); (b) `unelected-install` —
      every overwrite scatter into persistent state must carry SORTED in
      its write facts (indices/updates descend from the election, not an
      unconstrained recomputation); (c) `uncertified-install` — each
      such install must also declare ``unique_indices=True`` (the
      one-writer claim stated to XLA; losing it both serializes the
      scatter and silently drops the certification tests pin against
      jaxlib lowering drift — see ops/segments.first_rank_where).

Targets whose builders close no protocol loop in-trace declare fewer
flags: `sharded/*` single-step servers execute client-driven ops (the
coordinator in clients/ owns lock/validate/abort sequencing), so only
the replication check applies; `tatp_dense/drain` installs boundary
cohorts certified in the block trace, so only abort-implies-unlock
(whose expiring-stamp witness is in-trace) applies. Fixtures in
tests/test_dintlint.py prove each check fires on a mutated engine and
stays silent on the safe idiom.
"""
from __future__ import annotations

from .. import dataflow as df
from ..core import Finding, SEV_ERROR, TargetTrace, register_pass

# protocol flags understood on TargetTrace.protocol
FLAG_CERTIFIED = "certified"
FLAG_OCC = "occ"
FLAG_REPLICATED = "replicated"
FLAG_DRAIN = "drain"
FLAG_SERVER = "server"
FLAG_ELECTED = "elected"


def _installs(flow: df.Dataflow):
    """Overwrite scatters into persistent state (the install writes the
    first two checks govern). Pallas kernel bodies are excluded like
    every table-discipline pass; counter bumps are scatter-adds and the
    arbitration itself is scatter-max/min, so neither appears here."""
    return [r for r in flow.scatters
            if r.prim == "scatter" and r.is_state and not r.in_pallas]


def _lock_roots(flow: df.Dataflow):
    """Group state scatters by operand root and keep the arrays that
    receive lock grants (some scatter's write facts carry LOCK_WIN)."""
    by_root: dict = {}
    for r in flow.scatters:
        if r.is_state and not r.in_pallas and r.root is not None:
            by_root.setdefault(id(r.root), []).append(r)
    return [recs for recs in by_root.values()
            if any(df.LOCK_WIN in r.write_facts for r in recs)]


@register_pass("protocol")
def protocol(trace: TargetTrace) -> list[Finding]:
    """Proves lock-dominates-write, validate-before-install,
    abort-implies-unlock, and commit-after-replication dataflow."""
    if trace.jaxpr is None:
        return []                    # the purity pass owns trace failures
    flags = set(getattr(trace, "protocol", None) or ())
    if not flags:
        return []
    flow = df.analyze(trace)
    out: list[Finding] = []

    installs = _installs(flow)
    if FLAG_CERTIFIED in flags:
        for r in installs:
            if not (r.write_facts & {df.LOCK_WIN, df.SORTED}):
                out.append(Finding(
                    "protocol", "unlocked-install", SEV_ERROR, trace.name,
                    "overwrite scatter into persistent table state whose "
                    "indices/updates carry neither LOCK_WIN (a lock-grant "
                    "dependency) nor segment-sort evidence: the write "
                    "mask does not descend from lock certification, so a "
                    "refactor can install rows nobody locked",
                    primitive=r.prim, site=r.site, path="/".join(r.path),
                    suggestion="derive the scatter mask (or its "
                               "where()-masked indices) from the grant "
                               "vector of the lock arbitration, as "
                               "engines/tatp_dense.pipe_step's wmask "
                               "does, or resolve writers with "
                               "ops/segments.sort_batch"))

    if FLAG_OCC in flags:
        for r in installs:
            if df.VALIDATED not in r.write_facts:
                out.append(Finding(
                    "protocol", "unvalidated-install", SEV_ERROR,
                    trace.name,
                    "install scatter on an OCC path whose indices/updates "
                    "do not depend on VALIDATED (the read-set stamp "
                    "equality re-check): the engine can install a write "
                    "whose read set changed after wave 1 — the exact "
                    "FaSST verify-stage contract",
                    primitive=r.prim, site=r.site, path="/".join(r.path),
                    suggestion="fold the validate compare into the "
                               "surviving-txn mask before the install "
                               "wave (alive &= ~changed in "
                               "engines/tatp_dense.pipe_step)"))

    if flags & {FLAG_CERTIFIED, FLAG_DRAIN}:
        aborts = flow.seeded(df.ABORT_MASK)
        roots = _lock_roots(flow)
        if aborts:
            for recs in roots:
                expiring = any(df.STAMP in r.update_facts for r in recs)
                releasing = any(df.ABORT_MASK in r.write_facts
                                for r in recs)
                two_site = len({r.site for r in recs}) >= 2 \
                    or len(recs) >= 2
                if not (expiring or releasing or two_site):
                    grant_site = next(
                        (r for r in recs
                         if df.LOCK_WIN in r.write_facts), recs[0])
                    out.append(Finding(
                        "protocol", "abort-leaks-lock", SEV_ERROR,
                        trace.name,
                        "this trace produces an abort mask "
                        f"(first seed: {aborts[0].prim} at "
                        f"{aborts[0].site}) but the lock array written "
                        "here is grant-only: no expiring step stamp in "
                        "its updates, no write whose facts carry "
                        "ABORT_MASK, and no second release site — an "
                        "aborting transaction leaves its lock held "
                        "forever",
                        primitive=grant_site.prim, site=grant_site.site,
                        path="/".join(grant_site.path),
                        suggestion="stamp the step counter into the "
                                   "lock word so stale locks expire "
                                   "(engines/smallbank_dense), or add "
                                   "the release wave over every granted "
                                   "lock, committed or aborted "
                                   "(engines/smallbank_pipeline's REL "
                                   "block)"))

    if FLAG_REPLICATED in flags:
        if not flow.ppermutes:
            out.append(Finding(
                "protocol", "no-replication-push", SEV_ERROR, trace.name,
                "replicated path with no ppermute in the trace: install "
                "records are never forwarded to the +1/+2 backup devices "
                "(the reference's CommitBck x2 / CommitLog x3 fan-out)",
                suggestion="forward the Installs record with "
                           "jax.lax.ppermute as "
                           "parallel/dense_sharded.py does"))
        elif not any(df.REPL_PUSHED in r.write_facts and r.is_state
                     for r in flow.scatters):
            out.append(Finding(
                "protocol", "push-not-applied", SEV_ERROR, trace.name,
                "ppermute present but nothing gathered from the hop is "
                "ever scattered into persistent state: the pushed "
                "install records are discarded, so backups and forwarded "
                "logs silently diverge from the primary",
                primitive="ppermute", site=flow.ppermutes[0].site,
                path="/".join(flow.ppermutes[0].path),
                suggestion="apply the ppermuted record to the backup "
                           "tables and append it to the local log "
                           "(parallel/dense_sharded._apply_backup)"))

    if FLAG_ELECTED in flags:
        elections = [r for r in flow.scatters
                     if r.prim in ("scatter-max", "scatter-min")
                     and not r.in_pallas
                     and df.SORTED in r.index_facts]
        if not elections:
            out.append(Finding(
                "protocol", "no-writer-election", SEV_ERROR, trace.name,
                "lock-free server trace with no segment reduction: no "
                "non-pallas scatter-max/min over SORTED indices exists, "
                "so nothing arbitrates between duplicate writers to the "
                "same key — last-writer-wins degrades to whichever lane "
                "XLA happens to scatter last",
                suggestion="elect one writer per key segment with "
                           "ops/segments.seg_max_where over the sorted "
                           "batch ranks, as engines/store.step's "
                           "last_w_rank does"))
        for r in installs:
            if df.SORTED not in r.write_facts:
                out.append(Finding(
                    "protocol", "unelected-install", SEV_ERROR,
                    trace.name,
                    "overwrite scatter into persistent server state "
                    "whose indices/updates carry no SORTED evidence: "
                    "the write mask does not descend from the segment "
                    "writer election, so duplicate or unelected lanes "
                    "can install racing rows",
                    primitive=r.prim, site=r.site, path="/".join(r.path),
                    suggestion="route the install mask through the "
                               "sorted-batch election "
                               "(segments.sort_batch + seg_max_where) "
                               "before scattering"))
            elif not r.unique_indices:
                out.append(Finding(
                    "protocol", "uncertified-install", SEV_ERROR,
                    trace.name,
                    "elected install scatter without "
                    "unique_indices=True: the one-writer-per-row claim "
                    "is no longer stated to XLA, so the scatter "
                    "serializes and the OOB-dup lowering contract the "
                    "tests pin (segments.first_rank_where) is "
                    "unguarded",
                    primitive=r.prim, site=r.site, path="/".join(r.path),
                    suggestion="restore unique_indices=True with "
                               "mode='drop' on the masked install, as "
                               "engines/store.step's table writes "
                               "declare"))

    return out
