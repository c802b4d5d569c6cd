"""ServeEngine: the always-on dintserve serving loop.

Turns the batch certification engines into a long-lived service: an
open-loop arrival stream (arrivals.py) fills variable-occupancy cohorts,
a depth-k double-buffered pump keeps the device busy while the host
accumulates the next block and drains the previous one, and an SLO
controller (controller.py) adapts the cohort width among a menu of
pre-compiled widths and sheds admissions the SLO can no longer cover.

Three structural commitments, each pinned by a test:

* **Bit-identity.** Transaction content comes from the cohort PRNG key
  (fold_in(base_key, block_idx) — the closed-loop convention), and the
  occupancy mask erases lanes >= occ AFTER full-width generation. At
  occ == width the serve path is therefore value-identical to the
  closed-loop runner on the same keys: serving is a masking of batch
  certification, not a fork of it.
* **Zero steady-state allocation.** Every serve block runs through the
  same jitted callable with donate_argnums=0: after warmup the carry
  (db tables, contexts, counters) ping-pongs through donated buffers
  and `jax.live_arrays()` stays constant block over block.
* **Graceful degradation.** Past saturation the controller sits at the
  knee width and SHEDS (newest-first) instead of stalling; every shed
  lane is tallied host-side and mirrored into the device counter ledger
  (serve_shed_lanes), so the artifact can prove the service never
  silently dropped work.

Clocking: a RealClock serves wall time (hardware runs); a VirtualClock
plus the controller's ServiceModel makes the whole loop — ingestion,
width choices, shedding — a deterministic function of (schedule, seed),
which is how the CPU tests pin controller behaviour.
"""
from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor as mon
from ..stats import LatencyHistogram
from .arrivals import ArrivalStream
from .controller import (ControllerCfg, ServiceModel, WidthController,
                         recommend_hot_frac)


class RealClock:
    """Wall time (monotonic) — hardware serving."""

    def now(self) -> float:
        return time.monotonic()

    def sleep(self, s: float) -> None:
        if s > 0:
            time.sleep(s)


class VirtualClock:
    """Deterministic time: advances only when told. Under it the serve
    loop never calls time.*, so two runs with the same schedule + seed
    are bit-identical — including every controller decision."""

    def __init__(self):
        self.t = 0.0

    def now(self) -> float:
        return self.t

    def sleep(self, s: float) -> None:
        if s > 0:
            self.t += s


# process-wide (run, init, drain) cache: two ServeEngines over the same
# (engine, geometry, width, flags) share one jitted serve step — the
# callables are stateless, so sharing is safe, and a restarted engine
# (or a CPU test rerunning a config) never pays the compile twice
_RUNNER_CACHE: dict = {}


def cached_runner(engine: str, size: int, *, val_words: int = 4, **kw):
    """Build (run, init, drain) for a dense engine, at most once per
    process per distinct (engine, size, val_words, kw) — the serve
    plane's compile cache, also usable for closed-loop comparison
    builds in tests. Unhashable kw values fall back to an uncached
    build rather than failing."""
    try:
        key = (engine, size, val_words, tuple(sorted(kw.items())))
    except TypeError:
        key = None
    if key is not None and key in _RUNNER_CACHE:
        return _RUNNER_CACHE[key]
    if engine == "tatp_dense":
        from ..engines import tatp_dense as td
        out = td.build_pipelined_runner(size, val_words=val_words, **kw)
    elif engine == "store":
        # round-20 dintscan: the KV store as a first-class serve family
        # (YCSB-E-shaped on-device cohorts, optional ordered-run scans)
        from ..engines import store as st
        out = st.build_serve_runner(size, val_words=val_words, **kw)
    elif engine == "multihost_sb":
        # the mesh serving plane (serve/mesh.py): kw carries the 2-D
        # mesh; the builder is itself memoized, this cache just keeps
        # the lookup uniform across engine families
        from ..parallel import multihost_sb as mhs
        mkw = dict(kw)
        out = mhs.build_multihost_sb_runner(mkw.pop("mesh"), size, **mkw)
    else:
        from ..engines import smallbank_dense as sd
        out = sd.build_pipelined_runner(size, **kw)
    if key is not None:
        _RUNNER_CACHE[key] = out
    return out


class ServeEngine:
    """Long-lived serving plane over one dense engine family.

    Parameters
    ----------
    engine : 'tatp_dense' | 'smallbank_dense'
    size : table size (n_sub / n_accounts)
    cfg / model : controller config + service-time prior
    cohorts_per_block : steps per dispatched block (pipeline depth rides
        the existing engines; this is the scan length per dispatch)
    depth : host->device pump depth — the host runs at most ``depth``
        blocks ahead of the oldest unretired block (2 = the classic
        double buffer; shim/pump.py got the same knob this round)
    clock : RealClock (default) or VirtualClock (deterministic tests)
    monitor : thread the dintmon counter plane (needed for the serve
        counter reconciliation identity and hot_frac auto-sizing)
    runner_kw : forwarded to build_pipelined_runner (mix, use_hotset,
        hot_frac, ...) — always wins over the plan
    plan : "auto" (default) reads the pinned PLAN.json (analysis/plan):
        the width menu + SLO come from the plan's serve priors when
        ``cfg`` is None, build knobs the plan pins for this engine's
        serve workload replace the env-flag default path, and the
        hot_frac prior seeds the rebuild loop. A plan dict is accepted
        directly (tests); None disables plan consumption. Without a
        readable plan everything falls back to today's defaults and
        the snapshot records ``"plan": None`` — never a silent default.
    adapt_hot_frac : rebuild the width menu at the plan-recommended
        hot_frac at width-switch drain boundaries (the only points the
        pipeline is empty and the tables are host-side, so a re-shape
        is safe). None = auto: on iff a hot_frac prior exists and the
        counter plane that feeds the recommendation is threaded.
    """

    # engine families this class can drive; subclasses (serve/mesh.py's
    # MeshServeEngine) narrow it to their own runner-builder path
    ENGINES: tuple[str, ...] = ("tatp_dense", "smallbank_dense", "store")

    def __init__(self, engine: str, size: int, *,
                 cfg: ControllerCfg | None = None,
                 model: ServiceModel | None = None,
                 cohorts_per_block: int = 2, depth: int = 2,
                 val_words: int = 4, clock=None, monitor: bool = True,
                 seed: int = 0, idle_poll_us: float = 50_000.0,
                 runner_kw: dict | None = None, plan="auto",
                 adapt_hot_frac: bool | None = None):
        assert engine in self.ENGINES, engine
        assert depth >= 1
        self.engine = engine
        self.size = size
        self.cpb = cohorts_per_block
        self.depth = depth
        self.val_words = val_words
        self.clock = clock or RealClock()
        self.monitor = monitor
        self.idle_poll_us = idle_poll_us
        self.runner_kw = dict(runner_kw or {})

        plan_knobs, priors, self.plan_meta = self._resolve_plan(plan)
        if cfg is None and priors:
            cfg = ControllerCfg(
                widths=tuple(sorted(int(w) for w in priors["widths"])),
                slo_us=float(priors["slo_us"]))
        self.cfg = cfg or ControllerCfg()
        if model is None and priors:
            model = ServiceModel(base_us=priors["model"]["base_us"],
                                 per_lane_ns=priors["model"]["per_lane_ns"])
        self.model = model or ServiceModel()
        self._apply_plan_knobs(plan_knobs)

        # hot_frac rebuild loop: prior from runner_kw if pinned by the
        # caller, else the plan's serve prior; None = engine family has
        # no hot tier and the loop stays off
        self._hot_frac = self.runner_kw.get("hot_frac")
        if self._hot_frac is None and priors:
            self._hot_frac = priors.get("hot_frac")
        if adapt_hot_frac is None:
            adapt_hot_frac = self._hot_frac is not None and self.monitor
        self.adapt_hot_frac = bool(adapt_hot_frac)
        self.hot_frac_rebuilds = 0

        self.base_key = jax.random.PRNGKey(seed)
        self.ctl = WidthController(self.cfg, self.model)

        # one pre-compiled (run, init, drain) per registered width —
        # built eagerly so no width switch ever pays a compile online
        self._runners = {w: self._build(w) for w in self.cfg.widths}

        self._db = self._fresh_db(seed)
        self._cur_w: int | None = None
        self._carry = None

        # host-side ledgers
        self.queue_hist = LatencyHistogram()     # per admitted lane (µs)
        self.service_hist = LatencyHistogram()   # per retired block (µs)
        self.stats_total = None                  # summed engine stats
        self.counters_total: dict[str, int] = {}
        self.shed_total = 0
        self._shed_pending = 0                   # awaiting device mirror
        self.admitted_total = 0
        self.offered_total = 0
        self.blocks = 0
        self.steps_by_width: dict[int, int] = {w: 0 for w in self.cfg.widths}
        self._backlog: collections.deque[float] = collections.deque()
        self._pending: collections.deque = collections.deque()
        self._block_idx = 0
        self._t0 = None
        self._elapsed = 0.0

    # -- construction ---------------------------------------------------

    def _resolve_plan(self, plan):
        """-> (knobs, serve_priors | None, meta | None) for this
        engine's serve workload. Missing / unreadable plan degrades to
        (today's env-default behaviour, no priors, meta None)."""
        if plan is None:
            return {}, None, None
        from ..analysis import plan as P
        doc = plan if isinstance(plan, dict) else None
        if doc is None:
            try:
                doc = P.load_plan()
            except (OSError, ValueError):
                return {}, None, None
        wname = P.SERVE_WORKLOADS.get(self.engine)
        if wname is None or wname not in doc.get("workloads", {}):
            return {}, None, None
        knobs, meta = P.resolve_for(wname, plan=doc)
        return knobs, doc["workloads"][wname].get("serve"), meta

    def _apply_plan_knobs(self, knobs: dict) -> None:
        """Plan-resolved build knobs replace the env-flag default path:
        a knob the caller left out of runner_kw builds at the plan's
        pinned value instead of whatever the ambient DINT_* flags say
        (under DINT_PLAN_OVERRIDE=1 resolve_for already folded the env
        value back in). Explicit runner_kw always wins."""
        for k, v in knobs.items():
            self.runner_kw.setdefault(k, v)

    def _fresh_db(self, seed: int):
        if self.engine == "tatp_dense":
            from ..engines import tatp_dense as td
            return td.populate(np.random.default_rng(seed), self.size,
                               val_words=self.val_words)
        if self.engine == "store":
            from ..clients import micro
            return micro.make_store_table(self.size,
                                          val_words=self.val_words)
        from ..engines import smallbank_dense as sd
        return sd.create(self.size)

    def _build(self, w: int):
        return cached_runner(
            self.engine, self.size, val_words=self.val_words,
            w=w, cohorts_per_block=self.cpb, monitor=self.monitor,
            trace=False, serve=True, **self.runner_kw)

    def warmup(self) -> None:
        """Compile every registered width's serve step + drain before
        serving starts: compilation is minutes-scale on TPU and must
        never be charged to a client's queueing delay. Runs each width
        once on a THROWAWAY copy of the tables (run/drain donate their
        carry, so the live db is never touched); the jit cache keyed on
        the carry shapes then serves every later dispatch. VirtualClock
        tests skip this — virtual time never observes compile time."""
        zeros = np.zeros(self.cpb, np.int32)
        key = jax.random.PRNGKey(0)
        for w in self.cfg.widths:
            run, init, drain = self._runners[w]
            db = jax.tree_util.tree_map(jnp.array, self._db)
            carry = init(db)
            carry, _ = run(carry, key, zeros, zeros)
            drain(carry)

    # -- width lifecycle ------------------------------------------------

    def _attach(self, w: int) -> None:
        """init at width w (first block or after a width-switch drain)."""
        _, init, _ = self._runners[w]
        self._carry = init(self._db)
        self._db = None          # ownership moved into the carry
        self._cur_w = w

    def _detach(self) -> None:
        """Drain the live pipeline: flush in-flight cohorts, absorb the
        tail stats and the device counter ledger, recover the db."""
        self._retire_all()
        _, _, drain = self._runners[self._cur_w]
        out = drain(self._carry)
        self._carry = None
        db, tail = out[0], out[1]
        self._absorb_stats(np.asarray(tail, np.int64))
        if self.monitor:
            snap = mon.snapshot(out[-1])
            for k, v in snap.items():
                self.counters_total[k] = self.counters_total.get(k, 0) + v
        self._db = db
        self._cur_w = None

    def _absorb_stats(self, stats: np.ndarray) -> None:
        row = stats.astype(np.int64).sum(axis=0)
        self.stats_total = (row if self.stats_total is None
                            else self.stats_total + row)

    def _maybe_rebuild_hot_frac(self) -> None:
        """At a width-switch drain boundary (pipeline empty, tables
        host-side — the only safe re-shape points) fold the observed
        hot-tier counters into a new hot_frac and rebuild the width
        menu when the recommendation moved. With no hot-tier traffic
        (hot counters zero) the recommendation is the status quo and
        this is a no-op, so plans without a hot tier never rebuild."""
        if not self.adapt_hot_frac or self._hot_frac is None:
            return
        rec = self.hot_frac_recommendation(self._hot_frac)
        self.ctl.journal_hot_frac(
            self._hot_frac, self.counters_total.get("hot_hits", 0),
            self.counters_total.get("hot_cold_rows", 0), rec)
        if rec == self._hot_frac:
            return
        self._hot_frac = rec
        self.runner_kw["hot_frac"] = rec
        self.hot_frac_rebuilds += 1
        self._runners = {w: self._build(w) for w in self.cfg.widths}

    # -- the pump -------------------------------------------------------

    def _dispatch(self, occ: np.ndarray, shed0: int) -> None:
        run, _, _ = self._runners[self._cur_w]
        key = jax.random.fold_in(self.base_key, self._block_idx)
        shed = np.zeros(self.cpb, np.int32)
        shed[0] = shed0
        t_disp = self.clock.now()
        self._carry, stats = run(self._carry, key,
                                 occ.astype(np.int32), shed)
        self._pending.append((stats, t_disp, self._cur_w))
        self._block_idx += 1
        self.blocks += 1
        self.steps_by_width[self._cur_w] += self.cpb
        if isinstance(self.clock, VirtualClock):
            # the model IS the device under virtual time
            self.clock.sleep(self.cpb * self.model.service_us(self._cur_w)
                             * 1e-6)
        if len(self._pending) >= self.depth:
            self._retire_one()

    def _retire_one(self) -> None:
        stats, t_disp, w = self._pending.popleft()
        host = np.asarray(stats, np.int64)     # blocks until materialized
        if isinstance(self.clock, VirtualClock):
            service_us = self.cpb * self.model.service_us(w)
        else:
            service_us = max((self.clock.now() - t_disp) * 1e6, 1e-3)
        self._absorb_stats(host)
        self.service_hist.add(service_us)
        self.ctl.observe_service(w, service_us / self.cpb)

    def _retire_all(self) -> None:
        while self._pending:
            self._retire_one()

    # -- the serving loop -----------------------------------------------

    def _rel_now(self) -> float:
        return self.clock.now() - self._t0

    def _ingest(self, stream: ArrivalStream, dt: float) -> None:
        got = stream.take_until(self._rel_now())
        self.offered_total += len(got)
        self._backlog.extend(got.tolist())
        if dt > 0:
            self.ctl.observe_rate(len(got) / dt)

    def _admit(self) -> int:
        """Shed newest arrivals past the SLO-feasible backlog bound.
        Returns lanes shed this poll (also queued for device mirror)."""
        cap = self.ctl.max_backlog()
        backlog0 = len(self._backlog)
        shed = 0
        while len(self._backlog) > cap:
            self._backlog.pop()               # newest first
            shed += 1
        if shed:
            self.ctl.journal_shed(backlog0, shed)
        self.shed_total += shed
        self._shed_pending += shed
        return shed

    def _fill_block(self, w: int) -> np.ndarray:
        """Pop FIFO arrivals into per-cohort occupancies and charge each
        admitted lane its queueing delay (dispatch − arrival)."""
        occ = np.zeros(self.cpb, np.int32)
        t = self._rel_now()
        for i in range(self.cpb):
            n = min(len(self._backlog), w)
            occ[i] = n
            if n:
                ts = np.fromiter((self._backlog.popleft() for _ in range(n)),
                                 np.float64, count=n)
                self.queue_hist.add(np.maximum(t - ts, 0.0) * 1e6)
        self.admitted_total += int(occ.sum())
        return occ

    def run(self, schedule: np.ndarray, *, max_blocks: int | None = None
            ) -> dict:
        """Serve one arrival schedule to completion (every arrival either
        served or shed), then flush the pump, drain the pipeline, and
        return the report. Re-entrant: a second schedule continues on
        the same tables."""
        stream = ArrivalStream(schedule)
        if self._t0 is None:
            self._t0 = self.clock.now()
        last_poll = self._rel_now()

        while True:
            now = self._rel_now()
            self._ingest(stream, now - last_poll)
            last_poll = now
            self._admit()

            if not self._backlog:
                if stream.exhausted:
                    break
                nxt = stream.peek() - self._rel_now()
                # idle: park until the next arrival (bounded by the idle
                # poll so a real server still services its control plane)
                self.clock.sleep(max(min(nxt, self.idle_poll_us * 1e-6),
                                     1e-9))
                continue

            w = self.ctl.width()
            if w != self._cur_w:
                if self._cur_w is not None:
                    self._detach()
                self._maybe_rebuild_hot_frac()
                self._attach(w)

            occ = self._fill_block(w)
            shed0, self._shed_pending = self._shed_pending, 0
            self._dispatch(occ, shed0)

            if max_blocks is not None and self.blocks >= max_blocks:
                break

        self._retire_all()
        self._elapsed = self._rel_now()
        return self.snapshot()

    def close(self) -> None:
        """Flush + drain; recovers the tables into self._db."""
        if self._cur_w is not None:
            self._detach()

    # -- reporting ------------------------------------------------------

    def hot_frac_recommendation(self, cur: float) -> float:
        """Auto-size hot_frac from the observed hot-tier counters (to be
        applied at the next engine rebuild — hot_frac is a shape)."""
        return recommend_hot_frac(
            cur, self.counters_total.get("hot_hits", 0),
            self.counters_total.get("hot_cold_rows", 0))

    def snapshot(self) -> dict:
        elapsed = self._elapsed or max(self._rel_now(), 1e-9)
        qp, sp = self.queue_hist.percentiles(), self.service_hist.percentiles()
        counters = dict(self.counters_total)
        if self.monitor and self._carry is not None:
            # non-destructive peek at the live ledger (absorbed for real
            # at the next drain; snapshot() must reconcile mid-flight)
            for k, v in mon.snapshot(self._carry[-1]).items():
                counters[k] = counters.get(k, 0) + v
        committed = attempted = 0
        if self.stats_total is not None:
            # STAT_ATTEMPTED / STAT_COMMITTED are 0/1 for both families
            attempted, committed = int(self.stats_total[0]), \
                int(self.stats_total[1])
        return {
            "engine": self.engine,
            "widths": list(self.cfg.widths),
            "blocks": self.blocks,
            "steps_by_width": {str(k): v
                               for k, v in self.steps_by_width.items()},
            "offered": self.offered_total,
            "admitted": self.admitted_total,
            "shed": self.shed_total,
            "attempted": attempted,
            "committed": committed,
            "elapsed_s": elapsed,
            "offered_rate": self.offered_total / elapsed,
            "achieved_rate": committed / elapsed,
            "slo_us": self.cfg.slo_us,
            "slo_met": qp["p99"] <= self.cfg.slo_us,
            "queue": {**qp, "hist": self.queue_hist.to_dict()},
            "service": {**sp, "hist": self.service_hist.to_dict()},
            "controller": self.ctl.snapshot(),
            "counters": counters,
            "plan": self.plan_meta,
            "hot_frac": {"current": self._hot_frac,
                         "adaptive": self.adapt_hot_frac,
                         "rebuilds": self.hot_frac_rebuilds},
        }
