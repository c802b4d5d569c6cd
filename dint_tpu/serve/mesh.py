"""MeshServeEngine: the whole (hosts x chips) mesh as ONE open-loop
transactional service (dintmesh, round 18).

DINT tops out at 3 shard servers with per-packet in-kernel handling; our
answer to "millions of users" is to serve SmallBank over the full 2-D
(dcn x ici) mesh as a single always-on plane. The two halves already
exist: `serve/engine.py` (round 17) is the single-device open-loop pump
with pre-drawn Caladan-style arrivals, and `parallel/multihost_sb.py`
(round 14) is cross-shard 2PC over the mesh but closed-loop. This module
composes them on the serve=True cohort form the runner gained this
round:

* **Per-host admission, one global controller.** Arrivals are routed to
  hosts round-robin at ingest (arrival k -> host k mod H — a stand-in
  for H independent NIC queues, deterministic under VirtualClock); each
  host sheds NEWEST-FIRST against its own backlog bound, but the width
  policy is ONE `WidthController` over the global offered rate observed
  in per-device units (``lanes_scale = H*C``) — every device always
  serves at the same width, which is what keeps one jitted step valid
  for the whole mesh.
* **Mesh-coordinated width switches at drain boundaries.** A width
  switch is a recompile point, so it is already the natural mesh-wide
  barrier: `_detach` drains the jitted pipeline across every device
  (flush steps + tail stats + counter ledger), then `_attach` inits the
  new width. No device ever runs a different width than its peers.
* **Shed mirror across the mesh.** Host h's shed tally rides the next
  dispatched block at occ/shed slot [h, 0, 0], so the device-side
  serve_shed_lanes counter reconciles with the per-host host tallies
  exactly as on the single-device plane.
* **Overlap knob.** ``overlap=True`` serves through the double-buffered
  route (cohort i+1's host-aggregated DCN all_to_all issued under
  cohort i's owner waves — bit-identical to the unoverlapped route by
  the runner's pin). Default OFF pending the pre-registered hardware
  A/B (PERF.md round 18 decision rule); the CPU tests pin that the
  serving plane's reports are identical either way.

Deterministic end-to-end under VirtualClock: the ServiceModel IS the
device (one block advances virtual time by cpb x service_us(w)), so two
runs with the same (schedule, seed, geometry) produce bit-identical
reports, width trajectories, and shed counts.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from .arrivals import ArrivalStream
from .controller import ControllerCfg, ServiceModel, WidthController
from .engine import ServeEngine, VirtualClock, cached_runner


class MeshServeEngine(ServeEngine):
    """Open-loop SmallBank serving over the 2-D (dcn x ici) mesh.

    Parameters beyond ServeEngine's: ``mesh_shape`` = (n_hosts, n_ici)
    (>= 3 hosts — the replication fault-domain rule); ``hierarchical``
    picks the ici-then-dcn exchange (on by default); ``overlap`` enables
    the double-buffered route (off by default, pending the
    pre-registered hardware A/B of PERF.md round 18). size is
    n_accounts (global)."""

    ENGINES = ("multihost_sb",)

    def __init__(self, n_accounts: int, *,
                 mesh_shape: tuple[int, int] = (4, 2),
                 cfg: ControllerCfg | None = None,
                 model: ServiceModel | None = None,
                 cohorts_per_block: int = 2, depth: int = 2,
                 clock=None, monitor: bool = True, seed: int = 0,
                 idle_poll_us: float = 50_000.0,
                 hierarchical: bool = True, overlap: bool = False,
                 runner_kw: dict | None = None):
        from ..parallel import multihost_sb as mhs
        self.n_hosts, self.n_ici = int(mesh_shape[0]), int(mesh_shape[1])
        self.mesh = mhs.make_mesh_2d(self.n_hosts, self.n_ici)
        self.n_devices = self.n_hosts * self.n_ici
        self.hierarchical = bool(hierarchical)
        self.overlap = bool(overlap)
        super().__init__("multihost_sb", n_accounts, cfg=cfg, model=model,
                         cohorts_per_block=cohorts_per_block, depth=depth,
                         clock=clock, monitor=monitor, seed=seed,
                         idle_poll_us=idle_poll_us, runner_kw=runner_kw)
        # ONE global controller in per-device units: D cohorts of width w
        # serve every step, so the single-device policy functions apply
        # to offered_rate / D unchanged
        self.ctl = WidthController(self.cfg, self.model,
                                   lanes_scale=self.n_devices)
        # per-host admission state (the base class _backlog is unused)
        self._host_backlog: list[collections.deque] = [
            collections.deque() for _ in range(self.n_hosts)]
        self._host_shed_pending = [0] * self.n_hosts
        self.shed_by_host = [0] * self.n_hosts
        self.admitted_by_host = [0] * self.n_hosts
        self._arrival_idx = 0

    # -- construction ---------------------------------------------------

    def _fresh_db(self, seed: int):
        from ..parallel import multihost_sb as mhs
        return mhs.create_multihost_sb(self.mesh, self.size)

    def _build(self, w: int):
        return cached_runner(
            "multihost_sb", self.size, mesh=self.mesh, w=w,
            cohorts_per_block=self.cpb, monitor=self.monitor,
            hierarchical=self.hierarchical, serve=True,
            overlap=self.overlap, **self.runner_kw)

    def warmup(self) -> None:
        zeros = np.zeros((self.n_hosts, self.n_ici, self.cpb), np.int32)
        key = jax.random.PRNGKey(0)
        for w in self.cfg.widths:
            run, init, drain = self._runners[w]
            db = jax.tree_util.tree_map(jnp.array, self._db)
            carry = init(db)
            carry, _ = run(carry, key, zeros, zeros)
            drain(carry)

    # -- the pump -------------------------------------------------------

    def _dispatch(self, occ: np.ndarray, shed: np.ndarray) -> None:
        run, _, _ = self._runners[self._cur_w]
        key = jax.random.fold_in(self.base_key, self._block_idx)
        t_disp = self.clock.now()
        self._carry, stats = run(self._carry, key, occ, shed)
        self._pending.append((stats, t_disp, self._cur_w))
        self._block_idx += 1
        self.blocks += 1
        self.steps_by_width[self._cur_w] += self.cpb
        if isinstance(self.clock, VirtualClock):
            # the model IS the device: the whole mesh advances one block
            self.clock.sleep(self.cpb * self.model.service_us(self._cur_w)
                             * 1e-6)
        if len(self._pending) >= self.depth:
            self._retire_one()

    # -- per-host admission ---------------------------------------------

    def _ingest(self, stream: ArrivalStream, dt: float) -> None:
        got = stream.take_until(self._rel_now())
        self.offered_total += len(got)
        for ts in got.tolist():
            self._host_backlog[self._arrival_idx % self.n_hosts].append(ts)
            self._arrival_idx += 1
        if dt > 0:
            # global rate; the controller converts to per-device units
            self.ctl.observe_rate(len(got) / dt)

    def _admit(self) -> int:
        """Per-host newest-first shedding: each host's bound is the
        single-device backlog bound times the n_ici chips it feeds."""
        cap = self.ctl.max_backlog() * self.n_ici
        shed = 0
        for h, bl in enumerate(self._host_backlog):
            backlog0 = len(bl)
            host_shed = 0
            while len(bl) > cap:
                bl.pop()                      # newest first
                self.shed_by_host[h] += 1
                self._host_shed_pending[h] += 1
                host_shed += 1
            if host_shed:
                self.ctl.journal_shed(backlog0, host_shed,
                                      scale=self.n_ici, host=h)
            shed += host_shed
        self.shed_total += shed
        self._shed_pending += shed
        return shed

    def _fill_block(self, w: int) -> np.ndarray:
        """Per-host FIFO fill into [H, C, cpb] occupancies (cohort-major
        across the host's chips) + queue-delay charge per admitted
        lane."""
        occ = np.zeros((self.n_hosts, self.n_ici, self.cpb), np.int32)
        t = self._rel_now()
        for h, bl in enumerate(self._host_backlog):
            for i in range(self.cpb):
                for c in range(self.n_ici):
                    n = min(len(bl), w)
                    occ[h, c, i] = n
                    if n:
                        ts = np.fromiter(
                            (bl.popleft() for _ in range(n)),
                            np.float64, count=n)
                        self.queue_hist.add(np.maximum(t - ts, 0.0) * 1e6)
            self.admitted_by_host[h] += int(occ[h].sum())
        self.admitted_total += int(occ.sum())
        return occ

    def _shed_mirror(self) -> np.ndarray:
        """Move the pending per-host shed tallies onto the device ledger:
        host h's count rides slot [h, 0, 0] of the next block."""
        shed = np.zeros((self.n_hosts, self.n_ici, self.cpb), np.int32)
        for h in range(self.n_hosts):
            shed[h, 0, 0] = self._host_shed_pending[h]
            self._host_shed_pending[h] = 0
        self._shed_pending = 0
        return shed

    # -- the serving loop -----------------------------------------------

    def run(self, schedule: np.ndarray, *, max_blocks: int | None = None
            ) -> dict:
        stream = ArrivalStream(schedule)
        if self._t0 is None:
            self._t0 = self.clock.now()
        last_poll = self._rel_now()

        while True:
            now = self._rel_now()
            self._ingest(stream, now - last_poll)
            last_poll = now
            self._admit()

            if not any(self._host_backlog):
                if stream.exhausted:
                    break
                nxt = stream.peek() - self._rel_now()
                self.clock.sleep(max(min(nxt, self.idle_poll_us * 1e-6),
                                     1e-9))
                continue

            w = self.ctl.width()
            if w != self._cur_w:
                # mesh-coordinated switch: _detach's drain flushes the
                # jitted pipeline on EVERY device — the recompile point
                # is the mesh-wide barrier, no extra protocol needed
                if self._cur_w is not None:
                    self._detach()
                self._attach(w)

            occ = self._fill_block(w)
            self._dispatch(occ, self._shed_mirror())

            if max_blocks is not None and self.blocks >= max_blocks:
                break

        self._retire_all()
        self._elapsed = self._rel_now()
        return self.snapshot()

    # -- reporting ------------------------------------------------------

    def snapshot(self) -> dict:
        rep = super().snapshot()
        rep["mesh"] = {"n_hosts": self.n_hosts, "n_ici": self.n_ici,
                       "hierarchical": self.hierarchical,
                       "overlap": self.overlap}
        rep["per_host"] = [
            {"host": h, "admitted": self.admitted_by_host[h],
             "shed": self.shed_by_host[h]}
            for h in range(self.n_hosts)]
        return rep
