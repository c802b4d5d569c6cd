"""L5 measurement layer: the reference's client-side stat contract.

Re-expresses the reference's per-thread counters + timed stat window
(/root/reference/store/caladan/stat.h:10-20: warmup to t=5s, measure to
t=15s) and the final metric block every client prints (throughput, goodput,
average/median/99th/99.9th latency in microseconds —
tatp/caladan/client_ebpf_shard.cc:368-377). Batched TPU execution changes
*how* latencies arise (a txn's latency spans the waves of its cohort) but
not the metric definitions, which are kept identical so results are
side-by-side comparable with the reference's clients.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np


@dataclasses.dataclass
class Window:
    """Warmup/measure/exit schedule (store/caladan/stat.h:10-13)."""
    warmup_s: float = 5.0
    measure_s: float = 10.0

    @property
    def total_s(self):
        return self.warmup_s + self.measure_s


class StatClock:
    """Drives a client loop through warmup -> measure -> done phases.

    Usage: tick() each iteration; record counters only when `measuring`
    (False again once the window has ended).
    """

    def __init__(self, window: Window | None = None):
        self.window = window or Window()
        self.t0 = time.monotonic()
        self._measure_t0 = None
        self._measure_t1 = None
        self._done = False

    def tick(self) -> str:
        now = time.monotonic()
        t = now - self.t0
        # Close the interval over the wave that ran since the previous tick
        # BEFORE classifying this one, so the final measured wave's duration
        # is included when this tick crosses into "done" (counts and time
        # then cover exactly the same waves).
        if self._measure_t0 is not None and not self._done:
            self._measure_t1 = now
        if t < self.window.warmup_s:
            return "warmup"
        if t < self.window.total_s:
            if self._measure_t0 is None:
                self._measure_t0 = self._measure_t1 = now
            return "measure"
        self._done = True
        return "done"

    @property
    def measuring(self) -> bool:
        return (not self._done and self._measure_t0 is not None
                and self._measure_t1 is not None)

    @property
    def measured_s(self) -> float:
        if self._measure_t0 is None or self._measure_t1 is None:
            return 0.0
        return self._measure_t1 - self._measure_t0


class LatencyHistogram:
    """Fixed log-bucketed latency histogram (µs) — the dintscope SLO
    sensor that rides NEXT TO the reservoir (bench/exp artifacts carry it
    as the "lat_hist" block alongside the percentile block).

    Why a second structure when `LatencyReservoir` already exists: the
    reservoir is exact until `cap` and then SAMPLED — merging two
    downsampled reservoirs (cross-shard, cross-window) is approximate and
    order-dependent. Bucket counts add exactly: `merge` is associative
    and commutative, so per-shard / per-window histograms compose into
    run totals with zero loss (the same property the reference gets from
    per-CPU counter maps), which is what an always-on serving plane needs
    for SLO accounting. The price is resolution: 8 buckets per octave
    (width 2^(1/8) ≈ 9.05%), so a percentile read off the histogram is
    within ±2^(1/16)-1 ≈ 4.4% relative error of the exact nth-element
    value (buckets represent by their geometric midpoint; bounded-error
    contract pinned in tests/test_stats.py).

    Range: 2^-4 µs .. 2^28 µs (~4.5 min), 256 buckets; out-of-range
    samples clamp to the edge buckets (the bound does not cover them).
    Totality matches the round-3 reservoir contract: empty -> zeros,
    n == 1 -> every percentile is the same defined value, non-finite
    samples are excluded (counted in `dropped_nonfinite`), never NaN.
    """

    LO_EXP = -4
    HI_EXP = 28
    PER_OCTAVE = 8
    N_BUCKETS = (HI_EXP - LO_EXP) * PER_OCTAVE
    SCHEMA = 1

    def __init__(self):
        self.counts = np.zeros(self.N_BUCKETS, np.int64)
        self.n = 0
        self.sum_us = 0.0
        self.dropped_nonfinite = 0

    def add(self, lat_us: np.ndarray | float):
        arr = np.atleast_1d(np.asarray(lat_us, np.float64))
        finite = np.isfinite(arr)
        self.dropped_nonfinite += int(len(arr) - finite.sum())
        arr = arr[finite]
        if not len(arr):
            return
        # log2 of a non-positive sample is -inf -> clamps to bucket 0
        with np.errstate(divide="ignore"):
            idx = np.floor(np.log2(np.maximum(arr, 0.0))
                           * self.PER_OCTAVE) - self.LO_EXP * self.PER_OCTAVE
        idx = np.clip(np.nan_to_num(idx, neginf=0.0), 0,
                      self.N_BUCKETS - 1).astype(np.int64)
        np.add.at(self.counts, idx, 1)
        self.n += len(arr)
        self.sum_us += float(arr.sum())

    def merge(self, other: "LatencyHistogram"):
        """Exact, associative, commutative: bucket counts add. Returns
        self (accumulator style: `total.merge(shard_a).merge(shard_b)`)."""
        self.counts += other.counts
        self.n += other.n
        self.sum_us += other.sum_us
        self.dropped_nonfinite += other.dropped_nonfinite
        return self

    def _edge(self, i: int) -> float:
        return 2.0 ** (self.LO_EXP + i / self.PER_OCTAVE)

    def _rep(self, i: int) -> float:
        """Bucket representative: geometric midpoint of its edges."""
        return 2.0 ** (self.LO_EXP + (i + 0.5) / self.PER_OCTAVE)

    def quantile(self, q: float) -> float:
        """Value at quantile q in [0, 1] (0 when empty): the
        representative of the bucket holding the ceil(q*n)-th sample —
        the histogram analogue of nth_element."""
        if self.n == 0:
            return 0.0
        rank = min(max(int(np.ceil(q * self.n)), 1), self.n)
        cum = np.cumsum(self.counts)
        i = int(np.searchsorted(cum, rank))
        return self._rep(i)

    def percentiles(self) -> dict:
        """Same keys/totality as LatencyReservoir.percentiles."""
        if self.n == 0:
            return dict(avg=0.0, p50=0.0, p99=0.0, p999=0.0)
        return dict(avg=self.sum_us / self.n, p50=self.quantile(0.50),
                    p99=self.quantile(0.99), p999=self.quantile(0.999))

    def to_dict(self) -> dict:
        """Sparse, schema-stable serialization (artifact "lat_hist"
        block): only non-zero buckets, keyed by index."""
        return {
            "schema": self.SCHEMA,
            "lo_exp": self.LO_EXP, "per_octave": self.PER_OCTAVE,
            "n": int(self.n), "sum_us": round(self.sum_us, 3),
            "dropped_nonfinite": int(self.dropped_nonfinite),
            "buckets": {str(i): int(c) for i, c in enumerate(self.counts)
                        if c},
            **{f"{k}_us": round(v, 2)
               for k, v in self.percentiles().items()},
        }

    @classmethod
    def from_dict(cls, d: dict) -> "LatencyHistogram":
        if d.get("lo_exp", cls.LO_EXP) != cls.LO_EXP or \
                d.get("per_octave", cls.PER_OCTAVE) != cls.PER_OCTAVE:
            raise ValueError("histogram bucket geometry mismatch")
        h = cls()
        for i, c in (d.get("buckets") or {}).items():
            h.counts[int(i)] = int(c)
        h.n = int(d.get("n", int(h.counts.sum())))
        h.sum_us = float(d.get("sum_us", 0.0))
        h.dropped_nonfinite = int(d.get("dropped_nonfinite", 0))
        return h


class LatencyReservoir:
    """Latency sample store (µs). The reference keeps every sample in a
    per-thread vector and nth_element's it (store/caladan/stat.h:15-20);
    we keep up to `cap` samples with reservoir downsampling past that.

    Every sample is ALSO counted into a `LatencyHistogram` (`self.hist`):
    the reservoir serves exact percentiles for one window, the histogram
    serves exact cross-shard/cross-window merges and the artifact
    "lat_hist" block — two views of the same stream."""

    def __init__(self, cap: int = 1 << 20, seed: int = 0):
        self.cap = cap
        self.samples = np.empty(cap, np.float64)
        self.n_kept = 0
        self.n_seen = 0
        self.hist = LatencyHistogram()
        self._rng = np.random.default_rng(seed)

    def add(self, lat_us: np.ndarray | float):
        arr = np.atleast_1d(np.asarray(lat_us, np.float64))
        self.hist.add(arr)
        for start in range(0, len(arr), self.cap):
            self._add_chunk(arr[start:start + self.cap])

    def _add_chunk(self, arr):
        n = len(arr)
        room = self.cap - self.n_kept
        take = min(room, n)
        if take:
            self.samples[self.n_kept:self.n_kept + take] = arr[:take]
            self.n_kept += take
        rest = arr[take:]
        if len(rest):
            # reservoir: each later sample replaces a random kept one with
            # probability cap / seen-so-far
            seen = self.n_seen + take + np.arange(1, len(rest) + 1)
            keep = self._rng.random(len(rest)) < (self.cap / seen)
            idx = self._rng.integers(0, self.cap, size=len(rest))
            self.samples[idx[keep]] = rest[keep]
        self.n_seen += n

    def percentiles(self):
        """Metric dict, DEFINED at every fill level (tests/test_stats.py):

        * empty reservoir -> all zeros (a window that measured nothing
          reports 0, never NaN — the reference prints 0 lat lines too);
        * n == 1 -> every percentile equals the sample (linear
          interpolation over one point degenerates to it);
        * non-finite samples (a NaN/inf fed by a timing glitch) are
          EXCLUDED rather than poisoning every percentile — np.percentile
          propagates NaN through the whole vector otherwise.
        """
        s = self.samples[: self.n_kept]
        if len(s):
            s = s[np.isfinite(s)]
        if len(s) == 0:
            return dict(avg=0.0, p50=0.0, p99=0.0, p999=0.0)
        p50, p99, p999 = np.percentile(s, [50, 99, 99.9])
        return dict(avg=float(s.mean()), p50=float(p50), p99=float(p99),
                    p999=float(p999))


class CpuMonitor:
    """Independent host-utilization measurement, the reference's cpu_util
    service (smallbank/cpu_util.h:37-46: user vs kernel core-seconds from
    /proc/stat over the measurement window, printed as `primary
    ucores/kcores` in every client's final stats). Machine-wide AND
    process-level (this process = the host shim + dispatch loop, the TPU
    analogue of the reference's 16 server worker cores)."""

    def __init__(self):
        self._t0 = time.monotonic()
        self._m0 = self._machine()
        self._p0 = self._process()

    @staticmethod
    def _machine():
        with open("/proc/stat") as f:
            parts = f.readline().split()
        # user, nice, system, idle, iowait, irq, softirq
        user = int(parts[1]) + int(parts[2])
        kernel = int(parts[3]) + int(parts[6]) + int(parts[7])
        return user, kernel

    @staticmethod
    def _process():
        with open("/proc/self/stat") as f:
            parts = f.read().rsplit(") ", 1)[1].split()
        return int(parts[11]), int(parts[12])   # utime, stime

    def cores(self) -> dict:
        """Core-equivalents busy since construction (jiffies / HZ / wall)."""
        hz = float(os.sysconf("SC_CLK_TCK"))
        dt = max(time.monotonic() - self._t0, 1e-9)
        m1 = self._machine()
        p1 = self._process()
        return {
            "host_ucores": round((m1[0] - self._m0[0]) / hz / dt, 3),
            "host_kcores": round((m1[1] - self._m0[1]) / hz / dt, 3),
            "proc_ucores": round((p1[0] - self._p0[0]) / hz / dt, 3),
            "proc_kcores": round((p1[1] - self._p0[1]) / hz / dt, 3),
        }


def steady_blocks(block_s):
    """Trim run_window's block-time samples to steady state: the first is
    dispatch-only (async) and the last folds in the final queue-drain fetch
    (~2x a steady block)."""
    return block_s[1:-1] if len(block_s) > 2 else block_s


def cohort_latency_percentiles(block_s, cohorts_per_block: int, depth: int):
    """Latency percentiles at cohort granularity from per-block wall times.

    A txn completes `depth` pipeline steps after its cohort's dispatch.
    Cohort j of a block spends its first (cpb - j) steps in its own block
    (per-step time = that block's wall / cpb) and any remaining steps
    spill into the NEXT block's per-step time — so samples carry real
    cross-block jitter instead of one value per block, and p99.9 is
    measured, not structurally equal to p99 (the reference samples every
    txn and nth_elements the vector, store/caladan/stat.h:15-20; this is
    the batched analogue at scan-block timestamp granularity).

    Returns the percentile dict + ``n`` = sample count."""
    bs = np.asarray(steady_blocks(block_s), np.float64)
    lat = LatencyReservoir()
    if len(bs):
        step = bs / cohorts_per_block
        j = np.arange(cohorts_per_block)
        spill = np.minimum(np.maximum(j + depth - cohorts_per_block, 0),
                           depth)
        for b in range(len(bs)):
            s_next = step[b + 1] if b + 1 < len(bs) else step[b]
            lat.add(((depth - spill) * step[b] + spill * s_next) * 1e6)
    out = lat.percentiles()
    out["n"] = lat.n_seen
    out["hist"] = lat.hist.to_dict()    # the artifact "lat_hist" block
    return out


def run_latency_window(runner, state, key, window_s: float, n_stats: int,
                       depth: int, warmup_blocks: int = 2):
    """Latency-mode window: MEASURED per-cohort latency from real
    timestamps instead of the block-time model.

    Built for runners with cohorts_per_block == 1: every call dispatches
    one pipeline step and its stats are fetched SYNCHRONOUSLY, so the
    cohort dispatched at call j completes during call j+depth-1 (its
    wave-1 step plus depth-1 further steps) and its latency is the
    wall-clock difference t_end[j+depth-1] - t_start[j] — an actual
    measurement spanning real device execution, the batched analogue of
    the reference's every-txn microtime() sampling
    (store/caladan/stat.h:15-20). The per-step sync fetch costs
    throughput relative to run_window's overlapped dispatch — that is the
    latency/throughput trade a latency-mode run exists to expose.

    Returns (state, total, dt, steps, percentiles dict with ``n`` =
    cohort sample count). Totals note: a cohort's outcome stats surface
    depth-1 steps after its dispatch, so the timed fetches (+ the
    caller's drain) also capture the warmup cohorts' outcomes —
    `total` covers warmup_blocks + steps dispatched cohorts (a
    ~warmup/steps relative overcount vs the timed window, <1% at any
    real window length)."""
    import jax

    for i in range(warmup_blocks):
        state, stats = runner(state, jax.random.fold_in(key, 10**6 + i))
        np.asarray(stats)   # fetch = sync

    total = np.zeros(n_stats, np.int64)
    t_start, t_end = [], []
    t0 = time.time()
    i = 0
    while time.time() - t0 < window_s:
        t_start.append(time.time())
        state, stats = runner(state, jax.random.fold_in(key, i))
        total += np.asarray(stats, np.int64).sum(axis=0)    # sync fetch
        t_end.append(time.time())
        i += 1
    dt = time.time() - t0
    lat = LatencyReservoir()
    if i > depth:
        samples = (np.asarray(t_end[depth - 1:]) -
                   np.asarray(t_start[: i - depth + 1])) * 1e6
        lat.add(samples)
    out = lat.percentiles()
    out["n"] = lat.n_seen
    out["hist"] = lat.hist.to_dict()
    return state, total, dt, i, out


def run_window(runner, state, key, window_s: float, n_stats: int,
               warmup_blocks: int = 1):
    """Timed measurement loop shared by the device-fused pipeline benches.

    Runs `warmup_blocks` dispatches (compile + cache warm), then dispatches
    until `window_s` elapses, overlapping the host-side stats reduction of
    block i-1 with device execution of block i. Every timed iteration
    ends in a VALUE FETCH (np.asarray) of the previous block's stats: the
    host needs those values anyway, and a fetch waits for the dispatch
    that produced them exactly as jax.block_until_ready would.

    Returns (state, total [n_stats] i64, warm_total [n_stats] i64,
    elapsed_s, blocks, block_s): `total` covers only the timed window;
    `warm_total` covers warmup (callers with table-vs-accounting invariants
    need it — warmup writes land in the tables too). `block_s` is the wall
    time of each timed loop iteration (dispatch of block i + fetch of block
    i-1's stats) — in steady state ≈ one block of device time, the basis
    for cohort-granularity latency percentiles.
    """
    import jax

    warm_total = np.zeros(n_stats, np.int64)
    for i in range(warmup_blocks):
        state, stats = runner(state, jax.random.fold_in(key, i))
        warm_total += np.asarray(stats, np.int64).sum(axis=0)

    total = np.zeros(n_stats, np.int64)
    block_s = []
    t0 = time.time()
    i = warmup_blocks
    pending = None
    tprev = t0
    while time.time() - t0 < window_s:
        state, stats = runner(state, jax.random.fold_in(key, i))
        if pending is not None:
            total += np.asarray(pending, np.int64).sum(axis=0)
        pending = stats
        i += 1
        now = time.time()
        block_s.append(now - tprev)
        tprev = now
    if pending is not None:
        total += np.asarray(pending, np.int64).sum(axis=0)  # fetch = sync
        # the final fetch closes the last block's device time
        block_s[-1] = time.time() - tprev + block_s[-1]
    dt = time.time() - t0
    return state, total, warm_total, dt, i - warmup_blocks, block_s


@dataclasses.dataclass
class TxnStats:
    """Base attempted/committed accounting shared by all txn coordinators
    (client Stats dataclasses subclass this with their abort breakdowns)."""
    attempted: int = 0
    committed: int = 0

    @property
    def abort_rate(self):
        if self.attempted == 0:
            return 0.0
        return 1.0 - self.committed / self.attempted


@dataclasses.dataclass
class MetricBlock:
    """The fixed stat block (client_ebpf_shard.cc:368-377), plus the TPU
    device-duty-cycle analogue of `primary ucores/kcores`."""
    throughput: float        # attempted txn/s (pkt/s for microbenchmarks)
    goodput: float           # committed txn/s
    avg_us: float
    p50_us: float
    p99_us: float
    p999_us: float
    device_duty: float = 0.0   # fraction of wall time the device was stepping
    extra: dict = dataclasses.field(default_factory=dict)

    @property
    def abort_rate(self):
        if self.throughput <= 0:
            return 0.0
        return 1.0 - self.goodput / self.throughput

    def to_dict(self):
        d = dict(throughput=round(self.throughput, 1),
                 goodput=round(self.goodput, 1),
                 abort_rate=round(self.abort_rate, 6),
                 avg_us=round(self.avg_us, 2), p50_us=round(self.p50_us, 2),
                 p99_us=round(self.p99_us, 2), p999_us=round(self.p999_us, 2),
                 device_duty=round(self.device_duty, 4))
        d.update(self.extra)
        return d

    def format(self) -> str:
        """Human block in the reference's shape (client_ebpf_shard.cc:368-377)."""
        lines = [
            f"throughput: {self.throughput:.1f}",
            f"goodput: {self.goodput:.1f}",
            f"average: {self.avg_us:.2f} us",
            f"median: {self.p50_us:.2f} us",
            f"99th: {self.p99_us:.2f} us",
            f"99.9th: {self.p999_us:.2f} us",
            f"device duty: {self.device_duty:.4f}",
        ]
        for k, v in self.extra.items():
            lines.append(f"{k}: {v}")
        return "\n".join(lines)

    def json(self) -> str:
        return json.dumps(self.to_dict())


class Recorder:
    """Counter + latency accumulator a client drives during the measure
    window; emits the MetricBlock at the end.

    Call :meth:`reset` after warmup so jit compile time and cold-cache waves
    don't pollute the measured window (the reference's stat window likewise
    excludes the first 5 s, store/caladan/stat.h:10-13)."""

    def __init__(self, lat_cap: int = 1 << 20):
        self._lat_cap = lat_cap
        self.extra: dict = {}
        self.reset()

    def reset(self):
        self.attempted = 0
        self.committed = 0
        self.lat = LatencyReservoir(self._lat_cap)
        self.device_busy_s = 0.0

    def record(self, attempted: int, committed: int,
               lat_us: np.ndarray | None = None,
               device_s: float = 0.0):
        self.attempted += attempted
        self.committed += committed
        if lat_us is not None and len(np.atleast_1d(lat_us)):
            self.lat.add(lat_us)
        self.device_busy_s += device_s

    def block(self, elapsed_s: float) -> MetricBlock:
        p = self.lat.percentiles()
        el = max(elapsed_s, 1e-12)
        extra = dict(self.extra)
        # the exact-merge histogram rides every metric block next to the
        # reservoir percentiles (artifact schema hygiene, OBSERVABILITY.md)
        extra.setdefault("lat_hist", self.lat.hist.to_dict())
        return MetricBlock(
            throughput=self.attempted / el,
            goodput=self.committed / el,
            avg_us=p["avg"], p50_us=p["p50"], p99_us=p["p99"],
            p999_us=p["p999"],
            device_duty=self.device_busy_s / el,
            extra=extra,
        )
