"""L6 experiment driver: one command regenerates a results directory.

TPU port of the reference's exp/ harness: run_tatp_wrapper.sh:3-7 sweeps
client threads (closed-loop) and target load (open-loop) per backend,
run_tatp.sh:188-214 scrapes each client's metric block into
exp/results/*.txt. Here each point writes a JSON metric block
(stats.MetricBlock: throughput/goodput/avg/p50/p99/p99.9 + workload extras)
to <out>/<name>.json, plus a summary.json index.

Sweep axes (reference analogues):
  * cohort width w      == client uthread count (in-flight txns)
  * offered load        == target_load with net_intv pacing
                           (tatp/caladan/client_ebpf_shard.cc:1607-1611)
  * workload            == store / lock_2pl / lock_fasst / log_server /
                           smallbank / tatp

Closed-loop points drive the device flat out (run_window); open-loop
points schedule cohort arrivals at a fixed rate and measure latency as
completion minus SCHEDULED arrival, so queueing delay appears when offered
load exceeds capacity — the latency-vs-load hockey stick the reference
plots. Open-loop rates are swept relative to the measured closed-loop peak
so the curve brackets saturation on any backend.

Usage:
  python exp.py                  # full sweep -> exp_results/
  python exp.py --quick          # small shapes, short windows (smoke)
  python exp.py --only tatp      # name-substring filter
  python exp.py --out DIR --window 5
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

# ---------------------------------------------------------------- helpers


def _percentiles(samples_us):
    from dint_tpu import stats as st

    lat = st.LatencyReservoir()
    for s in samples_us:
        lat.add(s)
    p = lat.percentiles()
    p["hist"] = lat.hist.to_dict()
    return p


def _monitor_on() -> bool:
    """DINT_MONITOR=1 threads the dintmon counter plane through every
    pipeline sweep point; each point's artifact then embeds the counter
    snapshot (explicit null otherwise — OBSERVABILITY.md)."""
    return os.environ.get("DINT_MONITOR") == "1"


def _trace_on() -> bool:
    """DINT_TRACE=1 threads the dinttrace flight-recorder ring through
    every pipeline sweep point; each closed point's artifact then embeds
    the event summary (explicit null otherwise — OBSERVABILITY.md).
    DINT_TRACE_RATE tunes the sampling mask; the full JSONL stream is a
    bench.py feature (DINT_TRACE_JSONL), not a sweep one."""
    return os.environ.get("DINT_TRACE") == "1"


# plan consumption (ISSUE 17): the pinned PLAN.json replaces the env-flag
# default path for the sweep's build knobs; ambient DINT_* flags win only
# under DINT_PLAN_OVERRIDE=1 (which the per-workload meta records). One
# load per process; _PLAN_OVERRIDDEN accumulates the union of knobs the
# override actually changed so every point artifact can carry it.
_PLAN_DOC: list | None = None
_PLAN_OVERRIDDEN: set = set()


def _plan_doc():
    global _PLAN_DOC
    if _PLAN_DOC is None:
        doc = None
        if os.environ.get("DINT_BENCH_PLAN", "1") != "0":
            try:
                from dint_tpu.analysis import plan as dplan
                doc = dplan.load_plan()
            except Exception:  # noqa: BLE001 — sweep must not die on a
                doc = None     # missing/corrupt plan; points record null
        _PLAN_DOC = [doc]
    return _PLAN_DOC[0]


def _plan_knobs(workload: str) -> dict:
    """Plan-resolved build knobs for one workload ({} without a readable
    plan — the builders then env-resolve exactly as before)."""
    doc = _plan_doc()
    if doc is None:
        return {}
    from dint_tpu.analysis import plan as dplan
    knobs, meta = dplan.resolve_for(workload, plan=doc)
    _PLAN_OVERRIDDEN.update(meta["overridden"])
    return knobs


def _plan_meta():
    """The artifact's "plan" field: {source, hash, overridden} when the
    sweep resolved knobs from a pinned plan, EXPLICIT None otherwise."""
    doc = _plan_doc()
    if doc is None:
        return None
    from dint_tpu.analysis import plan as dplan
    return {"source": str(dplan.plan_path()),
            "hash": doc.get("provenance", {}).get("cost_model_hash"),
            "overridden": sorted(_PLAN_OVERRIDDEN)}


def _drain(drain, carry):
    """Drain a runner under the current flags. Runners return
    (state, stats) + ((ring,) if DINT_TRACE) + ((counters,) if
    DINT_MONITOR) — flag-aware unpacking, NOT length heuristics (a
    traced-but-unmonitored drain is also length 3). Returns
    (tail_stats, counter_snapshot_or_None, ring_or_None)."""
    out = drain(carry)
    tail, rest = out[1], list(out[2:])
    ring = rest.pop(0) if _trace_on() and rest else None
    counters = None
    if _monitor_on() and rest:
        from dint_tpu import monitor as dm

        counters = dm.snapshot(rest.pop(0))
    return tail, counters, ring


def _wrap_trace(run, init):
    """DINT_TRACE=1: wrap a runner so each block's event ring is drained
    into a per-point TxnMonitor (the ring zeroes at block entry, so the
    observe must ride every dispatch; defer=True double-buffers the
    fetch). The monitor hangs off the returned fn as ``txn_monitor`` for
    the closed-loop window to summarize."""
    if not _trace_on() or getattr(init, "trace_cfg", None) is None:
        return run
    from dint_tpu.monitor import txnevents as txe

    tmon = txe.TxnMonitor(init.trace_cfg)
    ring_ix = -2 if _monitor_on() else -1

    def traced(carry, key, _run=run, _ix=ring_ix):
        carry, stats = _run(carry, key)
        tmon.observe(carry[_ix], defer=True)
        return carry, stats

    traced.txn_monitor = tmon
    return traced


def pipeline_closed(run, carry, drain, n_stats, *, window_s, cpb,
                    depth, magic_idx, key_seed=0):
    """Closed-loop window over a fused pipelined runner.

    Latency is cohort-granularity: a txn completes `depth` pipeline steps
    after its cohort's dispatch; a steady-state block of cpb steps takes
    block_s. The magic-byte integrity check covers warmup + pre-run blocks
    too (their writes land in the same tables — same rule as bench.py).
    Returns (totals [n_stats], dt, percentiles dict, host cores dict)."""
    import jax

    from dint_tpu import stats as st

    from dint_tpu.monitor import trace as mtrace

    key = jax.random.PRNGKey(key_seed)
    s0 = np.zeros(n_stats, np.int64)
    for warm_key in (999_999, 999_998):   # fresh + donated-carry layouts
        carry, s = run(carry, jax.random.fold_in(key, warm_key))
        s0 += np.asarray(s, np.int64).sum(axis=0)  # fetch = sync
    cpu = st.CpuMonitor()   # strictly over the timed window
    # DINT_EXP_TRACE_DIR: bracket every closed window with a jax.profiler
    # device trace (one timestamped session per point lands in the dir);
    # a profiler failure never voids the measurement
    with mtrace.profiler_session(os.environ.get("DINT_EXP_TRACE_DIR")):
        carry, total, warm, dt, _blocks, block_s = st.run_window(
            run, carry, key, window_s, n_stats, warmup_blocks=0)
    cores = cpu.cores()
    tail, counters, ring = _drain(drain, carry)
    total = total + np.asarray(tail, np.int64).sum(axis=0)
    if int(s0[magic_idx] + warm[magic_idx] + total[magic_idx]) != 0:
        raise RuntimeError("magic-byte integrity violated (incl. warmup)")
    p = st.cohort_latency_percentiles(block_s, cpb, depth)
    trace_sum = None
    tmon = getattr(run, "txn_monitor", None)
    if tmon is not None:
        tmon.flush()
        if ring is not None:    # the drained boundary cohorts' events
            tmon.observe(ring)
        trace_sum = tmon.summary()
    return total, dt, p, cores, counters, trace_sum


def pipeline_open(make_runner, n_stats, *, rate, window_s, w, cpb, depth,
                  key_seed=0):
    """Open-loop window: blocks of cpb cohorts are DISPATCHED on a fixed
    schedule (block i at t0 + i * cpb*w/rate) and each is fetched
    synchronously; per-cohort latency = completion - scheduled arrival
    (+ depth pipeline steps are inside the block wall time). Saturation
    shows up as schedule slip -> latency growth.

    make_runner() -> (run, carry, drain): fresh state per rate point.
    Returns (totals, dt, percentiles, offered_rate, blocks_dispatched,
    split) where ``split`` separates QUEUEING delay (schedule slip at
    dispatch: how long past its scheduled arrival a block waited for the
    device) from SERVICE time (dispatch -> completion) — the honest
    decomposition of the latency-vs-load hockey stick: under saturation
    the queue term grows without bound while service stays ~flat. Each
    carries the percentile dict + the exact-merge histogram."""
    import jax

    from dint_tpu import stats as st

    run, carry, drain = make_runner()
    key = jax.random.PRNGKey(key_seed)
    # warm TWICE: the first call compiles for fresh-array layouts, the
    # second for the steady-state donated-carry layout (a second compile)
    for warm in (999_999, 999_998):
        carry, s0 = run(carry, jax.random.fold_in(key, warm))
        np.asarray(s0)  # sync

    period = cpb * w / rate            # seconds per block
    total = np.zeros(n_stats, np.int64)
    lat_blocks = []
    queue_lat = st.LatencyReservoir()      # open-loop arrival timestamps:
    service_lat = st.LatencyReservoir()    # queueing vs service, separated
    t0 = time.time()
    i = 0
    while time.time() - t0 < window_s:
        sched = t0 + i * period
        now = time.time()
        if sched > now:
            time.sleep(sched - now)
        t_disp = time.time()
        carry, s = run(carry, jax.random.fold_in(key, i))
        total += np.asarray(s, np.int64).sum(axis=0)   # fetch = completion
        done = time.time()
        # per-cohort arrivals spread across the block's schedule slot
        arr = sched + np.arange(cpb) * (w / rate)
        lat_blocks.append(np.maximum(done - arr, 0.0) * 1e6)
        queue_lat.add(max(t_disp - sched, 0.0) * 1e6)
        service_lat.add((done - t_disp) * 1e6)
        i += 1
    dt = time.time() - t0
    tail, _, _ = _drain(drain, carry)
    total += np.asarray(tail, np.int64).sum(axis=0)
    p = _percentiles(lat_blocks)
    offered = i * cpb * w / dt

    def _side(lat):
        d = {f"{k}_us": round(v, 2) for k, v in lat.percentiles().items()}
        d["hist"] = lat.hist.to_dict()
        return d

    split = {"queue": _side(queue_lat), "service": _side(service_lat)}
    return total, dt, p, offered, i, split


# ---------------------------------------------------------------- workloads


def _tatp_runner(n_sub, w, cpb, seed=0):
    import jax

    from dint_tpu.engines import tatp_dense as td

    knobs = _plan_knobs("tatp_uniform")
    kb = {k: knobs[k] for k in ("use_hotset",) if k in knobs}
    # on-device populate: the full sweep runs at the reference's 7M
    # subscribers (~6.2 GB) — generated in HBM, not via the host
    db = td.populate_device(jax.random.PRNGKey(seed), n_sub, val_words=10)
    run, init, drain = td.build_pipelined_runner(
        n_sub, w=w, val_words=10, cohorts_per_block=cpb,
        monitor=_monitor_on(), trace=_trace_on(), **kb)
    run = _wrap_trace(run, init)
    return run, init(db), drain


def _tatp_extras(total):
    from dint_tpu.engines import tatp_dense as td

    att = int(total[td.STAT_ATTEMPTED])
    com = int(total[td.STAT_COMMITTED])
    if int(total[td.STAT_MAGIC_BAD]) != 0:
        raise RuntimeError("tatp magic-byte integrity violated")
    return att, com, {
        "ab_lock": int(total[td.STAT_AB_LOCK]),
        "ab_missing": int(total[td.STAT_AB_MISSING]),
        "ab_validate": int(total[td.STAT_AB_VALIDATE]),
    }


def _sb_runner(n_acc, w, cpb, hot_frac=None, hot_prob=None):
    import jax

    from dint_tpu.engines import smallbank_dense as sd

    knobs = _plan_knobs("smallbank_skewed")
    kb = {k: knobs[k] for k in ("use_hotset",) if k in knobs}
    db = sd.create(n_acc)
    run, init, drain = sd.build_pipelined_runner(
        n_acc, w=w, cohorts_per_block=cpb,
        hot_frac=hot_frac, hot_prob=hot_prob,
        monitor=_monitor_on(), trace=_trace_on(), **kb)
    run = _wrap_trace(run, init)
    return run, init(db), drain


def _sb_extras(total):
    from dint_tpu.engines import smallbank_dense as sd

    att = int(total[sd.STAT_ATTEMPTED])
    com = int(total[sd.STAT_COMMITTED])
    if int(total[sd.STAT_MAGIC_BAD]) != 0:
        raise RuntimeError("smallbank magic-byte integrity violated")
    return att, com, {
        "ab_lock": int(total[sd.STAT_AB_LOCK]),
        "ab_logic": int(total[sd.STAT_AB_LOGIC]),
    }


def _mh_sb_runner(n_acc, w, cpb, hierarchical):
    from dint_tpu.parallel import multihost as mhost
    from dint_tpu.parallel import multihost_sb as mh

    n_hosts, n_ici = mhost.mesh_shape_from_env()
    mesh = mh.make_mesh_2d(n_hosts, n_ici)
    run, init, drain = mh.build_multihost_sb_runner(
        mesh, n_acc, w=w, cohorts_per_block=cpb,
        hierarchical=hierarchical, monitor=_monitor_on(),
        trace=_trace_on())
    run = _wrap_trace(run, init)
    return run, init(mh.create_multihost_sb(mesh, n_acc)), drain


def _mh_sb_extras(total):
    from dint_tpu.parallel import dense_sharded_sb as dsb

    att, com, extra = _sb_extras(total)
    extra["route_overflow"] = int(total[dsb.STAT_OVERFLOW])
    return att, com, extra


def run_point(results, name, fn):
    """Run one sweep point. No retry and no error artifact: a point that
    raises fails the run (every finished point is already on disk —
    _ResultSink — and --skip-done resumes after them)."""
    if getattr(results, "already_done", lambda n: False)(name):
        print(f"point {name}: skipped (already done)", flush=True)
        return
    out = fn()
    if isinstance(out, dict):
        # artifact provenance: which pinned plan resolved the build
        # knobs (object or EXPLICIT null — same consumer contract as
        # counters/breakdown)
        out.setdefault("plan", _plan_meta())
    results[name] = out


def _metric_json(att, com, dt, p, extra, breakdown=None):
    from dint_tpu.monitor import attrib
    from dint_tpu.stats import MetricBlock

    d = MetricBlock(
        throughput=att / dt, goodput=com / dt,
        avg_us=p["avg"], p50_us=p["p50"], p99_us=p["p99"],
        p999_us=p["p999"], extra=extra).to_dict()
    # artifact schema hygiene (OBSERVABILITY.md): every sweep point
    # carries the schema version, the log-bucket histogram next to the
    # percentile block, and a breakdown that is an object exactly when
    # dintscope attribution ran (explicit null otherwise)
    d["schema"] = attrib.ARTIFACT_SCHEMA
    d["lat_hist"] = p.get("hist")
    d["breakdown"] = breakdown
    return d


def sweep_pipeline(name, runner_fn, extras_fn, n_stats, *, widths, cpb,
                   depth, magic_idx, window_s, open_rates, results,
                   lat_widths=(), point_extra=None, geom=None):
    """Closed-loop width sweep, then open-loop rate sweep at the widest
    width relative to its measured peak, then latency-mode points
    (cohorts_per_block=1, per-step sync fetch) whose percentiles come
    from MEASURED timestamps rather than the block-time model.
    ``point_extra`` (dict) is recorded verbatim in every point's extras
    (skew/hot-tier provenance). ``geom`` (dict: k/l/vw formula vars) feeds
    the dintscope bytes formulas when DINT_EXP_TRACE_DIR attribution is
    on."""
    peak = None
    peak_w = None

    def _breakdown(w):
        """Attribute the point's freshest profiler trace when
        DINT_EXP_TRACE_DIR is set (pipeline_closed brackets the window
        with a profiler session into that dir); explicit None otherwise —
        a failed attribution must not void the sweep point."""
        tdir = os.environ.get("DINT_EXP_TRACE_DIR")
        if not tdir:
            return None
        try:
            from dint_tpu.monitor import attrib

            return attrib.report(tdir, geometry=dict(geom or {}, w=w))
        except Exception as e:      # noqa: BLE001
            print(f"dintscope attribution failed: {e!r}"[:200],
                  flush=True)
            return None

    def closed_point(w):
        def fn():
            run, carry, drain = runner_fn(w, cpb)
            total, dt, p, cores, counters, trace_sum = pipeline_closed(
                run, carry, drain, n_stats, window_s=window_s, cpb=cpb,
                depth=depth, magic_idx=magic_idx)
            att, com, extra = extras_fn(total)
            extra.update(cores)
            extra["mode"] = "closed"
            extra["width"] = w
            extra.update(point_extra or {})
            # end-of-point dintmon snapshot; explicit null when off
            extra["counters"] = counters
            # dinttrace flight-recorder summary; same null contract
            extra["dinttrace"] = trace_sum
            return _metric_json(att, com, dt, p, extra,
                                breakdown=_breakdown(w))

        return fn

    for w in widths:
        nm = f"{name}_closed_w{w}"
        run_point(results, nm, closed_point(w))
        # peak derives from the RESULT (measured now or loaded by
        # --skip-done), so a resumed sweep still anchors its open-loop
        # rates — the in-closure nonlocal update lost the anchor when
        # every closed point was skipped on restart
        blk = results.get(nm) or {}
        if "throughput" in blk and (peak is None
                                    or blk["throughput"] > peak):
            peak, peak_w = blk["throughput"], blk.get("width", w)
    if peak is None:      # no closed point survived: no rate anchor
        return

    def open_point(frac):
        def fn():
            rate = max(peak * frac, 1.0)
            total, dt, p, offered, _, split = pipeline_open(
                lambda: runner_fn(peak_w, cpb), n_stats, rate=rate,
                window_s=window_s, w=peak_w, cpb=cpb, depth=depth)
            att, com, extra = extras_fn(total)
            extra.update(mode="open", width=peak_w,
                         target_rate=round(rate, 1),
                         offered_rate=round(offered, 1),
                         load_frac=frac,
                         # queueing delay vs service time, separated from
                         # the scheduled-arrival timestamps (the SLO
                         # sensors the serving plane closes its loop on)
                         queue=split["queue"], service=split["service"])
            return _metric_json(att, com, dt, p, extra)

        return fn

    for frac in open_rates:
        run_point(results, f"{name}_open_{int(frac * 100)}pct",
                  open_point(frac))

    def latency_point(w):
        def fn():
            import jax

            from dint_tpu import stats as st

            run, carry, drain = runner_fn(w, 1)   # one cohort per dispatch
            carry, total, dt, steps, p = st.run_latency_window(
                run, carry, jax.random.PRNGKey(7), window_s, n_stats,
                depth=depth)
            tail, _, _ = _drain(drain, carry)
            total = total + np.asarray(tail, np.int64).sum(axis=0)
            att, com, extra = extras_fn(total)
            extra.update(mode="latency_measured", width=w, cpb=1,
                         steps=steps, lat_samples=int(p["n"]))
            return _metric_json(att, com, dt, p, extra)

        return fn

    for w in lat_widths:
        run_point(results, f"{name}_latency_w{w}", latency_point(w))


def sweep_serve(name, engine, size, *, window_s, open_rates, results,
                quick, cpb=4, depth=2, slo_us=5_000.0):
    """dintserve latency-vs-offered-load curve (round 17): drive the
    always-on serving plane with open-loop Poisson arrival schedules at a
    ladder of offered rates anchored to a measured saturation probe.

    Point 0 (``_sat``) dumps a block of same-instant arrivals on an empty
    queue: the width controller parks at its knee width, admission
    control sheds everything past the SLO-feasible backlog, and the
    achieved rate IS the serving capacity — the anchor the rate ladder
    multiplies. Every point's artifact carries offered vs achieved rate,
    the exact queue/service percentile split (the serving plane's two
    SLO sensors, measured separately — a closed-loop driver cannot see
    the queue side at all), the shed count, the width trajectory the
    controller took, and the SLO verdict, all through the standard
    artifact schema (percentile block = QUEUEING delay: that is the
    number the SLO is written against)."""
    from dint_tpu.serve import ControllerCfg, ServeEngine
    from dint_tpu.serve import arrivals as arr

    widths = (64, 256) if quick else (256, 1024, 4096, 8192)
    max_arrivals = 50_000 if quick else 2_000_000

    def make():
        return ServeEngine(engine, size,
                           cfg=ControllerCfg(widths=widths, slo_us=slo_us),
                           cohorts_per_block=cpb, depth=depth,
                           monitor=True, seed=0)

    def point(schedule_fn, extra_static):
        def fn():
            eng = make()
            eng.warmup()          # compile outside the serving window
            eng.run(schedule_fn())
            eng.close()
            rep = eng.snapshot()
            p = {**eng.queue_hist.percentiles(),
                 "hist": eng.queue_hist.to_dict()}
            extra = dict(extra_static)
            extra.update(
                mode="serve", engine=engine, widths=list(widths),
                offered=rep["offered"], admitted=rep["admitted"],
                shed=rep["shed"], blocks=rep["blocks"],
                offered_rate=round(rep["offered_rate"], 1),
                achieved_rate=round(rep["achieved_rate"], 1),
                slo_us=slo_us, slo_met=rep["slo_met"],
                service={**eng.service_hist.percentiles(),
                         "hist": eng.service_hist.to_dict()},
                controller=rep["controller"],
                serve_counters={
                    k: rep["counters"].get(k, 0)
                    for k in ("serve_occupancy_lanes", "serve_padded_lanes",
                              "serve_shed_lanes")})
            return _metric_json(rep["attempted"], rep["committed"],
                                rep["elapsed_s"], p, extra)

        return fn

    # saturation probe: every arrival at t=0; shed-don't-stall measured
    n_probe = min(widths[-1] * cpb * 32, max_arrivals)
    nm = f"{name}_sat"
    run_point(results, nm,
              point(lambda: np.zeros(n_probe), {"load": "sat"}))
    blk = results.get(nm) or {}
    peak = blk.get("achieved_rate")   # MetricBlock flattens extra
    if not peak:
        return

    for frac in open_rates:
        rate = max(peak * frac, 1.0)
        win = min(window_s, max_arrivals / rate)
        run_point(
            results, f"{name}_r{int(frac * 100)}pct",
            point(lambda r=rate, w=win: arr.poisson_schedule(r, w, seed=11),
                  {"load": frac, "target_rate": round(rate, 1)}))


def sweep_serve_mesh(name, n_acc, *, window_s, open_rates, results,
                     quick, cpb=4, depth=2, slo_us=5_000.0):
    """dintmesh latency-vs-offered-load curve (round 18): the whole 2-D
    (dcn x ici) mesh served as ONE open-loop plane (serve/mesh.py) —
    per-host admission feeding one global SLO controller, width
    switches coordinated mesh-wide at drain boundaries. Same ladder
    protocol as sweep_serve (saturation probe anchors the rate ladder);
    every artifact additionally carries the mesh shape, the per-host
    admitted/shed split, and the route_prefetch counter so an overlap
    A/B (DINT_SERVE_OVERLAP=1 flips the double-buffered route — see
    tools/hw_mesh_serve.sh and the PERF.md round-18 decision rule)
    diffs as two branches of the same artifact schema."""
    import jax

    from dint_tpu.parallel import multihost as mhost
    from dint_tpu.serve import ControllerCfg, MeshServeEngine
    from dint_tpu.serve import arrivals as arr

    n_hosts, n_ici = mhost.mesh_shape_from_env()
    if len(jax.devices()) < n_hosts * n_ici or n_hosts < 3:
        print(f"{name}: skipped ({n_hosts}x{n_ici} mesh needs "
              f"{n_hosts * n_ici} devices and >= 3 hosts; have "
              f"{len(jax.devices())} devices)", flush=True)
        return
    overlap = os.environ.get("DINT_SERVE_OVERLAP", "0") == "1"
    widths = (64, 256) if quick else (256, 1024, 4096)
    max_arrivals = 50_000 if quick else 2_000_000

    def make():
        return MeshServeEngine(
            n_acc, mesh_shape=(n_hosts, n_ici),
            cfg=ControllerCfg(widths=widths, slo_us=slo_us),
            cohorts_per_block=cpb, depth=depth, monitor=True, seed=0,
            overlap=overlap)

    def point(schedule_fn, extra_static):
        def fn():
            eng = make()
            eng.warmup()          # compile outside the serving window
            eng.run(schedule_fn())
            eng.close()
            rep = eng.snapshot()
            p = {**eng.queue_hist.percentiles(),
                 "hist": eng.queue_hist.to_dict()}
            extra = dict(extra_static)
            extra.update(
                mode="serve_mesh", engine="multihost_sb",
                widths=list(widths), mesh=rep["mesh"],
                per_host=rep["per_host"],
                offered=rep["offered"], admitted=rep["admitted"],
                shed=rep["shed"], blocks=rep["blocks"],
                offered_rate=round(rep["offered_rate"], 1),
                achieved_rate=round(rep["achieved_rate"], 1),
                slo_us=slo_us, slo_met=rep["slo_met"],
                service={**eng.service_hist.percentiles(),
                         "hist": eng.service_hist.to_dict()},
                controller=rep["controller"],
                serve_counters={
                    k: rep["counters"].get(k, 0)
                    for k in ("serve_occupancy_lanes", "serve_padded_lanes",
                              "serve_shed_lanes",
                              "route_prefetch_lanes")})
            return _metric_json(rep["attempted"], rep["committed"],
                                rep["elapsed_s"], p, extra)

        return fn

    # saturation probe across the whole mesh: every arrival at t=0
    n_probe = min(widths[-1] * cpb * n_hosts * n_ici * 8, max_arrivals)
    nm = f"{name}_sat"
    run_point(results, nm,
              point(lambda: np.zeros(n_probe), {"load": "sat"}))
    blk = results.get(nm) or {}
    peak = blk.get("achieved_rate")   # MetricBlock flattens extra
    if not peak:
        return

    for frac in open_rates:
        rate = max(peak * frac, 1.0)
        win = min(window_s, max_arrivals / rate)
        run_point(
            results, f"{name}_r{int(frac * 100)}pct",
            point(lambda r=rate, w=win: arr.poisson_schedule(r, w, seed=11),
                  {"load": frac, "target_rate": round(rate, 1)}))


def _timed_client(client, go, window_s):
    go()                             # compile
    client.rec.reset()
    t0 = time.time()
    while time.time() - t0 < window_s:
        go()
    return client.rec.block(time.time() - t0).to_dict()


def sweep_micro(window_s, quick, results, want=lambda name: True):
    """store / lock_2pl / lock_fasst (+attribution) / log_server
    microbenchmarks via their reference-parity clients. `want` gates each
    point BEFORE it runs (the --only filter must skip work, not discard
    results)."""
    from dint_tpu.clients import micro, workloads as wl

    rng = np.random.default_rng(0)
    n_keys = 10_000 if quick else 1_000_000
    widths = [1024] if quick else [1024, 4096, 16384]

    def timed(name, client, go):
        if not want(name):
            return

        def fn():
            go()                     # compile
            client.rec.reset()
            t0 = time.time()
            while time.time() - t0 < window_s:
                go()
            return client.rec.block(time.time() - t0).to_dict()

        run_point(results, name, fn)

    for read_frac, tag in ((0.5, "contention"), (1.0, "parallel")):
        for w in widths:
            name = f"store_{tag}_w{w}"
            if not want(name):
                continue
            def store_fn(w=w, read_frac=read_frac):
                c = micro.StoreClient.populated(n_keys, width=w,
                                                read_frac=read_frac)
                return _timed_client(c, lambda: c.run_wave(rng),
                                     window_s) | {"width": w,
                                                  "scan": None}

            run_point(results, name, store_fn)

    # DINT's skewed store benchmark: Zipfian keys whose hot head is the
    # dintcache prefix (DINT_USE_HOTSET=1 serves it from the mirror —
    # record the A/B state in every artifact)
    for w in widths:
        name = f"store_zipf_w{w}"
        if not want(name):
            continue

        def zipf_fn(w=w):
            c = micro.StoreClient.populated(n_keys, width=w,
                                            read_frac=0.5,
                                            key_dist="zipfian")
            return _timed_client(c, lambda: c.run_wave(rng), window_s) | {
                "width": w, "key_dist": "zipfian",
                "zipf_theta": wl.ZIPF_THETA,
                "use_hotset": c.use_hotset,
                "scan": None}

        run_point(results, name, zipf_fn)

    # round-20 dintscan: the scan-fraction ladder over the ordered run —
    # YCSB-B shape (0%) through YCSB-E (95% scans) at one fixed width,
    # Zipfian start keys, uniform lengths. Every artifact carries the
    # "scan" object (or EXPLICIT null on the point-op rows above — same
    # consumer contract as plan/counters): resolved routes + the mix, so
    # the hw A/B behind PERF.md's round-20 decision rule is replayable.
    scan_w = 1024 if quick else 4096
    scan_max = 16 if quick else wl.YCSB_E_MAX_SCAN
    for frac in (0.0, 0.05, 0.5, 0.95):
        name = f"store_scan_f{int(frac * 100)}"
        if not want(name):
            continue

        def scan_fn(frac=frac, w=scan_w, scan_max=scan_max):
            c = micro.StoreClient.populated(
                n_keys, width=w, read_frac=0.5, key_dist="zipfian",
                use_scan=True, scan_frac=frac, scan_max=scan_max,
                rebuild_every=1)
            return _timed_client(c, lambda: c.run_wave(rng),
                                 window_s) | {
                "width": w, "key_dist": "zipfian",
                "zipf_theta": wl.ZIPF_THETA,
                "scan": {"use_scan": c.use_scan, "scan_frac": frac,
                         "scan_max": scan_max,
                         "max_scan_len": c.max_scan_len,
                         "delta_cap": c.delta_cap,
                         "rebuild_every": c.rebuild_every}}

        run_point(results, name, scan_fn)

    if any(want(n) for n in ("lock_2pl", "lock_fasst", "lock_fasst_attr")):
        trace = wl.lock_trace(rng, n_txns=200 if quick else 20_000,
                              key_range=4800)
        for cls, name, kw in ((micro.Lock2PLClient, "lock_2pl", {}),
                              (micro.FasstClient, "lock_fasst", {}),
                              (micro.FasstClient, "lock_fasst_attr",
                               {"attribute": True})):
            if not want(name):
                continue
            c = cls(trace, cohort=64 if quick else 512, **kw)
            timed(name, c, c.run_round)

    if want("log_server"):
        c = micro.LogClient(width=1024 if quick else 8192)
        timed("log_server", c, lambda: c.run_wave(rng))

    if want("store_wire"):
        run_point(results, "store_wire",
                  lambda: _store_wire_bench(window_s, quick))

    if want("tatp_wire"):
        run_point(results, "tatp_wire",
                  lambda: _tatp_wire_bench(window_s, quick))

    if want("tatp_wire_txn"):
        run_point(results, "tatp_wire_txn",
                  lambda: _tatp_wire_txn_bench(window_s, quick))

    # colocate analogue (exp/run_tatp_colocate.sh:27: servers share 8
    # cores): pin THIS process — pump RX thread, batch parse, reply
    # serialization, dispatch loop — to N cores and re-measure the wire
    # path; host_ucores scaling vs pkt/s is the reported curve
    for n in (1, 2, 4):
        name = f"tatp_colocate_c{n}"
        if want(name):
            run_point(results, name,
                      lambda n=n: _colocate_bench(n, window_s, quick))

    for tag in ("wb_bloom", "wb_nobloom", "wt"):
        name = f"store_cached_{tag}"
        if want(name):
            run_point(results, name,
                      lambda tag=tag: _store_cached_bench(tag, window_s,
                                                          quick))


def _store_cached_bench(tag, window_s, quick):
    """Two-tier cached store (device cache + host KVS): the reference's
    store-server ablation matrix — write-back + bloom vs write-back without
    bloom vs write-through (store/ebpf/store_kern.c vs store_wb_kern.c vs
    store_wt_kern.c). Keyspace is ~2x the cache capacity so the miss/refill
    path is live; extras report the hit/miss/bloom split."""
    from dint_tpu.clients.micro import STORE_MAGIC
    from dint_tpu.engines import store_cache
    from dint_tpu.engines.types import Op
    from dint_tpu.shim.host_kvs import CachedStore
    from dint_tpu.stats import Recorder

    policy = {"wb_bloom": store_cache.WB_BLOOM,
              "wb_nobloom": store_cache.WB_NOBLOOM,
              "wt": store_cache.WT}[tag]
    cache_buckets = 1 << (10 if quick else 16)
    n_keys = cache_buckets * 8           # cache holds ~half the keyspace
    width = 1_024 if quick else 4_096

    srv = CachedStore(cache_buckets, val_words=10, policy=policy,
                      width=width)
    keys_all = np.arange(1, n_keys + 1, dtype=np.uint64)
    vals = np.zeros((n_keys, 10), np.uint32)
    vals[:, 0] = keys_all.astype(np.uint32)
    vals[:, 1] = STORE_MAGIC
    srv.populate(keys_all, vals)

    rng = np.random.default_rng(0)
    wv = np.zeros((width, 10), np.uint32)
    wv[:, 1] = STORE_MAGIC

    def wave():
        k = rng.integers(1, int(n_keys * 1.1), width).astype(np.uint64)
        is_read = rng.random(width) < 0.5
        ops = np.where(is_read, Op.GET, Op.SET).astype(np.int32)
        t0 = time.monotonic()
        srv.serve(ops, k, wv)
        rec.record(width, width, np.full(width,
                                         (time.monotonic() - t0) * 1e6))

    rec = Recorder()
    wave()     # compiles cache_step; queues refills for its misses
    wave()     # compiles the refill path (pending is non-empty now)
    rec.reset()
    srv.stats = type(srv.stats)()
    t0 = time.time()
    while time.time() - t0 < window_s:
        wave()
    block = rec.block(time.time() - t0)
    st = srv.stats
    block.extra.update(policy=tag, hits=st.hits, misses=st.misses,
                       bloom_negatives=st.bloom_negatives,
                       writebacks=st.writebacks,
                       hit_rate=round(st.hits / max(st.hits + st.misses, 1),
                                      4))
    return block.to_dict()


def _store_wire_bench(window_s, quick):
    """store served OVER THE WIRE: reference-wire-format UDP datagrams
    through the native C++ pump (recvmmsg batch -> jitted store.step ->
    sendmmsg scatter, double-buffered), measured in pkt/s from concurrent
    loopback clients — the TPU analogue of the reference's store server
    benchmark (store/udp/server.cc:50-98; server pps counter,
    store/ebpf/store_user.c:58-65)."""
    import threading

    from dint_tpu.clients.micro import make_store_table
    from dint_tpu.engines import store
    from dint_tpu.shim import STORE, EnginePump, ShimClient
    from dint_tpu.stats import LatencyReservoir, MetricBlock

    n_keys = 4_096 if quick else 200_000
    width = 1_024 if quick else 4_096
    n_clients = 2
    wave = width // n_clients

    table = make_store_table(n_keys)

    with EnginePump(STORE, store.step, table, width=width,
                    flush_us=500).start() as pump:
        with ShimClient("127.0.0.1", pump.port) as c:     # warm past compile
            for attempt in range(8):
                if c.exchange(np.zeros(1, np.uint8),
                              np.array([1], np.uint64),
                              timeout_ms=20_000)["n"] == 1:
                    break
            else:
                raise RuntimeError(
                    "store_wire pump answered no warmup exchange in 8 "
                    "attempts — refusing to publish a compile-polluted "
                    "measurement")

        stop_at = time.time() + window_s
        sent = np.zeros(n_clients, np.int64)
        answered = np.zeros(n_clients, np.int64)
        lats = [LatencyReservoir(seed=i) for i in range(n_clients)]

        def worker(i):
            rng = np.random.default_rng(i)
            with ShimClient("127.0.0.1", pump.port) as c:
                while time.time() < stop_at:
                    k = rng.integers(1, n_keys + 1, size=wave).astype(np.uint64)
                    is_read = rng.random(wave) < 0.5     # contention mix
                    t0 = time.monotonic()
                    r = c.exchange(np.where(is_read, 0, 1).astype(np.uint8),
                                   k, timeout_ms=10_000)
                    dt = time.monotonic() - t0
                    sent[i] += wave
                    answered[i] += r["n"]
                    lats[i].add(np.full(r["n"], dt * 1e6))

        t0 = time.time()
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.time() - t0
        pump_lat = pump.latency_snapshot()

    # cross-client merge: reservoirs re-add kept samples (approximate past
    # cap); the histograms merge EXACTLY (stats.LatencyHistogram)
    agg = LatencyReservoir()
    for lr in lats:
        agg.add(lr.samples[:lr.n_kept])
        if lr is not lats[0]:
            lats[0].hist.merge(lr.hist)
    p = agg.percentiles()
    return MetricBlock(
        throughput=float(sent.sum()) / dt,
        goodput=float(answered.sum()) / dt,
        avg_us=p["avg"], p50_us=p["p50"], p99_us=p["p99"],
        p999_us=p["p999"],
        extra={"unit": "pkt/s", "clients": n_clients, "wave": wave,
               "transport": "udp_loopback_shim",
               "lat_hist": lats[0].hist.to_dict(),
               "pump": pump_lat}).to_dict()


def _tatp_wire_bench(window_s, quick):
    """TATP served OVER THE WIRE: the flagship workload's full
    request->batch->certify->reply path through the C++ pump — the
    reference's inherently-networked serving mode (tatp/udp/
    server_shard.cc, wire codes tatp/ebpf/utils.h:38-73). Loopback
    clients drive the reference's read-dominant shape (80% kRead across
    the 5 tables) plus a live kAcquireLock/kAbort slice (each wave aborts
    the previous wave's grants, so lock occupancy is steady-state);
    reports pkt/s like the reference's server pps counter."""
    import threading

    from dint_tpu.clients import tatp_client as tc
    from dint_tpu.engines import tatp
    from dint_tpu.shim import TATP, EnginePump, ShimClient
    from dint_tpu.stats import LatencyReservoir, MetricBlock

    n_sub = 2_000 if quick else 100_000
    width = 512 if quick else 4_096
    n_clients = 2
    wave = width // n_clients
    n_lock = wave // 10

    # quick mode scales the recovery-log ring down with everything else:
    # the full 1<<20 window is a ~1 GB zero-fill before the first packet
    shard = tc.populate_shards(np.random.default_rng(0), n_sub, val_words=10,
                               log_capacity=1 << 14 if quick else 1 << 20,
                               )[0][0]

    with EnginePump(TATP, tatp.step, shard, width=width,
                    flush_us=500).start() as pump:
        with ShimClient("127.0.0.1", pump.port) as c:   # warm past compile
            for attempt in range(8):
                if c.exchange(np.zeros(1, np.uint8),
                              np.array([1], np.uint64),
                              timeout_ms=20_000)["n"] == 1:
                    break
            else:
                raise RuntimeError(
                    "tatp_wire pump answered no warmup exchange in 8 "
                    "attempts — refusing to publish a compile-polluted "
                    "measurement")

        stop_at = time.time() + window_s
        sent = np.zeros(n_clients, np.int64)
        answered = np.zeros(n_clients, np.int64)
        grants = np.zeros(n_clients, np.int64)
        lats = [LatencyReservoir(seed=i) for i in range(n_clients)]

        def worker(i):
            rng = np.random.default_rng(i)
            # lock keys partition by client so an abort always targets a
            # row this client locked (disjoint subscriber halves)
            lo = 1 + i * (n_sub // n_clients)
            hi = lo + n_sub // n_clients
            prev_locks = np.zeros(0, np.uint64)
            with ShimClient("127.0.0.1", pump.port) as c:
                while time.time() < stop_at:
                    n_ab = len(prev_locks)
                    n_rd = wave - n_lock - n_ab
                    rd_tbl = rng.integers(0, 5, n_rd).astype(np.uint8)
                    rd_key = rng.integers(1, n_sub + 1, n_rd)
                    rd_key = np.where(
                        rd_tbl >= tatp.ACCESS_INFO, rd_key * 4
                        + rng.integers(0, 4, n_rd), rd_key)
                    rd_key = np.where(
                        rd_tbl == tatp.CALL_FORWARDING,
                        np.asarray(tatp.cf_key(
                            rng.integers(1, n_sub + 1, n_rd),
                            rng.integers(1, 5, n_rd),
                            rng.integers(0, 3, n_rd) * 8)), rd_key)
                    lk_key = rng.choice(hi - lo, n_lock,
                                        replace=False) + lo
                    types = np.concatenate([
                        np.zeros(n_rd, np.uint8),
                        np.ones(n_lock, np.uint8),
                        np.full(n_ab, 2, np.uint8)])
                    tbls = np.concatenate([
                        rd_tbl, np.zeros(n_lock + n_ab, np.uint8)])
                    keys = np.concatenate([
                        rd_key.astype(np.uint64),
                        lk_key.astype(np.uint64), prev_locks])
                    t0 = time.monotonic()
                    r = c.exchange(types, keys, tables=tbls,
                                   timeout_ms=10_000)
                    dt = time.monotonic() - t0
                    sent[i] += len(types)
                    answered[i] += r["n"]
                    lats[i].add(np.full(r["n"], dt * 1e6))
                    granted = r["key"][r["type"] == 7]   # kGrantLock
                    grants[i] += len(granted)
                    prev_locks = granted.astype(np.uint64)
                # release what's still held so the run ends clean
                if len(prev_locks):
                    c.exchange(np.full(len(prev_locks), 2, np.uint8),
                               prev_locks, timeout_ms=10_000)

        t0 = time.time()
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.time() - t0
        pump_lat = pump.latency_snapshot()

    agg = LatencyReservoir()
    for lr in lats:
        agg.add(lr.samples[:lr.n_kept])
        if lr is not lats[0]:
            lats[0].hist.merge(lr.hist)
    p = agg.percentiles()
    return MetricBlock(
        throughput=float(sent.sum()) / dt,
        goodput=float(answered.sum()) / dt,
        avg_us=p["avg"], p50_us=p["p50"], p99_us=p["p99"],
        p999_us=p["p999"],
        extra={"unit": "pkt/s", "clients": n_clients, "wave": wave,
               "lock_grants": int(grants.sum()),
               "n_subscribers": n_sub,
               "transport": "udp_loopback_shim",
               "lat_hist": lats[0].hist.to_dict(),
               "pump": pump_lat}).to_dict()


def _tatp_wire_txn_bench(window_s, quick):
    """FULL TATP transactions over the wire: 3 UDP shard servers + the
    wave coordinator fanning per-shard datagram batches — the reference's
    actual serving topology (3 servers + Caladan client,
    client_ebpf_shard.cc:636-677), txn/s with the abort classes. This is
    the protocol-fidelity point; the device-fused pipeline remains the
    throughput path (bench.py)."""
    from dint_tpu.clients import tatp_wire as tw

    n_sub = 2_000 if quick else 100_000
    # w=2048 ≈ 2.7k lanes/shard in wave 1: ~11 chunks pipelined across 8
    # sockets per shard, exercising the >256-in-flight path (the
    # reference's uthread resend-loop concurrency,
    # client_ebpf_shard.cc:643-677) instead of stair-stepping on _CHUNK
    w = 128 if quick else 2048

    from dint_tpu.stats import LatencyReservoir, MetricBlock

    lat = LatencyReservoir()
    with tw.serve_shards(n_sub, width=4 * w, flush_us=500) as ports:
        with tw.WireCoordinator(ports, n_sub, width=4 * w,
                                n_socks=8) as coord:
            rng = np.random.default_rng(0)
            coord.run_cohort(rng, w)            # compile all wave shapes
            coord.stats = type(coord.stats)()
            t0 = time.time()
            while time.time() - t0 < window_s:
                c0 = time.monotonic()
                coord.run_cohort(rng, w)
                # closed-loop: a txn's latency is its cohort's full
                # multi-wave wall span (all RTTs + certify steps)
                lat.add(np.full(w, (time.monotonic() - c0) * 1e6))
            dt = time.time() - t0
            st = coord.stats

    p = lat.percentiles()
    return MetricBlock(
        throughput=st.attempted / dt, goodput=st.committed / dt,
        avg_us=p["avg"], p50_us=p["p50"], p99_us=p["p99"],
        p999_us=p["p999"],
        extra={"unit": "txn/s", "width": w, "n_subscribers": n_sub,
               "ab_lock": st.aborted_lock, "ab_missing": st.aborted_missing,
               "ab_validate": st.aborted_validate,
               "ab_timeout": st.aborted_timeout,
               "timeout_lanes": st.timeout_lanes,
               "transport": "udp_loopback_3shard"}).to_dict()


def _colocate_bench(n_cores, window_s, quick):
    """The reference's colocated-eBPF experiment analogue
    (exp/run_tatp_colocate.sh:27 pins servers to 8 shared cores): restrict
    the whole host process — C++ RX thread, wire parse, reply scatter,
    dispatch — to ``n_cores`` and rerun the TATP wire bench. Threads
    spawned inside inherit the affinity."""
    from dint_tpu.stats import CpuMonitor

    all_cpus = os.sched_getaffinity(0)
    cpu = CpuMonitor()
    try:
        # inside the try: an exception anywhere after narrowing must not
        # leave the rest of the sweep pinned
        os.sched_setaffinity(0, set(sorted(all_cpus)[:n_cores]))
        out = _tatp_wire_bench(window_s, quick)
    finally:
        os.sched_setaffinity(0, all_cpus)
    out.update(cpu.cores())
    out["host_cores_pinned"] = n_cores
    return out


OPEN_RATES = (0.25, 0.5, 0.75, 0.9, 1.1)


class _ResultSink(dict):
    """Results dict that persists each point to <out>/<name>.json the
    moment it lands: a point that fails the run leaves every finished
    point on disk."""

    def __init__(self, out: str, skip_done: bool = False):
        super().__init__()
        self.out = out
        self.skip_done = skip_done

    def __setitem__(self, name, block):
        super().__setitem__(name, block)
        with open(os.path.join(self.out, f"{name}.json"), "w") as f:
            json.dump(block, f, indent=1)

    def already_done(self, name) -> bool:
        """--skip-done restart support: after a failed or killed run,
        rerun with --skip-done to skip every point that already has an
        artifact."""
        if not self.skip_done:
            return False
        try:
            with open(os.path.join(self.out, f"{name}.json")) as f:
                block = json.load(f)
        except (OSError, ValueError):
            return False
        super().__setitem__(name, block)   # load for the summary
        return True


def run_all(out: str, window_s: float = 10.0, quick: bool = False,
            only: str | None = None, skip_done: bool = False,
            hot_frac: float | None = None,
            hot_prob: float | None = None) -> dict:
    os.makedirs(out, exist_ok=True)
    results: dict[str, dict] = _ResultSink(out, skip_done=skip_done)

    # full sweep at the reference's workload scale: 7M subscribers
    # (tatp/caladan/tatp.h:28), 24M accounts (smallbank.h:16); widths
    # include 256/1024 to measure the latency floor at reduced load
    n_sub = 2_000 if quick else int(os.environ.get(
        "DINT_EXP_SUBSCRIBERS", 7_000_000))
    n_acc = 20_000 if quick else int(os.environ.get(
        "DINT_EXP_SB_ACCOUNTS", 24_000_000))
    # peak width first: the highest-value anchor point lands before the
    # latency-floor small widths
    widths = [256] if quick else [8192, 256, 1024, 2048, 32768]
    # measured-timestamp latency points (run_latency_window): small widths
    # where the per-step sync fetch does not dominate the step itself
    lat_widths = [256] if quick else [256, 1024, 8192]
    cpb = 4
    rates = OPEN_RATES[1::2] if quick else OPEN_RATES

    def want(name):
        # bidirectional substring: --only tatp matches point tatp_closed_w256
        # via `only in name`; --only tatp_closed passes the coarse `tatp`
        # gate via `name in only`
        return only is None or only in name or name in only

    if want("tatp"):
        from dint_tpu.engines import tatp_dense as td

        sweep_pipeline("tatp", lambda w, b: _tatp_runner(n_sub, w, b),
                       _tatp_extras, td.N_STATS, widths=widths, cpb=cpb,
                       depth=3, magic_idx=td.STAT_MAGIC_BAD,
                       window_s=window_s, open_rates=rates, results=results,
                       lat_widths=lat_widths,
                       geom={"k": td.K, "vw": 10})
    skew_preset = only is not None and "skew" in only
    if want("smallbank") and not skew_preset:
        from dint_tpu.clients import workloads as wl
        from dint_tpu.engines import smallbank_dense as sd
        from dint_tpu.ops import hotset

        skew_extra = {
            "hot_frac": (wl.SB_HOT_FRAC if hot_frac is None
                         else float(hot_frac)),
            "hot_prob": (wl.SB_HOT_PROB if hot_prob is None
                         else float(hot_prob)),
            # the value that actually built: plan-pinned when a plan is
            # readable, env-resolved otherwise (matches _sb_runner)
            "use_hotset": _plan_knobs("smallbank_skewed").get(
                "use_hotset", hotset.resolve_use_hotset(None)),
        }
        sweep_pipeline("smallbank",
                       lambda w, b: _sb_runner(n_acc, w, b, hot_frac,
                                               hot_prob),
                       _sb_extras, sd.N_STATS, widths=widths, cpb=cpb,
                       depth=2, magic_idx=sd.STAT_MAGIC_BAD,
                       window_s=window_s, open_rates=rates, results=results,
                       lat_widths=lat_widths, point_extra=skew_extra,
                       geom={"l": sd.L, "vw": sd.VW})

    if want("multihost_sb") and not skew_preset:
        # hierarchical-vs-flat transport A/B over the 2-D (dcn x ici)
        # mesh (parallel/multihost_sb.py): same global geometry, bit-
        # identical outputs, only the collective decomposition differs —
        # PERF.md round 14's "virtual-mesh bench no slower" leg of the
        # hierarchical decision rule. DINT_BENCH_MESH picks the shape.
        import jax

        from dint_tpu.engines import smallbank_pipeline as sp
        from dint_tpu.parallel import dense_sharded_sb as dsb
        from dint_tpu.parallel import multihost as mhost

        n_hosts, n_ici = mhost.mesh_shape_from_env()
        if len(jax.devices()) < n_hosts * n_ici or n_hosts < 3:
            print(f"multihost_sb: skipped ({n_hosts}x{n_ici} mesh needs "
                  f"{n_hosts * n_ici} devices and >= 3 hosts; have "
                  f"{len(jax.devices())} devices)", flush=True)
        else:
            mesh_extra = {
                "n_shards": n_hosts * n_ici,
                "mesh": {"n_hosts": n_hosts, "n_ici": n_ici,
                         "axes": [mhost.DCN_AXIS, mhost.ICI_AXIS]}}
            for tag, hier in (("hier", True), ("flat", False)):
                sweep_pipeline(
                    f"multihost_sb_{tag}",
                    lambda w, b, h=hier: _mh_sb_runner(n_acc, w, b, h),
                    _mh_sb_extras, dsb.N_STATS, widths=[256] if quick
                    else [8192], cpb=cpb, depth=2,
                    magic_idx=sp.STAT_MAGIC_BAD, window_s=window_s,
                    open_rates=(), results=results,
                    point_extra=dict(mesh_extra, hierarchical=hier),
                    geom={"l": 3, "vw": 2, "d": n_hosts * n_ici})

    if skew_preset:
        # skew-sweep preset (--only smallbank_skew): one width, hot_frac
        # swept across the 90%-hot workload — the dintcache decision curve
        # (arm DINT_USE_HOTSET=0/1 runs to A/B the hot tier at each skew)
        from dint_tpu.engines import smallbank_dense as sd
        from dint_tpu.ops import hotset

        skew_w = 256 if quick else 8192
        for frac in (0.01, 0.04, 0.16, 0.5):
            sweep_pipeline(
                f"smallbank_skew_h{int(frac * 100):02d}",
                lambda w, b, f=frac: _sb_runner(n_acc, w, b, f, hot_prob),
                _sb_extras, sd.N_STATS, widths=[skew_w], cpb=cpb,
                depth=2, magic_idx=sd.STAT_MAGIC_BAD, window_s=window_s,
                open_rates=(), results=results,
                point_extra={"hot_frac": frac,
                             "hot_prob": (0.9 if hot_prob is None
                                          else float(hot_prob)),
                             "use_hotset": _plan_knobs(
                                 "smallbank_skewed").get(
                                 "use_hotset",
                                 hotset.resolve_use_hotset(None))},
                geom={"l": sd.L, "vw": sd.VW})
    # --only serve_mesh is a preset (like skew): the bidirectional
    # substring filter would also fire the single-device serve legs
    # ("serve" in "serve_mesh"), so the mesh preset suppresses them
    mesh_preset = only is not None and "mesh" in only
    if want("serve") and not mesh_preset:
        # always-on serving plane (dint_tpu/serve): open-loop
        # latency-vs-offered-load curves with exact queue/service
        # attribution; RealClock, so rates/latencies are wall-measured
        sweep_serve("serve_tatp", "tatp_dense", n_sub,
                    window_s=window_s, open_rates=rates, results=results,
                    quick=quick, cpb=cpb)
        sweep_serve("serve_smallbank", "smallbank_dense", n_acc,
                    window_s=window_s, open_rates=rates, results=results,
                    quick=quick, cpb=cpb)
    if want("serve_mesh") and not skew_preset:
        # mesh-wide serving plane (serve/mesh.py): the whole 2-D mesh
        # as one open-loop service; self-gates on device count/hosts
        sweep_serve_mesh("serve_mesh", n_acc, window_s=window_s,
                         open_rates=rates, results=results, quick=quick,
                         cpb=cpb)

    sweep_micro(window_s, quick, results, want=want)  # self-gates per point

    summary = {"configs": sorted(results),
               "window_s": window_s, "quick": quick}
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return results


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="exp_results")
    ap.add_argument("--window", type=float, default=10.0)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-done", action="store_true",
                    help="skip points whose artifact already exists "
                         "(restart after a failed or killed run)")
    ap.add_argument("--hot-frac", type=float, default=None,
                    help="SmallBank hot-set fraction override (default: "
                         "the reference 4%%); the dintcache mirror "
                         "(DINT_USE_HOTSET=1) aligns to it")
    ap.add_argument("--hot-prob", type=float, default=None,
                    help="SmallBank hot-set probability override "
                         "(default: the reference 90%%)")
    args = ap.parse_args()
    from dint_tpu import _runtime

    _runtime.require_tpu()      # the CLI measures; tests call run_all
    _runtime.enable_compile_cache()
    if args.quick and args.window == 10.0:
        args.window = 1.0
    results = run_all(args.out, window_s=args.window, quick=args.quick,
                      only=args.only, skip_done=args.skip_done,
                      hot_frac=args.hot_frac, hot_prob=args.hot_prob)
    for name in sorted(results):
        r = results[name]
        print(f"{name}: goodput={r['goodput']:.0f}/s "
              f"abort={r['abort_rate']:.4f} p99={r['p99_us']:.0f}us")


if __name__ == "__main__":
    main()
