"""Headline benchmark: TATP committed txns/s on one TPU chip.

The last JSON line on stdout is the result (the TATP line is printed
once on its own before the SmallBank leg runs, so a failure there still
leaves the TATP numbers on stdout next to the non-zero exit code):
  {"metric": ..., "value": N, "unit": ..., "device": {...}, ...}

Protocol mirrors the reference's measurement contract (BASELINE.md): TATP
mix 35/35/10/2/14/2/2, NURand subscriber ids, 3 replicated shards
(primary-backup, log x3 + bck x2 + prim commit pipeline), warmup then timed
window, committed (goodput) txns/s. The whole coordinator pipeline runs
on-device via the sort-free dense engine with REAL cross-cohort concurrency
(engines/tatp_dense.py: wave 1 of cohort t + validate of t-1 + commit of
t-2 fused per step, live validation aborts) — the TPU-first equivalent of
the reference's client coordinator + 3 eBPF servers on one machine
boundary. Extra JSON fields: "mode": "device_fused_pipelined" (workload
generated on device, no wire path — NOT comparable to the reference's
over-the-network numbers without that caveat), the abort breakdown
(ab_lock / ab_missing / ab_validate, client_ebpf_shard.cc:688-768), the
full latency metric block (avg/p50/p99/p99.9 µs at cohort granularity: a
txn's latency is its cohort's wave1->wave3 span = 3 pipeline steps,
client_ebpf_shard.cc:368-377), and a smallbank goodput figure when the
fused SmallBank pipeline runs.

DINT_BENCH_PROFILE=1 adds a "profile" field (populate/compile seconds,
per-block wall-time stats, per-step and per-txn device cost) so the time
split is a recorded fact; DINT_BENCH_TRACE_DIR additionally saves a jax
profiler trace of a few steady-state blocks.

One process: the measurement runs in `main()`, demands a TPU
(dint_tpu/_runtime.require_tpu: no platform override, no probe
subprocess, no retry, no answer from an old artifact) and a leg that
fails raises, so the exit code is non-zero. The static gates
(dintlint / dintcost / dintdur) are CPU work that tier-1 and
tools/dintgate.sh run; they are not part of a chip run. The reference
publishes no numbers (BASELINE.md "Published numbers: None"), so a result
is compared with this repo's own previous ledger line, never with an
assumed baseline.
"""
from __future__ import annotations

import json
import os
import sys

# DINT_BENCH_* env overrides exist for smoke tests / the L6 sweep driver;
# defaults are the headline configuration: the reference's FULL keyspace,
# 7M subscribers x 5 tables (tatp/caladan/tatp.h:28), ~6.2 GB of tables
# in the tight interleaved layout, populated on device.
N_SUBSCRIBERS = int(os.environ.get("DINT_BENCH_SUBSCRIBERS", 7_000_000))
WIDTH = int(os.environ.get("DINT_BENCH_WIDTH", 8192))   # txns per cohort
BLOCK = int(os.environ.get("DINT_BENCH_BLOCK", 16))     # cohorts per dispatch
VAL_WORDS = 10
WINDOW_S = float(os.environ.get("DINT_BENCH_WINDOW_S", 10.0))
# SmallBank skew knobs (the --hot-frac/--hot-prob of the sweep drivers,
# env-style like every bench knob): None = the reference 90%/4% skew. The
# dintcache hot tier (DINT_USE_HOTSET=1) aligns its mirror to HOT_FRAC.
HOT_FRAC = (float(os.environ["DINT_BENCH_HOT_FRAC"])
            if "DINT_BENCH_HOT_FRAC" in os.environ else None)
HOT_PROB = (float(os.environ["DINT_BENCH_HOT_PROB"])
            if "DINT_BENCH_HOT_PROB" in os.environ else None)


def main():
    import time as _time

    import jax
    import numpy as np

    from dint_tpu import _runtime
    from dint_tpu import stats as st
    from dint_tpu.engines import tatp_dense as td

    devices = _runtime.require_tpu()
    _runtime.enable_compile_cache()

    # A/B knob: DINT_BENCH_CHECK_MAGIC=0 drops the per-step magic-parity
    # gather (one [w,K] single-word random gather over the 6.2 GB val
    # array) to measure its cost; the default keeps the integrity oracle
    check_magic = os.environ.get("DINT_BENCH_CHECK_MAGIC", "1") != "0"
    # DINT_MONITOR=1 threads the dintmon counter plane through the carry
    # (dint_tpu/monitor, OBSERVABILITY.md): the artifact embeds the
    # end-of-run counter snapshot, and DINT_MONITOR_JSONL=path
    # additionally emits one wave event per dispatched block (the
    # per-block counter fetch is ~100 bytes but synchronizes the stream,
    # so leave it off for headline numbers). Off (default) the engines
    # run the unmonitored jaxpr and the artifact records counters: null.
    monitor_on = os.environ.get("DINT_MONITOR") == "1"
    # DINT_TRACE=1 threads the dinttrace flight-recorder ring through the
    # carry (dint_tpu/monitor/txnevents, OBSERVABILITY.md): the artifact
    # embeds the end-of-run event summary, DINT_TRACE_JSONL=path streams
    # the decoded per-window events for tools/dinttrace.py, and
    # DINT_TRACE_RATE tunes the deterministic sampling mask. Off (the
    # default) the engines run the untraced jaxpr and the artifact
    # records dinttrace: null.
    trace_on = os.environ.get("DINT_TRACE") == "1"
    from dint_tpu.ops import hotset

    # plan-resolved knobs replace the env-flag default path (ISSUE 17):
    # the pinned PLAN.json decides use_hotset for the headline config; ambient DINT_* flags win only under
    # DINT_PLAN_OVERRIDE=1 and the artifact records which knobs the
    # override changed. Without a readable plan, behaviour is exactly the
    # old env resolution and the artifact records "plan": null.
    plan_knobs, plan_meta = _plan_resolve("tatp_uniform")
    plan_kw = {k: plan_knobs[k] for k in ("use_hotset",)
               if k in plan_knobs} if plan_meta else {}

    def build_and_warm():
        t0 = _time.time()
        # on-device populate: at 7M subscribers the val array is ~6.2 GB —
        # generate it in HBM instead of building it in host numpy
        db = td.populate_device(jax.random.PRNGKey(0), N_SUBSCRIBERS,
                                val_words=VAL_WORDS)
        run, init, drain = td.build_pipelined_runner(
            N_SUBSCRIBERS, w=WIDTH, val_words=VAL_WORDS,
            cohorts_per_block=BLOCK, check_magic=check_magic,
            monitor=monitor_on, trace=trace_on,
            **plan_kw)
        carry = init(db)
        populate_s = _time.time() - t0

        t0 = _time.time()
        carry, stats0 = run(carry, jax.random.PRNGKey(99))
        np.asarray(stats0)  # fetch = sync (compile + first block)
        carry, stats1 = run(carry, jax.random.PRNGKey(98))
        np.asarray(stats1)  # steady-state donated-carry layout compile
        stats0 = np.asarray(stats0, np.int64).sum(axis=0) \
            + np.asarray(stats1, np.int64).sum(axis=0)
        compile_s = _time.time() - t0
        return run, drain, carry, stats0, populate_s, compile_s, \
            init.trace_cfg

    (run, drain, carry, stats0,
     populate_s, compile_s, trace_cfg) = build_and_warm()

    # dintmon drain loop: per-block wave events when a JSONL path is set
    # (the per-block counter fetch synchronizes the stream — an accepted
    # cost of asking for the timeline), end-of-run snapshot either way
    monitor_obj = None
    if monitor_on:
        from dint_tpu import monitor as dm

        jsonl = os.environ.get("DINT_MONITOR_JSONL")
        writer = dm.TraceWriter(jsonl, meta={
            "name": "bench_tatp", "width": WIDTH, "block": BLOCK,
            "n_subscribers": N_SUBSCRIBERS}) if jsonl else None
        monitor_obj = dm.Monitor(writer)
        if writer is not None:
            bare_run, t_prev = run, [_time.time()]

            def run(carry, key, _run=bare_run):
                carry, stats = _run(carry, key)
                now = _time.time()
                # defer=True double-buffers the ~100-byte counter fetch:
                # block i-1's snapshot is materialized only after block i
                # has been dispatched (an on-device copy keeps it alive
                # past the carry donation), so the JSONL drain no longer
                # serializes the dispatch stream (monitor/trace.Monitor)
                monitor_obj.observe(carry[-1], batch=WIDTH * BLOCK,
                                    dur_s=now - t_prev[0], defer=True)
                t_prev[0] = now
                return carry, stats

    # dinttrace drain loop: the ring zeroes at every block entry, so each
    # block's events must be observed per dispatch; defer=True keeps the
    # (cap x 16 B) fetch double-buffered off the dispatch critical path
    # like the counter plane's. Opt-in diagnostic mode — the fetch cost
    # is real, so leave DINT_TRACE off for headline numbers.
    tmon = None
    if trace_on:
        from dint_tpu.monitor import txnevents as txe

        tmon = txe.TxnMonitor(
            trace_cfg, path=os.environ.get("DINT_TRACE_JSONL"),
            meta={"name": "bench_tatp", "width": WIDTH, "block": BLOCK,
                  "n_subscribers": N_SUBSCRIBERS})
        ring_ix = -2 if monitor_on else -1
        traced_run = run

        def run(carry, key, _run=traced_run, _ix=ring_ix):
            carry, stats = _run(carry, key)
            tmon.observe(carry[_ix], defer=True)
            return carry, stats

    # host core-seconds strictly over the timed window (warmup above);
    # no device_duty field: a busy/idle share comes from a profiler trace
    # (monitor/attrib), not from host clocks
    cpu = st.CpuMonitor()
    carry, total, warm, dt, blocks, block_s = st.run_window(
        run, carry, jax.random.PRNGKey(0), WINDOW_S, td.N_STATS,
        warmup_blocks=0)
    cores = cpu.cores()

    trace_dir = os.environ.get("DINT_BENCH_TRACE_DIR") \
        if os.environ.get("DINT_BENCH_PROFILE") == "1" else None
    if trace_dir:   # must precede drain: drain donates the carry
        from dint_tpu.monitor import trace as mtrace
        with mtrace.profiler_session(trace_dir) as prof:
            carry, s = run(carry, jax.random.PRNGKey(1234))
            np.asarray(s)
        if prof.get("error"):
            raise RuntimeError(f"profiler trace failed: {prof['error']}")

    if monitor_obj is not None:
        monitor_obj.flush()     # land the deferred final wave event
    if tmon is not None:
        tmon.flush()            # land the deferred final event window
    counters_out = None
    trace_out = None
    outs = drain(carry)
    tail, rest = outs[1], list(outs[2:])
    if trace_on:            # drained boundary cohorts' events
        tmon.observe(rest.pop(0))
    if monitor_on:
        from dint_tpu import monitor as dm
        counters_out = dm.snapshot(rest.pop(0))
    # in-flight cohorts at window end emit their stats on completion
    total = total + np.asarray(tail, np.int64).sum(axis=0)
    if tmon is not None:
        trace_out = tmon.summary()
        tmon.close()

    committed = int(total[td.STAT_COMMITTED])
    attempted = int(total[td.STAT_ATTEMPTED])
    tps = committed / dt
    bad = int(total[td.STAT_MAGIC_BAD] + warm[td.STAT_MAGIC_BAD]
              + stats0[td.STAT_MAGIC_BAD])
    if bad != 0:
        raise RuntimeError(f"magic-byte integrity violated: {bad} "
                           "bad VAL replies (table corruption)")

    # latency at cohort granularity: each cohort's txns complete 3 pipeline
    # steps after dispatch (wave1 -> validate -> commit)
    steady = st.steady_blocks(block_s)
    p = st.cohort_latency_percentiles(block_s, BLOCK, depth=3)

    # dintscope attribution: the per-wave time breakdown of the traced
    # steady-state block — PERF.md's closing accounting as an artifact
    # field (object when a trace was recorded, explicit null otherwise)
    from dint_tpu.monitor import attrib

    breakdown = None
    if trace_dir:
        breakdown = attrib.report(
            trace_dir, jsonl=os.environ.get("DINT_MONITOR_JSONL"),
            geometry={"w": WIDTH, "k": td.K, "vw": VAL_WORDS})

    # dintserve saturation probe (round 17, opt-in): a short open-loop
    # burst through the serving plane at the bench width records serving
    # capacity and the queue/service split NEXT TO the closed-loop
    # headline — the two should agree at occupancy == width, and the gap
    # is the serving plane's ingestion overhead. Object when
    # DINT_BENCH_SERVE=1, EXPLICIT null otherwise.
    serve_out = None
    if os.environ.get("DINT_BENCH_SERVE") == "1":
        from dint_tpu.serve import ControllerCfg, ServeEngine
        s_eng = ServeEngine(
            "tatp_dense", N_SUBSCRIBERS,
            cfg=ControllerCfg(widths=(WIDTH,)),
            cohorts_per_block=BLOCK, val_words=VAL_WORDS,
            monitor=True)
        s_eng.warmup()
        s_eng.run(np.zeros(WIDTH * BLOCK * 8))
        s_eng.close()
        rep = s_eng.snapshot()
        serve_out = {k: rep[k] for k in
                     ("offered", "admitted", "shed", "blocks",
                      "achieved_rate", "slo_us", "slo_met",
                      "queue", "service", "controller", "plan")}

    out = {
        "schema": attrib.ARTIFACT_SCHEMA,
        "metric": "tatp_committed_txns_per_sec",
        "value": round(tps, 1),
        "unit": "txn/s",
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "mode": "device_fused_pipelined",
        "throughput": round(attempted / dt, 1),
        "abort_rate": round(1 - committed / max(attempted, 1), 5),
        # aborts from lock/validate conflicts only: the number comparable
        # to the reference's abort rate. ab_missing is TATP semantics —
        # GET_ACCESS / GET_NEW_DEST / CF txns fail on absent rows BY
        # DESIGN (~25% analytic floor, pinned in
        # test_ab_missing_matches_population_analytics) — and dominates
        # abort_rate at every contention level, exactly as in the
        # reference's goodput accounting (client_ebpf_shard.cc:583-587).
        "contention_abort_rate": round(
            float(total[td.STAT_AB_LOCK] + total[td.STAT_AB_VALIDATE])
            / max(attempted, 1), 5),
        "ab_lock": int(total[td.STAT_AB_LOCK]),
        "ab_missing": int(total[td.STAT_AB_MISSING]),
        "ab_validate": int(total[td.STAT_AB_VALIDATE]),
        "avg_us": round(p["avg"], 1),
        "p50_us": round(p["p50"], 1),
        "p99_us": round(p["p99"], 1),
        "p999_us": round(p["p999"], 1),
        "lat_samples": int(p["n"]),
        # log-bucketed histogram next to the percentile block: exact
        # cross-window/cross-shard merges (stats.LatencyHistogram)
        "lat_hist": p.get("hist"),
        "n_subscribers": N_SUBSCRIBERS,
        "width": WIDTH,
        # mesh provenance, schema-stable: the headline legs are 1-D
        # single-device pipelines, so both fields are EXPLICIT nulls; the
        # 2-D (dcn x ici) measurements live in exp.py --only multihost_sb
        # and tools/hw_multihost.sh, whose points record n_shards plus
        # {n_hosts, n_ici, axes} parsed from DINT_BENCH_MESH
        "n_shards": None,
        "mesh": None,
        # dintcache hot tier + skew provenance (TATP itself keeps the hot
        # tier off — uniform NURand; the flag records the env so the
        # SmallBank leg's A/B state is readable from the headline line)
        "use_hotset": hotset.env_use_hotset(),
        "hot_frac": HOT_FRAC,
        "hot_prob": HOT_PROB,
        # which pinned plan resolved the build knobs, schema-stable:
        # {source, hash, overridden} when PLAN.json was readable (dintplan,
        # ANALYSIS.md "Static configuration planning"), EXPLICIT null
        # otherwise — an artifact can always prove whether its knobs were
        # plan-resolved or ambient
        "plan": plan_meta,
        # end-of-run dintmon snapshot, schema-stable: a {name: count}
        # object when DINT_MONITOR=1, EXPLICIT null otherwise — consumers
        # never need to distinguish "off" from "old artifact schema"
        "counters": counters_out,
        # dinttrace flight-recorder summary, schema-stable: a summary
        # object when DINT_TRACE=1 (windows/events/dropped — the full
        # stream goes to DINT_TRACE_JSONL for tools/dinttrace.py),
        # EXPLICIT null otherwise
        "dinttrace": trace_out,
        # dintserve saturation probe (object when DINT_BENCH_SERVE=1,
        # explicit null otherwise — same consumer contract as counters)
        "serve": serve_out,
        # dintscope per-wave breakdown (object when DINT_BENCH_TRACE_DIR
        # recorded a trace, explicit null when attribution is off)
        "breakdown": breakdown,
        **({} if check_magic else {"integrity_checks": "off (A/B knob)"}),
        "blocks": blocks,
        "window_s": round(dt, 2),
        # the reference's `primary ucores/kcores` analogue
        # (smallbank/cpu_util.h:37-46)
        **cores,
    }
    if os.environ.get("DINT_BENCH_PROFILE") == "1":
        bs = np.asarray(steady)
        out["profile"] = {
            "populate_s": round(populate_s, 2),
            "compile_s": round(compile_s, 2),
            "block_ms_min": round(float(bs.min()) * 1e3, 2),
            "block_ms_mean": round(float(bs.mean()) * 1e3, 2),
            "block_ms_max": round(float(bs.max()) * 1e3, 2),
            "step_ms": round(float(bs.min()) / BLOCK * 1e3, 3),
            "txn_ns": round(float(bs.min()) / (BLOCK * WIDTH) * 1e9, 1),
        }
        if trace_dir:
            out["profile"]["trace_dir"] = trace_dir
    # the TATP line first: a SmallBank leg that raises then leaves it on
    # stdout beside the non-zero exit code
    print(json.dumps(out), flush=True)
    print(f"attempted={attempted} blocks={blocks} window_s={dt:.2f}",
          file=sys.stderr)
    out.update(_bench_smallbank())
    print(json.dumps(out), flush=True)


def _plan_resolve(workload):
    """Plan-resolved build knobs for one workload from the pinned
    PLAN.json (analysis/plan.resolve_for): the plan replaces the env-flag
    default path, and ambient DINT_* flags win only under
    DINT_PLAN_OVERRIDE=1 (meta["overridden"] records which knobs moved —
    the plan_check gate makes any other contradiction an ERROR). Returns
    ({}, None) when no plan is readable or DINT_BENCH_PLAN=0: knobs then
    fall back to plain env resolution and the artifact records
    "plan": null, never a silent default."""
    if os.environ.get("DINT_BENCH_PLAN", "1") == "0":
        return {}, None
    try:
        from dint_tpu.analysis import plan as dplan
        knobs, meta = dplan.resolve_for(workload)
        if meta.get("source") is None:
            return {}, None
        return knobs, meta
    except Exception:  # noqa: BLE001 — a broken plan must not kill bench
        return {}, None


def _bench_smallbank():
    """Secondary metric: SmallBank committed txn/s (device-fused pipeline).

    Returns extra JSON fields; raises if the pipeline is unavailable."""
    from dint_tpu.clients import bench_smallbank

    # measured on v5e: SmallBank's 3-lane txns amortize per-step overheads
    # past TATP's w=8192 knee (870k @8192 -> 1.32M @16384) but wider
    # points pay in abort rate — both sides of the trade are benched and
    # quoted; the headline is the abort-matched point (bench_smallbank.run)
    env_w = os.environ.get("DINT_BENCH_SB_WIDTH")
    widths = (int(env_w),) if env_w else bench_smallbank.WIDTHS
    sb_knobs, sb_meta = _plan_resolve("smallbank_skewed")
    out = bench_smallbank.run(
        window_s=WINDOW_S,
        n_accounts=int(os.environ.get("DINT_BENCH_SB_ACCOUNTS",
                                      bench_smallbank.N_ACCOUNTS)),
        widths=widths,
        block=BLOCK,
        hot_frac=HOT_FRAC,
        hot_prob=HOT_PROB,
        knobs={k: v for k, v in sb_knobs.items()
               if k.startswith("use_")} if sb_meta else None)
    out["smallbank_plan"] = sb_meta
    return out


if __name__ == "__main__":
    main()
