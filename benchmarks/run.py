#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n>
        --seconds <s> --trace <0|1>

One process, one import of JAX, no child, no thread of its own. The cell
is an entry of BENCHMARK.json's ``workloads``; everything that belongs to
it is found by name:

    benchmarks/configs/<config>.json       the deployment's sizes
    benchmarks/deployments/<kind>.py       how that kind is built, checked
                                           and compared (the contract:
                                           benchmarks/deployments/__init__.py)
    benchmarks/traffic/<traffic>.json      the traffic mix: loop, parameters
    benchmarks/loops/<kind>.py             the loop that offers it
    benchmarks/end_to_end/<metric>.py      one reader per end-to-end metric
    benchmarks/layer_metrics/<metric>.py   one reader per per-layer metric

What is the harness's and what the deployment's. This file knows two
stat columns, ``attempted`` and ``committed``, and reads one key of a
configuration, ``deployment``; the rest of the file goes to that module
untouched. The module brings ``build`` (the object a loop drives, with
``stat_names`` and, among them, the lawful ``outcomes``, the ``faults``
and the ``contention`` outcomes; its ``verify`` holds the deployment's
guarantees after the warm-up and after the window) and ``compare_small``
(the engine against independent code at a small size, in the traced run).
The harness computes ``failed = attempted - sum(outcomes) + sum(faults)``,
hands ``contention`` to the readers in ``ctx``, and adds the checks that
hold for any deployment: ``window.nothing_compiled`` here, the
accounting in checks.check_accounting for a ``verify`` to call. A
deployment of a new engine must bring a ``verify`` that holds its own
guarantees and a ``compare_small`` against code that shares nothing with
the engine; nothing here supplies either.

Every line of output is one JSON object. The last is the result the
driver reads. A run that cannot give one says why in a line
``{"error": ..., "stage": ...}`` and on stderr, and exits non-zero; a run
whose check fails gives its result with ``"correct": false`` and the
names of the failed checks.

``--rehearse`` runs the same phases at the configuration's ``rehearse``
sizes on whatever backend is there (the harness's own tests). It reports
``device.platform`` as it is, puts no time or rate under ``metrics`` and
marks the line ``"rehearsal": true``: never a line to take for a chip
run."""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

STAGE = ["start"]       # what the run was doing, for the line that says why


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def stage(name: str) -> None:
    STAGE[0] = name
    emit(stage=name, t=time.perf_counter() - T_PROCESS)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def reader_path(directory: str, name: str) -> str:
    """``<directory>/<name>.py``, or for a variant ``<quantity>.<cells>``
    with no file of its own (``step_ms.lat``: the same arithmetic under a
    name, and a bound, of its own) its quantity's ``<quantity>.py``."""
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.isfile(path) and "." in name:
        path = os.path.join(HERE, directory, name.rsplit(".", 1)[0] + ".py")
    return path


def load_reader(directory: str, name: str):
    """The reader of one metric (a name may hold dots, so the file is
    loaded by path)."""
    path = reader_path(directory, name)
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{directory}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(manifest: dict, section: str, cell: str) -> list:
    return [m for m in manifest[section]
            if cell in m.get("workloads", [cell])]


class KeySchedule:
    """``fold_in(PRNGKey(seed), i)`` for dispatch i, made on the device a
    chunk at a time and kept on the host, so a dispatch costs no second
    program. The chunk program takes the base key as an argument: one
    executable serves every seed."""

    def __init__(self, seed: int, chunk: int):
        import jax
        import jax.numpy as jnp
        import numpy as np

        self._np = np
        self._base = jax.random.PRNGKey(seed)
        self._make = jax.jit(lambda base, start: jax.vmap(
            lambda i: jax.random.fold_in(base, i))(
                start + jnp.arange(chunk, dtype=jnp.uint32)))
        self._keys = np.zeros((0, 2), np.uint32)
        self[0]

    def __getitem__(self, i: int):
        while i >= len(self._keys):
            more = self._make(self._base, self._np.uint32(len(self._keys)))
            self._keys = self._np.concatenate(
                [self._keys, self._np.asarray(more)])
        return self._keys[i]


def run_phase(loop, dep, carry, key_of, seconds, max_dispatches,
              before_drain=lambda: None) -> dict:
    res = loop.run(dep, carry, key_of, seconds, max_dispatches,
                   before_drain)
    res["totals"] = {n: int(v) for n, v in zip(dep.stat_names,
                                               res["totals"])}
    res["dispatched_txns"] = res["dispatches"] * dep.txns_per_dispatch
    return res


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def result_line(cell: str, seed: int, checks, res: dict, dep,
                metrics: dict, device: dict, peaks: list) -> dict:
    """The last line. ``attempted``: transactions dispatched in the
    window. ``failed``: those without a lawful outcome (the gap in
    sum(``dep.outcomes``) == attempted) plus what the ``dep.faults``
    columns counted; an abort is an answer the protocol gives, and is a
    per-layer metric."""
    t = res["totals"]
    peaks = [p for p in peaks if p is not None]
    device["memory_peak_bytes"] = max(peaks) if peaks else None
    return {"correct": checks.ok, "attempted": res["dispatched_txns"],
            "failed": t["attempted"] - sum(t[n] for n in dep.outcomes)
            + sum(t[n] for n in dep.faults),
            "metrics": metrics, "device": device,
            "failed_checks": checks.failed, "checks": checks.n,
            "workload": cell, "seed": seed}


def _main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    stage("resolve")
    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no cell {args.workload!r} in BENCHMARK.json; "
                         f"cells: {sorted(cells)}")
    cell = cells[args.workload]
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if args.rehearse:
        traffic = {**traffic, **traffic["rehearse"]}
    section = "per_layer" if args.trace else "end_to_end"
    wanted = cell_metrics(manifest, section, cell["name"])
    readers = {m["name"]: load_reader(
        "layer_metrics" if args.trace else "end_to_end", m["name"])
        for m in wanted}

    stage("jax")
    import jax

    from benchmarks import checks as ck
    from benchmarks import trace_reduce
    from dint_tpu import _runtime

    deployment = importlib.import_module(
        "benchmarks.deployments." + config["deployment"])
    loop = importlib.import_module("benchmarks.loops." + traffic["loop"])

    if args.rehearse:
        devices = jax.devices()[:cell["chips"]]
        if len(devices) < cell["chips"]:
            raise SystemExit(f"need {cell['chips']} devices to rehearse, "
                             f"found {len(devices)}")
    else:
        devices = _runtime.require_tpu(cell["chips"])
        cache_dir = _runtime.enable_compile_cache()
        # every program, however quick to compile, so that a second run
        # in this checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        emit(compile_cache_dir=cache_dir)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    emit(jax=jax.__version__, device=device, workload=cell["name"],
         seed=args.seed, seconds=args.seconds, trace=args.trace,
         rehearsal=args.rehearse,
         hbm_bytes_limit=(devices[0].memory_stats() or {}).get(
             "bytes_limit"))

    events: collections.Counter = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.update((name,)))
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: events.update((name,)))

    def compiles() -> int:
        return sum(n for name, n in events.items()
                   if name.startswith("/jax/core/compile"))

    checks = ck.Checks(emit)

    if args.trace:
        # against independent code: 12.7 s with every program cached (my
        # chip run, PR 28), a third of set-up, so it rides in the one
        # traced run the driver makes per cell and not in every run
        stage("compare_small")
        t = time.perf_counter()
        deployment.compare_small(config, args.seed, checks)
        emit(compare_small_s=time.perf_counter() - t)

    stage("populate")
    dep = deployment.build(config, traffic["params"], args.seed, devices,
                           emit, args.rehearse)
    keys = KeySchedule(args.seed, traffic["keys_per_chunk"])

    stage("warmup")
    # the same loop, a few dispatches and a drain, held to the same
    # checks: compiles (or loads) every program the window and the checks
    # after it will use, and leaves an empty pipeline
    t = time.perf_counter()
    n_warm = traffic["warmup_dispatches"]
    warm = run_phase(loop, dep, dep.start(), keys.__getitem__, 3600.0,
                     n_warm)
    emit(warmup_s=time.perf_counter() - t, dispatches=warm["dispatches"],
         peak_bytes_in_use=peak_bytes(devices))
    dep.verify(warm["final"], checks, "warmup", warm["totals"],
               warm["dispatched_txns"])
    carry = dep.restart(warm.pop("final"))
    emit(cache_hits=events["/jax/compilation_cache/cache_hits"],
         cache_misses=events["/jax/compilation_cache/cache_misses"],
         compile_events=compiles(), peak_bytes_in_use=peak_bytes(devices))

    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    tracing = [False]
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        tracing[0] = True

    def stop_trace():
        if tracing[0]:
            tracing[0] = False
            jax.profiler.stop_trace()

    stage("window")
    n_compiles = compiles()
    cpu0 = os.times()
    setup_s = time.perf_counter() - T_PROCESS
    try:
        res = run_phase(loop, dep, carry, lambda i: keys[n_warm + i],
                        args.seconds,
                        traffic["trace_dispatches"] if args.trace else None,
                        stop_trace)
    finally:
        stop_trace()        # the session never outlives a failed window
    del carry
    cpu1 = os.times()
    window_s = res["t1"] - res["t0"]
    checks.add("window.nothing_compiled", compiles() == n_compiles,
               compile_events=compiles() - n_compiles)
    emit(window_s=window_s, dispatches=res["dispatches"],
         totals=res["totals"],
         host_cores=(cpu1.user - cpu0.user + cpu1.system - cpu0.system)
         / window_s, peak_bytes_in_use=peak_bytes(devices))

    stage("verify")
    t = time.perf_counter()
    snap = dep.verify(res["final"], checks, "window", res["totals"],
                      res["dispatched_txns"])
    del res["final"]
    emit(verify_s=time.perf_counter() - t,
         peak_bytes_in_use=peak_bytes(devices))

    stage("metrics")
    reduced = None
    if args.trace:
        reduced = trace_reduce.reduce(trace_reduce.load_xplane(
            trace_reduce.find_xplane(trace_dir)))
        if not args.rehearse:
            trace_reduce.require_device_work(reduced, len(devices))
    ctx = {"loop": res, "window_s": window_s, "setup_s": setup_s,
           "totals": res["totals"], "contention": dep.contention,
           "counters": snap, "trace": reduced,
           "device": device, "geometry": dep.geometry,
           "steps": dep.steps_per_dispatch * res["dispatches"],
           "txns_per_dispatch": dep.txns_per_dispatch,
           "n_devices": dep.n_devices, "depth": dep.depth,
           "peak_bytes_in_use": peak_bytes(devices)}
    metrics, off_device = {}, {}
    for m in wanted:
        value = readers[m["name"]](ctx)
        if value is None:       # nothing to read: the metric is left out
            continue
        keep = (metrics if not args.rehearse
                or m["source"] == "program_counter" else off_device)
        keep[m["name"]] = {"value": float(value), "unit": m["unit"]}

    line = result_line(cell["name"], args.seed, checks, res, dep, metrics,
                       device, ctx["peak_bytes_in_use"])
    if reduced is not None and not args.rehearse:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = trace_reduce.breakdown(reduced, ctx["steps"])
    if args.rehearse:
        line["rehearsal"] = True
        line["rehearsal_host_clock"] = off_device
    stage("done")
    emit(**line)
    return 0


def main(argv=None) -> int:
    try:
        return _main(argv)
    except SystemExit as e:
        if e.code in (0, None):
            raise
        # argparse has already written its usage; a refusal (no TPU, no
        # such cell) comes as text
        text = e.code if isinstance(e.code, str) else f"exit {e.code}"
        emit(error=text, stage=STAGE[0])
        print(f"benchmarks/run.py: {text} (stage {STAGE[0]})",
              file=sys.stderr, flush=True)
        return e.code if isinstance(e.code, int) else 1
    except BaseException as e:  # noqa: BLE001 — the boundary that reports
        traceback.print_exc()
        emit(error=f"{type(e).__name__}: {e}", stage=STAGE[0])
        print(f"benchmarks/run.py: failed in stage {STAGE[0]}",
              file=sys.stderr, flush=True)
        if isinstance(e, KeyboardInterrupt):
            raise
        return 1


if __name__ == "__main__":
    sys.exit(main())
