"""dintscope attribution: profiler traces -> per-wave time breakdowns.

PERF.md's closing accounting ("~6 chained random-access HBM ops at
0.6-0.9 ms each plus ~1.8 ms/step dispatch") was hand-derived from one-off
profiler sessions. This module makes that ledger a reproducible artifact:
it parses a `jax.profiler` Chrome/Perfetto trace (the `profiler_session`
output bench.py / exp.py already write under DINT_BENCH_TRACE_DIR /
DINT_EXP_TRACE_DIR) plus, optionally, the dintmon JSONL wave stream, and
attributes device time to the wave names in `monitor/waves.py` — the
`jax.named_scope("dint.<engine>.<wave>")` annotations survive jit into
XLA op metadata, so every profiler slice whose name or args carry a
registered wave name is charged to it.

The breakdown is schema-stable (`BREAKDOWN_SCHEMA`): every registered
wave appears (zeros when unobserved, listed in "missing"), per-wave
ms/step and %-of-attributed-step, and — when the caller supplies run
geometry — effective HBM bandwidth from the registry's declared bytes
formulas. `diff_breakdowns` is the perf-regression gate behind
`tools/dintscope.py diff`: configurable per-wave / step / throughput /
percentile thresholds, regressions named per wave.

`synthesize_trace` writes a deterministic synthetic trace covering the
registry — the checked-in fixture tier-1 drives the report/diff CLI on
(tests/test_dintscope.py), so the whole attribution path is CI-gated with
no TPU in the loop.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

from . import waves

# bench.py / exp.py artifact schema version. Version 1 is the implicit
# pre-dintscope era (no "schema" key); 2 adds "schema", "breakdown"
# (object | explicit null) and the "lat_hist" histogram block next to
# the percentile block.
ARTIFACT_SCHEMA = 2
# the breakdown object's own schema version
BREAKDOWN_SCHEMA = 1

_WAVE_RE = re.compile(r"dint\.[A-Za-z0-9_]+\.[A-Za-z0-9_]+")

# default regression thresholds for diff_breakdowns (percent; a wave/step
# must regress past these to fail the gate) and the floor below which a
# wave is dispatch noise, not signal
DEFAULT_WAVE_PCT = 25.0
DEFAULT_STEP_PCT = 10.0
DEFAULT_RATE_PCT = 10.0
DEFAULT_MIN_MS = 0.05

# A route that runs a wave's work under another scope makes an A/B see the
# wave vanish on one side. Without folding, the diff reports it under
# "missing" and the successor as an infinite regression — both
# meaningless. This map sends each such wave to its successor;
# diff_breakdowns folds its time into the successor on BOTH sides
# whenever the two sides observed the pair differently, so the gate
# compares like against like. `tools/dintscope.py diff --no-alias`
# disables the fold for debugging raw per-scope time.
WAVE_ALIASES: dict[str, str] = {
    waves.full_name(e, src): waves.full_name(e, dst)
    for e, src, dst in (
        # overlap=True moves the mesh route's exchange one step early
        # under its own scope — an overlap-on vs overlap-off A/B sees
        # `route` vanish on one side; fold it into route_prefetch so the
        # gate compares the route's total time and names a no-longer-
        # hidden DCN wave as a route_prefetch regression
        ("multihost_sb", "route", "route_prefetch"),
    )
}
for _src, _dst in WAVE_ALIASES.items():
    assert _src in waves.WAVE_DOCS and _dst in waves.WAVE_DOCS, (
        f"WAVE_ALIASES references unregistered wave: {_src} -> {_dst}")
del _src, _dst


# ---------------------------------------------------------------- loading


def _read_json(path: str):
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rt") as f:
        return json.load(f)


def find_trace_file(path: str) -> str:
    """Resolve a trace argument to one Chrome-trace JSON file: a file is
    taken as-is; a directory (a `jax.profiler.start_trace` target) is
    searched recursively for the NEWEST ``*.trace.json.gz`` /
    ``*.trace.json`` (each profiler session writes a fresh timestamped
    subdir, so newest = the session just recorded)."""
    if os.path.isfile(path):
        return path
    if os.path.isdir(path):
        hits = []
        for pat in ("**/*.trace.json.gz", "**/*.trace.json",
                    "**/*.json.gz"):
            hits.extend(glob.glob(os.path.join(path, pat), recursive=True))
        if not hits:
            raise FileNotFoundError(
                f"no profiler trace (*.trace.json[.gz]) under {path!r}")
        return max(hits, key=lambda p: (os.path.getmtime(p), p))
    raise FileNotFoundError(path)


def load_trace_events(path: str) -> tuple[list[dict], str]:
    """Load trace events from a Chrome-trace JSON file / .gz / profiler
    trace dir. Returns (events, resolved file path)."""
    f = find_trace_file(path)
    obj = _read_json(f)
    if isinstance(obj, dict):
        events = obj.get("traceEvents", [])
    elif isinstance(obj, list):
        events = obj
    else:
        raise ValueError(f"{f!r} is not a Chrome trace")
    return [e for e in events if isinstance(e, dict)], f


def _wave_of(event: dict) -> str | None:
    """The registered wave name a trace slice belongs to, or None. Scope
    names survive into different fields depending on the exporter (the
    slice name itself, `args.name`/`args.tf_op`/`args.long_name`), so
    search the name first, then the args values."""
    m = _WAVE_RE.search(str(event.get("name", "")))
    if m is None:
        args = event.get("args")
        if isinstance(args, dict):
            for v in args.values():
                m = _WAVE_RE.search(str(v))
                if m is not None:
                    break
    if m is None:
        return None
    name = m.group(0)
    return name if name in waves.WAVE_DOCS else None


# ------------------------------------------------------------ attribution


def _jsonl_summary(jsonl_path: str | None) -> dict | None:
    if not jsonl_path:
        return None
    from . import trace as tr

    meta, wave_events = tr.read_events(jsonl_path)
    return tr.summarize_events(meta, wave_events)


def attribute(events: list[dict], *, steps: int | None = None,
              jsonl: str | None = None,
              geometry: dict | None = None,
              trace_path: str | None = None) -> dict:
    """Attribute complete-slice device time to registered wave names.

    ``steps``: pipeline steps the trace covers. Resolution order:
    explicit arg > the dintmon JSONL stream's `steps` counter total >
    the max slice count observed for any single wave (each wave appears
    once per step, so the busiest wave's slice count is the step count
    when neither authority is available).

    ``geometry``: formula variables (w=, k=, l=, vw=, d=) for the
    registry's bytes formulas; effective bandwidth is only reported for
    waves whose formula fully evaluates.
    """
    per_wave_ms: dict[str, float] = {n: 0.0 for n in waves.ALL_WAVES}
    per_wave_slices: dict[str, int] = {n: 0 for n in waves.ALL_WAVES}
    total_ms = 0.0
    for e in events:
        if e.get("ph") != "X":
            continue
        try:
            dur_ms = float(e.get("dur", 0.0)) / 1e3
        except (TypeError, ValueError):
            continue
        if dur_ms <= 0:
            continue
        total_ms += dur_ms
        name = _wave_of(e)
        if name is not None:
            per_wave_ms[name] += dur_ms
            per_wave_slices[name] += 1

    summary = _jsonl_summary(jsonl)
    if steps is None and summary is not None and summary.get("counters"):
        steps = int(summary["counters"].get("steps", 0)) or None
    if steps is None:
        steps = max(per_wave_slices.values(), default=0) or None

    attributed_ms = sum(per_wave_ms.values())
    geometry = geometry or {}
    out_waves = {}
    for name in waves.ALL_WAVES:
        ms = per_wave_ms[name]
        rec = {
            "ms": round(ms, 6),
            "slices": per_wave_slices[name],
            "ms_per_step": round(ms / steps, 6) if steps else None,
            "pct": round(100.0 * ms / attributed_ms, 3)
            if attributed_ms > 0 else 0.0,
            "bytes_per_step": None,
            "gbps": None,
        }
        b = waves.wave_bytes(name, **geometry)
        if b is not None and steps and ms > 0:
            rec["bytes_per_step"] = int(b)
            rec["gbps"] = round(b / (ms / steps * 1e-3) / 1e9, 3)
        out_waves[name] = rec

    out = {
        "schema": BREAKDOWN_SCHEMA,
        "kind": "dintscope_breakdown",
        "trace": trace_path,
        "steps": steps,
        "geometry": {k: v for k, v in geometry.items() if v is not None},
        "total_ms": round(total_ms, 6),
        "attributed_ms": round(attributed_ms, 6),
        "unattributed_ms": round(total_ms - attributed_ms, 6),
        "step_ms": round(attributed_ms / steps, 6) if steps else None,
        "waves": out_waves,
        "missing": [n for n in waves.ALL_WAVES
                    if per_wave_slices[n] == 0],
    }
    if summary is not None:
        out["rates"] = {
            "dur_s": summary.get("dur_s"),
            "txn_attempted_per_s":
                (summary.get("rates_per_s") or {}).get("txn_attempted"),
            "txn_committed_per_s":
                (summary.get("rates_per_s") or {}).get("txn_committed"),
            "abort_rate": summary.get("abort_rate"),
        }
    return out


def report(path: str, *, steps: int | None = None,
           jsonl: str | None = None, geometry: dict | None = None) -> dict:
    """Load a trace (file or profiler dir) and attribute it."""
    events, resolved = load_trace_events(path)
    return attribute(events, steps=steps, jsonl=jsonl, geometry=geometry,
                     trace_path=resolved)


def load_breakdown(path: str) -> dict:
    """Load a diff operand: a breakdown artifact (from ``report -o``) is
    used directly; anything else (raw trace file / profiler dir) is
    attributed on the fly."""
    try:
        obj = _read_json(path) if os.path.isfile(path) else None
    except ValueError:
        obj = None
    if isinstance(obj, dict) and obj.get("kind") == "dintscope_breakdown":
        return obj
    if isinstance(obj, dict) and isinstance(
            obj.get("breakdown"), dict):     # a bench.py artifact
        return obj["breakdown"]
    return report(path)


# ------------------------------------------------------------------- diff


def _wave_observed(w: dict, name: str) -> bool:
    r = w.get(name) or {}
    return (r.get("slices") or 0) > 0 or (r.get("ms") or 0) > 0


def _fold_aliases(wa: dict, wb: dict) -> tuple[dict, dict, dict]:
    """Fold WAVE_ALIASES constituents into their successor on both
    sides of a diff — but ONLY for successors whose observation pattern
    is asymmetric between the sides (one side ran the successor's
    scope, the other the constituent's). A symmetric diff (or the
    all-waves synthetic fixture) never folds: its per-wave rows are
    already like-for-like and folding would only blur which wave moved.
    Returns (wa', wb', folded) where folded maps each triggered
    successor to the sorted constituents merged into it."""
    targets: dict[str, list[str]] = {}
    for src, dst in WAVE_ALIASES.items():
        oa, ob = _wave_observed(wa, dst), _wave_observed(wb, dst)
        asym = oa != ob or (_wave_observed(wa, src)
                            != _wave_observed(wb, src))
        if (oa or ob) and asym:
            targets.setdefault(dst, []).append(src)
    if not targets:
        return wa, wb, {}
    for dst in targets:
        targets[dst].sort()

    def fold(w: dict) -> dict:
        out = {k: dict(v) for k, v in w.items() if isinstance(v, dict)}
        for dst, srcs in targets.items():
            d = out.setdefault(dst, {"ms": 0.0, "slices": 0,
                                     "ms_per_step": None, "pct": 0.0,
                                     "bytes_per_step": None, "gbps": None})
            for src in srcs:
                r = out.pop(src, None)
                if not r:
                    continue
                d["ms"] = round((d.get("ms") or 0.0)
                                + (r.get("ms") or 0.0), 6)
                d["slices"] = (d.get("slices") or 0) + (r.get("slices")
                                                        or 0)
                d["pct"] = round((d.get("pct") or 0.0)
                                 + (r.get("pct") or 0.0), 3)
                ms, mr = d.get("ms_per_step"), r.get("ms_per_step")
                if mr is not None:
                    d["ms_per_step"] = round((ms or 0.0) + mr, 6)
        return out

    return fold(wa), fold(wb), targets


def diff_breakdowns(a: dict, b: dict, *, wave_pct: float = DEFAULT_WAVE_PCT,
                    step_pct: float = DEFAULT_STEP_PCT,
                    rate_pct: float = DEFAULT_RATE_PCT,
                    min_ms: float = DEFAULT_MIN_MS,
                    alias: bool = True) -> dict:
    """Compare breakdown B (candidate) against A (baseline). A regression
    is: a wave's ms_per_step growing past ``wave_pct`` % (ignoring waves
    under ``min_ms`` on both sides — dispatch noise), the attributed step
    time growing past ``step_pct`` %, committed throughput falling past
    ``rate_pct`` % (when both artifacts carry rates). With ``alias``
    (default), WAVE_ALIASES folds a wave another scope took over into
    that successor on both sides before comparing, so the A/B attributes
    the removed wave to its successor instead of reporting it missing. Returns a dict with
    ``regressions`` (list of {kind, wave?, a, b, pct} — empty = gate
    passes); `tools/dintscope.py diff` exits 1 when it is non-empty."""
    regressions = []
    rows = []
    wa, wb = a.get("waves", {}), b.get("waves", {})
    folded: dict[str, list[str]] = {}
    if alias:
        wa, wb, folded = _fold_aliases(wa, wb)
    merged_away = {s for srcs in folded.values() for s in srcs}
    for name in waves.ALL_WAVES:
        if name in merged_away:
            continue
        ra, rb = wa.get(name) or {}, wb.get(name) or {}
        ma, mb = ra.get("ms_per_step"), rb.get("ms_per_step")
        row = {"wave": name, "a_ms_per_step": ma, "b_ms_per_step": mb}
        if name in folded:
            row["includes"] = folded[name]
        if ma is not None and mb is not None and max(ma, mb) >= min_ms:
            pct = 100.0 * (mb - ma) / ma if ma > 0 else float("inf")
            row["pct"] = round(pct, 2) if ma > 0 else None
            if (mb > ma * (1 + wave_pct / 100.0)
                    and mb - ma >= min_ms):
                regressions.append({
                    "kind": "wave", "wave": name, "a": ma, "b": mb,
                    "pct": row["pct"]})
        rows.append(row)

    sa, sb = a.get("step_ms"), b.get("step_ms")
    if sa and sb and sb > sa * (1 + step_pct / 100.0):
        regressions.append({
            "kind": "step", "a": sa, "b": sb,
            "pct": round(100.0 * (sb - sa) / sa, 2)})

    ta = ((a.get("rates") or {}).get("txn_committed_per_s"))
    tb = ((b.get("rates") or {}).get("txn_committed_per_s"))
    if ta and tb and tb < ta * (1 - rate_pct / 100.0):
        regressions.append({
            "kind": "throughput", "a": ta, "b": tb,
            "pct": round(100.0 * (tb - ta) / ta, 2)})

    return {
        "schema": BREAKDOWN_SCHEMA,
        "kind": "dintscope_diff",
        "a": a.get("trace"), "b": b.get("trace"),
        "thresholds": {"wave_pct": wave_pct, "step_pct": step_pct,
                       "rate_pct": rate_pct, "min_ms": min_ms},
        "aliased": folded,
        "rows": rows,
        "regressions": regressions,
        "ok": not regressions,
    }


# ---------------------------------------------------------------- fixture


def synthesize_trace(out_path: str, *, steps: int = 4,
                     engines: tuple[str, ...] | None = None,
                     scale: dict[str, float] | None = None) -> int:
    """Write a deterministic synthetic Chrome trace covering every
    registered wave of ``engines`` (default: all). Each wave gets one
    slice per step whose duration is derived from its registry position
    (stable across runs), times ``scale.get(wave_name, 1.0)`` — tests
    perturb one wave's scale to inject a regression. Also emits a few
    unscoped filler slices so unattributed time is exercised. This is
    what built the checked-in fixture
    (tests/fixtures/dintscope_trace.json); regenerate it with
    `python tools/dintscope.py synth` after appending to the registry.
    Returns the number of events written."""
    engines = engines or waves.ENGINES
    scale = scale or {}
    events = [{"name": "process_name", "ph": "M", "pid": 1,
               "args": {"name": "/device:TPU:0 (synthetic)"}}]
    ts = 0.0
    for step in range(steps):
        for eng in engines:
            for i, name in enumerate(waves.WAVES_BY_ENGINE[eng]):
                dur_us = (100.0 + 50.0 * i) * float(scale.get(name, 1.0))
                events.append({
                    "name": f"fusion.{i}", "ph": "X", "pid": 1, "tid": 0,
                    "ts": round(ts, 3), "dur": round(dur_us, 3),
                    "args": {"long_name": f"jit_block/{name}/scatter"}})
                ts += dur_us
        # unscoped filler (infeed/outfeed-style slices)
        events.append({"name": f"copy-done.{step}", "ph": "X", "pid": 1,
                       "tid": 0, "ts": round(ts, 3), "dur": 25.0,
                       "args": {}})
        ts += 25.0
    with open(out_path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f,
                  indent=1)
    return len(events)
