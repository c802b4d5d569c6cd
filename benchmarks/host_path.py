"""The host's side of a dispatch, split on the profiler's one clock.

A traced run starts the profiler with ``host_tracer_level = 2``
(``run.py``), so beside the benchmark's two annotations
(``bench.dispatch``, ``bench.fetch``) the host plane holds the runtime's
own events on every host thread (the jit fast path, the executable's
launch, the completion callbacks, the device-to-host copy of the stats),
all on the host's clock. ``trace_reduce.load_xplane`` keeps the two
annotations alone; this reduction keeps every event of every ``/host:``
line between the first ``bench.dispatch`` and the last ``bench.fetch``,
and device 0's ``XLA Modules``.

Per dispatch *i* of a ``closed_step`` window (one dispatch, one fetch, one
program run) it joins the *i*-th ``bench.dispatch`` [d0, d1], the *i*-th
``bench.fetch`` [f0, f1] and the *i*-th run of the block program
[m0, m1], and gives, in ns:

  call            d1 - d0           the jit call
  launch_lag      m0 - d0           call begun -> device program begun
  run             m1 - m0           the program on the device
  completion_lag  f1 - m1           device program ended -> stats on host
  loop_gap        d0(i+1) - f1(i)   the loop's own Python
  cycle           d0(i+1) - d0(i)   one dispatch to the next

``launch_lag + completion_lag == (f1 - d0) - run`` in every step, which is
what ``host_overhead_ms.lat`` takes the median of, whatever the clocks.

**The clock.** [m0, m1] are on the device's clock, the rest on the host's.
A shift of the device's clock trades ``launch_lag`` against
``completion_lag`` one for one. The shifts under which no lag of any
step is negative are an interval: from the two annotations alone
[-min launch_lag, min completion_lag], ``clock_slack_spans`` wide. The
runtime narrows it where it stamps a host event with the ``run_id`` that
also stands on the device's ``XLA Modules`` event (learned from a v5e
trace, jax 0.9.0, PR 41): ``DoEnqueueProgram`` (the launch thread hands
run *r* to the driver: the device cannot have begun *r* before it
starts) and ``CompleteCallbacks`` (the completion thread runs *r*'s
callbacks: the device had ended *r* before it starts). What is left is
``clock_slack``, and ``set_by`` names the pair at each end. Where the
runtime's pairs are found the device's clock is moved to the middle of
the interval (``clock_shift``; every lag is then within half the slack of
the truth); where only the annotations are there it is left as recorded,
and a negative lag in any step is a note and no result, never a clamped
zero. An empty interval (the clocks drift, or a join went wrong) is a
note and no result too.

Between the pairs the split needs no device clock at all: ``to_enqueue``
(d0 -> ``DoEnqueueProgram``), ``device_round_trip`` (``DoEnqueueProgram``
-> ``CompleteCallbacks``, less the run) and ``from_complete``
(``CompleteCallbacks`` -> f1) add up to the same host overhead, each on
the host's clock alone.

``events``: every runtime event's **self time** (its duration less what
its children on the same line cover: ``trace_reduce.self_times``) inside
[d0, f1], mean ns a dispatch, kept apart for before m0, during the run
and after m1, under ``<line>/<name>`` (a line is a host thread; its
``/<tid>`` is dropped).

``read(ctx)`` finds the run's own trace (``part_times.newest_xplane``),
reduces once, keeps the result on ``ctx``, prints one line
``{"host_path_us_per_dispatch": ...}`` and returns None where there is no
device plane (a rehearsal) or nothing to split; it never raises into the
result line.

    python3 -m benchmarks.host_path show <trace dir or .xplane.pb>
    python3 -m benchmarks.host_path fixture <trace> <n> <out.json>

``fixture`` cuts the first n dispatches (all host lines, device 0's
modules) out of a recorded trace, with what this reduction gives for
them."""
from __future__ import annotations

import bisect
import json
import statistics
import sys

from benchmarks import part_times
from benchmarks import trace_reduce as tr

DISPATCH, FETCH = tr.HOST_SPANS
HOST_PLANE = "/host:"
LAUNCHED_BY = "DoEnqueueProgram"        # starts before the device begins r
COMPLETED_BY = "CompleteCallbacks"      # starts after the device ended r
PHASES = ("before", "during", "after")
TOP_EVENTS = 15


def load(path: str) -> dict:
    """{"lines": [{"name": line, "events": [[name, start_ns, dur_ns(,
    run_id)]]}], "modules": [[name, start_ns, dur_ns(, run_id)]]}: every
    host line's events from the first ``bench.dispatch`` to the last
    ``bench.fetch``, and device 0's ``XLA Modules``."""
    from jax.profiler import ProfileData

    def row(e):
        out = [e.name, float(e.start_ns), float(e.duration_ns)]
        for key, val in e.stats:
            if key == "run_id":
                out.append(int(val))
        return out

    lines, devices = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(HOST_PLANE):
            lines += [{"name": ln.name, "events": [row(e) for e in ln.events]}
                      for ln in plane.lines]
        elif plane.name.startswith(tr.DEVICE_PLANE):
            devices[plane.name] = [row(e) for ln in plane.lines
                                   if ln.name == tr.MODULES_LINE
                                   for e in ln.events]
    return cut({"lines": lines,
                "modules": devices[min(devices)] if devices else []})


def spans_of(trace: dict, name: str) -> list:
    return sorted((e[1], e[1] + e[2]) for ln in trace["lines"]
                  for e in ln["events"] if e[0] == name)


def cut(trace: dict, n: int | None = None) -> dict:
    """The trace from its first dispatch to the end of its n-th fetch
    (its last by default): the host events that start in there, and the
    first n runs of the block program (all by default; they are not cut
    by time: they are on another clock)."""
    d, f = spans_of(trace, DISPATCH), spans_of(trace, FETCH)
    runs = set(block_runs(trace["modules"])[:n])
    modules = [m for m in trace["modules"] if (m[1], m[1] + m[2]) in runs]
    if not d or not f:
        return {"lines": [], "modules": modules}
    lo, hi = d[0][0], f[-1 if n is None else min(n, len(f)) - 1][1]
    lines = [{"name": ln["name"],
              "events": [e for e in ln["events"] if lo <= e[1] <= hi]}
             for ln in trace["lines"]]
    return {"lines": [ln for ln in lines if ln["events"]],
            "modules": modules}


def block_runs(modules: list) -> list:
    return tr.block_modules({"modules": [m[:3] for m in modules]})


def _pct(values, q: float) -> float:
    """The q-th percentile, linear between the sorted samples."""
    v = sorted(values)
    at = (len(v) - 1) * q
    lo = int(at)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (at - lo)


def _by_run_id(trace: dict, name: str):
    """({run_id: start_ns}, "<line>/<name>") of the host events so named
    that carry a run_id."""
    starts, where = {}, name
    for ln in trace["lines"]:
        for e in ln["events"]:
            if e[0] == name and len(e) > 3:
                starts[e[3]] = e[1]
                where = f"{line_key(ln['name'])}/{name}"
    return starts, where


def line_key(line_name: str) -> str:
    """``pjrt-tpu-tasks/332`` -> ``pjrt-tpu-tasks``: the thread's id
    changes from run to run."""
    head, _, tail = line_name.rpartition("/")
    return head if head and tail.lstrip("-").isdigit() else line_name


def split(trace: dict):
    """(found, None), or (None, note) where there is nothing to split."""
    d, f = spans_of(trace, DISPATCH), spans_of(trace, FETCH)
    runs = block_runs(trace["modules"])
    if not (len(d) == len(f) == len(runs)) or not d:
        return None, (f"{len(d)} {DISPATCH}, {len(f)} {FETCH} and "
                      f"{len(runs)} runs of the block program: not one of "
                      "each a dispatch, nothing is joined")
    n = len(d)
    launch = [m[0] - a[0] for a, m in zip(d, runs)]
    completion = [b[1] - m[1] for b, m in zip(f, runs)]
    lo, hi = -min(launch), min(completion)
    found = {"dispatches": n, "clock_slack_spans": hi - lo,
             "set_by": {"launch": DISPATCH, "completion": FETCH}}

    # the runtime's pairs, joined to the i-th run by its run_id
    rid = {(m[1], m[1] + m[2]): m[3] for m in trace["modules"] if len(m) > 3}
    (enq, enq_name), (comp, comp_name) = (_by_run_id(trace, LAUNCHED_BY),
                                          _by_run_id(trace, COMPLETED_BY))
    ids = [rid.get(m) for m in runs]
    paired = all(i in enq and i in comp for i in ids)
    if paired:
        e0, c0 = [enq[i] for i in ids], [comp[i] for i in ids]
        if not all(a[0] <= e <= c <= b[1]
                   for a, b, e, c in zip(d, f, e0, c0)):
            return None, (f"a run's {LAUNCHED_BY} or {COMPLETED_BY} lies "
                          "outside its dispatch's [d0, f1]: the join by "
                          "ordinal and the join by run_id disagree")
        e_lo = max(e - m[0] for e, m in zip(e0, runs))
        c_hi = min(c - m[1] for c, m in zip(c0, runs))
        if e_lo > lo:
            lo, found["set_by"]["launch"] = e_lo, enq_name
        if c_hi < hi:
            hi, found["set_by"]["completion"] = c_hi, comp_name
        found["to_enqueue"] = [e - a[0] for e, a in zip(e0, d)]
        found["device_round_trip"] = [
            (c - e) - (m[1] - m[0]) for c, e, m in zip(c0, e0, runs)]
        found["from_complete"] = [b[1] - c for b, c in zip(f, c0)]
    if hi < lo:
        return None, (f"no shift of the device's clock leaves every lag "
                      f"positive (it would have to be >= {lo:.0f} ns and "
                      f"<= {hi:.0f} ns): the clocks drift, or a join went "
                      "wrong")
    shift = (lo + hi) / 2 if paired else 0.0
    if not lo <= shift <= hi:
        return None, (f"a negative lag on the clocks as recorded (launch "
                      f"{min(launch):.0f} ns, completion "
                      f"{min(completion):.0f} ns at the least) and no "
                      f"{LAUNCHED_BY} / {COMPLETED_BY} with a run_id to "
                      "set the device's clock by")
    runs = [(m[0] + shift, m[1] + shift) for m in runs]
    found.update({
        "clock_slack": hi - lo, "clock_shift": shift,
        "call": [a[1] - a[0] for a in d],
        "launch_lag": [x + shift for x in launch],
        "run": [m[1] - m[0] for m in runs],
        "completion_lag": [x - shift for x in completion],
        "loop_gap": [d[i + 1][0] - f[i][1] for i in range(n - 1)],
        "cycle": [d[i + 1][0] - d[i][0] for i in range(n - 1)],
        "events": event_self_times(trace, d, f, runs)})
    return found, None


def event_self_times(trace: dict, d: list, f: list, runs: list) -> dict:
    """{"<line>/<name>": {"before": ns, "during": ns, "after": ns}}: mean
    self time a dispatch inside [d0, m0], [m0, m1], [m1, f1]. An event is
    clipped to the phase before the nesting is worked out, so one that
    spans two phases gives each its part."""
    out: dict = {}
    begins, ends = [a[0] for a in d], [b[1] for b in f]
    for ln in trace["lines"]:
        key = line_key(ln["name"])
        mine = [[] for _ in d]      # the events that touch dispatch i
        # a parent before its child, so that of two an edge clips to the
        # same interval the inner one keeps the time
        for e in sorted(ln["events"], key=lambda e: (e[1], -e[2])):
            for i in range(bisect.bisect_left(ends, e[1]),
                           bisect.bisect_right(begins, e[1] + e[2])):
                mine[i].append(e)
        for a, b, m, events in zip(d, f, runs, mine):
            edges = (a[0], m[0], m[1], b[1])
            for p, phase in enumerate(PHASES):
                lo, hi = edges[p], edges[p + 1]
                inside = []
                for e in events:
                    s, t = max(e[1], lo), min(e[1] + e[2], hi)
                    if t > s:
                        inside.append((e[0], None, s, t - s))
                for op, self_ns, _ in tr.self_times(inside):
                    if self_ns > 0:
                        row = out.setdefault(f"{key}/{op[0]}",
                                             dict.fromkeys(PHASES, 0.0))
                        row[phase] += self_ns / len(d)
    return out


def summary(found: dict) -> dict:
    """The line's body, in us: [p50, p95] of each per-dispatch quantity,
    the clock, the largest events."""
    us = 1e-3
    out = {}
    for k in ("call", "launch_lag", "run", "completion_lag", "loop_gap",
              "cycle", "to_enqueue", "device_round_trip", "from_complete"):
        if found.get(k):
            out[k] = [_pct(found[k], 0.5) * us, _pct(found[k], 0.95) * us]
    for k in ("clock_slack", "clock_slack_spans", "clock_shift"):
        out[k] = found[k] * us
    out["set_by"] = found["set_by"]
    top = sorted(found["events"].items(),
                 key=lambda kv: -sum(kv[1].values()))[:TOP_EVENTS]
    out["events"] = {k: {p: v[p] * us for p in PHASES} for k, v in top}
    out["dispatches"] = found["dispatches"]
    return out


def read(ctx: dict):
    """This run's split, or None where there is nothing to read. Reduced
    and printed once per run: the result is kept on ``ctx``."""
    if "host_path" not in ctx:
        ctx["host_path"] = _read(ctx)
    return ctx["host_path"]


def _read(ctx: dict):
    path = part_times.newest_xplane() if tr.traced(ctx) else None
    if path is None:
        return None
    try:
        found, note = split(load(path))
    except Exception as e:  # noqa: BLE001 — the run keeps its result line
        found, note = None, f"not read: {type(e).__name__}: {e}"
    if found is None:
        print(json.dumps({"host_path": note, "xplane": path}), flush=True)
        return None
    print(json.dumps({"host_path_us_per_dispatch": summary(found)}),
          flush=True)
    return found


def median_ms(ctx: dict, quantity: str):
    """The median over the traced dispatches of one per-dispatch
    quantity, in ms; None where there is no split."""
    found = read(ctx)
    return found and statistics.median(found[quantity]) / 1e6


def event_ms(ctx: dict, event: str, phase: str):
    """One event's mean self time a dispatch in one phase, in ms; None,
    with a note, where this trace has a split and no such event."""
    found = read(ctx)
    if not found:
        return None
    if event not in found["events"]:
        print(json.dumps({"host_path": f"no event {event} in this trace; "
                          "the metric that reads it is left out"}),
              flush=True)
        return None
    return found["events"][event][phase] / 1e6


def cut_fixture(path: str, n: int) -> dict:
    trace = cut(load(path), n)
    found, note = split(trace)
    return {"trace": trace, "expected": found, "note": note}


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "show":
        found, note = split(load(tr.find_xplane(sys.argv[2])))
        print(json.dumps(summary(found) if found else {"host_path": note},
                         indent=1))
    elif len(sys.argv) == 5 and sys.argv[1] == "fixture":
        with open(sys.argv[4], "w") as out:
            json.dump(cut_fixture(tr.find_xplane(sys.argv[2]),
                                  int(sys.argv[3])), out)
    else:
        sys.exit(__doc__)
