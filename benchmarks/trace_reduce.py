"""From the profiler's trace to numbers: the benchmark's own reduction.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into a
plain structure (what ``benchmarks/fixtures/*.json`` records, cut down),
and ``reduce`` turns that into busy time, idle gaps, time per
``dint.<engine>.<wave>`` scope, collective time and the block programs'
own intervals. Nothing here reads the program: only names the program
gives its scopes and the names XLA gives its ops.

    python3 -m benchmarks.trace_reduce describe <trace dir or .xplane.pb>

prints what a trace holds, plane by plane: how the layout below was
learned, and the first thing to run when a new runtime changes it.

    python3 -m benchmarks.trace_reduce fixture <trace> <n> <out.json>

cuts a recorded trace down to n executions of the block program and
writes it with the numbers the reduction gives: a test fixture."""
from __future__ import annotations

import glob
import json
import os
import re
import sys

SCOPE = re.compile(r"dint\.[a-z0-9_]+\.[a-z0-9_]+")
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("bench.dispatch", "bench.fetch")
COLLECTIVE = re.compile(r"collective-permute|all-reduce|all-gather|"
                        r"all-to-all|reduce-scatter")


def find_xplane(trace_dir: str) -> str:
    if os.path.isfile(trace_dir):
        return trace_dir
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _fields(buf):
    """The fields of one protobuf message: (number, value) with value an
    int (varint) or a memoryview (length-delimited); fixed-width fields
    are skipped. Enough of the wire format to read names out of an
    xplane without a schema compiler."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        num, wire = key >> 3, key & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield num, val
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield num, buf[i:i + size]
            i += size
        else:
            i += 8 if wire == 1 else 4


def op_scopes(path: str) -> dict:
    """{plane name: {op name: scope}} for the device planes of an xplane.

    The profiler names a device op by its HLO text and keeps the JAX name
    stack (``jit(block)/while/body/.../dint.tatp_dense.lock/gather``) as
    the stat ``tf_op`` of the op's XEventMetadata, which
    ``jax.profiler.ProfileData`` does not hand out. So the metadata is
    read from the file itself: XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4 and .stat_metadata = 5 (maps: key 1, value 2);
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (a string kept
    once, as a stat metadata's name). Lines and events, the bulk of the
    file, are skipped whole."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = None, [], {}
        for num, val in _fields(plane):
            if num == 2:
                name = bytes(val).decode()
            elif num in (4, 5):
                entry = dict(_fields(val))
                (events.append if num == 4 else
                 lambda e: stat_names.__setitem__(
                     e[1], bytes(dict(_fields(e[2]))[2]).decode()))(entry)
        if not name or not name.startswith(DEVICE_PLANE):
            continue
        scopes = out[name] = {}
        for entry in events:
            md_name, scope = None, None
            for num, val in _fields(entry[2]):
                if num == 2:
                    md_name = bytes(val).decode()
                elif num == 5:
                    stat = dict(_fields(val))
                    text = (bytes(stat[5]).decode() if 5 in stat
                            else stat_names.get(stat.get(7), ""))
                    m = SCOPE.search(text)
                    if m and stat_names.get(stat.get(1)) == "tf_op":
                        scope = m.group(0)
            scopes[md_name] = scope
    return out


def load_xplane(path: str) -> dict:
    """{"devices": [{"name", "ops": [[name, scope, start_ns, dur_ns]],
    "modules": [[name, start_ns, dur_ns]]}], "host": [[name, start_ns,
    dur_ns]]}, as learned from a v5e trace (jax 0.9.0): devices are the
    planes named ``/device:TPU:<n>``; their line ``XLA Ops`` holds one
    event per executed HLO op, nested where an op (``while``) holds
    others, and their line ``XLA Modules`` one event per executed
    program, named ``jit_<fn>(<fingerprint>)``. Asynchronous copies and
    collectives' transfers are on ``Async XLA Ops`` and are not counted
    as the core being busy. Host spans are the benchmark's own
    TraceAnnotations, on the host plane's line of the main thread; the
    device's clock is aligned to the host's to about a millisecond."""
    from jax.profiler import ProfileData

    scopes = op_scopes(path)
    data = ProfileData.from_file(path)
    out = {"devices": [], "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = {"name": plane.name, "ops": [], "modules": []}
            scope_of = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [
                        [short_name(e.name), scope_of.get(e.name),
                         float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name in HOST_SPANS]
    out["devices"].sort(key=lambda d: d["name"])
    out["host"].sort(key=lambda s: s[1])
    return out


def short_name(hlo_text: str) -> str:
    """``%fusion.102 = (u32[1]...) fusion(...), kind=kLoop`` ->
    ``fusion.102``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


# ----------------------------------------------------------- arithmetic


def union_ns(intervals) -> float:
    """Total length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times(ops):
    """Each op's duration less the part its children cover (an op that
    holds others, as a ``while`` holds its body, is on the same line as
    they are). Returns [(op, self_ns, holds_others)]."""
    ops = sorted(ops, key=lambda o: (o[2], -o[3]))
    stack, child = [], []
    out = []

    def close():
        op, used = stack.pop(), child.pop()
        out.append((op, max(op[3] - used, 0.0), used > 0))
        if child:
            child[-1] += op[3]

    for op in ops:
        # a child lies wholly inside its parent; an op that only overlaps
        # the one before it (an asynchronous collective) is its sibling
        while stack and op[2] + op[3] > stack[-1][2] + stack[-1][3]:
            close()
        stack.append(op)
        child.append(0.0)
    while stack:
        close()
    return out


def gaps_ns(intervals):
    """The idle gaps between merged intervals: [(start, end), ...]."""
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def reduce(trace: dict) -> dict:
    """Per device and averaged over devices: the window (first op's start
    to last op's end), busy time (union of op intervals), seconds per
    scope (self times), seconds in collectives and the part of them
    during which no other op runs, and the programs' intervals."""
    devs = []
    for d in trace["devices"]:
        ops = d["ops"]
        if not ops:
            continue
        spans = [(o[2], o[2] + o[3]) for o in ops]
        start = min(s for s, _ in spans)
        end = max(e for _, e in spans)
        scope_ns: dict = {}
        op_ns: dict = {}
        leaves = []
        for op, self_ns, holds_others in self_times(ops):
            if not holds_others:
                leaves.append(op)
            if self_ns <= 0:
                continue
            op_ns[op[0]] = op_ns.get(op[0], 0.0) + self_ns
            if op[1]:
                scope_ns[op[1]] = scope_ns.get(op[1], 0.0) + self_ns
        coll = [(o[2], o[2] + o[3]) for o in ops if COLLECTIVE.search(o[0])]
        others = [(o[2], o[2] + o[3]) for o in leaves
                  if not COLLECTIVE.search(o[0])]
        coll_ns = union_ns(coll)
        exposed_ns = (union_ns(coll + others) - union_ns(others)
                      if coll else 0.0)
        devs.append({
            "name": d["name"], "start_ns": start, "end_ns": end,
            "window_s": (end - start) / 1e9,
            "busy_s": union_ns(spans) / 1e9,
            "scope_s": {k: v / 1e9 for k, v in scope_ns.items()},
            "op_s": {k: v / 1e9 for k, v in op_ns.items()},
            "collective_s": coll_ns / 1e9,
            "collective_exposed_s": exposed_ns / 1e9,
            "gaps_ns": gaps_ns(spans),
            "modules": d["modules"]})
    n = len(devs)
    return {
        "devices": devs, "host": trace["host"],
        "busy_s": sum(d["busy_s"] for d in devs) / n if n else 0.0,
        "window_s": sum(d["window_s"] for d in devs) / n if n else 0.0,
    }


def require_device_work(reduced: dict, n_devices: int) -> None:
    """A traced run on the chip in which no op ran on some device, or in
    which no scope was found, is an error and not a zero."""
    if len(reduced["devices"]) != n_devices or reduced["busy_s"] <= 0:
        raise RuntimeError(
            f"the trace shows device work on {len(reduced['devices'])} of "
            f"{n_devices} devices")
    for d in reduced["devices"]:
        if not d["scope_s"]:
            raise RuntimeError(f"no dint.<engine>.<wave> scope found on "
                               f"{d['name']}: the reduction cannot name "
                               "the waves")


def mean_over_devices(reduced: dict, key: str, sub: str | None = None):
    """Mean of one of ``reduce``'s per-device numbers. A scope that is
    missing on a device is a KeyError: an error, not a zero."""
    vals = [d[key] if sub is None else d[key][sub]
            for d in reduced["devices"]]
    return sum(vals) / len(vals)


def traced(ctx: dict):
    """A reader's view of the run's reduced trace, or None where there is
    nothing to read (no traced run, or no device plane)."""
    tr = ctx["trace"]
    return tr if tr and tr["devices"] and tr["window_s"] else None


def busy_ms_per_step(ctx: dict):
    tr = traced(ctx)
    return tr and tr["busy_s"] * 1e3 / ctx["steps"]


def idle_share_pct(ctx: dict):
    tr = traced(ctx)
    return tr and 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def block_modules(dev: dict) -> list:
    """The intervals of the program that ran most often on this device in
    the traced window: the block (or step) program."""
    by_name: dict = {}
    for name, s, dur in dev["modules"]:
        by_name.setdefault(name, []).append((s, s + dur))
    if not by_name:
        return []
    return sorted(max(by_name.values(), key=len))


def host_cover(host: list, gap) -> str:
    """Which of the benchmark's host spans covers most of an idle gap."""
    best, best_ns = "none", 0.0
    for name, s, dur in host:
        cover = min(gap[1], s + dur) - max(gap[0], s)
        if cover > best_ns:
            best, best_ns = name.split(".", 1)[1], cover
    return best


def breakdown(reduced: dict, steps: int) -> dict:
    """What the driver copies into the ledger. ``device_ops``: seconds
    per engine step (mean over devices) of every scope found, under its
    own name, then of the heaviest single ops. ``idle_gaps``: the first
    device's longest idle gap under each of the benchmark's host spans,
    in seconds."""
    n = len(reduced["devices"]) * max(steps, 1)
    scopes, ops = {}, {}
    for d in reduced["devices"]:
        for k, v in d["scope_s"].items():
            scopes[k] = scopes.get(k, 0.0) + v / n
        for k, v in d["op_s"].items():
            ops[k] = ops.get(k, 0.0) + v / n
    rank = sorted(scopes.items(), key=lambda kv: -kv[1]) \
        + sorted(ops.items(), key=lambda kv: -kv[1])
    by_host: dict = {}
    for g in reduced["devices"][0]["gaps_ns"]:
        name = host_cover(reduced["host"], g)
        by_host[name] = max(by_host.get(name, 0.0), (g[1] - g[0]) / 1e9)
    idle = sorted(by_host.items(), key=lambda kv: -kv[1])
    return {"device_ops": [list(x) for x in rank[:10]],
            "idle_gaps": [list(x) for x in idle[:10]]}


# ------------------------------------------------------------- describe


def describe(path: str, out=sys.stdout) -> None:
    from jax.profiler import ProfileData

    path = find_xplane(path)
    scopes = op_scopes(path)
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}", file=out)
        scope_of = scopes.get(plane.name, {})
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            for e in events[:6]:
                print(f"    {e.name[:100]!r} start={e.start_ns} "
                      f"dur={e.duration_ns} scope={scope_of.get(e.name)} "
                      f"stats={dict(e.stats)}", file=out)


def cut_fixture(trace: dict, n_programs: int) -> dict:
    """A recorded trace cut down to the first ``n_programs`` executions
    of the block program on each device (and the host spans beside
    them), with the numbers this reduction gives for it: what
    benchmarks/fixtures/*.trace.json hold."""
    first = trace["devices"][0]
    runs = block_modules({"modules": first["modules"]})[:n_programs]
    lo, hi = runs[0][0], runs[-1][1]
    cut = {"devices": [], "host": [
        h for h in trace["host"] if lo - 5e6 <= h[1] <= hi]}
    for d in trace["devices"]:
        cut["devices"].append({
            "name": d["name"],
            "ops": [o for o in d["ops"] if lo <= o[2] and o[2] + o[3] <= hi],
            "modules": [m for m in d["modules"]
                        if lo <= m[1] and m[1] + m[2] <= hi]})
    red = reduce(cut)
    return {"trace": cut, "expected": {
        "n_devices": len(red["devices"]), "busy_s": red["busy_s"],
        "window_s": red["window_s"],
        "scope_s": red["devices"][0]["scope_s"],
        "block_programs": len(block_modules(red["devices"][0])),
        "dispatch_spans": sum(h[0] == "bench.dispatch"
                              for h in cut["host"])}}


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "describe":
        describe(sys.argv[2])
    elif len(sys.argv) == 5 and sys.argv[1] == "fixture":
        with open(sys.argv[4], "w") as f:
            json.dump(cut_fixture(load_xplane(find_xplane(sys.argv[2])),
                                  int(sys.argv[3])), f)
    else:
        sys.exit(__doc__)
