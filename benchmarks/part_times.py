"""Device time per part: the level below the waves.

The program wraps the pieces of a wave, and what a step does outside
every wave, in ``jax.named_scope("part.<name>")``
(dint_tpu/monitor/waves.py ``_PARTS``). An op's name stack
(``jit(block)/while/body/closed_call/dint.tatp_dense.install/
part.val_scatter/scatter``) is the stat ``tf_op`` of its XEventMetadata,
the one ``trace_reduce.op_scopes`` reads the wave from. Here the whole
stack is kept: the wave is the FIRST ``dint.<engine>.<wave>`` on it, as in
``trace_reduce``, the part the LAST ``part.<name>``. An op with a wave
and no part keeps its wave; one with neither is ``unnamed``: what XLA
puts in on its own (the loop, copies). Times are ``trace_reduce``'s self
times, so an op that holds others (``while``) is not counted twice.

``read(ctx)`` finds the run's own trace (the newest under
``<checkout>/.bench_trace/``: ``ctx`` carries no path), prints one line
``{"parts_ms_per_step": {wave: {part: ms}}}`` and hands the readers in
``layer_metrics/`` a dict. It returns None where there is no device
plane (a rehearsal), and where there is one and no part at all: a
program from before the parts, or a compile-cache hit on one. A reader
of one part returns None, with a note, where the trace has parts and
not this one. Never a zero for something that was not there (only
``unnamed`` may read 0.0), never an exception that costs the run its
result line.

    python3 -m benchmarks.part_times show <trace dir or .xplane.pb> <steps>
    python3 -m benchmarks.part_times fixture <trace> <n> <steps> <out.json>

``fixture`` cuts the first n executions of the block program (``steps``
engine steps in all) out of a recorded trace, with the numbers this
reduction gives for them."""
from __future__ import annotations

import glob
import json
import os
import re
import sys

from benchmarks import trace_reduce as tr
from benchmarks.trace_reduce import _fields

PART = re.compile(r"part\.([a-z0-9_]+)")
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_trace")
NO_WAVE, NO_PART, UNNAMED = "(no wave)", "(no part)", "unnamed"
NONE_FOUND = ("none found; a compile-cache hit on a program compiled "
              "before the parts were added gives this")


def newest_xplane():
    found = glob.glob(os.path.join(
        TRACE_ROOT, "*", "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def op_stacks(path: str) -> dict:
    """{plane name: {op name: name stack}} for the device planes of an
    xplane: ``trace_reduce.op_scopes``'s walk over the same fields, with
    the whole ``tf_op`` text kept ("" where an op has none)."""
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
            elif num == 4:
                events.append(dict(_fields(val))[2])
            elif num == 5:
                entry = dict(_fields(val))
                stat_names[entry[1]] = bytes(
                    dict(_fields(entry[2]))[2]).decode()
        if not name or not name.startswith(tr.DEVICE_PLANE):
            continue
        stacks = out[name] = {}
        for metadata in events:
            md_name, stack = None, ""
            for num, val in _fields(metadata):
                if num == 2:
                    md_name = bytes(val).decode()
                elif num == 5:
                    stat = dict(_fields(val))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        stack = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            stacks[md_name] = stack
    return out


def load_ops(path: str) -> list:
    """[{"name": plane, "ops": [[op, name stack, start_ns, dur_ns]]}],
    one per device plane, from its line ``XLA Ops``."""
    from jax.profiler import ProfileData

    stacks = op_stacks(path)
    devices = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(tr.DEVICE_PLANE):
            continue
        stack_of = stacks.get(plane.name, {})
        for line in plane.lines:
            if line.name == tr.OPS_LINE:
                devices.append({"name": plane.name, "ops": [
                    [tr.short_name(e.name), stack_of.get(e.name, ""),
                     float(e.start_ns), float(e.duration_ns)]
                    for e in line.events]})
    return sorted(devices, key=lambda d: d["name"])


def names_of(stack: str) -> tuple:
    """(wave, part) of a name stack: the first wave, the last part."""
    wave = tr.SCOPE.search(stack)
    parts = PART.findall(stack)
    return (wave.group(0) if wave else None, parts[-1] if parts else None)


def per_step(devices: list, steps: int):
    """{"by_wave": {wave: {part: ms}}, "parts": {part: ms}, "unnamed":
    ms}: ms per engine step, mean over devices. None where no device ran
    an op; "parts" is empty where no op carries a part."""
    devices = [d for d in devices if d["ops"]]
    if not devices:
        return None
    n = len(devices) * max(steps, 1) * 1e6
    by_wave: dict = {}
    for d in devices:
        for op, self_ns, _ in tr.self_times(d["ops"]):
            wave, part = names_of(op[1])
            row = by_wave.setdefault(wave or NO_WAVE, {})
            key = part or (NO_PART if wave else UNNAMED)
            row[key] = row.get(key, 0.0) + self_ns / n
    parts: dict = {}
    for row in by_wave.values():
        for key, ms in row.items():
            if key not in (NO_PART, UNNAMED):
                parts[key] = parts.get(key, 0.0) + ms
    return {"by_wave": by_wave, "parts": parts,
            "unnamed": by_wave.get(NO_WAVE, {}).get(UNNAMED, 0.0)}


def read(ctx: dict):
    """The parts of this run's traced window, or None where there is
    nothing to read. Reduced and printed once per run: the result is
    kept on ``ctx``, the run's own state, for the next reader."""
    if "parts" not in ctx:
        ctx["parts"] = _read(ctx)
    return ctx["parts"]


def _read(ctx: dict):
    path = newest_xplane() if tr.traced(ctx) else None
    if path is None:
        return None
    try:
        found = per_step(load_ops(path), ctx["steps"])
    except Exception as e:  # noqa: BLE001 — the run keeps its result line
        print(json.dumps({"parts": f"not read: {type(e).__name__}: {e}",
                          "xplane": path}), flush=True)
        return None
    if found is None:
        return None
    if not found["parts"]:
        print(json.dumps({"parts": NONE_FOUND}), flush=True)
        return None
    print(json.dumps({"parts_ms_per_step": found["by_wave"]}), flush=True)
    return found


def part_ms(ctx: dict, part: str):
    """One part's ms per step; None where this trace has no parts, and
    None with a note where it has parts and no op of this one: a part
    renamed, removed or fused away falls silent, it does not read as a
    perfect 0 (``trace_reduce.mean_over_devices`` holds a missing wave
    to the same rule)."""
    found = read(ctx)
    if not found:
        return None
    if part not in found["parts"]:
        print(json.dumps({"parts": f"no op under part.{part} in this "
                          "trace; the metric that reads it is left out"}),
              flush=True)
        return None
    return found["parts"][part]


def unnamed_ms(ctx: dict):
    """What ran under neither a wave nor a part; 0.0 where the trace has
    parts and every op has a name: the one reading for which nothing
    found is the number."""
    found = read(ctx)
    return found and found["unnamed"]


def cut_fixture(path: str, n_programs: int, steps: int) -> dict:
    """The ops of the first ``n_programs`` executions of the block
    program, name stacks kept once in a table, with what ``per_step``
    gives for them."""
    first = tr.load_xplane(path)["devices"][0]
    runs = tr.block_modules(first)[:n_programs]
    lo, hi = runs[0][0], runs[-1][1]
    table: dict = {}
    devices = []
    for d in load_ops(path):
        devices.append({"name": d["name"], "ops": [
            [o[0], table.setdefault(o[1], len(table)), o[2], o[3]]
            for o in d["ops"] if lo <= o[2] and o[2] + o[3] <= hi]})
    fx = {"stacks": list(table), "devices": devices, "steps": steps}
    fx["expected"] = per_step(fixture_ops(fx), steps)
    return fx


def fixture_ops(fx: dict) -> list:
    return [{"name": d["name"], "ops": [
        [o[0], fx["stacks"][o[1]], o[2], o[3]] for o in d["ops"]]}
        for d in fx["devices"]]


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "show":
        print(json.dumps(per_step(load_ops(tr.find_xplane(sys.argv[2])),
                                  int(sys.argv[3])), indent=1))
    elif len(sys.argv) == 6 and sys.argv[1] == "fixture":
        with open(sys.argv[5], "w") as f:
            json.dump(cut_fixture(tr.find_xplane(sys.argv[2]),
                                  int(sys.argv[3]), int(sys.argv[4])), f)
    else:
        sys.exit(__doc__)
