"""The host's side of a dispatch (benchmarks/host_path.py) on traces whose
numbers are known: a hand-made one for the joins, the clock and the self
times, a hand-encoded xplane for the walk from file to events, the old
recorded trace of ``tatp7m-lat`` (the two annotations alone), the
recorded host path of PR 41's chip run, and the six readers with their
manifest entries."""
import copy
import json
import os
import statistics

import pytest

from benchmarks import host_path as hp
from benchmarks import run as bench_run
from benchmarks import trace_reduce as tr

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")
PER_DISPATCH = ("dispatch_call_ms.lat", "launch_lag_ms.lat",
                "completion_lag_ms.lat")
BY_EVENT = {"stats_copy_ms.lat": ("python3/np.asarray(jax.Array)", "after"),
            "launch_alloc_ms.lat": ("main/DeferredTpuAllocator::Allocate",
                                    "before")}
NEW = PER_DISPATCH + ("clock_slack_ms.lat",) + tuple(BY_EVENT)
LATE = (0, 20, 10)      # how much later step i's program begins, in ns


def _hand_made(ids=True, device_clock=0):
    """Three dispatches, 2,000 ns apart, times in ns. The main thread's
    ``bench.dispatch`` holds a jit call that holds an execute; its
    ``bench.fetch`` holds the stats' copy. A launch thread enqueues run
    10 + i at d0 + 650 and waits across the program's start; a
    completion thread runs its callbacks at d0 + 1,100 (+ 40 in step 2).
    The device's program runs 300 ns from d0 + 200 + LATE[i] on the
    device's clock as recorded (``device_clock`` shifts that); a second
    program runs once."""
    main, launcher, completer, modules = [], [], [], []
    for i, late in enumerate(LATE):
        d0 = 1_000 + 2_000 * i
        rid = [10 + i] if ids else []
        main += [["bench.dispatch", d0, 600],
                 ["PjitFunction(block)", d0 + 50, 500],
                 ["Execute", d0 + 100, 300],
                 ["bench.fetch", d0 + 610, 1_290],
                 ["np.asarray(jax.Array)", d0 + 1_500, 350]]
        launcher += [["DoEnqueueProgram", d0 + 650, 30] + rid,
                     ["Wait", d0 + 700, 60]]
        completer.append(["CompleteCallbacks",
                          d0 + 1_100 + (40 if i == 2 else 0), 50] + rid)
        modules.append(["jit_block(1)", d0 + 200 + late + device_clock, 300]
                       + rid)
    modules.append(["jit_other(2)", 2_500 + device_clock, 40] + [99] * ids)
    return {"lines": [{"name": "python3", "events": main},
                      {"name": "launcher/7", "events": launcher},
                      {"name": "completer/9", "events": completer}],
            "modules": modules}


def test_the_two_annotations_alone_leave_the_clock_as_recorded():
    found, note = hp.split(_hand_made(ids=False))
    assert note is None and found["dispatches"] == 3
    assert found["call"] == [600, 600, 600]
    assert found["launch_lag"] == [200, 220, 210]
    assert found["run"] == [300, 300, 300]
    assert found["completion_lag"] == [1_400, 1_380, 1_390]
    assert found["loop_gap"] == [100, 100]
    assert found["cycle"] == [2_000, 2_000]
    # min launch + min completion: every shift in [-200, 1380] is lawful
    assert found["clock_slack_spans"] == found["clock_slack"] == 1_580
    assert found["clock_shift"] == 0
    assert found["set_by"] == {"launch": "bench.dispatch",
                               "completion": "bench.fetch"}
    assert "to_enqueue" not in found
    for a, b in zip(found["launch_lag"], found["completion_lag"]):
        assert a + b == 1_900 - 300         # (f1 - d0) - run, every step


@pytest.mark.parametrize("device_clock", [0, -300, 1_700])
def test_run_ids_set_the_clock_whatever_was_recorded(device_clock):
    """enqueue - m0 = 450, 430, 440 bounds the shift from below,
    callbacks - m1 = 600, 580, 630 from above: [450, 580], the middle
    515; a device clock recorded 300 ns early or 1,700 late (a negative
    lag as recorded) gives the same lags."""
    found, note = hp.split(_hand_made(device_clock=device_clock))
    assert note is None
    assert found["clock_slack_spans"] == 1_580
    assert found["clock_slack"] == 130
    assert found["clock_shift"] == 515 - device_clock
    assert found["set_by"] == {"launch": "launcher/DoEnqueueProgram",
                               "completion": "completer/CompleteCallbacks"}
    assert found["launch_lag"] == [715, 735, 725]
    assert found["completion_lag"] == [885, 865, 875]
    assert found["run"] == [300, 300, 300]
    # the same overhead on the host's clock alone
    assert found["to_enqueue"] == [650, 650, 650]
    assert found["device_round_trip"] == [150, 150, 190]
    assert found["from_complete"] == [800, 800, 760]
    for i in range(3):
        assert (found["launch_lag"][i] + found["completion_lag"][i]
                == found["to_enqueue"][i] + found["device_round_trip"][i]
                + found["from_complete"][i] == 1_600)


def test_self_times_by_line_and_phase():
    found, _ = hp.split(_hand_made())
    ev = found["events"]
    zero = dict.fromkeys(hp.PHASES, 0.0)
    # parents give up what their children on the same line cover
    assert ev["python3/bench.dispatch"] == {**zero, "before": 100}
    assert ev["python3/PjitFunction(block)"] == {**zero, "before": 200}
    assert ev["python3/Execute"] == {**zero, "before": 300}
    assert ev["python3/np.asarray(jax.Array)"] == {**zero, "after": 350}
    # the fetch waits from d0 + 610 through the run (m0 = d0 + 715 + late)
    assert ev["python3/bench.fetch"] == pytest.approx(
        {"before": 115, "during": 300, "after": 525})
    # another thread's events, the thread's id dropped; one that spans
    # the program's start gives each phase its part
    assert ev["launcher/DoEnqueueProgram"] == {**zero, "before": 30}
    assert ev["launcher/Wait"] == pytest.approx(
        {"before": 25, "during": 35, "after": 0})
    assert ev["completer/CompleteCallbacks"] == {**zero, "after": 50}
    assert sum(sum(v.values()) for k, v in ev.items()
               if k.startswith("python3/")) == pytest.approx(1_890)
    assert hp.line_key("pjrt-tpu-tasks/332") == "pjrt-tpu-tasks"
    assert hp.line_key("tf_XLAEigen/-1234") == "tf_XLAEigen"
    assert hp.line_key("python3") == "python3"
    assert hp.line_key("a/b") == "a/b"


def test_the_line_is_in_us_with_both_percentiles():
    found, _ = hp.split(_hand_made())
    line = hp.summary(found)
    assert line["call"] == [0.6, 0.6]
    assert line["launch_lag"] == pytest.approx([0.725, 0.734])
    assert line["completion_lag"] == pytest.approx([0.875, 0.884])
    assert line["loop_gap"] == pytest.approx([0.1, 0.1])
    assert line["clock_slack"] == pytest.approx(0.13)
    assert line["clock_slack_spans"] == pytest.approx(1.58)
    assert line["dispatches"] == 3
    assert list(line["events"])[0] == "python3/bench.fetch"     # largest
    assert len(line["events"]) <= hp.TOP_EVENTS
    json.dumps(line)


def _without_last(trace, name):
    out = copy.deepcopy(trace)
    events = out["lines"][0]["events"]
    del events[max(i for i, e in enumerate(events) if e[0] == name)]
    return out


def _swapped_ids(trace):
    out = copy.deepcopy(trace)
    out["modules"][0][3], out["modules"][1][3] = 11, 10
    return out


def _one_run_early(trace):
    out = copy.deepcopy(trace)
    out["modules"][0][1] -= 200     # enqueue - m0 = 650 > 580
    return out


@pytest.mark.parametrize("trace, says", [
    (_hand_made(ids=False, device_clock=-300), "negative lag"),
    (_hand_made(ids=False, device_clock=1_700), "negative lag"),
    ({"lines": _hand_made()["lines"], "modules": _hand_made()["modules"][1:]},
     "3 bench.dispatch, 3 bench.fetch and 2 runs"),
    (_without_last(_hand_made(), "bench.fetch"), "2 bench.fetch"),
    ({"lines": [], "modules": []}, "0 bench.dispatch"),
    (_swapped_ids(_hand_made()), "join by run_id disagree"),
    (_one_run_early(_hand_made()), "clocks drift"),
], ids=["device-clock-early", "device-clock-late", "a-run-missing",
        "a-fetch-missing", "empty", "ids-of-two-runs-swapped", "drift"])
def test_what_cannot_be_split_is_a_note_and_no_number(trace, says):
    found, note = hp.split(trace)
    assert found is None and says in note


def test_cut_keeps_whole_dispatches_and_only_the_block_program():
    whole = _hand_made()
    two = hp.cut(whole, 2)
    assert [m[3] for m in two["modules"]] == [10, 11]
    assert len(hp.spans_of(two, "bench.dispatch")) == 2
    assert len(hp.spans_of(two, "bench.fetch")) == 2
    assert max(e[1] for ln in two["lines"] for e in ln["events"]) < 4_900
    found, _ = hp.split(two)
    assert found["launch_lag"] == [715, 735]      # [450, 580] still
    assert [m[0] for m in hp.cut(whole)["modules"]] == ["jit_block(1)"] * 3


# ------------------------------------------------- from file to events


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _ld(num, body):          # a length-delimited field
    return _varint(num << 3 | 2) + _varint(len(body)) + body


def _vi(num, n):             # a varint field
    return _varint(num << 3) + _varint(n)


def _plane(name, lines, names, run_id_md=9):
    """An XPlane by hand: ``names`` = {metadata id: event name}; a line
    is (name, [(metadata id, offset_ps, duration_ps, run_id or None)]);
    the stat metadata 9 is ``run_id``."""
    body = _ld(2, name)
    for line_name, events in lines:
        body += _ld(3, _vi(1, 1) + _ld(2, line_name) + b"".join(
            _ld(4, _vi(1, md) + _vi(2, at) + _vi(3, dur)
                + (_ld(4, _vi(1, run_id_md) + _vi(3, rid))
                   if rid is not None else b""))
            for md, at, dur, rid in events))
    for i, text in names.items():
        body += _ld(4, _vi(1, i) + _ld(2, _vi(1, i) + _ld(2, text)))
    body += _ld(5, _vi(1, run_id_md)
                + _ld(2, _vi(1, run_id_md) + _ld(2, b"run_id")))
    return _ld(1, body)


def test_every_host_line_and_the_run_ids_out_of_a_hand_encoded_xplane(
        tmp_path):
    ns = 1_000      # ps
    host = _plane(b"/host:CPU", [
        (b"python3", [(1, 500 * ns, 10 * ns, None),      # before the window
                      (2, 1_000 * ns, 600 * ns, None),
                      (3, 1_610 * ns, 1_290 * ns, None)]),
        (b"launcher/7", [(4, 1_650 * ns, 30 * ns, 10)]),
        (b"completer/9", [(5, 2_100 * ns, 50 * ns, 10),
                          (5, 9_000 * ns, 50 * ns, 11)])],  # after it
        {1: b"warmup", 2: b"bench.dispatch", 3: b"bench.fetch",
         4: b"DoEnqueueProgram", 5: b"CompleteCallbacks"})
    dev0 = _plane(b"/device:TPU:0", [
        (b"XLA Ops", [(1, 1_200 * ns, 300 * ns, None)]),
        (b"XLA Modules", [(2, 1_200 * ns, 300 * ns, 10)])],
        {1: b"%fusion.1 = u32[8]{0} fusion(...)", 2: b"jit_block(1)"})
    dev1 = _plane(b"/device:TPU:1", [
        (b"XLA Modules", [(2, 7_777 * ns, 300 * ns, 10)])],
        {2: b"jit_block(1)"})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(dev1 + host + dev0)
    trace = hp.load(str(path))
    assert trace == {
        "lines": [
            {"name": "python3", "events": [["bench.dispatch", 1_000, 600],
                                           ["bench.fetch", 1_610, 1_290]]},
            {"name": "launcher/7",
             "events": [["DoEnqueueProgram", 1_650, 30, 10]]},
            {"name": "completer/9",
             "events": [["CompleteCallbacks", 2_100, 50, 10]]}],
        "modules": [["jit_block(1)", 1_200, 300, 10]]}
    found, note = hp.split(trace)
    assert note is None and found["clock_shift"] == (450 + 600) / 2
    # no device plane at all: a rehearsal's trace
    path.write_bytes(host)
    assert hp.split(hp.load(str(path)))[0] is None


# ------------------------------------------------ the recorded traces


def _old_fixture():
    """The six steps of PR 28's program: ``load_xplane``'s output, the
    two annotations and device 0's modules."""
    with open(os.path.join(FIXTURES, "tatp7m-lat.v5e.trace.json")) as f:
        fx = json.load(f)["trace"]
    return fx, {"lines": [{"name": "python3", "events": fx["host"]}],
                "modules": fx["devices"][0]["modules"]}


def test_the_old_recorded_trace_splits_as_issue_41_read_it():
    fx, trace = _old_fixture()
    found, note = hp.split(trace)
    assert note is None and found["dispatches"] == 6
    us = 1e3
    assert statistics.median(found["call"]) == pytest.approx(660.7695 * us)
    assert statistics.median(found["launch_lag"]) \
        == pytest.approx(223.8835 * us)
    assert statistics.median(found["completion_lag"]) \
        == pytest.approx(1_302.7215 * us)
    assert (min(found["call"]), max(found["call"])) \
        == pytest.approx((449.88 * us, 955.48 * us))
    assert 14 * us < min(found["loop_gap"]) \
        and max(found["loop_gap"]) < 30 * us
    # nothing but the annotations: the whole overhead is slack
    assert found["clock_slack"] == found["clock_slack_spans"] \
        == pytest.approx(172_912 + 1_280_923)
    assert found["clock_shift"] == 0 and "to_enqueue" not in found
    assert found["set_by"] == {"launch": "bench.dispatch",
                               "completion": "bench.fetch"}
    assert set(found["events"]) == {"python3/bench.dispatch",
                                    "python3/bench.fetch"}
    # per step, what host_overhead_ms.lat takes the median of, to 1 ns
    ctx = {"trace": tr.reduce(fx)}
    overhead = bench_run.load_reader("layer_metrics",
                                     "host_overhead_ms.lat")(ctx)
    both = [a + b for a, b in zip(found["launch_lag"],
                                  found["completion_lag"])]
    assert statistics.median(both) == pytest.approx(overhead * 1e6, abs=1)
    d, f = hp.spans_of(trace, "bench.dispatch"), hp.spans_of(trace,
                                                             "bench.fetch")
    for i, run in enumerate(tr.block_modules(fx["devices"][0])):
        assert both[i] == pytest.approx(
            (f[i][1] - d[i][0]) - (run[1] - run[0]), abs=1)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(FIXTURES, "tatp7m-lat.v5e.hostpath.json")) as f:
        return json.load(f)


def test_the_recorded_host_path_reduces_to_its_known_numbers(recorded):
    """Eight dispatches of PR 41's traced chip run: every host line,
    device 0's modules, what ``split`` gave when it was cut."""
    assert os.path.getsize(os.path.join(
        FIXTURES, "tatp7m-lat.v5e.hostpath.json")) < 400_000
    trace, want = recorded["trace"], recorded["expected"]
    assert recorded["note"] is None and want["dispatches"] == 8
    assert {hp.line_key(ln["name"]) for ln in trace["lines"]} >= {
        "python3", "main", "pjrt-tpu-tasks", "tfrt-non-blocking-queue",
        "futex-default-SDomainT"}
    found, note = hp.split(trace)
    assert note is None
    for k, v in want.items():
        if k == "events":
            assert set(found[k]) == set(v)
            for name, row in v.items():
                assert found[k][name] == pytest.approx(row, abs=1e-6), name
        elif isinstance(v, list):
            assert found[k] == pytest.approx(v, abs=1e-6), k
        else:
            assert found[k] == v, k
    # what the chip's clocks were like: the device's recorded 1.3-1.8 ms
    # early, a negative launch lag in every step, and the run ids bound
    # the shift to a third of a millisecond
    assert all(x - found["clock_shift"] < 0 for x in found["launch_lag"])
    assert 1.3e6 < found["clock_shift"] < 1.8e6
    assert found["clock_slack"] < 0.4e6 < 1.0e6 < found["clock_slack_spans"]
    assert found["set_by"] == {
        "launch": "tfrt-non-blocking-queue/DoEnqueueProgram",
        "completion": "futex-default-SDomainT/CompleteCallbacks"}
    assert min(found["device_round_trip"]) >= found["clock_slack"]
    for event, phase in BY_EVENT.values():
        row = found["events"][event]
        assert row[phase] > 100e3 and sum(row.values()) == row[phase]
    # without the runtime's ids this trace has no lawful reading
    bare = {"lines": [{"name": ln["name"], "events": [e[:3] for e in
                                                      ln["events"]]}
                      for ln in trace["lines"]],
            "modules": [m[:3] for m in trace["modules"]]}
    assert "negative lag" in hp.split(bare)[1]


# ------------------------------------------------- readers and manifest


@pytest.fixture
def ctx(monkeypatch):
    """A traced run's context whose newest trace is the hand-made one."""
    monkeypatch.setattr(hp.part_times, "newest_xplane", lambda: "a.pb")
    monkeypatch.setattr(hp, "load", lambda path: _hand_made())
    return {"trace": {"devices": [{}], "window_s": 1.0}}


def _readers():
    return {n: bench_run.load_reader("layer_metrics", n) for n in NEW}


def test_the_readers_take_their_numbers_from_one_reduction(ctx, capsys):
    got = {n: read(ctx) for n, read in _readers().items()}
    ns = 1e-6       # one ns, in ms
    assert got.pop("launch_alloc_ms.lat") is None
    assert got == pytest.approx({
        "dispatch_call_ms.lat": 600 * ns, "launch_lag_ms.lat": 725 * ns,
        "completion_lag_ms.lat": 875 * ns, "clock_slack_ms.lat": 130 * ns,
        "stats_copy_ms.lat": 350 * ns})
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    split_lines = [x for x in lines if "host_path_us_per_dispatch" in x]
    assert len(split_lines) == 1            # reduced and printed once
    body = split_lines[0]["host_path_us_per_dispatch"]
    assert set(body) == {
        "call", "launch_lag", "run", "completion_lag", "loop_gap", "cycle",
        "to_enqueue", "device_round_trip", "from_complete", "clock_slack",
        "clock_slack_spans", "clock_shift", "set_by", "events",
        "dispatches"}
    # the hand-made trace has no allocator event: a note, not a zero
    assert ["no event main/DeferredTpuAllocator::Allocate" in x["host_path"]
            for x in lines if "host_path" in x] == [True]
    assert ctx["host_path"]["dispatches"] == 3


def test_the_readers_find_their_events_in_the_recorded_host_path(
        recorded, ctx, monkeypatch):
    monkeypatch.setattr(hp, "load", lambda path: recorded["trace"])
    readers = _readers()
    want = recorded["expected"]
    for name, (event, phase) in BY_EVENT.items():
        assert readers[name](ctx) == pytest.approx(
            want["events"][event][phase] / 1e6)
    for name, quantity in zip(PER_DISPATCH,
                              ("call", "launch_lag", "completion_lag")):
        assert readers[name](ctx) == pytest.approx(
            statistics.median(want[quantity]) / 1e6)
    # per step the two lags are host_overhead_ms.lat's (f1 - d0) - run
    d, f = (hp.spans_of(recorded["trace"], n) for n in tr.HOST_SPANS)
    for i, m in enumerate(hp.block_runs(recorded["trace"]["modules"])):
        assert want["launch_lag"][i] + want["completion_lag"][i] \
            == pytest.approx((f[i][1] - d[i][0]) - (m[1] - m[0]), abs=1)
    assert readers["clock_slack_ms.lat"](ctx) \
        == pytest.approx(want["clock_slack"] / 1e6)


def _raise(path):
    raise OSError("truncated")


@pytest.mark.parametrize("case", ["untraced", "rehearsal", "no-trace-file",
                                  "unreadable", "nothing-to-join"])
def test_nothing_to_read_is_none_from_every_reader(case, ctx, monkeypatch,
                                                   capsys):
    if case == "untraced":
        ctx = {"trace": None}
    elif case == "rehearsal":       # a CPU's trace: no device plane
        host = [["bench.dispatch", 0.0, 5.0], ["bench.fetch", 6.0, 5.0]]
        ctx = {"trace": tr.reduce({"devices": [], "host": host})}
    elif case == "no-trace-file":
        monkeypatch.setattr(hp.part_times, "newest_xplane", lambda: None)
    elif case == "unreadable":
        monkeypatch.setattr(hp, "load", _raise)
    else:
        monkeypatch.setattr(hp, "load", lambda path: _hand_made(
            ids=False, device_clock=-300))
    assert [read(ctx) for read in _readers().values()] == [None] * len(NEW)
    notes = [json.loads(x)["host_path"]
             for x in capsys.readouterr().out.splitlines()]
    assert len(notes) == {"unreadable": 1, "nothing-to-join": 1}.get(case, 0)
    assert all(("OSError: truncated" if case == "unreadable"
                else "negative lag") in n for n in notes)


@pytest.mark.parametrize("name", NEW)
def test_each_new_entry_follows_index_47_and_resolves_to_its_reader(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(name)
    assert at == 48 + NEW.index(name)
    assert manifest["per_layer"][at] == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "dispatch",
        "moves": "txn_latency_p50_ms.lat", "workloads": ["tatp7m-lat"]}
    assert bench_run.reader_path("layer_metrics", name) == os.path.join(
        REPO, "benchmarks", "layer_metrics", name + ".py")
    read = bench_run.load_reader("layer_metrics", name)
    assert callable(read) and read.__module__.endswith(
        name.replace(".", "_"))
    assert names[1] == "host_overhead_ms.lat"       # split, not replaced
