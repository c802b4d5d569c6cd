"""Device time per part (benchmarks/part_times.py) on traces whose numbers
are known: hand-made ops for the arithmetic, a hand-encoded xplane for
the walk from file to ops, the chip's recorded parts under
benchmarks/fixtures/, and the three quantities' readers with their six
manifest entries."""
import glob
import json
import os

import pytest

from benchmarks import part_times as pt
from benchmarks import run as bench_run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")
BODY = "jit(block)/while/body/closed_call/"
INSTALL, LOCK = "dint.tatp_dense.install", "dint.tatp_dense.lock"
QUANTITIES = {"val_scatter_ms": "kernels", "monitor_ms": "counter plane",
              "unnamed_ms": "engine step"}


def _hand_made():
    """Two steps of one program on one device, times in ns: a ``while``
    that holds everything, a part nested in another, an op with a wave
    and no part, a copy with no name stack at all."""
    ops = []
    for t0 in (1_000, 12_000):
        ops += [
            ["while.1", "jit(block)/while", t0, 10_000],
            ["fusion.2", BODY + INSTALL + "/part.install_build/add",
             t0 + 100, 500],
            ["fusion.3", BODY + INSTALL + "/part.val_scatter/scatter",
             t0 + 600, 4_000],
            ["fusion.4", BODY + LOCK + "/gather", t0 + 4_600, 1_000],
            ["fusion.5", BODY + "part.validate/part.monitor/reduce_sum",
             t0 + 5_600, 300],
            ["fusion.6", BODY + "part.monitor/scatter-add", t0 + 5_900, 200],
            ["copy.7", "", t0 + 6_100, 400],
        ]
    return [{"name": "/device:TPU:0", "ops": ops}]


def test_last_part_wins_wave_is_kept_and_the_rest_is_unnamed():
    assert pt.names_of(BODY + INSTALL + "/part.a/part.b/scatter") \
        == (INSTALL, "b")
    assert pt.names_of(BODY + LOCK + "/gather") == (LOCK, None)
    assert pt.names_of("jit(block)/while") == (None, None)
    got = pt.per_step(_hand_made(), steps=2)
    ms = 1e-6       # one ns, in ms
    assert got["by_wave"] == {
        pt.NO_WAVE: {pt.UNNAMED: pytest.approx(4_000 * ms),
                     "monitor": pytest.approx(500 * ms)},
        INSTALL: {"install_build": pytest.approx(500 * ms),
                  "val_scatter": pytest.approx(4_000 * ms)},
        LOCK: {pt.NO_PART: pytest.approx(1_000 * ms)}}
    # the while's self time (10,000 less its six children's 6,400) and
    # the copy: 3,600 + 400 a step, nothing counted twice
    assert got["unnamed"] == pytest.approx(4_000 * ms)
    assert got["parts"] == pytest.approx(
        {"install_build": 500 * ms, "val_scatter": 4_000 * ms,
         "monitor": 500 * ms})
    assert sum(v for row in got["by_wave"].values() for v in row.values()) \
        == pytest.approx(10_000 * ms)       # the whole step, once


def test_mean_over_devices_and_nothing_where_no_device_ran():
    one = _hand_made()[0]
    two = {"name": "/device:TPU:1", "ops": [
        [o[0], o[1], o[2], o[3] / 2 if o[0] == "fusion.3" else o[3]]
        for o in one["ops"]]}
    got = pt.per_step([one, two], steps=2)
    assert got["parts"]["val_scatter"] == pytest.approx(3_000e-6)
    assert got["unnamed"] == pytest.approx(5_000e-6)    # the while waits
    assert pt.per_step([], 2) is None
    assert pt.per_step([{"name": "/device:TPU:0", "ops": []}], 2) is None


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


def _xplane(path, with_parts=True):
    """One device plane by hand: stat metadata ``tf_op`` (7) and one
    string kept by reference (300), three ops' metadata, a line ``XLA
    Ops`` with one event each; and a host plane."""
    part = "part.val_scatter/" if with_parts else ""
    scatter = (BODY + INSTALL + "/" + part + "scatter").encode()
    monitor = (BODY + ("part.monitor/" if with_parts else "")
               + "reduce_sum").encode()

    def stat_md(i, name):
        return _ld(5, _vi(1, i) + _ld(2, _vi(1, i) + _ld(2, name)))

    def event_md(i, name, *stats):
        return _ld(4, _vi(1, i) + _ld(2, _vi(1, i) + _ld(2, name)
                                      + b"".join(_ld(5, s) for s in stats)))

    def event(md, offset_ps, dur_ps):
        return _ld(4, _vi(1, md) + _vi(2, offset_ps) + _vi(3, dur_ps))

    line = _ld(3, _vi(1, 1) + _ld(2, b"XLA Ops") + _vi(3, 1_000)
               + event(1, 0, 4_000_000) + event(2, 4_000_000, 500_000)
               + event(3, 4_500_000, 250_000))
    plane = (_vi(1, 3) + _ld(2, b"/device:TPU:0") + line
             + stat_md(7, b"tf_op") + stat_md(300, monitor)
             + event_md(1, b"%fusion.131 = u32[8]{0} fusion(...)",
                        _vi(1, 7) + _ld(5, scatter))
             + event_md(2, b"%fusion.9 = u32[] fusion(...)",
                        _vi(1, 7) + _vi(7, 300))
             + event_md(3, b"%copy.1 = u32[8]{0} copy(...)"))
    host = _ld(2, b"/host:CPU") + event_md(1, b"bench.dispatch")
    with open(path, "wb") as f:
        f.write(_ld(1, plane) + _ld(1, host))
    return scatter.decode(), monitor.decode()


def test_name_stacks_and_times_out_of_a_hand_encoded_xplane(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    scatter, monitor = _xplane(path)
    assert pt.op_stacks(path) == {"/device:TPU:0": {
        "%fusion.131 = u32[8]{0} fusion(...)": scatter,
        "%fusion.9 = u32[] fusion(...)": monitor,
        "%copy.1 = u32[8]{0} copy(...)": ""}}
    (dev,) = pt.load_ops(path)
    assert dev["name"] == "/device:TPU:0"
    assert [(o[0], o[1], o[3]) for o in dev["ops"]] == [
        ("fusion.131", scatter, 4_000.0), ("fusion.9", monitor, 500.0),
        ("copy.1", "", 250.0)]
    got = pt.per_step([dev], steps=1)
    assert got["parts"] == pytest.approx(
        {"val_scatter": 4_000e-6, "monitor": 500e-6})
    assert got["unnamed"] == pytest.approx(250e-6)


def _ctx():
    return {"steps": 1, "trace": {"devices": [{}], "window_s": 1.0}}


def _readers():
    return {q: bench_run.load_reader("layer_metrics", q + ".tput")
            for q in QUANTITIES}


def test_readers_read_the_newest_trace_once(tmp_path, monkeypatch, capsys):
    old = tmp_path / "tatp7m-lat" / "plugins" / "profile" / "a"
    new = tmp_path / "tatp7m-sat" / "plugins" / "profile" / "b"
    old.mkdir(parents=True)
    new.mkdir(parents=True)
    _xplane(str(old / "h.xplane.pb"), with_parts=False)
    _xplane(str(new / "h.xplane.pb"))
    os.utime(old / "h.xplane.pb", (1, 1))
    monkeypatch.setattr(pt, "TRACE_ROOT", str(tmp_path))
    assert pt.newest_xplane() == str(new / "h.xplane.pb")
    ctx = _ctx()
    got = {q: read(ctx) for q, read in _readers().items()}
    assert got == pytest.approx({"val_scatter_ms": 4_000e-6,
                                 "monitor_ms": 500e-6,
                                 "unnamed_ms": 250e-6})
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert len(lines) == 1          # reduced and printed once, not thrice
    assert lines[0]["parts_ms_per_step"][INSTALL]["val_scatter"] \
        == pytest.approx(4_000e-6)


def test_no_part_at_all_is_a_note_and_none_never_a_zero(
        tmp_path, monkeypatch, capsys):
    """What the parent commit's program, or a compile-cache hit on it,
    gives: device work, waves, no part. The metric is left out."""
    where = tmp_path / "tatp7m-sat" / "plugins" / "profile" / "a"
    where.mkdir(parents=True)
    _xplane(str(where / "h.xplane.pb"), with_parts=False)
    monkeypatch.setattr(pt, "TRACE_ROOT", str(tmp_path))
    ctx = _ctx()
    assert [read(ctx) for read in _readers().values()] == [None] * 3
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert lines == [{"parts": pt.NONE_FOUND}]
    assert "compile-cache hit" in pt.NONE_FOUND


def test_a_part_that_is_absent_falls_silent_and_only_unnamed_reads_zero(
        capsys):
    """A trace with parts and none called ``monitor`` (renamed, removed,
    fused into a neighbour): the ``better: lower`` metric that reads it
    is left out with a note, not booked as a perfect 0."""
    ops = [o for o in _hand_made()[0]["ops"]
           if "part.monitor" not in o[1] and o[0] not in ("copy.7",
                                                          "while.1")]
    ctx = {"steps": 2, "parts": pt.per_step(
        [{"name": "/device:TPU:0", "ops": ops}], 2)}
    readers = _readers()
    assert readers["val_scatter_ms"](ctx) == pytest.approx(4_000e-6)
    assert readers["monitor_ms"](ctx) is None
    assert readers["unnamed_ms"](ctx) == 0.0
    (line,) = capsys.readouterr().out.splitlines()
    assert "part.monitor" in json.loads(line)["parts"]


def test_nothing_to_read_is_none_and_a_broken_trace_costs_no_result(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(pt, "TRACE_ROOT", str(tmp_path))
    # no traced run, a rehearsal's trace without a device plane, no file
    for ctx in ({"steps": 1, "trace": None},
                {"steps": 1, "trace": {"devices": [], "window_s": 0.0}},
                _ctx()):
        assert [read(ctx) for read in _readers().values()] == [None] * 3
        assert ctx["parts"] is None
    assert capsys.readouterr().out == ""
    where = tmp_path / "c" / "plugins" / "profile" / "a"
    where.mkdir(parents=True)
    (where / "h.xplane.pb").write_bytes(b"\x0a\xff\xff")     # cut short
    ctx = _ctx()
    assert [read(ctx) for read in _readers().values()] == [None] * 3
    (line,) = capsys.readouterr().out.splitlines()
    assert json.loads(line)["parts"].startswith("not read: ")


def test_the_six_entries_are_appended_and_resolve_by_quantity():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    tail = manifest["per_layer"][-6:]
    assert [m["name"] for m in tail] == [
        q + v for q in QUANTITIES for v in (".tput", ".lat")]
    for m in tail:
        quantity, variant = m["name"].rsplit(".", 1)
        assert m["layer"] == QUANTITIES[quantity]
        assert (m["unit"], m["better"], m["source"]) \
            == ("ms", "lower", "program_span")
        assert m["workloads"] == [
            {"tput": "tatp7m-sat", "lat": "tatp7m-lat"}[variant]]
        assert m["moves"] == {"tput": "committed_txn_per_s",
                              "lat": "txn_latency_p50_ms.lat"}[variant]
        assert bench_run.reader_path("layer_metrics", m["name"]) \
            == os.path.join(REPO, "benchmarks", "layer_metrics",
                            quantity + ".py")


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(FIXTURES, "*.parts.json"))) or [None])
def test_a_recorded_tpu_trace_gives_its_known_parts(path):
    """Each fixture holds the ops of a few steps of a real run with their
    name stacks, and what ``per_step`` gave when it was recorded."""
    if path is None:
        pytest.skip("no parts fixture was cut: no chip run was had")
    with open(path) as f:
        fx = json.load(f)
    got = pt.per_step(pt.fixture_ops(fx), fx["steps"])
    want = fx["expected"]
    assert got["unnamed"] == pytest.approx(want["unnamed"], rel=1e-9)
    assert got["parts"] == pytest.approx(want["parts"], rel=1e-9)
    # the value scatter is most of the install wave, the counter plane
    # and what is left unnamed are small beside the step
    install = got["by_wave"][INSTALL]
    assert install["val_scatter"] > 0.8 * sum(install.values())
    step = sum(v for row in got["by_wave"].values() for v in row.values())
    assert 0 < got["parts"]["monitor"] < 0.1 * step
    assert got["unnamed"] < 0.1 * step
