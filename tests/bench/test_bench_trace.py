"""The reduction from a trace to numbers, on traces whose numbers are
known: a hand-made one for the arithmetic, and a cut-down recorded TPU
trace (benchmarks/fixtures/) for what a real xplane looks like."""
import glob
import json
import os

import pytest

from benchmarks import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "fixtures")
LOCK, INST, REPL = ("dint.tatp_dense.lock", "dint.tatp_dense.install",
                    "dint.dense_sharded.replicate")


def _hand_made():
    """Two executions of one program on one device, times in ns. A
    ``while`` op holds its body's ops; a collective-permute overlaps a
    scatter for 200 of its 500 ns."""
    ops = []
    for t0 in (1_000, 12_000):
        ops += [
            ["while.1", None, t0, 10_000],
            ["gather.2", LOCK, t0 + 100, 2_000],
            ["scatter.3", INST, t0 + 2_100, 3_000],
            ["collective-permute.4", REPL, t0 + 4_900, 500],
            ["scatter.5", REPL, t0 + 5_400, 1_000],
            ["fusion.6", None, t0 + 7_000, 2_000],
        ]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops, "modules": [
        ["jit_block(1)", 1_000, 10_000], ["jit_block(1)", 12_000, 10_000],
        ["jit_other(2)", 500, 100]]}],
        "host": [["bench.dispatch", 0, 300], ["bench.fetch", 300, 10_800],
                 ["bench.dispatch", 11_100, 200],
                 ["bench.fetch", 11_300, 10_900]]}


def test_union_self_times_and_gaps():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert tr.gaps_ns([(0, 10), (5, 20), (30, 40)]) == [(20, 30)]
    selfs = {op[0]: s for op, s, _ in tr.self_times(
        [["outer", None, 0, 100], ["a", None, 10, 20],
         ["inner", None, 40, 50], ["b", None, 50, 10], ["c", None, 200, 5]])}
    assert selfs == {"outer": 30, "a": 20, "inner": 40, "b": 10, "c": 5}


def test_reduce_a_hand_made_trace_to_known_numbers():
    red = tr.reduce(_hand_made())
    dev = red["devices"][0]
    assert dev["window_s"] == pytest.approx(21_000e-9)
    assert dev["busy_s"] == pytest.approx(20_000e-9)   # the while ops
    assert red["busy_s"] == dev["busy_s"]
    assert dev["scope_s"] == pytest.approx(
        {LOCK: 4_000e-9, INST: 6_000e-9, REPL: 3_000e-9})
    # the while's self time: 10,000 less its five children (8,500)
    assert dev["op_s"]["while.1"] == pytest.approx(3_000e-9)
    assert dev["collective_s"] == pytest.approx(1_000e-9)
    # 200 ns of each permute run beside scatter.3
    assert dev["collective_exposed_s"] == pytest.approx(600e-9)
    assert dev["gaps_ns"] == [(11_000, 12_000)]
    assert tr.block_modules(dev) == [(1_000, 11_000), (12_000, 22_000)]
    tr.require_device_work(red, 1)
    with pytest.raises(RuntimeError, match="1 of 4 devices"):
        tr.require_device_work(red, 4)


def test_a_scope_the_reduction_cannot_find_is_an_error_not_a_zero():
    trace = _hand_made()
    for op in trace["devices"][0]["ops"]:
        op[1] = None
    with pytest.raises(RuntimeError, match="no dint"):
        tr.require_device_work(tr.reduce(trace), 1)


def test_breakdown_names_scopes_per_step_and_gaps_by_host_span():
    b = tr.breakdown(tr.reduce(_hand_made()), steps=2)
    ops = dict(b["device_ops"])
    assert ops[INST] == pytest.approx(3_000e-9)      # seconds per step
    assert ops[LOCK] == pytest.approx(2_000e-9)
    assert ops["while.1"] == pytest.approx(1_500e-9)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    # the one gap (11,000-12,000) lies under the first fetch for 100 ns,
    # under the second dispatch for 200 and under the second fetch for 700
    assert b["idle_gaps"] == [["fetch", pytest.approx(1_000e-9)]]


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _ld(num, body):          # a length-delimited field
    return _varint(num << 3 | 2) + _varint(len(body)) + body


def test_op_scopes_reads_the_name_stack_out_of_a_hand_encoded_xplane(
        tmp_path):
    """The wire format by hand: one device plane whose op metadata carry
    ``tf_op`` once as a string and once as a reference, one op without,
    and a host plane that is skipped."""
    stack = b"jit(block)/while/body/dint.tatp_dense.lock/gather"
    tf_op = _ld(5, _varint(1 << 3) + _varint(7)
                + _ld(2, _varint(1 << 3) + _varint(7) + _ld(2, b"tf_op")))
    kept = _ld(5, _varint(1 << 3) + _varint(300) + _ld(
        2, _varint(1 << 3) + _varint(300)
        + _ld(2, b"jit(block)/dint.tatp_dense.install/scatter")))
    other = _ld(5, _varint(1 << 3) + _varint(9)
                + _ld(2, _varint(1 << 3) + _varint(9) + _ld(2, b"flops")))

    def event_md(i, name, *stats):
        return _ld(4, _varint(1 << 3) + _varint(i) + _ld(
            2, _varint(1 << 3) + _varint(i) + _ld(2, name)
            + b"".join(_ld(5, s) for s in stats)))

    def stat(md_id, str_value=None, ref=None, number=None):
        out = _varint(1 << 3) + _varint(md_id)
        if str_value is not None:
            out += _ld(5, str_value)
        if ref is not None:
            out += _varint(7 << 3) + _varint(ref)
        if number is not None:      # a double: fixed 64-bit, skipped
            out += _varint(2 << 3 | 1) + b"\0" * 8
        return out

    plane = (_varint(1 << 3) + _varint(3) + _ld(2, b"/device:TPU:0")
             + _ld(3, b"\x12\x03abc")       # a line: skipped whole
             + tf_op + kept + other
             + event_md(1, b"%gather.7 = u32[8]{0} gather(...)",
                        stat(9, number=1.0), stat(7, str_value=stack))
             + event_md(2, b"%scatter.9 = u32[8]{0} scatter(...)",
                        stat(7, ref=300))
             + event_md(3, b"%copy.1 = u32[8]{0} copy(...)",
                        stat(9, number=2.0)))
    host = _ld(2, b"/host:CPU") + event_md(1, b"bench.dispatch")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_ld(1, plane) + _ld(1, host))
    assert tr.op_scopes(str(path)) == {"/device:TPU:0": {
        "%gather.7 = u32[8]{0} gather(...)": LOCK,
        "%scatter.9 = u32[8]{0} scatter(...)": INST,
        "%copy.1 = u32[8]{0} copy(...)": None}}
    assert tr.short_name("%gather.7 = u32[8]{0} gather(...)") == "gather.7"


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(FIXTURES, "*.trace.json"))) or [None])
def test_reduce_a_recorded_tpu_trace_to_its_known_numbers(path):
    """Each fixture is ``load_xplane``'s output for a few steps of a real
    run, with the numbers the reduction gave when it was recorded."""
    assert path is not None, "no recorded trace under benchmarks/fixtures"
    with open(path) as f:
        fx = json.load(f)
    red = tr.reduce(fx["trace"])
    want = fx["expected"]
    tr.require_device_work(red, want["n_devices"])
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    dev = red["devices"][0]
    # what the scopes name is part of the busy time, and nearly all of it
    assert 0.9 * red["busy_s"] < sum(dev["scope_s"].values()) \
        <= red["busy_s"]
    assert max(dev["scope_s"], key=dev["scope_s"].get) \
        == "dint.tatp_dense.install"
    for scope, seconds in want["scope_s"].items():
        assert dev["scope_s"][scope] == pytest.approx(seconds, rel=1e-9)
    assert len(tr.block_modules(dev)) == want["block_programs"]
    assert sum(n == "bench.dispatch" for n, _, _ in red["host"]) \
        == want["dispatch_spans"]
