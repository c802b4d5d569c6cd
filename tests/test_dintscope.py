"""dintscope: wave registry, attribution, the regression gate, exports.

Tier-1 drives the whole timing plane on a CHECKED-IN synthetic profiler
trace (tests/fixtures/dintscope_trace.json — regenerate with
`python tools/dintscope.py synth` after appending to the registry), so
schema stability, every-registered-wave coverage, and the diff gate's
nonzero exit on an injected regression are CI facts, not TPU-day facts.
The named-scope annotations themselves are pinned semantics-neutral:
engine outputs bit-identical with scopes present vs replaced by null
contexts.
"""
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from dint_tpu.monitor import attrib, waves

pytestmark = pytest.mark.scope

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "dintscope_trace.json")
GEOM = {"w": 8192, "r": 2, "l": 3, "vw": 10, "d": 8,
        "lg": 13, "ld": 7, "sl": 8, "dc": 64}
CLI = [sys.executable, os.path.join(REPO, "tools", "dintscope.py")]


def _cli(args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(CLI + args, capture_output=True, text=True,
                          timeout=120, env=env, cwd=REPO, **kw)


# ---------------------------------------------------------------- registry


def test_registry_schema():
    # unique full names, non-empty docs, engines cover all six hot paths
    assert len(set(waves.ALL_WAVES)) == waves.N_WAVES
    assert all(waves.WAVE_DOCS[n] for n in waves.ALL_WAVES)
    for eng in ("tatp_dense", "smallbank_dense", "tatp_pipeline",
                "smallbank_pipeline", "dense_sharded", "dense_sharded_sb"):
        assert waves.WAVES_BY_ENGINE[eng], eng
    # every declared bytes formula evaluates to a positive int at full
    # geometry, and returns None (not garbage) when variables are missing
    for name in waves.ALL_WAVES:
        if waves.WAVE_BYTES[name] is None:
            assert waves.wave_bytes(name, **GEOM) is None
        else:
            b = waves.wave_bytes(name, **GEOM)
            assert isinstance(b, int) and b > 0, name
            assert waves.wave_bytes(name) is None, name   # no vars -> None


def test_scope_rejects_unregistered_wave():
    with pytest.raises(KeyError):
        waves.scope("tatp_dense", "no_such_wave")


def test_scope_annotation_is_semantics_neutral(monkeypatch):
    """Acceptance: engine outputs bit-identical with scopes present vs
    replaced by null contexts — named_scope adds no jaxpr equations.
    There is no switch in the program; the test takes the scopes out
    itself."""
    import contextlib

    import jax

    from dint_tpu.engines import smallbank_dense as sd

    def run_once():
        run, init, drain = sd.build_pipelined_runner(
            512, w=64, cohorts_per_block=2)
        carry = init(sd.create(512))
        carry, stats = run(carry, jax.random.PRNGKey(3))
        db, tail = drain(carry)
        return (np.asarray(stats), np.asarray(tail),
                np.asarray(db.bal), np.asarray(db.x_step))

    a = run_once()
    monkeypatch.setattr(waves, "scope",
                        lambda engine, wave: contextlib.nullcontext())
    sd.build_pipelined_runner.cache.clear()     # not in the memo's key
    b = run_once()
    sd.build_pipelined_runner.cache.clear()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ------------------------------------------------------------- attribution


def test_fixture_covers_every_registered_wave():
    """Acceptance: report on the trace fixture attributes time to EVERY
    registered wave of (at least) the two dense engines and one sharded
    path — the fixture actually covers all engines, so registry growth
    without regenerating it fails here with a actionable message."""
    bd = attrib.report(FIXTURE, geometry=GEOM)
    assert bd["schema"] == attrib.BREAKDOWN_SCHEMA
    assert bd["kind"] == "dintscope_breakdown"
    assert bd["missing"] == [], (
        "fixture does not cover the registry — regenerate it: "
        "python tools/dintscope.py synth")
    for eng in ("tatp_dense", "smallbank_dense", "dense_sharded_sb"):
        for name in waves.WAVES_BY_ENGINE[eng]:
            rec = bd["waves"][name]
            assert rec["ms"] > 0 and rec["slices"] > 0, name
            assert rec["ms_per_step"] > 0, name
    # schema-stable per-wave record
    for rec in bd["waves"].values():
        assert set(rec) == {"ms", "slices", "ms_per_step", "pct",
                            "bytes_per_step", "gbps"}
    # bandwidth appears exactly for formula-carrying waves
    assert bd["waves"]["dint.tatp_dense.install"]["gbps"] is not None
    assert bd["waves"]["dint.tatp_dense.gen"]["gbps"] is None
    # steps inferred from slice counts (no JSONL given): 4 per the fixture
    assert bd["steps"] == 4
    assert bd["unattributed_ms"] > 0          # the filler slices
    assert bd["attributed_ms"] == pytest.approx(
        sum(r["ms"] for r in bd["waves"].values()))


def test_fixture_matches_fresh_synth(tmp_path):
    """Drift guard: the checked-in fixture IS synthesize_trace's output
    (the synthesizer is deterministic — durations derive from registry
    position, no clocks). A registry change that alters synth output
    without regenerating the fixture fails here, not three tests later
    with a confusing coverage message."""
    fresh = str(tmp_path / "synth.json")
    attrib.synthesize_trace(fresh, steps=4)
    with open(FIXTURE) as fa, open(fresh) as fb:
        a, b = json.load(fa), json.load(fb)
    assert a == b, (
        "tests/fixtures/dintscope_trace.json drifted from the "
        "synthesizer — regenerate it: python tools/dintscope.py synth")


def test_attribution_uses_jsonl_steps_and_rates(tmp_path):
    from dint_tpu.monitor import trace as tr

    from dint_tpu.monitor import counters as ctr

    jsonl = str(tmp_path / "run.jsonl")
    with tr.TraceWriter(jsonl, meta={"name": "t"}) as wr:
        for i in range(3):
            c = dict(ctr.zeros_dict(), steps=2, txn_attempted=100,
                     txn_committed=90)
            wr.wave(step=i, t=0.1 * i, dur_s=0.1, batch=100, counters=c)
    bd = attrib.report(FIXTURE, jsonl=jsonl, geometry=GEOM)
    assert bd["steps"] == 6                    # 3 waves x 2 steps each
    assert bd["rates"]["txn_committed_per_s"] == pytest.approx(
        270 / 0.3, rel=1e-6)
    assert 0 < bd["rates"]["abort_rate"] < 1


def test_diff_detects_injected_wave_regression(tmp_path):
    pert = str(tmp_path / "pert.json")
    attrib.synthesize_trace(pert, steps=4,
                            scale={"dint.smallbank_dense.read": 1.8})
    a = attrib.report(FIXTURE, geometry=GEOM)
    b = attrib.report(pert, geometry=GEOM)
    d = attrib.diff_breakdowns(a, b)
    assert not d["ok"]
    kinds = {(r["kind"], r.get("wave")) for r in d["regressions"]}
    assert ("wave", "dint.smallbank_dense.read") in kinds
    # identical breakdowns pass the gate
    assert attrib.diff_breakdowns(a, a)["ok"]
    # thresholds are honored: an 80% bump passes a 100% gate
    assert attrib.diff_breakdowns(a, b, wave_pct=100.0, step_pct=50.0)["ok"]


def test_diff_names_overlap_route_prefetch_regression(tmp_path):
    """Round 18: a regression in the double-buffered mesh exchange — the
    wave that must stay HIDDEN under cohort i's owner waves — is named
    by diff. tools/hw_mesh_serve.sh's overlap A/B stage gates on exactly
    this: overlap that stops overlapping fails loudly, by name."""
    pert = str(tmp_path / "pert.json")
    attrib.synthesize_trace(
        pert, steps=4, scale={"dint.multihost_sb.route_prefetch": 2.0})
    a = attrib.report(FIXTURE, geometry=GEOM)
    b = attrib.report(pert, geometry=GEOM)
    d = attrib.diff_breakdowns(a, b)
    assert not d["ok"]
    assert any(r.get("wave") == "dint.multihost_sb.route_prefetch"
               for r in d["regressions"])


# ------------------------------------------------- the aliased diff gate


def _zero_row():
    return {"ms": 0.0, "slices": 0, "ms_per_step": None, "pct": 0.0,
            "bytes_per_step": None, "gbps": None}


def _taken_over_ab_artifacts():
    """An A/B pair built from the checked-in fixture: A ran every aliased
    wave under its own scope (successors unobserved), B ran them under
    their successors (constituents unobserved, each successor carrying
    its own time plus its constituents') — the equal-work case the
    aliased gate must pass."""
    base = attrib.report(FIXTURE, geometry=GEOM)
    a, b = copy.deepcopy(base), copy.deepcopy(base)
    dsts = sorted(set(attrib.WAVE_ALIASES.values()))
    for dst in dsts:
        srcs = [s for s, d in attrib.WAVE_ALIASES.items() if d == dst]
        for src in srcs:
            a["waves"][src] = dict(a["waves"][src], **{
                k: round(base["waves"][src][k] + base["waves"][dst][k], 6)
                for k in ("ms", "ms_per_step", "pct")})
            a["waves"][src]["slices"] += base["waves"][dst]["slices"]
            b["waves"][src] = _zero_row()
        a["waves"][dst] = _zero_row()
        b["waves"][dst] = dict(
            _zero_row(), slices=a["waves"][srcs[0]]["slices"],
            **{k: round(sum(a["waves"][s][k] for s in srcs), 6)
               for k in ("ms", "ms_per_step", "pct")})
    return a, b


def test_aliased_fold_merges_constituents():
    for src, dst in attrib.WAVE_ALIASES.items():
        assert src in waves.ALL_WAVES and dst in waves.ALL_WAVES
        assert src.split(".")[1] == dst.split(".")[1]   # same engine
    a, b = _taken_over_ab_artifacts()
    d = attrib.diff_breakdowns(a, b)
    assert d["ok"], d["regressions"]
    assert set(d["aliased"]) == set(attrib.WAVE_ALIASES.values())
    rows = {r["wave"]: r for r in d["rows"]}
    for src, dst in attrib.WAVE_ALIASES.items():
        assert src not in rows                  # merged away
        assert rows[dst]["includes"] == sorted(
            s for s, t in attrib.WAVE_ALIASES.items() if t == dst)
        # folding conserves time
        assert abs(rows[dst]["a_ms_per_step"]
                   - rows[dst]["b_ms_per_step"]) < 1e-6
    # symmetric sides never fold
    assert attrib.diff_breakdowns(a, a)["aliased"] == {}
    assert attrib.diff_breakdowns(b, b)["aliased"] == {}
    assert attrib.diff_breakdowns(a, b, alias=False)["aliased"] == {}


def test_aliased_diff_cli_gate_names_regressed_wave(tmp_path):
    """The CLI gate folds the alias map, passes the equal-work A/B, and
    exits 1 NAMING the successor when it regresses past threshold — a
    regression --no-alias provably cannot see (the raw rows have no
    common observed wave)."""
    a, b = _taken_over_ab_artifacts()
    dst = "dint.multihost_sb.route_prefetch"
    b2 = copy.deepcopy(b)
    for k in ("ms", "ms_per_step"):
        b2["waves"][dst][k] = round(b2["waves"][dst][k] * 1.6, 6)
    pa, pb, pb2 = (str(tmp_path / f"{n}.json") for n in ("a", "b", "b2"))
    for p, obj in ((pa, a), (pb, b), (pb2, b2)):
        with open(p, "w") as f:
            json.dump(obj, f)
    c = _cli(["diff", pa, pb])
    assert c.returncode == 0, (c.stdout, c.stderr)
    assert "aliased:" in c.stdout                # the fold is announced
    c = _cli(["diff", pa, pb2, "--json"])
    assert c.returncode == 1, (c.stdout, c.stderr)
    d = json.loads(c.stdout.strip().splitlines()[-1])
    assert any(r.get("wave") == dst for r in d["regressions"])
    assert d["aliased"][dst] == sorted(
        s for s, t in attrib.WAVE_ALIASES.items() if t == dst)
    assert _cli(["diff", pa, pb2, "--no-alias"]).returncode == 0


def test_diff_ignores_sub_noise_waves():
    a = attrib.report(FIXTURE, geometry=GEOM)
    b = json.loads(json.dumps(a))
    name = "dint.tatp_dense.gen"
    # a 10x regression on a wave below min_ms is dispatch noise
    b["waves"][name]["ms_per_step"] = 0.004
    a2 = json.loads(json.dumps(a))
    a2["waves"][name]["ms_per_step"] = 0.0004
    d = attrib.diff_breakdowns(a2, b, min_ms=0.05)
    assert all(r.get("wave") != name for r in d["regressions"])


# ---------------------------------------------------------------- the CLI


def test_report_cli_json_and_artifact(tmp_path):
    out = str(tmp_path / "bd.json")
    c = _cli(["report", FIXTURE, "--geom", "w=8192", "r=2", "l=3",
              "vw=10", "d=8", "--json", "-o", out])
    assert c.returncode == 0, c.stderr
    bd = json.loads(c.stdout.strip().splitlines()[-1])
    assert bd["kind"] == "dintscope_breakdown"
    assert bd["missing"] == []
    with open(out) as f:
        assert json.load(f) == bd


def test_diff_cli_exits_nonzero_naming_regressed_wave(tmp_path):
    """Acceptance: diff against a perturbed fixture fails with a nonzero
    exit naming the regressed wave."""
    pert = str(tmp_path / "pert.json")
    attrib.synthesize_trace(pert, steps=4,
                            scale={"dint.tatp_dense.meta_gather": 2.5})
    c = _cli(["diff", FIXTURE, pert, "--json"])
    assert c.returncode == 1, (c.stdout, c.stderr)
    d = json.loads(c.stdout.strip().splitlines()[-1])
    assert any(r.get("wave") == "dint.tatp_dense.meta_gather"
               for r in d["regressions"])
    # human mode also names it, and self-diff exits 0
    c2 = _cli(["diff", FIXTURE, pert])
    assert c2.returncode == 1
    assert "dint.tatp_dense.meta_gather" in c2.stdout
    assert _cli(["diff", FIXTURE, FIXTURE]).returncode == 0


def test_describe_cli_matches_registry():
    c = _cli(["describe", "--json"])
    assert c.returncode == 0, c.stderr
    d = json.loads(c.stdout.strip().splitlines()[-1])
    assert [wv["name"] for wv in d["waves"]] == list(waves.ALL_WAVES)
    assert sorted(d["engines"]) == sorted(waves.ENGINES)


# -------------------------------------------------- merged timeline export


def test_export_trace_merge_aligns_clocks(tmp_path):
    from dint_tpu.monitor import counters as ctr
    from dint_tpu.monitor import trace as tr

    jsonl = str(tmp_path / "run.jsonl")
    with tr.TraceWriter(jsonl, meta={"name": "merge_test"}) as wr:
        for i in range(2):
            wr.wave(step=i, t=1.0 + 0.5 * i, dur_s=0.5, batch=64,
                    counters=dict(ctr.zeros_dict(), steps=1))
    out = str(tmp_path / "merged.json")
    n = tr.export_chrome_trace(jsonl, out, merge_trace=FIXTURE)
    assert n > 0
    with open(out) as f:
        events = json.load(f)["traceEvents"]
    wave_ev = [e for e in events if str(e.get("name", "")).startswith("wave ")]
    dev_ev = [e for e in events if e.get("ph") == "X"
              and attrib._wave_of(e) is not None]
    assert wave_ev and dev_ev
    # shared clock offset: the first wave lands at the device trace start
    dev_t0 = min(float(e["ts"]) for e in dev_ev)
    assert min(float(e["ts"]) for e in wave_ev) == pytest.approx(dev_t0,
                                                                 abs=1.0)
    # wave slices keep their own pid row (never interleaved with ops)
    assert {e["pid"] for e in wave_ev} == {1000}


def test_export_trace_merge_cli(tmp_path):
    from dint_tpu.monitor import counters as ctr
    from dint_tpu.monitor import trace as tr

    jsonl = str(tmp_path / "run.jsonl")
    with tr.TraceWriter(jsonl) as wr:
        wr.wave(step=0, t=0.0, dur_s=0.1, batch=1,
                counters=dict(ctr.zeros_dict(), steps=1))
    out = str(tmp_path / "merged.json")
    c = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dintmon.py"),
         "export-trace", jsonl, "-o", out, "--merge", FIXTURE, "--json"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO)
    assert c.returncode == 0, c.stderr
    rec = json.loads(c.stdout.strip().splitlines()[-1])
    assert rec["merged"] == FIXTURE and rec["events"] > 0


# ------------------------------------------------- artifact schema hygiene


def test_exp_artifacts_carry_schema_breakdown_and_histogram(tmp_path):
    """Acceptance: sweep artifacts carry "schema" + "breakdown" (explicit
    null when attribution is off) and the latency histogram block next to
    the percentile block — including the open-loop queue/service split."""
    import exp

    out = str(tmp_path / "res")
    results = exp.run_all(out, window_s=0.3, quick=True,
                          only="tatp_closed")
    blocks = [b for b in results.values() if "error" not in b]
    assert blocks
    for b in blocks:
        assert b["schema"] == attrib.ARTIFACT_SCHEMA
        assert "breakdown" in b and b["breakdown"] is None   # no trace dir
        h = b["lat_hist"]
        assert h["n"] > 0 and h["buckets"]
        # Two views of one sample stream must agree where agreement is
        # guaranteed: the mean exactly (both track a sum), and the
        # reservoir's interpolated p50 inside the bucket span bracketing
        # the middle order statistics. A rel=0.10 p50 compare only holds
        # at scale on unimodal samples (test_stats.py) — a short measured
        # window's median can straddle a bimodal steady-state/contended
        # gap, where interpolation and the ceil-rank read legitimately
        # diverge.
        from dint_tpu import stats as dstats
        hist = dstats.LatencyHistogram.from_dict(h)
        assert h["avg_us"] == pytest.approx(b["avg_us"], rel=1e-4,
                                            abs=0.02)
        assert h["p50_us"] == round(hist.quantile(0.5), 2)
        cum = np.cumsum(hist.counts)
        n = hist.n
        lo_rank, hi_rank = (n + 1) // 2, n // 2 + 1
        i_lo = int(np.searchsorted(cum, lo_rank))
        i_hi = int(np.searchsorted(cum, hi_rank))
        lo_edge = 2.0 ** (h["lo_exp"] + i_lo / h["per_octave"])
        hi_edge = 2.0 ** (h["lo_exp"] + (i_hi + 1) / h["per_octave"])
        assert lo_edge * 0.999 <= b["p50_us"] <= hi_edge * 1.001
