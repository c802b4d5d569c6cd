"""L6 sweep driver: structural smoke over the quick TATP sweep."""
import json
import os

import pytest

import exp


@pytest.mark.slow  # ~48s of compiles on the 1-core tier-1 box
def test_quick_tatp_sweep(tmp_path):
    out = str(tmp_path / "res")
    results = exp.run_all(out, window_s=0.4, quick=True, only="tatp")

    names = sorted(results)
    assert any(n.startswith("tatp_closed_w") for n in names)
    assert any(n.startswith("tatp_open_") for n in names)
    # the wire + colocate points are gated in by `only in name` too
    assert "tatp_wire" in names
    assert any(n.startswith("tatp_colocate_c") for n in names)

    for name, block in results.items():
        # run_point has no error artifacts: a point that fails raises out
        # of run_all, so every point here measured and must carry the
        # full reference metric contract
        assert "error" not in block, (name, block)
        for field in ("throughput", "goodput", "abort_rate", "avg_us",
                      "p50_us", "p99_us", "p999_us"):
            assert field in block, (name, field)
        assert block["goodput"] > 0
        assert block["p99_us"] >= block["p50_us"] >= 0
        if name.startswith(("tatp_closed", "tatp_open")):
            # abort breakdown travels with every pipeline TATP point
            for field in ("ab_lock", "ab_missing", "ab_validate"):
                assert field in block, (name, field)
        # one JSON file per config, written the moment the point landed
        with open(os.path.join(out, f"{name}.json")) as f:
            assert json.load(f) == block
    with open(os.path.join(out, "summary.json")) as f:
        summary = json.load(f)
    assert sorted(summary["configs"]) == names

    # open-loop points record offered vs target load
    op = next(v for k, v in results.items() if k.startswith("tatp_open_"))
    assert op["mode"] == "open"
    assert op["target_rate"] > 0 and op["offered_rate"] > 0


@pytest.mark.slow
def test_quick_serve_mesh_sweep(tmp_path):
    """--only serve_mesh is a preset: it drives the mesh serving plane
    ladder (saturation probe + rate points with mesh/per-host extras)
    and SUPPRESSES the single-device serve legs the bidirectional
    substring filter would otherwise fire."""
    out = str(tmp_path / "res")
    results = exp.run_all(out, window_s=0.3, quick=True, only="serve_mesh")

    names = sorted(results)
    assert "serve_mesh_sat" in names
    assert not any(n.startswith(("serve_tatp", "serve_smallbank"))
                   for n in names), names
    blk = results["serve_mesh_sat"]
    assert "error" not in blk, blk
    assert blk["mesh"]["n_hosts"] >= 3 and blk["mesh"]["n_ici"] >= 1
    assert blk["offered"] == blk["admitted"] + blk["shed"]
    assert sum(h["admitted"] for h in blk["per_host"]) == blk["admitted"]
    sc = blk["serve_counters"]
    assert sc["serve_occupancy_lanes"] == blk["admitted"]
    assert "route_prefetch_lanes" in sc
    assert blk["controller"]["lanes_scale"] == \
        blk["mesh"]["n_hosts"] * blk["mesh"]["n_ici"]
    # the ladder ran past the anchor
    assert any(n.startswith("serve_mesh_r") for n in names)


def test_run_point_raises_instead_of_recording_an_error_artifact(tmp_path):
    """A failed point fails the run: no retry, no backoff, no
    {"error": ...} artifact for a sweep that then exits 0. Points that
    finished before it stay on disk."""
    sink = exp._ResultSink(str(tmp_path))
    exp.run_point(sink, "good", lambda: {"goodput": 1.0})
    calls = []

    def bad():
        calls.append(1)
        raise RuntimeError("device lost")

    with pytest.raises(RuntimeError, match="device lost"):
        exp.run_point(sink, "bad", bad)
    assert calls == [1]                          # ran once, not retried
    assert sorted(os.listdir(tmp_path)) == ["good.json"]
    assert "bad" not in sink
