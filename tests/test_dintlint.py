"""dintlint: each pass proven live on a deliberately-broken mini step,
silent on the matching safe idiom, suppressible by an allowlist entry —
plus the standing tier-1 gate: the full pass suite over every registered
engine/sharded target must report zero unsuppressed errors.

The broken fixtures are the bug classes the passes exist for:
  * a colliding scatter (no unique_indices, no segment mask),
  * an aliased Pallas kernel whose donated input is read afterwards (and a
    double-aliased one),
  * a jitted call whose donated operand stays live,
  * host callbacks / Python branching on traced data in a "step",
  * a packed stamp cast to int32 and compared signed,
  * ppermutes whose permutation disagrees with the mesh.
"""
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

import dint_tpu.parallel  # noqa: F401 — installs the jax.shard_map shim
from dint_tpu import analysis
from dint_tpu.analysis import allowlist as al
from dint_tpu.analysis import core
from dint_tpu.analysis.targets import TARGETS
from dint_tpu.ops import segments

S = jax.ShapeDtypeStruct
U32 = jnp.uint32
I32 = jnp.int32

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_pass(name, fn, args, mesh_axes=(), protocol=("certified",)):
    tr = core.trace_target(f"fixture/{name}", fn, args, mesh_axes=mesh_axes,
                           protocol=protocol)
    return analysis.PASSES[name](tr)


def codes(findings, severity=None):
    return {f.code for f in findings
            if severity is None or f.severity == severity}


# ------------------------------------------------------------ scatter_race


def test_scatter_race_fires_on_colliding_scatter():
    def bad(tab, idx, v):
        return tab.at[idx].set(v)       # arbitrary idx: duplicate = race

    fs = run_pass("scatter_race", bad,
                  (S((64,), U32), S((8,), I32), S((8,), U32)))
    assert "nonunique-scatter" in codes(fs, "error")


def test_scatter_race_accepts_declared_unique_and_segment_masked():
    def ok_unique(tab, idx, v):
        return tab.at[idx].set(v, mode="drop", unique_indices=True)

    def ok_segmented(tab, kh, kl, v):
        sb = segments.sort_batch(kh, kl)
        return segments.scatter_rows(tab, sb.key_lo.astype(I32), v[sb.perm],
                                     sb.last)   # one writer per key

    fs1 = run_pass("scatter_race", ok_unique,
                   (S((64,), U32), S((8,), I32), S((8,), U32)))
    fs2 = run_pass("scatter_race", ok_segmented,
                   (S((64,), U32), S((8,), U32), S((8,), U32), S((8,), U32)))
    assert not codes(fs1, "error") and not codes(fs2, "error")


# ---------------------------------------------------------------- aliasing


def _inc_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] + 1


def test_aliasing_pallas_use_after_donate():
    def bad(x):
        y = pl.pallas_call(_inc_kernel, out_shape=S(x.shape, x.dtype),
                           input_output_aliases={0: 0}, interpret=True)(x)
        return y + x        # x was updated in place: torn read

    def ok(x):
        y = pl.pallas_call(_inc_kernel, out_shape=S(x.shape, x.dtype),
                           input_output_aliases={0: 0}, interpret=True)(x)
        return y + 1        # only the kernel's output is used

    assert "use-after-donate" in codes(run_pass("aliasing", bad,
                                                (S((8,), U32),)), "error")
    assert not codes(run_pass("aliasing", ok, (S((8,), U32),)), "error")


def test_aliasing_double_aliased_kernel():
    def _add_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] + y_ref[...]

    def bad(x, y):
        return pl.pallas_call(_add_kernel, out_shape=S(x.shape, x.dtype),
                              input_output_aliases={0: 0, 1: 0},
                              interpret=True)(x, y)

    fs = run_pass("aliasing", bad, (S((8,), U32), S((8,), U32)))
    assert "double-alias-output" in codes(fs, "error")


def test_aliasing_pjit_donated_operand_still_live():
    @functools.partial(jax.jit, donate_argnums=0)
    def g(x):
        return x + 1

    def bad(x):
        y = g(x)
        return y + x

    fs = run_pass("aliasing", bad, (S((8,), jnp.float32),))
    assert "use-after-donate" in codes(fs, "error")


# ------------------------------------------------------------------ purity


def test_purity_flags_callbacks_and_debug_print():
    def bad_cb(x):
        return jax.pure_callback(lambda a: np.asarray(a),
                                 S((), jnp.float32), x.sum())

    def warn_dbg(x):
        jax.debug.print("x={x}", x=x.sum())
        return x * 2

    assert "pure_callback" in codes(run_pass("purity", bad_cb,
                                             (S((8,), jnp.float32),)),
                                    "error")
    fs = run_pass("purity", warn_dbg, (S((8,), jnp.float32),))
    assert "debug_callback" in codes(fs, "warning") and not codes(fs, "error")


def test_purity_flags_python_branch_on_traced_data():
    def bad(x):
        if x.sum() > 0:     # concretizes a tracer: host sync + retrace
            return x
        return -x

    fs = run_pass("purity", bad, (S((8,), jnp.float32),))
    assert "untraceable" in codes(fs, "error")


# ------------------------------------------------------------ u64_overflow


def test_u64_flags_stamp_sign_drift_and_signed_compare():
    def bad(step, lane):
        packed = ((step << U32(18)) | lane).astype(I32)
        return packed < 0

    fs = run_pass("u64_overflow", bad, (S((8,), U32), S((8,), U32)))
    assert {"stamp-sign-drift", "signed-stamp-compare"} <= codes(fs, "error")


def test_u64_accepts_masked_convert():
    def ok(step, lane):
        # masked below 2^31 before the convert: the repo's bucket-index idiom
        packed = (((step << U32(18)) | lane) & U32(0x3FFFF)).astype(I32)
        return packed < 0

    assert not run_pass("u64_overflow", ok, (S((8,), U32), S((8,), U32)))


# ------------------------------------------------------ shard_consistency


def _mesh4():
    from dint_tpu.parallel.sharded import make_mesh
    assert len(jax.devices()) >= 4
    return make_mesh(4)


def test_shard_consistency_flags_bad_perms():
    mesh = _mesh4()

    def dup_dest(x):
        return jax.lax.ppermute(x, "shard", [(0, 1), (2, 1)])

    def out_of_range(x):
        return jax.lax.ppermute(x, "shard", [(0, 7)])

    def ok(x):
        return jax.lax.ppermute(x, "shard",
                                [(i, (i + 1) % 4) for i in range(4)])

    def sm(body):
        return jax.shard_map(body, mesh=mesh, in_specs=P("shard"),
                             out_specs=P("shard"))

    arg = (S((8, 4), jnp.float32),)
    assert "perm-duplicate-dest" in codes(
        run_pass("shard_consistency", sm(dup_dest), arg), "error")
    assert "perm-out-of-range" in codes(
        run_pass("shard_consistency", sm(out_of_range), arg), "error")
    assert not codes(run_pass("shard_consistency", sm(ok), arg), "error")


# ---------------------------------------------------------------- protocol
#
# Mutated-engine fixtures for the dataflow pass: a miniature step-stamped
# OCC engine under lax.scan (so facts must flow around the carry exactly
# like the real pipelines' cohort contexts) with one protocol edge
# deliberately severed per variant, and a mini explicit-release 2PL
# engine plus a mini replicated shard step for the other two invariants.


def _mini_occ_args():
    W, N = 8, 32
    return (S((N + 1,), U32), S((N + 1,), U32), S((N + 1,), U32), S((), U32),
            S((W,), I32), S((W,), U32), S((W,), jnp.bool_), S((3, W), I32))


def _mini_occ(variant: str):
    """Step-stamped OCC mini engine: acquire (scatter-max of step<<K),
    validate (meta re-read vs snapshot), install (mask descends from the
    surviving-txn chain alive & ~changed, like the real pipelines).
    `variant` severs one edge: "drop_lock" installs on validation alone,
    "drop_validate" installs on the grant alone."""
    W, N, KB = 8, 32, 8

    def fn(tab, meta, arb, step, c_rows, c_snap, c_alive, xs_rows):
        def body(carry, rows):
            tab, meta, arb, step, c_rows, c_snap, c_alive = carry
            # wave 3 of the in-flight cohort: validate then install
            cur = meta[c_rows]
            valid = cur == c_snap                      # VALIDATED seed
            changed = (~valid)[:, None].any(axis=1)    # ABORT_MASK seed
            if variant == "drop_lock":
                mask = ~changed
            elif variant == "drop_validate":
                mask = c_alive
            else:
                mask = c_alive & ~changed
            widx = jnp.where(mask, c_rows, N + 1)
            meta2 = meta.at[widx].set(cur + U32(1), mode="drop",
                                      unique_indices=True)
            tab2 = tab.at[widx].set(c_rows.astype(U32), mode="drop",
                                    unique_indices=True)
            # wave 1 of a new cohort: expiring-stamp lock arbitration
            lane = jnp.arange(W, dtype=U32)
            packed = (step << U32(KB)) | (U32(W) - lane)
            held = (arb[rows] >> U32(KB)) == step - U32(1)
            cand = ~held
            arb2 = arb.at[jnp.where(cand, rows, N + 1)].max(
                packed, mode="drop")
            grant = cand & (arb2[rows] == packed)      # LOCK_WIN seed
            rejected = (~grant)[:, None].any(axis=1)   # ABORT_MASK seed
            alive = grant & ~rejected
            snap = meta2[rows]
            carry = (tab2, meta2, arb2, step + U32(1), rows, snap, alive)
            return carry, (changed | rejected).sum(dtype=jnp.int32)

        carry = (tab, meta, arb, step, c_rows, c_snap, c_alive)
        return jax.lax.scan(body, carry, xs_rows)

    return fn


def _mini_2pl(release: bool):
    """Explicit-release mini 2PL engine: first-lane-wins arbitration over
    a bool lock array (no step stamp — locks are sticky), validation,
    install. ``release=True`` adds the release wave clearing EVERY
    granted lock (committed or aborted); False models "return early past
    the unlock wave": the only lock write left is the grant."""
    W, N = 8, 32
    BIG = jnp.int32(1 << 30)

    def fn(tab, lock, c_rows, c_snap, c_grant, xs_rows):
        def body(carry, rows):
            tab, lock, c_rows, c_snap, c_grant = carry
            cur = tab[c_rows]
            valid = cur == c_snap                      # VALIDATED seed
            changed = (~valid)[:, None].any(axis=1)    # ABORT_MASK seed
            commit = c_grant & ~changed
            widx = jnp.where(commit, c_rows, N + 1)
            tab2 = tab.at[widx].set(cur + U32(1), mode="drop",
                                    unique_indices=True)
            lock2 = lock
            if release:
                # the release mask is `granted` — commits AND aborts —
                # so it legitimately does NOT depend on the abort bit
                ridx = jnp.where(c_grant, c_rows, N + 1)
                lock2 = lock.at[ridx].set(False, mode="drop",
                                          unique_indices=True)
            # new cohort: first-lane-wins acquire on the lock array
            lane = jnp.arange(W, dtype=I32)
            first = jnp.full((N + 1,), BIG, I32).at[rows].min(
                lane, mode="drop")
            free = ~lock2[rows]
            grant = free & (first[rows] == lane)       # LOCK_WIN seed
            gidx = jnp.where(grant, rows, N + 1)
            lock3 = lock2.at[gidx].set(True, mode="drop",
                                       unique_indices=True)
            snap = tab2[rows]
            carry = (tab2, lock3, rows, snap, grant)
            return carry, changed.sum(dtype=jnp.int32)

        return jax.lax.scan(body, (tab, lock, c_rows, c_snap, c_grant),
                            xs_rows)

    return fn


def _mini_repl(variant: str):
    """Mini replicated shard step under shard_map: install locally, then
    ("ok") ppermute the record to the +1 neighbor and apply it to the
    backup slice; "no_push" installs without any collective; "drop_push"
    ppermutes but applies the LOCAL record to the backup instead."""
    mesh = _mesh4()
    perm = [(i, (i + 1) % 4) for i in range(4)]

    def body(bal, bck, rows, vals, mask):
        bal, bck, rows, vals, mask = (x[0] for x in
                                      (bal, bck, rows, vals, mask))
        N = bal.shape[0] - 1
        widx = jnp.where(mask, rows, N)
        bal2 = bal.at[widx].set(vals, mode="drop", unique_indices=True)
        if variant == "no_push":
            f_rows, f_vals, f_mask = rows, vals, mask
        else:
            pp = functools.partial(jax.lax.ppermute, axis_name="shard",
                                   perm=perm)
            f_rows, f_vals, f_mask = pp(rows), pp(vals), pp(mask)
            if variant == "drop_push":
                f_rows, f_vals, f_mask = rows, vals, mask
        bidx = jnp.where(f_mask, f_rows, N)
        bck2 = bck.at[bidx].set(f_vals, mode="drop", unique_indices=True)
        return bal2[None], bck2[None]

    def fn(bal, bck, rows, vals, mask):
        sm = jax.shard_map(body, mesh=mesh,
                           in_specs=(P("shard"),) * 5,
                           out_specs=(P("shard"),) * 2)
        return sm(bal, bck, rows, vals, mask)

    return fn


def _repl_args():
    return (S((4, 33), U32), S((4, 33), U32), S((4, 8), I32),
            S((4, 8), U32), S((4, 8), jnp.bool_))


@pytest.mark.parametrize("variant,code", [
    ("drop_lock", "unlocked-install"),
    ("drop_validate", "unvalidated-install"),
])
@pytest.mark.lint
def test_protocol_occ_fixtures_fire(variant, code):
    fs = run_pass("protocol", _mini_occ(variant), _mini_occ_args(),
                  protocol=("certified", "occ"))
    assert code in codes(fs, "error"), [str(f) for f in fs]
    # each severed edge trips exactly its own invariant, not its sibling
    other = ({"unlocked-install", "unvalidated-install"} - {code}).pop()
    assert other not in codes(fs, "error")


@pytest.mark.lint
def test_protocol_safe_occ_engine_clean():
    fs = run_pass("protocol", _mini_occ("safe"), _mini_occ_args(),
                  protocol=("certified", "occ"))
    assert not codes(fs, "error"), [str(f) for f in fs]


@pytest.mark.lint
def test_protocol_abort_unlock_fixture():
    args = (S((33,), U32), S((33,), jnp.bool_), S((8,), I32), S((8,), U32),
            S((8,), jnp.bool_), S((3, 8), I32))
    broken = run_pass("protocol", _mini_2pl(release=False), args)
    assert "abort-leaks-lock" in codes(broken, "error"), \
        [str(f) for f in broken]
    safe = run_pass("protocol", _mini_2pl(release=True), args)
    assert "abort-leaks-lock" not in codes(safe, "error"), \
        [str(f) for f in safe]


@pytest.mark.parametrize("variant,code", [
    ("no_push", "no-replication-push"),
    ("drop_push", "push-not-applied"),
])
@pytest.mark.lint
def test_protocol_replication_fixtures_fire(variant, code):
    fs = run_pass("protocol", _mini_repl(variant), _repl_args(),
                  protocol=("replicated",))
    assert code in codes(fs, "error"), [str(f) for f in fs]


@pytest.mark.lint
def test_protocol_safe_replication_clean():
    fs = run_pass("protocol", _mini_repl("ok"), _repl_args(),
                  protocol=("replicated",))
    assert not codes(fs, "error"), [str(f) for f in fs]


@pytest.mark.lint
@pytest.mark.parametrize("target", [
    "tatp_dense/block",            # dense OCC
    "tatp_pipeline/block",         # generic sort-based OCC
    "smallbank_dense/block",       # 2PL expiring stamps
    "dense_sharded/block",         # OCC + ICI replication
])
def test_protocol_clean_on_real_engines(target):
    """Safe-idiom controls: the dense and pipeline variants of
    the real engines satisfy every protocol check through genuine
    dataflow (no allowlist involved)."""
    fs = analysis.run(targets=[target], passes=["protocol"])
    assert not [str(f) for f in fs if f.severity == "error"]


@pytest.mark.lint
def test_protocol_dense_installs_prove_lock_and_validate():
    """The interprocedural claim itself: the flagship engine's install
    scatters carry LOCK_WIN *and* VALIDATED — facts seeded at the grant
    compare / validate compare and flowed around two scan-carry hops —
    without leaning on the segment-sort evidence ladder."""
    from dint_tpu.analysis import dataflow as df
    trace = analysis.get_trace("tatp_dense/block")
    flow = df.analyze(trace)
    installs = [r for r in flow.scatters
                if r.prim == "scatter" and r.is_state and not r.in_pallas]
    assert installs
    for r in installs:
        assert df.LOCK_WIN in r.write_facts, r.site
        assert df.VALIDATED in r.write_facts, r.site
        assert df.SORTED not in r.write_facts, r.site


# --------------------------------------------------------------- allowlist


def _broken_scatter_findings():
    def bad(tab, idx, v):
        return tab.at[idx].set(v)

    return run_pass("scatter_race", bad,
                    (S((64,), U32), S((8,), I32), S((8,), U32)))


def test_allowlist_suppresses_matched_finding(tmp_path):
    path = tmp_path / "allow.json"
    path.write_text(json.dumps([
        {"pass": "scatter_race", "code": "nonunique-scatter",
         "target": "fixture/scatter_race",
         "reason": "fixture: uniqueness proven by the test harness"}]))
    fs = al.apply(_broken_scatter_findings(), al.load(str(path)))
    assert not analysis.has_errors(fs)
    assert any(f.suppressed for f in fs)     # visible, flagged, not hidden


def test_allowlist_requires_reason_and_reports_stale_entries(tmp_path):
    bad = tmp_path / "noreason.json"
    bad.write_text(json.dumps([{"pass": "x", "code": "y"}]))
    with pytest.raises(al.AllowlistError):
        al.load(str(bad))

    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps([
        {"pass": "scatter_race", "code": "no-such-code",
         "reason": "matches nothing"}]))
    fs = al.apply(_broken_scatter_findings(), al.load(str(stale)))
    assert "unused-entry" in codes(fs, "warning")
    assert analysis.has_errors(fs)           # the real finding stays fatal


def test_allowlist_mismatch_does_not_suppress(tmp_path):
    path = tmp_path / "other.json"
    path.write_text(json.dumps([
        {"pass": "scatter_race", "code": "nonunique-scatter",
         "target": "some/other-target", "reason": "scoped elsewhere"}]))
    fs = al.apply(_broken_scatter_findings(), al.load(str(path)),
                  check_unused=False)
    assert analysis.has_errors(fs)


def _broken_findings(pname):
    """Fresh findings from the canonical broken fixture of each pass."""
    if pname == "scatter_race":
        return _broken_scatter_findings()
    if pname == "aliasing":
        def bad(x):
            y = pl.pallas_call(_inc_kernel, out_shape=S(x.shape, x.dtype),
                               input_output_aliases={0: 0},
                               interpret=True)(x)
            return y + x
        return run_pass("aliasing", bad, (S((8,), U32),))
    if pname == "purity":
        def bad(x):
            return jax.pure_callback(lambda a: np.asarray(a),
                                     S((), jnp.float32), x.sum())
        return run_pass("purity", bad, (S((8,), jnp.float32),))
    if pname == "u64_overflow":
        def bad(step, lane):
            return ((step << U32(18)) | lane).astype(I32) < 0
        return run_pass("u64_overflow", bad, (S((8,), U32), S((8,), U32)))
    if pname == "shard_consistency":
        def body(x):
            return jax.lax.ppermute(x, "shard", [(0, 1), (2, 1)])
        sm = jax.shard_map(body, mesh=_mesh4(), in_specs=P("shard"),
                           out_specs=P("shard"))
        return run_pass("shard_consistency", sm, (S((8, 4), jnp.float32),))
    if pname == "protocol":
        return run_pass("protocol", _mini_occ("drop_lock"),
                        _mini_occ_args(), protocol=("certified", "occ"))
    if pname == "cost_budget":
        # a registered dispatch budget of 0 turns any memory op into a
        # regression; the full gate lives in tests/test_dintcost.py
        from dint_tpu.analysis import targets as T

        def bad(tab, idx, v):
            return tab.at[idx].set(v, mode="drop", unique_indices=True)
        T.TARGET_COST["fixture/cost_budget"] = {
            "steps": 1.0, "geom": {}, "wave_expect": {},
            "budget": {"dispatches": 0, "bytes": None, "footprint": None}}
        try:
            return run_pass("cost_budget", bad,
                            (S((64,), U32), S((8,), I32), S((8,), U32)))
        finally:
            T.TARGET_COST.pop("fixture/cost_budget", None)
    if pname == "durability":
        # the canonical broken durability fixture (an engine that
        # installs certified writes with no log append) lives with the
        # rest of the dintdur fixtures
        import test_dintdur
        return test_dintdur.broken_wal_order_findings()
    if pname == "calib_check":
        # the canonical broken calibration fixture (hand-edited
        # coefficient => unfit-model + stale-provenance) lives with the
        # rest of the dintcal fixtures
        import test_dintcal
        return test_dintcal.broken_calib_findings()
    if pname == "mut_check":
        # the canonical broken mutation fixture (a killed cell flipped
        # to survived => stale-provenance + survivor) lives with the
        # rest of the dintmut fixtures
        import test_dintmut
        return test_dintmut.broken_mutcov_findings()
    raise AssertionError(pname)


@pytest.mark.parametrize("pname", sorted(analysis.PASSES))
def test_every_pass_fires_and_is_allowlist_silenceable(pname, tmp_path):
    """Acceptance contract: each registered pass is proven live by a
    deliberately-broken fixture that FAILS the lint, and a scoped
    allowlist entry silences exactly that failure."""
    findings = _broken_findings(pname)
    assert analysis.has_errors(findings), f"{pname} fixture did not fire"

    path = tmp_path / "allow.json"
    path.write_text(json.dumps([
        {"pass": pname, "code": "*", "target": f"fixture/{pname}",
         "reason": "test fixture: violation is constructed on purpose"}]))
    fs = al.apply(_broken_findings(pname), al.load(str(path)),
                  check_unused=False)
    assert not analysis.has_errors(fs)
    assert any(f.suppressed for f in fs)


# ------------------------------------------------------------ tier-1 gate


ALLOW = os.path.join(REPO, "tools", "dintlint_allow.json")


@pytest.mark.lint
@pytest.mark.parametrize("target", sorted(TARGETS))
def test_dintlint_gate(target):
    """The standing CI gate, one case a target: every pass, repo allowlist
    applied — zero unsuppressed errors."""
    findings = analysis.run(targets=[target], allowlist_path=ALLOW)
    errors = [str(f) for f in findings
              if f.severity == "error" and not f.suppressed]
    assert not errors, "dintlint gate failed:\n" + "\n".join(errors)


@pytest.mark.lint
def test_dintlint_gate_all_targets():
    """The one run over the whole matrix, which alone can see a stale
    allowlist entry (an entry for an untraced target is not stale) —
    and that every target's builder still runs."""
    findings = analysis.run(allowlist_path=ALLOW)
    assert not [str(f) for f in findings if f.code == "unused-entry"]
    assert not [str(f) for f in findings
                if f.code == "target-build-failed"]
    errors = [str(f) for f in findings
              if f.severity == "error" and not f.suppressed]
    assert not errors, "dintlint gate failed:\n" + "\n".join(errors)


@pytest.mark.lint
def test_cli_json_single_target():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dintlint.py"),
         "--target", "tatp_dense/block", "--json", "--time"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["metric"] == "dintlint" and payload["ok"] is True
    # schema-stable keys downstream parsing (bench artifacts) relies on
    for k in ("schema", "targets", "passes", "n_findings", "n_errors",
              "n_suppressed", "findings"):
        assert k in payload
    assert isinstance(payload["schema"], int) and payload["schema"] >= 2
    # --time: per-target trace/pass wall time rides the payload
    t = payload["timing"]["targets"]["tatp_dense/block"]
    assert "trace_s" in t and "protocol" in t["passes"]


@pytest.mark.lint
def test_cli_unknown_names_exit_2_with_registry():
    """Typos exit 2 with the registered names, never a traceback."""
    for args in (["--target", "nope/bad"], ["--all", "--pass", "nope"]):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "dintlint.py"),
             *args],
            capture_output=True, text=True, cwd=REPO, timeout=300)
        assert out.returncode == 2, (out.returncode, out.stderr[-500:])
        assert "Traceback" not in out.stderr
        assert "unknown" in out.stderr and "registered" in out.stderr
        assert "tatp_dense/block" in out.stderr or "protocol" \
            in out.stderr


# ---------------------------------------------------------- prune helpers


def test_allowlist_prune_drops_only_stale_entries(tmp_path):
    """--prune-allowlist semantics at the library level: after apply()
    over findings, prune_entries splits used from stale and save()
    rewrites the file without private bookkeeping keys."""
    path = tmp_path / "allow.json"
    path.write_text(json.dumps([
        {"pass": "scatter_race", "code": "nonunique-scatter",
         "target": "fixture/scatter_race", "reason": "live entry"},
        {"pass": "scatter_race", "code": "no-such-code",
         "reason": "stale entry"}]))
    entries = al.load(str(path))
    al.apply(_broken_scatter_findings(), entries)
    kept, dropped = al.prune_entries(entries)
    assert [e["code"] for e in kept] == ["nonunique-scatter"]
    assert [e["code"] for e in dropped] == ["no-such-code"]
    al.save(str(path), kept)
    rewritten = json.loads(path.read_text())
    assert rewritten == [{"pass": "scatter_race",
                          "code": "nonunique-scatter",
                          "target": "fixture/scatter_race",
                          "reason": "live entry"}]   # `_used` stripped


def _dintlint_main():
    """Load tools/dintlint.py as a module so main() runs in-process and
    the full-matrix prune reuses this process's TraceCache instead of
    re-tracing 36 targets in a subprocess."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_dintlint_cli", os.path.join(REPO, "tools", "dintlint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


@pytest.mark.lint
def test_prune_check_is_a_dry_run_that_fails_on_stale(tmp_path, capsys):
    """--prune-allowlist --check: exit 1 on stale entries WITHOUT
    rewriting the file; without --check the same run prunes and passes.
    This is the CI form — allowlist rot fails the gate instead of
    waiting for someone to remember the manual prune."""
    main = _dintlint_main()
    repo_allow = os.path.join(REPO, "tools", "dintlint_allow.json")
    entries = json.loads(open(repo_allow).read())
    entries.append({"pass": "scatter_race", "code": "no-such-code",
                    "reason": "stale on purpose"})
    path = tmp_path / "allow.json"
    path.write_text(json.dumps(entries))
    before = path.read_text()

    assert main(["--prune-allowlist", "--check",
                 "--allowlist", str(path)]) == 1
    assert path.read_text() == before          # dry-run: NOT rewritten
    out = capsys.readouterr().out
    assert "NOT rewritten" in out and "no-such-code" in out

    assert main(["--prune-allowlist", "--allowlist", str(path)]) == 0
    pruned = json.loads(path.read_text())
    assert [e["code"] for e in entries
            if e["code"] != "no-such-code"] == [e["code"] for e in pruned]

    # and pruning to a clean file means a following --check passes
    assert main(["--prune-allowlist", "--check",
                 "--allowlist", str(path)]) == 0

    with pytest.raises(SystemExit) as exc:     # --check needs the prune
        main(["--check", "--all"])
    assert exc.value.code == 2


# ----------------------------------------------------- tools/dintgate.sh


def test_dintgate_orchestration_smoke(tmp_path):
    """tools/dintgate.sh is ONE entry point for the six standing gates.
    The smoke pins the orchestration — seven invocations (dintcal
    contributes check AND the journal audit) in order through $PYTHON,
    the allowlist-rot dry-runs riding the three matrix gates, the five
    finding gates' SARIF logs merged into one multi-run document, the
    per-stage wall-clock timings JSON line, a failing gate named WITHOUT
    stopping the others — against a millisecond stub; each real gate has
    its own in-depth tests (and the full script runs in CI proper)."""
    import stat
    import textwrap

    calls = tmp_path / "calls.log"
    stub = tmp_path / "fakepy"
    stub.write_text(textwrap.dedent("""\
        #!/bin/sh
        # dintgate's SARIF merge runs "$PY - out in..." — that one is
        # real work, hand it to the actual interpreter
        if [ "$1" = "-" ]; then exec python "$@"; fi
        echo "$*" >> "$CALLS"
        tool=$(basename "$1" .py)
        out=""; prev=""
        for a in "$@"; do
            [ "$prev" = "--sarif" ] && out="$a"
            prev="$a"
        done
        [ -n "$out" ] && printf \\
          '{"version":"2.1.0","runs":[{"tool":{"driver":{"name":"%s"}},"results":[]}]}' \\
          "$tool" > "$out"
        [ "$tool" = dintdur ] && [ "${FAIL_DUR:-0}" = 1 ] && exit 1
        exit 0
        """))
    stub.chmod(stub.stat().st_mode | stat.S_IEXEC)
    script = os.path.join(REPO, "tools", "dintgate.sh")
    env = dict(os.environ, PYTHON=str(stub), CALLS=str(calls))

    merged = tmp_path / "gate.sarif"
    timings = tmp_path / "timings.json"
    r = subprocess.run(["bash", script, "--sarif", str(merged),
                        "--timings", str(timings)],
                       capture_output=True, text=True, env=env,
                       timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "all 6 gates ok" in r.stdout

    lines = calls.read_text().splitlines()
    assert [ln.split()[0].rsplit("/", 1)[-1] for ln in lines] == \
        ["dintlint.py", "dintcost.py", "dintdur.py", "dintmon.py",
         "dintcal.py", "dintcal.py", "dintmut.py"]
    # the three matrix gates carry the allowlist-rot dry-run
    assert "--prune-allowlist --check" in lines[0]
    assert "check --prune-allowlist --check" in lines[1]
    assert "check --prune-allowlist --check" in lines[2]
    assert lines[3].endswith("tests/fixtures/dintmon_counters.json")
    assert os.path.exists(os.path.join(
        REPO, "tests", "fixtures", "dintmon_counters.json"))
    assert "check" in lines[4] and "--sarif" in lines[4]
    assert lines[5].endswith("tests/fixtures/dintcal_journal.jsonl")
    assert os.path.exists(os.path.join(
        REPO, "tests", "fixtures", "dintcal_journal.jsonl"))
    assert "check --quick" in lines[6]       # the dintmut sampled tier

    doc = json.loads(merged.read_text())
    assert doc["version"] == "2.1.0"
    assert sorted(r_["tool"]["driver"]["name"] for r_ in doc["runs"]) \
        == ["dintcal", "dintcost", "dintdur", "dintlint", "dintmut"]

    # the per-stage wall-clock block: one JSON line, mirrored to --timings
    tline = next(ln for ln in r.stdout.splitlines()
                 if ln.startswith('{"metric": "dintgate"'))
    tdoc = json.loads(tline)
    assert tdoc == json.loads(timings.read_text())
    assert [s["gate"] for s in tdoc["stages"]] == \
        ["dintlint", "dintcost", "dintdur", "dintmon", "dintcal",
         "dintcal-audit", "dintmut"]
    assert all(s["ok"] is True and s["wall_s"] >= 0
               for s in tdoc["stages"])
    assert tdoc["total_s"] > 0

    # one failing gate fails the run BY NAME, the rest still execute
    calls.write_text("")
    r = subprocess.run(["bash", script], capture_output=True, text=True,
                       env=dict(env, FAIL_DUR="1"), timeout=120)
    assert r.returncode == 1
    assert "dintgate: FAIL" in r.stdout and "dintdur" in r.stdout
    assert len(calls.read_text().splitlines()) == 7   # no fail-fast
    tdoc = json.loads(next(ln for ln in r.stdout.splitlines()
                           if ln.startswith('{"metric": "dintgate"')))
    assert [s["gate"] for s in tdoc["stages"]
            if s["ok"] is False] == ["dintdur"]

    # unknown flags are a usage error; --help documents the contract
    assert subprocess.run(["bash", script, "--frobnicate"],
                          capture_output=True, timeout=120).returncode == 2
    h = subprocess.run(["bash", script, "--help"], capture_output=True,
                       text=True, timeout=120)
    assert h.returncode == 0 and "dintcal  check+audit" in h.stdout
