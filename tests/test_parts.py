"""Parts: the named level below the waves (monitor/waves.py ``_PARTS``).

A part is ``jax.named_scope("part.<name>")`` round a piece of a wave or
round what a step does outside every wave. Pinned here: the registry's
shape, that every part reaches compiled HLO under its wave, that no
equation of the dense block is left without a wave or a part, that the
names move neither reader of the waves, and that outputs are
bit-identical without them. tests/test_dintcost.py, test_dintcal.py and
test_dintscope.py pass unedited beside this file: the proof that the
per-wave budgets did not move."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import trace_reduce
from dint_tpu import _runtime
from dint_tpu.analysis import cost
from dint_tpu.engines import smallbank_dense as sd
from dint_tpu.engines import store
from dint_tpu.engines import tatp_dense as td
from dint_tpu.monitor import waves
from dint_tpu.parallel import dense_sharded as ds
from dint_tpu.parallel import dense_sharded_sb as dsb
from dint_tpu.tables import kv

pytestmark = pytest.mark.scope

N_SUB, W, CPB, VW = 2000, 64, 2, 10
NAMED = re.compile(r"dint\.[a-z0-9_]+\.[a-z0-9_]+|part\.[a-z0-9_]+")


def _dense(monitor=True):
    run, init, drain = td.build_pipelined_runner(
        N_SUB, w=W, val_words=VW, cohorts_per_block=CPB, monitor=monitor)
    db = td.populate(np.random.default_rng(0), N_SUB, val_words=VW)
    return run, init(db), drain


def _bank(monitor=True):
    run, init, drain = sd.build_pipelined_runner(
        N_SUB, w=W, cohorts_per_block=CPB, monitor=monitor, trace=False)
    return run, init(sd.create(N_SUB, log_capacity=1 << 10)), drain


def _op_names(compiled_text: str) -> set:
    """The name stacks in a compiled module's metadata (XLA joins the
    names of ops it merged with ``;``)."""
    return {n for joined in re.findall(r'op_name="([^"]*)"', compiled_text)
            for n in joined.split(";")}


def _assert_parts_under_their_waves(names: set, owners: tuple,
                                    skip: tuple = ()) -> None:
    """Every registered part of ``owners`` is in some op_name, and where
    the registry gives it a wave, each such name has that wave before
    it."""
    for owner, wave, part, _ in waves._PARTS:
        if not set(waves.PART_OWNERS[part]) & set(owners) or part in skip:
            continue
        here = waves.part_name(part)
        stacks = [n.split("/") for n in names if here in n.split("/")]
        assert stacks, f"{here} is in no op_name"
        for stack in stacks if wave else ():
            assert any(re.fullmatch(rf"dint\.[a-z0-9_]+\.{wave}", s)
                       for s in stack[:stack.index(here)]), "/".join(stack)


def test_part_registry_schema():
    assert len({p for _, _, p, _ in waves._PARTS}) == len(waves._PARTS)
    short = {n.split(".", 2)[2] for n in waves.ALL_WAVES}
    for owner, wave, part, doc in waves._PARTS:
        assert re.fullmatch(r"[a-z0-9_]+", part) and doc, part
        assert wave is None or wave in short, (part, wave)
        owners = waves.PART_OWNERS[part]
        assert owners == ((owner,) if isinstance(owner, str) else owner)
        assert len(set(owners)) == len(owners) >= 1
        for o in owners:
            assert o in waves.ENGINES or o == "log", o
        # a part several engines open lies outside any wave: a wave's
        # name would belong to one of them
        assert len(owners) == 1 or wave is None, part
        # a part's name is read by neither reader of the waves
        name = f"jit(block)/while/body/{waves.part_name(part)}/scatter"
        assert not trace_reduce.SCOPE.search(name)
        assert not cost._WAVE_RE.search(name)


def test_part_rejects_unregistered_name():
    with pytest.raises(KeyError, match="part registry"):
        waves.part("tatp_dense", "no_such_part")
    with pytest.raises(KeyError):
        waves.part("smallbank_dense", "val_scatter")    # another's part
    with pytest.raises(KeyError):
        waves.part("tatp_dense", "lock_arb")


def test_an_engine_neutral_part_is_shared_by_the_dense_engines_alone():
    """... and, since the cell store-ycsb-b, by the KV store's runner,
    which steps a block as they do (engines/store.py), and since the cell
    smallbank24m-x4-sat by the sharded SmallBank's
    (parallel/dense_sharded_sb.py)."""
    for name in ("monitor", "stats", "block_pre"):
        assert waves.PART_OWNERS[name] == ("tatp_dense", "smallbank_dense",
                                           "store", "dense_sharded_sb")
        for owner in waves.PART_OWNERS[name]:
            with waves.part(owner, name):
                pass
        with pytest.raises(KeyError, match="part registry"):
            waves.part("tatp_pipeline", name)
        with pytest.raises(KeyError, match="part registry"):
            waves.part("log", name)


def test_every_dense_part_reaches_compiled_hlo_under_its_wave():
    run, carry, _ = _dense()
    text = run.lower(carry, jax.random.PRNGKey(0)).compile().as_text()
    _assert_parts_under_their_waves(_op_names(text), ("tatp_dense", "log"))


def test_every_smallbank_part_reaches_compiled_hlo_under_its_wave():
    run, carry, _ = _bank()
    text = run.lower(carry, jax.random.PRNGKey(0)).compile().as_text()
    _assert_parts_under_their_waves(_op_names(text),
                                    ("smallbank_dense", "log"))


def test_the_sharded_block_carries_the_dense_steps_parts():
    """The four-device block runs the same `pipe_step`, so its trace
    splits the same way; `replicate` has four parts of its own (below),
    and `append_rep`'s ride under it where a backup appends."""
    d = 4
    mesh = ds.make_mesh(d)
    state = ds.create_sharded(mesh, d, 4 * 512, val_words=4, seed=0)
    run, init, _ = ds.build_sharded_pipelined_runner(
        mesh, d, 4 * 512, w=16, val_words=4, cohorts_per_block=2,
        monitor=True)
    names = _op_names(jax.jit(run).lower(
        init(state), jax.random.PRNGKey(0)).compile().as_text())
    # block_pre is the one-chip runner's prologue
    _assert_parts_under_their_waves(
        {n for n in names if "dint.dense_sharded.replicate" not in n},
        ("tatp_dense", "log"), skip=("block_pre",))
    assert any(re.search(r"dint\.dense_sharded\.replicate/.*"
                         r"part\.log_scatter", n) for n in names)


def _unnamed_equations(jaxpr, named: bool, path: tuple, out: list) -> list:
    """Leaf equations with neither a wave nor a part on their own name
    stack or on that of an equation that holds them (inner jaxprs' name
    stacks are relative to their holder's, as analysis/cost.py's
    ``wave_ctx`` has it)."""
    for eqn in jaxpr.eqns:
        here = named or bool(NAMED.search(str(eqn.source_info.name_stack)))
        inner = [j for v in eqn.params.values()
                 for x in (v if isinstance(v, (list, tuple)) else (v,))
                 for j in (getattr(x, "jaxpr", x),)
                 if hasattr(j, "eqns")]
        for j in inner:
            _unnamed_equations(j, here, path + (eqn.primitive.name,), out)
        if not inner and not here:
            out.append(("/".join(path), eqn.primitive.name,
                        str(eqn.source_info.name_stack)))
    return out


@pytest.mark.parametrize("monitor", [True, False])
def test_every_equation_of_the_dense_block_carries_a_wave_or_a_part(
        monitor):
    """What is left for ``unnamed_ms`` is what XLA inserts on its own:
    the program leaves no equation of the block unnamed (the ``scan``
    equation itself is a holder: its body's equations are the step)."""
    run, carry, _ = _dense(monitor)
    closed = jax.make_jaxpr(run)(carry, jax.random.PRNGKey(0))
    assert _unnamed_equations(closed.jaxpr, False, (), []) == []


@pytest.mark.parametrize("monitor", [True, False])
def test_every_equation_of_the_smallbank_block_carries_a_wave_or_a_part(
        monitor):
    run, carry, _ = _bank(monitor)
    closed = jax.make_jaxpr(run)(carry, jax.random.PRNGKey(0))
    assert _unnamed_equations(closed.jaxpr, False, (), []) == []


def test_parts_are_semantics_neutral(monkeypatch):
    def run_once():
        run, carry, drain = _dense()
        carry, stats = run(carry, jax.random.PRNGKey(3))
        db, tail, counters = drain(carry)
        return [np.asarray(x) for x in (
            stats, tail, counters.buf, db.val, db.meta, db.arb,
            db.log.entries, db.log.head)]

    a = run_once()
    td.build_pipelined_runner.cache.clear()     # not in the memo's key
    monkeypatch.setattr(waves, "part",
                        lambda owner, name: contextlib.nullcontext())
    b = run_once()
    td.build_pipelined_runner.cache.clear()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_smallbank_parts_are_semantics_neutral(monkeypatch):
    def run_once():
        run, carry, drain = _bank()
        carry, stats = run(carry, jax.random.PRNGKey(3))
        db, tail, counters = drain(carry)
        return [np.asarray(x) for x in (
            stats, tail, counters.buf, db.bal, db.x_step, db.s_step,
            db.log.entries, db.log.head)]

    a = run_once()
    sd.build_pipelined_runner.cache.clear()     # not in the memo's key
    monkeypatch.setattr(waves, "part",
                        lambda owner, name: contextlib.nullcontext())
    b = run_once()
    sd.build_pipelined_runner.cache.clear()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def _scoped(name):
    def f(x):
        with jax.named_scope(name):
            return jnp.where(x > 0, x, 0).sum()
    return f


def _lower_here(f, x):
    return jax.jit(f).lower(x).as_text(debug_info=True)


def _lower_there(f, x):
    # another caller, another line: another call stack
    return jax.jit(f).lower(x).as_text(debug_info=True)


def test_compile_cache_key_holds_the_names_and_no_call_stack(monkeypatch):
    """With the metadata in the key a program that differs from a cached
    one only in its named scopes is a miss (without it: a hit that runs
    under the old names). The metadata is the module's locations, the
    text compared here; with no stack frames in them the same ops under
    the same names key alike whoever called them."""
    seen = {}
    update = jax.config.update
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    _runtime.enable_compile_cache()
    monkeypatch.undo()
    assert seen["jax_compilation_cache_include_metadata_in_key"] is True
    limit = "jax_traceback_in_locations_limit"
    before = jax.config.jax_traceback_in_locations_limit
    x = jnp.arange(8.0)
    try:
        update(limit, seen[limit])
        one = _lower_here(_scoped("part.one"), x)
        assert "part.one" in one and __file__ not in one
        assert _lower_there(_scoped("part.one"), x) == one
        assert _lower_here(_scoped("part.two"), x) != one
    finally:
        update(limit, before)
    assert _lower_there(_scoped("part.one"), x) \
        != _lower_here(_scoped("part.one"), x)       # the default: frames


# ------------------------------------------- the parts under `replicate`

REPLICATE = "dint.dense_sharded.replicate"
REPL_PARTS = ("repl_hop", "bck_compact", "bck_meta_scatter",
              "bck_val_scatter", "bck_log_append")


def _sharded(d=4, monitor=True):
    mesh = ds.make_mesh(d)
    state = ds.create_sharded(mesh, d, 4 * 512, val_words=4, seed=0)
    run, init, drain = ds.build_sharded_pipelined_runner(
        mesh, d, 4 * 512, w=16, val_words=4, cohorts_per_block=2,
        monitor=monitor)
    return run, init(state), drain


def test_the_replicate_parts_are_registered_under_their_wave():
    rows = {p: (o, w) for o, w, p, _ in waves._PARTS}
    for part in REPL_PARTS:
        assert rows[part] == ("dense_sharded", "replicate")
        assert waves.PART_OWNERS[part] == ("dense_sharded",)
    with pytest.raises(KeyError, match="part registry"):
        waves.part("tatp_dense", "repl_hop")


def _equations_under(jaxpr, wave: str, stack: str, out: list) -> list:
    """(primitive, full name stack) of every leaf equation whose stack,
    its holders' included, carries ``wave``."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        inner = [j for v in eqn.params.values()
                 for x in (v if isinstance(v, (list, tuple)) else (v,))
                 for j in (getattr(x, "jaxpr", x),)
                 if hasattr(j, "eqns")]
        for j in inner:
            _equations_under(j, wave, here, out)
        if not inner and wave in here:
            out.append((eqn.primitive.name, here))
    return out


@pytest.mark.parametrize("monitor", [True, False])
def test_every_equation_under_replicate_carries_a_part(monitor):
    """Each leaf equation under the wave is booked to one of its parts:
    the LAST `part.` on its stack (benchmarks/part_times.py). Two parts
    on one stack only where the two scatters lie inside the chunk loop of
    `bck_compact` (as `val_scatter` inside `ws_compact` on one chip)."""
    run, carry, _ = _sharded(monitor=monitor)
    closed = jax.make_jaxpr(run)(carry, jax.random.PRNGKey(0))
    under = _equations_under(closed.jaxpr, REPLICATE, "", [])
    assert len(under) > 40              # two hops of 8 leaves, two applies
    seen = set()
    for prim, stack in under:
        after = stack[stack.index(REPLICATE):].split("/")
        mine = [p for n in after for p in REPL_PARTS
                if n == waves.part_name(p)]
        assert mine, (prim, stack)
        assert len(mine) == 1 or (
            mine[0] == "bck_compact" and len(mine) == 2
            and mine[1] in ("bck_meta_scatter", "bck_val_scatter")), stack
        seen.add(mine[-1])
        # the collectives are the hop's, the scatters the backup's, and
        # each scatter of a backup table is a chunk's, under its own part
        if prim == "ppermute":
            assert mine == ["repl_hop"]
        if prim == "scatter":
            assert mine[-1] in ("bck_meta_scatter", "bck_val_scatter",
                                "bck_log_append"), stack
    assert seen == set(REPL_PARTS)
    assert sum(p == "ppermute" for p, _ in under) == 2 * 8


def test_the_replicate_parts_reach_compiled_hlo_under_their_wave():
    run, carry, _ = _sharded()
    names = _op_names(jax.jit(run).lower(
        carry, jax.random.PRNGKey(0)).compile().as_text())
    _assert_parts_under_their_waves(names, ("dense_sharded",))
    # append_rep_live's own parts lie inside the backup's append
    assert any(re.search(rf"{re.escape(REPLICATE)}/.*part\.bck_log_append/"
                         r".*part\.log_scatter", n) for n in names)


def test_replicate_parts_are_semantics_neutral(monkeypatch):
    def run_once():
        run, carry, drain = _sharded()
        carry, stats = run(carry, jax.random.PRNGKey(3))
        state, tail, counters = drain(carry)
        return [np.asarray(x) for x in (
            stats, tail, counters.buf, state.db.val, state.db.meta,
            state.bck_val, state.bck_meta, state.db.log.entries,
            state.db.log.head)]

    a = run_once()
    ds.build_sharded_pipelined_runner.cache.clear()     # not in its key
    monkeypatch.setattr(waves, "part",
                        lambda owner, name: contextlib.nullcontext())
    b = run_once()
    ds.build_sharded_pipelined_runner.cache.clear()
    td.build_pipelined_runner.cache.clear()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ------------------------------------------------ the KV store's block

STORE_PARTS = ("store_gen", "key_sort", "slot_alloc", "reply_build",
               "probe_keys", "probe_val", "kv_val_scatter",
               "kv_meta_scatter", "kv_compact")
# 2^15 buckets x 4 slots = 2,048 entries a lane of the block's 64: the
# block's install is compacted (`kv_compact` is in the program), the
# populate's, at 256 lanes, is not
STORE_BUCKETS = 1 << 15


def _store(monitor=True, theta=0.99):
    run, init, drain = store.build_serve_runner(
        N_SUB, w=W, cohorts_per_block=CPB, val_words=VW, read_frac=0.95,
        theta=theta, use_scan=False, monitor=monitor)
    table, spilled = store.build_populate(N_SUB, STORE_BUCKETS, 256,
                                          val_words=VW)()
    assert int(spilled) == 0
    assert store.install_is_compacted(table, W)
    assert not store.install_is_compacted(table, 256)
    return run, init(table), drain


def test_the_store_parts_are_registered_under_their_waves():
    rows = {p: (o, w) for o, w, p, _ in waves._PARTS}
    assert [rows[p] for p in STORE_PARTS] == [
        ("store", None)] * 4 + [("store", "probe")] * 2 + [
        ("store", "install")] * 3
    with pytest.raises(KeyError, match="part registry"):
        waves.part("tatp_dense", "key_sort")


# the ordered run's parts (PR 45): a block with use_scan has them
SCAN_PARTS = ("scan_window", "scan_merge", "run_merge_rank",
              "run_merge_move", "run_snapshot")


def test_every_store_part_reaches_compiled_hlo_under_its_wave():
    run, carry, _ = _store()
    names = _op_names(run.lower(carry,
                                jax.random.PRNGKey(0)).compile().as_text())
    _assert_parts_under_their_waves(names, ("store",), skip=SCAN_PARTS)


def test_the_ordered_runs_parts_reach_compiled_hlo_under_their_waves():
    """A block of the YCSB-E shape (hashed keys, scans and inserts, the
    ordered run rebuilt at its end): every part of the scan and the
    rebuild sits under its wave."""
    run, init, _ = store.build_serve_runner(
        2_000, w=64, cohorts_per_block=2, val_words=4, scan_frac=0.95,
        max_scan_len=8, scan_max=8, delta_cap=64,
        theta=0.99, use_scan=True, monitor=True)
    table, _ = store.build_populate(2_000, 1 << 10, 256, val_words=4,
                                    hashed=True)()
    names = _op_names(run.lower(init(table), jax.random.PRNGKey(0))
                      .compile().as_text())
    _assert_parts_under_their_waves(
        names, ("store",), skip=tuple(
            p for _, _, p, _ in waves._PARTS if p not in SCAN_PARTS))


@pytest.mark.parametrize("theta", [0.99, None], ids=["zipfian", "hot"])
@pytest.mark.parametrize("monitor", [True, False])
def test_every_equation_of_the_store_block_carries_a_wave_or_a_part(
        monitor, theta):
    """The point block (``scan(step . gen)``, no scans, no hot tier), with
    either generator: ``unnamed_ms.kv`` is left what XLA inserts."""
    run, carry, _ = _store(monitor, theta)
    closed = jax.make_jaxpr(run)(carry, jax.random.PRNGKey(0))
    assert _unnamed_equations(closed.jaxpr, False, (), []) == []


def test_store_parts_are_semantics_neutral(monkeypatch):
    def run_once():
        run, carry, drain = _store()
        carry, stats = run(carry, jax.random.PRNGKey(3))
        table, tail, counters = drain(carry)
        return [np.asarray(x) for x in (
            stats, tail, counters.buf, *jax.tree.leaves(table))]

    a = run_once()
    monkeypatch.setattr(waves, "part",
                        lambda owner, name: contextlib.nullcontext())
    b = run_once()
    assert a[0][:, 1].sum() > 0
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ------------------------------- the sharded SmallBank's block (PR 43)

SBX = "dint.dense_sharded_sb."
# (part, the waves it is opened under): a part the lock requests and the
# installs share is registered with no wave and opened under both
SBX_PARTS = {
    "route_addr": ("route", "install_route"),
    "a2a_rank": ("route", "install_route"),
    "a2a_pack": ("route", "install_route"),
    "a2a_requests": ("route",),
    "owner_arb": ("arbitrate",), "owner_held_read": ("arbitrate",),
    "owner_grant": ("arbitrate",), "owner_stamp": ("arbitrate",),
    "owner_bal_read": ("arbitrate",), "owner_compact": ("arbitrate",),
    "a2a_replies": ("reply",), "reply_unpack": ("reply",),
    "reply_classify": ("reply",),
    "a2a_installs": ("install_route",), "owner_install": ("install_route",),
    "owner_log_append": ("install_route",),
    "sb_repl_hop": ("replicate",), "sb_bck_scatter": ("replicate",),
    "sb_bck_log_append": ("replicate",),
    "sbx_frame": (), "sbx_carry": (), "owner_addr": ()}


def _sharded_bank(monitor=True):
    mesh = dsb.make_mesh(4)
    state = dsb.create_sharded_sb(mesh, 4, 4096, log_capacity=1 << 9)
    run, init, drain = dsb.build_sharded_sb_runner(
        mesh, 4, 4096, w=32, cohorts_per_block=2, monitor=monitor)
    return run, init(state), drain


def test_the_sharded_smallbank_parts_are_registered_under_their_waves():
    rows = {p: (o, w) for o, w, p, _ in waves._PARTS}
    for part, under in SBX_PARTS.items():
        assert rows[part] == ("dense_sharded_sb",
                              under[0] if len(under) == 1 else None)
        assert waves.PART_OWNERS[part] == ("dense_sharded_sb",)
    with pytest.raises(KeyError, match="part registry"):
        waves.part("dense_sharded", "sb_repl_hop")
    with pytest.raises(KeyError, match="part registry"):
        waves.part("dense_sharded_sb", "repl_hop")


@pytest.mark.parametrize("monitor", [True, False])
def test_every_equation_of_the_sharded_smallbank_block_carries_a_part(
        monitor):
    """No equation of the block without a wave or a part, and under each
    of its six waves every leaf equation is booked to a part of that
    wave (the LAST `part.` on its stack; `log_plan` / `log_scatter` inside
    an append, the arbitration's own parts inside the chunk loops of
    `owner_compact`): the collectives to the exchange they belong to, the
    scatters to the table they write."""
    run, carry, _ = _sharded_bank(monitor)
    closed = jax.make_jaxpr(run)(carry, jax.random.PRNGKey(0))
    assert _unnamed_equations(closed.jaxpr, False, (), []) == []
    collectives = {"route": ("a2a_requests", 2), "reply": ("a2a_replies", 2),
                   "install_route": ("a2a_installs", 5)}
    seen = set()
    for wave in ("gen", "route", "arbitrate", "reply", "install_route",
                 "replicate"):
        under = _equations_under(closed.jaxpr, SBX + wave, "", [])
        assert under, wave
        mine = {p for p, ws in SBX_PARTS.items() if wave in ws}
        for prim, stack in under:
            after = stack[stack.index(SBX + wave):]
            parts = [p for p in re.findall(r"part\.([a-z0-9_]+)", after)]
            if wave == "gen":
                assert not parts
                continue
            assert parts and parts[0] in mine, (prim, stack)
            if parts[0] == "owner_compact":
                assert len(parts) <= 2 and set(parts[1:]) <= mine, stack
                seen.update(parts)
                continue
            assert parts[1:] in ([], ["log_plan"], ["log_scatter"]), stack
            if parts[1:]:
                assert parts[0].endswith("log_append")
            seen.add(parts[0])
            if prim == "all_to_all":
                assert parts == [collectives[wave][0]]
            if prim == "ppermute":
                assert parts == ["sb_repl_hop"]
        if wave in collectives:
            assert sum(p == "all_to_all" for p, _ in under) \
                == collectives[wave][1]
    assert seen == {p for p, ws in SBX_PARTS.items() if ws}
    under = _equations_under(closed.jaxpr, SBX + "replicate", "", [])
    assert sum(p == "ppermute" for p, _ in under) == 2 * 5


def test_the_sharded_smallbank_parts_reach_compiled_hlo_under_their_waves():
    run, carry, _ = _sharded_bank()
    names = _op_names(jax.jit(run).lower(
        carry, jax.random.PRNGKey(0)).compile().as_text())
    # the CPU compiles the carry's `x[0]` / `x[None]` to bitcasts, which
    # keep no name; the v5e's copies do (tests/test_chip_compile_*.py)
    _assert_parts_under_their_waves(names, ("dense_sharded_sb",),
                                    skip=("sbx_carry",))
    for part, under in SBX_PARTS.items():
        for wave in under:
            assert any(re.search(rf"{re.escape(SBX + wave)}/.*part\.{part}"
                                 r"(/|$)", n) for n in names), (part, wave)
    # append_rep's own parts lie inside the owner's and the backups' appends
    for outer in ("owner_log_append", "sb_bck_log_append"):
        assert any(re.search(rf"part\.{outer}/.*part\.log_scatter", n)
                   for n in names), outer


def test_sharded_smallbank_parts_are_semantics_neutral(monkeypatch):
    def run_once():
        run, carry, drain = _sharded_bank()
        carry, stats = run(carry, jax.random.PRNGKey(3))
        state, tail, counters = drain(carry)
        return [np.asarray(x) for x in (
            stats, tail, counters.buf, state.bal, state.bck_bal,
            state.x_step, state.s_step, state.log.entries, state.log.head)]

    a = run_once()
    dsb.build_sharded_sb_runner.cache.clear()       # not in the memo's key
    monkeypatch.setattr(waves, "part",
                        lambda owner, name: contextlib.nullcontext())
    b = run_once()
    dsb.build_sharded_sb_runner.cache.clear()
    assert a[0][:, 1].sum() > 0 and a[7].any()
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
