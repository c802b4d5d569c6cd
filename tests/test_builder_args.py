"""The engines take their configuration from their callers alone.

* Retired keywords: each builder the benchmark calls still accepts the
  keywords its callers pass as False (``use_pallas`` / ``use_fused`` /
  ``use_hotset``, engines/_memo.refuse_retired). A falsy value lowers to
  the program omitting it lowers to; a truthy one raises.
* No environment: a variable the library used to read at build time,
  set to 1, leaves the lowered block program as it is.
* The memo keys on the arguments alone.
* ServeEngine's defaults are the serve priors the planner used to pin:
  ControllerCfg()'s widths and SLO, and the pinned CALIB.json's model.

Every program here is lowered at a tiny geometry, not compiled."""
import functools

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from dint_tpu.engines import smallbank_dense as sd
from dint_tpu.engines import store
from dint_tpu.engines import tatp_dense as td
from dint_tpu.parallel import dense_sharded as ds
from dint_tpu.parallel import dense_sharded_sb as dsb
from dint_tpu.tables import kv

D = 4                         # devices of the sharded builders' mesh
KEY = jax.eval_shape(lambda: jax.random.PRNGKey(0))


def _mesh(axis):
    return Mesh(np.array(jax.devices()[:D]), (axis,))


# builder -> (build(**kw) -> (run, init, drain), state() -> what init takes)
BUILDERS = {
    "tatp_dense": (
        lambda **kw: td.build_pipelined_runner(
            200, w=32, cohorts_per_block=2, **kw),
        lambda: td.create(200, log_capacity=1 << 8)),
    "smallbank_dense": (
        lambda **kw: sd.build_pipelined_runner(
            400, w=32, cohorts_per_block=2, **kw),
        lambda: sd.create(400, log_capacity=1 << 8)),
    "dense_sharded": (
        lambda **kw: ds.build_sharded_pipelined_runner(
            _mesh(ds.SHARD_AXIS), D, 400, w=32, cohorts_per_block=2, **kw),
        lambda: ds.create_sharded(_mesh(ds.SHARD_AXIS), D, 400,
                                  log_capacity=1 << 8)),
    "dense_sharded_sb": (
        lambda **kw: dsb.build_sharded_sb_runner(
            _mesh(dsb.AXIS), D, 800, w=32, cohorts_per_block=2, **kw),
        lambda: dsb.create_sharded_sb(_mesh(dsb.AXIS), D, 800,
                                      log_capacity=1 << 8)),
    "store": (
        lambda **kw: store.build_serve_runner(
            500, w=32, cohorts_per_block=2, read_frac=0.95, theta=0.99,
            **kw),
        lambda: kv.create(256)),
}

# the keywords each builder keeps for the benchmark's callers
RETIRED = {
    "tatp_dense": ("use_pallas", "use_fused"),
    "smallbank_dense": ("use_pallas", "use_fused", "use_hotset"),
    "dense_sharded": ("use_pallas", "use_fused"),
    "dense_sharded_sb": ("use_hotset",),
    "store": (),
}

# what the library read at build time before it took its callers' word
ENV = ("DINT_USE_HOTSET", "DINT_USE_SCAN", "DINT_TRACE", "DINT_TRACE_RATE",
       "DINT_PLAN_OVERRIDE")


def block_text(name, **kw) -> str:
    """The lowered text of the builder's block program, built afresh (the
    memo cleared, so nothing built earlier answers)."""
    build, state = BUILDERS[name]
    for builder in (td.build_pipelined_runner, sd.build_pipelined_runner,
                    ds.build_sharded_pipelined_runner,
                    dsb.build_sharded_sb_runner):
        builder.cache.clear()         # (the store's builder has no memo)
    run, init, _ = build(**kw)
    carry = jax.eval_shape(lambda: init(state()))
    fn = run if hasattr(run, "lower") else jax.jit(run)
    return fn.lower(carry, KEY).as_text()


@functools.cache
def baseline(name) -> str:
    return block_text(name)


@pytest.fixture(autouse=True)
def _no_env(monkeypatch):
    for var in ENV:
        monkeypatch.delenv(var, raising=False)


@pytest.mark.parametrize("name, keyword, value", [
    (name, kw, value) for name, kws in RETIRED.items() for kw in kws
    for value in (False, True)])
def test_retired_keyword(name, keyword, value):
    if value:
        with pytest.raises(ValueError, match=keyword):
            BUILDERS[name][0](**{keyword: value})
    else:
        assert block_text(name, **{keyword: value}) == baseline(name)


@pytest.mark.parametrize("var", ENV)
@pytest.mark.parametrize("name", list(BUILDERS))
def test_program_ignores_the_environment(name, var, monkeypatch):
    want = baseline(name)
    monkeypatch.setenv(var, "1")
    assert block_text(name) == want


def test_memo_shares_a_build_across_environments(monkeypatch):
    build = BUILDERS["tatp_dense"][0]
    first = build()
    for var in ENV:
        monkeypatch.setenv(var, "1")
    assert build() is first


def test_memo_builds_apart_for_other_arguments():
    build = BUILDERS["tatp_dense"][0]
    assert build(monitor=True) is not build()
    assert build(monitor=True) is build(monitor=True)


@pytest.mark.parametrize("engine", ["tatp_dense", "smallbank_dense",
                                    "store", "mesh"])
def test_serve_defaults(engine):
    """With no ``cfg`` / ``model`` the serving plane runs the widths and
    SLO of ControllerCfg() and the pinned CALIB.json's fitted model (for
    ``store`` too, which ran on ServiceModel()'s 150 µs and 40 ns a lane
    while the planner chose the others' priors)."""
    from dint_tpu.serve import MeshServeEngine, ServeEngine, VirtualClock
    if engine == "mesh":
        eng = MeshServeEngine(600, mesh_shape=(3, 1), clock=VirtualClock(),
                              monitor=False)
    else:
        eng = ServeEngine(engine, 600, clock=VirtualClock(), monitor=False)
    assert eng.cfg.widths == (256, 1024, 4096, 8192)
    assert eng.cfg.slo_us == 5000.0
    assert (eng.model.base_us, eng.model.per_lane_ns) == \
        (161.97914, 38.003079)
