"""What the chip-compile test files share: the described v5e, placing
abstract arguments on it, and reading sizes out of compiled HLO text.

The TPU compiler is installed here and compiles for a v5e that is
described, not attached: what it refuses in those files it would refuse
on the chip, at no chip time. Nothing runs, so nothing there is a result
or a time — only "compiles, and fits" or the compiler's refusal.

Geometry is the flagship deployment's (chip_smoke.py): TATP at 7 000 000
subscribers, val_words=10, w=8192, 16 cohorts per block.

The topology is described inside a module-scoped fixture (never at
import, never in conftest.py: only one process may hold the TPU library,
and every xdist worker imports every test file). A test file takes the
fixtures by importing them, and so describes its own topology once. The
files are split by deployment family (tatp, smallbank, store, sharded)
so that ``--dist loadfile`` spreads them over the workers."""
import os
import re

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from dint_tpu.engines import tatp_dense as td

HBM_BYTES = 16e9                 # one v5e chip
N_SUB, W, CPB, VW = 7_000_000, 8192, 16, 10
R = td.READ_LANES
N1 = td.n_rows(N_SUB) + 1        # table rows incl. the sentinel
U32, I32 = jnp.uint32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2. Around these compiles the persistent compile
    cache is off (a compile for a described device cannot be read back)
    and XLA optimises as it does on the chip, not as conftest.py sets it
    for the CPU suite."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # or libtpu logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    opt_was = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_disable_most_optimizations", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_disable_most_optimizations", opt_was)
    cc.reset_cache()
    if log_dir is None:
        del os.environ["TPU_LOG_DIR"]
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def placed(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _s(shape, dt=U32):
    return jax.ShapeDtypeStruct(shape, dt)


def compiled_bytes(jitted, *args):
    c = jitted.lower(*args).compile()
    return c, c.memory_analysis()


def _count(shape: str) -> int:
    return int(np.prod([int(d) for d in shape.split(",") if d]))


def _shapes(hlo: str) -> dict:
    return dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", hlo))


def scatter_index_counts(hlo: str, table_words: int) -> list:
    """How many indices each native scatter into a u32[table_words] table
    issues (its index operand's element count), from compiled HLO text."""
    shape_of = _shapes(hlo)
    return [_count(shape_of[idx]) for idx in re.findall(
        rf"u32\[{table_words}\]\S* scatter\(%[\w.\-]+, %([\w.\-]+),", hlo)]


def all_scatter_index_counts(hlo: str) -> set:
    """The index counts of every native scatter in compiled HLO text,
    whatever it writes."""
    shape_of = _shapes(hlo)
    return {_count(shape_of[idx]) for idx in re.findall(
        r" scatter\(%[\w.\-]+, %([\w.\-]+),", hlo)}


def gather_lane_counts(hlo: str, table_words: int) -> list:
    """How many lanes each native gather out of a u32[table_words] table
    issues (its output's element count), from compiled HLO text."""
    shape_of = _shapes(hlo)
    return [_count(out) for out, table in re.findall(
        r"%[\w.\-]+ = u32\[([\d,]*)\]\S* gather\(%([\w.\-]+),", hlo)
        if shape_of.get(table) == str(table_words)]


def dispatches(fn, *args, also=()) -> list:
    """The programs ``fn`` dispatches: each jitted call at the top level
    of its jaxpr compiled apart, donation and all, as at run time
    (`jax.jit(fn)` would inline them into one program that never runs).
    An operand takes the sharding of the abstract argument of its shape
    (``also``: operands ``fn`` makes itself). Returns [(name, compiled)]."""
    pool = {(s.shape, s.dtype): s for s in jax.tree.leaves((args, also))}
    out = []
    for eqn in jax.make_jaxpr(fn)(*args).eqns:
        if eqn.primitive.name != "jit":
            continue
        operands = [pool[v.aval.shape, v.aval.dtype] for v in eqn.invars]
        donated = tuple(i for i, d in enumerate(
            eqn.params["donated_invars"]) if d)
        jitted = jax.jit(jax.extend.core.jaxpr_as_fun(eqn.params["jaxpr"]),
                         donate_argnums=donated)
        out.append((eqn.params["name"], jitted.lower(*operands).compile()))
    return out


# an ENTRY instruction that hands a buffer on and moves no element of it
_PLUMBING = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
             "conditional"}


def entry_ops_of_size(hlo: str, counts: set) -> list:
    """(opcode, result type) of every instruction of compiled HLO text's
    ENTRY computation, plumbing apart, whose result (or a member of its
    tuple) has as many elements as one of ``counts``, leading 1s or not."""
    entry = hlo[hlo.index("\nENTRY "):]
    found = []
    for line in entry[:entry.index("\n}")].splitlines():
        # "%name = <type or (tuple of types)> opcode(operands...", and
        # not the computation's own header
        m = re.match(r".*? = (\(.*?\)|\S+) ([\w\-]+)\(", line)
        if m and m[2] not in _PLUMBING and counts & {
                _count(dims) for dims in re.findall(r"\w\[([\d,]*)\]", m[1])}:
            found.append((m[2], m[1]))
    return found
