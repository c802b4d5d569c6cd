"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a v5e that is
described, not attached: what it refuses in this file it would refuse on
the chip, at no chip time. Nothing runs, so nothing here is a result or a
time — only "compiles, and fits" or the compiler's refusal.

Geometry is the flagship deployment's (chip_smoke.py): TATP at 7 000 000
subscribers, val_words=10, w=8192, 16 cohorts per block.

The topology is described inside a module-scoped fixture (never at
import, never in conftest.py: only one process may hold the TPU library,
and every xdist worker imports every test file)."""
import os
import re

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from dint_tpu.engines import smallbank_dense as sd
from dint_tpu.engines import store
from dint_tpu.engines import tatp_dense as td
from dint_tpu.ops import compact
from dint_tpu.parallel import dense_sharded as ds
from dint_tpu.parallel import dense_sharded_sb as dsb
from dint_tpu.tables import kv

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


# ------------------------------------------------ the default program


def _runner():
    return td.build_pipelined_runner(
        N_SUB, w=W, val_words=VW, cohorts_per_block=CPB, monitor=True,
        trace=False)


def test_tatp7m_populate_fits_one_chip(one_chip):
    """Round 5 lost a chip window to a populate that OOMed at compile
    time (a [p1, 4, 3] draw padded 42.7x); the flat layout must fit."""
    key = placed(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
    _, ma = compiled_bytes(
        jax.jit(lambda k: td.populate_device(k, N_SUB, val_words=VW)), key)
    assert ma.output_size_in_bytes > 7e9          # the real tables
    assert ma.output_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES


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


def test_tatp7m_block_program_fits_one_chip(one_chip):
    run, init, drain = _runner()
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    carry = jax.eval_shape(
        lambda k: init(td.populate_device(k, N_SUB, val_words=VW)), key)
    carry, key = placed(carry, one_chip), placed(key, one_chip)
    for fn, args in ((run, (carry, key)), (drain, (carry,))):
        c, ma = compiled_bytes(fn, *args)
        assert ma.argument_size_in_bytes > 7e9    # the real tables
        assert ma.argument_size_in_bytes + ma.temp_size_in_bytes \
            < HBM_BYTES
        # the carry is donated: the tables update in place, through the
        # chunk loops of the compacted install and of the lock wave as
        # well (its first loop carries arb and reads the held stamps from
        # that carry: a second, closed-over arb would be a 0.6 GB copy)
        assert ma.alias_size_in_bytes > 0.99 * ma.argument_size_in_bytes
        assert ma.temp_size_in_bytes < 1e9
        # the install issues a chunk of the live write slots, not all 2w
        # (ops/compact.py): C x VW value words, C meta words
        chunk = compact.chunk_lanes(2 * W)
        assert chunk == 512
        hlo = c.as_text()
        # no sort: the proofs read one, and the compiler puts none before
        # the lock wave's 512-index scatter-max (as it does before
        # smallbank_dense's 24,576-index ones, below)
        assert " sort(" not in hlo
        assert set(scatter_index_counts(hlo, N1 * VW)) == {chunk * VW}
        # meta's install, and the lock wave's scatter-max over arb (the
        # same shape): a chunk of the active write slots, not all 2w
        assert set(scatter_index_counts(hlo, N1)) == {chunk}
        assert "part.lock_scatter_max/scatter-max" in hlo
        # nor does a gather out of arb or meta issue 2w lanes: the stamp
        # read and the winner read-back issue a chunk each, and the fused
        # meta gather the R read lanes of c1 and of the new cohort, not
        # all K = 4 lanes of a txn
        assert set(gather_lane_counts(hlo, N1)) == {chunk, 2 * W * R}
        # the magic gather, the one gather out of val: R lanes a txn (the
        # drain's new cohorts are constants: its magic gather folds away)
        assert set(gather_lane_counts(hlo, N1 * VW)) == (
            {W * R} if fn is run else set())


def test_smallbank24m_block_program_fits_one_chip(one_chip):
    """The `smallbank24m-sat` cell's programs at the deployment's scale:
    24,000,000 accounts, 2^25 hashed lock slots, w=8192 x 16 cohorts. Pins
    the sizes PERF.md reckons with: 0.56 GB of state donated and updated
    in place, and of the two slot-table-wide arbitration arrays (134 MB
    each) one live at a time as the step's temporaries."""
    n_acc = 24_000_000
    run, init, drain = sd.build_pipelined_runner(
        n_acc, w=W, cohorts_per_block=CPB, monitor=True,
        use_hotset=False, trace=False)
    carry = placed(jax.eval_shape(lambda: init(sd.create(n_acc))), one_chip)
    key = placed(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
    db = carry[0]
    assert db.lock_slots == sd.MAX_LOCK_SLOTS == 1 << 25    # hashed
    assert db.bal.shape == (2 * n_acc + 1,)
    # balances, two stamp tables, and the rings as the chip lays them
    # out (a slot's 18 words padded to 24)
    state = 4 * (2 * n_acc + 1) + 2 * 4 * (1 << 25) \
        + 4 * 16 * (1 << 16) * 24
    for fn, args in ((run, (carry, key)), (drain, (carry,))):
        c, ma = compiled_bytes(fn, *args)
        assert state < ma.argument_size_in_bytes < state + 2e6
        assert ma.alias_size_in_bytes > 0.99 * ma.argument_size_in_bytes
        assert ma.temp_size_in_bytes < 0.2e9
        # the program asks for no sort; the compiler puts one of wL =
        # 24,576 (index, value) pairs before each scatter of the lock wave
        # (the two scatter-mins, whose indices repeat, and the two stamp
        # scatters), and one of 31,360 before the install's, which issues
        # that many lanes so that it does (compact.sorted_scatter_lanes;
        # PERF.md section 6, PR 36); before no other
        hlo = c.as_text()
        sorts = re.findall(r' sort\([^\n]*op_name="([^"]*)"', hlo)
        assert all(name.endswith(("part.lock_arb/scatter-min",
                                  "part.lock_stamp/scatter",
                                  "dint.smallbank_dense.install/scatter"))
                   for name in sorts)
        assert sum(name.endswith("dint.smallbank_dense.install/scatter")
                   for name in sorts) == 1
        install, = re.findall(
            rf"u32\[{2 * n_acc + 1}\]\S* scatter\([^\n]*", hlo)
        assert "indices_are_sorted=true" in install
        assert scatter_index_counts(hlo, 2 * n_acc + 1) == [31_360]
        if fn is run:
            assert len(sorts) == 5
            # the compiler gathers out of the first arbitration array
            # before it fills the second: one is live, not both (ISSUE 33
            # reckoned 268 MB); the drain has no requests to arbitrate
            assert ma.temp_size_in_bytes >= 4 * (1 << 25)


def test_store24m_block_program_fits_one_chip(one_chip):
    """The `store-ycsb-b` cell's programs at the deployment's scale:
    24,000,000 keys of 10 words in 2^24 buckets x 4 slots, w=8192 x 16
    steps, YCSB-B's mix and Zipfian. Pins what PERF.md reckons with: 3.69
    GB of table donated and updated in place, next to no temporaries (the
    n_buckets-wide `taken` fill is one), which scatters the compiler
    sorts, and, since PR 40, that the install issues a chunk of the
    step's elected writers from inside its loops, not all w lanes: the
    three bounds on arguments, aliases and temporaries are what would
    catch a loop that copied a table."""
    n_keys, nb, slots = 24_000_000, 1 << 24, 4
    ne = nb * slots
    run, init, drain = store.build_serve_runner(
        n_keys, w=W, cohorts_per_block=CPB, val_words=VW, read_frac=0.95,
        theta=0.99, monitor=True)
    carry = placed(jax.eval_shape(lambda: init(kv.create(nb))), one_chip)
    key = placed(jax.eval_shape(lambda: jax.random.PRNGKey(0)), one_chip)
    # key_hi, key_lo, version, VW value words, a valid byte an entry; two
    # bloom words a bucket
    state = ne * (4 + 4 + 4 + 4 * VW + 1) + nb * 8
    assert 3.69e9 < state < 3.70e9
    for fn, args in ((run, (carry, key)), (drain, (carry,))):
        c, ma = compiled_bytes(fn, *args)
        assert state <= ma.argument_size_in_bytes < state + 2e6
        assert ma.alias_size_in_bytes > 0.99 * ma.argument_size_in_bytes
        assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES
        assert ma.temp_size_in_bytes < 0.2e9
        if fn is drain:
            continue
        hlo = c.as_text()
        sorts = re.findall(r' sort\([^\n]*op_name="([^"]*)"', hlo)
        # the program's own four: the batch by key, twice by bucket for
        # the slot allocation, and the counter plane's distinct-key count
        own = [n for n in sorts if n.endswith("/sort")]
        assert sorted(n.split("closed_call/")[-1] for n in own) == [
            "part.key_sort/sort", "part.monitor/jit(sort)/sort",
            "part.slot_alloc/sort", "part.slot_alloc/sort"]
        # the compiler's: before the unsorts (w indices into w words, as
        # dense as a scatter gets), and before NONE of the five install
        # scatters nor the `taken` scatter-add, whose indices repeat. At
        # full width the five issued w indices, or w x VW, into 67 M /
        # 671 M words: one per 8,192 words, against the ~1,630 at which
        # the compiler sorts, so the rule compacts them
        # (store.install_is_compacted): each sits in the body of a chunk
        # loop and issues C lanes, C x VW value words, of the elected
        # writers
        assert len(sorts) > len(own)
        assert all(n.endswith(("part.key_sort/scatter",
                               "part.slot_alloc/scatter"))
                   for n in sorts if n not in own)
        assert not compact.compiler_sorts(ne, W)
        chunk = compact.chunk_lanes(W)
        assert chunk == 256
        installs = re.findall(
            rf"(?:u32|pred)\[(?:{ne}|{ne * VW})\]\S* scatter\([^\n]*", hlo)
        assert len(installs) == 5
        assert not any("indices_are_sorted=true" in x for x in installs)
        assert all("dint.store.install/part.kv_compact/while/body/" in x
                   for x in installs)
        assert sum("part.kv_val_scatter" in x for x in installs) == 1
        assert sum("part.kv_meta_scatter" in x for x in installs) == 4
        assert scatter_index_counts(hlo, ne) == [chunk] * 3
        assert scatter_index_counts(hlo, ne * VW) == [chunk * VW]
        taken, = re.findall(rf"s32\[{nb + 1}\]\S* scatter\([^\n]*", hlo)
        assert "part.slot_alloc/scatter-add" in taken
        assert "indices_are_sorted=true" not in taken
        # the probe reads both buckets' four slots of three arrays, then
        # one value and one version a lane
        assert sorted(gather_lane_counts(hlo, ne)) == [W] + [W * slots] * 4
        assert gather_lane_counts(hlo, ne * VW) == [W * VW]


def test_store24m_populate_fits_one_chip(one_chip):
    """The populate through the INSERT path at the configuration's 65,536
    lanes a step: the whole table is its output, made on the device."""
    n_keys, nb = 24_000_000, 1 << 24
    populate = store.build_populate(n_keys, nb, 65_536, val_words=VW)
    # no argument to place: the outputs' sharding names the device
    c, ma = compiled_bytes(jax.jit(populate.__wrapped__,
                                   out_shardings=one_chip))
    assert 3.69e9 < ma.output_size_in_bytes < 3.70e9
    assert ma.output_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES
    assert ma.temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("table_words, lanes, dense", [
    (48_000_001, 24_576, 31_360),       # smallbank24m's balances
    (40_000_001, 16_384, 26_112),
    (1 << 26, 24_576, 43_776),
])
def test_compiler_sorts_a_scatter_at_the_lanes_the_rule_gives(
        one_chip, table_words, lanes, dense):
    """What compact.SORTED_SCATTER_WORDS_PER_LANE rests on: the v5e
    compiler sorts the (index, value) pairs of a 1-D scatter, and marks
    it `indices_are_sorted`, once it issues about one index per 1,630
    table words. At the lane count the rule gives it does, at the
    scatter's own it does not. This case fails the day a compiler moves
    the threshold past the rule's margin."""
    assert compact.sorted_scatter_lanes(table_words, lanes) == dense

    def compiled(r):
        c, _ = compiled_bytes(
            jax.jit(lambda t, i, v: t.at[i].set(v, mode="drop",
                                                unique_indices=True),
                    donate_argnums=0),
            *placed((_s((table_words,)), _s((r,), I32), _s((r,))),
                    one_chip))
        return c.as_text()

    hlo = compiled(dense)
    assert " sort(" in hlo
    assert "indices_are_sorted=true" in hlo
    hlo = compiled(lanes)
    assert " sort(" not in hlo
    assert "indices_are_sorted=true" not in hlo


def test_windowed_row_scatter_is_expanded_to_a_loop_on_v5e(one_chip):
    """"Scatter rows, not words" (PERF.md §7 before PR 30) does not
    compile to what it hopes: a window of VW words into the 1-D val array
    is no native scatter on v5e, the compiler expands it into a `while`
    of one dynamic-update-slice per index (16,384 trips on the 6.16 GB
    array). This case fails, and reopens the row form, the day the
    compiler keeps it native."""
    dn = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0,))

    def rows(val, start, new):
        return jax.lax.scatter(val, start[:, None], new, dn,
                               unique_indices=True, mode="drop")

    c, _ = compiled_bytes(
        jax.jit(rows, donate_argnums=0),
        *placed((_s((N1 * VW,)), _s((2 * W,), I32), _s((2 * W, VW))),
                one_chip))
    hlo = c.as_text()
    assert " scatter(" not in hlo
    assert " while(" in hlo and "dynamic-update-slice(" in hlo
    assert f"constant({2 * W})" in hlo            # the loop's trip count


# ------------------------------------------------------ four chips


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


def test_dense_sharded_block_program_on_four_chips(topo):
    """The sharded TATP-7M programs compile for a 4-device v5e mesh, each
    device holds a quarter of the state (created sharded: nothing global
    on one chip), the replication rides collective-permutes, and the
    block's parameters and results are its scan's own carry buffers."""
    mesh = Mesh(np.array(topo.devices), (ds.SHARD_AXIS,))
    n = mesh.size
    run, init, drain = ds.build_sharded_pipelined_runner(
        mesh, n, N_SUB, w=W, val_words=VW, cohorts_per_block=CPB,
        monitor=True)

    def create():
        return ds.create_sharded(mesh, n, N_SUB, val_words=VW, seed=0)

    by_device = NamedSharding(mesh, P(ds.SHARD_AXIS))
    stacked = placed(jax.eval_shape(create), by_device)
    key = placed(jax.eval_shape(lambda: jax.random.PRNGKey(0)),
                 NamedSharding(mesh, P()))
    _, ma = compiled_bytes(jax.jit(create))
    total = n * ma.output_size_in_bytes
    assert total > 20e9          # primary + 2 backups: more than one chip
    assert ma.output_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES

    # the carry as `init` hands it over: its shapes, and the shardings its
    # conversion compiles to. The way in is one copy a leaf and no
    # temporary (leaf by leaf at run time: the state and one leaf)
    made, ma = compiled_bytes(jax.jit(init, donate_argnums=0), stacked)
    assert ma.temp_size_in_bytes < 1e6
    carry = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(init, stacked), made.output_shardings)
    n1 = td.n_rows(ds.n_sub_local(N_SUB, n)) + 1
    table_words = {n1, n1 * VW, ds.N_BCK * n1, ds.N_BCK * n1 * VW}
    for leaf in jax.tree.leaves(carry[0]):
        assert leaf.sharding.is_equivalent_to(by_device, leaf.ndim)
        # no stacked axis: a device's shard is the table itself
        assert leaf.sharding.shard_shape(leaf.shape) == (
            leaf.shape[0] // n,) + leaf.shape[1:]
    assert table_words <= {int(np.prod(x.shape)) // n
                           for x in jax.tree.leaves(carry[0])}

    (_, block), = dispatches(run, carry, key)
    (_, steps), *back = dispatches(drain, carry, also=key)
    assert len(back) == len(jax.tree.leaves(carry[0])) - 1   # `db.step`

    # (program, ceiling on its temporaries). The block 0.020 GB and the
    # drain's two steps 0.011 beside 5.305 GB of donated state (AOT, PR
    # 42). With the shards carried stacked, [1, N] a device, they were
    # 7.794 and 8.868 (AOT, PR 38): `block_local` squeezed every leaf into
    # the scan's carry and unsqueezed it out again, a copy of each table
    # at the entry and three passes over it at the exit, 5.0 ms of the
    # 12.9 ms step on the chip (PERF.md §6, PR 42).
    chunk = compact.chunk_lanes(2 * W)
    for c, ceiling, magic in ((block, 0.1e9, {W * R}),
                              (steps, 0.012e9, set())):
        ma = c.memory_analysis()
        assert abs(ma.argument_size_in_bytes - total / n) < 0.01 * total / n
        assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES
        # the carry is donated: primaries, backups and rings update in
        # place
        assert ma.alias_size_in_bytes > 0.99 * ma.argument_size_in_bytes
        assert ma.temp_size_in_bytes < ceiling
        hlo = c.as_text()
        assert "collective-permute" in hlo
        # outside the scan nothing moves a table: parameter -> the loop
        # -> result
        assert entry_ops_of_size(hlo, table_words) == []
        # each hop's backup install issues a chunk of the forwarded
        # record's live lanes (C x VW value words, C meta words), as the
        # primary's own install does, as on one chip; nothing in the
        # program issues all 2w x VW indices any more
        assert set(scatter_index_counts(hlo, ds.N_BCK * n1 * VW)) \
            == {chunk * VW}
        assert set(scatter_index_counts(hlo, ds.N_BCK * n1)) == {chunk}
        assert set(scatter_index_counts(hlo, n1 * VW)) == {chunk * VW}
        assert 2 * W * VW not in all_scatter_index_counts(hlo)
        # the primary's gathers, as on one chip: meta the R read lanes of
        # c1 and of the new cohort (arb a chunk), val the R magic words
        assert set(gather_lane_counts(hlo, n1)) == {chunk, 2 * W * R}
        assert set(gather_lane_counts(hlo, n1 * VW)) == magic

    # the way back to the stacked state, once a drain and a leaf at a
    # time: the compiler writes [N] -> [1, N] as a zero fill and a one-trip
    # loop of a slice and an update, so a leaf is held three times
    # (argument, temporary, result); beside the rest of the state that
    # peaks at `bck_val`: 5.3 + 2 x 3.08 = 11.5 GB (AOT, PR 42)
    worst = 0
    for _, c in back:
        ma = c.memory_analysis()
        new = (ma.temp_size_in_bytes + ma.output_size_in_bytes
               - ma.alias_size_in_bytes)
        assert new < 2.01 * ma.argument_size_in_bytes + 1e6
        worst = max(worst, new)
    assert 6.1e9 < worst and total / n + worst < 11.6e9 < HBM_BYTES


def test_dense_sharded_sb_block_program_on_four_chips(topo):
    """`smallbank24m-x4r3` as the benchmark builds it: 24 M accounts over
    a 4-device v5e mesh, w = 8,192 x 16 cohorts, counters on. The block
    and the drain compile, the donated carry is updated in place, a
    step's exchange is nine all-to-alls and its replication ten
    collective-permutes, the owner's arbitration issues chunks of its
    inbox's live requests that the compiler sorts, and the temporaries
    are the two arbitration arrays and the stacked carry's copies, not a
    second state."""
    n_acc = 24_000_000
    mesh = Mesh(np.array(topo.devices), (dsb.AXIS,))
    n = mesh.size
    run, init, drain = dsb.build_sharded_sb_runner(
        mesh, n, n_acc, w=W, cohorts_per_block=CPB, monitor=True,
        use_hotset=False, trace=False, mix=(15, 15, 15, 25, 15, 15),
        hot_frac=0.04, hot_prob=0.9)
    by_device = NamedSharding(mesh, P(dsb.AXIS))
    state = placed(jax.eval_shape(
        lambda: dsb.create_sharded_sb(mesh, n, n_acc)), by_device)
    key = placed(jax.eval_shape(lambda: jax.random.PRNGKey(0)),
                 NamedSharding(mesh, P()))
    carry = placed(jax.eval_shape(init, state), by_device)
    m1 = dsb.m1_local(n_acc, n)
    assert m1 == 12_000_001
    cap = 2 * (W * dsb.L // n)
    assert n * cap == 49_152
    a_device = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(carry)) / n
    # primary 48 MB, two backups 96, two stamp tables 96, the ring 25
    assert 0.26e9 < a_device < 0.27e9

    (_, block), = dispatches(run, carry, key)
    (_, steps), = dispatches(drain, carry, also=key)
    # (program, ceiling on its temporaries, all-to-alls). 0.2866 GB and
    # 0.1364 (AOT, PR 43): two fresh 48 MB arbitration arrays a step and
    # the `[1, N]` carry's copies at the block's entry and exit (the form
    # PR 42 took out of dense_sharded; ROADMAP Queue 1). A drain generates
    # no cohort: its request exchange is a constant's, folded to six.
    # The arbitration's chunk loops keep the block's at 0.2884: its two
    # arrays and the state's tables never share a loop (where they did,
    # the compiler kept a table in its alternate memory and an array in
    # HBM: 0.3826, AOT).
    chunk = compact.sorted_chunk_lanes(m1, n * cap)
    assert chunk == 7_936
    for c, ceiling, a2a in ((block, 0.30e9, 9), (steps, 0.15e9, 6)):
        ma = c.memory_analysis()
        assert abs(ma.argument_size_in_bytes - a_device) < 0.01 * a_device
        assert ma.alias_size_in_bytes > 0.99 * ma.argument_size_in_bytes
        assert ma.temp_size_in_bytes < ceiling
        assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES
        hlo = c.as_text()
        assert len(re.findall(r" all-to-all(?:-start)?\(", hlo)) == a2a
        assert len(re.findall(r" collective-permute(?:-start)?\(",
                              hlo)) == 10
        # the stacked carry's squeeze and unsqueeze are real ops here
        assert "part.sbx_carry" in hlo and "part.a2a_pack" in hlo
        # the owner's append and the two forwarded ones issue a chunk of
        # the inbox's live rows a trip, not its D x cap slots (PR 44)
        ring = carry[0].log.entries.shape[1:]
        shape_of = _shapes(hlo)
        assert [_count(shape_of[idx]) for idx in re.findall(
            rf"u32\[{ring[0]},{ring[1]}\]\S* scatter\(%[\w.\-]+, "
            r"%([\w.\-]+),", hlo)] == [compact.chunk_lanes(n * cap)] * 3
        # the owner's arbitration issues a chunk of its inbox's live
        # requests a trip: the two scatter-mins into the fresh
        # arrays and the two stamp scatters, C indices each, all four
        # sorted by the compiler; beside them only the install's scatter
        # into the primary writes an m1-word table, at the inbox's width
        arb = re.findall(rf"[su]32\[{m1}\]\S* scatter\([^\n]*", hlo)
        assert sorted(_count(shape_of[i]) for i in re.findall(
            r" scatter\(%[\w.\-]+, %([\w.\-]+),", "\n".join(arb))) \
            == [chunk] * 4 + [n * cap]
        assert all("indices_are_sorted=true" in x for x in arb)
        assert sum("part.owner_compact/while/body/part.owner_arb/scatter-min"
                   in x for x in arb) == 2
        assert sum("part.owner_compact/while/body/part.owner_stamp/scatter"
                   in x for x in arb) == 2
        # and no gather of the stamps or the balances issues the inbox's
        # D x cap slots: they read a chunk each (three in the block; the
        # drain's constant empty cohort lets the compiler drop one)
        assert set(gather_lane_counts(hlo, m1)) == {chunk}


def test_the_arbitration_chunk_is_one_the_compiler_sorts(one_chip):
    """Why the owner's arbitration chunks by the table's words and not by
    the inbox's width: into a device's 12,000,001-word arbitration array
    a scatter-min of compact.sorted_chunk_lanes = 7,936 indices is sorted
    by the compiler first, one of chunk_lanes(49,152) = 1,536 is not (it
    would issue its lanes at the ~90 ns unsorted price)."""
    m1, slots = 12_000_001, 49_152
    chunk = compact.sorted_chunk_lanes(m1, slots)
    assert chunk == 7_936 and compact.chunk_lanes(slots) == 1_536
    assert compact.compiler_sorts(m1, chunk)
    assert not compact.compiler_sorts(m1, chunk - 128)

    def compiled(r):
        c, _ = compiled_bytes(
            jax.jit(lambda t, i, v: t.at[i].min(v, mode="drop"),
                    donate_argnums=0),
            *placed((_s((m1,), I32), _s((r,), I32), _s((r,), I32)),
                    one_chip))
        return c.as_text()

    hlo = compiled(chunk)
    assert " sort(" in hlo and "indices_are_sorted=true" in hlo
    hlo = compiled(compact.chunk_lanes(slots))
    assert " sort(" not in hlo and "indices_are_sorted=true" not in hlo
