"""Ask the chip's compiler, without the chip: the four-chip TATP and
SmallBank deployments, and the arbitration chunk the latter rests on
(chip_compile_common.py says how)."""
import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chip_compile_common import (  # noqa: F401 (fixtures)
    CPB, HBM_BYTES, I32, N_SUB, R, VW, W, _count, _s, _shapes,
    all_scatter_index_counts, compiled_bytes, dispatches,
    entry_ops_of_size, gather_lane_counts, one_chip, placed,
    scatter_index_counts, topo)
from dint_tpu.engines import tatp_dense as td
from dint_tpu.ops import compact
from dint_tpu.parallel import dense_sharded as ds
from dint_tpu.parallel import dense_sharded_sb as dsb


# ------------------------------------------------------ four chips


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
    # drain's two steps 0.011 beside 5.305 GB of donated state (AOT).
    # With the shards carried stacked, [1, N] a device, they were 7.794
    # and 8.868 (AOT): `block_local` squeezed every leaf into the scan's
    # carry and unsqueezed it out again, a copy of each table at the
    # entry and three passes over it at the exit, 5.0 ms of the 12.9 ms
    # step on the chip (PERF.md).
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
    # peaks at `bck_val`: 5.3 + 2 x 3.08 = 11.5 GB (AOT)
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
        trace=False, mix=(15, 15, 15, 25, 15, 15),
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
    # 0.1364 (AOT): two fresh 48 MB arbitration arrays a step and the
    # `[1, N]` carry's copies at the block's entry and exit (the form
    # dense_sharded no longer takes; ROADMAP Queue 1). A drain generates
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
        # the inbox's live rows a trip, not its D x cap slots
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
