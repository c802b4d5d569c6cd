"""Ask the chip's compiler, without the chip: the one-chip TATP
deployment (chip_compile_common.py says how)."""
import jax

from chip_compile_common import (  # noqa: F401 (fixtures)
    CPB, HBM_BYTES, I32, N1, N_SUB, R, VW, W, _s, compiled_bytes,
    gather_lane_counts, one_chip, placed, scatter_index_counts, topo)
from dint_tpu.engines import tatp_dense as td
from dint_tpu.ops import compact


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


def test_windowed_row_scatter_is_expanded_to_a_loop_on_v5e(one_chip):
    """"Scatter rows, not words" (an old PERF.md §7 proposal) does not
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
