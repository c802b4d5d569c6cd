"""Ask the chip's compiler, without the chip: the one-chip SmallBank
deployment and the sort rule its install rests on
(chip_compile_common.py says how)."""
import re

import jax
import pytest

from chip_compile_common import (  # noqa: F401 (fixtures)
    CPB, I32, W, _s, compiled_bytes, one_chip, placed,
    scatter_index_counts, topo)
from dint_tpu.engines import smallbank_dense as sd
from dint_tpu.ops import compact


def test_smallbank24m_block_program_fits_one_chip(one_chip):
    """The `smallbank24m-sat` cell's programs at the deployment's scale:
    24,000,000 accounts, 2^25 hashed lock slots, w=8192 x 16 cohorts. Pins
    the sizes PERF.md reckons with: 0.56 GB of state donated and updated
    in place, and of the two slot-table-wide arbitration arrays (134 MB
    each) one live at a time as the step's temporaries."""
    n_acc = 24_000_000
    run, init, drain = sd.build_pipelined_runner(
        n_acc, w=W, cohorts_per_block=CPB, monitor=True, trace=False)
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
        # PERF.md section 6); before no other
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
            # before it fills the second: one is live, not both (268 MB
            # were reckoned); the drain has no requests to arbitrate
            assert ma.temp_size_in_bytes >= 4 * (1 << 25)


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
