"""Ask the chip's compiler, without the chip: the KV store's
deployment (chip_compile_common.py says how)."""
import re

import jax

from chip_compile_common import (  # noqa: F401 (fixtures)
    CPB, HBM_BYTES, VW, W, compiled_bytes, gather_lane_counts, one_chip,
    placed, scatter_index_counts, topo)
from dint_tpu.engines import store
from dint_tpu.ops import compact
from dint_tpu.tables import kv


def test_store24m_block_program_fits_one_chip(one_chip):
    """The `store-ycsb-b` cell's programs at the deployment's scale:
    24,000,000 keys of 10 words in 2^24 buckets x 4 slots, w=8192 x 16
    steps, YCSB-B's mix and Zipfian. Pins what PERF.md reckons with: 3.69
    GB of table donated and updated in place, next to no temporaries (the
    n_buckets-wide `taken` fill is one), which scatters the compiler
    sorts, and that the install issues a chunk of the
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
