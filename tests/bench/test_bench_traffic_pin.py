"""The benchmark cannot hold the traffic generator yet: the engines draw
their cohorts on the device (tatp_dense.gen_cohort inside pipe_step). So
the generator is pinned here. A PR that changes what a key generates at
the benchmark's two widths, or the mix, changes this test, and with it
every cell's traffic: say so in PERF.md."""
import hashlib

import jax
import numpy as np
import pytest

from dint_tpu.engines import tatp_dense as td

N_SUB = 7_000_000
KEY = 20240928
PINNED = {
    8192: "dbbd7e62ae9ed9ac7b9fd07143ada8a2ddd2dc931ecfd3671f6a3882d629ecce",
    256: "44e9502652439f4bf5162a268af410bb8baa3836867cec900ac2b38577c4e88b",
}


@pytest.mark.parametrize("w", sorted(PINNED))
def test_gen_cohort_outputs_for_a_fixed_key(w):
    h = hashlib.sha256()
    for x in jax.tree.leaves(td.gen_cohort(jax.random.PRNGKey(KEY), w,
                                           N_SUB)):
        a = np.ascontiguousarray(np.asarray(x))
        h.update(str(a.dtype).encode() + str(a.shape).encode()
                 + a.tobytes())
    assert h.hexdigest() == PINNED[w]


def test_mix_shares_within_their_binomial_bands():
    """tatp.h:57-63: 35/35/10/2/14/2/2 over 64 cohorts of 8192; each
    share within five standard deviations of its own binomial."""
    gen = jax.jit(lambda k: td.gen_cohort(k, 8192, N_SUB)[0])
    key = jax.random.PRNGKey(KEY)
    ttype = np.concatenate([np.asarray(gen(jax.random.fold_in(key, i)))
                            for i in range(64)])
    n = len(ttype)
    share = np.bincount(ttype, minlength=7) / n
    want = np.array([35, 35, 10, 2, 14, 2, 2]) / 100
    sigma = np.sqrt(want * (1 - want) / n)
    assert len(share) == 7 and (np.abs(share - want) < 5 * sigma).all(), \
        share
