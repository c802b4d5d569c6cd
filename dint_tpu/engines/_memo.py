"""Process-wide builder memoisation.

Every `build_*_runner` returns a triple of stateless jitted closures;
the only inputs that shape the compiled program are the builder's own
(hashable) arguments. Callers in different modules still pay a full
XLA compile each, because each call creates fresh `jax.jit` objects —
in the test suite that means the same dense engine at the same
geometry compiles once per test FILE, and in the serving plane a
restarted engine recompiles its whole width menu. Memoising the
builder collapses those to one compile per distinct configuration per
process. Unhashable arguments (shouldn't happen, but e.g. an ad-hoc
dict) fall back to an uncached build rather than failing.
"""
from __future__ import annotations

import functools

# builders resolve None-valued knobs from the ambient environment at
# BUILD time (ops/hotset.resolve_use_hotset, monitor/txnevents trace
# defaults), so those values are part of the compiled program's
# identity — fold a snapshot into the key or a monkeypatched env would
# hit a stale entry. The snapshot is analysis/plan.env_knob_signature():
# the CANONICALIZED resolution of every build-identity knob, from the
# same single resolver the builders and the plan checker use — unset,
# "" and "0" (all False to a builder) share one memo entry, and the
# memo key can never disagree with the builder about what a flag means.


def _env_signature() -> tuple:
    from ..analysis import plan           # deferred: engines must import
    return plan.env_knob_signature()      # without the analysis package


def memoize_builder(fn):
    cache: dict = {}

    @functools.wraps(fn)
    def wrapped(*args, **kw):
        env = _env_signature()
        try:
            key = (args, tuple(sorted(kw.items())), env)
            hit = cache.get(key)         # hashing happens here too (ndarray
        except TypeError:                # mix= etc.): build uncached
            return fn(*args, **kw)
        if hit is None:
            hit = cache[key] = fn(*args, **kw)
        return hit

    wrapped.cache = cache        # introspection / explicit clears in tests
    return wrapped


def refuse_kernel_flags(use_pallas, use_fused):
    # benchmarks/deployments/*.py, benchmarks/checks.py and
    # tests/bench/test_bench_harness.py still pass both as False
    if use_pallas or use_fused:
        raise ValueError("use_pallas / use_fused: the Pallas kernels were "
                         "deleted in PR 35; the engines have one route")
