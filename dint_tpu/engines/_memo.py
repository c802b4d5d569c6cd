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
dict) fall back to an uncached build rather than failing. A builder
reads nothing but its arguments (no environment variable), so they are
the whole key.
"""
from __future__ import annotations

import functools


def memoize_builder(fn):
    cache: dict = {}

    @functools.wraps(fn)
    def wrapped(*args, **kw):
        try:
            key = (args, tuple(sorted(kw.items())))
            hit = cache.get(key)         # hashing happens here too (ndarray
        except TypeError:                # mix= etc.): build uncached
            return fn(*args, **kw)
        if hit is None:
            hit = cache[key] = fn(*args, **kw)
        return hit

    wrapped.cache = cache        # introspection / explicit clears in tests
    return wrapped


def refuse_retired(**flags):
    """The retired builder keywords: ``use_pallas`` / ``use_fused`` (the
    Pallas kernels are gone; the engines have one route) and
    ``use_hotset`` (the hot-set mirror is gone). The benchmark's
    deployments and its tests still pass them as False, so a falsy value
    builds what omitting it builds, and a truthy one raises."""
    on = sorted(k for k, v in flags.items() if v)
    if on:
        raise ValueError(f"{', '.join(on)}: a retired keyword, accepted "
                         "only as False")
