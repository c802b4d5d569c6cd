"""Process set-up shared by the programs that run on the chip
(chip_smoke.py, bench.py, exp.py, tools/drive.py): where the persistent
compile cache lives, and the refusal to measure on anything but a TPU.

tests/conftest.py calls neither: the suite runs on XLA:CPU with the
cache off (its NOTE says why)."""
from __future__ import annotations

import os

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """The directory of JAX's persistent compile cache for this process:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else the
    fixed ``<checkout>/.jax_cache``. The path is part of the cache key's
    neighbourhood on disk — it never carries a temp name, pid or time, or
    no second run would ever hit."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(REPO, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.
    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so the path is set in
    code only when the environment is silent."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    # op metadata is left out of the cache key by default (jax 0.9.0), so
    # a program that differs from a cached one only in its named scopes
    # is a HIT and runs the old executable under the old names: a wave or
    # part added or renamed would reach a trace only after a cold
    # compile. With the metadata in the key such a program misses once.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # The metadata is the ops' MLIR locations: the name stack, and by
    # default up to ten frames of the Python call stack under it. A
    # helper jitted earlier in a process (`_where`, `_threefry_split`)
    # keeps its first caller's frames, so a process that runs another
    # phase first (the benchmark's traced run makes its comparison
    # before it populates) would key the same programs differently and
    # compile them again (6 of 14 on the chip; PERF.md §6, PR 29). With
    # no frames the locations are the names alone: the key follows the
    # ops and their scopes, not who called them or from which line, and
    # a second run of any kind in a checkout compiles nothing. The price:
    # compiled programs carry no source file or line in their metadata;
    # nothing in this repo reads one (the name stack is what the traces'
    # readers use).
    jax.config.update("jax_traceback_in_locations_limit", 0)
    return compile_cache_dir()


def require_tpu(n_devices: int = 1) -> list:
    """The first ``n_devices`` TPU devices, or SystemExit saying what was
    found instead: a measurement path never falls back to another
    platform, retries, or answers from an old artifact."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"no TPU: jax.devices() reports platform "
            f"{devs[0].platform!r} ({devs[0].device_kind}); this program "
            "measures on the chip only")
    if len(devs) < n_devices:
        raise SystemExit(f"need {n_devices} TPU devices, found {len(devs)}")
    return devs[:n_devices]
