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
