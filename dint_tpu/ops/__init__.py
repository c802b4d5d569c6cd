from . import u64, hashing, segments  # noqa: F401
