"""ctypes bindings for the native host shim (native/shim.cc).

The shim is the framework's L0: a C++ UDP pump that batches the reference's
wire formats into fixed-width struct-of-arrays buffers (one per engine
step) and scatters replies with sendmmsg. Python sees numpy views over the
C++ buffers — zero copies on the poll side.
"""
from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess

import numpy as np

VAL_SIZE = 40           # bytes, store/ebpf/utils.h:11
VAL_WORDS = VAL_SIZE // 4

# wire formats (native/shim.cc)
FMT_MSG55 = 0
FMT_LOCK6 = 1
FMT_FASST9 = 2
FMT_LOG53 = 3

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_SO = os.path.abspath(os.path.join(_NATIVE_DIR, "libdintshim.so"))
_SRC = os.path.abspath(os.path.join(_NATIVE_DIR, "shim.cc"))


class _View(ctypes.Structure):
    _fields_ = [
        ("count", ctypes.c_uint32),
        ("slot", ctypes.c_uint32),
        ("ord", ctypes.POINTER(ctypes.c_uint8)),
        ("type", ctypes.POINTER(ctypes.c_uint8)),
        ("table", ctypes.POINTER(ctypes.c_uint8)),
        ("key", ctypes.POINTER(ctypes.c_uint64)),
        ("val", ctypes.POINTER(ctypes.c_uint8)),
        ("ver", ctypes.POINTER(ctypes.c_uint32)),
    ]


_lib = None


def load() -> ctypes.CDLL:
    """Load libdintshim.so, (re)building it with make if missing/stale.
    The binary is never tracked in git — a checkout builds its own — so
    several processes (the test workers) may arrive here at once: an
    exclusive file lock makes one of them build while the rest wait."""
    global _lib
    if _lib is not None:
        return _lib
    with open(_SO + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if (not os.path.exists(_SO)
                or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
            subprocess.run(["make", "-C", os.path.dirname(_SO)], check=True,
                           capture_output=True)
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            # stale/foreign binary (e.g. built on another arch): rebuild
            subprocess.run(["make", "-B", "-C", os.path.dirname(_SO)],
                           check=True, capture_output=True)
            lib = ctypes.CDLL(_SO)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.shim_server_create.restype = ctypes.c_void_p
    lib.shim_server_create.argtypes = [ctypes.c_char_p, ctypes.c_uint16,
                                       ctypes.c_uint32, ctypes.c_uint32,
                                       ctypes.c_uint32, ctypes.c_int]
    lib.shim_server_port.restype = ctypes.c_uint16
    lib.shim_server_port.argtypes = [ctypes.c_void_p]
    lib.shim_server_poll.restype = ctypes.c_int
    lib.shim_server_poll.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                     ctypes.POINTER(_View)]
    lib.shim_server_reply.restype = ctypes.c_int
    lib.shim_server_reply.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                      u8p, u8p, u32p]
    lib.shim_server_stats.argtypes = [ctypes.c_void_p, u64p]
    lib.shim_server_destroy.argtypes = [ctypes.c_void_p]
    lib.shim_client_create.restype = ctypes.c_void_p
    lib.shim_client_create.argtypes = [ctypes.c_char_p, ctypes.c_uint16,
                                       ctypes.c_int]
    lib.shim_client_exchange.restype = ctypes.c_int
    lib.shim_client_exchange.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                         u8p, u8p, u8p, u64p, u8p, u32p,
                                         u8p, u8p, u8p, u64p, u8p, u32p,
                                         ctypes.c_uint32]
    lib.shim_client_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def _as_np(ptr, n, dtype):
    return np.ctypeslib.as_array(ptr, shape=(n,)).view(dtype)


class ShimServer:
    """The batching UDP pump. poll() -> dict of numpy views; reply() sends."""

    def __init__(self, port: int = 0, width: int = 4096, flush_us: int = 200,
                 nrings: int = 8, fmt: int = FMT_MSG55, ip: str = "127.0.0.1"):
        self._lib = load()
        self._h = self._lib.shim_server_create(ip.encode(), port, width,
                                               flush_us, nrings, fmt)
        if not self._h:
            raise OSError(f"shim: cannot bind UDP {ip}:{port}")
        self.width = width
        self.port = self._lib.shim_server_port(self._h)
        self._pending: dict[int, int] = {}   # slot -> polled batch count

    def poll(self, timeout_us: int = 100_000):
        """Returns (slot, batch dict of numpy views) or None on timeout.
        Views alias C++ memory: invalid after reply(slot)."""
        v = _View()
        if not self._lib.shim_server_poll(self._h, timeout_us,
                                          ctypes.byref(v)):
            return None
        n = v.count
        self._pending[v.slot] = n
        return v.slot, {
            "ord": _as_np(v.ord, n, np.uint8),
            "type": _as_np(v.type, n, np.uint8),
            "table": _as_np(v.table, n, np.uint8),
            "key": _as_np(v.key, n, np.uint64),
            "val": np.ctypeslib.as_array(v.val, shape=(n, VAL_SIZE)),
            "ver": _as_np(v.ver, n, np.uint32),
        }

    def reply(self, slot: int, rtype, rval=None, rver=None):
        n = len(rtype)
        expect = self._pending.pop(slot, None)
        if expect is not None and n != expect:
            raise ValueError(
                f"reply() got {n} lanes for slot {slot}, poll() returned "
                f"{expect} — C++ reads the full polled count")
        rtype = np.ascontiguousarray(rtype, np.uint8)
        if rval is None:
            rval = np.zeros((n, VAL_SIZE), np.uint8)
        rval = np.ascontiguousarray(rval, np.uint8)
        rver = np.ascontiguousarray(
            rver if rver is not None else np.zeros(n), np.uint32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        return self._lib.shim_server_reply(
            self._h, slot, rtype.ctypes.data_as(u8p),
            rval.ctypes.data_as(u8p), rver.ctypes.data_as(u32p))

    def stats(self):
        out = (ctypes.c_uint64 * 4)()
        self._lib.shim_server_stats(self._h, out)
        return {"pkts_rx": out[0], "pkts_tx": out[1], "batches": out[2],
                "dropped": out[3]}

    def close(self):
        if self._h:
            self._lib.shim_server_destroy(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class ShimClient:
    """Native synthetic client: one 1-RTT batched exchange per call."""

    def __init__(self, ip: str, port: int, fmt: int = FMT_MSG55):
        self._lib = load()
        self._h = self._lib.shim_client_create(ip.encode(), port, fmt)

    def exchange(self, types, keys, tables=None, vals=None, vers=None,
                 ords=None, timeout_ms: int = 1000):
        """Send n requests, wait for n replies. Returns dict of reply arrays
        (count may be < n on timeout; see 'n')."""
        n = len(types)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        u64p = ctypes.POINTER(ctypes.c_uint64)

        def u8(x, default=None):
            if x is None:
                x = default if default is not None else np.zeros(n, np.uint8)
            return np.ascontiguousarray(x, np.uint8)

        types = u8(types)
        ords = u8(ords, np.arange(n, dtype=np.uint8))
        tables = u8(tables)
        keys = np.ascontiguousarray(keys, np.uint64)
        if vals is None:
            vals = np.zeros((n, VAL_SIZE), np.uint8)
        vals = np.ascontiguousarray(vals, np.uint8)
        vers = np.ascontiguousarray(
            vers if vers is not None else np.zeros(n), np.uint32)

        r_ord = np.zeros(n, np.uint8)
        r_type = np.zeros(n, np.uint8)
        r_table = np.zeros(n, np.uint8)
        r_key = np.zeros(n, np.uint64)
        r_val = np.zeros((n, VAL_SIZE), np.uint8)
        r_ver = np.zeros(n, np.uint32)
        got = self._lib.shim_client_exchange(
            self._h, n, ords.ctypes.data_as(u8p), types.ctypes.data_as(u8p),
            tables.ctypes.data_as(u8p), keys.ctypes.data_as(u64p),
            vals.ctypes.data_as(u8p), vers.ctypes.data_as(u32p),
            r_ord.ctypes.data_as(u8p), r_type.ctypes.data_as(u8p),
            r_table.ctypes.data_as(u8p), r_key.ctypes.data_as(u64p),
            r_val.ctypes.data_as(u8p), r_ver.ctypes.data_as(u32p),
            timeout_ms)
        return {"n": got, "ord": r_ord[:got], "type": r_type[:got],
                "table": r_table[:got], "key": r_key[:got],
                "val": r_val[:got], "ver": r_ver[:got]}

    def close(self):
        if self._h:
            self._lib.shim_client_destroy(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
