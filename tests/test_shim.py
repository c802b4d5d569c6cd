"""End-to-end tests of the native host shim (C++ UDP pump) over loopback:
reference-wire-format requests in, engine-certified replies out. This is
the cross-layer test the reference runs only on a real cluster (SURVEY.md
§4.3); here the whole L0->L2 path runs in-process over 127.0.0.1."""
import numpy as np
import pytest

from dint_tpu.engines import lock2pl, logsrv, store
from dint_tpu.shim import (FMT_LOCK6, FMT_LOG53, LOCK2PL, LOG, STORE,
                           EnginePump, ShimClient)
from dint_tpu.tables import kv, locks
from dint_tpu.tables import log as logring


def _warm(pump, fmt=None):
    """Absorb the pump's first XLA compile before the test's short-timeout
    exchanges: under full-suite CPU load the first step can take >5s, which
    otherwise shows up as a flaky 0-reply timeout."""
    kw = {} if fmt is None else {"fmt": fmt}
    with ShimClient("127.0.0.1", pump.port, **kw) as c:
        for _ in range(12):
            r = c.exchange(np.zeros(1, np.uint8),
                           np.array([1], np.uint64), timeout_ms=10_000)
            if r["n"] == 1:
                return
    raise RuntimeError("pump did not answer warmup exchanges")


@pytest.fixture
def store_pump():
    table = kv.create(1 << 8, val_words=10)
    with EnginePump(STORE, store.step, table, width=256,
                    flush_us=2000).start() as p:
        _warm(p)
        yield p


def test_store_wire_roundtrip(store_pump):
    with ShimClient("127.0.0.1", store_pump.port) as c:
        n = 32
        keys = np.arange(1, n + 1, dtype=np.uint64)
        vals = np.zeros((n, 40), np.uint8)
        vals[:, 0] = np.arange(n)
        vals[:, 1] = 0xAB  # magic-byte convention, store/caladan/client_caladan.cc:160
        # INSERT (wire type 2) everything in one exchange
        r = c.exchange(np.full(n, 2, np.uint8), keys, vals=vals,
                       timeout_ms=5000)
        assert r["n"] == n
        assert (r["type"] == 8).all()  # INSERT_ACK
        # READ (wire type 0) them back
        r = c.exchange(np.zeros(n, np.uint8), keys, timeout_ms=5000)
        assert r["n"] == n
        assert (r["type"] == 3).all()  # GRANT_READ
        got = {int(k): (v[0], v[1]) for k, v in zip(r["key"], r["val"])}
        for i, k in enumerate(keys):
            assert got[int(k)] == (i, 0xAB)
        # READ a missing key -> NOT_EXIST (7)
        r = c.exchange(np.zeros(1, np.uint8), np.array([999], np.uint64),
                       timeout_ms=5000)
        assert r["n"] == 1 and r["type"][0] == 7


def test_store_set_bumps_version(store_pump):
    with ShimClient("127.0.0.1", store_pump.port) as c:
        key = np.array([7], np.uint64)
        c.exchange(np.array([2], np.uint8), key, timeout_ms=5000)  # INSERT
        r1 = c.exchange(np.array([1], np.uint8), key, timeout_ms=5000)  # SET
        assert r1["type"][0] == 5  # SET_ACK
        r2 = c.exchange(np.array([0], np.uint8), key, timeout_ms=5000)  # READ
        assert r2["ver"][0] == r1["ver"][0]
        assert r2["ver"][0] >= 1


def test_lock2pl_wire(rng):
    table = locks.create_sx(1 << 10)
    with EnginePump(LOCK2PL, lock2pl.step, table, width=64,
                    flush_us=2000).start() as p:
        with ShimClient("127.0.0.1", p.port, fmt=FMT_LOCK6) as c:
            lid = np.array([42], np.uint64)
            # ACQUIRE (0) shared (table byte 0) -> GRANT_LOCK (2)
            r = c.exchange(np.zeros(1, np.uint8), lid, timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 2
            # ACQUIRE exclusive (table byte 1) on same lid -> REJECT_LOCK (3)
            r = c.exchange(np.zeros(1, np.uint8), lid,
                           tables=np.ones(1, np.uint8), timeout_ms=5000)
            assert r["type"][0] == 3
            # RELEASE (1) shared -> RELEASE_ACK (5); then X grant succeeds
            r = c.exchange(np.ones(1, np.uint8), lid, timeout_ms=5000)
            assert r["type"][0] == 5
            r = c.exchange(np.zeros(1, np.uint8), lid,
                           tables=np.ones(1, np.uint8), timeout_ms=5000)
            assert r["type"][0] == 2


def test_log_wire(rng):
    ring = logring.create(4, 1 << 8, val_words=10)
    with EnginePump(LOG, logsrv.step, ring, width=64,
                    flush_us=2000).start() as p:
        with ShimClient("127.0.0.1", p.port, fmt=FMT_LOG53) as c:
            n = 16
            keys = rng.integers(0, 1000, n).astype(np.uint64)
            vals = rng.integers(0, 256, (n, 40)).astype(np.uint8)
            r = c.exchange(np.zeros(n, np.uint8), keys, vals=vals,
                           vers=np.arange(n, dtype=np.uint32),
                           timeout_ms=5000)
            assert r["n"] == n
            assert (r["type"] == 1).all()  # ACK


def test_pump_batches_full_width():
    """A single exchange wider than flush granularity still round-trips."""
    table = kv.create(1 << 10, val_words=10)
    with EnginePump(STORE, store.step, table, width=512,
                    flush_us=1000).start() as p:
        with ShimClient("127.0.0.1", p.port) as c:
            n = 512
            keys = np.arange(1, n + 1, dtype=np.uint64)
            r = c.exchange(np.full(n, 2, np.uint8), keys, timeout_ms=10000)
            assert r["n"] == n
            assert (r["type"] == 8).all()
        assert p.server.stats()["pkts_rx"] >= n


def test_smallbank_wire_lock_commit_roundtrip(rng):
    """SmallBank over the reference 55-byte wire format: fused X-lock+read
    grants with the balance, COMMIT_PRIM installs + releases, re-lock sees
    the new balance (smallbank/caladan/proto.h:14-37 type codes)."""
    from dint_tpu.clients.smallbank_client import init_shards
    from dint_tpu.clients import workloads as wl
    from dint_tpu.engines import smallbank
    from dint_tpu.shim import SMALLBANK

    shard = init_shards(64, init_balance=100)[0]
    with EnginePump(SMALLBANK, smallbank.step, shard, width=128,
                    flush_us=2000, val_words=2).start() as p:
        _warm(p)
        with ShimClient("127.0.0.1", p.port) as c:
            # kAcquireExclusive (1) on SAVINGS acct 7: grant carries balance
            r = c.exchange(np.array([1], np.uint8),
                           np.array([7], np.uint64),
                           tables=np.array([smallbank.SAVINGS], np.uint8),
                           timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 9      # kGrantExclusive
            bal = int(np.frombuffer(r["val"][0][:4].tobytes(),
                                    np.uint32)[0])
            assert bal == 100
            # kCommitPrim (4) installs bal 250; release is the
            # coordinator's SEPARATE final kReleaseExclusive phase
            # (smallbank/caladan/proto.h:19-20) — the row stays X-held,
            # asserted by the REJECT below
            nv = np.zeros((1, 40), np.uint8)
            nv[0, :4] = np.frombuffer(np.uint32(250).tobytes(), np.uint8)
            nv[0, 4:8] = np.frombuffer(np.uint32(wl.SB_MAGIC).tobytes(),
                                       np.uint8)
            r = c.exchange(np.array([4], np.uint8),
                           np.array([7], np.uint64), vals=nv,
                           vers=np.array([2], np.uint32),
                           tables=np.array([smallbank.SAVINGS], np.uint8),
                           timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 13     # kCommitPrimAck
            # while still X-held, a second acquire REJECTS (type 10)
            r = c.exchange(np.array([1], np.uint8),
                           np.array([7], np.uint64),
                           tables=np.array([smallbank.SAVINGS], np.uint8),
                           timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 10     # kRejectExclusive
            # kReleaseExclusive (3): the coordinator's final phase
            # (lock -> log x3 -> bck x2 -> prim -> RELEASE,
            #  client_ebpf_shard.cc:389-560)
            r = c.exchange(np.array([3], np.uint8),
                           np.array([7], np.uint64),
                           tables=np.array([smallbank.SAVINGS], np.uint8),
                           timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 12     # kReleaseExclusiveAck
            # re-acquire: grant carries the NEW balance
            r = c.exchange(np.array([1], np.uint8),
                           np.array([7], np.uint64),
                           tables=np.array([smallbank.SAVINGS], np.uint8),
                           timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 9
            bal = int(np.frombuffer(r["val"][0][:4].tobytes(),
                                    np.uint32)[0])
            assert bal == 250


def test_tatp_wire_occ_roundtrip(rng):
    """TATP over the reference 55-byte wire format through the pump — the
    path the reference serves with tatp/udp/server_shard.cc: kRead with
    bloom-negative NOT_EXIST, kAcquireLock CAS, kCommitPrim install +
    row-lock release, kAbort release (tatp/ebpf/utils.h:38-73 codes;
    handler tatp/caladan/server_shard.cc:131-230)."""
    from dint_tpu.clients import tatp_client as tc
    from dint_tpu.engines import tatp
    from dint_tpu.shim import TATP

    shard = tc.populate_shards(np.random.default_rng(0), 64,
                               val_words=10, log_capacity=1 << 14)[0][0]
    sub = np.array([tatp.SUBSCRIBER], np.uint8)
    k5 = np.array([5], np.uint64)
    with EnginePump(TATP, tatp.step, shard, width=128,
                    flush_us=2000).start() as p:
        _warm(p)
        with ShimClient("127.0.0.1", p.port) as c:
            # kRead (0) SUBSCRIBER 5 -> kGrantRead (4) with payload + ver
            r = c.exchange(np.zeros(1, np.uint8), k5, tables=sub,
                           timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 4
            assert int(np.frombuffer(r["val"][0][:4].tobytes(),
                                     np.uint32)[0]) == 5
            ver1 = int(r["ver"][0])
            assert ver1 >= 1
            # kRead on an absent CALL_FORWARDING row -> kNotExist (6)
            r = c.exchange(np.zeros(1, np.uint8),
                           np.array([tatp.cf_key(9, 1, 0)], np.uint64),
                           tables=np.array([tatp.CALL_FORWARDING],
                                           np.uint8), timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 6
            # kAcquireLock (1) -> kGrantLock (7); a second -> kRejectLock (8)
            r = c.exchange(np.ones(1, np.uint8), k5, tables=sub,
                           timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 7
            r = c.exchange(np.ones(1, np.uint8), k5, tables=sub,
                           timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 8
            # kCommitPrim (12) installs AND releases the row lock
            # (shard_kern.c:338-476)
            nv = np.zeros((1, 40), np.uint8)
            nv[0, :4] = np.frombuffer(np.uint32(777).tobytes(), np.uint8)
            r = c.exchange(np.array([12], np.uint8), k5, vals=nv,
                           vers=np.array([ver1 + 1], np.uint32),
                           tables=sub, timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 15   # kCommitPrimAck
            # re-read: new payload, bumped version
            r = c.exchange(np.zeros(1, np.uint8), k5, tables=sub,
                           timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 4
            assert int(np.frombuffer(r["val"][0][:4].tobytes(),
                                     np.uint32)[0]) == 777
            assert int(r["ver"][0]) == ver1 + 1
            # lock free again: grant then kAbort (2) -> kAbortAck (9)
            r = c.exchange(np.ones(1, np.uint8), k5, tables=sub,
                           timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 7
            r = c.exchange(np.array([2], np.uint8), k5, tables=sub,
                           timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 9


def test_fasst_wire_occ_roundtrip(rng):
    """FaSST OCC over the 9-byte wire format {type, lid u32, ver u32}
    (lock_fasst/caladan/proto.h:32-36): READ returns the version,
    ACQUIRE_LOCK CAS grants then rejects, COMMIT bumps ver + unlocks,
    ABORT unlocks (lock_fasst/ebpf/ls_kern.c:58-97)."""
    from dint_tpu.engines import fasst
    from dint_tpu.shim import FASST, FMT_FASST9
    from dint_tpu.tables import locks

    table = locks.create_occ(1 << 10)
    lid = np.array([17], np.uint64)
    with EnginePump(FASST, fasst.step, table, width=64,
                    flush_us=2000).start() as p:
        _warm(p, fmt=FMT_FASST9)
        with ShimClient("127.0.0.1", p.port, fmt=FMT_FASST9) as c:
            # READ (0) -> GRANT_READ (4), ver 0
            r = c.exchange(np.zeros(1, np.uint8), lid, timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 4
            assert int(r["ver"][0]) == 0
            # ACQUIRE_LOCK (1) -> GRANT_LOCK (5); second -> REJECT_LOCK (6)
            r = c.exchange(np.ones(1, np.uint8), lid, timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 5
            r = c.exchange(np.ones(1, np.uint8), lid, timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 6
            # COMMIT (3) -> COMMIT_ACK (8): ver++ and unlock
            r = c.exchange(np.array([3], np.uint8), lid, timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 8
            r = c.exchange(np.zeros(1, np.uint8), lid, timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 4
            assert int(r["ver"][0]) == 1
            # lock again (freed by COMMIT), then ABORT (2) -> ABORT_ACK (7)
            r = c.exchange(np.ones(1, np.uint8), lid, timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 5
            r = c.exchange(np.array([2], np.uint8), lid, timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 7
            # and the slot is lockable again after the abort release
            r = c.exchange(np.ones(1, np.uint8), lid, timeout_ms=5000)
            assert r["n"] == 1 and r["type"][0] == 5


@pytest.mark.slow  # ~58s: heaviest wire e2e; the per-op wire roundtrips
def test_tatp_full_transactions_over_wire():  # above stay tier-1
    """FULL TATP transactions over the wire against 3 UDP shard servers —
    the reference's client/server topology (3 server processes + a
    coordinator fanning per-shard batches, client_ebpf_shard.cc:636-677)
    in-process: every phase (read+lock, validate, log x3, bck x2, prim,
    abort) crosses loopback datagrams in the 55-byte format."""
    from dint_tpu.clients import tatp_wire as tw

    with tw.serve_shards(200, width=256, flush_us=1000) as ports:
        with tw.WireCoordinator(ports, 200, width=256) as coord:
            rng = np.random.default_rng(0)
            for _ in range(3):
                coord.run_cohort(rng, 64)
            st = coord.stats
            assert st.attempted == 3 * 64
            assert st.committed > 0
            # outcome classification closes
            assert (st.committed + st.aborted_lock + st.aborted_validate
                    + st.aborted_missing + st.aborted_timeout) \
                == st.attempted
            assert st.timeout_lanes == 0    # loopback: no loss
            # population-driven miss floor is ~25% of the mix; leave slack
            # for the tiny keyspace's contention
            assert st.committed > st.attempted * 0.45


def test_tatp_wire_timeout_counts_not_raises():
    """A lossy/dead server must yield a NUMBER plus a timeout count, not a
    voided run (round-4 verdict: the reference client retries forever so
    loss shows up as latency; our capped retry budget surfaces it as
    ab_timeout txns instead of raising away the whole bench point)."""
    import socket

    from dint_tpu.clients import tatp_wire as tw

    # 3 bound-but-never-served ports: every datagram vanishes
    socks = [socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
             for _ in range(3)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    try:
        with tw.WireCoordinator(ports, 200, width=256, timeout_ms=50,
                                max_tries=2) as coord:
            st = coord.run_cohort(np.random.default_rng(0), 32)
            assert st.attempted == 32
            assert st.committed == 0
            assert st.aborted_timeout == 32       # every txn classified
            assert st.timeout_lanes > 0           # raw datagram count too
            assert (st.aborted_lock + st.aborted_validate
                    + st.aborted_missing) == 0    # no misclassification
    finally:
        for s in socks:
            s.close()
