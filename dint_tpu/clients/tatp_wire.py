"""TATP coordinator over the WIRE: full transactions against 3 UDP servers.

The reference's TATP numbers are inherently over-network: a Caladan client
coordinator fans each transaction's per-shard message batches to 3 shard
servers over UDP (tatp/caladan/client_ebpf_shard.cc:636-677, servers
tatp/udp/server_shard.cc). This module is that exact topology in-process:
three `EnginePump`s (one populated TATP shard each — real separate
"servers" with their own UDP sockets, RX batching, and jitted certify
steps) and a `WireCoordinator` that reuses the host coordinator's wave
logic (clients/tatp_client.Coordinator) with `_run_wave` rerouted through
`ShimClient` datagrams in the reference's 55-byte wire format.

Every phase of every transaction — read+lock, validate, CommitLog x3,
CommitBck x2, CommitPrim, abort — crosses the wire as datagrams, so this
is the full request -> batch -> certify -> reply path for the flagship
workload (the round-3 verdict's missing demonstration), measured by
`exp.py`'s `tatp_wire_txn` point.

Wire-format constraint: the MSG55 `ord` field is u8, so ONE SOCKET
matches at most 256 in-flight datagrams per server; waves are chunked to
that bound and replies are reordered by the echoed `ord` (UDP may
reorder). To hold more than 256 in flight per shard — the reference keeps
hundreds outstanding via per-uthread resend loops
(client_ebpf_shard.cc:643-677) — each shard gets `n_socks` independent
sockets and chunks are pipelined concurrently across them, each socket
being its own u8-ord space. Unanswered lanes retry on their own socket;
after `max_tries` the lane is marked Reply.TIMEOUT and its txn is counted
in the ab_timeout classification (the reference resends forever, so loss shows
up as latency; a capped budget must yield a number + timeout count, not a
voided run). Replies whose echoed ord/key/table do not match a STILL
OUTSTANDING request are late stragglers from a timed-out try and are
discarded (the reference's `assert(msg.key == key)` pattern).
Shared-with-reference hazard: a retried OCC_LOCK whose original GRANT
reply was lost re-sends against its own server-side lock and reads
REJECT — a UDP request/reply protocol cannot distinguish that from a
true conflict (the reference's NetHandshake loop has the same exposure);
on loopback, reply loss is effectively nil.
"""
from __future__ import annotations

import contextlib
import threading

import numpy as np

from ..engines import tatp
from ..engines.types import Op, Reply
from ..shim import TATP, EnginePump, ShimClient
from ..shim.native import VAL_SIZE
from . import tatp_client as tc

N_SHARDS = tc.N_SHARDS
_CHUNK = 256        # u8 ord field: max matchable datagrams per exchange

# engine op -> wire request code (inverse of shim.wire.TATP.req_map)
_OP2WIRE = np.full(64, 255, np.uint8)
for _w, _op in enumerate(TATP.req_map):
    if _op != Op.NOP:
        _OP2WIRE[_op] = _w

# (wire request, wire reply) -> engine Reply code (inverse of rep_map)
_WIRE2REP = np.full((64, 256), Reply.NONE, np.int32)
for _w in range(TATP.rep_map.shape[0]):
    for _r in range(TATP.rep_map.shape[1]):
        _code = TATP.rep_map[_w, _r]
        if _code >= 0:
            _WIRE2REP[_w, _code] = _r


@contextlib.contextmanager
def serve_shards(n_subscribers: int, width: int = 1024, val_words: int = 10,
                 flush_us: int = 500, seed: int = 0, **kw):
    """Start 3 shard servers (reference topology: one process per shard,
    tatp/udp/server_shard.cc) on loopback UDP; yields their ports."""
    shards, _ = tc.populate_shards(np.random.default_rng(seed),
                                   n_subscribers, val_words=val_words, **kw)
    pumps = []
    try:
        for s in shards:
            pumps.append(EnginePump(TATP, tatp.step, s, width=width,
                                    flush_us=flush_us,
                                    val_words=val_words).start())
        yield [p.port for p in pumps]
    finally:
        for p in pumps:
            p.close()


class WireCoordinator(tc.Coordinator):
    """tc.Coordinator with every wave crossing the wire to 3 UDP servers.

    Inherits the whole transaction state machine (run_cohort: mix/NURand
    generation, wave structure, abort classes, magic asserts) — only the
    transport differs, exactly like the reference's client_udp vs
    client_caladan variants share their txn logic."""

    def __init__(self, ports, n_subscribers: int, width: int = 4096,
                 val_words: int = 10, host: str = "127.0.0.1",
                 timeout_ms: int = 10_000, max_tries: int = 8,
                 n_socks: int = 4):
        # no local shards: state lives behind the sockets
        self.p = n_subscribers
        self.width = width
        self.vw = val_words
        self.attr = False
        self.stats = tc.Stats()
        self.timeout_ms = timeout_ms
        self.max_tries = max_tries
        # n_socks sockets per shard: each is an independent u8-ord space,
        # so a shard holds up to n_socks*256 requests in flight
        self.clients = [[ShimClient(host, p) for _ in range(n_socks)]
                        for p in ports]

    def close(self):
        for socks in self.clients:
            for c in socks:
                c.close()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()

    def _exchange_chunk(self, client, chunk, lo, ops, tbls, keys, vals,
                        vers, rt, rv, rver, wire_req) -> int:
        """One <=256-lane chunk on one socket: send, reorder replies by
        echoed ord, retry unanswered lanes. Writes this chunk's disjoint
        slice of rt/rv/rver; returns the number of timed-out lanes."""
        pend = chunk
        for _ in range(self.max_tries):
            if len(pend) == 0:
                return 0
            wv = np.zeros((len(pend), VAL_SIZE), np.uint8)
            wv[:, : self.vw * 4] = np.ascontiguousarray(
                vals[pend, : self.vw].astype(np.uint32)
            ).view(np.uint8).reshape(len(pend), -1)
            # ords are STABLE across retries (lane's position within
            # its original chunk), so a straggler reply from an
            # earlier try always maps back to the lane that sent it —
            # per-try renumbering could mis-credit a same-key lane
            r = client.exchange(
                wire_req[pend], keys[pend].astype(np.uint64),
                tables=tbls[pend].astype(np.uint8), vals=wv,
                vers=vers[pend].astype(np.uint32),
                ords=(pend - lo).astype(np.uint8),
                timeout_ms=self.timeout_ms)
            n = r["n"]
            if n == 0:
                continue
            # ord -> lane within the chunk; sanity-check the echoed
            # key/table against what that lane sent (the reference's
            # assert(msg.key == key) pattern) and drop mismatches
            ordv = r["ord"][:n].astype(np.int64)
            ok = ordv < len(chunk)
            cand = chunk[np.where(ok, ordv, 0)]
            ok &= (r["key"][:n] == keys[cand].astype(np.uint64)) \
                & (r["table"][:n] == tbls[cand].astype(np.uint8))
            # a straggler whose lane was ALREADY answered by a later try
            # must not clobber the recorded reply (for OCC_LOCK it could
            # arbitrarily flip GRANT/REJECT attribution)
            ok &= np.isin(cand, pend)
            idx = cand[ok]
            if len(idx):
                sel_n = np.nonzero(ok)[0]
                rt[idx] = _WIRE2REP[wire_req[idx], r["type"][:n][sel_n]]
                got_v = r["val"][:n][sel_n].reshape(len(sel_n), VAL_SIZE)
                rv[idx] = np.ascontiguousarray(
                    got_v[:, : self.vw * 4]).view(np.uint32).reshape(
                        len(sel_n), -1)
                rver[idx] = r["ver"][:n][sel_n]
                pend = pend[~np.isin(pend, idx)]
        # resend budget exhausted: surface as a counted timeout, not a
        # voided run (run_cohort classifies these txns as ab_timeout)
        rt[pend] = Reply.TIMEOUT
        return len(pend)

    def _exchange_shard(self, s, ops, tbls, keys, vals, vers):
        """One shard's lanes: chunk to the u8-ord bound and pipeline the
        chunks concurrently across the shard's sockets (each socket = one
        independent ord space; exchange blocks in C with the GIL released,
        so the chunks genuinely overlap on the wire)."""
        m = len(ops)
        rt = np.full(m, Reply.NONE, np.int32)
        rv = np.zeros((m, self.vw), np.uint32)
        rver = np.zeros(m, np.uint32)
        wire_req = _OP2WIRE[ops]
        chunks = [(lo, np.arange(lo, min(lo + _CHUNK, m)))
                  for lo in range(0, m, _CHUNK)]
        socks = self.clients[s]
        timeouts = [0] * len(socks)

        def worker(wi):
            # socket wi serves chunks wi, wi+n_socks, ... serially; other
            # sockets run their share concurrently
            for ci in range(wi, len(chunks), len(socks)):
                lo, chunk = chunks[ci]
                timeouts[wi] += self._exchange_chunk(
                    socks[wi], chunk, lo, ops, tbls, keys, vals, vers,
                    rt, rv, rver, wire_req)

        if len(chunks) == 1:
            worker(0)
        else:
            ts = [threading.Thread(target=worker, args=(wi,))
                  for wi in range(min(len(socks), len(chunks)))]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        return rt, rv, rver, sum(timeouts)

    def _run_wave(self, ops, tbls, keys, shard_of=None, vals=None,
                  vers=None):
        m = len(ops)
        rt = np.full(m, Reply.NONE, np.int32)
        rv = np.zeros((m, self.vw), np.uint32)
        rver = np.zeros(m, np.uint32)
        if vals is None:
            vals = np.zeros((m, self.vw), np.uint32)
        if vers is None:
            vers = np.zeros(m, np.uint32)
        if shard_of is None:
            shard_of = keys % N_SHARDS
        active = ops != Op.NOP
        # concurrent per-shard fan-out, like the reference's 3 coordinator
        # threads (client_ebpf_shard.cc:636-677): exchange blocks in C
        # (GIL released), so the 3 server round-trips overlap
        errs = []
        tmo = [0] * N_SHARDS

        def one(s, idx):
            try:
                srt, srv, srver, stmo = self._exchange_shard(
                    s, ops[idx], tbls[idx], keys[idx], vals[idx],
                    vers[idx])
                rt[idx] = srt
                rv[idx] = srv
                rver[idx] = srver
                tmo[s] = stmo
            except Exception as e:      # surfaced after join
                errs.append(e)

        threads = []
        for s in range(N_SHARDS):
            idx = np.nonzero(active & (shard_of == s))[0]
            if len(idx):
                threads.append(threading.Thread(target=one, args=(s, idx)))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise errs[0]
        self.stats.timeout_lanes += sum(tmo)  # after join: single-threaded
        return rt, rv, rver
