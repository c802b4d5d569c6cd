"""Device-fused SmallBank transaction pipeline: whole txns in one jitted step.

Companion to engines/tatp_pipeline.py for the SmallBank workload. The
reference's client coordinator (smallbank/caladan/client_ebpf_shard.cc)
drives each txn through the wave pipeline

  fused X/S lock+read at primaries  ->  compute  ->  CommitLog x3 shards
  ->  CommitBck x2 backups  ->  CommitPrim  ->  Release granted locks

(:389-560; abort path releases granted locks, :330-370). The host
coordinator port (clients/smallbank_client.py) keeps that wave structure but
pays a host<->device RTT per wave. Here the entire cohort — on-device
workload generation (mix 15/15/15/25/15/15, 90%-hot-set skew,
smallbank/caladan/smallbank.h:16-18,29-50,63-69), shard routing, both
certification waves, replication fan-out, balance logic, and abort
accounting — runs inside one jitted function over the 3 stacked shard
replicas (vmapped smallbank.step), with a lax.scan running many cohorts per
dispatch. Host traffic per block is one RNG key in, one stats matrix out.

Wave structure per cohort (2 vmapped steps):
  wave 1  [3w lanes]  fused ACQ_{S,X}_READ at owner shards (up to 3 lock
                      slots per txn)
  wave 2  [9w lanes]  log block (COMMIT_LOG on all shards) + role block
                      (COMMIT_PRIM at owner / COMMIT_BCK at backups) +
                      release block (REL_X/REL_S of every granted lock,
                      committed or aborted, at owners)

Intra-cohort lock conflicts are real concurrency: two txns in one cohort
contending on an account resolve exactly like the reference's no-wait 2PL
(first-in-lane-order wins, rest REJECT -> txn aborts), so the abort rate
responds to skew/contention.

Stats additionally track the signed sum of balance deltas written by
committed txns (STAT_BAL_DELTA) so a bench window can check the
balance-conservation invariant without fetching the tables.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..clients import workloads as wl
from ._memo import memoize_builder
from ..monitor import counters as mon
from ..monitor import waves
from . import smallbank
from .types import Batch, Op, PAD_KEY, Reply

I32 = jnp.int32
U32 = jnp.uint32

N_SHARDS = 3
L = 3                  # lock slots per txn
AMT = 5                # fixed amount for deposit/send_payment/write_check
TS_AMT_MAX = 20        # transact_saving samples a SIGNED amount in
                       # [-TS_AMT_MAX, TS_AMT_MAX]: withdrawals can overdraw
                       # (esp. after amalgamate zeroes a hot savings row),
                       # making the negative-balance logic abort a live path
MAGIC = wl.SB_MAGIC
VW = 2                 # word0 = balance (i32 bits), word1 = magic

# stats vector layout
STAT_ATTEMPTED = 0
STAT_COMMITTED = 1
STAT_AB_LOCK = 2
STAT_AB_LOGIC = 3
STAT_MAGIC_BAD = 4
STAT_BAL_DELTA = 5     # signed; sums the window's committed balance deltas
N_STATS = 6

_PAD32 = U32(PAD_KEY & 0xFFFFFFFF)


def create_stacked(n_accounts: int, init_balance: int = 1000) -> smallbank.Shard:
    """3 identically-populated replicas as one stacked Shard pytree
    (reference populates every record on all 3 servers,
    smallbank/ebpf/shard_user.c:74-77). Built on device: no host-side
    materialization of the 24M-account tables."""
    def one():
        s = smallbank.create(n_accounts, val_words=VW)
        val = jnp.zeros((n_accounts, VW), U32)
        val = val.at[:, 0].set(U32(init_balance))
        val = val.at[:, 1].set(U32(MAGIC))
        val = val.reshape(-1)            # flat interleaved (tables.dense)
        ver = jnp.ones((n_accounts,), U32)
        return s.replace(sav=s.sav.replace(val=val, ver=ver),
                         chk=s.chk.replace(val=val, ver=ver))

    proto = one()
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None],
                                                   (N_SHARDS,) + x.shape), proto)


def total_balance(stacked: smallbank.Shard, replica: int = 0):
    """Device-side balance sum over one replica, wrapping mod 2^32 (x64 is
    off, so i32 accumulate; conservation checks must compare DELTAS under
    the same wraparound — exact because two's-complement add is associative)."""
    vw = stacked.sav.val_words
    sav = stacked.sav.val[replica, 0::vw].astype(I32)   # word0 = balance
    chk = stacked.chk.val[replica, 0::vw].astype(I32)
    return sav.sum(dtype=I32) + chk.sum(dtype=I32)


def gen_cohort(key, w: int, n_accounts: int, hot_frac: float = wl.SB_HOT_FRAC,
               hot_prob: float = wl.SB_HOT_PROB, mix=None):
    """On-device workload generation: (ttype [w], a1 [w], a2 [w]).

    Hot-set skew per smallbank/caladan/smallbank.h:29-50: 90% of samples in
    the first 4% of the keyspace (skew/mix overridable for sweep ablations).

    One `random.bits` draw feeds every field via modular reduction — the
    reference's generators are likewise `rand() % n` (smallbank.h:29-50);
    threefry split chains + weighted `choice` measured ~2 ms per 8192-txn
    step on v5e."""
    bits = jax.random.bits(key, (w, 5), U32)
    thresh = jnp.asarray(wl.mix_thresholds(
        wl.SB_MIX if mix is None else mix))
    ttype = jnp.minimum(
        jnp.searchsorted(thresh, bits[:, 0], side="right"), 5).astype(I32)
    hot_n = max(int(n_accounts * hot_frac), 1)
    hot_cut = U32(min(int(hot_prob * 2.0**32), 0xFFFFFFFF))

    def sample(word, coin):
        hot = (word % U32(hot_n)).astype(I32)
        uni = (word % U32(n_accounts)).astype(I32)
        return jnp.where(coin < hot_cut, hot, uni)

    a1 = sample(bits[:, 1], bits[:, 3])
    a2 = sample(bits[:, 2], bits[:, 4])
    a2 = jnp.where(a1 == a2, (a2 + 1) % n_accounts, a2)
    return ttype, a1, a2


def _lock_slots(ttype, a1, a2):
    """Per-txn lock set [w, L]: (op, table, acct) — the reference's per-txn
    lock lists (client_ebpf_shard.cc TxnAmalgamate:255 etc.)."""
    w = ttype.shape[0]
    SAV, CHK = smallbank.SAVINGS, smallbank.CHECKING
    X, S = Op.ACQ_X_READ, Op.ACQ_S_READ
    z = jnp.zeros((w,), I32)

    t = ttype
    is_am = t == wl.SB_AMALGAMATE
    is_ba = t == wl.SB_BALANCE
    is_de = t == wl.SB_DEPOSIT
    is_sp = t == wl.SB_SEND_PAYMENT
    is_ts = t == wl.SB_TRANSACT_SAVING
    is_wc = t == wl.SB_WRITE_CHECK

    # slot 0: amalgamate/transact X SAV, balance/write_check S SAV,
    # deposit/send_payment X CHK
    op0 = jnp.select([is_am | is_de | is_sp | is_ts, is_ba | is_wc], [X, S], 0)
    tb0 = jnp.where(is_de | is_sp, CHK, SAV)
    ac0 = a1
    # slot 1
    op1 = jnp.select([is_am | is_sp | is_wc, is_ba], [X, S], 0)
    tb1 = jnp.full((w,), CHK, I32)
    ac1 = jnp.where(is_sp, a2, a1)
    # slot 2
    op2 = jnp.where(is_am, X, 0)
    tb2 = jnp.full((w,), CHK, I32)
    ac2 = a2

    ops = jnp.stack([op0, op1, op2], axis=1)
    tbl = jnp.stack([tb0, tb1, tb2], axis=1)
    acc = jnp.stack([ac0, ac1, ac2], axis=1)
    return ops, tbl, acc


def compute_phase(ttype, bal, alive, ts_amt):
    """Per-txn-type balance logic, shared by every SmallBank engine
    (client_ebpf_shard.cc TxnAmalgamate:255 / TxnSendPayment:830 /
    TxnTransactSaving:1116 / TxnWriteCheck:1241 compute steps).

    bal [w, L] are the fused-read balances of the txn's lock slots.
    Returns (nw [w, L] new balances, do [w, L] slots written,
    logic_abort [w], commit [w] writes-install, committed [w])."""
    w, _ = bal.shape
    t = ttype
    b0, b1, b2 = bal[:, 0], bal[:, 1], bal[:, 2]
    nw = jnp.zeros((w, L), I32)
    do = jnp.zeros((w, L), bool)
    logic_abort = jnp.zeros((w,), bool)

    m = alive & (t == wl.SB_AMALGAMATE)
    nw = nw.at[:, 2].set(jnp.where(m, b2 + b0 + b1, nw[:, 2]))
    do = do | (m[:, None] & jnp.ones((1, L), bool))
    m = alive & (t == wl.SB_DEPOSIT)
    nw = nw.at[:, 0].set(jnp.where(m, b0 + AMT, nw[:, 0]))
    do = do.at[:, 0].set(do[:, 0] | m)
    m = alive & (t == wl.SB_SEND_PAYMENT)
    insufficient = b0 < AMT
    logic_abort |= m & insufficient
    ok = m & ~insufficient
    nw = nw.at[:, 0].set(jnp.where(ok, b0 - AMT, nw[:, 0]))
    nw = nw.at[:, 1].set(jnp.where(ok, b1 + AMT, nw[:, 1]))
    do = do.at[:, 0].set(do[:, 0] | ok)
    do = do.at[:, 1].set(do[:, 1] | ok)
    m = alive & (t == wl.SB_TRANSACT_SAVING)
    neg = (b0 + ts_amt) < 0
    logic_abort |= m & neg
    ok = m & ~neg
    nw = nw.at[:, 0].set(jnp.where(ok, b0 + ts_amt, nw[:, 0]))
    do = do.at[:, 0].set(do[:, 0] | ok)
    m = alive & (t == wl.SB_WRITE_CHECK)
    overdraw = (b0 + b1) < AMT
    nw = nw.at[:, 1].set(jnp.where(
        m, b1 - AMT - jnp.where(overdraw, 1, 0), nw[:, 1]))
    do = do.at[:, 1].set(do[:, 1] | m)

    commit = alive & ~logic_abort & (t != wl.SB_BALANCE)
    committed = commit | (alive & (t == wl.SB_BALANCE))
    return nw, do, logic_abort, commit, committed


def _broadcast_batch(op_s, table, key_lo, val, ver):
    s = op_s.shape[0]

    def bc(x):
        return jnp.broadcast_to(x[None], (s,) + x.shape)

    return Batch(op=op_s, table=bc(table),
                 key_hi=bc(jnp.zeros_like(key_lo)), key_lo=bc(key_lo),
                 val=bc(val), ver=bc(ver))


def _merge(owner, stacked):
    r = owner.shape[0]
    return stacked[owner, jnp.arange(r)]


def cohort_step(stacked: smallbank.Shard, key, *, w: int, n_accounts: int,
                counters: mon.Counters | None = None):
    """One full cohort of w txns against the 3 stacked replicas.
    Returns (stacked', stats [N_STATS] i32), with the updated Counters
    appended when the dintmon plane is threaded (``counters``)."""
    step_v = jax.vmap(smallbank.step)
    kgen, kamt = jax.random.split(key)
    with waves.scope("smallbank_pipeline", "gen"):
        ttype, a1, a2 = gen_cohort(kgen, w, n_accounts)
        ts_amt = jax.random.randint(kamt, (w,), -TS_AMT_MAX,
                                    TS_AMT_MAX + 1, dtype=I32)
        l_op, l_tb, l_ac = _lock_slots(ttype, a1, a2)     # [w, L]
    r = w * L

    lane_op = l_op.reshape(r)
    lane_tbl = l_tb.reshape(r)
    lane_acc = l_ac.reshape(r)
    used = lane_op != 0
    lane_key = jnp.where(used, lane_acc.astype(U32), _PAD32)
    owner = (lane_acc % N_SHARDS).astype(I32)
    sid = jnp.arange(N_SHARDS, dtype=I32)

    zval = jnp.zeros((r, VW), U32)
    zver = jnp.zeros((r,), U32)

    # ---- wave 1: fused lock+read at owners ---------------------------------
    with waves.scope("smallbank_pipeline", "wave1"):
        op_s = jnp.where((owner[None] == sid[:, None]) & used[None],
                         lane_op[None], Op.NOP)
        stacked, rep1 = step_v(stacked, _broadcast_batch(
            op_s, lane_tbl, lane_key, zval, zver))
        rt1 = _merge(owner, rep1.rtype).reshape(w, L)
        rv1 = _merge(owner, rep1.val)                     # [r, VW]
        rver1 = _merge(owner, rep1.ver).reshape(w, L)

        active = l_op != 0
        granted = active & (rt1 == Reply.GRANT)
        magic_bad = jnp.sum(granted.reshape(r) & (rv1[:, 1] != MAGIC),
                            dtype=I32)
        lock_rejected = (active & (rt1 == Reply.REJECT)).any(axis=1)
        alive = ~lock_rejected

        bal = jnp.where(granted,
                        rv1[:, 0].reshape(w, L).astype(I32), 0)  # [w, L]

    with waves.scope("smallbank_pipeline", "compute"):
        nw, do, logic_abort, commit, committed = compute_phase(
            ttype, bal, alive, ts_amt)
        do_write = do & commit[:, None] & active          # [w, L]
        bal_delta = jnp.sum(jnp.where(do_write, nw - bal, 0), dtype=I32)

    # ---- wave 2: log x3 + role (prim/bck) + release ------------------------
    with waves.scope("smallbank_pipeline", "wave2"):
        c_val = jnp.zeros((r, VW), U32)
        c_val = c_val.at[:, 0].set(nw.reshape(r).astype(U32))
        c_val = c_val.at[:, 1].set(jnp.where(do_write.reshape(r),
                                             U32(MAGIC), U32(0)))
        c_ver = jnp.where(do_write, rver1 + 1, 0).reshape(r).astype(U32)
        dwf = do_write.reshape(r)
        c_key = jnp.where(dwf, lane_acc.astype(U32), _PAD32)

        log_op = jnp.where(dwf, Op.COMMIT_LOG, Op.NOP)    # all shards
        role_s = jnp.where(dwf[None],
                           jnp.where(owner[None] == sid[:, None],
                                     Op.COMMIT_PRIM, Op.COMMIT_BCK),
                           Op.NOP)                         # [S, r]

        relf = granted.reshape(r)
        rel_op = jnp.where(lane_op == Op.ACQ_X_READ, Op.REL_X, Op.REL_S)
        rel_s = jnp.where(relf[None] & (owner[None] == sid[:, None]),
                          rel_op[None], Op.NOP)            # [S, r]
        rel_key = jnp.where(relf, lane_acc.astype(U32), _PAD32)

        lane2_key = jnp.concatenate([c_key, c_key, rel_key])
        lane2_tbl = jnp.concatenate([lane_tbl, lane_tbl, lane_tbl])
        lane2_val = jnp.concatenate([c_val, c_val, jnp.zeros((r, VW), U32)])
        lane2_ver = jnp.concatenate([c_ver, c_ver, jnp.zeros((r,), U32)])
        op2_s = jnp.concatenate([
            jnp.broadcast_to(log_op[None], (N_SHARDS, r)), role_s, rel_s],
            axis=1)
        stacked, _ = step_v(stacked, _broadcast_batch(
            op2_s, lane2_tbl, lane2_key, lane2_val, lane2_ver))

    stats = jnp.stack([
        jnp.asarray(w, I32),
        committed.sum(dtype=I32),
        lock_rejected.sum(dtype=I32),
        logic_abort.sum(dtype=I32),
        magic_bad,
        bal_delta,
    ])
    if counters is not None:
        counters = mon.bump(counters, {
            mon.CTR_STEPS: 1,
            mon.CTR_TXN_ATTEMPTED: stats[STAT_ATTEMPTED],
            mon.CTR_TXN_COMMITTED: stats[STAT_COMMITTED],
            mon.CTR_AB_LOCK: stats[STAT_AB_LOCK],
            mon.CTR_AB_LOGIC: stats[STAT_AB_LOGIC],
            mon.CTR_MAGIC_BAD: magic_bad,
            mon.CTR_LOCK_REQUESTS: active.sum(dtype=I32),
            mon.CTR_LOCK_GRANTED: granted.sum(dtype=I32),
            mon.CTR_LOCK_REJECTED: (active & ~granted).sum(dtype=I32),
            mon.CTR_INSTALL_WRITES: do_write.sum(dtype=I32),
            mon.CTR_LOG_APPENDS: do_write.sum(dtype=I32),
        })
        return stacked, stats, counters
    return stacked, stats


@memoize_builder
def build_runner(n_accounts: int, w: int = 4096,
                 cohorts_per_block: int = 8, monitor: bool = False):
    """jit(scan(cohort_step)): one dispatch runs `cohorts_per_block` cohorts.

    Returns run(stacked, key) -> (stacked', stats [cohorts_per_block, N_STATS]).
    State is donated — tables update in place in HBM.

    ``monitor``: thread the dintmon counter plane — the carry becomes
    (stacked, counters) and run returns it updated; off (default) =
    contract and jaxpr unchanged.
    """
    step = functools.partial(cohort_step, w=w, n_accounts=n_accounts)

    def scan_fn(carry, key):
        if monitor:
            stacked, cnt = carry
            stacked, stats, cnt = step(stacked, key, counters=cnt)
            return (stacked, cnt), stats
        stacked, stats = step(carry, key)
        return stacked, stats

    def block(carry, key):
        keys = jax.random.split(key, cohorts_per_block)
        return jax.lax.scan(scan_fn, carry, keys)

    return jax.jit(block, donate_argnums=0)
