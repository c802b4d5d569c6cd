"""The KV store on one chip: engines/store.py over the two-choice bucketed
hash table tables/kv.py, the whole key space in one device's HBM, one copy
of the data, no log and no replica (the source has none).

The table is populated on the device through the engine's own INSERT path
(``store.build_populate``) and served by the engine's normal path
(``store.build_serve_runner``: ``scan(step . gen)``, ``monitor=True``).
Nothing table-sized leaves the device, in any phase.

What ``verify`` can hold without the window's history ever being kept. A
dispatch's batches can be made again from its key (``store.
build_generator``, run alone), so the deployment keeps the keys it was
handed and, in ``verify``, adds every update lane of every dispatch of the
phase into a count per key on the device (``n_keys`` words; a scatter-add
that shares nothing with the engine and lives across warm-up and window).
An update's value is a function of (key, step), so a key's lawful final
state is known from that count and the last step that updated it: version
``1 + count[key]``, value what that step's lanes carried.

The store has ONE copy of the data. The three ``acked_writes_read_back_
from_*`` checks of a phase are three routes to that copy, not three
replicas, and are named for the route: ``engine_get`` (GET lanes through a
jit of ``store.step`` alone), ``table_rows`` (the deployment's own search
of the two candidate buckets in the table's arrays), ``every_live_entry``
(a sweep of the whole table on the device).

Against independent code (benchmarks/references/store.py, a dict, which
shares nothing with the engine but the batches it is handed):
``compare_small`` at a small size in the traced run, every reply of every
lane and the final table; and in every run the warm-up dispatches of the
timed program itself, at the deployment's scale: the totals of every
stats column (both reply checksums: every value and version a GET
returned), the touched rows, and the traffic against YCSB's law."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import checks as ck
from benchmarks.references import store as ref
from dint_tpu import monitor
from dint_tpu.engines import store
from dint_tpu.engines.types import Batch
from dint_tpu.ops import hashing

STAT_NAMES = ref.STAT_NAMES
assert tuple(store.STAT_NAMES) == STAT_NAMES
assert (store.Op.NOP, store.Op.GET, store.Op.SET, store.Op.INSERT,
        store.Op.DELETE) == (ref.NOP, ref.GET, ref.SET, ref.INSERT,
                             ref.DELETE)
assert (store.Reply.ACK, store.Reply.NOT_EXIST, store.Reply.VAL) == (
    ref.ACK, ref.NOT_EXIST, ref.VAL) and store.STORE_MAGIC == ref.MAGIC
OUTCOMES = ("committed", "not_exist")
FAULTS = ("spill", "retry", "magic_bad")
CONTENTION = ()     # nothing is refused for another lane's sake: reads 0
COUNTER_PAIRS = (("txn_attempted", "attempted"),
                 ("txn_committed", "committed"), ("magic_bad", "magic_bad"),
                 ("store_gets", "gets"), ("store_updates", "updates"),
                 ("store_not_exist", "not_exist"),
                 ("store_spill", "spill"))
ROUTES = ("engine_get", "table_rows", "every_live_entry")
GUARANTEE_CHECKS = (*(f"acked_writes_read_back_from_{r}" for r in ROUTES),
                    "populate_spilled_nothing", "live_keys_unchanged")
WARMUP_CHECKS = ("stats_equal_reference", "touched_rows_equal_reference",
                 "traffic_as_configured")
COMPARE_CHECKS = ("compare.replies_equal_reference",
                  "compare.table_equals_reference",
                  "compare.stats_equal_reference")
SIGMAS = 6.0
SWEEP_CHUNK = 1 << 20       # entries a trip of the whole-table sweep
U32, I32 = jnp.uint32, jnp.int32


def runner_args(sizes: dict, params: dict) -> dict:
    """The traffic file's mix and distribution as the program takes them."""
    if params["distribution"] != "zipfian":
        raise ValueError(f"no generator for {params['distribution']!r}")
    if abs(params["read"] + params["update"] - 1.0) > 1e-9:
        raise ValueError("the mix is GET and update alone: read + update "
                         f"== 1, not {params['read']} + {params['update']}")
    return dict(val_words=sizes["val_words"], read_frac=params["read"],
                theta=params["theta"])


def cohort_program(n_keys: int, w: int, cohorts_per_block: int, args: dict):
    """block key -> Batch with a leading [cohorts_per_block] axis: the
    batches a block of the runner generates from that key, again (a block
    splits its key into one per step; a step hands its key to ``gen``)."""
    gen = store.build_generator(n_keys, w, **args)
    return jax.jit(lambda key: jax.vmap(gen)(
        jax.random.split(key, cohorts_per_block)))


def value_words(key, stamp):
    """Words 3.. of the record an update of ``key`` writes under ``stamp``
    (engines/store.py ``stamped_value``, written again: murmur3's
    finalizer over key * 0x9E3779B1 + stamp * 0x7FEB352D + j). ``key``,
    ``stamp``: u32 [m]; returns a function of the word index j."""
    def word(j: int):
        h = key * U32(0x9E3779B1) + stamp * U32(0x7FEB352D) + U32(j)
        h = (h ^ (h >> U32(16))) * U32(0x85EBCA6B)
        h = (h ^ (h >> U32(13))) * U32(0xC2B2AE35)
        return h ^ (h >> U32(16))
    return word


# ------------------------------------------------------- the traffic's law


class ZipfLaw:
    """What YCSB's ZipfianGenerator draws over [1, n], in float64: keys 1
    and 2 are the Zipfian's own (1 / zetan, 0.5^theta / zetan); beyond
    them Gray's closed form is an approximation, ``P(key <= k) = ((k /
    n)^(1 - theta) - 1 + eta) / eta``. YCSB's users get YCSB's, so that is
    the law the generator is held to, not the ideal Zipfian."""

    def __init__(self, n: int, theta: float):
        self.n, self.theta = n, theta
        zetan = 0.0
        for lo in range(1, n + 1, 1 << 20):
            i = np.arange(lo, min(lo + (1 << 20), n + 1), dtype=np.float64)
            zetan += float((i ** -theta).sum())
        self.zetan = zetan
        self.zeta2 = 1.0 + 0.5 ** theta
        self.eta = (1.0 - (2.0 / n) ** (1.0 - theta)) \
            / (1.0 - self.zeta2 / zetan)

    def cdf(self, k):
        """P(key <= k), k an int or a float64 array of ints in [0, n]."""
        k = np.asarray(k, np.float64)
        tail = ((np.maximum(k, 2.0) / self.n) ** (1.0 - self.theta) - 1.0
                + self.eta) / self.eta
        return np.where(k < 1, 0.0, np.where(
            k < 2, 1.0 / self.zetan, np.minimum(tail, 1.0)))

    def dup_lanes(self, w: int) -> tuple:
        """(mean, variance bound) of the lanes of a step of ``w`` draws
        whose key an earlier lane carries: ``w`` less the distinct keys,
        whose mean is ``sum_k q_k`` with ``q_k = 1 - (1 - p_k)^w``. The
        occupancy indicators are negatively correlated, so the variance
        of their sum is at most ``sum_k q_k (1 - q_k)``."""
        mean = var = 0.0
        for lo in range(1, self.n + 1, 1 << 20):
            k = np.arange(lo, min(lo + (1 << 20), self.n + 1),
                          dtype=np.float64)
            p = self.cdf(k) - self.cdf(k - 1)
            q = -np.expm1(w * np.log1p(-p))
            mean += float(q.sum())
            var += float((q * (1.0 - q)).sum())
        return w - mean, var


class TrafficTally:
    """What the generator drew in a phase, against the traffic file: the
    read share, the shares of key 1, key 2, keys 3-10 and of the first
    1 % of the keys, each within six standard deviations of its binomial
    mean under ``ZipfLaw``; every key in [1, n]; and the mean of
    ``store_dup_lanes`` a step within six standard deviations (``ZipfLaw.
    dup_lanes``'s bound, a step's draws being independent of another's)
    plus 0.5 % of the mean for what float32 does to the tail's ranks."""

    def __init__(self, n_keys: int, w: int, read: float, theta: float):
        self.n_keys, self.w, self.read = n_keys, w, read
        self.law = ZipfLaw(n_keys, theta)
        self.head = max(n_keys // 100, 10)
        self.lanes = self.gets = self.steps = 0
        self.outside = 0
        self.keys = np.zeros(4, np.int64)   # 1, 2, 3-10, first 1 %

    def add(self, ops: np.ndarray, klo: np.ndarray) -> None:
        klo = klo.astype(np.int64).ravel()
        self.lanes += klo.size
        self.steps += ops.shape[0]
        self.gets += int((ops == ref.GET).sum())
        self.outside += int(((klo < 1) | (klo > self.n_keys)).sum())
        self.keys += [int((klo == 1).sum()), int((klo == 2).sum()),
                      int(((klo >= 3) & (klo <= 10)).sum()),
                      int((klo <= self.head).sum())]

    def within(self, count, n: int, p) -> bool:
        band = SIGMAS * np.sqrt(n * p * (1.0 - p)) + 1.0
        return bool(np.all(np.abs(count - n * p) <= band))

    def result(self, dup_lanes: int) -> dict:
        f = self.law.cdf
        p = np.array([f(1), f(2) - f(1), f(10) - f(2), f(self.head)])
        dup_mean, dup_var = self.law.dup_lanes(self.w)
        steps = max(self.steps, 1)
        dup_band = SIGMAS * np.sqrt(dup_var / steps) + 0.005 * dup_mean
        n = self.lanes
        return {"ok": n > 0 and self.outside == 0
                and self.within(self.gets, n, self.read)
                and self.within(self.keys, n, p)
                and abs(dup_lanes / steps - dup_mean) <= dup_band,
                "lanes": n, "gets": self.gets, "gets_stated": self.read * n,
                "keys_1_2_3to10_first_percent": self.keys,
                "keys_stated": p * n, "zetan": self.law.zetan,
                "outside_key_space": self.outside,
                "dup_lanes_a_step": dup_lanes / steps,
                "dup_lanes_stated": dup_mean, "dup_band": dup_band}


# ------------------------------------------- against independent code


def host_batches(block: Batch):
    """A fetched block of cohorts as the reference takes them: one (ops,
    keys, vals) per step."""
    return [(block.op[j], block.key_lo[j].astype(np.int64), block.val[j])
            for j in range(block.op.shape[0])]


def table_entries(table) -> dict:
    """{key: (value tuple, version)} of every live entry (small tables
    only: the arrays are fetched whole); a key met twice raises."""
    valid = np.asarray(table.valid)
    e = np.nonzero(valid)[0]
    assert not np.asarray(table.key_hi)[e].any()
    keys = np.asarray(table.key_lo)[e].tolist()
    vals = np.asarray(table.val).reshape(-1, table.val_words)[e].tolist()
    out = dict(zip(keys, zip(map(tuple, vals),
                             np.asarray(table.ver)[e].tolist())))
    if len(out) != len(keys):
        raise AssertionError("a key lives in two entries")
    return out


def first_difference(got: np.ndarray, want: np.ndarray) -> dict:
    if got.shape != want.shape:
        return {"shapes": [list(got.shape), list(want.shape)]}
    bad = np.argwhere((got - want) % ref.MOD != 0)
    if not len(bad):
        return {}
    row = int(bad[0][0])
    return {"row": row, "got": got[row], "want": want[row]}


def compare_small(config: dict, seed: int, checks: ck.Checks) -> None:
    """The runner at the configuration's ``compare_small`` size against
    the reference on the same batches (the generator run alone gives
    them): every reply of every lane (a second jit of ``step`` that
    returns ``Replies`` gives what the block reduces to stats), the final
    table entry for entry, and the stats rows. Exact, no band: the engine
    is deterministic and integer."""
    size = config["compare_small"]
    n, w, cpb = size["n_keys"], size["w"], size["cohorts_per_block"]
    vw, slots = config["sizes"]["val_words"], config["sizes"]["slots"]
    args = runner_args(config["sizes"], size)
    populate = store.build_populate(n, size["n_buckets"],
                                    size["populate_lanes"], val_words=vw,
                                    slots=slots)
    run, init, drain = store.build_serve_runner(
        n, w=w, cohorts_per_block=cpb, **args)
    cohorts = cohort_program(n, w, cpb, args)
    step = jax.jit(store.step)
    key = jax.random.PRNGKey(seed)
    keys = [jax.random.fold_in(key, i) for i in range(size["blocks"])]

    (table, spilled), (shadow, _) = populate(), populate()
    oracle = ref.Store(n, vw)
    carry, got, want, lanes_differing = init(table), [], [], 0
    for k in keys:
        carry, stats = run(carry, k)
        got.append(np.asarray(stats, np.int64))
        block = jax.tree.map(np.asarray, cohorts(k))
        for j, (ops, klo, vals) in enumerate(host_batches(block)):
            shadow, rep = step(shadow, jax.tree.map(lambda x: x[j], block))
            rtype, rval, rver, row = oracle.step(ops, klo, vals)
            want.append(row)
            lanes_differing += int((
                (np.asarray(rep.rtype) != rtype)
                | (np.asarray(rep.ver) != rver)
                | (np.asarray(rep.val) != rval).any(axis=1)).sum())
    table, tail = drain(carry)
    got, want = np.concatenate(got), np.stack(want)
    checks.add("compare.replies_equal_reference",
               lanes_differing == 0 and len(want) > 0,
               lanes=len(want) * w, lanes_differing=lanes_differing)
    checks.add("compare.stats_equal_reference",
               ref.equal_mod32(got, want) and not np.asarray(tail).any()
               and int(got[:, 1].sum()) > 0, steps=len(want),
               totals=got.sum(axis=0), **first_difference(got, want))
    expect = {k: ((k, ref.MAGIC) + (0,) * (vw - 2), 1)
              for k in range(1, n + 1)}
    rkeys, live, vals, vers = oracle.final_rows()
    for k, alive, val, ver in zip(rkeys.tolist(), live, vals.tolist(),
                                  vers.tolist()):
        if alive:
            expect[k] = (tuple(val), ver)
        else:
            del expect[k]
    entries, shadow_entries = table_entries(table), table_entries(shadow)
    checks.add("compare.table_equals_reference",
               entries == expect == shadow_entries and int(spilled) == 0
               and len(rkeys) > 0, keys=len(expect),
               keys_written=len(rkeys),
               keys_differing=sum(entries.get(k) != v
                                  for k, v in expect.items()),
               populate_spilled=int(spilled))


# ------------------------------------------------------- the deployment


class KVStore:
    stat_names = STAT_NAMES
    outcomes = OUTCOMES
    faults = FAULTS
    contention = CONTENTION
    depth = 1
    n_devices = 1

    def __init__(self, sizes: dict, params: dict, emit):
        self.n = n = sizes["n_keys"]
        self.vw = vw = sizes["val_words"]
        self.slots = sizes["slots"]
        self.nb = sizes["n_buckets"]
        self.w = w = params["w"]
        self.cpb = cpb = params["cohorts_per_block"]
        self.params = params
        self.txns_per_dispatch = w * cpb
        self.steps_per_dispatch = cpb

        t0 = time.perf_counter()
        table, spilled = store.build_populate(
            n, self.nb, sizes["populate_lanes"], val_words=vw,
            slots=self.slots)()
        jax.block_until_ready(table)
        self.populate_spilled = int(spilled)
        table_bytes = sum(int(x.nbytes) for x in jax.tree.leaves(table))
        self.geometry = {"w": w, "val_words": vw, "slots": self.slots,
                         "n_buckets": self.nb, "n_keys": n,
                         "table_bytes": table_bytes}
        emit(phase="populate", populate_s=time.perf_counter() - t0,
             populate_lanes=sizes["populate_lanes"],
             populate_spilled=self.populate_spilled,
             cohorts_per_block=cpb, **self.geometry)

        args = runner_args(sizes, params)
        self._run, self._init, self._drain = store.build_serve_runner(
            n, w=w, cohorts_per_block=cpb, monitor=True, **args)
        self._cohorts = cohort_program(n, w, cpb, args)
        self._table = table
        self._dispatched: list = []      # this phase's keys, 8 B each
        self._warm = True                # until the warm-up is verified
        self._count = jnp.zeros((n + 1,), U32)   # updates per key, ever
        self._programs()

    def _programs(self) -> None:
        n, vw, s, w = self.n, self.vw, self.slots, self.w
        ne = self.nb * s
        assert ne % SWEEP_CHUNK == 0 or ne < SWEEP_CHUNK
        chunk = min(SWEEP_CHUNK, ne)

        def tally(count, key):
            """The phase's update lanes, added into the count per key."""
            b = self._cohorts(key)
            return count.at[b.key_lo.reshape(-1).astype(I32)].add(
                (b.op.reshape(-1) == ref.SET).astype(U32), mode="drop")

        self._tally = jax.jit(tally, donate_argnums=0)

        @jax.jit
        def engine_get(table, klo, live):
            """GET lanes through ``store.step`` alone; the table it would
            hand back is dropped, so nothing is written or copied."""
            batch = Batch(
                op=jnp.where(live, I32(ref.GET), I32(ref.NOP)),
                table=jnp.zeros((w,), I32),
                key_hi=jnp.where(live, U32(0), U32(0xFFFFFFFF)),
                key_lo=jnp.where(live, klo, U32(0xFFFFFFFF)),
                val=jnp.zeros((w, vw), U32), ver=jnp.zeros((w,), U32))
            return store.step(table, batch)[1]

        self._engine_get = engine_get

        @jax.jit
        def candidate_rows(table, count, klo, b1, b2):
            """The 2 x S entries of each key's two candidate buckets, as
            the arrays hold them, and the key's update count."""
            e = jnp.concatenate(
                [b[:, None] * s + jnp.arange(s, dtype=I32)[None]
                 for b in (b1, b2)], axis=1)                    # [w, 2S]
            words = e[..., None] * vw + jnp.arange(vw, dtype=I32)
            return (table.valid[e], table.key_hi[e], table.key_lo[e],
                    table.ver[e], table.val[words],
                    count[jnp.minimum(klo, U32(n)).astype(I32)])

        self._candidate_rows = candidate_rows

        @jax.jit
        def sweep(table, count):
            """Every entry of the table, ``chunk`` at a time: the live
            ones counted, held to their key's update count and to the
            record an update or the populate writes, and marked in a
            table of keys seen."""
            def trip(i, acc):
                seen, sums = acc
                lo = i * chunk
                valid = jax.lax.dynamic_slice(table.valid, (lo,), (chunk,))
                khi = jax.lax.dynamic_slice(table.key_hi, (lo,), (chunk,))
                klo = jax.lax.dynamic_slice(table.key_lo, (lo,), (chunk,))
                ver = jax.lax.dynamic_slice(table.ver, (lo,), (chunk,))
                val = jax.lax.dynamic_slice(
                    table.val, (lo * vw,), (chunk * vw,)).reshape(chunk, vw)
                in_space = (khi == 0) & (klo >= 1) & (klo <= U32(n))
                idx = jnp.where(valid & in_space, klo, U32(0)).astype(I32)
                ver_ok = ver == count[idx] + U32(1)
                word = value_words(klo, val[:, 2])
                fresh = ver == 1        # never updated: as populated
                whole = (val[:, 0] == klo) & (val[:, 1] == U32(ref.MAGIC))
                for j in range(2, vw):
                    want = word(j) if j > 2 else val[:, 2]
                    whole &= val[:, j] == jnp.where(fresh, U32(0), want)
                seen = seen.at[jnp.where(valid, idx, n + 1)].add(
                    U32(1), mode="drop")
                sums = sums + jnp.stack([
                    valid.sum(dtype=I32),
                    (valid & ~in_space).sum(dtype=I32),
                    (valid & in_space & ~ver_ok).sum(dtype=I32),
                    (valid & in_space & ~whole).sum(dtype=I32),
                    (valid & in_space & ~fresh).sum(dtype=I32)])
                return seen, sums
            seen, sums = jax.lax.fori_loop(
                0, ne // chunk, trip,
                (jnp.zeros((n + 1,), U32), jnp.zeros((5,), I32)))
            once = (seen[1:] == 1).sum(dtype=I32)
            return jnp.concatenate([sums, jnp.stack([once, seen[0].astype(
                I32)])])

        self._sweep = sweep

    # ------------------------------------------------ the loop's contract

    def start(self):
        table, self._table = self._table, None
        return self._init(table)

    def restart(self, final):
        return self._init(final[0])

    def dispatch(self, carry, key):
        self._dispatched.append(np.array(key))
        return self._run(carry, key)

    def drain(self, carry):
        out = self._drain(carry)
        return out, np.asarray(out[1], np.int64)

    # ----------------------------------------------------- the read-back

    def in_lanes(self, keys: np.ndarray):
        """``keys`` in pieces of w lanes (one compiled shape): (klo [w]
        u32, live [w] bool, how many are real)."""
        for i in range(0, len(keys), self.w):
            piece = keys[i:i + self.w]
            klo = np.zeros(self.w, np.uint32)
            klo[:len(piece)] = piece
            yield klo, np.arange(self.w) < len(piece), len(piece)

    def read_by_engine(self, table, keys: np.ndarray):
        """(found [m] bool, values [m, VW], versions [m]) by GET lanes."""
        found, vals, vers = [], [], []
        for klo, live, m in self.in_lanes(keys):
            rep = jax.tree.map(np.asarray,
                               self._engine_get(table, klo, live))
            found.append(rep.rtype[:m] == ref.VAL)
            vals.append(rep.val[:m])
            vers.append(rep.ver[:m])
        return np.concatenate(found), np.concatenate(vals), \
            np.concatenate(vers)

    def read_by_rows(self, table, keys: np.ndarray):
        """The same by the deployment's own search: the two candidate
        buckets from the host's hash (numpy, ops/hashing.py: where a key
        may live is the table's layout), their 2 x S entries fetched as
        they lie, the match made here. Also (entries holding the key
        [m], the key's update count [m])."""
        found, vals, vers, copies, counts = [], [], [], [], []
        for klo, live, m in self.in_lanes(keys):
            b1, b2 = hashing.bucket_pair_np(klo.astype(np.uint64), self.nb)
            valid, khi, elo, ver, val, count = jax.tree.map(
                np.asarray, self._candidate_rows(
                    table, self._count, klo, b1.astype(np.int32),
                    b2.astype(np.int32)))
            # a key whose two candidates are one bucket sees it twice
            match = valid & (khi == 0) & (elo == klo[:, None])
            match[:, self.slots:] &= (b1 != b2)[:, None]
            at = match.argmax(axis=1)
            rows = np.arange(self.w)
            found.append(match.any(axis=1)[:m])
            copies.append(match.sum(axis=1)[:m])
            vals.append(val[rows, at][:m])
            vers.append(ver[rows, at][:m])
            counts.append(count[:m])
        return tuple(np.concatenate(x) for x in (found, vals, vers, copies,
                                                 counts))

    def verify(self, final, checks: ck.Checks, tag: str, totals: dict,
               dispatched: int) -> dict:
        table, _, counters = final
        snap = monitor.snapshot(counters)
        ck.check_accounting(checks, tag, totals, snap, dispatched, OUTCOMES,
                            FAULTS, COUNTER_PAIRS)
        keys, self._dispatched = self._dispatched, []
        for key in keys:
            self._count = self._tally(self._count, key)

        # the keys the phase's last two dispatches updated, each with the
        # record its last updating step wrote; nothing later can hide them
        last: dict = {}
        blocks = [jax.tree.map(np.asarray, self._cohorts(k))
                  for k in keys[-2:]]
        for block in blocks:
            for ops, klo, vals in host_batches(block):
                for i in np.nonzero(ops == ref.SET)[0]:
                    last[int(klo[i])] = vals[i]
        wrote = np.array(sorted(last), np.uint32)
        want_val = np.stack([last[int(k)] for k in wrote]) if len(wrote) \
            else np.zeros((0, self.vw), np.uint32)
        found_r, val_r, ver_r, copies, count = self.read_by_rows(table,
                                                                 wrote)
        want_ver = count + 1
        found_g, val_g, ver_g = self.read_by_engine(table, wrote)
        for route, found, val, ver in (("engine_get", found_g, val_g, ver_g),
                                       ("table_rows", found_r, val_r,
                                        ver_r)):
            lost = int((~found).sum() + (found & (ver < want_ver)).sum())
            differs = int((found & ((ver > want_ver) | (
                (ver == want_ver) & (val != want_val).any(axis=1)))).sum())
            checks.add(f"{tag}.acked_writes_read_back_from_{route}",
                       len(wrote) > 0 and lost == 0 and differs == 0
                       and (route != "table_rows" or bool(
                           (copies == 1).all())),
                       keys=len(wrote), lost=lost, differs=differs,
                       dispatches_read=len(blocks), copies_of_the_data=1)

        live, outside, bad_ver, torn, updated, once, stray = (
            int(x) for x in np.asarray(self._sweep(table, self._count)))
        checks.add(f"{tag}.acked_writes_read_back_from_every_live_entry",
                   live > 0 and updated > 0 and outside == 0
                   and bad_ver == 0 and torn == 0,
                   live_entries=live, entries_ever_updated=updated,
                   key_outside_the_key_space=outside,
                   version_not_one_plus_updates=bad_ver,
                   value_torn_or_misplaced=torn, copies_of_the_data=1)
        checks.add(f"{tag}.populate_spilled_nothing",
                   self.populate_spilled == 0,
                   spilled=self.populate_spilled)
        checks.add(f"{tag}.live_keys_unchanged",
                   live == self.n == once and stray == 0, live_entries=live,
                   keys_in_exactly_one_entry=once, n_keys=self.n)
        if self._warm:
            self._warm = False
            self.check_warmup(table, keys, totals, snap, checks)
        return snap

    def check_warmup(self, table, keys, totals: dict, snap: dict,
                     checks: ck.Checks) -> None:
        """The timed program's own warm-up, at the deployment's scale,
        against the reference run over the same batches from the
        populated state (it has the whole history): the totals of every
        stats column, the final state of every key it wrote, and those
        batches against the traffic file."""
        t0 = time.perf_counter()
        oracle = ref.Store(self.n, self.vw)
        tally = TrafficTally(self.n, self.w, self.params["read"],
                             self.params["theta"])
        want = np.zeros(len(STAT_NAMES), np.int64)
        for key in keys:
            block = jax.tree.map(np.asarray, self._cohorts(key))
            tally.add(block.op, block.key_lo)
            want += oracle.run(host_batches(block))[1].sum(axis=0)
        checks.add("warmup.traffic_as_configured",
                   **tally.result(snap["store_dup_lanes"]))
        got = np.array([totals[n] for n in STAT_NAMES], np.int64)
        checks.add("warmup.stats_equal_reference",
                   ref.equal_mod32(got, want) and len(keys) > 0,
                   steps=len(keys) * self.cpb, got=got, want=want)
        rkeys, live, vals, vers = oracle.final_rows()
        found, val, ver, copies, _ = self.read_by_rows(
            table, rkeys.astype(np.uint32))
        differs = int((~found | (ver != vers)
                       | (val != vals).any(axis=1)).sum())
        checks.add("warmup.touched_rows_equal_reference",
                   len(rkeys) > 0 and bool(live.all()) and differs == 0
                   and bool((copies == 1).all()), rows=len(rkeys),
                   differs=differs,
                   reference_s=time.perf_counter() - t0)


def build(config: dict, params: dict, seed: int, devices, emit,
          rehearse: bool) -> KVStore:
    sizes = config["rehearse"] if rehearse else config["sizes"]
    return KVStore(sizes, params, emit)
