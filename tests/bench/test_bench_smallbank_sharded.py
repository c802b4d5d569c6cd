"""The `smallbank24m-x4r3` configuration's own files: every PR's entries
sit where that PR appended them (what two older tests pinned to a tail
that has moved, tests/conftest.py), the configuration states the
source's scale, the plain reference answers hand-made distributed cases
and agrees with its copy and with its own cohort-at-a-time form, the
four-device program equals it, ``verify`` notices a doctored ring and a
doctored backup by the checks that should and by no other, the byte
models give hand-worked numbers, and the recorded chip trace reduces to
the metrics of its line."""
import glob
import json
import os

import jax
import numpy as np
import pytest

from benchmarks import bytes_model, bytes_model_ici_sb as ici
from benchmarks import checks as ck
from benchmarks import part_times as pt
from benchmarks import run as bench_run
from benchmarks import trace_reduce as tr
from benchmarks.deployments import smallbank_dense_sharded as dep_mod
from benchmarks.references import smallbank as one
from benchmarks.references import smallbank_sharded as ref
from dint_tpu.testing import smallbank_sharded as copy

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures", "smallbank24m-x4r3")
CELL, CONFIG = "smallbank24m-x4-sat", "smallbank24m-x4r3"
WAVE = "dint.dense_sharded_sb."
NAMES = ("step_ms", "device_idle_share", "contention_abort_share",
         "monitor_ms", "unnamed_ms", "dispatch_gap_ms", "exchange_ms",
         "arbitrate_ms", "replicate_ms", "a2a_pack_ms",
         "collective_exposed_ms", "ici_roofline_share",
         "hbm_roofline_share", "hbm_peak_gb", "xshard_txn_share",
         "remote_lock_share")
# by the variant rule, no file of their own (the last three quantities'
# files are this PR's: the older cells each carry a copy)
VARIANTS = (*NAMES[:5], "dispatch_gap_ms", "collective_exposed_ms",
            "hbm_peak_gb")
OWN_READERS = tuple(q for q in NAMES if q not in VARIANTS)
OLDER_CELLS = ["tatp7m-sat", "smallbank24m-sat", "tatp7m-x4-sat",
               "store-ycsb-b"]


def _manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(name: str = CONFIG) -> dict:
    return bench_run.load_json(REPO, "benchmarks", "configs", name + ".json")


# ------------------------------------------------------------ the manifest


def test_the_entries_are_where_each_pr_appended_them():
    m = _manifest()
    layer = [x["name"] for x in m["per_layer"]]
    # PR 37's (its own test pins them to the tail), PR 39's (its test
    # pins the two lists to four cells), PR 41's: by absolute position
    assert m["configs"][2]["name"] == "tatp7m-x4r3"
    assert m["workloads"][3]["name"] == "tatp7m-x4-sat"
    assert m["configs"][3]["name"] == "store24m"
    assert m["configs"][3]["reduced"] == [] == _config("store24m")["reduced"]
    assert m["configs"][3]["source"] == _config("store24m")["source"]
    assert m["workloads"][4] == {
        "name": "store-ycsb-b", "config": "store24m", "traffic": "ycsb-b",
        "chips": 1, "why": m["workloads"][4]["why"]}
    assert layer[24] == "logic_abort_share.sb"
    assert layer[25:37] == [q + ".x4" for q in (
        "step_ms", "device_idle_share", "contention_abort_share",
        "monitor_ms", "unnamed_ms", "replicate_ms", "collective_exposed_ms",
        "hbm_peak_gb", "dispatch_gap_ms", "bck_val_scatter_ms",
        "hbm_roofline_share", "ici_roofline_share")]
    assert all(x["workloads"] == ["tatp7m-x4-sat"]
               for x in m["per_layer"][25:37])
    assert m["per_layer"][32]["source"] == "program_counter"
    assert layer[37:48] == [q + ".kv" for q in (
        "step_ms", "device_idle_share", "contention_abort_share",
        "monitor_ms", "unnamed_ms", "dispatch_gap_ms", "probe_ms",
        "install_ms", "key_sort_ms", "dup_key_share",
        "hbm_roofline_share")]
    assert all(x["workloads"] == ["store-ycsb-b"]
               and x["moves"] == "committed_txn_per_s"
               for x in m["per_layer"][37:48])
    assert layer[48:54] == [q + ".lat" for q in (
        "dispatch_call_ms", "launch_lag_ms", "completion_lag_ms",
        "clock_slack_ms", "stats_copy_ms", "launch_alloc_ms")]
    assert all(x["workloads"] == ["tatp7m-lat"]
               for x in m["per_layer"][48:54])
    for i, name in ((0, "committed_txn_per_s"), (1, "txn_latency_p50_ms")):
        assert m["end_to_end"][i]["name"] == name
        assert m["end_to_end"][i]["bound"] == 0.025
        assert m["end_to_end"][i]["workloads"][:4] == OLDER_CELLS
        # ... then this PR's cell, fifth
        assert m["end_to_end"][i]["workloads"][4] == CELL
    # this PR's: one configuration, one cell, sixteen metrics, each by its
    # index (a later PR appends after them and breaks nothing here)
    assert m["configs"][4]["name"] == CONFIG
    assert m["configs"][4]["reduced"] == [] == _config()["reduced"]
    assert m["configs"][4]["source"] == _config()["source"]
    assert len(m["configs"][4]["source"]) <= 200
    assert m["workloads"][5] == {
        "name": CELL, "config": CONFIG, "traffic": "sat", "chips": 4,
        "why": m["workloads"][5]["why"]}
    assert sum(c["chips"] == 4 for c in m["workloads"][:6]) == 2
    mine = m["per_layer"][54:70]
    assert [x["name"] for x in mine] == [q + ".sbx4" for q in NAMES]
    assert not any(CELL in x["workloads"] for x in m["per_layer"][:54])
    for x in mine:
        assert x["workloads"] == [CELL]
        assert x["moves"] == "committed_txn_per_s"
    about = {x["name"][:-5]: (x["layer"], x["source"], x["unit"])
             for x in mine}
    assert about["exchange_ms"] == ("multi-chip", "device_trace", "ms")
    assert about["arbitrate_ms"] == ("engine step", "device_trace", "ms")
    assert about["replicate_ms"][0] == about["ici_roofline_share"][0] \
        == about["collective_exposed_ms"][0] == "multi-chip"
    assert about["a2a_pack_ms"] == ("kernels", "program_span", "ms")
    assert about["hbm_roofline_share"] == ("kernels", "device_trace", "%")
    assert about["xshard_txn_share"] == about["remote_lock_share"] == (
        "engine step", "program_counter", "%")
    assert about["monitor_ms"][0] == "counter plane"
    assert about["dispatch_gap_ms"][0] == "dispatch"
    assert about["device_idle_share"][0] == "device"
    for q in VARIANTS:          # by the variant rule: no file of their own
        assert bench_run.reader_path("layer_metrics", q + ".sbx4") \
            == os.path.join(REPO, "benchmarks", "layer_metrics", q + ".py")
    for q in OWN_READERS:
        assert bench_run.reader_path("layer_metrics", q + ".sbx4") \
            == os.path.join(REPO, "benchmarks", "layer_metrics",
                            q + ".sbx4.py")


def test_the_configuration_is_smallbank24m_on_the_replicated_topology():
    mine, flat, x4 = _config(), _config("smallbank24m"), _config(
        "tatp7m-x4r3")
    assert mine["sizes"] == flat["sizes"] and mine["reduced"] == []
    assert mine["sizes"]["n_accounts"] == 24_000_000
    assert mine["traffic_shape"] == flat["traffic_shape"]
    assert mine["rehearse"] == flat["rehearse"]
    assert (mine["chips"], mine["deployment"]) == (
        4, "smallbank_dense_sharded")
    # smallbank24m's guarantees, the log's and the ring's made stronger
    assert len(mine["guarantees"]) == len(flat["guarantees"]) == 5
    assert mine["guarantees"][1] == x4["guarantees"][1]
    assert mine["guarantees"][4] == flat["guarantees"][4]
    assert "magic word" in mine["guarantees"][2]
    assert "four primaries" in mine["guarantees"][3]
    for key in ("fault_domains", "partitioning", "lock_slots",
                "bucket_capacity", "aborted_locks", "traffic_source",
                "log_rings", "window_drift", "warmup_reference"):
        assert mine["assumed"][key], key
    small = mine["compare_small"]
    assert {k: small[k] for k in flat["compare_small"]} \
        == flat["compare_small"] == {"n_accounts": 20000, "w": 256,
                                     "cohorts_per_block": 2, "blocks": 4}
    # rings that cannot wrap: 8 installing steps and the drain's, each of
    # at most 3w writes a source over the mesh, wherever they land
    assert small["log_lanes"] * small["log_capacity"] >= 4 * 3 * small[
        "w"] * (small["blocks"] * small["cohorts_per_block"] + 1)
    assert ref.EXACT_SLOTS >= 2 * mine["sizes"]["n_accounts"] + 1
    assert dep_mod.N == 4 and ref.bucket_cap(8192, 4) == 12288


# ------------------------------------- the reference on hand-made cases

N, D, INIT = 40, 4, 100
EMPTY = tuple(np.zeros(0, np.int64) for _ in range(4))


def _bank(module, by_cohort, cap=None):
    return module.ShardedSmallBank(N, D, INIT, cap=cap, by_cohort=by_cohort)


def _step(bank, by_source: dict):
    """One step: {source device: [(type, a1, a2, amount), ...]}."""
    return bank.step([tuple(np.array(col, np.int64)
                            for col in zip(*by_source[s]))
                      if s in by_source else EMPTY for s in range(D)])


BOTH = pytest.mark.parametrize("by_cohort", [False, True],
                               ids=["oracle", "by_cohort"])


@BOTH
def test_a_send_payment_whose_accounts_live_on_two_devices(by_cohort):
    bank = _bank(ref, by_cohort)
    row = _step(bank, {0: [(one.SEND_PAYMENT, 5, 10, 0)]})     # owners 1, 2
    assert row.tolist() == [1, 1, 0, 0, 0, 0, 0]
    assert bank.distributed == {"txns": 1, "xshard_txns": 1,
                                "lock_lanes": 2, "remote_lock_lanes": 2}
    bank.drain()
    n_loc = ref.n_local(N, D)
    assert n_loc == 10
    for dev, acct, balance in ((1, 5, INIT - one.AMT),
                               (2, 10, INIT + one.AMT)):
        rows, balances = bank.touched(dev)
        assert rows.tolist() == [n_loc + acct // D]      # CHECKING's half
        assert balances.tolist() == [balance]
        assert bank.table(dev)[-1] == 0 and len(bank.table(dev)) == 21
    assert [len(bank.touched(d)[0]) for d in range(D)] == [0, 1, 1, 0]
    assert bank.total_balance() == 2 * N * INIT
    if not by_cohort:
        assert bank.stream(1) == [(one.CHECKING, 5, 3, INIT - one.AMT,
                                   one.MAGIC)]
        assert bank.stream(2) == [(one.CHECKING, 10, 3, INIT + one.AMT,
                                   one.MAGIC)]
        # device 1's stream: its own ring under tag 0, rings 2 and 3
        # under tag 2; ring 0 holds nothing of it, and device 2's
        assert bank.ring(1)[0] == bank.ring(2)[2] == bank.ring(3)[2] \
            == bank.stream(1)
        assert bank.ring(0) == {0: [], 4: [], 3: bank.stream(2)}


@BOTH
def test_two_sources_ask_one_owner_for_x_and_the_lower_device_wins(
        by_cohort):
    bank = _bank(ref, by_cohort)
    row = _step(bank, {3: [(one.TRANSACT_SAVING, 6, 7, 9)],
                       0: [(one.TRANSACT_SAVING, 6, 8, 7)]})
    assert row.tolist() == [2, 1, 1, 0, 0, 7, 0]
    bank.drain()
    rows, balances = bank.touched(2)                    # 6 % 4
    assert rows.tolist() == [6 // D] and balances.tolist() == [INIT + 7]
    # in one source, the lower lane; a shared holder refuses the X after
    bank = _bank(ref, by_cohort)
    row = _step(bank, {1: [(one.BALANCE, 6, 0, 0), (one.BALANCE, 6, 1, 0),
                           (one.DEPOSIT_CHECKING, 6, 2, 0)]})
    assert row.tolist() == [3, 2, 1, 0, 0, 0, 0]
    if not by_cohort:
        assert bank.bank.tally["s_shared"] == 2
        assert bank.bank.tally["x_rejected_cohort"] == 1


@BOTH
def test_an_s_holder_of_the_last_step_refuses_a_remote_x(by_cohort):
    bank = _bank(ref, by_cohort)
    assert _step(bank, {2: [(one.BALANCE, 4, 9, 0)]}).tolist() \
        == [1, 1, 0, 0, 0, 0, 0]                        # S on both rows of 4
    refused = _step(bank, {1: [(one.DEPOSIT_CHECKING, 4, 9, 0)],
                           3: [(one.BALANCE, 4, 9, 0)]})
    assert refused.tolist() == [2, 1, 1, 0, 0, 0, 0]    # S joins, X does not
    # the second BALANCE's stamp holds another step; then the row is free
    assert _step(bank, {1: [(one.WRITE_CHECK, 4, 9, 0)]}).tolist() \
        == [1, 0, 1, 0, 0, 0, 0]
    assert _step(bank, {}).tolist() == [0, 0, 0, 0, 0, 0, 0]
    assert _step(bank, {1: [(one.DEPOSIT_CHECKING, 4, 9, 0)]}).tolist() \
        == [1, 1, 0, 0, 0, one.AMT, 0]
    if not by_cohort:
        assert bank.bank.tally["x_rejected_prev_s"] == 2


def test_placement_of_backups_and_streams_and_a_lost_devices_replay():
    where = ref.placement(D)
    assert where[3]["backups"] == [(0, 0), (1, 1)]
    assert where[3]["streams"] == [(3, 0), (0, 4), (1, 4)]
    assert ref.carried(D, 0) == [(0, 0), (3, 4), (2, 3)]
    assert [ref.owner(a, D) for a in (0, 5, 10, 39)] == [0, 1, 2, 3]
    assert ref.local_row(one.CHECKING, 39, 10, D) == 19
    entries = [(one.SAVINGS, 7, 3, 50, one.MAGIC),
               (one.SAVINGS, 7, 5, 70, one.MAGIC),
               (one.CHECKING, 3, 4, 9, one.MAGIC)]
    for order in (entries, entries[::-1]):
        table = ref.replay(order, 3, N, D, INIT)
        assert table[7 // D] == 70 and table[10 + 3 // D] == 9
        assert (table != ref.fresh_table(10, INIT)).sum() == 2
    with pytest.raises(ValueError, match="device 2's stream"):
        ref.replay(entries, 2, N, D, INIT)
    with pytest.raises(ValueError, match="magic"):
        ref.replay([(0, 7, 3, 50, 0)], 3, N, D, INIT)


@BOTH
def test_a_lane_past_its_bucket_is_counted_not_answered(by_cohort):
    bank = _bank(ref, by_cohort, cap=2)
    row = _step(bank, {0: [(one.AMALGAMATE, 4, 8, 0)],     # 3 lanes, owner 0
                       1: [(one.DEPOSIT_CHECKING, 4, 0, 0)]})
    assert row[-1] == 1 and ref.STAT_NAMES[-1] == "overflow"
    assert dep_mod.FAULTS == ("magic_bad", "overflow")


def _random_cohorts(rng, n, w, hot):
    out = []
    for _ in range(D):
        accounts = [np.where(rng.random(w) < 0.9, rng.integers(0, hot, w),
                             rng.integers(0, n, w)) for _ in range(2)]
        accounts[1] = np.where(accounts[0] == accounts[1],
                               (accounts[1] + 1) % n, accounts[1])
        out.append((rng.integers(0, 6, w), *accounts,
                    rng.integers(-60, 61, w)))
    return out


@pytest.mark.parametrize("seed,n,w,hot,init", [
    (0, 2000, 128, 60, 1000), (1, 200, 64, 16, 7), (2, 64, 64, 8, 2)])
def test_the_cohort_form_and_both_copies_agree_on_contended_cohorts(
        seed, n, w, hot, init):
    """The benchmark's reference through the sequential oracle, its
    cohort-at-a-time form (what the 24 M warm-up goes through) and the
    program's copy of both: every stats row, every touched row, the
    streams, the tallies."""
    rng = np.random.default_rng(seed)
    cap = ref.bucket_cap(w, D)
    banks = [mod.ShardedSmallBank(n, D, init, cap=cap, by_cohort=fast)
             for mod in (ref, copy) for fast in (False, True)]
    for _ in range(12):
        cohorts = _random_cohorts(rng, n, w, hot)
        rows = [b.step(cohorts) for b in banks]
        for row in rows[1:]:
            np.testing.assert_array_equal(row, rows[0])
    for b in banks:
        b.drain()
    tally = banks[0].bank.tally
    assert min(tally[k] for k in (
        "s_shared", "x_rejected_cohort", "s_rejected_cohort",
        "x_rejected_prev_x", "x_rejected_prev_s", "s_rejected_prev_x")) > 0
    for b in banks[1:]:
        assert b.distributed == banks[0].distributed
        assert b.total_balance() == banks[0].total_balance()
        for d in range(D):
            for x, y in zip(b.touched(d), banks[0].touched(d)):
                np.testing.assert_array_equal(x, y)
    assert banks[2].bank.tally == tally
    for d in range(D):
        assert banks[2].stream(d) == banks[0].stream(d)
        assert banks[2].ring(d) == banks[0].ring(d)
    assert ref.STAT_NAMES == copy.STAT_NAMES
    assert ref.EXACT_SLOTS == copy.EXACT_SLOTS
    assert ref.placement(D) == copy.placement(D)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmarks", "references",
                           "smallbank_sharded.py")) as f:
        text = f.read()
    assert "dint_tpu" not in text.split('"""', 2)[2]
    assert "import jax" not in text


# -------------------------- what the traffic and the partition must give


def test_the_distributed_shares_by_hand():
    shape = _config()["traffic_shape"]
    got = dep_mod.distributed_shares(shape, 24_000_000)
    # AMALGAMATE 15 % + SEND_PAYMENT 25 % name two accounts: two owners
    # three times in four
    assert got["p_xshard"] == pytest.approx(0.40 * 0.75, abs=1e-12)
    # 3 + 2 + 1 + 2 + 1 + 2 lanes by the mix: 1.85 a transaction
    assert got["remote_mean"] == pytest.approx(1.85 * 0.75, abs=1e-12)
    lanes = {0: (2, 1), 1: (2, 0), 2: (1, 0), 3: (1, 1), 4: (1, 0),
             5: (2, 0)}
    mix = np.array(shape["mix"]) / 100
    second = sum(p * (sum(c * c for c in lanes[t]) * 0.1875
                      + (sum(lanes[t]) * 0.75) ** 2)
                 for t, p in enumerate(mix))
    assert got["remote_var"] == pytest.approx(second - (1.85 * 0.75) ** 2,
                                              abs=1e-12)
    # a table the devices do not share evenly
    uneven = dep_mod.distributed_shares({**shape, "hot_prob": 0.0}, 5)
    assert uneven["p_xshard"] == pytest.approx(
        0.40 * (1 - (3 * 0.04 + 0.16)), abs=1e-12)   # device 0 owns 2 of 5


# --------------------------------- the comparison runs the sharded program


def _compared(seed: int) -> tuple:
    made = {}
    checks = ck.Checks(lambda **kw: made.setdefault(kw["check"], kw))
    dep_mod.compare_small(_config(), seed, checks)
    return checks, made


@pytest.mark.parametrize("seed", [5, 2147484029, 3700000101])
def test_the_four_device_program_equals_the_reference(seed):
    checks, made = _compared(seed)
    assert checks.failed == [] and checks.ok
    assert tuple(made) == dep_mod.COMPARE_CHECKS
    stats = made["compare.stats_equal_reference"]
    assert stats["steps"] == 8 and stats["totals"][0] == 8 * 4 * 256
    assert stats["totals"][1] > 0 and stats["totals"][2] > 0
    assert stats["distributed"]["xshard_txns"] > 0
    logs = made["compare.three_log_streams_equal_reference"]
    assert not logs["wrapped"] and min(logs["entries"]) > 0


def _no_magic(real):
    def log_free(ring, mask, table, is_del, key_hi, key_lo, ver, val):
        return real(ring, mask, table, is_del, key_hi, key_lo, ver,
                    val.at[:, 1].set(0))
    return log_free


def test_a_program_that_logs_no_magic_word_fails_the_streams(monkeypatch):
    """ISSUE 43 (a): the parent's entries carried the balance alone."""
    from dint_tpu.parallel import dense_sharded_sb as dsb

    dsb.build_sharded_sb_runner.cache.clear()        # not in the memo's key
    monkeypatch.setattr(dsb.logring, "append_rep",
                        _no_magic(dsb.logring.append_rep))
    try:
        checks, made = _compared(5)
    finally:
        dsb.build_sharded_sb_runner.cache.clear()
    assert set(checks.failed) == {
        "compare.three_log_streams_equal_reference",
        *(f"compare.lost_device_recovered_from_stream_{h}"
          for h in range(3))}


# ------------------------------------------------ verify notices a doctoring


@pytest.fixture(scope="module")
def drained():
    """A rehearsal-sized deployment after two dispatches and a drain."""
    config = _config()
    lines = []
    dep = dep_mod.build(config, {"w": 256, "cohorts_per_block": 2}, 7,
                        jax.devices()[:4], lambda **kw: lines.append(kw),
                        True)
    key = jax.random.PRNGKey(7)
    carry, total = dep.start(), np.zeros(len(dep.stat_names), np.int64)
    for i in range(2):
        carry, stats = dep.dispatch(carry, jax.random.fold_in(key, i))
        total += np.asarray(stats, np.int64).sum(axis=0)
    final, tail = dep.drain(carry)
    total += tail.sum(axis=0)
    return dep, final, dict(zip(dep.stat_names, total.tolist()))


def _verify(dep, final, totals) -> list:
    checks = ck.Checks(lambda **kw: None)
    seen, warm, balance = dep._heads_seen, dep._warm, dep._balance
    try:
        dep.verify(final, checks, "warmup", totals, totals["attempted"])
    finally:        # the same phase again, for the next case
        dep._heads_seen, dep._warm, dep._balance = seen, warm, balance
    return checks.failed


def _newest_entry(state, ring: int, tag: int) -> tuple:
    """(flat slot, account, table) of a newest-step entry of one stream
    of one ring: a row is written once a step, so it is its row's
    newest."""
    entries = np.asarray(state.log.entries)[ring]
    mine = np.nonzero((entries[:, 1] == tag) & (entries[:, 5] != 0))[0]
    slot = int(mine[np.argmax(entries[mine, 3])])
    return slot, int(entries[slot, 2]), int(entries[slot, 0] >> 8)


def _doctor_magic(dep, final):
    state, tail, counters = final
    slot, _, _ = _newest_entry(state, 2, 2)     # device 1's stream, ring 2
    entries = state.log.entries.at[2, slot, ck.HDR_WORDS + 1].add(1)
    return state.replace(log=state.log.replace(entries=entries)), tail, \
        counters


def _doctor_ring_balance(dep, final):
    state, tail, counters = final
    slot, _, _ = _newest_entry(state, 0, 0)     # device 0's own ring
    entries = state.log.entries.at[0, slot, ck.HDR_WORDS].add(1)
    return state.replace(log=state.log.replace(entries=entries)), tail, \
        counters


def _doctor_backup_row(dep, final):
    state, tail, counters = final
    _, acct, table = _newest_entry(state, 3, 0)     # a row device 3 wrote
    row = ref.local_row(table, acct, dep.n_loc, 4)
    # its second backup: slot 1 of device 3 + 2
    bck = state.bck_bal.at[1, dep.m1 + row].add(1)
    return state.replace(bck_bal=bck), tail, counters


DOCTORED_STATE = {
    "a_forwarded_entrys_magic": (_doctor_magic, {
        "warmup.device_1_acked_writes_read_back_from_ring_2",
        "warmup.stream_1_identical_in_three_rings"}),
    "an_own_entrys_balance": (_doctor_ring_balance, {
        "warmup.device_0_acked_writes_read_back_from_ring_0",
        "warmup.stream_0_identical_in_three_rings"}),
    "a_backup_row": (_doctor_backup_row, {
        "warmup.backup_2_of_device_3_equals_primary",
        "warmup.touched_rows_equal_reference"}),
}


def test_verify_passes_on_what_the_program_left(drained):
    dep, final, totals = drained
    assert _verify(dep, final, totals) == []


@pytest.mark.parametrize("case", DOCTORED_STATE)
def test_verify_fails_on_what_was_doctored_and_on_nothing_else(case,
                                                               drained):
    dep, final, totals = drained
    doctor, must_fail = DOCTORED_STATE[case]
    assert set(_verify(dep, doctor(dep, final), totals)) == must_fail


def test_a_program_without_the_counters_is_refused_at_once(monkeypatch):
    """The parent commit under this PR's benchmark: no such deployment,
    said as the module is imported (run.py has not started the chips
    then), in a line and a non-zero exit code."""
    import importlib.util

    monkeypatch.delattr(dep_mod.mon, "CTR_XSHARD_TXNS")
    spec = importlib.util.spec_from_file_location("parents_view_of_it",
                                                  dep_mod.__file__)
    with pytest.raises(SystemExit, match="lacks the counters"):
        spec.loader.exec_module(importlib.util.module_from_spec(spec))


def test_a_deployment_on_other_than_four_devices_is_refused():
    with pytest.raises(SystemExit, match="laid out on 4 devices"):
        dep_mod.build(_config(), {"w": 256, "cohorts_per_block": 2}, 1,
                      jax.devices()[:2], lambda **kw: None, True)


# ------------------------------------------------------------ byte models


def test_the_ici_bytes_and_the_time_in_flight_by_hand():
    assert ici.bucket_cap(8192, 4, 3) == 12288
    got = ici.step_bytes(8192, 3, 4)
    # nine exchanged arrays: 8 x i32 + 1 bool a slot, 3 of 4 buckets leave
    assert got["all_to_all"] == 3 * 12288 * (8 * 4 + 1) == 1_216_512
    # two hops of 4 x i32 + 1 bool a slot, all four buckets
    assert got["ppermute"] == 2 * 49152 * 17 == 1_671_168
    assert got["total"] == 2_887_680
    ops = [["all-to-all-start.1", "", 0.0, 10.0],
           ["fusion.3", "", 10.0, 50.0],
           ["all-to-all-done.1", "", 60.0, 5.0],            # 0 .. 65
           ["all-to-all.7", "", 100.0, 20.0],               # 100 .. 120
           ["collective-permute-start.2", "", 110.0, 1.0],
           ["collective-permute-start.4", "", 111.0, 1.0],
           ["collective-permute-done.4", "", 130.0, 2.0],   # 111 .. 132
           ["collective-permute-done.2", "", 140.0, 10.0],  # 110 .. 150
           ["all-reduce.9", "", 200.0, 50.0],               # the stats'
           ["all-to-all-start.8", "", 300.0, 5.0]]          # cut: no done
    assert ici.in_flight_ns(ops) == 65.0 + 50.0
    assert ici.roofline_share_pct(200e9 * 1e-3, 2e-3, "TPU v5 lite") \
        == pytest.approx(50.0)


def _ctx(**over):
    ctx = {"trace": {"devices": [{}], "window_s": 1.0, "busy_s": 0.96},
           "steps": 96, "n_devices": 4, "device": {"kind": "TPU v5 lite"},
           "geometry": {"w": 8192, "l": 3, "val_words": 2,
                        "log_replicas": 3, "n_backups": 2},
           "totals": {"attempted": 1000},
           "counters": {"lock_granted": 4 * 96 * 14000,
                        "install_writes": 4 * 96 * 9000,
                        "lock_requests": 2000, "xshard_txns": 300,
                        "remote_lock_lanes": 1500},
           "peak_bytes_in_use": [5e8, 6e8, None, 5.5e8]}
    ctx.update(over)
    return ctx


def test_the_hbm_share_and_the_counter_shares_by_hand():
    read = bench_run.load_reader("layer_metrics", "hbm_roofline_share.sbx4")
    # a device a step: 3 words a lane, a stamp read and written a grant,
    # a word an install on the primary and on two backups, three log
    # entries of 6 words an install
    need = 3 * 8192 * 3 * 4 + 14000 * 8 + 9000 * 4 * 3 + 9000 * 3 * 24
    assert read(_ctx()) == pytest.approx(
        100 * need / 819e9 / 0.01, rel=1e-12)
    assert read(_ctx(trace=None)) is None
    for name, want in (("xshard_txn_share.sbx4", 30.0),
                       ("remote_lock_share.sbx4", 75.0),
                       ("hbm_peak_gb.sbx4", 0.6)):
        read = bench_run.load_reader("layer_metrics", name)
        assert read(_ctx()) == pytest.approx(want)
    # the parent's program has no such counters: left out, not zero
    old = {"lock_granted": 1, "install_writes": 1, "lock_requests": 5}
    for name in ("xshard_txn_share.sbx4", "remote_lock_share.sbx4"):
        assert bench_run.load_reader("layer_metrics", name)(
            _ctx(counters=old)) is None
    assert bytes_model.WORD == 4


def test_the_exchange_reader_sums_two_waves_and_the_routing_parts(
        monkeypatch):
    read = bench_run.load_reader("layer_metrics", "exchange_ms.sbx4")
    by_wave = {
        WAVE + "route": {"route_addr": 0.1, "a2a_rank": 0.2,
                         "a2a_pack": 0.3, "a2a_requests": 0.4},
        WAVE + "reply": {"a2a_replies": 1.0, "reply_unpack": 2.0,
                         "reply_classify": 4.0},
        WAVE + "install_route": {"route_addr": 0.01, "a2a_rank": 0.02,
                                 "a2a_pack": 0.03, "a2a_installs": 0.04,
                                 "owner_install": 50.0, "log_scatter": 60.0},
        WAVE + "arbitrate": {"owner_arb": 70.0}}
    ctx = _ctx(parts={"by_wave": by_wave, "parts": {"a2a_pack": 0.33},
                      "unnamed": 0.0})
    assert read(ctx) == pytest.approx(1.0 + 7.0 + 0.1)
    assert bench_run.load_reader("layer_metrics", "a2a_pack_ms.sbx4")(
        ctx) == 0.33
    del by_wave[WAVE + "reply"]         # the parent's trace has them all;
    assert read(ctx) is None            # a trace without one: left out
    assert read(_ctx(parts=None)) is None


# ------------------------------------------------- the recorded chip trace


def _fixture(suffix: str) -> dict:
    found = glob.glob(os.path.join(FIXTURES, "*." + suffix))
    assert len(found) == 1, f"one recorded *.{suffix} under {FIXTURES}"
    with open(found[0]) as f:
        return json.load(f)


def test_the_recorded_trace_reduces_to_the_metrics_of_its_line():
    fx = _fixture("trace.json")
    red, want = tr.reduce(fx["trace"]), fx["expected"]
    tr.require_device_work(red, 4)
    assert want["n_devices"] == 4 and want["steps"] == 16
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    for dev in red["devices"]:
        assert dev["collective_s"] > 0
        assert {WAVE + w for w in ("gen", "route", "arbitrate", "reply",
                                   "install_route", "replicate")} \
            <= set(dev["scope_s"])
    for scope, seconds in want["scope_s"].items():
        assert red["devices"][0]["scope_s"][scope] \
            == pytest.approx(seconds, rel=1e-9)
    ctx = {"trace": red, "steps": want["steps"]}
    line = want["metrics"]
    for name in ("step_ms.sbx4", "device_idle_share.sbx4",
                 "arbitrate_ms.sbx4", "replicate_ms.sbx4",
                 "collective_exposed_ms.sbx4", "dispatch_gap_ms.sbx4"):
        read = bench_run.load_reader("layer_metrics", name)
        got = read(ctx)
        if name in line:
            assert got == pytest.approx(line[name], rel=1e-9), name
        else:                   # one program in the cut: no gap to read
            assert got is None and name == "dispatch_gap_ms.sbx4"
    # the interconnect's share from the same ops: under 100 %
    flights = [ici.in_flight_ns(d["ops"]) / 1e9
               for d in fx["trace"]["devices"]]
    assert all(f > 0 for f in flights)
    share = ici.roofline_share_pct(
        want["steps"] * ici.step_bytes(8192, 3, 4)["total"],
        sum(flights) / 4, "TPU v5 lite")
    assert share == pytest.approx(line["ici_roofline_share.sbx4"], rel=1e-9)
    assert 0 < share < 100


def test_the_recorded_trace_gives_its_known_parts():
    fx = _fixture("parts.json")
    got = pt.per_step(pt.fixture_ops(fx), fx["steps"])
    want = fx["expected"]
    assert got["unnamed"] == pytest.approx(want["unnamed"], rel=1e-9)
    assert got["parts"] == pytest.approx(want["parts"], rel=1e-9)
    ctx = {"parts": got}
    line = want["metrics"]
    for name in ("exchange_ms.sbx4", "a2a_pack_ms.sbx4", "monitor_ms.sbx4",
                 "unnamed_ms.sbx4"):
        read = bench_run.load_reader("layer_metrics", name)
        assert read(ctx) == pytest.approx(line[name], rel=1e-9), name
    # the three exchanges' collectives are told apart, and every op
    # under a wave of the program is booked to one of its parts
    for part in ("a2a_requests", "a2a_replies", "a2a_installs",
                 "sb_repl_hop", "a2a_pack", "a2a_rank", "owner_arb",
                 "sbx_carry"):
        assert got["parts"][part] > 0, part
    for wave in ("route", "arbitrate", "reply", "install_route",
                 "replicate"):
        assert pt.NO_PART not in got["by_wave"][WAVE + wave], wave
    waves = sum(sum(row.values()) for name, row in got["by_wave"].items()
                if name.startswith(WAVE))
    assert line["exchange_ms.sbx4"] < waves <= line["step_ms.sbx4"] * 1.001
