"""The small comparison's verdict on two engines' stats, and the
accounting any deployment's ``verify`` can call: what passes, and what
must still fail."""
import json
import os

import pytest

from benchmarks import checks as ck

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAMES = ("attempted", "committed", "ab_lock", "ab_missing", "ab_validate",
         "magic_bad")
# what the two engines said at the configuration's compare_small size, on
# the chip and on the CPU alike (my chip runs, PR 29 and PR 32): in each a
# transaction reads a CALL_FORWARDING row that cohort t-2 deletes (the
# first) or inserts (the second) in the same step
SEEN = {
    2147484029: ((2048, 1522, 1, 525, 0, 0), (2048, 1522, 1, 524, 1, 0)),
    3000000017: ((2048, 1518, 3, 527, 0, 0), (2048, 1517, 3, 528, 0, 0)),
}
DENSE, GENERIC = (dict(zip(NAMES, v)) for v in SEEN[2147484029])


def _collect():
    made = {}
    return ck.Checks(lambda **kw: made.setdefault(kw["check"], kw)), made


@pytest.mark.parametrize("dense,generic,versions_equal,races,ok,moved", [
    (DENSE, DENSE, True, 0, True, 0),
    (DENSE, DENSE, False, 0, True, 0),  # the versions have their own check
    (DENSE, GENERIC, True, 1, True, 1),
    (GENERIC, DENSE, True, 1, True, 1),     # either engine may be first
    ({**DENSE, "committed": 1523, "ab_missing": 524}, DENSE, True, 1, True,
     1),                                    # a row inserted: found, not missed
    (DENSE, GENERIC, True, 0, False, 1),    # no race in the run to blame
    (DENSE, GENERIC, False, 1, False, 1),   # a table differs too
    ({**DENSE, "committed": 1521}, GENERIC, True, 5, False, 1),
    ({**DENSE, "committed": 1524, "ab_missing": 523}, DENSE, True, 1, False,
     2),                                    # more moved than raced
    ({**DENSE, "ab_lock": 2, "ab_missing": 524}, DENSE, True, 5, False, 0),
    ({**DENSE, "magic_bad": 1}, GENERIC, True, 5, False, 1),
    ({**DENSE, "attempted": 2049}, GENERIC, True, 5, False, 1),
], ids=["equal", "equal_versions_apart", "row_deleted_missing_for_validate",
        "the_other_way", "row_inserted_commit_for_missing",
        "a_difference_and_no_race", "raced_but_a_table_differs",
        "a_commit_lost", "two_moved_one_raced", "a_lock_abort_for_a_miss",
        "a_bad_magic_word", "attempted_apart"])
def test_what_the_two_engines_may_disagree_in(dense, generic, versions_equal,
                                              races, ok, moved):
    assert ck.tatp_stats_agree(dense, generic, versions_equal,
                               races) == (ok, moved)


def test_a_race_is_a_read_of_a_key_written_two_cohorts_earlier():
    import numpy as np
    reads = [np.array(r) for r in ([5], [1, 2], [7, 8, 9], [3, 4], [7, 7])]
    writes = [np.array(w) for w in ([7, 9], [3], [1, 2], [], [5])]
    #          cohort 2 reads 7 and 9 (cohort 0), 3 reads 3 (cohort 1),
    #          4 reads 7 twice and cohort 2 wrote no 7
    assert ck.cf_races(reads, writes) == 3
    assert ck.cf_races(reads[:2], writes[:2]) == 0


def _compare_small_at(seed):
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "tatp7m.json")) as f:
        config = json.load(f)
    checks, made = _collect()
    ck.compare_small(checks, seed, config["compare_small"],
                     config["sizes"]["val_words"])
    return checks, made


@pytest.mark.parametrize("seed", sorted(SEEN))
def test_the_comparison_passes_on_a_seed_whose_one_race_shows(seed):
    """Failed on PR 30's tree (commits and tables equal or one read-only
    commit apart, one transaction classed otherwise): refused an honest
    run on about 1 seed in 60."""
    checks, made = _compare_small_at(seed)
    stats = made["compare.dense_stats_equal_generic_engine"]
    assert checks.ok and checks.n == 6, checks.failed
    assert (tuple(stats["dense"]), tuple(stats["generic"])) == SEEN[seed]
    assert any(stats["difference"])
    assert stats["moved"] == 1 and stats["cf_races"] == 1
    assert made["compare.table_versions_equal_generic_engine"]["passed"]


def test_the_comparison_still_fails_for_a_changed_committed(monkeypatch):
    """Seed 2147484029, the generic engine's drain doctored: one commit
    reported as a validation abort. The columns still sum to attempted
    and no table moved, but two transactions differ and one raced."""
    from dint_tpu.engines import tatp_dense as td
    from dint_tpu.engines import tatp_pipeline as tp
    real = tp.build_pipelined_runner

    def doctored(*a, **kw):
        run, init, drain = real(*a, **kw)

        def drain_one_commit_less(carry):
            out = drain(carry)
            stats = out[1].at[0, td.STAT_COMMITTED].add(-1)
            return (out[0], stats.at[0, td.STAT_AB_VALIDATE].add(1),
                    *out[2:])

        return run, init, drain_one_commit_less

    monkeypatch.setattr(tp, "build_pipelined_runner", doctored)
    checks, made = _compare_small_at(2147484029)
    assert checks.failed == ["compare.dense_stats_equal_generic_engine"]
    stats = made["compare.dense_stats_equal_generic_engine"]
    assert stats["difference"] == [0, 1, 0, 1, -2, 0]
    assert stats["moved"] == 2 and stats["cf_races"] == 1


BANK = {"attempted": 100, "committed": 70, "ab_lock": 21, "ab_logic": 9,
        "magic_bad": 0, "bal_delta": -1234}
BANK_SNAP = {"txn_attempted": 100, "txn_committed": 70, "ab_lock": 21,
             "ab_logic": 9, "magic_bad": 0}
BANK_PAIRS = (("txn_attempted", "attempted"), ("txn_committed", "committed"),
              ("ab_lock", "ab_lock"), ("ab_logic", "ab_logic"),
              ("magic_bad", "magic_bad"))


@pytest.mark.parametrize("totals,snap,dispatched,failed", [
    (BANK, BANK_SNAP, 100, []),
    (BANK, BANK_SNAP, 128, ["p.attempted_equals_dispatched"]),
    ({**BANK, "ab_logic": 8}, {**BANK_SNAP, "ab_logic": 8}, 100,
     ["p.accounting_closes"]),
    ({**BANK, "magic_bad": 1}, {**BANK_SNAP, "magic_bad": 1}, 100,
     ["p.magic_bad_zero"]),
    ({**BANK, "committed": 0, "ab_lock": 91},
     {**BANK_SNAP, "txn_committed": 0, "ab_lock": 91}, 100,
     ["p.committed_some"]),
    (BANK, {**BANK_SNAP, "ab_logic": 10}, 100,
     ["p.monitor_reconciles_with_stats"]),
], ids=["closes", "a_dispatch_not_attempted", "an_outcome_missing",
        "a_fault_counted", "nothing_committed", "a_counter_apart"])
def test_the_accounting_any_deployment_can_call(totals, snap, dispatched,
                                                failed):
    """Over another engine's columns: a logic abort is an outcome, the
    signed balance delta is neither outcome nor fault."""
    checks, made = _collect()
    ck.check_accounting(checks, "p", totals, snap, dispatched,
                        ("committed", "ab_lock", "ab_logic"),
                        ("magic_bad",), BANK_PAIRS)
    assert list(made) == ["p.attempted_equals_dispatched",
                          "p.accounting_closes", "p.magic_bad_zero",
                          "p.committed_some",
                          "p.monitor_reconciles_with_stats"]
    assert checks.failed == failed


def test_the_lock_ledger_closes_or_says_so():
    snap = {"lock_requests": 10, "lock_granted": 7, "lock_rejected": 3,
            "lock_reject_held": 2, "lock_reject_arb": 1}
    checks, _ = _collect()
    ck.check_lock_ledger(checks, "p", snap)
    ck.check_lock_ledger(checks, "q", {**snap, "lock_reject_arb": 0})
    ck.check_lock_ledger(checks, "r", {**snap, "lock_granted": 8})
    assert checks.failed == ["q.lock_ledger_closes", "r.lock_ledger_closes"]
