"""The yardstick's arithmetic: bytes a step must move, the table of
peaks, and the read-back of acknowledged writes from a replica ring."""
import numpy as np
import pytest

from benchmarks import bytes_model, checks as ck


def test_step_bytes_against_hand_worked_numbers_at_w_8192():
    # one chip: w=8192, K=4, 10-word rows, 3 packed log replicas,
    # 1,426 installs and 1,450 lock requests per step
    b = bytes_model.step_bytes(8192, 4, 10, 3, installs=1426,
                               lock_requests=1450)
    assert b["meta_gather"] == 2 * 8192 * 4 * 4 == 262_144
    assert b["magic_gather"] == 131_072
    assert b["lock"] == 65_536 + 1450 * 8 + 65_536 == 142_672
    assert b["install"] == 1426 * 44 == 62_744
    assert b["log_append"] == 1426 * 3 * 56 == 239_568
    assert "replicate" not in b
    assert b["total"] == 838_200
    # four chips: one local entry per append, two forwarded records
    x = bytes_model.step_bytes(8192, 4, 10, 1, installs=1426,
                               lock_requests=1450, n_backups=2)
    hop = 2 * 8192 * (25 + 40)
    assert hop == 1_064_960
    assert x["log_append"] == 1426 * 56 == 79_856
    assert x["replicate"] == 2 * (2 * hop + 1426 * (44 + 56)) == 4_545_040
    assert x["total"] == 262_144 + 131_072 + 142_672 + 62_744 + 79_856 \
        + 4_545_040


def test_roofline_share_and_unknown_device_kind():
    # 838,200 B at 819 GB/s is 1.0234 us; over a 21.8 ms step: 0.0047 %
    share = bytes_model.roofline_share_pct(838_200, 21.8e-3, "TPU v5 lite")
    assert share == pytest.approx(100 * 838_200 / 819e9 / 21.8e-3)
    assert 0.0046 < share < 0.0048
    assert bytes_model.load_peaks("TPU v5 lite")["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no published peaks"):
        bytes_model.load_peaks("TPU v9 imaginary")


# ------------------------------------------------------------ read-back

N_SUB, VW, LANES, CAP = 50, 3, 2, 8
TABLE_ROWS = ck.tatp_table_rows(N_SUB)


def _entry(table, key, ver, val, is_del=0, key_hi=0):
    return [is_del | (table << 8), key_hi, key, ver, *val]


def _ring_and_tables(writes, lanes=LANES, cap=CAP):
    """Append ``writes`` (table, key, val, is_del) round-robin over the
    lanes as the engine would, and apply them to live tables."""
    n1 = 22 * (N_SUB + 1) + 1
    meta = np.zeros(n1, np.uint32)
    val = np.zeros((n1, VW), np.uint32)
    base = ck.table_bases(TABLE_ROWS)
    ring = np.zeros((lanes, cap, ck.HDR_WORDS + VW), np.uint32)
    heads = np.zeros(lanes, np.uint32)
    for i, (table, key, v, is_del) in enumerate(writes):
        row = base[table] + key
        ver = (meta[row] >> 1) + 1
        meta[row] = (ver << 1) | (0 if is_del else 1)
        val[row] = v
        lane = i % lanes
        ring[lane, heads[lane] % cap] = _entry(table, key, ver, v, is_del)
        heads[lane] += 1
    return ring, heads, meta, val


def _readback(ring, heads, meta, val, **kw):
    plan = ck.plan_readback(ring, heads, TABLE_ROWS, VW, **kw)
    return ck.compare_readback(plan, meta[plan["rows"]], val[plan["rows"]])


WRITES = [(0, 7, [1, 2, 3], 0), (2, 30, [4, 5, 6], 0), (0, 7, [7, 8, 9], 0),
          (4, 100, [1, 1, 1], 0), (4, 100, [0, 0, 0], 1), (1, 9, [5, 5, 5], 0)]


def test_readback_passes_on_what_the_engine_would_write():
    res = _readback(*_ring_and_tables(WRITES))
    assert res["ok"] and res["keys"] == 4 and res["matched"] == 4
    assert not res["wrapped"] and res["entries"] == 6


@pytest.mark.parametrize("word,what", [(4, "differs"), (3, "lost"),
                                       (0, "differs")])
def test_readback_fails_when_one_replicas_entry_is_altered(word, what):
    ring, heads, meta, val = _ring_and_tables(WRITES)
    # the newest entry of subscriber 7: lane 0, slot 1
    assert ring[0, 1, 2] == 7 and ring[0, 1, 3] == 2
    ring[0, 1, word] += 1 if word != 0 else 1     # value, version, is_del
    res = _readback(ring, heads, meta, val)
    assert not res["ok"] and res[what] == 1


def test_readback_fails_when_the_table_lost_or_changed_a_write():
    ring, heads, meta, val = _ring_and_tables(WRITES)
    row = ck.table_bases(TABLE_ROWS)[2] + 30
    val[row, 1] ^= 1                             # same version, other value
    assert _readback(ring, heads, meta, val)["differs"] == 1
    val[row, 1] ^= 1
    meta[row] = (0 << 1) | 1                     # the install never landed
    res = _readback(ring, heads, meta, val)
    assert not res["ok"] and res["lost"] == 1


def test_a_newer_live_row_is_lawful_only_where_the_ring_wrapped_over_it():
    # unwrapped: a live row newer than its newest entry means an
    # acknowledged write is in no log
    ring, heads, meta, val = _ring_and_tables(WRITES)
    row = ck.table_bases(TABLE_ROWS)[1] + 9
    meta[row] += 2
    res = _readback(ring, heads, meta, val)
    assert not res["ok"] and res["unlawful_stale"] == 1
    # wrapped: subscriber 3 is written once early and once at the end of
    # lane 1; overwrite that last entry with another key's, and the old
    # one (in lane 0's older half) is all that survives
    many = [(0, 3, [1, 1, 1], 0)] + [(0, 10 + i, [i, i, i], 0)
                                     for i in range(9)]
    many += [(0, 3, [2, 2, 2], 0)]
    ring, heads, meta, val = _ring_and_tables(many, lanes=1, cap=16)
    assert _readback(ring, heads, meta, val)["ok"]
    many2 = many + [(0, 30 + i, [i, i, i], 0) for i in range(15)]
    ring, heads, meta, val = _ring_and_tables(many2, lanes=1, cap=16)
    res = _readback(ring, heads, meta, val)
    assert res["wrapped"] and res["ok"] and res["keys"] == 16
    # a fresh entry (newer half of the window) that the table has
    # outrun is never lawful, wrapped or not
    row = ck.table_bases(TABLE_ROWS)[0] + 44          # the last write's row
    meta[row] += 2
    res = _readback(ring, heads, meta, val)
    assert not res["ok"] and res["unlawful_stale"] == 1


def test_an_entry_of_a_slow_lane_is_fresh_only_by_the_fastest_lanes_clock():
    """Lane 1 fills four times slower than lane 0. Its newest entries are
    young by its own count and old by lane 0's: a later write to the same
    key through lane 0 may be gone, so they are not fresh."""
    ring = np.zeros((2, 16, ck.HDR_WORDS + VW), np.uint32)
    heads = np.array([64, 16], np.uint32)
    _, fresh, wrapped = ck.surviving_entries(ring, heads)
    assert wrapped and len(fresh) == 32
    assert fresh[:16].tolist() == [False] * 8 + [True] * 8      # lane 0
    assert fresh[16:].tolist() == [False] * 14 + [True] * 2     # lane 1
    even = ck.surviving_entries(ring, np.array([64, 64], np.uint32))[1]
    assert even.tolist() == ([False] * 8 + [True] * 8) * 2


def test_readback_separates_the_streams_of_a_shared_ring():
    # even writes (lane 0) are this device's own, odd ones (lane 1) were
    # forwarded by device 2; no key is in both streams
    ring, heads, meta, val = _ring_and_tables(
        [(0, 7, [1, 2, 3], 0), (2, 30, [4, 5, 6], 0), (0, 7, [7, 8, 9], 0),
         (2, 31, [1, 1, 1], 0), (0, 8, [2, 2, 2], 0), (2, 30, [0, 0, 0], 1)])
    ring[1, :, 1] = 3
    own = _readback(ring, heads, meta, val, key_hi=0)
    fwd = _readback(ring, heads, meta, val, key_hi=3)
    assert own["entries"] == 3 and fwd["entries"] == 3
    assert own["ok"] and fwd["ok"]
    assert not _readback(ring, heads, meta, val, key_hi=1)["ok"]  # empty


def test_a_key_outside_its_table_is_reported_not_followed():
    ring, heads, meta, val = _ring_and_tables(WRITES)
    ring[0, 0, 2] = 10_000                       # subscriber table, key 10k
    res = _readback(ring, heads, meta, val)
    assert not res["ok"] and not res["in_range"]


@pytest.mark.parametrize("table_rows,table,key,row", [
    ((40, 40), 1, 39, 79),              # two tables of one size
    ((7, 100, 3), 2, 2, 109), ((7, 100, 3), 1, 0, 7)])
def test_readback_takes_the_table_layout_as_an_argument(table_rows, table,
                                                        key, row):
    assert ck.table_bases(table_rows).tolist() == [
        sum(table_rows[:t]) for t in range(len(table_rows))]
    ring = np.zeros((1, 4, ck.HDR_WORDS + 2), np.uint32)
    ring[0, 0] = _entry(table, key, 5, [900, 77])
    ring[0, 1] = _entry(table, key, 6, [850, 77])        # the newer one
    plan = ck.plan_readback(ring, np.array([2], np.uint32), table_rows, 2)
    assert plan["in_range"] and plan["rows"].tolist() == [row]
    assert plan["val"].tolist() == [[850, 77]] and not plan["wrapped"]
    assert plan["fresh"].tolist() == [True] and plan["n_entries"] == 2
    # a table the layout does not have, and a key past its table's end
    ring[0, 1] = _entry(len(table_rows), 0, 6, [1, 1])
    assert not ck.plan_readback(ring, np.array([2], np.uint32), table_rows,
                                2)["in_range"]
    ring[0, 1] = _entry(table, table_rows[table], 6, [1, 1])
    assert not ck.plan_readback(ring, np.array([2], np.uint32), table_rows,
                                2)["in_range"]


def test_ab_missing_band_is_at_least_four_sigma():
    obs, exp, band = ck.ab_missing_band(1_000_000, 248_600)
    assert abs(exp - 0.2486) < 2e-4 and band == 0.01
    _, _, wide = ck.ab_missing_band(1024, 250)
    assert wide == pytest.approx(4 * (exp * (1 - exp) / 1024) ** 0.5)
