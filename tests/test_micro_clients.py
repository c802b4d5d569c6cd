"""Microbenchmark clients: store/2PL/FaSST/log replay + stats contract."""
import numpy as np

from dint_tpu.clients import micro, workloads as wl
from dint_tpu.stats import LatencyReservoir, MetricBlock, Recorder


def test_store_client_mixes(rng):
    for frac in (1.0, 0.5):   # parallel / contention
        c = micro.StoreClient.populated(1000, width=512, read_frac=frac)
        for _ in range(3):
            ok = c.run_wave(rng, 512)
            assert ok == 512              # GET/SET on populated keys all succeed
        blk = c.rec.block(elapsed_s=1.0)
        assert blk.throughput == 3 * 512
        assert blk.goodput == 3 * 512
        assert blk.p99_us >= blk.p50_us > 0


def test_store_client_zipf_waves():
    """The micro client end-to-end on Zipfian keys: the jitted step keeps
    the magic intact and goodput == batch width."""
    rng = np.random.default_rng(0)
    c = micro.StoreClient.populated(2000, width=256, key_dist="zipfian")
    assert not c.use_scan
    for _ in range(3):
        assert c.run_wave(rng) == 256


def test_store_client_scan_mix(rng):
    """YCSB-E-shaped wave through the scan-threaded step: every scan
    lane answers VAL (run fresh each rebuild_every waves), counts obey
    len/scan_max clipping (asserted inside run_wave), goodput counts
    scan rows' lanes like any other reply."""
    c = micro.StoreClient.populated(500, width=256, read_frac=0.5,
                                    key_dist="zipfian", use_scan=True,
                                    scan_frac=0.3, scan_max=8,
                                    rebuild_every=2)
    assert c.use_scan and c.scan_frac == 0.3
    for _ in range(4):
        ok = c.run_wave(rng, 256)
        assert ok == 256
    blk = c.rec.block(elapsed_s=1.0)
    assert blk.goodput == 4 * 256


def test_store_client_scan_stale_rebuilds_and_retries(rng):
    """The in-doubt discipline: a stale overlay (tiny delta_cap, write-
    heavy mix) makes scans RETRY; the client rebuilds the run mid-wave
    and re-sends exactly those lanes, which must then answer VAL —
    run_wave asserts the contract, we pin that the path actually ran."""
    c = micro.StoreClient.populated(300, width=128, read_frac=0.0,
                                    use_scan=True, scan_frac=0.5,
                                    scan_max=4, delta_cap=4,
                                    rebuild_every=10_000)
    rebuilds = []
    orig = c._rebuild
    c._rebuild = lambda s: (rebuilds.append(1), orig(s))[1]
    for _ in range(3):
        c.run_wave(rng, 128)
    # rebuild_every is effectively off: every rebuild here was the
    # RETRY-recovery action
    assert rebuilds, "stale overlay never exercised the retry path"


def test_log_client(rng):
    c = micro.LogClient(width=256, lanes=4, capacity=1 << 10)
    for _ in range(4):
        c.run_wave(rng, 256)
    heads = np.asarray(c.state.head)
    assert heads.sum() == 4 * 256
    assert (heads == 256).all()          # round-robin balance


def test_lock2pl_client_conflicts(rng):
    trace = wl.lock_trace(rng, n_txns=200, key_range=64)   # heavy conflicts
    c = micro.Lock2PLClient(trace, n_slots=1 << 10, cohort=64, width=1024)
    total_committed = 0
    for _ in range(5):
        total_committed += c.run_round()
        # every granted lock was released in the same round
        assert np.asarray(c.state.num_sh).sum() == 0
        assert np.asarray(c.state.num_ex).sum() == 0
    assert 0 < total_committed <= c.rec.attempted
    assert c.rec.committed == total_committed
    blk = c.rec.block(1.0)
    assert 0.0 < blk.abort_rate < 1.0     # contention must cause some aborts


def test_lock2pl_no_conflict_commits_all(rng):
    # one txn per round, huge keyspace: no conflicts -> everything commits
    trace = wl.lock_trace(rng, n_txns=50, key_range=1 << 20)
    c = micro.Lock2PLClient(trace, n_slots=1 << 20, cohort=1, width=64)
    for _ in range(5):
        c.run_round()
    assert c.rec.committed == c.rec.attempted


def test_fasst_client(rng):
    # reference trace envelope: key range 4800 (lock_2pl/caladan/trace_init.sh)
    trace = wl.lock_trace(rng, n_txns=200, key_range=4800, read_prop=0.5)
    c = micro.FasstClient(trace, n_slots=1 << 16, cohort=64, width=1024)
    total = 0
    for _ in range(5):
        total += c.run_round()
        assert not np.asarray(c.state.locked).any()   # all locks resolved
    assert 0 < total < c.rec.attempted  # conflicts abort some, not all
    # committed writes bumped versions
    assert np.asarray(c.state.ver).sum() > 0


def test_fasst_client_validation_abort():
    # two txns, same single key: one reads it, one writes it. The writer's
    # wave-1 lock makes the reader's validation re-read see the lock bit ->
    # reader aborts (reference lock_fasst/caladan/client.cc:199-215).
    key = np.array([7], np.int64)
    trace = [(key, np.array([True])), (key, np.array([False]))]
    c = micro.FasstClient(trace, n_slots=1 << 10, cohort=2, width=64)
    committed = c.run_round()
    assert committed == 1        # writer commits, reader fails validation


def test_latency_reservoir_downsampling():
    r = LatencyReservoir(cap=100, seed=0)
    r.add(np.full(50, 10.0))
    assert r.n_kept == 50
    r.add(np.full(500, 20.0))
    assert r.n_kept == 100
    assert r.n_seen == 550
    p = r.percentiles()
    assert 10.0 <= p["p50"] <= 20.0


def test_metric_block_format():
    rec = Recorder()
    rec.record(100, 90, np.linspace(10, 1000, 100), device_s=0.5)
    blk = rec.block(elapsed_s=2.0)
    assert blk.throughput == 50.0
    assert blk.goodput == 45.0
    assert abs(blk.abort_rate - 0.1) < 1e-9
    assert blk.device_duty == 0.25
    assert "median" in blk.format()
    d = blk.to_dict()
    for k in ("throughput", "goodput", "abort_rate", "avg_us", "p50_us",
              "p99_us", "p999_us", "device_duty"):
        assert k in d


def test_stat_clock_phases():
    from dint_tpu.stats import StatClock, Window
    c = StatClock(Window(warmup_s=0.0, measure_s=0.05))
    assert c.tick() == "measure"
    import time
    time.sleep(0.06)
    assert c.tick() == "done"
    assert c.measured_s > 0
