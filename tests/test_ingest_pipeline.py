"""Streaming ingest plane (io/pipeline.py) + the loader/sampler fixes
that ride with it.

Covers the PR's exact-parity discipline (pipelined stream == plain
sequential stream, including across a simulated mid-epoch ``reform()``),
the ``data.pipeline`` chaos contract (an injected fault degrades one
batch to a synchronous fetch — no sample lost, none duplicated), the
decoded-sample cache in both modes, the process-worker fault surface
(clean error on a killed worker, ``timeout=`` honored), and the
observability wiring (per-stage spans/histograms, ``input_stall_pct``
as an exported gauge, cache hit/miss counters).
"""
import os
import signal
import threading
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import chaos, monitor
from paddle_tpu.io import (DataLoader, Dataset, DistributedBatchSampler,
                           RandomSampler, numpy_collate, random_split)
from paddle_tpu.io.pipeline import (CachedDataset, IngestPipeline,
                                    SampleCache, to_device)


class _VecDataset(Dataset):
    """index -> (index * ones(3) f32, index i64): value == identity, so
    order/dup/loss bugs are visible in the batch values themselves."""

    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.float32(i) * np.ones(3, np.float32), np.int64(i)


class _CountingDataset(Dataset):
    """Counts decode calls via a file (survives pickling; a memory
    counter would reset in a spawned worker)."""

    def __init__(self, n, log):
        self.n = n
        self.log = log

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        with open(self.log, "a") as f:
            f.write(f"{i}\n")
        return np.float32(i) * np.ones(4, np.float32), np.int64(i)


class _SlowDataset(Dataset):
    def __len__(self):
        return 64

    def __getitem__(self, i):
        time.sleep(0.05)
        return np.float32(i)


def _materialize(stream):
    out = []
    for batch in stream:
        out.append(tuple(np.asarray(b.numpy() if hasattr(b, "numpy")
                                    else b) for b in batch))
    return out


def _assert_streams_equal(a, b):
    assert len(a) == len(b)
    for (x1, y1), (x2, y2) in zip(a, b):
        assert x1.dtype == x2.dtype and y1.dtype == y2.dtype
        np.testing.assert_array_equal(x1, x2)
        np.testing.assert_array_equal(y1, y2)


class TestParity:
    def test_pipelined_equals_sequential(self):
        ds = _VecDataset(23)
        plain = _materialize(DataLoader(ds, batch_size=4))
        for depth in (0, 1, 3):
            pipe = IngestPipeline(DataLoader(ds, batch_size=4),
                                  prefetch_depth=depth)
            _assert_streams_equal(plain, _materialize(pipe))

    def test_parity_with_seeded_shuffle(self):
        ds = _VecDataset(23)

        def shuffled():
            dl = DataLoader(ds, batch_size=4)
            dl.batch_sampler.sampler = RandomSampler(ds, generator=7)
            return dl

        plain = _materialize(shuffled())
        piped = _materialize(IngestPipeline(shuffled(), prefetch_depth=2))
        _assert_streams_equal(plain, piped)
        # and the seed actually shuffles
        first = np.concatenate([y for _, y in plain])
        assert not np.array_equal(first, np.arange(23))

    def test_parity_across_midepoch_reform(self):
        """2 ranks consume k batches, the job shrinks to 1 rank
        mid-epoch: reshard() re-partitions exactly the unconsumed
        suffix — union(pre-reform, post-reform) == one full epoch, no
        sample lost, none duplicated."""
        ds = _VecDataset(23)
        B, consumed = 4, 2
        samplers = [DistributedBatchSampler(ds, B, num_replicas=2, rank=r)
                    for r in (0, 1)]
        seen = []
        for s in samplers:
            pipe = IngestPipeline(
                DataLoader(ds, batch_sampler=s), prefetch_depth=2)
            it = iter(pipe)
            for _ in range(consumed):
                xb, yb = next(it)
                seen.extend(yb.numpy().tolist())
            it.close()          # early exit: flushes background work
        # survivor (rank 0 of world 1) adopts the new membership
        survivor = samplers[0]
        survivor.reshard(rank=0, nranks=1, membership_epoch=1,
                         consumed_batches=consumed)
        pipe = IngestPipeline(DataLoader(ds, batch_sampler=survivor),
                              prefetch_depth=2)
        for xb, yb in pipe:
            seen.extend(yb.numpy().tolist())
        assert sorted(seen) == sorted(range(23))

    def test_sync_and_pipelined_paths_share_instrumentation(self):
        ds = _VecDataset(8)
        for depth in (0, 2):
            pipe = IngestPipeline(DataLoader(ds, batch_size=4),
                                  prefetch_depth=depth)
            list(pipe)
            assert pipe.batches == 2
            assert 0.0 <= pipe.input_stall_pct <= 100.0


class TestChaos:
    def test_injected_fault_degrades_not_drops(self):
        """data.pipeline mode='error': the consumer falls back to a
        synchronous fetch+transfer of the SAME batch — stream identical
        to the unfaulted one, misses counted."""
        ds = _VecDataset(23)
        plain = _materialize(DataLoader(ds, batch_size=4))
        chaos.reset(123)
        before = monitor.get_stat("ingest_prefetch_misses_total")
        with chaos.inject("data.pipeline", mode="error", every=2):
            pipe = IngestPipeline(DataLoader(ds, batch_size=4),
                                  prefetch_depth=1)
            got = _materialize(pipe)
        _assert_streams_equal(plain, got)
        assert monitor.get_stat("ingest_prefetch_misses_total") > before

    def test_latency_fault_absorbed_by_wait(self):
        ds = _VecDataset(8)
        plain = _materialize(DataLoader(ds, batch_size=4))
        chaos.reset(123)
        with chaos.inject("data.pipeline", mode="latency", latency=0.05,
                          every=1):
            pipe = IngestPipeline(DataLoader(ds, batch_size=4),
                                  prefetch_depth=1)
            got = _materialize(pipe)
        _assert_streams_equal(plain, got)

    def test_every_fault_seeded_run_is_deterministic(self):
        ds = _VecDataset(16)
        plain = _materialize(DataLoader(ds, batch_size=4))
        for _ in range(2):
            chaos.reset(7)
            with chaos.inject("data.pipeline", mode="error", p=0.5):
                got = _materialize(IngestPipeline(
                    DataLoader(ds, batch_size=4), prefetch_depth=2))
            _assert_streams_equal(plain, got)


class TestSamplers:
    def test_distributed_padding_cycles_when_ranks_exceed_dataset(self):
        """Regression: `indices += indices[:pad]` under-padded whenever
        pad > len(indices) (nranks > dataset), yielding unequal shards
        and a hang at the collective — padding must CYCLE."""
        ds = _VecDataset(3)
        shards = []
        for r in range(8):
            s = DistributedBatchSampler(ds, batch_size=2, num_replicas=8,
                                        rank=r)
            shards.append([i for b in s for i in b])
        lengths = {len(sh) for sh in shards}
        assert lengths == {1}, f"unequal shards: {shards}"
        # every real sample still appears somewhere
        assert set(range(3)) <= {i for sh in shards for i in sh}

    def test_distributed_epoch_and_reshard_counts(self):
        ds = _VecDataset(20)
        s = DistributedBatchSampler(ds, batch_size=3, num_replicas=2,
                                    rank=0, shuffle=True)
        s.set_epoch(1)
        full = [i for b in s for i in b]
        s.reshard(rank=0, nranks=1, membership_epoch=3,
                  consumed_batches=1)
        assert s.membership_epoch == 3
        rest = [i for b in s for i in b]
        assert len(rest) == 20 - 1 * 3 * 2
        # epoch order is membership-independent: remaining == suffix
        s2 = DistributedBatchSampler(ds, batch_size=3, num_replicas=1,
                                     rank=0, shuffle=True)
        s2.set_epoch(1)
        order = [i for b in s2 for i in b]
        assert rest == order[6:]

    def test_random_split_generator_reproducible(self):
        ds = _VecDataset(10)
        a1, b1 = random_split(ds, [6, 4], generator=42)
        a2, b2 = random_split(ds, [6, 4], generator=42)
        assert a1.indices == a2.indices and b1.indices == b2.indices
        a3, _ = random_split(ds, [6, 4], generator=43)
        assert a1.indices != a3.indices

    def test_random_sampler_generator_reproducible(self):
        ds = _VecDataset(16)
        s1 = list(RandomSampler(ds, generator=5))
        s2 = list(RandomSampler(ds, generator=5))
        assert s1 == s2 and sorted(s1) == list(range(16))
        # stateful stream: epoch 2 differs from epoch 1 but is itself
        # reproducible from the same seed
        r = RandomSampler(ds, generator=5)
        e1, e2 = list(r), list(r)
        assert e1 == s1 and e2 != e1


class TestCache:
    def test_memory_cache_skips_decode_on_epoch2(self, tmp_path):
        log = str(tmp_path / "decodes")
        cache = SampleCache(mode="memory", max_bytes=1 << 20)
        cds = CachedDataset(_CountingDataset(10, log), cache)
        for _ in range(3):
            list(DataLoader(cds, batch_size=5))
        with open(log) as f:
            decodes = f.read().splitlines()
        assert len(decodes) == 10          # epoch 2/3 never decoded
        assert cache.hits == 20 and cache.misses == 10

    def test_disk_cache_crash_safe_files(self, tmp_path):
        log = str(tmp_path / "decodes")
        cache = SampleCache(mode="disk", cache_dir=str(tmp_path / "c"),
                            max_bytes=1 << 20)
        cds = CachedDataset(_CountingDataset(6, log), cache)
        list(DataLoader(cds, batch_size=3))
        files = os.listdir(str(tmp_path / "c"))
        assert len([f for f in files if f.endswith(".pkl")]) == 6
        assert not [f for f in files if ".tmp." in f]   # no torn leftovers
        list(DataLoader(cds, batch_size=3))
        with open(log) as f:
            assert len(f.read().splitlines()) == 6
        # a second cache over the same dir hits immediately (the
        # cross-process sharing disk mode exists for)
        cache2 = SampleCache(mode="disk", cache_dir=str(tmp_path / "c"),
                             max_bytes=1 << 20)
        got = cache2.get(0)
        assert got is not None
        np.testing.assert_array_equal(got[0], np.zeros(4, np.float32))

    def test_byte_bound_stops_inserts(self):
        cache = SampleCache(mode="memory", max_bytes=100)
        big = np.zeros(20, np.float32)     # 80 bytes
        assert cache.put(0, big)
        assert not cache.put(1, big)       # would exceed the bound
        assert cache.get(0) is not None and cache.get(1) is None

    def test_byte_bound_counts_device_tensors(self):
        """Regression: a Tensor sample must be charged its real payload
        (not the 16-byte scalar fallback), or max_bytes is a no-op for
        Tensor-yielding datasets."""
        cache = SampleCache(mode="memory", max_bytes=100)
        t = paddle.to_tensor(np.zeros(64, np.float32))   # 256 bytes
        assert not cache.put(0, t)
        assert cache.bytes_used == 0

    def test_disk_cache_refuses_stale_directory(self, tmp_path):
        """Regression: rebinding a disk dir recorded for a different
        dataset must raise, not silently serve the old samples."""
        d = str(tmp_path / "c")
        CachedDataset(_VecDataset(6),
                      SampleCache(mode="disk", cache_dir=d,
                                  max_bytes=1 << 20))
        with pytest.raises(ValueError, match="stale"):
            CachedDataset(_VecDataset(7),
                          SampleCache(mode="disk", cache_dir=d,
                                      max_bytes=1 << 20))
        # same fingerprint rebinds fine; clear() unstamps for reuse
        cache = SampleCache(mode="disk", cache_dir=d, max_bytes=1 << 20)
        CachedDataset(_VecDataset(6), cache)
        cache.clear()
        CachedDataset(_VecDataset(7),
                      SampleCache(mode="disk", cache_dir=d,
                                  max_bytes=1 << 20))

    def test_memory_cache_warns_crossing_process_boundary(self):
        import pickle
        cache = SampleCache(mode="memory", max_bytes=1 << 20)
        cache.put(0, np.float32(0))
        with pytest.warns(RuntimeWarning, match="mode='disk'"):
            clone = pickle.loads(pickle.dumps(cache))
        assert clone.get(0) is None        # arrives empty, loudly
        disk_cache = SampleCache(mode="disk")
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            pickle.loads(pickle.dumps(disk_cache))   # disk mode: silent

    def test_transform_applies_after_cache(self):
        cache = SampleCache(mode="memory", max_bytes=1 << 20)
        calls = []

        class _D(Dataset):
            def __len__(self):
                return 2

            def __getitem__(self, i):
                calls.append(i)
                return np.float32(i)

        cds = CachedDataset(_D(), cache, transform=lambda s: s + 1)
        assert cds[0] == 1.0 and cds[0] == 1.0
        assert calls == [0]                # decode once, transform live

    def test_cached_parity_through_pipeline(self, tmp_path):
        ds = _VecDataset(23)
        plain = _materialize(DataLoader(ds, batch_size=4))
        cache = SampleCache(mode="memory", max_bytes=1 << 20)
        cds = CachedDataset(ds, cache)
        for _ in range(2):                 # epoch 1 records, epoch 2 hits
            got = _materialize(IngestPipeline(
                DataLoader(cds, batch_size=4), prefetch_depth=2))
            _assert_streams_equal(plain, got)


class TestWorkerFaults:
    @pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
    def test_worker_killed_mid_epoch_raises_clean(self):
        dl = DataLoader(_SlowDataset(), batch_size=4, num_workers=2,
                        use_process_workers=True)
        it = iter(dl)
        next(it)
        import multiprocessing as mp
        victim = mp.active_children()[0]
        os.kill(victim.pid, signal.SIGKILL)
        t0 = time.time()
        with pytest.raises(RuntimeError, match="worker .* died"):
            for _ in it:
                pass
        assert time.time() - t0 < 30       # an error, not a hang

    def test_timeout_honored(self):
        dl = DataLoader(_SlowDataset(), batch_size=16, num_workers=1,
                        use_process_workers=True, timeout=1)
        with pytest.raises(RuntimeError, match="timed out"):
            list(dl)

    def test_flush_on_wedged_fetch_fails_loudly(self):
        # a fetch hung inside the loader cannot be settled: flush()
        # must raise a clear RuntimeError, not ValueError('generator
        # already executing') from closing a mid-execution iterator
        entered, release = threading.Event(), threading.Event()

        def slow_batches():
            yield np.zeros(2, np.float32)
            entered.set()                  # fetch 1 is now un-cancelable
            release.wait(10)               # wedged fetch
            yield np.ones(2, np.float32)

        pipe = IngestPipeline(slow_batches(), prefetch_depth=2,
                              timeout=0.3)
        it = iter(pipe)
        next(it)                           # batch 0; batch 1 in flight
        assert entered.wait(10)            # the pool thread IS wedged
        with pytest.raises(RuntimeError, match="wedged"):
            pipe.flush()
        release.set()                      # let the thread finish

    def test_collate_in_worker_requires_process_workers(self):
        with pytest.raises(ValueError, match="use_process_workers"):
            DataLoader(_VecDataset(4), batch_size=2, num_workers=2,
                       collate_in_worker=True)
        # num_workers=0 would silently decode in-parent — refuse it too
        with pytest.raises(ValueError, match="num_workers"):
            DataLoader(_VecDataset(4), batch_size=2,
                       use_process_workers=True, collate_in_worker=True)

    def test_collate_in_worker_ships_contiguous_numpy(self):
        dl = DataLoader(_VecDataset(13), batch_size=4, num_workers=2,
                        use_process_workers=True, collate_in_worker=True)
        ys = []
        for xb, yb in dl:
            assert isinstance(xb, np.ndarray) and xb.flags.c_contiguous
            assert xb.dtype == np.float32 and yb.dtype == np.int64
            ys.extend(yb.tolist())
        assert ys == list(range(13))
        assert "decode_ms" in dl.last_stage_ms
        assert "collate_ms" in dl.last_stage_ms


class TestObservability:
    def test_stall_gauge_and_stage_histograms_export(self):
        pipe = IngestPipeline(DataLoader(_VecDataset(16), batch_size=4),
                              prefetch_depth=1)
        list(pipe)
        text = monitor.export_prometheus()
        for needle in ("input_stall_pct", "ingest_decode_ms_bucket",
                       "ingest_collate_ms_bucket",
                       "ingest_transfer_ms_bucket",
                       "ingest_wait_ms_bucket", "ingest_batches_total"):
            assert needle in text, f"{needle} missing from export"
        from paddle_tpu.framework.observability import validate_prometheus
        validate_prometheus(text)

    def test_cache_counters_export(self):
        cache = SampleCache(mode="memory", max_bytes=1 << 20)
        cds = CachedDataset(_VecDataset(4), cache)
        for _ in range(2):
            list(DataLoader(cds, batch_size=2))
        text = monitor.export_prometheus()
        assert "ingest_cache_hits_total" in text
        assert "ingest_cache_misses_total" in text

    @pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
    def test_worker_cache_counters_reach_parent_export(self, tmp_path):
        # hits/misses happen inside the WORKER processes; the per-batch
        # stat_deltas shipped with the collated batch must fold them
        # into the parent registry, the one export_prometheus() reads
        monitor.reset_all_stats()
        cache = SampleCache(mode="disk", cache_dir=str(tmp_path / "c"))
        cds = CachedDataset(_VecDataset(8), cache)
        for _ in range(2):
            list(DataLoader(cds, batch_size=4, num_workers=2,
                            use_process_workers=True,
                            collate_in_worker=True))
        assert monitor.get_stat("ingest_cache_misses_total") == 8
        assert monitor.get_stat("ingest_cache_hits_total") == 8

    def test_stage_spans_written(self, tmp_path):
        from paddle_tpu.framework.observability import Tracer
        tr = Tracer(str(tmp_path), label="ingest-test")
        pipe = IngestPipeline(DataLoader(_VecDataset(8), batch_size=4),
                              prefetch_depth=1, tracer=tr)
        list(pipe)
        import json
        with open(tr.path()) as f:
            names = [json.loads(line).get("name")
                     for line in f if line.strip()]
        for span in ("ingest.decode", "ingest.transfer", "ingest.wait"):
            assert span in names, f"{span} span missing: {names}"


class TestTransfer:
    def test_to_device_maps_nested(self):
        out = to_device({"x": np.ones(3, np.float32),
                         "pair": (np.zeros(2), [np.ones(1)])})
        assert not isinstance(out["x"], np.ndarray)     # device Tensor
        assert float(out["x"].numpy()[0]) == 1.0
        assert isinstance(out["pair"], tuple)

    def test_numpy_collate_contract(self):
        batch = [(np.ones(3, np.float32), np.int64(1)),
                 (np.zeros(3, np.float32), np.int64(2))]
        x, y = numpy_collate(batch)
        assert isinstance(x, np.ndarray) and x.flags.c_contiguous
        assert x.shape == (2, 3) and y.tolist() == [1, 2]
        t = paddle.to_tensor(np.ones((2, 2), np.float32))
        stacked = numpy_collate([t, t])
        assert isinstance(stacked, np.ndarray)          # never a Tensor
