#!/usr/bin/env python
"""CI gate for the streaming ingest plane (io/pipeline.py).

A small on-disk JPEG corpus through the FULL pipeline — DatasetFolder
JPEG decode -> uint8 numpy augment -> batch-granularity collate ->
IngestPipeline double-buffered device transfer — asserting op_bench-
style explicit thresholds:

1. **cache-epoch speedup**: epoch 1 records the decoded-sample cache,
   epoch 2 must drain >= ``CACHE_SPEEDUP_MIN`` x the epoch-1 rate
   (the cache's whole point: epoch >= 2 skips JPEG decode), with the
   hit/miss counters accounting for every sample;
2. **input stall**: a simulated train loop (fixed per-step compute)
   over the cached epoch must measure ``input_stall_pct`` under
   ``STALL_PCT_MAX`` — the overlap is doing its job when the consumer
   almost never waits on input;
3. the gauge and per-stage histograms must export through
   ``monitor.export_prometheus()``.

Exits non-zero on any violation.  CPU-only, deterministic corpus,
seconds.  (Exact pipelined-vs-sequential parity, chaos degradation and
worker-fault behavior are covered by tests/test_ingest_pipeline.py in
the pytest lane; this lane holds the PERFORMANCE line.)
"""
from __future__ import annotations

import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# op_bench-style thresholds: explicit, asserted, sized for a noisy
# 2-core CI host: these floors catch a broken cache or a serialized
# pipeline, not run-to-run jitter
CACHE_SPEEDUP_MIN = 1.3   # epoch-2 rate / epoch-1 rate
STALL_PCT_MAX = 25.0      # consumer wait share with compute overlapped
N_IMAGES, IMG_SIZE, CROP, BATCH = 48, 96, 64, 8
STEP_MS = 10.0            # simulated per-step compute


def _gen_corpus(root):
    from PIL import Image
    rng = np.random.default_rng(7)
    for c in range(4):
        os.makedirs(os.path.join(root, f"class_{c}"), exist_ok=True)
    for i in range(N_IMAGES):
        arr = rng.integers(0, 256, size=(IMG_SIZE, IMG_SIZE, 3),
                           dtype=np.uint8)
        Image.fromarray(arr).save(
            os.path.join(root, f"class_{i % 4}", f"{i:04d}.jpg"),
            quality=85)


def _drain(pipe):
    n, t0 = 0, time.perf_counter()
    for batch in pipe:
        n += int(batch[0].shape[0])
    return n, time.perf_counter() - t0


def main() -> int:
    from paddle_tpu.framework import monitor
    from paddle_tpu.io import DataLoader, numpy_collate
    from paddle_tpu.io.pipeline import (CachedDataset, IngestPipeline,
                                        SampleCache)
    from paddle_tpu.vision import transforms as T
    from paddle_tpu.vision.datasets import DatasetFolder

    def pil_loader(path):
        from PIL import Image
        with Image.open(path) as im:
            return np.asarray(im.convert("RGB"))

    with tempfile.TemporaryDirectory() as root:
        _gen_corpus(root)
        aug = T.Compose([T.RandomResizedCrop(CROP),
                         T.RandomHorizontalFlip()])
        ds = DatasetFolder(root, loader=pil_loader, extensions=(".jpg",),
                           transform=aug)
        cache = SampleCache(mode="memory", max_bytes=1 << 28)
        cds = CachedDataset(ds, cache)

        def pipeline():
            return IngestPipeline(DataLoader(
                cds, batch_size=BATCH, shuffle=False, drop_last=True,
                collate_fn=numpy_collate))

        # -- 1. cache-epoch speedup ----------------------------------------
        n1, dt1 = _drain(pipeline())        # epoch 1: decode + record
        assert cache.misses >= n1, \
            f"epoch 1 should miss every sample: {cache.misses} < {n1}"
        n2, dt2 = _drain(pipeline())        # epoch 2: cache hits
        assert cache.hits >= n2, \
            f"epoch 2 should hit every sample: {cache.hits} < {n2}"
        rate1, rate2 = n1 / dt1, n2 / dt2
        speedup = rate2 / rate1
        print(f"ingest_check: epoch1 {rate1:.0f} ex/s, epoch2 "
              f"{rate2:.0f} ex/s, cache speedup {speedup:.2f}x "
              f"(floor {CACHE_SPEEDUP_MIN}x)")
        assert speedup >= CACHE_SPEEDUP_MIN, \
            f"cache-epoch speedup {speedup:.2f}x < {CACHE_SPEEDUP_MIN}x"

        # -- 2. input stall with compute overlapped ------------------------
        pipe = pipeline()
        for batch in pipe:
            time.sleep(STEP_MS / 1e3)       # simulated train step
        stall = pipe.input_stall_pct
        print(f"ingest_check: cached-epoch input_stall_pct "
              f"{stall:.2f}% (ceiling {STALL_PCT_MAX}%)")
        assert stall < STALL_PCT_MAX, \
            f"input_stall_pct {stall:.2f} >= {STALL_PCT_MAX}"

        # -- 3. first-class export -----------------------------------------
        text = monitor.export_prometheus()
        for needle in ("input_stall_pct", "ingest_decode_ms_bucket",
                       "ingest_wait_ms_bucket",
                       "ingest_cache_hits_total",
                       "ingest_cache_misses_total"):
            assert needle in text, \
                f"{needle} missing from export_prometheus()"
        print("ingest_check: prometheus export OK")
    print("ingest_check: all gates passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
