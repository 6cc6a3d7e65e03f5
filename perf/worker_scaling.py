"""File-fed ingest worker-pool slope (round-4 verdict weak 6).

An earlier analysis argued from arithmetic that ~50-110 host cores
sustain chip-rate JPEG ingest through the multiprocess DataLoader — but
no bench leg ever spun the worker pool up.  This script measures the
loader-only drain rate of the same DatasetFolder+transform stack at
num_workers ∈ {0, 1, 2} and prints the measured per-worker slope.

This host has ONE vCPU, so absolute aggregate throughput cannot rise
past one core's rate; what the 2-worker leg shows is the *overhead
slope*: aggregate examples/s at 2 procs vs 1 proc vs in-process — i.e.
how much of a worker's core actually turns into ingest once IPC,
pickling, and the bounded buffer take their cut.  That efficiency
factor is exactly the number the analysis' core-count arithmetic was
missing.

Reference role: python/paddle/fluid/reader.py DataLoader worker pool +
framework/data_feed.cc multi-thread ingest.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

# CPU-only: ingest never touches the accelerator, and a process that
# opened the chip would take it from any job running alongside
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np


def build_dataset(hw=96, n_img=256):
    from bench import _gen_image_dataset
    from paddle_tpu.vision import transforms as T
    from paddle_tpu.vision.datasets import DatasetFolder

    root = f"/tmp/paddle_tpu_worker_scaling_{hw}_{n_img}"
    _gen_image_dataset(root, n_img, hw + 32, 10)

    mean = np.array([0.485, 0.456, 0.406], np.float32)
    std = np.array([0.229, 0.224, 0.225], np.float32)

    def to_chw_norm(img):
        arr = np.asarray(img, np.float32) / 255.0
        return ((arr - mean) / std).transpose(2, 0, 1)

    tf = T.Compose([T.RandomResizedCrop(hw), T.RandomHorizontalFlip(),
                    to_chw_norm])

    def pil_loader(path):
        from PIL import Image
        with Image.open(path) as im:
            return np.asarray(im.convert("RGB"))

    return DatasetFolder(root, loader=pil_loader, extensions=(".jpg",),
                        transform=tf)


def drain(ds, num_workers, batch_size=32, repeats=2):
    from paddle_tpu.io import DataLoader
    best = 0.0
    for _ in range(repeats):
        loader = DataLoader(ds, batch_size=batch_size, shuffle=False,
                            drop_last=False, num_workers=num_workers)
        n = 0
        t0 = time.perf_counter()
        for xb, yb in loader:
            n += int(xb.shape[0])
        dt = time.perf_counter() - t0
        best = max(best, n / dt)
    return best


def main():
    ds = build_dataset()
    rows = []
    for w in (0, 1, 2):
        rate = drain(ds, w)
        rows.append({"num_workers": w, "examples_per_sec": round(rate, 1)})
        print(json.dumps(rows[-1]), flush=True)

    r0, r1, r2 = (r["examples_per_sec"] for r in rows)
    eff1 = r1 / r0 if r0 else 0.0       # 1 worker proc vs in-process
    # 2 procs share the single core: their aggregate vs 1 proc measures
    # the added IPC/scheduling cost, not parallel speedup
    agg2 = r2 / r1 if r1 else 0.0
    para = [
        "",
        "### Measured worker-pool slope (round 5)",
        "",
        "| num_workers | ingest (examples/s) |",
        "|---|---|",
    ] + [f"| {r['num_workers']} | {r['examples_per_sec']} |" for r in rows] + [
        "",
        f"One 1-vCPU host, 96px RandomResizedCrop pipeline.  A single "
        f"worker process delivers **{eff1:.2f}×** the in-process rate "
        "(net of the IPC + pickling tax and the decode/batch-assembly "
        "overlap a worker buys) and two processes "
        f"time-slicing the same core aggregate to **{agg2:.2f}×** the "
        "one-worker rate (≈1.0 means the pool scheduling itself costs "
        "nothing; the core is the only bottleneck).  The projected core "
        "count for chip-rate ingest scales by 1/efficiency — e.g. at "
        f"{eff1:.2f} efficiency the ~50-110-core estimate becomes "
        f"~{int(round(50 / max(eff1, 1e-9)))}-"
        f"{int(round(110 / max(eff1, 1e-9)))} cores.",
    ]
    print("\n".join(para))


if __name__ == "__main__":
    main()
