#!/usr/bin/env python
"""Measure flash-attention block sizes on the attached TPU and persist
the winners into paddle_tpu/ops/pallas/flash_blocks.json.

    python tools/flash_autotune.py                  # bench/model configs
    python tools/flash_autotune.py --sq 4096 --sk 4096 --d 128 --causal
    python tools/flash_autotune.py --sq 512 --sk 512 --d 64 --split \
        --batch 32 --heads 12                       # at a model's own call

The shipped json is the measured cache the kernels consult at trace
time; re-run this on new hardware generations.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (sq, sk, d, dtype, causal, biased) — the bench + model-zoo kernel shapes
DEFAULT_CONFIGS = [
    (1024, 1024, 64, "bfloat16", True, False),    # GPT-2 345M
    (2048, 2048, 128, "bfloat16", True, False),   # longseq ref leg
    (8192, 8192, 128, "bfloat16", True, False),   # longseq 8k leg
    (2048, 2048, 64, "bfloat16", False, True),    # masked BERT-class
    (8192, 8192, 128, "bfloat16", True, True),    # packed longseq
    (512, 512, 64, "bfloat16", False, False),     # BERT-base at S = 512
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sq", type=int)
    ap.add_argument("--sk", type=int)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--biased", action="store_true")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--split", action="store_true",
                    help="tune fwd, bwd and (two-level nest) dk/dv block "
                         "sizes independently, each kernel by its own "
                         "device time from a trace")
    ap.add_argument("--no-verify", action="store_true",
                    help="skip the differential oracle pre-timing gate "
                         "(candidates are then recorded unstamped)")
    a = ap.parse_args(argv)

    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.ops.pallas.common import backend_is_tpu
    if not backend_is_tpu():
        print("no TPU attached — autotune must run on real hardware",
              file=sys.stderr)
        return 1
    if not a.no_verify:
        # every candidate passes the interpret-vs-compiled-vs-reference
        # oracle before it is timed; winners are stamped verified: true
        set_flags({"pallas_verify": True})

    configs = [(a.sq, a.sk, a.d, a.dtype, a.causal, a.biased)] \
        if a.sq else DEFAULT_CONFIGS
    for sq, sk, d, dt, causal, biased in configs:
        print(f"config sq={sq} sk={sk} d={d} {dt} "
              f"causal={causal} biased={biased}")
        rejected = {}
        if a.split:
            out = autotune.measure_split(sq, sk, d, dt, causal, biased,
                                         batch=a.batch, heads=a.heads,
                                         iters=a.iters, verbose=True,
                                         rejected=rejected)
            if out is None:
                print("  no viable candidate")
            else:
                fwd, bwd, dkv = out
                print(f"  -> fwd {fwd[0]}, bwd {bwd[0]}" +
                      (f", dkv {dkv[0]}" if dkv else ""))
        else:
            out = autotune.measure(sq, sk, d, dt, causal, biased,
                                   batch=a.batch, heads=a.heads,
                                   iters=a.iters, verbose=True,
                                   rejected=rejected)
            if out is None:
                print("  no viable candidate")
            else:
                best, _ = out
                print(f"  -> {best}")
        for (bq, bk), fails in sorted(rejected.items()):
            ops = ", ".join(sorted({f["operand"] for f in fails}))
            print(f"  rejected ({bq},{bk}): {len(fails)} corpus "
                  f"failure(s) [{ops}]")
    from paddle_tpu.framework import monitor
    faults = monitor.get_stat("pallas_verify_errors_total")
    if faults:
        # the oracle swallows its own faults; here a candidate whose
        # check did not finish was rejected above, and the sweep says so
        print(f"{int(faults)} oracle fault(s): a leg raised (e.g. Mosaic "
              "refused a tile) — those candidates were rejected, not "
              "verified", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    sys.exit(main())
