#!/usr/bin/env python
"""ResNet-50 HBM-bandwidth ledger v2 (round-4 verdict item 6).

Round 3's analysis summed the profiler's ``bytes_accessed``, which counts
HLO-level operand accesses — a figure that EXCEEDS physical HBM traffic
whenever operands are re-read from VMEM/caches inside a fusion (hence
"achieved 970 GB/s / 2.26 TB/s" against an 819 GB/s part).

This ledger computes the opposite bound from the TPU-optimized HLO of
the exact bench step: for every top-level instruction in the entry
computation, HBM bytes >= unique operand bytes + output bytes (fusion
internals live in VMEM/registers by construction).  Summing gives the
*minimum* HBM traffic the compiled schedule can do — a floor, stated in
bytes that must each cross HBM exactly once.

floor_time = floor_bytes / 819 GB/s is then directly comparable to the
measured step: measured/floor ≈ 1 ⇒ at the roofline.

Run from the repo root:  python - < perf/resnet50_ledger.py
"""
from __future__ import annotations

import re
import sys
import os

sys.path.insert(0, os.getcwd())

import numpy as np


DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s64": 8, "u64": 8, "pred": 1, "s8": 1, "u8": 1,
               "f64": 8, "s16": 2, "u16": 2}

SHAPE_RE = re.compile(r"\b(f32|bf16|f16|f64|s8|u8|s16|u16|s32|u32|s64|u64|"
                      r"pred)\[([0-9,]*)\]")


def shape_bytes(tok: str) -> int:
    total = 0
    for dt, dims in SHAPE_RE.findall(tok):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def main():
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50

    B, HW = 128, 224
    model = resnet50(num_classes=1000)
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters())

    def loss_fn(m, x, y):
        return F.cross_entropy(m(x), y).mean()

    step = TrainStep(model, loss_fn, opt, amp_level="O2",
                     amp_dtype="bfloat16")
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((B, 3, HW, HW))
                         .astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 1000, size=(B,)).astype(np.int64))
    loss = step(x, y)                       # compile + one step
    np.asarray(loss._data)
    hlo = step.compiled_text()

    # find the ENTRY computation (largest region is fine: parse every
    # computation but attribute only the entry's top-level instructions)
    entry = None
    blocks = re.split(r"\n(?=ENTRY |%?\w[\w.\-]* \()", hlo)
    for b in blocks:
        if b.startswith("ENTRY"):
            entry = b
            break
    if entry is None:                       # fall back: whole text
        entry = hlo

    per_cat = {}
    total = 0
    n_inst = 0
    for line in entry.splitlines():
        line = line.strip()
        m = re.match(r"%?([\w.\-]+) = (.+)", line)
        if not m or "ROOT" in line.split("=")[0]:
            pass
        if not m:
            continue
        name, rhs = m.groups()
        if "(" not in rhs:
            continue
        # output shape(s): the type token(s) before the op name
        opm = re.match(r"(\(?[a-z0-9\[\],\s]+\)?)\s+([a-z\-]+)", rhs)
        if not opm:
            continue
        out_tok, op = opm.groups()
        if op in ("parameter", "constant"):
            continue
        out_b = shape_bytes(out_tok)
        # operand shapes: HLO text repeats operand types inline only in
        # some dialects; in the common form operands are %names — resolve
        # via a shape table built from all definitions
        total += out_b
        n_inst += 1
        per_cat[op] = per_cat.get(op, 0) + out_b

    # second pass: operand bytes via definition table
    defs = {}
    for line in entry.splitlines():
        line = line.strip()
        m = re.match(r"%?([\w.\-]+) = (\(?[a-z0-9\[\],\s]+\)?)\s", line)
        if m:
            defs[m.group(1)] = shape_bytes(m.group(2))
    operand_total = 0
    for line in entry.splitlines():
        line = line.strip()
        m = re.match(r"%?([\w.\-]+) = (.+)", line)
        if not m:
            continue
        rhs = m.group(2)
        opm = re.match(r"(\(?[a-z0-9\[\],\s]+\)?)\s+([a-z\-]+)", rhs)
        if not opm or opm.group(2) in ("parameter", "constant"):
            continue
        args = re.findall(r"%([\w.\-]+)", rhs)
        seen = set()
        for a in args:
            if a in defs and a not in seen:
                seen.add(a)
                operand_total += defs[a]

    gb_out = total / 1e9
    gb_in = operand_total / 1e9
    gb_floor = gb_out + gb_in
    print(f"instructions: {n_inst}")
    print(f"output bytes (write floor): {gb_out:.2f} GB")
    print(f"operand bytes (read floor): {gb_in:.2f} GB")
    print(f"HBM floor: {gb_floor:.2f} GB  -> "
          f"{gb_floor / 819 * 1000:.1f} ms at 819 GB/s")
    print("top categories by output bytes:")
    for op, b in sorted(per_cat.items(), key=lambda kv: -kv[1])[:12]:
        print(f"  {op:28s} {b/1e9:7.2f} GB")


if __name__ == "__main__":
    from paddle_tpu.device import use_compile_cache
    use_compile_cache()
    main()
