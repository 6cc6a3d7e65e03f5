"""The flash kernels' share of their roofline: the least time the chip
could take for attention forward + backward at the cell's (B, H, S, d),
over the time the three kernels took per step.

Operations: FlashAttention-2's count.  Forward: Q K^T and P V, 2 matmuls;
backward: recompute S, dP = dO V^T, dV = P^T dO, dK = dS^T Q, dQ = dS K,
5 matmuls; each 2 * S * S * d FLOPs a head, halved for a causal mask and
whole for full attention (BERT's mask-free call, PR 31).
The program's backward is split into a dq and a dk/dv kernel that each
recompute S and dP (9 matmuls executed); like recomputation in ``mfu``,
the two extra are not counted, so the share is of the algorithm's need;
nor is the forward kernel's second run where a layer is recomputed
(``bert_base``: its time is in the divisor, its operations are not).
Bytes: forward reads Q, K, V and writes O (bf16) and the log-sum-exp
(float32); backward reads Q, K, V, O, dO and the log-sum-exp and writes
dQ, dK, dV.  The softmax's exponentials are not counted."""
from benchmarks.harness import roofline

FLASH_EVENT = r"flash_(fwd|bwd_dq|bwd_dkv)"


def flash_flops_and_bytes(b, h, s, d, causal, layers, itemsize=2):
    matmul = 2.0 * b * h * s * s * d * (0.5 if causal else 1.0)
    tensor = b * h * s * d * itemsize
    lse = b * h * s * 4
    return layers * 7 * matmul, layers * ((4 + 8) * tensor + 2 * lse)


def reduce(trace, run):
    chip = trace.chips[0]
    seconds = trace.per_step(chip, FLASH_EVENT)
    shape = getattr(run["reference"], "attention_shape", None)
    if seconds <= 0 or shape is None or run["peak"] is None:
        return None
    per_chip_batch = run["traffic"]["batch"] // len(trace.chips)
    flops, nbytes = flash_flops_and_bytes(
        *shape(run["sizes"], per_chip_batch, run["traffic"]["seq"]))
    least, bound = roofline.least_time(flops, nbytes, run["peak"])
    run["say"](f"flash_roofline: {flops:.4g} FLOPs and {nbytes:.4g} "
               f"bytes a step a chip, least time {least * 1e3:.3f} ms, "
               f"bound by {bound}; the kernels took {seconds * 1e3:.3f} ms")
    return 100.0 * least / seconds
