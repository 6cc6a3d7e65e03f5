"""Model FLOP utilization of the traced stretch: the configuration's
``flops_per_token`` (forward + backward, no recomputation) times tokens
per second of the steady traced window, over chips times the published
bf16 peak of the device."""
from benchmarks.harness import roofline


def reduce(trace, run):
    if run["peak"] is None:
        return None
    tokens_per_s = run["tokens_per_step"] * trace.steps / trace.window_s()
    flops = run["reference"].flops_per_token(run["sizes"],
                                             run["traffic"]["seq"])
    return roofline.mfu_percent(flops, tokens_per_s, len(trace.chips),
                                run["peak"])
