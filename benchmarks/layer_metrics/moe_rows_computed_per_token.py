"""Rows (token x expert) the held routed experts' matmuls execute for
one token of one expert layer: the program's trace-time counters
``moe_expert_rows_computed_total`` / ``moe_calls_traced_total`` over the
tokens of a step.  The routed load needs ``top_k * held / n_routed`` on
average (0.34 in the benchmark's cell); a dense mask computes ``held``
(8).  None where the program has no such counters or traced no expert
layer."""


def reduce(trace, run):
    from paddle_tpu.framework import monitor
    stats = monitor.all_stats()
    calls = stats.get("moe_calls_traced_total", 0)
    if not calls:
        return None
    return (stats.get("moe_expert_rows_computed_total", 0) / calls
            / run["tokens_per_step"])
