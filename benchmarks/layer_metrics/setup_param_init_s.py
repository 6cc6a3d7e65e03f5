"""Seconds of set-up spent drawing the model's parameters: the program's
counter ``model_init_seconds_total``, the sum of its ``model.init``
spans.  None where the program keeps no such counter."""


def reduce(trace, run):
    from paddle_tpu.framework import monitor
    return monitor.all_stats().get("model_init_seconds_total")
