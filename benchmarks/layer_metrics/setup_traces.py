"""jaxpr traces, nested ones included, that the program booked to the
step's calls (``health.compile_report()``'s ``TrainStep`` site: every
call from the start of ``TrainStep.prepare`` to the end of
``TrainStep.commit``).  Nothing compiles in the measured window, so this
is set-up's count.  None where the program books no such count."""


def reduce(trace, run):
    from paddle_tpu.framework import health
    return health.compile_report().get("TrainStep", {}).get("traces")
