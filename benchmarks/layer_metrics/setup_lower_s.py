"""Seconds of set-up spent lowering the step's jaxprs to StableHLO, every
Pallas kernel's Mosaic lowering included, booked by the program to the
step's calls (``lower_s`` of ``health.compile_report()``'s ``TrainStep``
site).  None where the program books no such span."""


def reduce(trace, run):
    from paddle_tpu.framework import health
    return health.compile_report().get("TrainStep", {}).get("lower_s")
