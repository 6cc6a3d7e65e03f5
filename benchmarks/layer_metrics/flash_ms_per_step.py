"""Summed device time of the flash attention kernels (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``) in one step on the first chip.
Nothing when the step runs none of them."""

FLASH_EVENT = r"flash_(fwd|bwd_dq|bwd_dkv)"


def reduce(trace, run):
    seconds = trace.per_step(trace.chips[0], FLASH_EVENT)
    return 1e3 * seconds if seconds > 0 else None
