"""Device time of the flash-attention kernels (forward, backward dq,
backward dkv: every call, remat's second forward included) per step,
summed from the trace's device operations."""

import kernels

NAME = "kernel.flash_ms_per_step"
UNIT = "ms"
LAYER = "model + kernels"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if not run.trace:
        return None
    seconds = kernels.kernel_seconds_per_step(run.trace, "flash")
    if seconds is None:
        return None
    calls = sum(
        op["count"] for op in kernels.kernel_ops(run.trace, "flash").values()
    )
    run.note(
        f"flash kernels: {calls / run.trace['steps']:.1f} calls a step"
    )
    return seconds * 1e3
