"""How often a step calls the flash-attention kernels (forward,
backward dq, backward dkv), counted from the trace's device
operations: the count ``kernel.flash_ms_per_step`` puts in its note,
as a number the ledger keeps.  Three a layer (an application, where a
stack is applied more than once): each kernel runs once.  Four: a
rematted block ran the forward a second time for its backward."""

import kernels

NAME = "kernel.flash_calls_per_step"
UNIT = "calls"
LAYER = "model + kernels"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if not run.trace or not run.trace["steps"]:
        return None
    ops = kernels.kernel_ops(run.trace, "flash")
    if not ops:
        return None
    return sum(op["count"] for op in ops.values()) / run.trace["steps"]
