"""Device time of the channel-wise rule's own kernels per traced step:
the operations whose instruction name holds ``kda_fwd`` or ``kda_bwd``
(the program's ``pl.pallas_call(name=...)``).  The note gives forward
and backward apart and the calls a step (12 ``kda_fwd`` + 6
``kda_bwd`` in the cell: six KDA layers x (forward, the block's remat
copy) and one backward each).  Beside ``kda.rule_ms_per_step`` it says
what of the scope is the kernels and what the layouts and running sums
round them."""

import ling_flops

NAME = "kda.kernel_ms_per_step"
UNIT = "ms"
LAYER = "linear attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    trace = run.trace
    if not trace or not trace.get("steps") or not trace.get("ops"):
        return None
    steps = trace["steps"]
    found = {
        kernel: [
            op for name, op in trace["ops"].items() if kernel in name
        ]
        for kernel in ling_flops.KERNELS
    }
    if not any(found.values()):
        return None
    seconds = {
        kernel: sum(op["seconds"] for op in ops) / steps
        for kernel, ops in found.items()
    }
    run.note("channel-wise rule kernels: " + ", ".join(
        f"{kernel} {seconds[kernel] * 1e3:.3f} ms in "
        f"{sum(op['count'] for op in ops) / steps:.1f} calls a step"
        for kernel, ops in found.items()
    ))
    return sum(seconds.values()) * 1e3
