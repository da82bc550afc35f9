"""Device time of the gated short convolution's own kernels per traced
step: the operations whose instruction name holds ``bcx_fwd`` or
``bcx_bwd`` (the program's ``pl.pallas_call(name=...)``).  The note
gives forward and backward apart and the calls a step (14 ``bcx_fwd``
+ 7 ``bcx_bwd`` in the cell: seven conv layers x (forward, the block's
remat copy) and one backward each).  Beside ``sconv.mix_ms_per_step``
it says what of the scope is the kernels and what the counter's
reduction round them."""

import lfm2_flops

NAME = "sconv.kernel_ms_per_step"
UNIT = "ms"
LAYER = "short convolution"
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
        for kernel in lfm2_flops.KERNELS
    }
    if not any(found.values()):
        return None
    seconds = {
        kernel: sum(op["seconds"] for op in ops) / steps
        for kernel, ops in found.items()
    }
    run.note("short convolution kernels: " + ", ".join(
        f"{kernel} {seconds[kernel] * 1e3:.3f} ms in "
        f"{sum(op['count'] for op in ops) / steps:.1f} calls a step"
        for kernel, ops in found.items()
    ))
    return sum(seconds.values()) * 1e3
