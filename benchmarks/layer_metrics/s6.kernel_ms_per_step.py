"""Device time of the selective scan's own kernels per traced step:
the operations whose instruction name holds ``s6_fwd`` or ``s6_bwd``
(the program's ``pl.pallas_call(name=...)``).  The note gives forward
and backward apart and the calls a step (13 ``s6_fwd`` + 13 ``s6_bwd``
in the cell: the remat policy keeps what the forward wrote, so it
runs once a layer).  Beside ``s6.scan_ms_per_step`` (everything under
the ``s6_scan`` scope) it says what of the scope is the kernels and
what is left round them.  A program without the kernels reports
nothing."""

NAME = "s6.kernel_ms_per_step"
UNIT = "ms"
LAYER = "selective scan layers"
MOVES = "tokens_per_s"
SOURCE = "device_trace"

KERNELS = ("s6_fwd", "s6_bwd")


def read(run):
    trace = run.trace
    if not trace or not trace.get("steps") or not trace.get("ops"):
        return None
    steps = trace["steps"]
    found = {
        kernel: [
            op for name, op in trace["ops"].items() if kernel in name
        ]
        for kernel in KERNELS
    }
    if not any(found.values()):
        return None
    seconds = {
        kernel: sum(op["seconds"] for op in ops) / steps
        for kernel, ops in found.items()
    }
    run.note("selective scan kernels: " + ", ".join(
        f"{kernel} {seconds[kernel] * 1e3:.3f} ms in "
        f"{sum(op['count'] for op in ops) / steps:.1f} calls a step"
        for kernel, ops in found.items()
    ))
    return sum(seconds.values()) * 1e3
