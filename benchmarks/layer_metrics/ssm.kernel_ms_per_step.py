"""Device time of the state-space scan's own kernels per traced step:
the operations whose instruction name holds ``ssd_fwd`` or ``ssd_bwd``
(the program's ``pl.pallas_call(name=...)``; ``%ssd_fwd.3``,
``%ssd_bwd.1``).  The note gives forward and backward apart and the
calls a step (16 ``ssd_fwd`` + 8 ``ssd_bwd`` in the cell: eight
state-space layers x (forward, the block's remat copy) and one
backward each).  Beside ``ssm.scan_ms_per_step`` (everything under
the ``ssm_scan`` scope) it says what of the scope is the kernels and
what is left round them (the transpose of ``dt``, the sum behind
``dA``, the final state's layout).  A program without the kernels
(the parent of PR 48, whose scan is XLA einsums) reports nothing."""

NAME = "ssm.kernel_ms_per_step"
UNIT = "ms"
LAYER = "state-space layers"
MOVES = "tokens_per_s"
SOURCE = "device_trace"

KERNELS = ("ssd_fwd", "ssd_bwd")


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
    run.note("state-space scan kernels: " + ", ".join(
        f"{kernel} {seconds[kernel] * 1e3:.3f} ms in "
        f"{sum(op['count'] for op in ops) / steps:.1f} calls a step"
        for kernel, ops in found.items()
    ))
    return sum(seconds.values()) * 1e3
