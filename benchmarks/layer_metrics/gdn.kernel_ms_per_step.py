"""Device time of the gated delta rule's own kernels per traced step:
the operations whose instruction name holds ``gdn_fwd`` or ``gdn_bwd``
(the program's ``pl.pallas_call(name=...)``; ``%jvp_gdn_fwd_.3``,
``%transpose_jvp_gdn_bwd__.1``).  The note gives forward and backward
apart and the calls a step (6 ``gdn_fwd`` + 3 ``gdn_bwd`` in the
cell: three linear layers x (forward, the block's remat copy) and one
backward each).  Beside ``gdn.rule_ms_per_step`` (everything under
the ``gdn_rule`` scope) it says what of the scope is the kernels and
what the layouts round them.  A program without the kernels (the
parent of PR 40) reports nothing."""

NAME = "gdn.kernel_ms_per_step"
UNIT = "ms"
LAYER = "linear attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"

KERNELS = ("gdn_fwd", "gdn_bwd")


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
    run.note("gated delta rule kernels: " + ", ".join(
        f"{kernel} {seconds[kernel] * 1e3:.3f} ms in "
        f"{sum(op['count'] for op in ops) / steps:.1f} calls a step"
        for kernel, ops in found.items()
    ))
    return sum(seconds.values()) * 1e3
