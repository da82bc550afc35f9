"""Device time of the depthwise causal convolution's own kernels per
traced step: the operations whose instruction name holds ``conv_fwd``
or ``conv_bwd`` (the program's ``pl.pallas_call(name=...)`` in
``dlrover_tpu/ops/causal_conv.py``; ``%conv_fwd.3``, ``%conv_bwd.1``).
The note gives forward and backward apart and the calls a step: 48
``conv_fwd`` + 24 ``conv_bwd`` in ``nemotron_steady_8k`` (eight
state-space layers x three windows of the projection's lanes, ``x``,
``B`` and ``C``, x (forward, the block's remat copy), and one backward
each), 18 + 9 in ``olmo_hybrid_steady_8k`` (three linear layers x
``q``, ``k``, ``v``).  Beside the ``ssm_conv`` / ``gdn_conv`` part of
``ssm.mix_ms_per_step`` / ``gdn.mix_ms_per_step`` it says what of the
scope is the kernels and what is left round them.  A program without
the kernels (the parent of PR 49, whose convolution is XLA's pad, cast
and four slices) reports nothing."""

NAME = "conv.kernel_ms_per_step"
UNIT = "ms"
LAYER = "model + kernels"
MOVES = "tokens_per_s"
SOURCE = "device_trace"

KERNELS = ("conv_fwd", "conv_bwd")


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
    run.note("causal convolution kernels: " + ", ".join(
        f"{kernel} {seconds[kernel] * 1e3:.3f} ms in "
        f"{sum(op['count'] for op in ops) / steps:.1f} calls a step"
        for kernel, ops in found.items()
    ))
    return sum(seconds.values()) * 1e3
