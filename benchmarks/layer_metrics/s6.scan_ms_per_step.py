"""Device time of the selective scan per traced step: the operations
under the program's ``s6_scan`` scope (the ``s6_fwd`` and ``s6_bwd``
kernels, the transposes of ``B`` and ``C`` round them, the sums behind
``dA`` and ``dD``, the final state's rms), every state-space layer,
forward, the block's remat copy and backward together; an instruction
that only holds others (a ``%while``) is left out and its body counted
(``jamba_flops.py``).  Printed above the result: where the step's
other device time lies by scope, against the device's busy time."""

import jamba_flops

NAME = "s6.scan_ms_per_step"
UNIT = "ms"
LAYER = "selective scan layers"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    scan = jamba_flops.scopes_ms(
        run, (jamba_flops.SCAN_SCOPE,), "selective scan"
    )
    if scan is None:
        return None
    found = jamba_flops.by_scope(run)
    ms = lambda s: f"{s * 1e3:.3f}"  # noqa: E731
    by_scope = {
        name: sum(found[name].values())
        for name in jamba_flops.STEP_SCOPES
    }
    total = sum(by_scope.values()) + found["other"] + found["unnamed"]
    busy = run.trace["busy_s"] / run.trace["steps"]
    layers = jamba_flops.layers(run.config, jamba_flops.MAMBA)
    run.note(
        f"step by scope over {layers} state-space layers, ms: "
        + " + ".join(
            f"{name} {ms(seconds)}" for name, seconds in by_scope.items()
        ) + f" + other scopes (the SwiGLUs, the norms, the embedding) "
        f"{ms(found['other'])} + no name stack {ms(found['unnamed'])} = "
        f"{ms(total)} (every %while left out, its body counted); the "
        f"device was busy {ms(busy)} ms a step: "
        f"{100 * total / busy:.1f}% accounted for"
    )
    return scan
