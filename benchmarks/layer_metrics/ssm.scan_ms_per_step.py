"""Device time of the state-space scan per traced step: the operations
under the program's ``ssm_scan`` scope (the chunk-wise form: a
chunk's scores and decays, the chunks' states, the hand-over between
chunks, the outputs), every state-space layer, forward, the block's
remat copy and backward together; an instruction that only holds
others (a ``%while``) is left out and its body counted
(``nemotron_flops.py``).  Printed above the result: the three apart
and a layer, and where the step's other device time lies by scope,
against the device's busy time."""

import nemotron_flops

NAME = "ssm.scan_ms_per_step"
UNIT = "ms"
LAYER = "state-space layers"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = nemotron_flops.by_scope(run)
    if found is None:
        return None
    parts = found[nemotron_flops.SCAN_SCOPE]
    scan = sum(parts.values())
    if not scan:
        return None
    ms = lambda s: f"{s * 1e3:.3f}"  # noqa: E731
    layers = nemotron_flops.layers(run.config, nemotron_flops.MAMBA)
    run.note(
        f"state-space scan, ms a step over {layers} layers, forward | "
        f"remat copy | backward: {ms(parts['forward'])} | "
        f"{ms(parts['remat'])} | {ms(parts['backward'])}; a layer "
        f"{ms(parts['forward'] / layers)} | {ms(parts['remat'] / layers)}"
        f" | {ms(parts['backward'] / layers)}"
    )
    by_scope = {
        name: sum(found[name].values())
        for name in nemotron_flops.STEP_SCOPES
    }
    total = sum(by_scope.values()) + found["other"] + found["unnamed"]
    busy = run.trace["busy_s"] / run.trace["steps"]
    run.note(
        "step by scope, ms: " + " + ".join(
            f"{name} {ms(seconds)}" for name, seconds in by_scope.items()
        ) + f" + other scopes {ms(found['other'])} + no name stack "
        f"{ms(found['unnamed'])} = {ms(total)} (every %while left out, "
        f"its body counted); the device was busy {ms(busy)} ms a step: "
        f"{100 * total / busy:.1f}% accounted for"
    )
    return scan * 1e3
