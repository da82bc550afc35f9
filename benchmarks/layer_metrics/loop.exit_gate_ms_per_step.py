"""What the exits' gate costs: device time per traced step under the
program's ``exit_gate`` scope (the gate's projection after each pass
but the last, the sigmoids, the exit distribution, its entropy and the
counters, forward and backward; the mixing of the exits is the
weighted head's, under ``loss_head``)."""

import ouro_flops

NAME = "loop.exit_gate_ms_per_step"
UNIT = "ms"
LAYER = "looped stack"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = ouro_flops.seconds_per_step(run, ouro_flops.GATE_SCOPE)
    if not found:
        return None
    run.note(
        f"exit gate: {found[0] * 1e3:.3f} ms a step "
        f"({found[1]:.0f} operations)"
    )
    return found[0] * 1e3
