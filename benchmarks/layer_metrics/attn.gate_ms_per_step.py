"""What the per-head output gate costs: device time per traced step
under the program's ``attn_gate`` scope (the gate's matmul, its
sigmoid, the scaling of every head's output, forward and backward)."""

import laguna_flops

NAME = "attn.gate_ms_per_step"
UNIT = "ms"
LAYER = "window attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return laguna_flops.scope_ms_per_step(
        run, laguna_flops.GATE_SCOPE, "per-head gate"
    )
