"""Device time of the experts per traced step: the operations under
the program's ``moe_experts`` scope (the ``gmm_*`` grouped-matmul
kernels and the activation between them), forward, backward and any
remat copy together; ``moe_flops.py`` says how they are found."""

import moe_flops

NAME = "moe.expert_ms_per_step"
UNIT = "ms"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = moe_flops.seconds_per_step(run, moe_flops.EXPERT_SCOPE)
    if found is None:
        return None
    seconds, count = found
    run.note(f"experts: {count:.1f} device operations a step")
    return seconds * 1e3
