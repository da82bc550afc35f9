"""The held experts' grouped matmuls: device time per traced step
under the program's ``moe_experts`` scope (``gmm_fwd`` / ``gmm_dlhs``
/ ``gmm_drhs`` over the rows that reached a held expert, and the
activation), forward, remat copy and backward."""

import sarvam_flops

NAME = "moe.held_expert_ms_per_step"
UNIT = "ms"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sarvam_flops.scopes_ms_per_step(
        run, (sarvam_flops.EXPERT_SCOPE,), "held experts"
    )
