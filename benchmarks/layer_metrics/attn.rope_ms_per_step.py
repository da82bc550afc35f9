"""What attention costs between its projections and the flash kernels:
device time per traced step under the program's ``attn_rope`` scope
(both rope tables, the whole and the partial rotation, the layouts into
the kernels)."""

import laguna_flops

NAME = "attn.rope_ms_per_step"
UNIT = "ms"
LAYER = "window attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return laguna_flops.scope_ms_per_step(
        run, laguna_flops.ROPE_SCOPE, "rope and layouts"
    )
