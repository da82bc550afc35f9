"""The shared expert (a plain SwiGLU every token takes): device time
per traced step under the program's ``moe_shared`` scope, forward,
remat copy and backward."""

import sarvam_flops

NAME = "moe.shared_ms_per_step"
UNIT = "ms"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sarvam_flops.scopes_ms_per_step(
        run, (sarvam_flops.SHARED_SCOPE,), "shared expert"
    )
